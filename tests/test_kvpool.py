"""Paged KV cache subsystem (ISSUE 19): page pool allocator, prefix
sharing, paged-attention op, and the DecodeEngine integration.

Oracles:
 - BITWISE: paged decode (live continuous batching, with admit/retire
   churn across a fragmented free list) is bit-identical to per-request
   sequential decode on a DENSE engine over the same config/seed — the
   page indirection moves where K/V rows live, never what they contain;
 - PREFIX SHARING: full prompt pages refcount-share across concurrent
   requests (``prefix_hits``), full-prefix admissions skip the prefill
   dispatch (``prefill_skips``), divergent tails produce each request's
   own dense-equal stream (the last page is always slot-private, so
   divergence needs no device copy), and shared pages SURVIVE a
   sharer's deadline expiry;
 - BACKPRESSURE: a dry pool re-queues admissions (``page_requeues``)
   instead of crashing or shedding, and every page returns to the free
   list after the traffic drains (the explicit-retire-frees-pages
   bugfix);
 - FAULT: ``PADDLE_FAULT_KV_PAGE_LEAK=n`` skips exactly n frees,
   visible in ``pages_leaked``/``kvpool.pages_free``;
 - KILL SWITCH: ``PADDLE_SERVE_PAGED=0`` restores the dense engine
   bitwise (``PADDLE_TPU_FUSED`` gates kernel vs unfused fallback the
   same way, also bitwise).

One module-scoped dense+paged engine pair serves the engine tests
(construction + warmup is the expensive part).  Tests run in definition
order under the tier-1 ``-p no:randomly`` contract.
"""

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observe
from paddle_tpu.fluid import fault as _fault
from paddle_tpu.fluid import layers
from paddle_tpu.models import transformer
from paddle_tpu.serving import DecodeEngine, PagePool, RequestTimeout

SLOTS, MAX_LEN, BUCKETS, PS = 3, 24, (4, 8), 4


def _model(paged, **kw):
    return transformer.DecodeModel(cfg=transformer.decode_lm_config(),
                                   max_slots=kw.pop("slots", SLOTS),
                                   max_len=kw.pop("max_len", MAX_LEN),
                                   prefill_buckets=list(
                                       kw.pop("buckets", BUCKETS)),
                                   paged=paged, page_size=PS, **kw)


@pytest.fixture(scope="module")
def engines():
    dense = DecodeEngine(_model(False))
    paged = DecodeEngine(_model(True))
    yield dense, paged
    paged.shutdown(timeout_s=30)
    dense.shutdown(timeout_s=30)


# ---------------------------------------------------------------------------
# PagePool unit level (no executor, no jax)
# ---------------------------------------------------------------------------

def test_pool_accounting_allocation_and_gauges():
    pool = PagePool(num_pages=8, page_size=4, pages_per_slot=4,
                    max_slots=2, page_bytes=128)
    assert pool.trash_page == 8
    assert pool.pages_free == 8 and pool.pages_live == 0
    assert pool.pages_needed(1) == 1      # private page only
    assert pool.pages_needed(5) == 2      # one full + private
    assert pool.pages_needed(4) == 1      # plen-1 == 3 fits page 0

    g = pool.admit(0, [2, 3, 4, 5, 6], bucket=8)
    assert g is not None and len(g.pages) == 2 and g.hits == 0
    assert pool.pages_free == 6
    t = pool.table()
    assert t.shape == (2, 4)
    assert list(t[0, :2]) == g.pages and all(t[0, 2:] == 8)
    assert all(t[1] == 8)
    # decode write locations walk the owned pages
    assert pool.write_loc(0, 4) == (g.pages[1], 0)
    assert pool.write_loc(0, 7) == (g.pages[1], 3)
    # growth: pos 8 needs a third page; pos within coverage is a no-op
    assert pool.ensure(0, 7) and len(pool.slot_pages(0)) == 2
    assert pool.ensure(0, 8) and len(pool.slot_pages(0)) == 3
    assert pool.pages_free == 5
    # prefill feed: owned pages then trash for the bucket's pad pages
    pf = pool.prefill_pages(0, bucket=16)
    assert pf.shape == (4,) and list(pf[:3]) == pool.slot_pages(0)
    assert pf[3] == 8

    snap = observe.registry().snapshot()["gauges"]
    assert snap["kvpool.pages_free"] == 5
    assert snap["kvpool.pages_live"] == 3
    assert snap["kvpool.hbm_bytes"] == 3 * 128
    assert pool.release(0) == 3
    assert pool.pages_free == 8 and pool.pages_live == 0
    assert observe.registry().snapshot()["gauges"]["kvpool.pages_free"] == 8


def test_pool_prefix_sharing_refcounts_and_sharer_expiry_survival():
    pool = PagePool(num_pages=8, page_size=4, pages_per_slot=4,
                    max_slots=3)
    prompt = list(range(2, 12))           # len 10: two shareable pages
    a = pool.admit(0, prompt, bucket=16)
    assert a.hits == 0 and len(a.pages) == 3 and not a.full_hit
    b = pool.admit(1, prompt, bucket=16)
    assert b.hits == 2 and len(b.pages) == 3
    assert b.pages[:2] == a.pages[:2] and b.pages[2] != a.pages[2]
    # (10-1) % 4 != 0: the private page starts mid-page (position 8 is
    # prefill-written), so the dispatch cannot be skipped
    assert not b.full_hit
    assert pool.pages_free == 8 - 4       # 3 + 3 with 2 shared

    # a sharer retires (completion OR deadline expiry — same path):
    # only its PRIVATE page frees, the shared prefix stays resident
    assert pool.release(0) == 1
    assert pool.pages_free == 5
    assert pool.slot_pages(1) == b.pages  # survivor untouched
    c = pool.admit(2, prompt, bucket=16)
    assert c.hits == 2 and c.pages[:2] == b.pages[:2]
    assert pool.release(1) == 1 and pool.release(2) == 3
    assert pool.pages_free == 8

    # full-hit: plen-1 divisible by page_size AND every full page hits
    p5 = [3, 4, 5, 6, 7]
    a = pool.admit(0, p5, bucket=8)
    assert not a.full_hit                 # first admission shares nothing
    b = pool.admit(1, p5, bucket=8)
    assert b.hits == 1 and b.full_hit
    # same tokens, DIFFERENT bucket => different program => no hit
    c = pool.admit(2, p5, bucket=4)
    assert c.hits == 0
    pool.release(0), pool.release(1), pool.release(2)
    # with the last holder gone the index forgets the prefix
    d = pool.admit(0, p5, bucket=8)
    assert d.hits == 0
    pool.release(0)
    assert pool.pages_free == 8

    # flush_index (weight swap / cache scrub): holders keep pages, new
    # admissions stop hitting
    a = pool.admit(0, p5, bucket=8)
    pool.flush_index()
    b = pool.admit(1, p5, bucket=8)
    assert b.hits == 0
    assert pool.release(0) == 2 and pool.release(1) == 2


def test_pool_admission_backpressure_returns_none():
    pool = PagePool(num_pages=3, page_size=4, pages_per_slot=3,
                    max_slots=2, prefix_share=False)
    a = pool.admit(0, list(range(2, 10)), bucket=8)   # needs 2
    assert a is not None and pool.pages_free == 1
    assert pool.admit(1, list(range(12, 20)), bucket=8) is None  # needs 2
    assert pool.pages_free == 1           # a refused admit allocates NOTHING
    assert pool.slot_pages(1) == []
    # growth backpressure: one more page fits, then the pool is dry
    assert pool.ensure(0, 8)
    assert pool.pages_free == 0
    pool.release(0)
    assert pool.pages_free == 3
    b = pool.admit(1, list(range(12, 20)), bucket=8)
    assert b is not None
    pool.release(1)


def test_pool_page_leak_fault_oracle():
    pool = PagePool(num_pages=6, page_size=4, pages_per_slot=3,
                    max_slots=2, page_bytes=64)
    try:
        _fault.install(_fault.FaultPlan(kv_page_leak=2))
        pool.admit(0, list(range(2, 10)), bucket=8)   # 2 pages
        pool.admit(1, list(range(12, 20)), bucket=8)  # 2 pages
        assert pool.release(0) == 0       # both frees skipped (leaked)
        assert pool.release(1) == 2       # oracle exhausted: frees again
    finally:
        _fault.clear()
    assert pool.pages_leaked == 2
    assert pool.pages_free == 4           # 6 - 2 leaked
    snap = observe.registry().snapshot()["gauges"]
    assert snap["kvpool.pages_leaked"] == 2
    assert snap["kvpool.hbm_bytes"] == 2 * 64   # the leak stays visible


# ---------------------------------------------------------------------------
# engine level: bitwise equivalence, sharing, backpressure, kill switch
# ---------------------------------------------------------------------------

def _jobs(vocab, seed=19):
    rng = np.random.RandomState(seed)
    lengths, news = [3, 5, 8, 4, 6], [4, 5, 6, 4, 4]
    return [([int(t) for t in rng.randint(2, vocab - 1, size=n)], m)
            for n, m in zip(lengths, news)]


def test_paged_churn_bitwise_vs_dense_and_pages_drain(engines):
    dense, paged = engines
    pool = paged._pool
    free0 = pool.pages_free
    jobs = _jobs(dense.model.vocab_size)
    sequential = [dense.decode_static([j])[0][0] for j in jobs]
    futs = [paged.submit(p, n) for p, n in jobs]   # 5 jobs, 3 slots
    outs = [f.result(timeout=120) for f in futs]
    assert outs == sequential
    assert paged.wait_idle(timeout_s=30)
    assert pool.pages_free == free0       # churn leaks nothing
    assert pool.pages_leaked == 0
    # static batching over the paged engine: same bits again
    static = [t for t, _ in paged.decode_static(jobs[:3])]
    assert static == sequential[:3]
    assert pool.pages_free == free0


def test_shared_prefix_hits_skip_and_divergence(engines):
    dense, paged = engines
    pool = paged._pool
    free0 = pool.pages_free
    base = [5, 6, 7, 8]                   # one shareable full page
    pa, pb = base + [9], base + [10]      # divergent tails, len 5
    base_a = dense.decode_static([(pa, 4)])[0][0]
    base_b = dense.decode_static([(pb, 4)])[0][0]
    m0 = paged.metrics.snapshot()
    # pause admissions so all three land in ONE admit pass: the first
    # registers the prefix page, the other two must hit it
    paged.pause_admissions()
    f1 = paged.submit(pa, 8)
    f2 = paged.submit(pa, 4)
    f3 = paged.submit(pb, 4)
    paged.resume_admissions()
    o1, o2, o3 = (f.result(timeout=120) for f in (f1, f2, f3))
    m1 = paged.metrics.snapshot()
    # (5-1) % 4 == 0: both later admissions are FULL hits (pb too — its
    # divergent token sits at plen-1, written by its own first decode
    # tick into its private page, never into the shared one)
    assert m1["prefix_hits"] - m0["prefix_hits"] >= 2
    assert m1["prefill_skips"] - m0["prefill_skips"] >= 2
    assert o1[:len(base_a)] == base_a and o2 == base_a  # same shared bits
    assert o3 == base_b                        # divergence is per-slot
    assert paged.wait_idle(timeout_s=30)
    assert pool.pages_free == free0


def test_sharer_deadline_expiry_keeps_survivors_bitwise(engines):
    dense, paged = engines
    pool = paged._pool
    free0 = pool.pages_free
    base = [11, 12, 13, 14]
    pa, pb = base + [9], base + [10]
    base_b = dense.decode_static([(pb, 6)])[0][0]
    expired0 = paged.metrics.snapshot()["expired"]
    try:
        _fault.install(_fault.FaultPlan(decode_stall_ms=40.0))
        paged.pause_admissions()
        fa = paged.submit(pa, 18, timeout_ms=150.0)  # will expire mid-gen
        fb = paged.submit(pb, 6)                     # shares the prefix page
        paged.resume_admissions()
        with pytest.raises(RequestTimeout):
            fa.result(timeout=120)
        # the sharer's expiry freed its PRIVATE pages only: the shared
        # prefix page must stay resident and bit-stable under pb
        assert fb.result(timeout=120) == base_b
    finally:
        _fault.clear()
    assert paged.metrics.snapshot()["expired"] == expired0 + 1
    assert paged.wait_idle(timeout_s=30)
    assert pool.pages_free == free0       # expiry returned its pages


def test_pool_exhaustion_backpressure_queues_not_crashes():
    """An engine whose pool holds ONE request's worth of pages serves
    two requests by queueing the second until the first retires."""
    eng = DecodeEngine(_model(True, slots=2, max_len=12, buckets=(8,),
                              num_pages=3))
    try:
        rng = np.random.RandomState(3)
        jobs = [[int(t) for t in rng.randint(2, 30, size=8)]
                for _ in range(2)]
        f1 = eng.submit(jobs[0], 4)
        f2 = eng.submit(jobs[1], 4)
        assert len(f1.result(timeout=120)) == 4
        assert len(f2.result(timeout=120)) == 4
        snap = eng.metrics.snapshot()
        assert snap["page_requeues"] >= 1   # backpressure, not a shed
        assert snap["shed"] == 0 and snap["failed"] == 0
        assert eng.wait_idle(timeout_s=30)
        assert eng._pool.pages_free == 3
    finally:
        eng.shutdown(timeout_s=30)


def test_paged_kill_switch_restores_dense_bitwise(engines, monkeypatch):
    dense, _ = engines
    monkeypatch.setenv("PADDLE_SERVE_PAGED", "1")
    assert _model(None).paged is True     # env opts in
    monkeypatch.setenv("PADDLE_SERVE_PAGED", "0")
    m = _model(None)
    assert m.paged is False               # kill switch wins
    eng = DecodeEngine(m)
    try:
        assert eng._pool is None
        job = _jobs(m.vocab_size)[1]
        assert eng.decode_static([job])[0][0] == \
            dense.decode_static([job])[0][0]
    finally:
        eng.shutdown(timeout_s=30)


# ---------------------------------------------------------------------------
# op level: kernel vs fallback, infer rule
# ---------------------------------------------------------------------------

def _paged_attention_run(monkeypatch, fused):
    monkeypatch.setenv("PADDLE_TPU_FUSED", str(fused))
    rng = np.random.RandomState(7)
    s_n, n_pages, ps, d = 2, 2, 4, 8
    q = rng.randn(s_n, 1, d).astype(np.float32)
    ck = rng.randn(5, ps, d).astype(np.float32)   # 4 pages + trash row
    cv = rng.randn(5, ps, d).astype(np.float32)
    pt = np.array([[0, 1], [2, 4]], np.int64)     # row 1 maps the trash
    bias = np.zeros((s_n, 1, n_pages * ps), np.float32)
    bias[0, 0, 6:] = -np.inf
    bias[1, 0, 3:] = -np.inf                      # trash page fully masked
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup), fluid.unique_name.guard():
        qv = layers.data("q", shape=[s_n, 1, d], dtype="float32",
                         append_batch_size=False)
        ckv = layers.data("ck", shape=[5, ps, d], dtype="float32",
                          append_batch_size=False)
        cvv = layers.data("cv", shape=[5, ps, d], dtype="float32",
                          append_batch_size=False)
        ptv = layers.data("pt", shape=[s_n, n_pages], dtype="int64",
                          append_batch_size=False)
        bv = layers.data("bias", shape=[s_n, 1, n_pages * ps],
                         dtype="float32", append_batch_size=False)
        out = layers.paged_attention(qv, ckv, cvv, ptv, bv, scale=0.25)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    (res,) = exe.run(prog, feed={"q": q, "ck": ck, "cv": cv, "pt": pt,
                                 "bias": bias}, fetch_list=[out])
    return np.asarray(res)


def test_paged_attention_kernel_matches_fallback(monkeypatch):
    """Kernel vs XLA-take fallback: same exact-softmax algorithm, so
    they agree to fp32 ULP (jit reduction-order only; the BITWISE
    sequential-equivalence contract lives on the engine path, where one
    lowering is used consistently — the engine tests above prove it)."""
    c0 = fluid.profiler.counters().get("ops.fused.paged_attention", 0)
    unfused = _paged_attention_run(monkeypatch, fused=0)
    fused = _paged_attention_run(monkeypatch, fused=1)  # Pallas, interpreted
    assert fused.shape == (2, 1, 8)
    np.testing.assert_allclose(fused, unfused, rtol=1e-6, atol=1e-6)
    assert np.isfinite(unfused).all()         # trash garbage fully masked
    c1 = fluid.profiler.counters().get("ops.fused.paged_attention", 0)
    assert c1 == c0 + 1


def test_paged_attention_infer_rule_flags_bad_bias():
    """The static verifier catches a bias whose key length disagrees
    with ``pages_per_slot * page_size`` (a silently truncated or
    over-gathered attention window at runtime)."""
    from paddle_tpu import analysis

    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup), fluid.unique_name.guard():
        qv = layers.data("q2", shape=[2, 1, 8], dtype="float32",
                         append_batch_size=False)
        ckv = layers.data("ck2", shape=[5, 4, 8], dtype="float32",
                          append_batch_size=False)
        cvv = layers.data("cv2", shape=[5, 4, 8], dtype="float32",
                          append_batch_size=False)
        ptv = layers.data("pt2", shape=[2, 2], dtype="int64",
                          append_batch_size=False)
        bv = layers.data("bias2", shape=[2, 1, 7],   # != n_pages * ps
                         dtype="float32", append_batch_size=False)
        out = layers.paged_attention(qv, ckv, cvv, ptv, bv)
    r = analysis.verify_program(
        prog, feed=["q2", "ck2", "cv2", "pt2", "bias2"], fetch_list=[out])
    assert any(d.code == "AN101" and d.op_type == "paged_attention"
               and d.severity == "error" for d in r.diagnostics), r.format()


# ---------------------------------------------------------------------------
# the tier-1 CI entry
# ---------------------------------------------------------------------------

def test_paged_smoke_tool():
    """tools/paged_smoke.py is the tier-1 CI entry (JSON 'ok'); run its
    main() in-process so a regression fails here."""
    import tools.paged_smoke as smoke

    report = smoke.main()
    assert report["ok"], report
    assert report["bitwise_vs_dense"]
    assert report["prefix_hits"] > 0
    assert report["pages_free_after_drain"] == report["pages_free_initial"]
