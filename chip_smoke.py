#!/usr/bin/env python
"""Chip smoke: the quickest proof that the system still starts on the chip.

With no arguments it needs ONE TPU chip and drives the two main paths once,
through the entry points a user calls, with every kernel gate left in AUTO:

 1. **train** — Transformer-base at the benchmark cell's sizes
    (``transformer.base_config()``: d_model 512, d_inner 2048,
    8 heads, 6+6 layers, vocabulary 30,000; src_len = tgt_len = 256, batch
    64, Adam, bf16 AMP with keep-low activations): startup program, two
    warm-up steps, eight timed steps through
    ``fluid.Executor(fluid.TPUPlace())``.  The loss must be finite on every
    step and lower at the end than at the start on the fixed feed made
    from ``--seed``.  The step that ran is then lowered again and read:
    ``tpu_custom_call`` per Pallas kernel family (against the
    ``ops.fused.*`` dispatch counters) and the number of 64-bit element
    types that reach the device.
 2. **serve** — ``serving.DecodeEngine`` over ``transformer.DecodeModel``
    with ``decode_lm_config()`` (a TOY: d_model 16, 2 layers, vocabulary
    64 — here because the paged kernel and the engine's dispatch loop must
    meet the chip, not as a measurement), paged cache against dense cache
    on the same requests: every request answered in full, the paged
    engine's per-step logits within ``SERVE_LOGIT_ATOL`` of the dense
    engine's.

``--chips 4`` runs ONLY the four-chip path and what it is compared with:
the same Transformer-base program under ``ParallelExecutor(mesh="dp2,tp2")``
in one process, and the single-device ``Executor`` run of the same program
and seed (loss trajectories within ``MESH_LOSS_RTOL``; parameters and the
batch on four distinct devices).

Each phase prints one JSON object per line; the LAST stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as jax reports it.  Any failed phase, and a machine where
jax finds no TPU, ends in ``{"ok": false, ...}`` and a non-zero exit.
Times printed here are smoke readings on the host clock, not benchmark
numbers.

``--rehearse`` is for the CPU tests and for rehearsing a change without
the chip: it only shrinks the sizes (``tiny_config()``) and skips the
platform assertion.  It never prints ``"platform": "tpu"``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
import traceback
from importlib import metadata

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

#: paged-engine logits against the dense engine's.  The dense step's fp32
#: matmuls run at XLA's default precision while the paged kernel
#: multiplies in fp32 on the VPU, so equality is not promised on the chip.
#: Seen: 0.0 on the v5e, 3.6e-7 on the CPU (PR 23).
SERVE_LOGIT_ATOL = 1e-3
#: per-step loss of the dp2 x tp2 run against the single-device run (bf16
#: activations, another reduction order).  Seen: 3.7e-5 on four v5e chips
#: at Transformer-base, 1.3e-4 on virtual CPU devices at tiny (PR 23).
MESH_LOSS_RTOL = 2e-3

#: Pallas kernel family -> (kernel names in the lowered program, counter)
TRAIN_FAMILIES = {
    "flash_fwd": (("_flash_kernel",), "ops.fused.flash_attention"),
    "flash_bwd": (("_dq_kernel", "_dkv_kernel"),
                  "ops.fused.flash_attention"),
    "softmax_xent_fwd": (("_xent_partial_kernel",),
                         "ops.fused.softmax_xent"),
    "softmax_xent_bwd": (("_xent_bwd_kernel",), "ops.fused.softmax_xent"),
    "fused_adam": (("_adam_kernel",), "ops.fused.adam"),
}
PAGED_FAMILY = {"paged_attention": (("_paged_kernel",),
                                    "ops.fused.paged_attention")}

_WIDE = re.compile(r"tensor<(?:[0-9?]+x)*(f64|i64|ui64)>")


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


def device_record() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def counters(prefix: str) -> dict:
    """Dispatch counters, mesh labels folded away."""
    import paddle_tpu.fluid as fluid

    out: dict = {}
    for name, v in fluid.profiler.counters().items():
        if name.startswith(prefix):
            base = name.split("{", 1)[0]
            out[base] = out.get(base, 0) + v
    return out


def kernel_lines(phase: str, families: dict, lowered_text: str,
                 before: dict, gate_on: dict, rehearse: bool) -> bool:
    """One line per kernel family: was it in the program that ran
    (``tpu_custom_call`` by kernel name), was it dispatched (counter), and
    does that agree with what its gate answers for this backend."""
    after = counters("ops.fused.")
    ok = True
    for fam, (kernels, counter) in families.items():
        calls = sum(lowered_text.count(f'kernel_name = "{k}"')
                    for k in kernels)
        dispatched = after.get(counter, 0) - before.get(counter, 0)
        want = gate_on[counter]
        # interpret mode (the CPU rehearsal) leaves no custom call behind
        good = (dispatched > 0) == want and \
            (rehearse or (calls > 0) == want)
        ok = ok and good
        emit(phase=phase, kernel_family=fam, gate="on" if want else "off",
             compiled=calls > 0, tpu_custom_call=calls,
             ran=dispatched > 0, dispatches=dispatched,
             interpret=bool(want and calls == 0), ok=good)
    return ok


def cache_counts() -> dict:
    import paddle_tpu.fluid as fluid

    c = fluid.profiler.counters()
    return {"store_hits": c.get("compile_cache.hit", 0),
            "store_misses": c.get("compile_cache.miss", 0)}


class BackendCacheEvents:
    """jax's own persistent-cache events: a hit loads an executable from
    the directory, a miss compiles and writes one."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def build_transformer(fluid, rehearse: bool, seed: int):
    """Transformer-base at the benchmark cell's sizes, and the fixed feed.  Labels repeat the decoder input, so ten steps on the one
    batch must lower the loss."""
    from paddle_tpu.models import transformer

    cfg = transformer.tiny_config() if rehearse else transformer.base_config()
    seq_len, batch = (32, 4) if rehearse else (256, 64)
    fluid.amp.enable("bfloat16", keep_activations=True)
    _, _, _, loss = transformer.build(cfg, src_len=seq_len, tgt_len=seq_len,
                                      lr=1e-3)
    fluid.default_startup_program().random_seed = seed
    fluid.default_main_program().random_seed = seed
    rng = np.random.RandomState(seed)
    tgt = rng.randint(1, cfg.tgt_vocab_size, size=(batch, seq_len))
    feed = {"src_word": rng.randint(1, cfg.src_vocab_size,
                                    size=(batch, seq_len)).astype(np.int64),
            "tgt_word": tgt.astype(np.int64),
            "lbl_word": tgt[..., None].astype(np.int64)}
    shape = {"model": f"transformer_{cfg.name}", "d_model": cfg.d_model,
             "d_inner": cfg.d_inner, "n_head": cfg.n_head,
             "n_layer": f"{cfg.n_layer}+{cfg.n_layer}",
             "vocab": cfg.tgt_vocab_size, "seq_len": seq_len,
             "batch": batch, "amp": fluid.amp.compute_dtype(),
             "optimizer": "adam"}
    return loss, feed, shape


def check_losses(losses) -> None:
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not decrease: {losses}")


def train_phase(args) -> None:
    import paddle_tpu.fluid as fluid
    from paddle_tpu.ops import kernel_choice

    loss, feed, shape = build_transformer(fluid, args.rehearse, args.seed)
    before = counters("ops.fused.")
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(fluid.default_startup_program())
    prog = fluid.default_main_program()

    t0 = time.perf_counter()
    (first,) = exe.run(prog, feed=feed, fetch_list=[loss])
    first_call_s = time.perf_counter() - t0
    losses = [float(first.reshape(-1)[0])]
    (second,) = exe.run(prog, feed=feed, fetch_list=[loss])
    losses.append(float(second.reshape(-1)[0]))

    # device-resident fetches; materializing the LAST one inside the timed
    # region blocks on the whole queue (the block_until_ready of the run)
    t0 = time.perf_counter()
    outs = [exe.run(prog, feed=feed, fetch_list=[loss],
                    return_numpy=False)[0] for _ in range(args.steps)]
    last = float(np.asarray(outs[-1]).reshape(-1)[0])
    step_s = (time.perf_counter() - t0) / args.steps
    losses += [float(np.asarray(o).reshape(-1)[0]) for o in outs[:-1]]
    losses.append(last)
    check_losses(losses)
    emit(phase="train", **shape, entry="fluid.Executor(fluid.TPUPlace())",
         warmup_steps=2, timed_steps=args.steps,
         loss_first=losses[0], loss_last=losses[-1],
         losses=[round(v, 5) for v in losses],
         setup_first_call_seconds=round(first_call_s, 3),
         smoke_reading_seconds_per_step=step_s,
         note="host-clock smoke reading, not a benchmark number", ok=True)

    text = exe.lower_step(prog, feed, [loss]).as_text()
    fused = kernel_choice.gate("fused")
    gates = {"ops.fused.flash_attention": kernel_choice.gate("flash"),
             "ops.fused.softmax_xent": fused, "ops.fused.adam": fused}
    kernels_ok = kernel_lines("train", TRAIN_FAMILIES, text, before, gates,
                              args.rehearse)
    wide = {}
    for m in _WIDE.finditer(text):
        wide[m.group(1)] = wide.get(m.group(1), 0) + 1
    emit(phase="train", tpu_custom_call_total=text.count("tpu_custom_call"),
         element_types_64bit=sum(wide.values()), by_type=wide,
         note="tensor types in the lowered step (jax x64 mode lets them "
              "reach the device)")
    if not kernels_ok:
        raise AssertionError("a kernel family did not run as its gate says")


def serve_phase(args) -> None:
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import transformer
    from paddle_tpu.ops import kernel_choice
    from paddle_tpu.serving import DecodeEngine

    fluid.amp.disable()  # the engine's model and its oracle are float32
    slots, max_len, buckets, page_size = 4, 32, [4, 8], 4
    cfg = transformer.decode_lm_config()
    rng = np.random.RandomState(args.seed)
    jobs = [([int(t) for t in rng.randint(2, cfg.tgt_vocab_size - 1,
                                          size=n)], new)
            for n, new in zip([3, 5, 8, 4, 6, 7], [6, 5, 7, 6, 5, 8])]

    def run(paged):
        """One engine over the jobs; per-request tokens and, through the
        engine's tick monitor, the logits row behind every token."""
        model = transformer.DecodeModel(
            cfg=cfg, max_slots=slots, max_len=max_len,
            prefill_buckets=list(buckets), paged=paged,
            page_size=page_size if paged else None, seed=args.seed)
        rows = {}

        def monitor(logits, dispatched):
            for i, req in enumerate(dispatched):
                if req is not None:
                    rows[(tuple(req.prompt), len(req.out_tokens) - 1)] = \
                        np.array(logits[i], np.float32)

        eng = DecodeEngine(model, place=fluid.TPUPlace())
        try:
            eng.set_tick_monitor(monitor)
            futs = [eng.submit(p, n) for p, n in jobs]
            outs = [f.result(timeout=300) for f in futs]
            eng.wait_idle(timeout_s=60)
            free = (eng._pool.pages_free, eng._pool.num_pages) \
                if paged else None
        finally:
            eng.shutdown(timeout_s=60)
        return model, outs, rows, free

    before = counters("ops.fused.")
    _, dense_out, dense_rows, _ = run(False)
    model, paged_out, paged_rows, (free, total) = run(True)

    for (prompt, new), out in zip(jobs, paged_out):
        if not (len(out) == new or (out and out[-1] == model.end_id)):
            raise AssertionError(
                f"request {prompt} answered {len(out)} of {new} tokens")
    if free != total:
        raise AssertionError(f"{total - free} pages not returned")
    # a step's logits are comparable while the two streams still fed the
    # same tokens: up to and including the first step that disagrees
    worst, compared = 0.0, 0
    for (prompt, _), d_out, p_out in zip(jobs, dense_out, paged_out):
        for t in range(min(len(d_out), len(p_out))):
            key = (tuple(prompt), t)
            diff = float(np.max(np.abs(dense_rows[key] - paged_rows[key])))
            worst, compared = max(worst, diff), compared + 1
            if d_out[t] != p_out[t]:
                break
    good = worst <= SERVE_LOGIT_ATOL and compared > 0
    emit(phase="serve", model="decode_lm (TOY: d_model 16, 2 layers, "
         "vocab 64)", entry="serving.DecodeEngine(DecodeModel, "
         "place=fluid.TPUPlace())", slots=slots, max_len=max_len,
         page_size=page_size, requests=len(jobs),
         answered=[len(o) for o in paged_out],
         asked=[n for _, n in jobs], logit_rows_compared=compared,
         max_abs_logit_diff_paged_vs_dense=worst, atol=SERVE_LOGIT_ATOL,
         tokens_agree=paged_out == dense_out, pages_returned=free == total,
         ok=good)

    # the paged step program the engine ran, lowered for its kernel
    exe, scope = fluid.Executor(fluid.TPUPlace()), fluid.Scope()
    exe.run(model.startup, scope=scope)
    zeros = {v.name: np.zeros(v.shape, v.dtype) for v in
             model.step_program.global_block().vars.values() if v.is_data}
    text = exe.lower_step(model.step_program, zeros,
                          [model.step_fetch, model.logits_fetch],
                          scope=scope).as_text()
    gates = {"ops.fused.paged_attention": kernel_choice.gate("fused")}
    kernels_ok = kernel_lines("serve", PAGED_FAMILY, text, before, gates,
                              args.rehearse)
    if not (good and kernels_ok):
        raise AssertionError("serve phase failed (see its lines)")


def mesh_phase(args) -> None:
    """The four-chip path and its comparison, nothing else."""
    import jax

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.parallel_executor import ParallelExecutor

    if len(jax.devices()) < 4:
        raise AssertionError(
            f"--chips 4 needs four devices, jax sees {len(jax.devices())}")
    loss, feed, shape = build_transformer(fluid, args.rehearse, args.seed)
    prog = fluid.default_main_program()
    steps = args.steps

    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(fluid.default_startup_program())
        single = [float(exe.run(prog, feed=feed, fetch_list=[loss])[0]
                        .reshape(-1)[0]) for _ in range(steps)]
        exe.close()
    check_losses(single)

    before = counters("ops.fused.")
    with fluid.scope_guard(fluid.Scope()):
        fluid.Executor(fluid.TPUPlace()).run(fluid.default_startup_program())
        pe = ParallelExecutor(loss_name=loss.name, main_program=prog,
                              mesh="dp2,tp2")
        t0 = time.perf_counter()
        sharded = [float(np.asarray(pe.run([loss], feed=feed)[0])
                         .reshape(-1)[0])]
        first_call_s = time.perf_counter() - t0
        sharded += [float(np.asarray(pe.run([loss], feed=feed)[0])
                          .reshape(-1)[0]) for _ in range(steps - 1)]
        check_losses(sharded)

        scope = fluid.global_scope()
        step = next(iter(pe._cache.values()))  # the step PE.run built
        placed = step.place_feed(feed)
        spread = {"batch:" + k: v for k, v in placed.items()}
        spread.update({p.name: scope.get(p.name)
                       for p, _ in prog._params_grads})
        on = {n: sorted(d.id for d in a.sharding.device_set)
              for n, a in spread.items()}
        bad = {n: ids for n, ids in on.items() if len(ids) != 4}
        if bad:
            raise AssertionError(f"not on four distinct devices: {bad}")
        sharded_params = sum(
            1 for n, a in spread.items() if not n.startswith("batch:")
            and not a.sharding.is_fully_replicated)
        batch_shard = {k: list(v.addressable_shards[0].data.shape)
                       for k, v in placed.items()}

    rel = [abs(a - b) / max(abs(b), 1e-12) for a, b in zip(sharded, single)]
    good = max(rel) <= MESH_LOSS_RTOL
    emit(phase="mesh", **shape, mesh=pe.mesh_label,
         entry='ParallelExecutor(mesh="dp2,tp2")', steps=steps,
         losses_single_device=[round(v, 5) for v in single],
         losses_dp2xtp2=[round(v, 5) for v in sharded],
         max_rel_loss_diff=max(rel), rtol=MESH_LOSS_RTOL,
         setup_first_call_seconds=round(first_call_s, 3),
         mesh_devices=[d.id for d in pe.mesh.devices.reshape(-1)],
         arrays_checked=len(on), arrays_on_four_devices=len(on) - len(bad),
         tp_sharded_params=sharded_params, batch_shard_shape=batch_shard,
         fused_dispatches={k: v - before.get(k, 0) for k, v in
                           counters("ops.fused.").items()},
         ok=good)
    if not good:
        raise AssertionError(
            f"dp2,tp2 losses left the single-device run: {rel}")


def run(args) -> dict:
    import jax

    dev = device_record()
    if not args.rehearse and dev["platform"] != "tpu":
        raise AssertionError(
            f"chip_smoke needs a TPU; jax sees {jax.devices()}")
    import jaxlib

    import paddle_tpu
    from paddle_tpu import compile_cache

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    emit(phase="device", **dev, chips_asked=args.chips,
         rehearse=args.rehearse, python=sys.version.split()[0],
         jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu,
         paddle_tpu=paddle_tpu.__version__,
         x64=bool(jax.config.jax_enable_x64))

    backend_events = BackendCacheEvents()
    store = compile_cache.get_store() or \
        compile_cache.configure(compile_cache.checkout_root())
    if args.chips == 4:
        mesh_phase(args)
    else:
        train_phase(args)
        serve_phase(args)
    emit(phase="compile_cache", store_root=store.root,
         backend_dir=compile_cache.backend_cache_dir(),
         backend_dir_from_env=bool(
             os.environ.get("JAX_COMPILATION_CACHE_DIR")),
         backend_hits=backend_events.hits,
         backend_misses=backend_events.misses, **cache_counts())
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: tiny sizes, no platform assertion")
    args = ap.parse_args(argv)
    args.steps = 2 if args.rehearse else 8  # timed, after two warm-ups
    try:
        dev = run(args)
    except Exception as exc:  # every failed phase ends in ok:false, exit 1
        traceback.print_exc()
        emit(ok=False, error=f"{type(exc).__name__}: {exc}"[:2000])
        return 1
    emit(ok=True, device=dev)
    return 0


if __name__ == "__main__":
    sys.exit(main())
