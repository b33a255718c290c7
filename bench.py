"""Benchmark driver: prints the headline metrics as JSON lines.

With no args it measures BOTH driver metrics (BASELINE.json): ResNet-50
training images/sec/chip and Transformer-base tokens/sec/chip, printing one
JSON line per model and a final combined line carrying both numbers (the
driver records the output; the combined last line guarantees both metrics
land in the record however many lines are parsed).  Set
BENCH_MODEL=resnet|transformer|mnist to measure a single model.

vs_baseline compares against the reference's best published number for the
model (reference benchmark/IntelOptimizedPaddle.md:43-45 — ResNet-50
training 84.08 images/sec on 2x Xeon 6148 MKL-DNN bs=256; the reference
publishes no per-chip TPU or Transformer figure, so the Transformer baseline
is the same hardware-era proxy documented in BASELINE.md).

Mixed precision: on an accelerator the bench trains with bf16 AMP
(fluid.amp — matmuls/convs in bfloat16 with fp32 accumulation and fp32
master weights), the TPU equivalent of the reference's float16 transpiler
(ref: paddle/contrib/float16/float16_transpiler.py).  BENCH_AMP=0 disables.

The device: the bench reads ``jax.devices()`` in this process and runs on
what it finds, through the same kernel gates every user gets (nothing here
sets ``PADDLE_TPU_FLASH`` / ``PADDLE_TPU_FUSED``).  It runs on the CPU only
when the process was pinned there on purpose (``JAX_PLATFORMS=cpu``), at
the small sizes below and with ``_cpu`` in the metric name; asked for
nothing and finding no accelerator, it exits non-zero.  Every line names
the device it ran on.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# Reference numbers to compare against (see module docstring).
BASELINES = {
    "resnet": 84.08,        # images/sec, ResNet-50 train bs=256, 2x Xeon 6148
    "transformer": 15468.3,  # tokens/sec, derived dimensionally from the
                             # reference's largest published seq-model figure:
                             # LSTM 2-layer h=1280, bs=256, padded seq len 100
                             # (reference benchmark/README.md:105,131-136) at
                             # 1655 ms/batch -> 256*100/1.655 = 15468 tok/s.
                             # The reference has no Transformer number; this is
                             # the honest tokens/sec of its best seq2seq-scale
                             # benchmark, not a ms/batch figure reused as a rate.
    "mnist": 10000.0,       # images/sec, no published figure; nominal.
    "resnet_infer": 217.69,  # images/sec, ResNet-50 infer bs=16
                             # (IntelOptimizedPaddle.md:85-87)
    "vgg": 28.46,            # images/sec, VGG-19 train bs=64, 2x Xeon 6148
                             # (IntelOptimizedPaddle.md:33-35)
    "alexnet": 399.00,       # images/sec, AlexNet train bs=64
                             # (IntelOptimizedPaddle.md:63-65)
    "googlenet": 250.46,     # images/sec, GoogleNet train bs=64
                             # (IntelOptimizedPaddle.md:53-55)
    "rnn": 347.83,           # sequences/sec: LSTM 2-layer+fc h=512 bs=64
                             # at 184 ms/batch (reference
                             # benchmark/README.md:113-120) -> 64/0.184
}


def _cache_counters():
    """(hit, miss) snapshot of the persistent compile cache's counters."""
    from paddle_tpu.fluid import profiler as _prof

    c = _prof.counters()
    return (c.get("compile_cache.hit", 0), c.get("compile_cache.miss", 0))


def _cold_info(t_compile, before, after, window_steps=1, prefetch=0):
    """BENCH-line cold-start fields: the first dispatch's wall time
    (trace + XLA compile + step) reported SEPARATELY from steady-state
    throughput, plus whether it was served warm from the persistent
    compile cache (PADDLE_COMPILE_CACHE_DIR) — so warm-vs-cold runs are
    distinguishable in the trajectory.  Every line also records the
    dispatch shape of the measured loop: ``window_steps`` (steps fused
    per run_steps dispatch; 1 = per-step), the resulting
    ``dispatches_per_step`` amortization, and the ``prefetch`` depth the
    loop staged input with (0 = synchronous / fixed resident feed)."""
    h0, m0 = before
    h1, m1 = after
    return {"compile_seconds": round(t_compile, 3),
            "cache_hit": bool(h1 > h0 and m1 == m0),
            "window_steps": int(window_steps),
            "dispatches_per_step": round(1.0 / max(1, int(window_steps)), 4),
            "prefetch": int(prefetch)}


def _timed_run_mesh(fluid, loss, feed, steps, spd, mesh_spec):
    """BENCH_MESH=dp4,tp2 (or PADDLE_TPU_MESH): the whole-program SPMD
    path — one ParallelExecutor over the named mesh, ``spd`` steps fused
    per dispatch (BENCH_SPD, default 4), so every BENCH line on this path
    records ``dispatches_per_step < 1`` plus the mesh label.  The batch
    must divide the mesh's dp extent (the runner raises the named
    ValueError otherwise — size your BENCH_*_BS accordingly)."""
    from paddle_tpu.fluid.parallel_executor import ParallelExecutor

    spd = spd if spd > 1 else min(4, max(1, steps))
    n_chunks = max(1, steps // spd)
    steps = n_chunks * spd
    prog = fluid.default_main_program()
    pe = ParallelExecutor(loss_name=loss.name, main_program=prog,
                          mesh=mesh_spec)
    feed_w = {k: np.stack([np.asarray(v)] * spd) for k, v in feed.items()}
    cc0 = _cache_counters()
    t_c = time.perf_counter()
    pe.run_steps([loss], feed=feed_w, n_steps=spd, feed_per_step=True)
    cold = _cold_info(time.perf_counter() - t_c, cc0, _cache_counters(),
                      spd, 0)
    cold["mesh"] = pe.mesh_label
    t0 = time.perf_counter()
    out = None
    for _ in range(n_chunks):
        (out,) = pe.run_steps([loss], feed=feed_w, n_steps=spd,
                              feed_per_step=True)
    last = float(np.asarray(out).reshape(-1)[0])
    dt = time.perf_counter() - t0
    assert np.isfinite(last), f"non-finite loss {last}"
    return dt, steps, pe, cold


def timed_run(fluid, on_accel, loss, feed, steps, warmup=2):
    """Shared harness: startup program, warmup (compile), timed steps.

    The feed is staged onto the device ONCE before timing (Executor accepts
    device-resident jax arrays and passes them through) — the equivalent of
    the reference's `--use_reader_op` path where data is already resident
    rather than re-fed from numpy every step (ref:
    benchmark/fluid/fluid_benchmark.py:149).

    BENCH_SPD=K>1 (or the library-wide PADDLE_TPU_SPD, honored when the
    bench knob is unset) opts into Executor.run_steps (lax.scan, K steps
    per dispatch) — guardian-gated and dynamic-fp16-loss-scaled programs
    included, since ISSUE 6 folded the sentinel + scaler into the scan
    carry.  NOT the default: the executor's per-step async dispatches
    already pipeline on the device, and whether the scanned loop beats
    them on the attached chip is not measured (ROADMAP S6).  run_steps
    is for loops where the host must SYNC every step (per-step
    metrics/logging), which the bench's deferred-fetch loop does not.

    BENCH_PREFETCH=1 (with SPD>1) additionally drives the
    production-shaped input path: per-step batches staged window-by-window
    through a DevicePrefetcher (feed_per_step windows, H2D overlapping
    compute) instead of one fixed device-resident feed.

    Returns (seconds, steps_actually_timed, executor, cold) — ``cold``
    carries the first-dispatch ``compile_seconds`` (trace + XLA compile,
    measured separately from the steady-state timing), ``cache_hit``
    (whether the persistent compile cache served it warm) and the
    window/prefetch shape fields (_cold_info)."""
    place = fluid.TPUPlace() if on_accel else fluid.CPUPlace()
    exe = fluid.Executor(place)
    exe.run(fluid.default_startup_program())
    prog = fluid.default_main_program()
    spd = int(os.environ.get("BENCH_SPD",
                             os.environ.get("PADDLE_TPU_SPD", "0") or "0")
              or 0)
    spd = max(1, min(spd, steps)) if spd > 0 else 1
    mesh_spec = os.environ.get(
        "BENCH_MESH", os.environ.get("PADDLE_TPU_MESH", "")).strip()
    if mesh_spec and not any(isinstance(v, tuple) for v in feed.values()):
        # sharded windowed path (LoD feeds need the per-step executor);
        # startup already ran above, so the scope state is live
        return _timed_run_mesh(fluid, loss, feed, steps, spd, mesh_spec)
    use_pf = spd > 1 and not any(isinstance(v, tuple) for v in feed.values()) \
        and os.environ.get("BENCH_PREFETCH", "").strip().lower() in ("1", "true")
    if on_accel and not use_pf:
        import jax

        from paddle_tpu.fluid import core as _core

        dev = _core.get_jax_device(place)
        # LoD feeds are (rows, lengths) tuples: stage only the rows array;
        # the lengths must stay host ints (the executor int()s each one —
        # device scalars there would mean per-element D2H syncs per step)
        feed = {k: ((jax.device_put(v[0], dev), v[1])
                    if isinstance(v, tuple) else jax.device_put(v, dev))
                for k, v in feed.items()}
    if spd > 1:
        n_chunks = max(1, steps // spd)
        steps = n_chunks * spd
        if use_pf:
            from paddle_tpu.fluid.prefetch import (DevicePrefetcher,
                                                   default_depth)

            depth = default_depth()
            batches = (dict(feed) for _ in range((n_chunks + 1) * spd))
            cc0 = _cache_counters()
            t_c = time.perf_counter()
            with DevicePrefetcher(batches, n_steps=spd, place=place,
                                  depth=depth) as pf:
                it = iter(pf)
                fd, cnt = next(it)
                exe.run_steps(prog, feed=fd, fetch_list=[loss],
                              n_steps=cnt, feed_per_step=True)
                cold = _cold_info(time.perf_counter() - t_c, cc0,
                                  _cache_counters(), spd, depth)
                t0 = time.perf_counter()
                out = None
                for _ in range(n_chunks):
                    fd, cnt = next(it)
                    (out,) = exe.run_steps(prog, feed=fd, fetch_list=[loss],
                                           n_steps=cnt, feed_per_step=True)
            last = float(np.asarray(out).reshape(-1)[0])
            dt = time.perf_counter() - t0
            assert np.isfinite(last), f"non-finite loss {last}"
            return dt, steps, exe, cold
        cc0 = _cache_counters()
        t_c = time.perf_counter()
        exe.run_steps(prog, feed=feed, fetch_list=[loss], n_steps=spd)
        cold = _cold_info(time.perf_counter() - t_c, cc0, _cache_counters(),
                          spd, 0)
        t0 = time.perf_counter()
        out = None
        for _ in range(n_chunks):
            (out,) = exe.run_steps(prog, feed=feed, fetch_list=[loss],
                                   n_steps=spd)
        last = float(np.asarray(out).reshape(-1)[0])
        dt = time.perf_counter() - t0
        assert np.isfinite(last), f"non-finite loss {last}"
        return dt, steps, exe, cold
    cc0 = _cache_counters()
    t_c = time.perf_counter()
    exe.run(prog, feed=feed, fetch_list=[loss])
    cold = _cold_info(time.perf_counter() - t_c, cc0, _cache_counters())
    for _ in range(max(0, warmup - 1)):
        exe.run(prog, feed=feed, fetch_list=[loss])
    # fetch device-resident losses per step (return_numpy=False defers the
    # D2H sync); materializing the LAST loss inside the timed region blocks
    # on the whole device queue, so the timing is honest while per-step
    # latency of the fetch transport overlaps with compute.
    t0 = time.perf_counter()
    out = None
    for _ in range(steps):
        (out,) = exe.run(prog, feed=feed, fetch_list=[loss],
                         return_numpy=False)
    last = float(np.asarray(out).reshape(-1)[0])
    dt = time.perf_counter() - t0
    assert np.isfinite(last), f"non-finite loss {last}"
    return dt, steps, exe, cold


def kernel_config():
    """The active kernel configuration, recorded in EVERY BENCH line so
    rounds are attributable to kernel changes: ``flash`` (Pallas flash
    attention on/off after the PADDLE_TPU_FLASH/attr/AUTO precedence) and
    ``fused`` (the pallas_fused families that would dispatch — softmax_xent
    + optimizer sweeps — under PADDLE_TPU_FUSED)."""
    from paddle_tpu.ops import pallas_fused
    from paddle_tpu.ops.attention_ops import _flash_decision

    return {"flash": bool(_flash_decision()),
            "fused": pallas_fused.active_families()}


def device_info():
    """The device this process runs on, as jax reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def result_line(name, value, unit, baseline_key, **extra):
    return {"metric": name, "value": round(value, 2), "unit": unit,
            "vs_baseline": round(value / BASELINES[baseline_key], 3),
            "device": device_info(), **kernel_config(), **extra}


def _env_int(model, name, default):
    """Per-model override (BENCH_RESNET_BS) > generic (BENCH_BS) > default.
    In the default both-models mode the generic var would force one model's
    tuning onto the other, so per-model vars take precedence."""
    v = os.environ.get(f"BENCH_{model.upper()}_{name}",
                       os.environ.get(f"BENCH_{name}"))
    return int(v) if v else default


def bench_resnet(fluid, platform, on_accel):
    from paddle_tpu.models import resnet

    batch = _env_int("resnet", "BS", 256 if on_accel else 4)
    steps = _env_int("resnet", "STEPS", 20 if on_accel else 3)
    image_hw = 224 if on_accel else 64
    class_dim = 1000 if on_accel else 100

    img, label, prediction, loss, acc = resnet.build(
        class_dim=class_dim, depth=50, image_shape=(3, image_hw, image_hw),
        lr=0.1)
    rng = np.random.RandomState(0)
    feed = {"img": rng.normal(size=(batch, 3, image_hw, image_hw)).astype(np.float32),
            "label": rng.randint(0, class_dim, size=(batch, 1)).astype(np.int64)}
    dt, steps, _, cold = timed_run(fluid, on_accel, loss, feed, steps)

    ips = batch * steps / dt
    # MFU input: ResNet-50 fwd ~3.86 GFLOP/img at 224px (scales ~(hw/224)^2);
    # train ~= 3x fwd.  Only meaningful on a real accelerator.
    extra = {"amp": fluid.amp.compute_dtype() or "off", **cold}
    if on_accel:
        from paddle_tpu.observe.trace import peak_tflops

        gflop_per_img = 3 * 3.86 * (image_hw / 224.0) ** 2
        tflops = ips * gflop_per_img / 1e3
        peak = peak_tflops(device_info()["kind"])
        extra["achieved_tflops"] = round(tflops, 2)
        extra["mfu_pct"] = round(100.0 * tflops / peak, 2)
        extra["peak_tflops"] = peak
    return result_line(f"resnet50_{image_hw}px_bs{batch}_train_{platform}",
                       ips, "images/sec/chip", "resnet", **extra)


def bench_transformer(fluid, platform, on_accel):
    from paddle_tpu.models import transformer

    batch = _env_int("transformer", "BS", 64 if on_accel else 2)
    steps = _env_int("transformer", "STEPS", 20 if on_accel else 3)
    seq_len = 256 if on_accel else 32
    cfg = (transformer.base_config() if on_accel
           else transformer.tiny_config())

    src, tgt, lbl, loss = transformer.build(
        cfg, src_len=seq_len, tgt_len=seq_len, lr=1e-3)
    rng = np.random.RandomState(0)
    feed = {"src_word": rng.randint(1, cfg.src_vocab_size, size=(batch, seq_len)).astype(np.int64),
            "tgt_word": rng.randint(1, cfg.tgt_vocab_size, size=(batch, seq_len)).astype(np.int64),
            "lbl_word": rng.randint(1, cfg.tgt_vocab_size, size=(batch, seq_len, 1)).astype(np.int64)}
    dt, steps, _, cold = timed_run(fluid, on_accel, loss, feed, steps)

    tps = batch * seq_len * steps / dt  # target tokens/sec
    return result_line(
        f"transformer_{cfg.name}_len{seq_len}_bs{batch}_train_{platform}",
        tps, "tokens/sec/chip", "transformer",
        amp=fluid.amp.compute_dtype() or "off", **cold)


def bench_vgg(fluid, platform, on_accel):
    """VGG-19 training (BENCH_MODEL=vgg; baseline: the reference's
    published 28.46 images/sec at bs=64 on 2x Xeon 6148)."""
    from paddle_tpu.models import vgg

    batch = _env_int("vgg", "BS", 64 if on_accel else 4)
    steps = _env_int("vgg", "STEPS", 10 if on_accel else 3)
    image_hw = 224 if on_accel else 32
    class_dim = 1000 if on_accel else 10
    img, label, prediction, loss, acc = vgg.build(
        class_dim=class_dim, image_shape=(3, image_hw, image_hw), lr=0.01,
        depth=19)
    rng = np.random.RandomState(0)
    feed = {"img": rng.normal(size=(batch, 3, image_hw, image_hw))
            .astype(np.float32),
            "label": rng.randint(0, class_dim,
                                 size=(batch, 1)).astype(np.int64)}
    dt, steps, _, cold = timed_run(fluid, on_accel, loss, feed, steps)
    ips = batch * steps / dt
    return result_line(f"vgg19_{image_hw}px_bs{batch}_train_{platform}",
                       ips, "images/sec/chip", "vgg",
                       amp=fluid.amp.compute_dtype() or "off", **cold)


def bench_mnist(fluid, platform, on_accel):
    from paddle_tpu.models import mnist

    batch = _env_int("mnist", "BS", 512 if on_accel else 64)
    steps = _env_int("mnist", "STEPS", 50 if on_accel else 10)
    img, label, prediction, loss, acc = mnist.mlp()
    fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)

    rng = np.random.RandomState(0)
    feed = {"img": rng.normal(size=(batch, 784)).astype(np.float32),
            "label": rng.randint(0, 10, size=(batch, 1)).astype(np.int64)}
    dt, steps, _, cold = timed_run(fluid, on_accel, loss, feed, steps)
    ips = batch * steps / dt
    return result_line(f"mnist_mlp_bs{batch}_train_{platform}",
                       ips, "images/sec/chip", "mnist", **cold)


def bench_resnet_infer(fluid, platform, on_accel):
    """Inference throughput via the predictor path (ref baseline: ResNet-50
    infer bs16 = 217.69 images/sec on 2x Xeon 6148, IntelOptimizedPaddle
    .md:85-87).  Forward-only for_test clone, deferred fetches.
    BENCH_INT8=1 additionally rewrites the weights int8-in-HBM
    (transpiler.Int8WeightTranspiler) — the weight-bandwidth-bound
    deployment configuration."""
    from paddle_tpu.models import resnet

    batch = _env_int("resnet_infer", "BS", 16)
    steps = _env_int("resnet_infer", "STEPS", 30 if on_accel else 3)
    image_hw = 224 if on_accel else 64
    class_dim = 1000 if on_accel else 100
    img, label, prediction, loss, acc = resnet.build(
        class_dim=class_dim, depth=50, image_shape=(3, image_hw, image_hw),
        lr=0.1)
    infer_prog = fluid.default_main_program().clone(for_test=True)
    int8 = os.environ.get("BENCH_INT8", "") in ("1", "true")

    place = fluid.TPUPlace() if on_accel else fluid.CPUPlace()
    exe = fluid.Executor(place)
    exe.run(fluid.default_startup_program())
    if int8:
        # AFTER startup: the transpiler quantizes the weights that now
        # live in the scope (before startup there is nothing to quantize
        # and every param would be silently skipped)
        from paddle_tpu.fluid.transpiler import Int8WeightTranspiler

        quantized = Int8WeightTranspiler().transpile(infer_prog)
        assert quantized, "int8 transpile quantized no weights"
    rng = np.random.RandomState(0)
    feed = {"img": rng.normal(size=(batch, 3, image_hw, image_hw))
            .astype(np.float32)}
    if on_accel:
        import jax

        from paddle_tpu.fluid import core as _core

        dev = _core.get_jax_device(place)
        # LoD feeds are (rows, lengths) tuples: stage only the rows array;
        # the lengths must stay host ints (the executor int()s each one —
        # device scalars there would mean per-element D2H syncs per step)
        feed = {k: ((jax.device_put(v[0], dev), v[1])
                    if isinstance(v, tuple) else jax.device_put(v, dev))
                for k, v in feed.items()}
    cc0 = _cache_counters()
    t_c = time.perf_counter()
    exe.run(infer_prog, feed=feed, fetch_list=[prediction])
    cold = _cold_info(time.perf_counter() - t_c, cc0, _cache_counters())
    exe.run(infer_prog, feed=feed, fetch_list=[prediction])
    t0 = time.perf_counter()
    out = None
    for _ in range(steps):
        (out,) = exe.run(infer_prog, feed=feed, fetch_list=[prediction],
                         return_numpy=False)
    last = np.asarray(out)
    dt = time.perf_counter() - t0
    assert np.isfinite(last).all()
    ips = batch * steps / dt
    tag = "_int8" if int8 else ""
    return result_line(
        f"resnet50_{image_hw}px_bs{batch}_infer{tag}_{platform}",
        ips, "images/sec/chip", "resnet_infer",
        amp=fluid.amp.compute_dtype() or "off",
        weights=("int8" if int8 else "fp32"), **cold)


def bench_decode(fluid, platform, on_accel):
    """Beam-search GENERATION throughput (BENCH_MODEL=decode).

    Default engine: JitBeamSearchDecoder — the WHOLE generation loop is one
    lax.while_loop XLA program (2 dispatches total: loop + LoD packaging),
    the VERDICT r4 missing-#1 path.  BENCH_DECODE_ENGINE=eager selects the
    legacy While-loop BeamSearchDecoder (per-op dispatches per step) for
    comparison.  No reference decode-throughput figure exists, so
    vs_baseline is reported as 0 and the metric stands on its absolute
    tokens/sec."""
    from paddle_tpu.fluid import layers
    from paddle_tpu.fluid.contrib.decoder import (BeamSearchDecoder,
                                                  InitState,
                                                  JitBeamSearchDecoder,
                                                  StateCell)

    engine = os.environ.get("BENCH_DECODE_ENGINE", "jit")
    decoder_cls = BeamSearchDecoder if engine == "eager" \
        else JitBeamSearchDecoder

    batch = _env_int("decode", "BS", 8)
    rounds = _env_int("decode", "STEPS", 3)
    v, d = 1000, 64
    max_len, beam = 16, 4

    src = layers.data(name="src", shape=[1], dtype="int64")
    h0 = layers.fc(input=layers.embedding(src, size=[v, d]), size=d,
                   act="tanh")
    cell = StateCell(inputs={"x": None},
                     states={"h": InitState(init=h0, need_reorder=True)},
                     out_state="h")

    @cell.state_updater
    def updater(c):
        c.set_state("h", layers.fc(input=[c.get_input("x"),
                                          c.get_state("h")],
                                   size=d, act="tanh"))

    init_ids = layers.data(name="init_ids", shape=[1], dtype="int64",
                           lod_level=2)
    init_scores = layers.data(name="init_scores", shape=[1],
                              dtype="float32", lod_level=2)
    dec = decoder_cls(cell, init_ids, init_scores,
                      target_dict_dim=v, word_dim=d, topk_size=50,
                      sparse_emb=False, max_len=max_len,
                      beam_size=beam, end_id=1)
    dec.decode()
    out_ids, _ = dec()

    place = fluid.TPUPlace() if on_accel else fluid.CPUPlace()
    exe = fluid.Executor(place)
    exe.run(fluid.default_startup_program())
    int8 = os.environ.get("BENCH_INT8", "") in ("1", "true")
    if int8:
        # weight-only int8 under the compiled decode loop: embeddings +
        # projection stream int8 from HBM, dequant fused at the consumer
        from paddle_tpu.fluid.transpiler.int8_transpiler import (
            Int8WeightTranspiler)
        quantized = Int8WeightTranspiler().transpile(
            fluid.default_main_program())
        assert quantized, "int8 transpile quantized no weights"
    rng = np.random.RandomState(0)
    lod2 = [[1] * batch, [1] * batch]
    feed = {"src": rng.randint(2, v, size=(batch, 1)).astype(np.int64),
            "init_ids": fluid.create_lod_tensor(
                np.zeros((batch, 1), np.int64), lod2),
            "init_scores": fluid.create_lod_tensor(
                np.zeros((batch, 1), np.float32), lod2)}
    cc0 = _cache_counters()
    t_c = time.perf_counter()
    (warm,) = exe.run(fluid.default_main_program(), feed=feed,
                      fetch_list=[out_ids], return_numpy=False)
    cold = _cold_info(time.perf_counter() - t_c, cc0, _cache_counters())
    t0 = time.perf_counter()
    n_tokens = 0
    for _ in range(rounds):
        (ids,) = exe.run(fluid.default_main_program(), feed=feed,
                         fetch_list=[out_ids], return_numpy=False)
        n_tokens += int(np.asarray(ids).size)
    dt = time.perf_counter() - t0
    return {"metric": f"beam_decode_b{batch}_beam{beam}_len{max_len}"
                      f"_{engine}{'_int8' if int8 else ''}_{platform}",
            "value": round(n_tokens / dt, 2), "unit": "tokens/sec/chip",
            "vs_baseline": 0.0, "device": device_info(),
            **kernel_config(), **cold,
            "note": "no published reference decode throughput; absolute "
                    "generation rate ("
                    + ("one compiled while_loop program"
                       if engine != "eager" else "eager-island execution")
                    + ")"}


def _bench_v2_image(model, fluid, platform, on_accel, ref_hw):
    """AlexNet/GoogleNet via their legacy-DSL configs (benchmark/v2/) —
    the configs themselves are the reference's; baselines are the
    published bs=64 CPU training rates (IntelOptimizedPaddle.md)."""
    from paddle_tpu.trainer_config_helpers import (
        build_settings_optimizer, get_outputs, set_config_args)

    batch = _env_int(model, "BS", 64 if on_accel else 4)
    steps = _env_int(model, "STEPS", 10 if on_accel else 3)
    # CPU fallback geometries keep every pool non-degenerate
    hw = ref_hw if on_accel else (67 if model == "alexnet" else 64)
    class_dim = 1000 if on_accel else 10
    set_config_args(height=hw, width=hw, num_class=class_dim,
                    batch_size=batch, is_infer=False)
    path = os.path.join(REPO, "benchmark", "v2", f"{model}.py")
    with open(path) as f:
        exec(compile(f.read(), path, "exec"), {"__name__": "config"})
    (loss,) = get_outputs()
    build_settings_optimizer().minimize(loss)

    rng = np.random.RandomState(0)
    feed = {"data": rng.normal(size=(batch, 3 * hw * hw)).astype(np.float32),
            "label": rng.randint(0, class_dim,
                                 size=(batch, 1)).astype(np.int64)}
    dt, steps, _, cold = timed_run(fluid, on_accel, loss, feed, steps)
    ips = batch * steps / dt
    return result_line(f"{model}_{hw}px_bs{batch}_train_{platform}",
                       ips, "images/sec/chip", model,
                       amp=fluid.amp.compute_dtype() or "off", **cold)


def bench_rnn(fluid, platform, on_accel):
    """IMDB-style LSTM training via the legacy-DSL rnn config
    (benchmark/v2/rnn.py == the reference benchmark/paddle/rnn/rnn.py
    structure).  Fixed-length sequences (the config's pad_seq=True
    regime: one compiled shape).  Baseline: LSTM 2-layer h=512 bs=64 at
    184 ms/batch -> 347.8 sequences/sec."""
    from paddle_tpu.trainer_config_helpers import (
        build_settings_optimizer, get_outputs, set_config_args)

    batch = _env_int("rnn", "BS", 64 if on_accel else 8)
    steps = _env_int("rnn", "STEPS", 10 if on_accel else 3)
    hidden = 512 if on_accel else 32
    seqlen = 100 if on_accel else 10
    vocab = 30000 if on_accel else 100
    set_config_args(vocab_size=vocab, hidden_size=hidden, lstm_num=2,
                    emb_size=128 if on_accel else 16, batch_size=batch)
    path = os.path.join(REPO, "benchmark", "v2", "rnn.py")
    with open(path) as f:
        exec(compile(f.read(), path, "exec"), {"__name__": "config"})
    (loss,) = get_outputs()
    build_settings_optimizer().minimize(loss)

    rng = np.random.RandomState(0)
    rows = rng.randint(1, vocab, size=(batch * seqlen, 1)).astype(np.int64)
    feed = {"data": (rows, [[seqlen] * batch]),
            "label": rng.randint(0, 2, size=(batch, 1)).astype(np.int64)}
    dt, steps, _, cold = timed_run(fluid, on_accel, loss, feed, steps)
    sps = batch * steps / dt
    return result_line(f"rnn_lstm2_h{hidden}_len{seqlen}_bs{batch}"
                       f"_train_{platform}", sps, "sequences/sec/chip",
                       "rnn", amp=fluid.amp.compute_dtype() or "off",
                       **cold)


def bench_alexnet(fluid, platform, on_accel):
    return _bench_v2_image("alexnet", fluid, platform, on_accel, 227)


def bench_googlenet(fluid, platform, on_accel):
    return _bench_v2_image("googlenet", fluid, platform, on_accel, 224)


BENCHES = {"resnet": bench_resnet, "transformer": bench_transformer,
           "mnist": bench_mnist, "resnet_infer": bench_resnet_infer,
           "decode": bench_decode, "vgg": bench_vgg,
           "alexnet": bench_alexnet, "googlenet": bench_googlenet,
           "rnn": bench_rnn}


def _run_one(model, fluid, platform, on_accel):
    """Run one bench in a fresh default program; returns its result dict.
    A failing model raises: the bench exits non-zero with the traceback."""
    import paddle_tpu.fluid.framework as fw

    with fw.program_guard(fw.Program(), fw.Program()):
        with fluid.scope_guard(fluid.Scope()):
            return BENCHES[model](fluid, platform, on_accel)


def main():
    model = os.environ.get("BENCH_MODEL", "")
    for i, a in enumerate(sys.argv):
        if a == "--model" and i + 1 < len(sys.argv):
            model = sys.argv[i + 1]
        elif a.startswith("--model="):
            model = a.split("=", 1)[1]
    if model and model not in BENCHES:
        sys.exit(f"BENCH_MODEL must be one of {sorted(BENCHES)}; "
                 f"got {model!r}")

    import jax

    platform = jax.devices()[0].platform
    on_accel = platform != "cpu"
    if not on_accel and jax.config.jax_platforms != "cpu":
        sys.exit("bench.py: jax found no accelerator (devices: "
                 f"{jax.devices()}) and the CPU was not asked for; set "
                 "JAX_PLATFORMS=cpu to run the small CPU sizes on purpose")

    import paddle_tpu.fluid as fluid

    if on_accel and os.environ.get("BENCH_AMP", "1") != "0":
        # keep-low activations: contraction outputs stay bf16 so
        # inter-layer HBM traffic halves (norm statistics and the loss
        # boundary remain fp32 — fluid/amp.py).  Opt out via
        # BENCH_AMP_KEEP=0 (bench knob) or PADDLE_TPU_AMP_KEEP=0 (the
        # library-wide knob, honored when the bench one is unset).
        keep_env = os.environ.get("BENCH_AMP_KEEP",
                                  os.environ.get("PADDLE_TPU_AMP_KEEP", "1"))
        keep = keep_env.strip().lower() not in ("0", "false")
        fluid.amp.enable("bfloat16", keep_activations=keep)

    if model:  # single-model mode
        print(json.dumps(_run_one(model, fluid, platform, on_accel)))
        return 0

    # Default: BOTH driver metrics (BASELINE.json: ResNet-50 images/sec/chip
    # AND Transformer-base tokens/sec/chip), one line each, then a combined
    # final line so a last-line-only parser still sees both numbers.
    res = _run_one("resnet", fluid, platform, on_accel)
    print(json.dumps(res), flush=True)
    trf = _run_one("transformer", fluid, platform, on_accel)
    print(json.dumps(trf), flush=True)

    combined = dict(res)
    combined["transformer_metric"] = trf["metric"]
    combined["transformer_tokens_per_sec_chip"] = trf["value"]
    combined["transformer_vs_baseline"] = trf["vs_baseline"]
    print(json.dumps(combined))
    return 0


if __name__ == "__main__":
    sys.exit(main())
