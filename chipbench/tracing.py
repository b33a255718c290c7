"""The traced stretch of a ``--trace 1`` run, and what is read from it.

After the unprofiled window the device is drained, the profiler is started
(host spans on, the Python tracer off), a few steps run through the same
loop, the device is drained again and the profiler stopped: the trace holds
whole steps only.  Nothing read here feeds an end-to-end metric.
"""

from __future__ import annotations

import glob
import os
import shutil


def calls_of_step(stablehlo_text):
    """The step's Pallas calls, each with its family (the kernel file that
    names its kernel), its signature, and the FLOPs and bytes of the shapes
    the call declares.  Also the kernels no family file names."""
    from chipbench import hlo, plugins, trace_reduce

    by_kernel = {m.KERNEL: (n, m)
                 for n, m in plugins.load_all("kernels").items()}
    out, unknown = [], {}
    for c in hlo.custom_calls(stablehlo_text):
        if c.kernel not in by_kernel:
            unknown[c.kernel] = unknown.get(c.kernel, 0) + 1
            continue
        name, mod = by_kernel[c.kernel]
        out.append(trace_reduce.Call(
            name, hlo.signature(c), mod.flops(c.operands, c.results),
            hlo.declared_bytes(c)))
    return out, unknown


def event_label(scopes, calls):
    """``kernel:<family>`` for a Pallas call's event, ``op:<fluid op>`` for
    an event whose instruction carries the executor's named scope,
    ``op:unjoined`` otherwise."""
    from chipbench import hlo

    family = {c.signature: c.family for c in calls}

    def label(ev):
        call = hlo.event_call(ev.name)
        if call is not None:
            return "kernel:" + family.get(call[0], "unknown")
        return "op:" + (scopes.get(hlo.instruction_name(ev.name))
                        or "unjoined")

    return label


def needed_flops(built):
    """FLOPs that one sample's forward and backward need: what the
    configuration states in a ``flops.py`` of its own, else the walk of the
    program (``chipbench/flops.py``), else None: the walk met an op type it
    cannot vouch for, and no reading is better than an undercount."""
    from chipbench import flops, plugins

    own = plugins.load(built.config_dir, "flops")
    if own is not None:
        n = int(own.train_flops_per_sample(built.sizes))
        print(f"flops per sample: {n}, stated by "
              f"chipbench/{built.config_dir}/flops.py", flush=True)
        return n
    unknown = flops.uncounted_op_types(built.main)
    if unknown:
        print("flops per sample: withheld, and mfu_pct with it: the walk "
              f"of chipbench/flops.py cannot count the op types {unknown}; "
              f"chipbench/{built.config_dir}/flops.py may state them",
              flush=True)
        return None
    n = flops.train_flops_per_sample(built.main)
    print(f"flops per sample: {n}, walked from the program", flush=True)
    return n


def traced_stretch(built, dispatch, finish, lower, traffic, trace_dir,
                   keep_dir=None):
    import jax

    from chipbench import loop, trace_reduce

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    steps = int(traffic["trace_steps"])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        traced = loop.run_window(dispatch, finish, steps=steps,
                                 lookahead=traffic["lookahead"])
    finally:
        jax.profiler.stop_trace()
    # the loop completes `steps` intervals after its first stamp and drains
    # its lookahead: that many whole steps ran under the profiler
    steps_traced = traced["attempted"]
    pbs = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True), key=os.path.getmtime)
    if not pbs:
        raise SystemExit(f"the profiler left no .xplane.pb under {trace_dir}")
    if keep_dir:
        os.makedirs(keep_dir, exist_ok=True)
        shutil.copy(pbs[-1], os.path.join(keep_dir, "trace.xplane.pb"))
    trace = trace_reduce.read(pbs[-1])
    stablehlo, optimized, step_memory = lower()
    if keep_dir:
        with open(os.path.join(keep_dir, "step.stablehlo.txt"), "w") as f:
            f.write(stablehlo)
        with open(os.path.join(keep_dir, "step.hlo.txt"), "w") as f:
            f.write(optimized)

    out = {"traced_losses": traced["losses"],
           "steps_traced": steps_traced,
           "optimized_hlo": optimized,
           "step_memory": step_memory,
           "trace_counts": {"device_planes": len(trace.devices),
                            "host_spans": len(trace.host_spans),
                            "steps_traced": steps_traced}}
    flops = needed_flops(built)
    if flops is not None:
        out["flops_per_sample"] = flops
    if built.rehearse:
        # a CPU trace has no device plane: the code path ran, the counts
        # are printed, and no share is made of it
        out["trace"] = None
        calls, unknown = calls_of_step(stablehlo)
        out["trace_counts"].update(custom_calls=len(calls) + len(unknown))
        return out

    from chipbench.peaks import peaks_for

    peaks = peaks_for(jax.devices()[0].device_kind)
    out.update(reduce_trace(trace, stablehlo, optimized, steps_traced, peaks))
    return out


def reduce_trace(trace, stablehlo, optimized, steps_traced, peaks):
    """Everything the per-layer metrics read from one traced stretch."""
    from chipbench import hlo, trace_reduce

    summary = trace_reduce.device_summary(trace)
    if summary["busy_s"] <= 0:
        raise SystemExit("no operation ran on the device in the trace")
    calls, unknown = calls_of_step(stablehlo)
    events = trace.devices[sorted(trace.devices)[0]]
    label = event_label(hlo.instruction_scopes(optimized), calls)
    by_label = trace_reduce.time_by_label(events, label)
    pallas_s = sum(v for k, v in by_label.items() if k.startswith("kernel:"))
    busy0, _ = trace_reduce.busy_and_window(events)
    roof = trace_reduce.kernel_roofline(
        events, calls, steps_traced, lambda e: hlo.event_call(e.name), peaks)
    # both readings of every family: HBM bytes only (the metric), and all
    # the bytes the call declares, on-chip operands included
    print("roofline families (HBM bytes / declared bytes): " + ", ".join(
        f"{n} events {c['events']} calls {c['calls']}"
        + (f" {c['pct']:.2f}% / {c['declared_pct']:.2f}%"
           if c.get("pct") is not None else " withheld")
        for n, c in roof["families"].items())
        + (f"; all counted {roof['pct']:.2f}% / {roof['declared_pct']:.2f}%"
           if roof["pct"] is not None else "; none counted")
        + (f"; kernels with no family file: {unknown}" if unknown else ""),
        flush=True)
    exposed = {n: trace_reduce.exposed_collective_s(evs)
               for n, evs in trace.devices.items()}
    return {
        "trace": summary,
        "pallas_time_pct": 100.0 * pallas_s / busy0,
        # every label of the device that was labelled, for the readers
        # that take one op's share (``chipbench/op_time.py``)
        "time_by_label": by_label,
        "labelled_busy_s": busy0,
        "roofline": roof,
        "collective_exposed_pct": 100.0 * max(
            e / trace_reduce.busy_and_window(trace.devices[n])[1]
            for n, (e, _) in exposed.items()),
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(
                by_label.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": trace_reduce.idle_gaps(events, trace.host_spans)},
    }
