"""The benchmark's arithmetic against hand-computed cases: stamps to
metrics, needed FLOPs, kernel families, lowered-call parsing and the trace
reduction (on small traces written as text protos, and on a recording
from the v5e kept under ``chipbench/testdata/``)."""

import glob
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")

from chipbench import flops, hlo, peaks, stats  # noqa: E402
from chipbench.plugins import load  # noqa: E402
from chipbench import trace_reduce as tr  # noqa: E402


# -- stamps to metrics -------------------------------------------------

STALL = [0.0, 0.1, 0.2, 0.3, 1.3, 1.4]      # five steps, one 1 s stall


def test_throughput_counts_the_stall():
    assert stats.throughput(STALL, 100) == pytest.approx(5 * 100 / 1.4)


def test_step_ms_p95_sees_the_stall():
    # intervals .1 .1 .1 1.0 .1 -> rank 3.8 of the sorted five
    assert stats.step_ms_p95(STALL) == pytest.approx(820.0)
    assert stats.step_ms_median(STALL) == pytest.approx(100.0)


def test_window_needs_two_stamps():
    with pytest.raises(ValueError):
        stats.throughput([1.0], 1)


@pytest.mark.parametrize("name,run,want", [
    ("tokens_per_s_per_chip",
     {"stamps": STALL, "units_per_step": 400, "chips": 4}, 5 * 100 / 1.4),
    ("images_per_s_per_chip",
     {"stamps": STALL, "units_per_step": 100, "chips": 1}, 5 * 100 / 1.4),
    ("step_ms_p95", {"stamps": STALL}, 820.0),
    ("peak_hbm_gib", {"memory_peak_bytes": 3 * 2 ** 30}, 3.0),
    ("setup_s", {"setup_s": 41.5}, 41.5),
])
def test_end_to_end_metric_files(name, run, want):
    assert load("metrics", name).value(run) == pytest.approx(want)


# -- needed FLOPs ------------------------------------------------------

def test_transformer_base_encoder_layer_by_hand():
    n, d, f, h = 256, 512, 2048, 8
    proj = 4 * flops.mul_flops([1, n, d], [d, d], x_num_col_dims=2)
    attn = flops.attention_flops([1, h, n, d // h], [1, h, n, d // h])
    ffn = flops.mul_flops([1, n, d], [d, f], 2) \
        + flops.mul_flops([1, n, f], [f, d], 2)
    assert proj == 4 * 2 * 256 * 512 * 512 == 536_870_912
    assert attn == 2 * 2 * 8 * 256 * 256 * 64 == 134_217_728
    assert ffn == 2 * 2 * 256 * 512 * 2048 == 1_073_741_824
    causal = flops.attention_flops([1, h, n, 64], [1, h, n, 64], causal=True)
    assert causal == attn // 2
    assert flops.matmul_flops([1, h, n, 64], [1, h, n, 64],
                              transpose_y=True) == attn // 2


def test_resnet50_bottleneck_by_hand():
    # conv2_x block at 56x56: 1x1 256->64, 3x3 64->64, 1x1 64->256
    a = flops.conv2d_flops([1, 64, 56, 56], [64, 256, 1, 1])
    b = flops.conv2d_flops([1, 64, 56, 56], [64, 64, 3, 3])
    c = flops.conv2d_flops([1, 256, 56, 56], [256, 64, 1, 1])
    assert a == 2 * 64 * 56 * 56 * 256 == 102_760_448
    assert b == 2 * 64 * 56 * 56 * 64 * 9 == 231_211_008
    assert c == a


def test_resnet50_program_needs_twice_3_86_gmacs():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import resnet

    resnet.build(class_dim=1000, depth=50, image_shape=(3, 224, 224))
    fwd = flops.forward_flops(fluid.default_main_program())
    # He et al. quote 3.8e9 multiply-adds for the 50-layer net
    assert fwd == pytest.approx(2 * 3.86e9, rel=0.01)
    assert flops.train_flops_per_sample(fluid.default_main_program()) \
        == 3 * fwd


@pytest.mark.parametrize("config", ["transformer_base_wmt",
                                    "resnet50_imagenet"])
def test_the_walk_knows_every_op_type_of_an_accepted_program(config):
    import json

    import paddle_tpu.fluid as fluid

    sizes = json.load(open(os.path.join(BENCH, "configs", config,
                                        "config.json")))
    build = load(os.path.join("configs", config), "build")
    for deterministic in (False, True):
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()), \
                fluid.unique_name.guard():
            build.build(fluid, sizes, deterministic=deterministic)
        assert flops.uncounted_op_types(main) == []
        assert flops.train_flops_per_sample(main) > 0


def test_the_walk_names_the_op_types_it_cannot_count():
    import paddle_tpu.fluid as fluid

    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()), \
            fluid.unique_name.guard():
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.sigmoid(fluid.layers.fc(input=x, size=8))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(
            fluid.layers.mean(h))
    assert flops.uncounted_op_types(main) == ["sgd", "sigmoid",
                                              "sigmoid_grad"]


# -- kernel families and the lowered calls -----------------------------

QKV = ((32, 256, 64), "bf16")
LINE = ('    %5:2 = stablehlo.custom_call @tpu_custom_call(%1, %2, %3, %4) '
        '{backend_config = "{\\22custom_call_config\\22: {\\22body\\22: '
        '\\22TUzv\\22}}", kernel_name = "_flash_kernel", '
        'operand_layouts = [dense<[2, 1, 0]> : tensor<3xindex>]} : '
        '(tensor<32x256x64xbf16>, tensor<32x256x64xbf16>, '
        'tensor<32x256x64xbf16>, tensor<4x1x256xf32>) -> '
        '(tensor<32x256x64xbf16>, tensor<32x256x1xf32>)')


def test_custom_calls_are_read_with_their_declared_shapes():
    text = "module {\n" + LINE + "\n" + LINE.replace(
        "_flash_kernel", "_adam_kernel") + "\n}"
    calls = hlo.custom_calls(text)
    assert [c.kernel for c in calls] == ["_flash_kernel", "_adam_kernel"]
    assert calls[0].operands == (QKV, QKV, QKV, ((4, 1, 256), "f32"))
    assert calls[0].results == (QKV, ((32, 256, 1), "f32"))
    assert hlo.declared_bytes(calls[0]) == \
        4 * 32 * 256 * 64 * 2 + 4 * 256 * 4 + 32 * 256 * 4


@pytest.mark.parametrize("family,n_in,matmuls", [
    ("flash_fwd", 3, 2), ("flash_dq", 6, 3), ("flash_dkv", 6, 4)])
def test_flash_family_flops(family, n_in, matmuls):
    mod = load("kernels", family)
    bias = ((4, 1, 256), "f32")
    full = 2 * matmuls * 32 * 256 * 256 * 64
    assert mod.flops((QKV,) * n_in + (bias,), (QKV,)) == full
    # no bias operand and tq == tk: taken as causal, half
    assert mod.flops((QKV,) * n_in, (QKV,)) == full / 2


@pytest.mark.parametrize("family", ["adam", "momentum", "xent_fwd",
                                    "xent_bwd"])
def test_sweep_families_are_bandwidth_bound(family):
    mod = load("kernels", family)
    p = ((512, 2048), "f32")
    assert mod.flops((p,) * 4, (p,) * 3) == 0.0
    call = hlo.CustomCall(mod.KERNEL, (p,) * 4, (p,) * 3)
    pk = peaks.peaks_for("TPU v5 lite")
    assert peaks.least_seconds(0.0, hlo.declared_bytes(call), pk) == \
        pytest.approx(7 * 512 * 2048 * 4 / 819e9)


def test_every_family_file_names_its_kernel():
    files = glob.glob(os.path.join(BENCH, "kernels", "*.py"))
    assert len(files) >= 7
    names = [load("kernels", os.path.basename(f)[:-3]).KERNEL for f in files]
    assert len(set(names)) == len(names)


def test_unknown_device_has_no_peak():
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


# -- the trace reduction -----------------------------------------------

def make_trace(device_events, host_events=(), devices=1):
    """A ProfileData from (name, start_ns, dur_ns) lists, via the text
    form of an XSpace."""
    from jax.profiler import ProfileData

    def plane(pid, name, line, events):
        names = sorted({e[0] for e in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * 1000)} "
            f"duration_ps: {int(d * 1000)} }}\n" for n, s, d in events)
        meta = "".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
            for n, i in ids.items())
        return (f'planes {{ id: {pid} name: "{name}"\n lines {{ id: 1 '
                f'name: "{line}" timestamp_ns: 0\n{evs} }}\n{meta} }}\n')

    text = "".join(plane(i + 1, f"/device:TPU:{i}", "XLA Ops", device_events)
                   for i in range(devices))
    text += plane(99, "/host:CPU", "main", list(host_events))
    return tr.from_profile(ProfileData.from_text_proto(text))


def test_busy_union_idle_and_self_time():
    t = make_trace([("while.1", 0, 10), ("fusion.2", 2, 2),
                    ("fusion.3", 12, 3)])
    evs = t.devices["/device:TPU:0"]
    busy, window = tr.busy_and_window(evs)
    assert busy == pytest.approx(13e-9) and window == pytest.approx(15e-9)
    assert tr.self_times(evs) == [8.0, 2.0, 3.0]
    s = tr.device_summary(t)
    assert s["idle_pct_worst"] == pytest.approx(100 * 2 / 15)
    assert s["busy_s"] == pytest.approx(13e-9)


def two_steps():
    """Two traced steps: per step two `_adam_kernel` events of 4 ns and one
    `_flash_kernel` event of 10 ns."""
    evs = []
    for step in (0, 100):
        evs += [("_adam_kernel.1", step, 4), ("_adam_kernel.2", step + 5, 4),
                ("_flash_kernel.7", step + 10, 10),
                ("fusion.9", step + 20, 30)]
    return evs


PEAKS = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}   # 1 B or 1 FLOP / ns
ADAM = [tr.Call("adam", "_adam_kernel", 0.0, 1)] * 2      # least 1 ns a call
FLASH = [tr.Call("flash_fwd", "_flash_kernel", 5.0, 2)]   # least 5 ns (FLOPs)


def describe(ev):
    """(signature, HBM bytes) of the synthetic Pallas events."""
    kernel = ev.name.split(".")[0]
    return (kernel, 10) if kernel.startswith("_") else None


def test_roofline_is_least_time_of_matched_events_over_their_time():
    evs = make_trace(two_steps()).devices["/device:TPU:0"]
    r = tr.kernel_roofline(evs, ADAM + FLASH, 2, describe, PEAKS)
    # (4*1 + 2*5) ns of least time over (16 + 20) ns of event time
    assert r["pct"] == pytest.approx(100 * 14 / 36)
    assert r["families"]["adam"]["pct"] == pytest.approx(25.0)
    assert r["families"]["flash_fwd"]["pct"] == pytest.approx(50.0)


def test_roofline_counts_only_bytes_that_cross_hbm():
    evs = make_trace(two_steps()).devices["/device:TPU:0"]
    big = [tr.Call("adam", "_adam_kernel", 0.0, 100)] * 2
    # the calls declare 100 B each, their events show 10 B outside on-chip
    # memory: the least time is that of the 10
    r = tr.kernel_roofline(evs, big, 2, describe, PEAKS)
    assert r["pct"] == pytest.approx(100 * 40 / 16)


def test_roofline_gives_both_readings_of_a_call_with_operands_on_chip():
    """The same momentum sweep as the profiler names it, once with every
    operand in HBM and once with param and velocity kept on chip (S(1))."""
    def event(on_chip):
        s = "S(1)" if on_chip else ""
        return ('%momentum.1 = (f32[256,128]{1,0:T(8,128)' + s + '}, '
                'f32[256,128]{1,0:T(8,128)' + s + '}) custom-call('
                'f32[256,128]{1,0:T(8,128)' + s + '} %p, '
                'f32[256,128]{1,0:T(8,128)} %g, '
                'f32[256,128]{1,0:T(8,128)' + s + '} %v), '
                'custom_call_target="tpu_custom_call", operand_layout')
    one = 256 * 128 * 4
    sig, hbm = hlo.event_call(event(False))
    assert (sig, hbm) == (hlo.event_call(event(True))[0], 5 * one)
    assert hlo.event_call(event(True))[1] == one      # the gradient alone
    call = [tr.Call("momentum", sig, 0.0, 5 * one)]
    peaks = {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9}
    took_ns = 4 * one                                  # 4 B/ns declared
    for on_chip, pct in ((False, 125.0), (True, 25.0)):
        evs = [tr.Event(event(on_chip), 0.0, float(took_ns))]
        r = tr.kernel_roofline(evs, call, 1,
                               lambda e: hlo.event_call(e.name), peaks)
        # the metric counts what crossed HBM; the declared reading is the
        # same in both and is what passes 100% when operands sit on chip
        assert r["pct"] == pytest.approx(pct)
        assert r["declared_pct"] == pytest.approx(125.0)
        assert r["families"]["momentum"]["declared_pct"] == \
            pytest.approx(125.0)


def test_roofline_is_withheld_not_inflated_when_events_are_missing():
    evs = [e for e in two_steps() if e != ("_adam_kernel.2", 105, 4)]
    evs = make_trace(evs).devices["/device:TPU:0"]
    # 3 adam events for 4 calls: the family's calls cover events that are
    # not there, so it is left out; setting all four calls' bytes against
    # the 12 ns that were matched would have inflated the share
    r = tr.kernel_roofline(evs, ADAM, 2, describe, PEAKS)
    assert r["pct"] is None
    assert r["families"]["adam"] == {"events": 3, "calls": 4,
                                     "counted": False}
    mixed = tr.kernel_roofline(evs, ADAM + FLASH, 2, describe, PEAKS)
    assert mixed["pct"] == pytest.approx(100 * 10 / 20)   # flash alone
    assert load("layer_metrics", "pallas_roofline_pct").value(
        {"roofline": r}) is None


def test_roofline_leaves_out_a_signature_two_families_share():
    evs = make_trace(two_steps()).devices["/device:TPU:0"]
    twin = [tr.Call("momentum", "_adam_kernel", 0.0, 1)]
    r = tr.kernel_roofline(evs, ADAM + twin + FLASH, 2, describe, PEAKS)
    assert r["families"]["adam"]["counted"] is False
    assert r["families"]["momentum"]["counted"] is False
    assert r["pct"] == pytest.approx(50.0)


def test_exposed_collective_time():
    t = make_trace([("fusion.1", 0, 5), ("all-reduce-start.1", 5, 4),
                    ("fusion.2", 7, 5), ("all-gather.3", 20, 2)])
    exposed, total = tr.exposed_collective_s(t.devices["/device:TPU:0"])
    assert total == pytest.approx(6e-9)
    assert exposed == pytest.approx(4e-9)       # [5, 7) and [20, 22)


def test_idle_gaps_are_named_by_the_open_host_span():
    t = make_trace([("fusion.1", 0, 10), ("fusion.2", 30, 10),
                    ("fusion.3", 45, 5), ("fusion.4", 60, 1)],
                   host_events=[("bench.dispatch", 8, 30),
                                ("bench.fetch", 40, 8), ("other", 0, 99)])
    gaps = tr.idle_gaps(t.devices["/device:TPU:0"], t.host_spans)
    assert gaps == [["bench.dispatch", pytest.approx(20e-9)],
                    ["host:none", pytest.approx(10e-9)],
                    ["bench.fetch", pytest.approx(5e-9)]]


def test_time_by_label_uses_self_time():
    evs = make_trace([("while.1", 0, 10), ("fusion.2", 2, 2)]
                     ).devices["/device:TPU:0"]
    by = tr.time_by_label(evs, lambda e: e.name.split(".")[0])
    assert by == {"while": pytest.approx(8e-9), "fusion": pytest.approx(2e-9)}


def test_op_time_share_sums_the_labels_that_start_so():
    from chipbench import op_time

    run = {"time_by_label": {"op:mul": 2.0, "op:mul_grad": 4.0,
                             "kernel:adam": 1.0, "op:scale": 1.0},
           "labelled_busy_s": 10.0}
    assert op_time.share(run, ("op:mul",)) == pytest.approx(0.6)
    assert op_time.share(run, ("kernel:", "op:scale")) == pytest.approx(0.2)
    assert op_time.share(run, ("op:moe",)) is None
    assert op_time.share({"steps": 3}, ("op:mul",)) is None


FAMILIES = ["flash_fwd", "flash_dq", "flash_dkv", "xent_fwd", "xent_bwd",
            "adam"]


@pytest.mark.parametrize("family", FAMILIES)
def test_family_roofline_metric_reads_its_own_family(family):
    metric = load("layer_metrics", family + "_roofline")
    roof = tr.kernel_roofline(
        [tr.Event("call." + f, 10.0 * i, 4.0 + i)
         for i, f in enumerate(FAMILIES)],
        [tr.Call(f, "sig." + f, 0.0, 819) for f in FAMILIES], 1,
        lambda e: ("sig." + e.name.split(".")[1], 819),
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    i = FAMILIES.index(family)
    # 819 bytes at 819 GB/s is 1 ns, of an event that took 4 + i ns
    assert metric.value({"roofline": roof}) == pytest.approx(100 / (4.0 + i))
    assert roof["families"][family]["counted"]
    # two steps traced, one event found: withheld, and so left out
    short = tr.kernel_roofline(
        [tr.Event("call." + family, 0.0, 4.0)],
        [tr.Call(family, "sig." + family, 0.0, 819)], 2,
        lambda e: ("sig." + family, 819),
        {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert metric.value({"roofline": short}) is None
    assert metric.value({"roofline": None}) is None
    assert metric.value({"steps": 3}) is None
    # a step that calls no kernel of the family (the ResNet cell)
    other = {"families": {"momentum": {"pct": 2.6, "counted": True}}}
    assert metric.value({"roofline": other}) is None


# -- a recording from the v5e ------------------------------------------
# 3.5 ms around the boundary of two Transformer-base steps (PR 26's chip
# run), cut from the full trace with the xplane proto; the expectations
# below were computed from the proto itself, not with trace_reduce.

RECORDED = os.path.join(BENCH, "testdata",
                        "transformer_step_boundary.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return tr.read(RECORDED)


def test_recorded_trace_planes_events_and_spans(recorded):
    assert list(recorded.devices) == ["/device:TPU:0"]
    assert len(recorded.devices["/device:TPU:0"]) == 606
    assert [n for n, _, _ in recorded.host_spans] == ["bench.fetch"]


def test_recorded_trace_busy_and_window(recorded):
    busy, window = tr.busy_and_window(recorded.devices["/device:TPU:0"])
    assert busy == pytest.approx(2909911868e-12, rel=1e-5)
    assert window == pytest.approx(3215370000e-12, rel=1e-5)
    s = tr.device_summary(recorded)
    assert s["idle_pct_worst"] == pytest.approx(
        100 * (1 - 2909911868 / 3215370000), rel=1e-4)


def test_recorded_trace_step_boundary_gap_is_under_the_fetch(recorded):
    gaps = tr.idle_gaps(recorded.devices["/device:TPU:0"],
                        recorded.host_spans)
    assert gaps[0][0] == "bench.fetch"
    assert gaps[0][1] >= 236032344e-12


def test_recorded_trace_pallas_events_by_signature(recorded):
    evs = recorded.devices["/device:TPU:0"]
    described = [hlo.event_call(e.name) for e in evs]
    pallas = [(e, d) for e, d in zip(evs, described) if d is not None]
    assert len(pallas) == 64
    # every one is an Adam sweep: seven float32 tensors of one shape and
    # the learning rate in, three out
    for _, (sig, hbm) in pallas:
        outs, ins = sig.split("<-")
        assert outs.count("f32[") == 3 and ins.count("f32[") == 5
    calls = [tr.Call("adam", sig, 0.0, 10 ** 12) for _, (sig, _) in pallas]
    pk = peaks.peaks_for("TPU v5 lite")
    r = tr.kernel_roofline(evs, calls, 1, lambda e: hlo.event_call(e.name),
                           pk)
    want = sum(h for _, (_, h) in pallas) / 819e9 \
        / (sum(e.dur_ns for e, _ in pallas) / 1e9)
    assert r["pct"] == pytest.approx(100 * want)
    assert 20 < r["pct"] < 100
    # one call fewer than events: withheld
    r = tr.kernel_roofline(evs, calls[1:], 1,
                           lambda e: hlo.event_call(e.name), pk)
    assert r["pct"] is None


def test_event_call_reads_shapes_and_leaves_out_on_chip_operands():
    text = ('%adam.1 = (f32[512,512]{1,0:T(8,128)S(1)}, f32[512,512]{1,0}) '
            'custom-call(f32[512,512]{1,0:T(8,128)} %p, f32[1]{0:T(128)S(6)}'
            ' %lr), custom_call_target="tpu_custom_call", operand_layout')
    sig, hbm = hlo.event_call(text)
    assert sig == "f32[512,512],f32[512,512]<-f32[512,512],f32[1]"
    assert hbm == 2 * 512 * 512 * 4
    assert hlo.event_call("%fusion.1 = f32[2]{0} fusion(f32[2]{0} %a)") \
        is None
    call = hlo.CustomCall("_adam_kernel", (((512, 512), "f32"), ((1,), "f32")),
                          (((512, 512), "f32"),) * 2)
    assert hlo.signature(call) == sig
    assert hlo.instruction_name(text) == "adam.1"


def test_instruction_scopes_join_events_to_fluid_ops():
    text = ('  %fusion.7 = f32[2]{0} fusion(f32[2]{0} %a), kind=kLoop, '
            'metadata={op_name="jit(fn)/dropout/mul" stack_frame_id=3}\n'
            '  ROOT %tuple.1 = (f32[2]{0}) tuple(%fusion.7)\n')
    assert hlo.instruction_scopes(text) == {"fusion.7": "dropout"}
    assert hlo.scope_of("jit(fn)/mut_state['conv2d_45.w_0']") == "mut_state"
    assert hlo.scope_of("jit(fn)/jit(inner)/adam/pallas_call") == "adam"
