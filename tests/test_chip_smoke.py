"""chip_smoke.py rehearsed on the CPU, and the no-hidden-fallback contracts.

``chip_smoke.py --rehearse`` runs the smoke's own phases at ``tiny_config()``
with the platform assertion skipped; these tests force the kernel gates on
(``PADDLE_TPU_FLASH=1`` / ``PADDLE_TPU_FUSED=1``: interpret mode off-TPU) so
the rehearsal walks the path the chip run takes, and assert the shape of
every printed line.  The steering is here, in the test; the script itself
sets no switch.  Beside it: what must FAIL when there is no chip, and where
the compile cache goes.
"""

import json
import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (repo-root module)

DEVICE_KEYS = {"platform", "kind", "count"}
KERNEL_KEYS = {"phase", "kernel_family", "gate", "compiled",
               "tpu_custom_call", "ran", "dispatches", "interpret", "ok"}


def _rehearse(argv, monkeypatch, capsys):
    import paddle_tpu.fluid as fluid

    monkeypatch.setenv("PADDLE_TPU_FLASH", "1")
    monkeypatch.setenv("PADDLE_TPU_FUSED", "1")
    try:
        rc = chip_smoke.main(["--rehearse"] + argv)
    finally:
        fluid.amp.disable()
    out = capsys.readouterr().out
    assert '"platform": "tpu"' not in out
    lines = [json.loads(l) for l in out.splitlines()]  # every line parses
    assert rc == 0, lines[-1]
    assert lines[-1] == {"ok": True, "device": lines[-1]["device"]}
    assert set(lines[-1]["device"]) == DEVICE_KEYS
    assert lines[-1]["device"]["platform"] == "cpu"
    assert lines[0]["phase"] == "device" and lines[0]["rehearse"] is True
    assert {"jax", "jaxlib", "libtpu", "python", "x64"} <= set(lines[0])
    cache = lines[-2]
    assert cache["phase"] == "compile_cache"
    assert cache["backend_dir"] == (
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or os.path.join(REPO, ".cache", "jax"))
    assert {"backend_hits", "backend_misses", "store_hits",
            "store_misses"} <= set(cache)
    return lines[1:-2]


def test_rehearse_one_chip_phases(monkeypatch, capsys):
    lines = _rehearse([], monkeypatch, capsys)
    train = lines[0]
    assert train["phase"] == "train" and train["ok"] is True
    assert train["model"] == "transformer_tiny"
    assert train["warmup_steps"] == 2 and train["timed_steps"] == 2
    assert len(train["losses"]) == 4
    assert train["loss_last"] < train["loss_first"]
    assert train["setup_first_call_seconds"] > 0
    assert train["smoke_reading_seconds_per_step"] > 0

    kernels = [l for l in lines if "kernel_family" in l]
    assert [k["kernel_family"] for k in kernels] == [
        "flash_fwd", "flash_bwd", "softmax_xent_fwd", "softmax_xent_bwd",
        "fused_adam", "paged_attention"]
    for k in kernels:
        assert set(k) == KERNEL_KEYS
        # interpret mode: dispatched, but no Mosaic call in the program
        assert k["ran"] and k["interpret"] and not k["compiled"], k

    (wide,) = [l for l in lines if "element_types_64bit" in l]
    assert wide["element_types_64bit"] == sum(wide["by_type"].values()) > 0
    assert wide["tpu_custom_call_total"] == 0

    (serve,) = [l for l in lines if l.get("phase") == "serve"
                and "requests" in l]
    assert serve["ok"] is True and "TOY" in serve["model"]
    assert serve["answered"] == serve["asked"]
    assert serve["logit_rows_compared"] > 0
    assert serve["max_abs_logit_diff_paged_vs_dense"] <= serve["atol"]
    assert serve["tokens_agree"] and serve["pages_returned"]


def test_rehearse_four_chip_phase_runs_only_the_mesh(monkeypatch, capsys):
    (mesh,) = _rehearse(["--chips", "4"], monkeypatch, capsys)
    assert mesh["phase"] == "mesh" and mesh["ok"] is True
    assert mesh["mesh"] == "dp2xtp2"
    assert len(mesh["losses_single_device"]) == 2 == len(
        mesh["losses_dp2xtp2"])
    assert mesh["max_rel_loss_diff"] <= mesh["rtol"]
    assert len(set(mesh["mesh_devices"])) == 4
    assert mesh["arrays_checked"] == mesh["arrays_on_four_devices"] > 0
    assert mesh["tp_sharded_params"] > 0
    assert mesh["batch_shard_shape"]["src_word"][0] * 2 == mesh["batch"]
    assert mesh["fused_dispatches"]["ops.fused.softmax_xent"] > 0


def test_smoke_fails_on_the_cpu_without_rehearse(capsys):
    rc = chip_smoke.main([])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert rc != 0
    assert lines[-1]["ok"] is False and "TPU" in lines[-1]["error"]
    assert not any(l.get("ok") is True for l in lines)


@pytest.fixture
def not_pinned_to_cpu():
    """jax sees only the CPU, but nobody asked for it."""
    old = jax.config.jax_platforms
    jax.config.update("jax_platforms", None)
    yield
    jax.config.update("jax_platforms", old)


def test_tpu_place_raises_without_a_tpu(not_pinned_to_cpu):
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core

    with pytest.raises(RuntimeError, match=r"needs a tpu device.*'cpu'"):
        core.get_jax_device(fluid.TPUPlace())
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    y = fluid.layers.fc(x, 2)
    exe = fluid.Executor(fluid.TPUPlace())
    with pytest.raises(RuntimeError, match="needs a tpu device"):
        exe.run(fluid.default_startup_program())
        exe.run(feed={"x": np.zeros((1, 4), np.float32)}, fetch_list=[y])
    # the CPU place itself stays available
    assert core.get_jax_device(fluid.CPUPlace()).platform == "cpu"


def test_tpu_place_resolves_when_pinned_to_cpu():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import core

    assert jax.config.jax_platforms == "cpu"
    assert core.get_jax_device(fluid.TPUPlace(3)).id == 3


@pytest.mark.parametrize("env_dir", [None, "/some/dir"])
def test_backend_cache_dir_is_decided_in_one_place(env_dir, monkeypatch,
                                                   tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the store leaves jax's setting
    alone (jax read the variable itself at start-up); without it the
    directory is the fixed in-checkout path, on and off again."""
    from paddle_tpu import compile_cache

    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    compile_cache.configure(str(tmp_path))
    fixed = os.path.join(REPO, ".cache", "jax")
    assert compile_cache.backend_cache_dir() == (env_dir or fixed)
    assert jax.config.jax_compilation_cache_dir == (before if env_dir
                                                    else fixed)
    assert not os.path.exists(tmp_path / "xla")
    compile_cache.reset()
    assert jax.config.jax_compilation_cache_dir == (before if env_dir
                                                    else None)
