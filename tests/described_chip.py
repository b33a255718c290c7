"""What the tests that compile for a chip without the chip share
(``tests/test_tpu_compile.py``, ``tests/test_tpu_compile_routed_layer.py``):
the described v5e topology, jax's persistent cache off, the types, and the
decoder cells' grouped products.  No test file, so that neither of the two
imports the other."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")
# compile-only use of libtpu: no chip is held, so parallel test workers may
# each load it (its lockfile otherwise lets one process in)
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {exc}")


@pytest.fixture(autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


#: the decoder cells' grouped products: rows of a walk (the slab of tokens x
#: top_k, ``parallel/moe.slab_rows``: a quarter of Trinity's 49,152, an eighth
#: of Kimi-Linear's 16,384, half of Instella's 49,152, a quarter of
#: Qwen3-Next's 81,920, all of the others'),
#: hidden width, expert width,
#: experts held
#: (``chipbench/configs/<cell>/config.json``)
GROUPED_CELLS = {"keye": (65536, 2048, 768, 16),
                 "trinity": (12288, 2048, 1024, 8),
                 "lfm2": (32768, 2048, 1792, 8),
                 "instella": (24576, 2048, 1408, 8),
                 "qwen3_next": (20480, 2048, 512, 16),
                 "mellum2": (65536, 2304, 896, 8),
                 "kimi_linear": (2048, 2304, 1024, 8)}
