"""Test harness config: force an 8-device virtual CPU mesh BEFORE jax import
(SURVEY.md §4 implication (c): multi-device tests via
xla_force_host_platform_device_count instead of the pserver/port dance)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_cpu_enable_concurrency_optimized_scheduler" not in flags:
    # The concurrency-optimized CPU thunk scheduler can start independent
    # collectives in different orders on different virtual devices, which
    # deadlocks the in-process rendezvous (seen with shard_map ppermute
    # pipelines + GSPMD grad all-reduces in one program).  Program-order
    # scheduling is deterministic; real TPUs sequence collectives anyway.
    flags = (flags
             + " --xla_cpu_enable_concurrency_optimized_scheduler=false")
os.environ["XLA_FLAGS"] = flags

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Pin the live jax config to the CPU as well: the tests run on the virtual
# host devices whatever else the machine has.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test gets fresh default programs, scope and name counters."""
    from paddle_tpu.fluid import framework as _framework

    _framework.fresh_session()
    yield
    # a test that enabled the persistent compile cache must not leak it
    # (or the jax disk-cache dir it points at) into later tests
    from paddle_tpu import compile_cache as _compile_cache

    _compile_cache.reset()
    # same for observability: close any sink/endpoint, clear the process
    # registry and the (step, program) stamp, re-arm env late-binding
    from paddle_tpu import observe as _observe

    _observe.reset()
    # verifier memoization is keyed per program token; clear it so warn
    # dedup in one test can't hide an expected warning in the next
    from paddle_tpu import analysis as _analysis

    _analysis.reset()
