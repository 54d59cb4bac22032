"""Every Pallas kernel compiled for a TPU v5e chip at full width.

No chip is needed: the TPU compiler is installed, and it compiles for
a chip that is described (`get_topology_desc`), not attached. Each
case is a kernel call the serving path makes for TinyLlama-1.1B
(grouped_matmul: qwen2-moe-a2.7b's experts), at the shapes
chip_smoke.py runs on the chip (repro.kernels.full_width). A refusal
here is one the chip would give.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers all import
this file.
"""
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.full_width import serving_kernel_cases

BATCH, CACHE_LEN = 4, 4112      # chip_smoke.py: 4 slots, 4096 + 16 tokens
CASES = sorted(serving_kernel_cases(
    get_config("tinyllama-1.1b"), get_config("qwen2-moe-a2.7b"),
    batch=BATCH, cache_len=CACHE_LEN))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topology = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topology
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", CASES)
def test_kernel_compiles_for_v5e(name, one_chip):
    case = serving_kernel_cases(
        get_config("tinyllama-1.1b"), get_config("qwen2-moe-a2.7b"),
        batch=BATCH, cache_len=CACHE_LEN)[name]
    shapes = jax.eval_shape(case.make_args, jax.random.key(0))
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
            for s in shapes]
    text = jax.jit(case.kernel).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
