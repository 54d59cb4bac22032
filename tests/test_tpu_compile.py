"""Every Pallas kernel compiled for a TPU v5e chip at full width.

No chip is needed: the TPU compiler is installed, and it compiles for
a chip that is described (`get_topology_desc`), not attached. Each
case is a kernel call the serving path makes for TinyLlama-1.1B
(grouped_matmul: qwen2-moe-a2.7b's experts), at the shapes
chip_smoke.py runs on the chip (repro.kernels.full_width). A refusal
here is one the chip would give. Also the dense model's serving layer
scans, whose compiled text shows which weight copies XLA makes around
the kernels.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers all import
this file.
"""
import os
import re

import jax
import jax.numpy as jnp
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


@pytest.mark.parametrize("step", ["prefill_blocks", "prefill_blocks_paged",
                                  "decode_step"])
def test_dense_layer_scans_copy_no_ffn_weight_matrix(step, one_chip,
                                                     monkeypatch):
    """The dense model's serving layer scans, compiled for v5e with the
    sparse FFN kernel, hold no instruction whose output is one layer's
    whole W_gate, W_up or W_down: the kernel reads its tiles out of the
    stacked leaves in place, and the dense rows' dots read the layer's
    slice in place. (Handed per-layer slices, the Pallas call makes
    XLA copy all three every layer.) TinyLlama-1.1B widths, 2 layers."""
    from repro.core import fastforward as FF
    from repro.kernels.sparse_ffn import ops
    from repro.models import dense
    from repro.models.registry import get_model
    from repro.nn.param import is_spec
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = get_config("tinyllama-1.1b").with_(n_layers=2)
    plan = FF.resolve_plan(cfg)
    L, N, D, F = cfg.n_layers, cfg.ff.block_size, cfg.d_model, cfg.d_ff
    P, S = 2, 4 * cfg.ff.block_size
    kv_row = (cfg.n_kv_heads, cfg.head_dim)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda s: arg(s.shape, s.dtype),
                          get_model(cfg).specs(cfg), is_leaf=is_spec)
    pages = (L, 2 * S // N + 1, N) + kv_row
    cache_shape = pages if step.endswith("paged") else (L, P, S) + kv_row
    cache = {k: arg(cache_shape, cfg.dtype) for k in ("k", "v")}
    rows = arg((P,))
    if step == "decode_step":
        fn = lambda p, c, t, pos: dense.decode_step(
            p, cfg, t, c, pos, active=jnp.ones((P,), bool), plan=plan)
        args = (rows, rows)
    else:
        tables = (None if step == "prefill_blocks"
                  else jnp.arange(1, 1 + P * S // N,
                                  dtype=jnp.int32).reshape(P, -1))
        fn = lambda p, c, t, pos: dense.prefill_blocks(
            p, cfg, t, c, pos, is_dense=pos == 0, lengths=pos + N,
            active=jnp.ones((P,), bool), page_tables=tables, plan=plan)
        args = (arg((P, N)), rows)
    text = jax.jit(fn).lower(params, cache, *args).compile().as_text()
    assert "sparse_ffn_batched" in text
    whole = re.compile(rf"= bf16\[({D},{F}|{F},{D})\]\{{[^}}]*\}} "
                       r"(?!parameter|get-tuple-element|bitcast|tuple)"
                       r"[\w-]+\(")
    assert [ln for ln in text.splitlines() if whole.search(ln)] == []
