"""Pallas TPU kernel skeleton: grouped matmul for dropless MoE dispatch.

The dropless routed-expert path (repro.models.moe) sorts the flattened
(token, expert) assignments by expert id, so expert e owns the
contiguous row segment [offsets[e], offsets[e+1]) of the sorted
activations. This kernel walks grid (M/block_m, E): each step DMAs
expert e's [D, F] weight slab into VMEM, and — only when the row tile
overlaps e's segment (`pl.when` on the scalar-prefetched offsets) —
computes the tile's dot product on the MXU and accumulates the rows
inside the segment into the output block.

Skeleton status: correct (interpret-mode checked against the XLA
oracle + jax.lax.ragged_dot in tests) but not tuned — a production
grouped matmul would precompute a tile->group map so each row tile
visits only the experts it intersects (MegaBlocks-style) instead of
predicating over all E, and would skip the weight DMA for skipped
steps. ROADMAP open item: on-device validation.

VMEM working set per step:
  lhs block  [block_m, D]
  rhs slab   [D, F]        (one expert's weight matrix)
  out        [block_m, F]  (accumulator, revisited across the E axis)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _grouped_matmul_kernel(offs_ref, lhs_ref, rhs_ref, o_ref, *,
                           block_m: int):
    e = pl.program_id(1)

    @pl.when(e == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    start = offs_ref[e]
    end = offs_ref[e + 1]
    m0 = pl.program_id(0) * block_m

    @pl.when((end > m0) & (start < m0 + block_m))
    def _compute():
        x = lhs_ref[...].astype(jnp.float32)
        y = jax.lax.dot(x, rhs_ref[0].astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        rows = m0 + jax.lax.broadcasted_iota(jnp.int32, (block_m, 1), 0)
        keep = (rows >= start) & (rows < end)
        o_ref[...] += jnp.where(keep, y, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def grouped_matmul(lhs, rhs, group_sizes, *, block_m: int = 128,
                   interpret: bool = False):
    """lhs: [M, D] rows sorted by group; rhs: [E, D, F]; group_sizes:
    [E] int32. Returns [M, F] float32; rows past sum(group_sizes) yield
    zeros (matching jax.lax.ragged_dot). M % block_m == 0 — the ops
    wrapper pads ragged row counts up to the tile."""
    M, D = lhs.shape
    E, _, F = rhs.shape
    assert M % block_m == 0, (M, block_m)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(group_sizes).astype(jnp.int32)])
    grid = (M // block_m, E)

    kernel = pl.pallas_call(
        functools.partial(_grouped_matmul_kernel, block_m=block_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_m, D), lambda m, e, offs: (m, 0)),
                pl.BlockSpec((1, D, F), lambda m, e, offs: (e, 0, 0)),
            ],
            out_specs=pl.BlockSpec((block_m, F), lambda m, e, offs: (m, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, F), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )
    return kernel(offs, lhs, rhs)
