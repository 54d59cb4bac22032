"""Jit'd public wrapper around the grouped-matmul kernel.

Backend dispatch rule (mirrors kernels/sparse_ffn/ops.py — the dropless
MoE routed-expert path relies on this):

  * TPU -> Pallas grouped-matmul kernel (segment offsets scalar-
           prefetched, one expert weight slab DMA per grid step);
  * XLA -> ``jax.lax.ragged_dot`` where this JAX exposes it (verified
           dispatch-group invariant: a row's output is bit-identical
           whatever group sizes surround it, which is exactly the
           blockwise-prefill == full-forward equivalence the serving
           stack asserts), masked-einsum oracle otherwise
           (`ref.grouped_matmul_ref`);
  * ``use_kernel=True`` off-TPU forces the interpret-mode kernel
           (tests cross-check it against both XLA paths).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.backend import on_tpu
from repro.kernels.grouped_matmul import kernel as K
from repro.kernels.grouped_matmul import ref as R


def has_ragged_dot() -> bool:
    return hasattr(jax.lax, "ragged_dot")


def _block_m_for(M: int) -> int:
    return min(128, max(8, -(-M // 8) * 8))


def grouped_matmul_op(lhs, rhs, group_sizes, use_kernel: bool | None = None):
    """lhs: [M, D] rows sorted by group; rhs: [E, D, F]; group_sizes:
    [E] int32 (sum <= M; leftover rows — sentinel-routed masked tokens
    and tile padding — come out zero). Returns [M, F] float32."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if use_kernel:
        M = lhs.shape[0]
        bm = _block_m_for(M)
        pad = -M % bm
        if pad:
            lhs = jnp.concatenate(
                [lhs, jnp.zeros((pad, lhs.shape[1]), lhs.dtype)])
        y = K.grouped_matmul(lhs, rhs, group_sizes, block_m=bm,
                             interpret=not on_tpu())
        return y[:M] if pad else y
    if has_ragged_dot():
        return jax.lax.ragged_dot(lhs, rhs,
                                  jnp.asarray(group_sizes, jnp.int32),
                                  preferred_element_type=jnp.float32)
    return R.grouped_matmul_ref(lhs, rhs, group_sizes)
