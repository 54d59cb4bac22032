"""Jit'd public wrappers around the sparse-FFN Pallas kernels.

Backend dispatch rule (the serving hot path relies on this):

  * TPU  -> Pallas kernels (Mosaic): `sparse_ffn` for a single [N, D]
           block, `sparse_ffn_batched` for the continuous-batching
           scheduler's [B, N, D] multi-request prefill batch (per-row
           scalar-prefetched tile ids, grid (B, n_token_blocks, K));
  * CPU  -> XLA gather path (ref oracles) — interpret-mode Pallas is
           orders of magnitude slower than XLA on host, so it is only
           used for validation (`use_kernel=True` off-TPU forces the
           interpret-mode kernel; tests cross-check it against the
           gather path).

`repro.core.sparse_ffn.ffn_sparse_batched` routes the models' gated
FFN through `sparse_ffn_batched_op`, so every model family hits the
kernel on TPU without touching model code.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.backend import on_tpu
from repro.kernels.sparse_ffn import kernel as K
from repro.kernels.sparse_ffn import ref as R


def _block_n_for(N: int) -> int:
    return N if N < 128 else 128


def sparse_ffn_op(x, wg, wu, wd, tile_ids, tile: int = 128,
                  use_kernel: bool | None = None, k_valid=None):
    """Dispatch: Pallas kernel on TPU, interpret-mode kernel if forced,
    jnp oracle otherwise. x: [N, D] or [B, N, D] (batched kernel).
    k_valid: optional traced valid-tile count (scalar for [N, D], [B]
    for batched) — a SparsityPlan's per-layer/per-row counts; the
    kernel `pl.when`-skips dead tiles, the oracle masks them."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if x.ndim == 3:
        return sparse_ffn_batched_op(x, wg, wu, wd, tile_ids, tile=tile,
                                     use_kernel=use_kernel,
                                     k_valid=k_valid)
    if use_kernel:
        interp = not on_tpu()
        return K.sparse_ffn(x, wg, wu, wd, tile_ids, k_valid, tile=tile,
                            block_n=_block_n_for(x.shape[0]),
                            interpret=interp)
    return R.sparse_ffn_ref(x, wg, wu, wd, tile_ids, tile,
                            k_valid=k_valid)


def sparse_ffn_batched_op(x, wg, wu, wd, tile_ids, tile: int = 128,
                          use_kernel: bool | None = None, k_valid=None):
    """Batched multi-request dispatch: x [B, N, D], tile_ids [B, K]
    (every row selects its own tiles) -> [B, N, D] float32.

    TPU: one `sparse_ffn_batched` Pallas call over the whole batch (NOT
    a vmap of B single-block kernels — the grid's batch axis keeps one
    kernel launch and lets Mosaic pipeline the per-row weight DMAs).
    CPU: reshape-free XLA gather path. `use_kernel=True` off-TPU runs the
    batched kernel in interpret mode (equivalence cross-check).

    k_valid: optional traced [B] int32 per-row valid tile counts (see
    kernel.sparse_ffn_batched) — the FLOP-reducing carrier of
    SparsityPlan layer counts and per-request effort tiers."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if use_kernel:
        interp = not on_tpu()
        return K.sparse_ffn_batched(x, wg, wu, wd, tile_ids, k_valid,
                                    tile=tile,
                                    block_n=_block_n_for(x.shape[1]),
                                    interpret=interp)
    return R.sparse_ffn_batched_ref(x, wg, wu, wd, tile_ids, tile,
                                    k_valid=k_valid)


def dense_ffn_op(x, wg, wu, wd, use_kernel: bool | None = None):
    if use_kernel is None:
        use_kernel = on_tpu()
    if use_kernel:
        return K.dense_ffn(x, wg, wu, wd, interpret=not on_tpu())
    return R.dense_ffn_ref(x, wg, wu, wd)
