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

Stacked-operand contract (`sparse_ffn_batched_op`): W_gate/W_up may be
the stacked [L, D, F] leaves and W_down [L, F, D], with a traced
`layer`; the kernel then DMAs its tiles straight out of layer `layer`
of the stacks, and the oracle indexes them dynamically. The dense
model's layer scans pass the stacks this way, so XLA copies no whole
per-layer matrix out of them for the Pallas call — unless the call's
rows re-read the matrices so often that the copies pay (see
`STACKED_MAX_REREAD`). The kernel carries `layer` in its `cnt`
scalar-prefetch array, so its custom call keeps the six operands (ids,
cnt, x, wg, wu, wd) the benchmark reads by position. 2-D weights take
no `layer`, as before.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.backend import on_tpu
from repro.kernels.sparse_ffn import kernel as K
from repro.kernels.sparse_ffn import ref as R


#: Most matrices' worth of tiles (rows x selected tiles x tile / d_ff)
#: one kernel call may read out of the stacked leaves in place. Each row
#: DMAs its own tiles, so a call with many rows (a 32-slot decode step)
#: re-reads every tile many times. Handed the layer's slices instead,
#: XLA copies the three matrices and can stage W_gate's copy in on-chip
#: memory, where its re-reads cost no HBM bytes. Per layer, with M one
#: matrix's bytes and r this ratio: in place the kernel reads 3rM; sliced
#: the copies move 5M (W_gate's write lands on the chip) and the kernel
#: reads 2rM. In place moves fewer below r = 5.
STACKED_MAX_REREAD = 5


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
                          use_kernel: bool | None = None, k_valid=None,
                          layer=None):
    """Batched multi-request dispatch: x [B, N, D], tile_ids [B, K]
    (every row selects its own tiles) -> [B, N, D] float32.

    TPU: one `sparse_ffn_batched` Pallas call over the whole batch (NOT
    a vmap of B single-block kernels — the grid's batch axis keeps one
    kernel launch and lets Mosaic pipeline the per-row weight DMAs).
    CPU: reshape-free XLA gather path. `use_kernel=True` off-TPU runs the
    batched kernel in interpret mode (equivalence cross-check).

    k_valid: optional traced [B] int32 per-row valid tile counts (see
    kernel.sparse_ffn_batched) — the FLOP-reducing carrier of
    SparsityPlan layer counts and per-request effort tiers.

    layer: traced int32 scalar with stacked [L, ...] weights (module
    docstring), None with one layer's 2-D weights. The kernel reads the
    stacks in place unless the rows would read more than
    `STACKED_MAX_REREAD` matrices' worth of tiles; then it gets the
    layer's slices. Same tiles, same arithmetic, same output."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if use_kernel:
        rows_tiles = x.shape[0] * tile_ids.shape[1] * tile
        if (layer is not None
                and rows_tiles > STACKED_MAX_REREAD * wg.shape[-1]):
            wg, wu, wd = (jax.lax.dynamic_index_in_dim(w, layer,
                                                       keepdims=False)
                          for w in (wg, wu, wd))
            layer = None
        interp = not on_tpu()
        return K.sparse_ffn_batched(x, wg, wu, wd, tile_ids, k_valid,
                                    layer, tile=tile,
                                    block_n=_block_n_for(x.shape[1]),
                                    interpret=interp)
    return R.sparse_ffn_batched_ref(x, wg, wu, wd, tile_ids, tile,
                                    k_valid=k_valid, layer=layer)


def dense_ffn_op(x, wg, wu, wd, use_kernel: bool | None = None):
    if use_kernel is None:
        use_kernel = on_tpu()
    if use_kernel:
        return K.dense_ffn(x, wg, wu, wd, interpret=not on_tpu())
    return R.dense_ffn_ref(x, wg, wu, wd)
