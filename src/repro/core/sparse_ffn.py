"""Tile-sparse gated FFN — the compute core of FastForward.

TPU adaptation (DESIGN.md §3): neurons are sparsified in tiles of 128
(MXU lane width). Two execution paths, cross-checked in tests:

  * mask path   — multiplicative neuron mask; differentiable; used for
                  training/distillation and for full-sequence fidelity
                  experiments (supports per-layer budgets, Algorithm 1).
  * gather path — tile-index gather of W_gate/W_up rows and W_down
                  columns; static K tiles; real FLOP reduction; used by
                  the serving engine and dry-runs. The Pallas kernel in
                  repro.kernels.sparse_ffn is its TPU twin.

Balanced per-shard top-K: with d_ff sharded over `model`, scores are
reshaped to [shards, tiles_per_shard] and top-(K/shards) is taken per
shard, so the weight gather never crosses a shard boundary.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn.param import ParamSpec
from repro.nn.layers import ACTIVATIONS, swiglu


def ffn_spec(d_model: int, d_ff: int, gated: bool = True, dtype=jnp.float32):
    sp = {
        "wu": ParamSpec((d_model, d_ff), ("embed", "mlp"), dtype=dtype),
        "wd": ParamSpec((d_ff, d_model), ("mlp", "embed"), dtype=dtype),
    }
    if gated:
        sp["wg"] = ParamSpec((d_model, d_ff), ("embed", "mlp"), dtype=dtype)
    return sp


#: The FFN matrices a layer scan may hand over as whole [L, ...] stacks
#: (with the layer index) instead of one layer's slices.
FFN_MATRICES = ("wg", "wu", "wd")


def ffn_at(params, layer):
    """One layer's FFN params from params whose matrices are the stacked
    [L, ...] leaves: each matrix indexed at the traced `layer` (XLA's
    dots read such a slice in place). Other leaves (predictor,
    compensator) are one layer's already. layer None: params are one
    layer's throughout, returned as they are."""
    if layer is None:
        return params
    return {k: (jax.lax.dynamic_index_in_dim(v, layer, keepdims=False)
                if k in FFN_MATRICES else v) for k, v in params.items()}


def ffn_hidden(params, x, act: str = "silu"):
    """Post-activation hidden h: [..., F] (used for labels + mask path)."""
    up = jnp.einsum("...d,df->...f", x, params["wu"],
                    preferred_element_type=jnp.float32).astype(x.dtype)
    if "wg" in params:
        gate = jnp.einsum("...d,df->...f", x, params["wg"],
                          preferred_element_type=jnp.float32).astype(x.dtype)
        return swiglu(gate, up)
    return ACTIVATIONS[act](up.astype(jnp.float32)).astype(x.dtype)


def ffn_dense(params, x, act: str = "silu"):
    h = ffn_hidden(params, x, act)
    y = jnp.einsum("...f,fd->...d", h, params["wd"],
                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


# ------------------------------------------------------------- mask path


def tile_scores(scores, tile: int):
    """Neuron scores [..., F] -> tile scores [..., F/tile]."""
    F = scores.shape[-1]
    return scores.reshape(scores.shape[:-1] + (F // tile, tile)).sum(-1)


def neuron_mask_from_scores(scores, keep_frac, tile: int, k_tiles=None):
    """Dynamic-threshold tile mask (supports traced per-layer budgets).

    scores: [..., F]; keep_frac: scalar (may be traced). Returns a
    {0,1} mask [..., F] keeping the top ceil(keep_frac * n_tiles) tiles.
    k_tiles: optional traced int32 tile count that OVERRIDES keep_frac
    — the mask-path twin of a SparsityPlan's per-layer counts, exact
    where ceil(keep * n_tiles) could drift by one tile in float.
    """
    # Hard top-k selection: not differentiable by construction (the
    # predictor is trained via its own BCE objective, paper §3.2), so the
    # whole mask is a stop_gradient region.
    ts = jax.lax.stop_gradient(tile_scores(scores, tile))  # [..., n_tiles]
    n_tiles = ts.shape[-1]
    if k_tiles is not None:
        k = jnp.clip(jnp.asarray(k_tiles, jnp.int32), 1, n_tiles)
    else:
        k = jnp.clip(jnp.ceil(keep_frac * n_tiles).astype(jnp.int32),
                     1, n_tiles)
    sorted_ts = jnp.sort(ts, axis=-1)                   # ascending
    thresh = jnp.take_along_axis(
        sorted_ts, (n_tiles - k) * jnp.ones(ts.shape[:-1] + (1,), jnp.int32),
        axis=-1)
    tmask = (ts >= thresh).astype(scores.dtype)         # [..., n_tiles]
    return jnp.repeat(tmask, tile, axis=-1)


def ffn_masked(params, x, mask, act: str = "silu"):
    """Mask path: h * mask before down-projection. mask: [..., F]
    broadcastable over the token axis of x [..., N, D]."""
    h = ffn_hidden(params, x, act)
    h = h * mask.astype(h.dtype)
    y = jnp.einsum("...f,fd->...d", h, params["wd"],
                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


# ----------------------------------------------------------- gather path


def balanced_topk_tiles(scores, k_tiles: int, tile: int, shards: int = 1):
    """Tile ids under balanced per-shard selection.

    scores: [..., F]. Returns int32 [..., k_tiles] of *global* tile ids;
    exactly k_tiles/shards tiles come from each shard's range.
    """
    ts = tile_scores(scores, tile)                      # [..., n_tiles]
    n_tiles = ts.shape[-1]
    if shards > 1 and n_tiles % shards == 0 and k_tiles % shards == 0:
        tps, kps = n_tiles // shards, k_tiles // shards
        grouped = ts.reshape(ts.shape[:-1] + (shards, tps))
        _, idx = jax.lax.top_k(grouped, kps)            # [..., shards, kps]
        base = (jnp.arange(shards) * tps)[..., :, None]
        return (idx + base).reshape(ts.shape[:-1] + (k_tiles,)).astype(jnp.int32)
    _, idx = jax.lax.top_k(ts, k_tiles)
    return idx.astype(jnp.int32)


def ffn_sparse_gather(params, x_block, tile_ids, tile: int, act: str = "silu",
                      k_valid=None):
    """Gather path for ONE block: x_block [N, D], tile_ids [K] -> [N, D].

    FLOPs = (K*tile/d_ff) of the dense FFN. The gathered tiles are
    consumed in [K, tile] layout — the einsums contract over (k, t)
    directly, so no [D, K*tile] reshape copies are materialized.
    (A single take over a concatenated [D, 2*n_tiles, tile] wg|wu view
    was measured ~1.8x SLOWER on XLA-CPU at tinyllama scale: the
    concat materializes the full [D, 2F] weights per layer call,
    memory traffic that dwarfs the take it saves. Two takes it is.)

    k_valid: optional traced int32 scalar — only the FIRST k_valid of
    the K selected tiles contribute (tile_ids are top-k ordered, so the
    prefix IS the top-k_valid selection). This is how a layer-wise
    SparsityPlan consumes fewer tiles on some layers while the scan
    over layers keeps one static K; invalid tiles are masked out of the
    hidden activations before the down-projection.
    """
    D, F = params["wu"].shape
    n_tiles = F // tile
    d = jnp.take(params["wd"].reshape(n_tiles, tile, D), tile_ids, axis=0)
    if "wg" in params:
        g = jnp.take(params["wg"].reshape(D, n_tiles, tile), tile_ids,
                     axis=1)                              # [D, K, tile]
        u = jnp.take(params["wu"].reshape(D, n_tiles, tile), tile_ids,
                     axis=1)
        hg = jnp.einsum("nd,dkt->nkt", x_block, g,
                        preferred_element_type=jnp.float32
                        ).astype(x_block.dtype)
        hu = jnp.einsum("nd,dkt->nkt", x_block, u,
                        preferred_element_type=jnp.float32
                        ).astype(x_block.dtype)
        h = swiglu(hg, hu)
    else:
        u = jnp.take(params["wu"].reshape(D, n_tiles, tile), tile_ids,
                     axis=1)                              # [D, K, tile]
        up = jnp.einsum("nd,dkt->nkt", x_block, u,
                        preferred_element_type=jnp.float32)
        h = ACTIVATIONS[act](up).astype(x_block.dtype)
    if k_valid is not None:
        K = tile_ids.shape[-1]
        valid = jnp.arange(K) < jnp.asarray(k_valid, jnp.int32)
        h = h * valid[None, :, None].astype(h.dtype)
    y = jnp.einsum("nkt,ktd->nd", h, d,
                   preferred_element_type=jnp.float32)
    return y.astype(x_block.dtype)


def ffn_sparse_batched(params, x_blocks, tile_ids, tile: int,
                       act: str = "silu", k_valid=None, layer=None):
    """x_blocks [B, N, D], tile_ids [B, K] -> [B, N, D] — every row
    selects its own tiles (the multi-request prefill hot path).

    Gated-silu FFNs dispatch through repro.kernels.sparse_ffn.ops:
    TPU hits the batched Pallas kernel (grid (B, n_token_blocks, K),
    per-row scalar-prefetched tile ids), CPU keeps the reshape-free XLA
    path. Other activations fall back to the vmapped gather path.

    k_valid: optional traced int32 scalar or [B] vector — per-row valid
    tile count (<= K). Rows consume only their first k_valid selected
    tiles: the Pallas kernel `pl.when`-skips the dead grid steps (real
    FLOP skip on TPU), the XLA paths mask the hidden tiles. This is
    the mechanism behind per-layer SparsityPlan counts (scalar, riding
    the layer scan) and per-request effort tiers at decode ([B], from
    traced plan ids).

    layer: optional traced int32 — params' FFN matrices are the stacked
    [L, ...] leaves and this is the layer to compute. The kernel reads
    its tiles out of the stacks in place (kernels/sparse_ffn/ops.py);
    the fallback path takes the layer's slices (`ffn_at`)."""
    if k_valid is not None:
        k_valid = jnp.broadcast_to(jnp.asarray(k_valid, jnp.int32),
                                   x_blocks.shape[:1])
    if "wg" in params and act == "silu":
        from repro.kernels.sparse_ffn import ops
        y = ops.sparse_ffn_batched_op(x_blocks, params["wg"], params["wu"],
                                      params["wd"], tile_ids, tile=tile,
                                      k_valid=k_valid, layer=layer)
        return y.astype(x_blocks.dtype)
    params = ffn_at(params, layer)
    if k_valid is None:
        return jax.vmap(
            lambda xb, ids: ffn_sparse_gather(params, xb, ids, tile, act)
        )(x_blocks, tile_ids)
    return jax.vmap(
        lambda xb, ids, kv: ffn_sparse_gather(params, xb, ids, tile, act,
                                              k_valid=kv)
    )(x_blocks, tile_ids, k_valid)


def ffn_block_sparse_shardmap(params, cfg, x_block, k_tiles: int, mesh):
    """shard_map gather path (EXPERIMENTS.md §Perf): every weight gather
    stays local to its model shard; only the [B,N,D] partial FFN output
    crosses the ICI (psum), instead of GSPMD all-gathering weight tiles.

    x_block: [B, N, D] (batch sharded over the data axes); params: one
    layer's FastForward FFN params with d_ff sharded over "model".
    """
    import functools
    from jax.sharding import PartitionSpec as P
    from repro.core import predictor as PR
    from repro.core import compensator as C

    tile = cfg.ff.tile
    act = cfg.act
    shards = mesh.shape["model"]
    k_local = max(k_tiles // shards, 1)
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = batch_axes if batch_axes else None

    # predictor pooling + bottleneck are tiny and replicated; only the
    # [r, F] output projection is sharded on F.
    a = PR.pool_block(params["pred"], x_block)                 # [B, D] f32
    h1 = jax.nn.relu(a @ params["pred"]["w1"].astype(jnp.float32))

    def local_fn(wg, wu, wd, w2, h1_, x):
        scores = jax.nn.sigmoid(h1_ @ w2.astype(jnp.float32))  # [B, F_loc]
        ids = balanced_topk_tiles(scores, k_local, tile, shards=1)
        y = ffn_sparse_batched({"wg": wg, "wu": wu, "wd": wd}, x, ids,
                               tile, act)
        return jax.lax.psum(y, "model")

    y = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(None, "model"), P(None, "model"), P("model", None),
                  P(None, "model"), P(bspec, None), P(bspec, None, None)),
        out_specs=P(bspec, None, None),
        check_vma=False,
    )(params["wg"], params["wu"], params["wd"], params["pred"]["w2"],
      h1, x_block)
    y = y.astype(x_block.dtype)
    if cfg.ff.use_compensator and "comp" in params:
        y = y + C.compensate(params["comp"], x_block)
    return y


def mask_from_tile_ids(tile_ids, n_tiles: int, tile: int):
    """Tile ids -> {0,1} neuron mask (for cross-checking the two paths)."""
    onehot = jax.nn.one_hot(tile_ids, n_tiles, dtype=jnp.float32).sum(-2)
    onehot = jnp.clip(onehot, 0.0, 1.0)
    return jnp.repeat(onehot, tile, axis=-1)
