"""Pure-jnp oracle for the block-sparse gated FFN kernel.

Semantics: given tokens x [N, D], full FFN weights, and a list of
selected neuron-tile ids [K] (tile width = kernel tile size), compute
the gated FFN restricted to the selected tiles:

    y = sum_k  silu(x @ Wg[:, tile_k]) * (x @ Wu[:, tile_k]) @ Wd[tile_k, :]
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sparse_ffn_ref(x, wg, wu, wd, tile_ids, tile: int, k_valid=None):
    """x: [N, D]; wg/wu: [D, F]; wd: [F, D]; tile_ids: [K] int32.
    Returns [N, D] in float32. k_valid: optional traced int32 scalar —
    only the first k_valid selected tiles contribute (SparsityPlan
    per-layer counts under a static K)."""
    D, F = wg.shape
    n_tiles = F // tile
    wg_t = wg.reshape(D, n_tiles, tile)
    wu_t = wu.reshape(D, n_tiles, tile)
    wd_t = wd.reshape(n_tiles, tile, D)
    g = jnp.take(wg_t, tile_ids, axis=1).reshape(D, -1)
    u = jnp.take(wu_t, tile_ids, axis=1).reshape(D, -1)
    d = jnp.take(wd_t, tile_ids, axis=0).reshape(-1, D)
    x32 = x.astype(jnp.float32)
    hg = x32 @ g.astype(jnp.float32)
    hu = x32 @ u.astype(jnp.float32)
    h = hg * jax.nn.sigmoid(hg) * hu
    if k_valid is not None:
        K = tile_ids.shape[-1]
        valid = jnp.arange(K) < jnp.asarray(k_valid, jnp.int32)
        h = h * jnp.repeat(valid, tile).astype(h.dtype)[None, :]
    return h @ d.astype(jnp.float32)


def sparse_ffn_batched_ref(x, wg, wu, wd, tile_ids, tile: int,
                           k_valid=None, layer=None):
    """Batched oracle: x [B, N, D]; tile_ids [B, K] — each row selects
    its own tiles. Returns [B, N, D] float32.

    One take per weight over the whole [B, K] id matrix; the gathered
    tiles stay in [K, tile] layout — the einsums contract over (k, t)
    directly, no [D, K*tile] reshape copies. (Fusing wg|wu into one
    concatenated take materializes the full weights per call — measured
    slower; see repro.core.sparse_ffn.ffn_sparse_gather.)

    k_valid: optional traced [B] int32 — row b consumes only its first
    k_valid[b] selected tiles; the rest are masked out of the hidden
    activations (the XLA twin of the kernel's pl.when skip).

    Stacked operands, as the kernel takes them: wg/wu [L, D, F] and wd
    [L, F, D] with a traced int32 `layer` compute layer `layer`'s FFN,
    the stacks indexed dynamically; 2-D weights take no `layer`."""
    if layer is not None:
        wg, wu, wd = (jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
                      for w in (wg, wu, wd))
    D, F = wg.shape
    n_tiles = F // tile
    g = jnp.take(wg.reshape(D, n_tiles, tile), tile_ids,
                 axis=1).astype(jnp.float32)              # [D, B, K, tile]
    u = jnp.take(wu.reshape(D, n_tiles, tile), tile_ids,
                 axis=1).astype(jnp.float32)
    d = jnp.take(wd.reshape(n_tiles, tile, D), tile_ids,
                 axis=0).astype(jnp.float32)              # [B, K, tile, D]
    x32 = x.astype(jnp.float32)
    hg = jnp.einsum("bnd,dbkt->bnkt", x32, g)
    hu = jnp.einsum("bnd,dbkt->bnkt", x32, u)
    h = hg * jax.nn.sigmoid(hg) * hu
    if k_valid is not None:
        K = tile_ids.shape[-1]
        valid = (jnp.arange(K)[None, :]
                 < jnp.asarray(k_valid, jnp.int32)[:, None])   # [B, K]
        h = h * valid[:, None, :, None].astype(h.dtype)
    return jnp.einsum("bnkt,bktd->bnd", h, d)


def dense_ffn_ref(x, wg, wu, wd):
    """Full (non-sparse) gated FFN oracle, f32 accumulation."""
    x32 = x.astype(jnp.float32)
    hg = x32 @ wg.astype(jnp.float32)
    hu = x32 @ wu.astype(jnp.float32)
    h = hg * jax.nn.sigmoid(hg) * hu
    return h @ wd.astype(jnp.float32)
