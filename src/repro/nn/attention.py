"""GQA attention: full/causal, sliding-window, cross, and cached decode.

The XLA (jnp) path here is the lowering path used for dry-runs and CPU
tests; the Pallas flash kernel in repro.kernels.flash_attention is the
TPU-target equivalent validated against ref oracles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn.param import ParamSpec
from repro.nn.layers import apply_rope

NEG_INF = -1e30


def attention_spec(d_model: int, n_heads: int, n_kv_heads: int, d_head: int,
                   bias: bool = False, dtype=jnp.float32):
    sp = {
        "wq": ParamSpec((d_model, n_heads, d_head), ("embed", "heads", "head_dim"), dtype=dtype),
        "wk": ParamSpec((d_model, n_kv_heads, d_head), ("embed", "kv_heads", "head_dim"), dtype=dtype),
        "wv": ParamSpec((d_model, n_kv_heads, d_head), ("embed", "kv_heads", "head_dim"), dtype=dtype),
        "wo": ParamSpec((n_heads, d_head, d_model), ("heads", "head_dim", "embed"), dtype=dtype),
    }
    if bias:
        sp["bq"] = ParamSpec((n_heads, d_head), ("heads", "head_dim"), init="zeros", dtype=dtype)
        sp["bk"] = ParamSpec((n_kv_heads, d_head), ("kv_heads", "head_dim"), init="zeros", dtype=dtype)
        sp["bv"] = ParamSpec((n_kv_heads, d_head), ("kv_heads", "head_dim"), init="zeros", dtype=dtype)
    return sp


def project_q(params, x, positions=None, rope_theta=None):
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
    if rope_theta is not None:
        q = apply_rope(q, positions, rope_theta)
    return q


def project_kv(params, x, positions=None, rope_theta=None):
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if "bk" in params:
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    if rope_theta is not None:
        k = apply_rope(k, positions, rope_theta)
    return k, v


def output_proj(params, o):
    y = jnp.einsum("bthk,hkd->btd", o, params["wo"],
                   preferred_element_type=jnp.float32)
    return y.astype(o.dtype)


def dot_attention(q, k, v, mask=None):
    """Grouped-query attention core.

    q: [B,T,H,dh]; k,v: [B,S,Kv,dh]; mask: broadcastable to [B,1,1,T,S]
    (True = attend). Softmax in f32.
    """
    B, T, H, dh = q.shape
    Kv = k.shape[2]
    rep = H // Kv
    qg = q.reshape(B, T, Kv, rep, dh)
    scores = jnp.einsum("btgrk,bsgk->bgrts", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(dh).astype(jnp.float32)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bgrts,bsgk->btgrk", probs.astype(v.dtype), v,
                   preferred_element_type=jnp.float32).astype(v.dtype)
    return o.reshape(B, T, H, dh)


# ------------------------------------------------------------------ masks


def causal_mask(t_q: int, t_k: int, q_offset=0):
    """[1,1,1,Tq,Tk] causal mask; query i at absolute pos q_offset+i."""
    qi = q_offset + jnp.arange(t_q)[:, None]
    kj = jnp.arange(t_k)[None, :]
    return (kj <= qi)[None, None, None]


def sliding_mask(t_q: int, t_k: int, window: int, q_offset=0):
    qi = q_offset + jnp.arange(t_q)[:, None]
    kj = jnp.arange(t_k)[None, :]
    return ((kj <= qi) & (kj > qi - window))[None, None, None]


def length_mask(lengths, t_k: int):
    """lengths: [B] valid key counts -> [B,1,1,1,Tk]."""
    kj = jnp.arange(t_k)[None, :]
    return (kj < lengths[:, None])[:, None, None, None, :]


# ------------------------------------------- chunked (online softmax)


def dot_attention_chunked(q, k, v, chunk: int, *, causal=True, window=None,
                          q_offset=0):
    """Flash-style online-softmax attention in pure XLA: scan over KV
    chunks carrying (m, l, acc). Never materializes the [T, S] score
    matrix — per-step transient is [B, Kv, rep, T, chunk]. Used by the
    memory-optimized train/prefill paths (EXPERIMENTS.md §Perf)."""
    B, T, H, dh = q.shape
    S = k.shape[1]
    Kv = k.shape[2]
    rep = H // Kv
    assert S % chunk == 0
    nc = S // chunk
    qg = (q.reshape(B, T, Kv, rep, dh).astype(jnp.float32)
          / jnp.sqrt(dh).astype(jnp.float32))
    kc = k.reshape(B, nc, chunk, Kv, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nc, chunk, Kv, dh).transpose(1, 0, 2, 3, 4)
    qi = q_offset + jnp.arange(T)

    def step(carry, inp):
        m, l, acc = carry
        ci, kk, vv = inp
        s = jnp.einsum("btgrk,bsgk->bgrts", qg, kk.astype(jnp.float32))
        if causal:
            kj = ci * chunk + jnp.arange(chunk)
            mask = kj[None, :] <= qi[:, None]
            if window:
                mask = mask & (kj[None, :] > qi[:, None] - window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bgrts,bsgk->bgrtk", p, vv.astype(jnp.float32))
        acc = acc * corr[..., None] + pv
        return (m_new, l, acc), None

    m0 = jnp.full((B, Kv, rep, T), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Kv, rep, T), jnp.float32)
    a0 = jnp.zeros((B, Kv, rep, T, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (jnp.arange(nc), kc, vc))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.transpose(0, 3, 1, 2, 4).reshape(B, T, H, dh).astype(v.dtype)


# ---------------------------------------------------------- full forward


def attend_full(params, x, positions, *, causal=True, window=None,
                rope_theta=10000.0, use_rope=True, chunk=0):
    """Self-attention over the full sequence (training / fused prefill).
    chunk > 0 switches to the online-softmax chunked core (no [T,S]
    score materialization)."""
    theta = rope_theta if use_rope else None
    q = project_q(params, x, positions, theta)
    k, v = project_kv(params, x, positions, theta)
    T = x.shape[1]
    if chunk and T % chunk == 0 and T > chunk:
        o = dot_attention_chunked(q, k, v, chunk, causal=causal,
                                  window=window)
    else:
        if causal and window:
            mask = sliding_mask(T, T, window)
        elif causal:
            mask = causal_mask(T, T)
        else:
            mask = None
        o = dot_attention(q, k, v, mask)
    return output_proj(params, o)


def attend_block_cached(params, x_block, k_cache, v_cache, pos0, *,
                        window=None, rope_theta=10000.0, use_rope=True,
                        lengths=None, attn_sel=None, attn_threshold=None):
    """Blockwise prefill: query block attends to cache[:pos0+block].

    x_block: [B,N,D]; k_cache/v_cache: [B,S,Kv,dh] with the current block
    already written at [pos0, pos0+N). lengths: optional [B] true prompt
    lengths (right-padded batches never attend past them). attn_sel:
    optional block-sparse attention budget (see `attend_block_rows`) —
    delegates to the per-row path with a broadcast offset. Returns [B,N,D].
    """
    B, N, _ = x_block.shape
    S = k_cache.shape[1]
    if attn_sel is not None:
        pos0s = jnp.broadcast_to(jnp.asarray(pos0, jnp.int32), (B,))
        return attend_block_rows(params, x_block, k_cache, v_cache,
                                 pos0s, window=window,
                                 rope_theta=rope_theta,
                                 use_rope=use_rope, lengths=lengths,
                                 attn_sel=attn_sel,
                                 attn_threshold=attn_threshold)
    positions = pos0 + jnp.arange(N)[None, :]
    theta = rope_theta if use_rope else None
    q = project_q(params, x_block, positions, theta)
    from repro.kernels.backend import on_tpu
    from repro.kernels.flash_attention import ops as FA
    if on_tpu():
        # kernel-backed dense baseline (gather/mask fallback off-TPU)
        lens = (lengths if lengths is not None
                else jnp.full((B,), S, jnp.int32))
        pos0s = jnp.broadcast_to(jnp.asarray(pos0, jnp.int32), (B,))
        o = FA.mha_flash_rows(q, k_cache, v_cache, pos0s, lens,
                              window=window)
        return output_proj(params, o.astype(v_cache.dtype))
    if window:
        mask = sliding_mask(N, S, window, q_offset=pos0)
    else:
        mask = causal_mask(N, S, q_offset=pos0)
    if lengths is not None:
        mask = mask & length_mask(lengths, S)
    o = dot_attention(q, k_cache, v_cache, mask)
    return output_proj(params, o)


def attn_sel_width(attn_sel, n_blocks: int) -> int:
    """STATIC top-k selection width for a block-sparse attention budget:
    the plan's max per-layer count (virtual-grid units) scaled onto the
    cache's real block grid, floor 2 (forced sink + diagonal)."""
    attn_k_max, attn_tiles, _ = attn_sel
    k = -(-attn_k_max * n_blocks // attn_tiles)               # ceil
    return min(max(2, k), n_blocks)


def attend_block_rows(params, x_block, k_cache, v_cache, pos0s, *,
                      window=None, rope_theta=10000.0, use_rope=True,
                      lengths=None, attn_sel=None, attn_threshold=None):
    """Per-row-offset blockwise prefill: row b's query block sits at
    absolute positions [pos0s[b], pos0s[b]+N) of ITS OWN sequence.

    The batched twin of `attend_block_cached` used by the continuous-
    batching scheduler to prefill one block of B distinct requests in a
    single call: each row carries its own offset, so the causal /
    sliding-window / length masks are built per row. x_block: [B,N,D];
    k_cache/v_cache: [B,S,Kv,dh] (current block already written);
    pos0s: [B] int32; lengths: optional [B] true prompt lengths.

    attn_sel: optional (attn_k_max, attn_tiles, a_l) block-sparse
    attention budget from a dual-budget SparsityPlan — attn_k_max and
    attn_tiles are STATIC (join the plan's jit key), a_l is this
    layer's traced virtual-grid count riding the layer scan. When set,
    KV blocks are scored by the pooled-QK proxy and only the kept
    selection is attended (kernels/block_sparse_attention dispatch:
    Pallas kernel on TPU, membership-masked GQA core off TPU — the
    latter is bit-identical to the dense path at full budget).
    Returns [B,N,D]."""
    B, N, _ = x_block.shape
    S = k_cache.shape[1]
    positions = pos0s[:, None] + jnp.arange(N)[None, :]       # [B, N]
    theta = rope_theta if use_rope else None
    q = project_q(params, x_block, positions, theta)
    if attn_sel is not None:
        from repro.kernels.block_sparse_attention import ops as BSA
        _, attn_tiles, a_l = attn_sel
        nc = -(-S // N)
        lens = (lengths if lengths is not None
                else jnp.full((B,), S, jnp.int32))
        ids, cnts = BSA.select_kv_blocks(
            q, BSA.pooled_block_keys(k_cache, N), pos0s, lens, blk=N,
            k_sel=attn_sel_width(attn_sel, nc), attn_tiles=attn_tiles,
            a_l=a_l, window=window, threshold=attn_threshold)
        o = BSA.block_sparse_prefill_op(q, k_cache, v_cache, ids, cnts,
                                        pos0s, lens, blk=N,
                                        window=window)
        return output_proj(params, o.astype(x_block.dtype))
    from repro.kernels.backend import on_tpu
    from repro.kernels.flash_attention import ops as FA
    if on_tpu():
        # kernel-backed dense baseline (gather/mask fallback off-TPU)
        lens = (lengths if lengths is not None
                else jnp.full((B,), S, jnp.int32))
        o = FA.mha_flash_rows(q, k_cache, v_cache, pos0s, lens,
                              window=window)
        return output_proj(params, o.astype(x_block.dtype))
    kj = jnp.arange(S)[None, None, :]
    valid = kj <= positions[:, :, None]                       # [B, N, S]
    if window:
        valid = valid & (kj > positions[:, :, None] - window)
    if lengths is not None:
        valid = valid & (kj < lengths[:, None, None])
    mask = valid[:, None, None]                               # [B,1,1,N,S]
    o = dot_attention(q, k_cache, v_cache, mask)
    return output_proj(params, o.astype(x_block.dtype))


def attend_decode(params, x_tok, k_cache, v_cache, position, *,
                  window=None, rope_theta=10000.0, use_rope=True):
    """One-token decode: x_tok [B,1,D]; cache holds `position` valid slots
    (ring-buffer semantics when window is set: cache length == window)."""
    B = x_tok.shape[0]
    S = k_cache.shape[1]
    theta = rope_theta if use_rope else None
    q = project_q(params, x_tok, jnp.full((B, 1), position), theta)
    kj = jnp.arange(S)[None, :]
    if window:
        # ring buffer: every slot is valid once position >= window
        valid = kj < jnp.minimum(position + 1, S)
    else:
        valid = kj <= position
    mask = valid[:, None, None, None, :]
    o = dot_attention(q, k_cache, v_cache, mask)
    return output_proj(params, o)


def write_kv_block(k_cache, v_cache, k_new, v_new, pos0):
    """Insert a block of K/V at [pos0, pos0+N) (static N, dynamic pos0)."""
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new.astype(k_cache.dtype), pos0, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new.astype(v_cache.dtype), pos0, axis=1)
    return k_cache, v_cache


def write_kv_rows(k_cache, v_cache, k_new, v_new, pos0s):
    """Per-row block write: row b's [N] new K/V land at [pos0s[b],
    pos0s[b]+N) of row b (static N, dynamic per-row offsets). The
    batched twin of `write_kv_block` for multi-request prefill."""
    def row(kc, kn, p):
        return jax.lax.dynamic_update_slice_in_dim(
            kc, kn.astype(kc.dtype), p, axis=0)
    k_cache = jax.vmap(row)(k_cache, k_new, pos0s)
    v_cache = jax.vmap(row)(v_cache, v_new, pos0s)
    return k_cache, v_cache


def write_kv_tok(k_cache, v_cache, k_new, v_new, positions, active=None):
    """Per-sequence single-token write (ragged decode). positions: [B].
    active: optional [B] bool — inactive rows keep their cache unchanged
    (serving slot pool: freed/prefilling slots ride along in the fixed
    decode batch without corrupting their KV)."""
    B = k_cache.shape[0]
    bidx = jnp.arange(B)
    k_w = k_new[:, 0].astype(k_cache.dtype)
    v_w = v_new[:, 0].astype(v_cache.dtype)
    if active is not None:
        sel = active[:, None, None]
        k_w = jnp.where(sel, k_w, k_cache[bidx, positions])
        v_w = jnp.where(sel, v_w, v_cache[bidx, positions])
    k_cache = k_cache.at[bidx, positions].set(k_w)
    v_cache = v_cache.at[bidx, positions].set(v_w)
    return k_cache, v_cache


def attend_decode_ragged(params, x_tok, k_cache, v_cache, positions, *,
                         window=None, rope_theta=10000.0, use_rope=True):
    """Per-sequence decode positions [B]; cache row b valid through
    positions[b] (inclusive). window: optional sliding-window size —
    unlike the ring-buffer scalar path, the cache here is full-length
    (absolute positions), so the window is a pure attention mask."""
    S = k_cache.shape[1]
    theta = rope_theta if use_rope else None
    q = project_q(params, x_tok, positions[:, None], theta)
    kj = jnp.arange(S)[None, :]
    valid = kj <= positions[:, None]
    if window:
        valid = valid & (kj > positions[:, None] - window)
    mask = valid[:, None, None, None, :]
    o = dot_attention(q, k_cache, v_cache, mask)
    return output_proj(params, o)


# --------------------------------------------------- paged KV (page pool)
#
# The paged layout replaces each request's contiguous [S, Kv, dh] slot
# rows with a page table into a pooled [n_pages, page_size, Kv, dh]
# buffer: table entry j of a row holds the page storing that row's
# absolute positions [j*psz, (j+1)*psz). Unallocated tail entries point
# at the reserved null page 0 — a shared write sink that no mask ever
# lets a query attend. Page tables are TRACED values (fixed
# [B, max_pages] int32 shapes), so churning tables never recompile.
#
# Ownership is refcounted (serving/page_pool.py): a page may appear in
# SEVERAL rows' tables when their prompts share a prefix. Writes stay
# race-free because shared pages are READ-ONLY until copy-on-write
# detaches them — every scatter below targets either (a) a page its
# row exclusively owns (fresh allocation or COW copy for the block
# being prefilled / the decode tail), (b) the null page, or (c) an
# inactive row's self-copy, which rewrites identical bytes. Pages a
# request publishes to the prefix index belong to COMPLETED blocks it
# never rewrites, so sharing adds readers, never writers.


def kv_page_size(pages):
    """Tokens per page for either heap representation: raw
    [n_pages, psz, Kv, dh] pages or the int8-quantized heap
    ({"q": int8 pages, "s": f32 [n_pages, Kv]}, kernels/kv_quant)."""
    return (pages["q"] if isinstance(pages, dict) else pages).shape[1]


def gather_pages(pages, page_table):
    """pages: [n_pages, psz, ...] (or the quantized {"q", "s"} heap);
    page_table: [B, max_pages] int32 -> contiguous
    [B, max_pages * psz, ...] (page j of row b lands at positions
    [j*psz, (j+1)*psz)). The ONE table-directed gather both the prefill
    path and the decode oracle build on — the paged-vs-slot bit-identity
    contract hangs off this single implementation. The quantized heap
    dequantizes ON THE GATHERED VIEW (each row's pages only), never the
    whole pool."""
    B, mp = page_table.shape
    flat_ids = page_table.reshape(-1)
    if isinstance(pages, dict):
        q = jnp.take(pages["q"], flat_ids, axis=0)
        s = jnp.take(pages["s"], flat_ids, axis=0)
        flat = q.astype(jnp.float32) * s[:, None, :, None]
        psz = q.shape[1]
        return flat.reshape((B, mp * psz) + q.shape[2:])
    psz = pages.shape[1]
    flat = jnp.take(pages, flat_ids, axis=0)
    return flat.reshape((B, mp * psz) + pages.shape[2:])


def gather_kv_pages(k_pages, v_pages, page_table):
    """Gather each row's pages into contiguous [B, max_pages*psz, Kv, dh]
    views (the XLA page-table attention path: the gathered view holds
    bit-identical values to a slot-pool cache row at every attended
    position, so downstream attention math is unchanged)."""
    return (gather_pages(k_pages, page_table),
            gather_pages(v_pages, page_table))


def copy_kv_pages(cache, src_pages, dst_pages):
    """Copy-on-write detach: duplicate page payloads src -> dst across
    every cache leaf ([L, n_pages, psz, Kv, dh]; page axis 1). The
    device half of PagedKVPool.cow — a request admitted onto a shared
    prefix whose tail page it must overwrite (partial-block tail) gets
    a private bit-identical copy before any write lands.

    src_pages/dst_pages: [W] int32, FIXED width (the scheduler pads
    with 0 -> 0 null-page self-copies), so every COW batch hits one
    executable regardless of how many pages actually detach. dst
    entries are freshly-allocated distinct pages (plus padding zeros
    writing identical null bytes), so duplicate-index scatter order
    never matters."""
    return jax.tree.map(
        lambda a: a.at[:, dst_pages].set(a[:, src_pages]), cache)


def _write_pages_quant(pages, new, pids, active):
    """Scatter whole freshly-quantized pages into the int8 heap. new:
    [B, npb, psz, Kv, dh] f32 page payloads; pids: [B, npb] target
    pages. Each written page gets a FRESH scale from its own payload
    (the block covers the page end to end, so no stale bytes leak into
    absmax); inactive rows write their target pages' existing (q, s)
    back — an exact self-copy, so the null-page invariant holds."""
    from repro.kernels.kv_quant import ops as KQ
    B, npb = pids.shape
    q_w, s_w = KQ.quantize_pages_op(
        new.astype(jnp.float32).reshape((B * npb,) + new.shape[2:]))
    q_w = q_w.reshape((B, npb) + q_w.shape[1:])
    s_w = s_w.reshape((B, npb) + s_w.shape[1:])
    if active is not None:
        q_w = jnp.where(active[:, None, None, None, None], q_w,
                        pages["q"][pids])
        s_w = jnp.where(active[:, None, None], s_w, pages["s"][pids])
    flat = pids.reshape(-1)
    return {"q": pages["q"].at[flat].set(
                q_w.reshape((B * npb,) + q_w.shape[2:])),
            "s": pages["s"].at[flat].set(
                s_w.reshape((B * npb,) + s_w.shape[2:]))}


def write_kv_rows_paged(k_pages, v_pages, k_new, v_new, page_table, pos0s,
                        active=None):
    """Per-row paged block write: row b's [N] new K/V land on the
    N/psz pages its table maps for [pos0s[b], pos0s[b]+N). The paged
    twin of `write_kv_rows` — but it scatters straight into the POOL
    (the block being written is backed by exclusively-owned pages —
    shared prefix pages are read-only until COW — so live rows never
    collide), instead of updating a gathered per-row view.

    k_new/v_new: [B, N, Kv, dh]; page_table: [B, max_pages] int32;
    pos0s: [B] int32 block offsets (block-aligned, so psz | pos0).
    active: optional [B] bool — inactive pad rows carry all-null tables
    and write their target pages' own content back (a deterministic
    self-copy: every pad row writes the identical null-page payload).
    Requires psz | N. On the quantized heap each covered page is
    quantized whole with a fresh per-(page, kv-head) scale."""
    B, N = k_new.shape[:2]
    psz = kv_page_size(k_pages)
    npb = N // psz                        # pages written per block
    tpos = pos0s[:, None] // psz + jnp.arange(npb)[None, :]     # [B, npb]
    pids = jnp.take_along_axis(page_table, tpos, axis=1)        # [B, npb]
    if isinstance(k_pages, dict):
        k_r = k_new.reshape((B, npb, psz) + k_new.shape[2:])
        v_r = v_new.reshape((B, npb, psz) + v_new.shape[2:])
        return (_write_pages_quant(k_pages, k_r, pids, active),
                _write_pages_quant(v_pages, v_r, pids, active))
    k_w = k_new.astype(k_pages.dtype).reshape((B, npb, psz)
                                              + k_new.shape[2:])
    v_w = v_new.astype(v_pages.dtype).reshape((B, npb, psz)
                                              + v_new.shape[2:])
    if active is not None:
        sel = active[:, None, None, None, None]
        k_w = jnp.where(sel, k_w, k_pages[pids])
        v_w = jnp.where(sel, v_w, v_pages[pids])
    flat = pids.reshape(-1)
    k_pages = k_pages.at[flat].set(k_w.reshape((B * npb, psz)
                                               + k_new.shape[2:]))
    v_pages = v_pages.at[flat].set(v_w.reshape((B * npb, psz)
                                               + v_new.shape[2:]))
    return k_pages, v_pages


def write_kv_block_paged(k_pages, v_pages, k_new, v_new, page_table, pos0):
    """Single-request paged block write (scalar pos0, table [max_pages])
    — the paged twin of `write_kv_block`, a width-1 `write_kv_rows_paged`."""
    return write_kv_rows_paged(k_pages, v_pages, k_new, v_new,
                               page_table[None], jnp.reshape(pos0, (1,)))


def _write_tok_quant(pages, tok, pid, off, active):
    """Single-token insert into the int8 heap via
    dequantize -> insert -> zero-past-offset -> requantize. tok:
    [B, Kv, dh]; pid/off: [B]. Zeroing slots > off guarantees the fresh
    scale reflects only the valid prefix [0, off]; inactive rows keep
    their page's existing (q, s) bit-exactly (self-copy)."""
    from repro.kernels.kv_quant import ops as KQ
    q_old = pages["q"][pid]                          # [B, psz, Kv, dh]
    s_old = pages["s"][pid]                          # [B, Kv]
    page = q_old.astype(jnp.float32) * s_old[:, None, :, None]
    B, psz = page.shape[:2]
    page = page.at[jnp.arange(B), off].set(tok.astype(jnp.float32))
    slot = jnp.arange(psz)[None, :, None, None]
    page = jnp.where(slot <= off[:, None, None, None], page, 0.0)
    q_new, s_new = KQ.quantize_pages_op(page)
    if active is not None:
        q_new = jnp.where(active[:, None, None, None], q_new, q_old)
        s_new = jnp.where(active[:, None], s_new, s_old)
    return {"q": pages["q"].at[pid].set(q_new),
            "s": pages["s"].at[pid].set(s_new)}


def write_kv_tok_paged(k_pages, v_pages, k_new, v_new, page_table,
                       positions, active=None):
    """Per-sequence paged single-token write (ragged decode): row b's
    token lands at offset positions[b] % psz of page
    table[b, positions[b] // psz]. active: optional [B] bool — inactive
    rows write their target cell's own content back (prefilling /
    freed slots ride along in the fixed decode batch; their tables map
    distinct pages or the shared null page, so self-copies never race a
    live write).

    On the quantized heap: dequantize the target page, insert the token
    at its offset, ZERO every slot past the offset (stale bytes beyond
    the valid prefix must not poison the fresh absmax), requantize with
    a fresh scale, and scatter both (q, s) leaves. The scale therefore
    depends only on valid tokens; earlier tokens may requantize under
    the new scale with error within the documented
    0.5 * absmax / 127 contract (kernels/kv_quant/ref.py)."""
    psz = kv_page_size(k_pages)
    if isinstance(k_pages, dict):
        pid = jnp.take_along_axis(page_table, (positions // psz)[:, None],
                                  axis=1)[:, 0]                 # [B]
        off = positions % psz
        return (_write_tok_quant(k_pages, k_new[:, 0], pid, off, active),
                _write_tok_quant(v_pages, v_new[:, 0], pid, off, active))
    pid = jnp.take_along_axis(page_table, (positions // psz)[:, None],
                              axis=1)[:, 0]                     # [B]
    off = positions % psz
    k_w = k_new[:, 0].astype(k_pages.dtype)
    v_w = v_new[:, 0].astype(v_pages.dtype)
    if active is not None:
        sel = active[:, None, None]
        k_w = jnp.where(sel, k_w, k_pages[pid, off])
        v_w = jnp.where(sel, v_w, v_pages[pid, off])
    k_pages = k_pages.at[pid, off].set(k_w)
    v_pages = v_pages.at[pid, off].set(v_w)
    return k_pages, v_pages


def attend_block_rows_paged(params, x_block, k_pages, v_pages, page_table,
                            pos0s, *, window=None, rope_theta=10000.0,
                            use_rope=True, lengths=None, attn_sel=None,
                            attn_threshold=None):
    """Paged twin of `attend_block_rows`: per-row-offset blockwise
    prefill attention indexing the KV pool through page tables. Without
    a block-sparse budget the gathered contiguous views feed the
    identical masked GQA core, so output is bit-identical to the slot
    layout; with `attn_sel` the page-table-aware prefill kernel reads
    the selected slabs straight out of the raw page pool on TPU (the
    XLA branch masks the gathered view — same bit-identity contract)."""
    if attn_sel is not None:
        from repro.kernels.block_sparse_attention import ops as BSA
        B, N = x_block.shape[:2]
        S = page_table.shape[1] * kv_page_size(k_pages)
        positions = pos0s[:, None] + jnp.arange(N)[None, :]
        theta = rope_theta if use_rope else None
        q = project_q(params, x_block, positions, theta)
        _, attn_tiles, a_l = attn_sel
        lens = (lengths if lengths is not None
                else jnp.full((B,), S, jnp.int32))
        nc = -(-S // N)
        ids, cnts = BSA.select_kv_blocks(
            q, BSA.pooled_block_keys_paged(k_pages, page_table, N),
            pos0s, lens, blk=N, k_sel=attn_sel_width(attn_sel, nc),
            attn_tiles=attn_tiles, a_l=a_l, window=window,
            threshold=attn_threshold)
        o = BSA.block_sparse_prefill_paged_op(
            q, k_pages, v_pages, page_table, ids, cnts, pos0s, lens,
            blk=N, window=window)
        return output_proj(params, o.astype(x_block.dtype))
    kc, vc = gather_kv_pages(k_pages, v_pages, page_table)
    return attend_block_rows(params, x_block, kc, vc, pos0s,
                             window=window, rope_theta=rope_theta,
                             use_rope=use_rope, lengths=lengths)


def attend_decode_ragged_paged(params, x_tok, k_pages, v_pages, page_table,
                               positions, *, window=None,
                               rope_theta=10000.0, use_rope=True,
                               use_kernel=None):
    """Paged twin of `attend_decode_ragged`, dispatched through
    kernels/paged_attention: TPU runs the Pallas kernel (scalar-
    prefetched page ids, no gathered copy), XLA runs the gather-based
    page-table path (bit-identical to the slot layout)."""
    from repro.kernels.paged_attention import ops as PA
    theta = rope_theta if use_rope else None
    q = project_q(params, x_tok, positions[:, None], theta)
    o = PA.paged_attention_op(q[:, 0], k_pages, v_pages, page_table,
                              positions, window=window,
                              use_kernel=use_kernel)
    return output_proj(params, o[:, None].astype(x_tok.dtype))


def write_kv_ring(k_cache, v_cache, k_new, v_new, position, window: int):
    """Single-token ring-buffer write at position % window."""
    slot = jnp.mod(position, window)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new.astype(k_cache.dtype), slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new.astype(v_cache.dtype), slot, axis=1)
    return k_cache, v_cache
