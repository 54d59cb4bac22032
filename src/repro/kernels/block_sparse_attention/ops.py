"""Selection math + backend dispatch for block-sparse prefill attention.

This is where the pooled-QK scoring proxy lives (the DESIGN note in
core/fastforward.py documents the semantics): `select_kv_blocks` turns
one query block + the pooled per-KV-block key means into a per-row
block selection (ids + live counts) under a SparsityPlan attention
budget, and the `block_sparse_prefill_op` twins dispatch it:

  * TPU  -> Pallas kernel (kernels/block_sparse_attention/kernel.py):
            scalar-prefetched slab ids, one K/V slab DMA per live
            selection slot, online softmax — FLOPs AND bytes scale
            with the budget. The paged twin resolves slab ids through
            the page table (slab granularity = page size) so the
            kernel reads the raw page pool: this is the paged PREFILL
            kernel the gather path previously stood in for.
  * XLA  -> ref.block_sparse_attention_masked — the selection as a
            membership mask over the full cache view, feeding the
            exact masked GQA core `attend_block_rows` uses, so the
            CPU serving path stays bit-identical to dense at full
            budget.
  * ``use_kernel=True`` off-TPU forces the interpret-mode kernel
    (tests cross-check it against the twin + dense oracle in ref.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.backend import on_tpu
from repro.kernels.block_sparse_attention import kernel as K
from repro.kernels.block_sparse_attention import ref as R


# ------------------------------------------------------- pooled K means


def pooled_block_keys(k_cache, blk: int):
    """[B, S, Kv, dh] -> [B, nc, Kv, dh] per-KV-block key means
    (nc = ceil(S / blk); the tail block zero-pads). Scoring-only: the
    attention masks, not the pooling, carry correctness."""
    B, S, Kv, dh = k_cache.shape
    nc = -(-S // blk)
    pad = nc * blk - S
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return k_cache.reshape(B, nc, blk, Kv, dh).mean(axis=2)


def pooled_block_keys_paged(k_pages, page_table, blk: int):
    """Paged twin: per-page means gathered through the table, then
    averaged page-groups per attention block (psz | blk, so a block's
    mean is the equal-weight mean of its pages' means). Accepts the
    int8-quantized heap ({"q": int8 pages, "s": f32 [n_pages, Kv]},
    kernels/kv_quant): the mean distributes over the per-page scale, so
    pooling the int8 values and scaling once is exact."""
    if isinstance(k_pages, dict):
        q, s = k_pages["q"], k_pages["s"]
        psz = q.shape[1]
        assert blk % psz == 0
        ppb = blk // psz
        page_means = q.astype(jnp.float32).mean(axis=1) * s[:, :, None]
        per_row = page_means[page_table]              # [B, mp, Kv, dh]
        B, mp = page_table.shape
        nc = -(-mp // ppb)
        pad = nc * ppb - mp
        if pad:
            per_row = jnp.pad(per_row,
                              ((0, 0), (0, pad), (0, 0), (0, 0)))
        return per_row.reshape((B, nc, ppb)
                               + per_row.shape[2:]).mean(axis=2)
    psz = k_pages.shape[1]
    assert blk % psz == 0
    ppb = blk // psz
    page_means = k_pages.mean(axis=1)                 # [n_pages, Kv, dh]
    per_row = page_means[page_table]                  # [B, mp, Kv, dh]
    B, mp = page_table.shape
    nc = -(-mp // ppb)
    pad = nc * ppb - mp
    if pad:
        per_row = jnp.pad(per_row, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return per_row.reshape((B, nc, ppb) + per_row.shape[2:]).mean(axis=2)


# ----------------------------------------------------------- selection


def select_kv_blocks(q, block_keys, pos0s, lengths, *, blk: int,
                     k_sel: int, attn_tiles: int, a_l, window=None,
                     threshold=None):
    """Pooled-QK proxy selection for one query block.

    q: [B, N, H, dh] (RoPE applied); block_keys: [B, nc, Kv, dh] pooled
    per-block key means; pos0s/lengths: [B] int32; k_sel: STATIC
    selection width (top-k size); attn_tiles: STATIC virtual budget
    grid; a_l: this layer's budget count in virtual-grid units (traced
    int32 scalar riding the layer scan, or a python int).

    Returns (ids [B, k_sel] int32, counts [B] int32): the first
    counts[b] slots of row b are its kept block indices in ASCENDING
    position order (so full-budget masked attention visits keys in
    dense order), the tail slots are don't-care ids the kernel skips.

    Selection is top-k on the proxy scores; the kept count is the
    budget fraction scaled onto the row's causally-valid block count
    nv: c = clip(ceil(a_l * nv / attn_tiles), min(2, nv), min(nv,
    k_sel)). The sink block 0 and the diagonal (current) block are
    force-included via score bias.

    threshold: opt-in FlashPrefill-style ADAPTIVE counts — keep the
    fewest top-scored blocks whose softmax mass over the k_sel
    candidates reaches `threshold` (computed on the RAW valid-masked
    proxy scores, before sink/diagonal forcing, which would saturate a
    softmax). The per-input count is CAPPED by the plan's budget count
    (the c above) — the budget stays the worst case, easy inputs spend
    less. threshold=1.0 keeps every candidate (the `1 +` below absorbs
    fp cumsum undershoot), so a full budget stays bit-identical to
    dense. None (default) = fixed budget counts, the pre-existing
    behavior."""
    B, N, H, dh = q.shape
    nc = block_keys.shape[1]
    Kv = block_keys.shape[2]
    rep = H // Kv
    k_sel = min(k_sel, nc)
    # pooled query: mean over the block's rows and the GQA head group
    qp = q.astype(jnp.float32).reshape(B, N, Kv, rep, dh).mean(
        axis=(1, 3))                                      # [B, Kv, dh]
    scores = jnp.einsum("bgd,bcgd->bc", qp,
                        block_keys.astype(jnp.float32))
    scores = scores / (Kv * (dh ** 0.5))                  # [B, nc]

    cur = (pos0s + N - 1) // blk                          # [B]
    bidx = jnp.arange(nc)[None, :]
    valid = bidx <= cur[:, None]
    if window:
        valid = valid & ((bidx + 1) * blk - 1 > pos0s[:, None] - window)
    big = jnp.float32(1e30)
    raw = jnp.where(valid, scores, -big)                  # pre-forcing
    scores = raw
    forced = (bidx == 0) | (bidx == cur[:, None])
    scores = jnp.where(forced & valid, big, scores)

    _, top_idx = jax.lax.top_k(scores, k_sel)             # [B, k_sel]
    nv = cur + 1
    a = jnp.asarray(a_l, jnp.int32)
    c = (a * nv + attn_tiles - 1) // attn_tiles
    c = jnp.clip(c, jnp.minimum(2, nv), jnp.minimum(nv, k_sel))
    if threshold is not None:
        # softmax mass of the candidates' RAW scores, best-first:
        # c_adaptive = smallest count whose inclusive mass reaches the
        # threshold. Invalid candidates carry exp(-inf) = 0 mass.
        top_raw = jnp.sort(
            jnp.take_along_axis(raw, top_idx, axis=-1), axis=-1)[:, ::-1]
        vmask = top_raw > -big / 2
        # floor valid weights above 0 so an extreme score gap cannot
        # underflow a candidate out of the mass entirely: at
        # threshold=1.0 the inclusive mass stays < 1.0 until the LAST
        # valid candidate, so every candidate is kept (dense at full
        # budget stays bit-identical)
        w = jnp.where(vmask,
                      jnp.maximum(jnp.exp(top_raw - top_raw[:, :1]),
                                  1e-30), 0.0)
        mass = jnp.cumsum(w, axis=-1) / jnp.maximum(
            jnp.sum(w, axis=-1, keepdims=True), 1e-30)
        thr = jnp.asarray(threshold, jnp.float32)
        c_adaptive = 1 + jnp.sum(mass < thr, axis=-1).astype(jnp.int32)
        c = jnp.minimum(c, jnp.clip(c_adaptive, jnp.minimum(2, nv),
                                    jnp.minimum(nv, k_sel)))
    # live prefix in ascending block order; dead slots keyed past nc so
    # a stable argsort pushes them to the tail
    slot = jnp.arange(k_sel)[None, :]
    sort_key = jnp.where(slot < c[:, None], top_idx, nc + slot)
    order = jnp.argsort(sort_key, axis=-1)
    ids = jnp.take_along_axis(top_idx, order, axis=-1)
    return ids.astype(jnp.int32), c.astype(jnp.int32)


# ------------------------------------------------------------ dispatch


def block_sparse_prefill_op(q, k_cache, v_cache, ids, counts, pos0s,
                            lengths, *, blk: int, window=None,
                            use_kernel: bool | None = None):
    """Slot-layout block-sparse prefill attention. q: [B, N, H, dh]
    (RoPE applied); k/v_cache: [B, S, Kv, dh]; ids/counts from
    `select_kv_blocks`. Returns [B, N, H, dh]."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if not use_kernel:
        return R.block_sparse_attention_masked(
            q, k_cache, v_cache, ids, counts, pos0s, lengths, blk=blk,
            window=window)
    B, S, Kv, dh = k_cache.shape
    pad = (-S) % blk
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nc = (S + pad) // blk
    kb = k_cache.reshape(B * nc, blk, Kv, dh)
    vb = v_cache.reshape(B * nc, blk, Kv, dh)
    pool_ids = ids + nc * jnp.arange(B, dtype=jnp.int32)[:, None]
    blk_pos = ids * blk
    return K.block_sparse_prefill(q, kb, vb, pool_ids, blk_pos, counts,
                                  pos0s, lengths, window=window,
                                  interpret=not on_tpu())


def block_sparse_prefill_paged_op(q, k_pages, v_pages, page_table, ids,
                                  counts, pos0s, lengths, *, blk: int,
                                  window=None,
                                  use_kernel: bool | None = None):
    """Paged twin: the kernel reads the RAW page pool through slab ids
    resolved from each row's page table (slab granularity = page
    size) — the paged PREFILL kernel. The XLA branch gathers the
    table-mapped contiguous view (positions == absolute positions) and
    reuses the slot masked path. Accepts the int8-quantized heap
    ({"q", "s"} dicts, kernels/kv_quant): the XLA branch dequantizes
    the gathered view on the fly, the kernel branch dispatches the
    fused-dequant quant kernel with the scale slabs riding the same
    table-resolved pool ids."""
    if use_kernel is None:
        use_kernel = on_tpu()
    quant = isinstance(k_pages, dict)
    if not use_kernel:
        B, mp = page_table.shape
        flat = page_table.reshape(-1)
        if quant:
            psz = k_pages["q"].shape[1]
            kc = (jnp.take(k_pages["q"], flat, axis=0)
                  .astype(jnp.float32)
                  * jnp.take(k_pages["s"], flat,
                             axis=0)[:, None, :, None])
            vc = (jnp.take(v_pages["q"], flat, axis=0)
                  .astype(jnp.float32)
                  * jnp.take(v_pages["s"], flat,
                             axis=0)[:, None, :, None])
            tail = kc.shape[2:]
        else:
            psz = k_pages.shape[1]
            kc = jnp.take(k_pages, flat, axis=0)
            vc = jnp.take(v_pages, flat, axis=0)
            tail = k_pages.shape[2:]
        kc = kc.reshape((B, mp * psz) + tail)
        vc = vc.reshape((B, mp * psz) + tail)
        return R.block_sparse_attention_masked(
            q, kc, vc, ids, counts, pos0s, lengths, blk=blk,
            window=window)
    psz = (k_pages["q"] if quant else k_pages).shape[1]
    assert blk % psz == 0
    ppb = blk // psz
    B, n_sel = ids.shape
    # selected block j -> its ppb table entries [j*ppb, (j+1)*ppb)
    tpos = ids[:, :, None] * ppb + jnp.arange(ppb)[None, None, :]
    tpos = tpos.reshape(B, n_sel * ppb)
    tpos = jnp.minimum(tpos, page_table.shape[1] - 1)
    pool_ids = jnp.take_along_axis(page_table, tpos, axis=1)
    blk_pos = (ids[:, :, None] * blk
               + jnp.arange(ppb)[None, None, :] * psz).reshape(B, -1)
    if quant:
        return K.block_sparse_prefill_quant(
            q, k_pages["q"], k_pages["s"], v_pages["q"], v_pages["s"],
            pool_ids, blk_pos, counts * ppb, pos0s, lengths,
            window=window, interpret=not on_tpu())
    return K.block_sparse_prefill(q, k_pages, v_pages, pool_ids,
                                  blk_pos, counts * ppb, pos0s, lengths,
                                  window=window,
                                  interpret=not on_tpu())
