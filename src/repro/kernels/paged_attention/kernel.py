"""Pallas TPU kernel: paged KV-cache decode attention.

vLLM-style PagedAttention adapted to the TPU dataflow (see
kernels/sparse_ffn for the pattern): the per-row page tables and decode
positions are scalar-prefetched, and each grid step's BlockSpec
index_map redirects the K/V slab DMA to page ``table[b, j]`` of the
pooled [n_pages, page_size, Kv, dh] buffers — the kernel never sees a
gathered contiguous cache, so decode attention reads exactly the pages
a row owns straight out of the shared pool.

Grid: (B, max_pages), online softmax over the page axis with running
max / sum / accumulator scratch in VMEM (flash-attention recurrence).
Pages entirely past a row's decode position (the unallocated null-page
tail) or fully behind the sliding window are DEAD: their compute is
skipped via pl.when AND their DMA is elided via the index-map clamp
used by the sparse-FFN / block-sparse-attention dead slots — a dead
grid step's K/V index map re-requests the nearest LIVE page's slab, and
Pallas skips the copy when consecutive steps ask for the same block.
Dead pages' bytes therefore never cross HBM->VMEM (the bit-test points
dead table entries at a poisoned page and the output is unchanged).
GQA is computed grouped: q [H, dh] reshaped to [Kv, rep, dh] against
the page's [psz, Kv, dh] keys.

VMEM working set per step: q (1, H, dh), one K page + one V page
(1, psz, Kv, dh), scratch m/l (H, 1) + acc (H, dh).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _paged_decode_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, psz, kv_heads, scale,
                         window):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[b]

    def compute():
        q = q_ref[0].astype(jnp.float32) * scale          # [H, dh]
        H, dh = q.shape
        rep = H // kv_heads
        qg = q.reshape(kv_heads, rep, dh)
        k = k_ref[0].astype(jnp.float32)                  # [psz, Kv, dh]
        s = jnp.einsum("grd,tgd->grt", qg, k)             # [Kv, rep, psz]
        kpos = j * psz + jax.lax.broadcasted_iota(
            jnp.int32, (kv_heads, rep, psz), 2)
        valid = kpos <= pos
        if window:
            valid = valid & (kpos > pos - window)
        s = jnp.where(valid, s, NEG_INF).reshape(H, psz)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                            # [H, psz]
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                  # [psz, Kv, dh]
        pv = jnp.einsum("grt,tgd->grd",
                        p.reshape(kv_heads, rep, psz), v)
        acc_scr[...] = acc_scr[...] * corr + pv.reshape(H, dh)
        m_scr[...] = m_new

    # skip pages whose first key is already past the decode position
    # (the row's unallocated null-page tail) or fully behind the window
    relevant = j * psz <= pos
    if window:
        relevant = relevant & ((j + 1) * psz - 1 > pos - window)
    pl.when(relevant)(compute)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _paged_decode_kernel_quant(table_ref, pos_ref, q_ref, k_ref, ks_ref,
                               v_ref, vs_ref, o_ref, m_scr, l_scr,
                               acc_scr, *, psz, kv_heads, scale, window):
    """Int8 twin of _paged_decode_kernel: K/V slabs arrive as int8 pages
    plus their per-(page, kv-head) f32 scales (kernels/kv_quant scheme)
    and are dequantized IN VMEM right before the MXU contractions — the
    f32 page never exists in HBM, which is the bandwidth point of the
    quantized heap."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[b]

    def compute():
        q = q_ref[0].astype(jnp.float32) * scale          # [H, dh]
        H, dh = q.shape
        rep = H // kv_heads
        qg = q.reshape(kv_heads, rep, dh)
        k = (k_ref[0].astype(jnp.float32)
             * ks_ref[0][None])                           # [psz, Kv, dh]
        s = jnp.einsum("grd,tgd->grt", qg, k)             # [Kv, rep, psz]
        kpos = j * psz + jax.lax.broadcasted_iota(
            jnp.int32, (kv_heads, rep, psz), 2)
        valid = kpos <= pos
        if window:
            valid = valid & (kpos > pos - window)
        s = jnp.where(valid, s, NEG_INF).reshape(H, psz)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                            # [H, psz]
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        v = (v_ref[0].astype(jnp.float32)
             * vs_ref[0][None])                           # [psz, Kv, dh]
        pv = jnp.einsum("grt,tgd->grd",
                        p.reshape(kv_heads, rep, psz), v)
        acc_scr[...] = acc_scr[...] * corr + pv.reshape(H, dh)
        m_scr[...] = m_new

    relevant = j * psz <= pos
    if window:
        relevant = relevant & ((j + 1) * psz - 1 > pos - window)
    pl.when(relevant)(compute)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, page_table, positions, *,
                           window: int | None = None,
                           interpret: bool = False):
    """q: [B, H, dh] (RoPE applied); k_pages/v_pages:
    [n_pages, psz, Kv, dh]; page_table: [B, max_pages] int32 (page j of
    row b holds that row's absolute positions [j*psz, (j+1)*psz), unused
    tail entries point at the reserved null page 0); positions: [B]
    int32 decode positions (inclusive — the just-written token).
    Returns [B, H, dh] float32."""
    B, H, dh = q.shape
    n_pages, psz, Kv, _ = k_pages.shape
    max_pages = page_table.shape[1]
    assert page_table.shape[0] == B and positions.shape == (B,)
    assert H % Kv == 0

    grid = (B, max_pages)

    def kv_index(b, j, tbl, pos):
        # DMA-skip dead pages (same clamp idiom as the sparse-FFN /
        # block-sparse-attention dead slots): clamp the page-axis step
        # into the row's LIVE range [first windowed page, pos // psz].
        # Dead steps re-request the boundary live page — consecutive
        # identical block indices elide the copy — so bytes of pages
        # past the decode position (null tail) or fully behind the
        # window never cross HBM->VMEM. Compute stays gated by the
        # matching pl.when(relevant) in the kernel body.
        live_hi = pos[b] // psz
        jj = jnp.minimum(j, live_hi)
        if window:
            live_lo = jnp.maximum((pos[b] - window + 1) // psz, 0)
            jj = jnp.maximum(jj, live_lo)
        return (tbl[b, jj], 0, 0, 0)

    kernel = pl.pallas_call(
        functools.partial(_paged_decode_kernel, psz=psz, kv_heads=Kv,
                          scale=1.0 / (dh ** 0.5), window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, H, dh), lambda b, j, tbl, pos: (b, 0, 0)),
                pl.BlockSpec((1, psz, Kv, dh), kv_index),
                pl.BlockSpec((1, psz, Kv, dh), kv_index),
            ],
            out_specs=pl.BlockSpec((1, H, dh),
                                   lambda b, j, tbl, pos: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )
    return kernel(jnp.asarray(page_table, jnp.int32),
                  jnp.asarray(positions, jnp.int32), q, k_pages, v_pages)


@functools.partial(jax.jit,
                   static_argnames=("window", "interpret"))
def paged_decode_attention_quant(q, k_pages, k_scales, v_pages, v_scales,
                                 page_table, positions, *,
                                 window: int | None = None,
                                 interpret: bool = False):
    """Quantized-heap twin of paged_decode_attention: k/v_pages are
    int8 [n_pages, psz, Kv, dh] with f32 scales [n_pages, Kv]
    (kernels/kv_quant scheme). The scale slabs ride the SAME clamped
    index map as their pages, so dead pages' scale bytes are DMA-elided
    together with their page bytes. Scales enter the kernel as
    [n_pages, Kv, 1] (see kernels/kv_quant/kernel.py)."""
    B, H, dh = q.shape
    n_pages, psz, Kv, _ = k_pages.shape
    max_pages = page_table.shape[1]
    assert page_table.shape[0] == B and positions.shape == (B,)
    assert H % Kv == 0
    assert k_scales.shape == v_scales.shape == (n_pages, Kv)

    grid = (B, max_pages)

    def kv_index(b, j, tbl, pos):
        live_hi = pos[b] // psz
        jj = jnp.minimum(j, live_hi)
        if window:
            live_lo = jnp.maximum((pos[b] - window + 1) // psz, 0)
            jj = jnp.maximum(jj, live_lo)
        return (tbl[b, jj], 0, 0, 0)

    def scale_index(b, j, tbl, pos):
        return kv_index(b, j, tbl, pos)[:3]

    kernel = pl.pallas_call(
        functools.partial(_paged_decode_kernel_quant, psz=psz,
                          kv_heads=Kv, scale=1.0 / (dh ** 0.5),
                          window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, H, dh), lambda b, j, tbl, pos: (b, 0, 0)),
                pl.BlockSpec((1, psz, Kv, dh), kv_index),
                pl.BlockSpec((1, Kv, 1), scale_index),
                pl.BlockSpec((1, psz, Kv, dh), kv_index),
                pl.BlockSpec((1, Kv, 1), scale_index),
            ],
            out_specs=pl.BlockSpec((1, H, dh),
                                   lambda b, j, tbl, pos: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )
    return kernel(jnp.asarray(page_table, jnp.int32),
                  jnp.asarray(positions, jnp.int32), q,
                  k_pages, k_scales.reshape(n_pages, Kv, 1),
                  v_pages, v_scales.reshape(n_pages, Kv, 1))
