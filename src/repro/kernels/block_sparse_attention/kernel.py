"""Pallas TPU kernel: block-sparse flash-attention prefill.

The FLOP-bending half of the dual-budget SparsityPlan (see the DESIGN
note in core/fastforward.py): per 128-token query block, a cheap
pooled-QK proxy (computed in XLA, see ops.select_kv_blocks) picks the
KV blocks worth attending, and this kernel walks ONLY the selection —
scalar-prefetched block-id + count operands, one K/V slab DMA per grid
step, online softmax over the selected-block axis.

The kernel is layout-agnostic and thereby page-table-aware: it reads
[P, blk, Kv, dh] POOL slabs through prefetched pool ids, so the slot
layout passes its reshaped contiguous cache (pool id = row * n_blocks
+ block) and the paged layout passes the raw page pool with ids
resolved through each row's page table (slab granularity = page size).
Each selected slab also carries its absolute start position
(`blk_pos`), since pool ids do not encode sequence position.

Grid: (B, K). Selection slots past a row's live count are dead:
`pl.when` skips the whole MXU body AND the index_map clamps the slab
id to the last live block, so dead slots re-request an already-resident
slab instead of moving new bytes (the DMA-skip idiom, same as the
sparse-FFN kernels). GQA is computed grouped per program over all
heads (kernels/flash_attention `gqa_slab_step`: head-major query
block, one 2-D dot per kv head).

VMEM working set per step: q (1, H, N, dh), one K + one V slab
(1, blk, Kv, dh), scratch m/l (Kv, rep*N, 1) + acc (Kv, rep*N, dh).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.kernel import (gqa_slab_finish,
                                                 gqa_slab_init,
                                                 gqa_slab_scratch,
                                                 gqa_slab_step)


def _bsa_kernel(ids_ref, bpos_ref, cnt_ref, p0_ref, len_ref, q_ref,
                k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                kv_heads, scale, window):
    _bsa_body(bpos_ref, cnt_ref, p0_ref, len_ref, q_ref, k_ref, v_ref,
              o_ref, m_scr, l_scr, acc_scr, kv_heads=kv_heads,
              scale=scale, window=window)


def _bsa_kernel_quant(ids_ref, bpos_ref, cnt_ref, p0_ref, len_ref, q_ref,
                      k_ref, ks_ref, v_ref, vs_ref, o_ref, m_scr, l_scr,
                      acc_scr, *, kv_heads, scale, window):
    """Int8 twin of _bsa_kernel: the K/V slabs arrive as int8 pages plus
    per-(page, kv-head) f32 scales (kernels/kv_quant scheme), dequantized
    in VMEM right before the MXU contractions — the quantized-heap
    PREFILL path never materializes an f32 page in HBM."""
    _bsa_body(bpos_ref, cnt_ref, p0_ref, len_ref, q_ref, k_ref, v_ref,
              o_ref, m_scr, l_scr, acc_scr, kv_heads=kv_heads,
              scale=scale, window=window, ks_ref=ks_ref, vs_ref=vs_ref)


def _bsa_body(bpos_ref, cnt_ref, p0_ref, len_ref, q_ref, k_ref, v_ref,
              o_ref, m_scr, l_scr, acc_scr, *, kv_heads, scale, window,
              ks_ref=None, vs_ref=None):
    b = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        gqa_slab_init(m_scr, l_scr, acc_scr)

    def compute():
        gqa_slab_step(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                      kv_heads=kv_heads, scale=scale, qpos0=p0_ref[b],
                      kpos0=bpos_ref[b, k], klen=len_ref[b],
                      window=window, ks_ref=ks_ref, vs_ref=vs_ref)

    # selection slots past this row's live count are dead grid steps:
    # no MXU work, and the index_map already clamped their slab DMA
    pl.when(k < cnt_ref[b])(compute)

    @pl.when(k == pl.num_programs(1) - 1)
    def _finish():
        gqa_slab_finish(o_ref, l_scr, acc_scr, kv_heads=kv_heads)


def _live_slab(b, k, ids, cnt):
    """Index-map helper: row b's slab id for selection slot k, clamped
    to its last live slot (dead slots re-request a resident slab, so
    no DMA is issued). Reads SMEM element by element — Mosaic loads
    only scalars from SMEM, never a row."""
    return ids[b, jnp.minimum(k, jnp.maximum(cnt[b] - 1, 0))]


def _bsa_call(kernel_fn, q, slabs, pool_ids, blk_pos, counts, pos0s,
              lengths, *, window, interpret):
    """Shared pallas_call plumbing of both twins. slabs: [kb, vb] or
    [kb, ks, vb, vs] with ks/vs [P, Kv, 1]; q enters head-major."""
    B, N, H, dh = q.shape
    _, blk, Kv, _ = slabs[0].shape
    K = pool_ids.shape[1]
    assert H % Kv == 0

    def slab_spec(a):
        block = (1,) + a.shape[1:]
        zeros = (0,) * (a.ndim - 1)
        return pl.BlockSpec(
            block, lambda b, k, ids, bp, cnt, p0, ln:
            (_live_slab(b, k, ids, cnt),) + zeros)

    kernel = pl.pallas_call(
        functools.partial(kernel_fn, kv_heads=Kv,
                          scale=1.0 / (dh ** 0.5), window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, K),
            in_specs=[pl.BlockSpec((1, H, N, dh),
                                   lambda b, k, ids, bp, cnt, p0, ln:
                                   (b, 0, 0, 0))]
                     + [slab_spec(a) for a in slabs],
            out_specs=pl.BlockSpec((1, H, N, dh),
                                   lambda b, k, ids, bp, cnt, p0, ln:
                                   (b, 0, 0, 0)),
            scratch_shapes=gqa_slab_scratch(H, N, dh, Kv),
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, N, dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )
    o = kernel(jnp.asarray(pool_ids, jnp.int32),
               jnp.asarray(blk_pos, jnp.int32),
               jnp.asarray(counts, jnp.int32),
               jnp.asarray(pos0s, jnp.int32),
               jnp.asarray(lengths, jnp.int32), q.transpose(0, 2, 1, 3),
               *slabs)
    return o.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def block_sparse_prefill(q, kb, vb, pool_ids, blk_pos, counts, pos0s,
                         lengths, *, window: int | None = None,
                         interpret: bool = False):
    """q: [B, N, H, dh] (RoPE applied); kb/vb: [P, blk, Kv, dh] pooled
    K/V slabs; pool_ids: [B, K] int32 slab ids into the pool (live
    prefix first); blk_pos: [B, K] int32 absolute start position of
    each selected slab; counts: [B] int32 live selection slots;
    pos0s: [B] int32 query-block offsets; lengths: [B] int32 valid key
    counts. Returns [B, N, H, dh] float32."""
    return _bsa_call(_bsa_kernel, q, [kb, vb], pool_ids, blk_pos, counts,
                     pos0s, lengths, window=window, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def block_sparse_prefill_quant(q, kb, ks, vb, vs, pool_ids, blk_pos,
                               counts, pos0s, lengths, *,
                               window: int | None = None,
                               interpret: bool = False):
    """Quantized-heap twin of block_sparse_prefill: kb/vb are int8
    [P, blk, Kv, dh] pooled slabs with f32 scales ks/vs [P, Kv]
    (kernels/kv_quant scheme, slab granularity = page size). The scale
    slabs ride the SAME clamped index map as their pages, so dead
    selection slots elide the scale DMA together with the page DMA;
    they enter the kernel as [P, Kv, 1] (see kernels/kv_quant)."""
    P, _, Kv, _ = kb.shape
    assert ks.shape == vs.shape == (P, Kv)
    return _bsa_call(_bsa_kernel_quant, q,
                     [kb, ks.reshape(P, Kv, 1), vb, vs.reshape(P, Kv, 1)],
                     pool_ids, blk_pos, counts, pos0s, lengths,
                     window=window, interpret=interpret)
