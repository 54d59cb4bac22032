"""Pallas TPU kernel: causal flash attention (online softmax).

Used for the fused-prefill path (the beyond-paper baseline the blockwise
FastForward prefill is compared against) and for block-cached prefill
attention (q_offset > 0). One (q-block, k-block) grid with f32 running
max / sum / accumulator scratch in VMEM; k-blocks entirely above the
causal diagonal are skipped via pl.when (no MXU work issued).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, block_q, block_k, q_offset, causal, window):
    qi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos0 = q_offset + qi * block_q

    def compute():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            rows = q_pos0 + jax.lax.broadcasted_iota(jnp.int32,
                                                     (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = cols <= rows
            if window:
                mask = mask & (cols > rows - window)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p, v_ref[...].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    if causal:
        # skip k-blocks entirely above the diagonal (or beyond the window)
        first_row = q_pos0
        last_row = q_pos0 + block_q - 1
        k_lo = ki * block_k
        relevant = k_lo <= last_row
        if window:
            relevant = relevant & (k_lo + block_k - 1 > first_row - window)
        pl.when(relevant)(compute)
    else:
        compute()

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_scr[...] /
                      jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def gqa_slab_init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def gqa_slab_step(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr, *, kv_heads,
                  scale, qpos0, kpos0, klen, window, ks_ref=None,
                  vs_ref=None):
    """One online-softmax step of every query head over one K/V slab.

    The layout is the one Mosaic lowers: q_ref is a head-major
    (1, H, N, dh) block, so the rep query heads of kv head g collapse
    into one [rep*N, dh] operand (row r is query token r % N); the slab
    refs are (1, blk, Kv, dh) and head g's [blk, dh] keys are sliced
    from the loaded slab. Every contraction is a plain 2-D dot. Scratch:
    m/l (Kv, rep*N, 1) and acc (Kv, rep*N, dh), f32.

    qpos0/kpos0: absolute positions of query row 0 and slab key 0;
    klen: valid key count. ks_ref/vs_ref: optional (1, Kv, 1) f32
    per-kv-head scales of an int8 slab (kernels/kv_quant scheme),
    applied in VMEM before the dots."""
    _, H, N, dh = q_ref.shape
    blk = k_ref.shape[1]
    rep = H // kv_heads
    rows = rep * N
    qpos = qpos0 + jax.lax.broadcasted_iota(jnp.int32, (rows, blk), 0) % N
    kpos = kpos0 + jax.lax.broadcasted_iota(jnp.int32, (rows, blk), 1)
    mask = (kpos <= qpos) & (kpos < klen)
    if window:
        mask = mask & (kpos > qpos - window)
    if ks_ref is None:
        k_slab, v_slab = k_ref[0], v_ref[0]
    else:
        # dequantize the whole slab at once: a [Kv, 1] scale broadcasts
        # over [blk, Kv, dh]; a per-head scalar scale would not lower
        k_slab = k_ref[0].astype(jnp.float32) * ks_ref[0][None]
        v_slab = v_ref[0].astype(jnp.float32) * vs_ref[0][None]
    for g in range(kv_heads):
        q = (q_ref[0, g * rep:(g + 1) * rep].astype(jnp.float32)
             .reshape(rows, dh) * scale)
        k = k_slab[:, g, :].astype(jnp.float32)             # [blk, dh]
        v = v_slab[:, g, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(mask, s, NEG_INF)                      # [rows, blk]
        m_prev = m_scr[g]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # where-guard: a row whose every key in this slab is masked while
        # the running max is still NEG_INF would compute
        # exp(NEG_INF - NEG_INF) == 1
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[g] = l_scr[g] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[g] = acc_scr[g] * corr + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[g] = m_new


def gqa_slab_finish(o_ref, l_scr, acc_scr, *, kv_heads):
    """Normalize the accumulators into the head-major (1, H, N, dh)
    output block."""
    _, H, N, dh = o_ref.shape
    rep = H // kv_heads
    for g in range(kv_heads):
        o = acc_scr[g] / jnp.maximum(l_scr[g], 1e-30)
        o_ref[0, g * rep:(g + 1) * rep] = (
            o.reshape(rep, N, dh).astype(o_ref.dtype))


def gqa_slab_scratch(H, N, dh, kv_heads):
    rows = (H // kv_heads) * N
    return [pltpu.VMEM((kv_heads, rows, 1), jnp.float32),
            pltpu.VMEM((kv_heads, rows, 1), jnp.float32),
            pltpu.VMEM((kv_heads, rows, dh), jnp.float32)]


def _flash_rows_kernel(p0_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                       m_scr, l_scr, acc_scr, *, block_k, kv_heads,
                       scale, window):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        gqa_slab_init(m_scr, l_scr, acc_scr)

    pos0 = p0_ref[b]
    N = q_ref.shape[2]

    def compute():
        gqa_slab_step(q_ref, k_ref, v_ref, m_scr, l_scr, acc_scr,
                      kv_heads=kv_heads, scale=scale, qpos0=pos0,
                      kpos0=j * block_k, klen=len_ref[b], window=window)

    # skip k-blocks entirely above this ROW's causal front (per-row
    # traced pos0s — the reason the static-q_offset kernel above can't
    # serve the batched multi-request prefill path)
    relevant = j * block_k <= pos0 + N - 1
    if window:
        relevant = relevant & ((j + 1) * block_k - 1 > pos0 - window)
    pl.when(relevant)(compute)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        gqa_slab_finish(o_ref, l_scr, acc_scr, kv_heads=kv_heads)


@functools.partial(jax.jit,
                   static_argnames=("block_k", "window", "interpret"))
def flash_attention_rows(q, k, v, pos0s, lengths, *, block_k: int = 128,
                         window: int | None = None,
                         interpret: bool = False):
    """Per-row-offset batched GQA flash attention (the dense kernel
    behind multi-request block prefill): row b's query block sits at
    absolute positions [pos0s[b], pos0s[b]+N) of its own cache row.

    q: [B, N, H, dh] (RoPE applied); k, v: [B, S, Kv, dh]; pos0s,
    lengths: [B] int32 (scalar-prefetched — they drive the per-row
    causal k-block skip). S % block_k == 0. Returns [B, N, H, dh] f32.
    The kernel runs on head-major q/o (see `gqa_slab_step`); only the
    query block and its output are transposed, never the cache."""
    B, N, H, dh = q.shape
    S, Kv = k.shape[1], k.shape[2]
    assert S % block_k == 0 and H % Kv == 0
    grid = (B, S // block_k)
    kernel = pl.pallas_call(
        functools.partial(_flash_rows_kernel, block_k=block_k,
                          kv_heads=Kv, scale=1.0 / (dh ** 0.5),
                          window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, H, N, dh),
                             lambda b, j, p0, ln: (b, 0, 0, 0)),
                pl.BlockSpec((1, block_k, Kv, dh),
                             lambda b, j, p0, ln: (b, j, 0, 0)),
                pl.BlockSpec((1, block_k, Kv, dh),
                             lambda b, j, p0, ln: (b, j, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, N, dh),
                                   lambda b, j, p0, ln: (b, 0, 0, 0)),
            scratch_shapes=gqa_slab_scratch(H, N, dh, Kv),
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, N, dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )
    o = kernel(jnp.asarray(pos0s, jnp.int32),
               jnp.asarray(lengths, jnp.int32), q.transpose(0, 2, 1, 3),
               k, v)
    return o.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=(
    "block_q", "block_k", "causal", "q_offset", "window", "interpret"))
def flash_attention(q, k, v, *, block_q: int = 128, block_k: int = 128,
                    causal: bool = True, q_offset: int = 0,
                    window: int | None = None, interpret: bool = False):
    """q: [T, dh]; k, v: [S, dh] -> o [T, dh] (f32). T % block_q == 0,
    S % block_k == 0. vmap over (batch, head) from the ops wrapper."""
    T, dh = q.shape
    S = k.shape[0]
    assert T % block_q == 0 and S % block_k == 0
    scale = 1.0 / (dh ** 0.5)
    grid = (T // block_q, S // block_k)
    kernel = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, q_offset=q_offset,
                          causal=causal, window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, dh), lambda qi, ki: (qi, 0)),
            pl.BlockSpec((block_k, dh), lambda qi, ki: (ki, 0)),
            pl.BlockSpec((block_k, dh), lambda qi, ki: (ki, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, dh), lambda qi, ki: (qi, 0)),
        out_shape=jax.ShapeDtypeStruct((T, dh), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )
    return kernel(q, k, v)
