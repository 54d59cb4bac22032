"""Jit'd wrappers: batched GQA flash attention over [B,T,H,dh] layouts."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.backend import on_tpu
from repro.kernels.flash_attention import kernel as K
from repro.kernels.flash_attention import ref as R


def mha_flash_rows(q, k, v, pos0s, lengths, *, window=None,
                   use_kernel: bool | None = None,
                   interpret: bool | None = None):
    """Per-row-offset batched GQA block-prefill attention — the
    kernel-backed dense baseline of the serving path (`attend_block_
    rows` routes here on TPU; off-TPU its masked-gather math is the
    fallback). q: [B, N, H, dh] (RoPE applied); k, v: [B, S, Kv, dh];
    pos0s, lengths: [B] int32. Returns [B, N, H, dh] f32.

    S is padded to a block_k multiple for the kernel (padded keys are
    masked by `lengths`)."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if interpret is None:
        interpret = not on_tpu()
    if use_kernel:
        block_k = q.shape[1]
        pad = (-k.shape[1]) % block_k
        if pad:
            cfgpad = ((0, 0), (0, pad), (0, 0), (0, 0))
            k = jnp.pad(k, cfgpad)
            v = jnp.pad(v, cfgpad)
        return K.flash_attention_rows(q, k, v, pos0s, lengths,
                                      block_k=block_k, window=window,
                                      interpret=interpret)
    # gather fallback: masked grouped-GQA softmax over the full cache
    B, N, H, dh = q.shape
    S, Kv = k.shape[1], k.shape[2]
    rep = H // Kv
    qg = q.astype(jnp.float32).reshape(B, N, Kv, rep, dh)
    s = jnp.einsum("bngrd,bsgd->bgrns", qg, k.astype(jnp.float32))
    s = s / (dh ** 0.5)
    qpos = pos0s[:, None] + jnp.arange(N)[None, :]
    kj = jnp.arange(S)[None, None, :]
    mask = (kj <= qpos[:, :, None]) & (kj < lengths[:, None, None])
    if window:
        mask = mask & (kj > qpos[:, :, None] - window)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrns,bsgd->bngrd", p, v.astype(jnp.float32))
    return o.reshape(B, N, H, dh)


def mha_flash(q, k, v, *, causal=True, q_offset=0, window=None,
              use_kernel: bool | None = None, interpret: bool | None = None):
    """q: [B,T,H,dh]; k,v: [B,S,Kv,dh] (GQA: H % Kv == 0). Returns
    [B,T,H,dh] f32."""
    B, T, H, dh = q.shape
    Kv = k.shape[2]
    rep = H // Kv
    if use_kernel is None:
        use_kernel = on_tpu()
    if interpret is None:
        interpret = not on_tpu()
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, T, dh)
    kh = jnp.repeat(k.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, -1, dh)
    vh = jnp.repeat(v.transpose(0, 2, 1, 3), rep, axis=1).reshape(B * H, -1, dh)
    if use_kernel:
        fn = lambda qq, kk, vv: K.flash_attention(
            qq, kk, vv, causal=causal, q_offset=q_offset, window=window,
            interpret=interpret)
    else:
        fn = lambda qq, kk, vv: R.attention_ref(
            qq, kk, vv, causal=causal, q_offset=q_offset, window=window)
    o = jax.vmap(fn)(qh, kh, vh)
    return o.reshape(B, H, T, dh).transpose(0, 2, 1, 3)
