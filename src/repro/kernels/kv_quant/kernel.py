"""Pallas TPU kernels: fused int8 page quantize / dequantize.

House pattern (see kernels/sparse_ffn): one grid step per page, the
whole [psz, Kv, dh] page slab resident in VMEM, scale reduction and
int8 cast fused in a single pass — the page never round-trips HBM
between the absmax reduction and the cast, which is the point of
fusing (an XLA twin materializes the f32 normalized page in HBM).

Quantization semantics are EXACTLY ref.quantize_pages_ref /
ref.dequantize_pages_ref (symmetric per-(page, kv-head), zero pages
keep scale 0); tests cross-check the interpret-mode kernels against
the oracles bit-exactly.

Scales cross the kernel boundary as [P, Kv, 1] (a free reshape of the
heap's [P, Kv]): a (1, Kv, 1) block keeps its last two dims equal to
the array's, as the TPU tiling rule asks, and its [Kv, 1] value
broadcasts straight against a [psz, Kv, dh] page.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.kv_quant.ref import INV_127


def _quantize_kernel(x_ref, q_ref, s_ref):
    x = x_ref[0].astype(jnp.float32)                  # [psz, Kv, dh]
    absmax = jnp.max(jnp.max(jnp.abs(x), axis=2, keepdims=True),
                     axis=0)                          # [Kv, 1]
    s = absmax * INV_127          # reciprocal multiply, same as the ref
    safe = jnp.where(s > 0, s, 1.0)
    q = jnp.clip(jnp.round(x / safe[None]), -127, 127)
    q_ref[0] = q.astype(jnp.int8)
    s_ref[0] = s


def _dequantize_kernel(q_ref, s_ref, o_ref):
    o_ref[0] = q_ref[0].astype(jnp.float32) * s_ref[0][None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_pages(x, *, interpret: bool = False):
    """[P, psz, Kv, dh] -> (q int8 [P, psz, Kv, dh], s f32 [P, Kv]),
    one fused absmax+cast pass per page."""
    P, psz, Kv, dh = x.shape
    kernel = pl.pallas_call(
        _quantize_kernel,
        grid=(P,),
        in_specs=[pl.BlockSpec((1, psz, Kv, dh), lambda p: (p, 0, 0, 0))],
        out_specs=(
            pl.BlockSpec((1, psz, Kv, dh), lambda p: (p, 0, 0, 0)),
            pl.BlockSpec((1, Kv, 1), lambda p: (p, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((P, psz, Kv, dh), jnp.int8),
            jax.ShapeDtypeStruct((P, Kv, 1), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )
    q, s = kernel(x)
    return q, s.reshape(P, Kv)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequantize_pages(q, s, *, interpret: bool = False):
    """(q int8 [P, psz, Kv, dh], s f32 [P, Kv]) -> f32 pages, fused
    cast+scale per page."""
    P, psz, Kv, dh = q.shape
    kernel = pl.pallas_call(
        _dequantize_kernel,
        grid=(P,),
        in_specs=[
            pl.BlockSpec((1, psz, Kv, dh), lambda p: (p, 0, 0, 0)),
            pl.BlockSpec((1, Kv, 1), lambda p: (p, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, psz, Kv, dh), lambda p: (p, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((P, psz, Kv, dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )
    return kernel(q, s.reshape(P, Kv, 1))
