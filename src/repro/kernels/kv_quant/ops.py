"""Jit'd public wrappers around the KV-quantization kernels.

Backend dispatch rule (same as kernels/sparse_ffn/ops.py — the paged
serving write/gather paths rely on this):

  * TPU -> fused Pallas quantize/dequantize kernels (one VMEM pass per
           page, no HBM round trip between reduction and cast);
  * XLA -> ref oracles (interpret-mode Pallas is far slower than XLA
           on host, so off-TPU the oracle IS the serving path);
  * ``use_kernel=True`` off-TPU forces the interpret-mode kernel
    (tests cross-check it bit-exactly against the oracle).
"""
from __future__ import annotations

from repro.kernels.backend import on_tpu
from repro.kernels.kv_quant import kernel as K
from repro.kernels.kv_quant import ref as R


def quantize_pages_op(x, use_kernel: bool | None = None):
    """[P, psz, Kv, dh] -> (q int8, s f32 [P, Kv]); see ref.py for the
    quantization scheme and error contract."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if use_kernel:
        return K.quantize_pages(x, interpret=not on_tpu())
    return R.quantize_pages_ref(x)


def dequantize_pages_op(q, s, use_kernel: bool | None = None):
    """(q int8 [P, psz, Kv, dh], s f32 [P, Kv]) -> f32 pages."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if use_kernel:
        return K.dequantize_pages(q, s, interpret=not on_tpu())
    return R.dequantize_pages_ref(q, s)
