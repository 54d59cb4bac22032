"""Jit'd public wrapper around the paged-attention decode kernel.

Backend dispatch rule (mirrors kernels/sparse_ffn/ops.py and
kernels/grouped_matmul/ops.py — the paged serving decode path relies on
this):

  * TPU -> Pallas paged-decode kernel (page tables + decode positions
           scalar-prefetched, one K/V page slab DMA per grid step,
           online softmax over the page axis);
  * XLA -> gather-based page-table attention (``ref.paged_attention_ref``
           — gathers each row's pages into a contiguous view and runs
           the exact ragged-decode GQA core, so off-TPU the paged
           serving engine is bit-identical to the slot-pool engine);
  * ``use_kernel=True`` off-TPU forces the interpret-mode kernel (tests
           cross-check it against both oracles in ref.py).
"""
from __future__ import annotations

from repro.kernels.backend import on_tpu
from repro.kernels.paged_attention import kernel as K
from repro.kernels.paged_attention import ref as R


def paged_attention_op(q, k_pages, v_pages, page_table, positions, *,
                       window=None, use_kernel: bool | None = None):
    """Paged decode attention. q: [B, H, dh] (RoPE applied);
    k_pages/v_pages: [n_pages, psz, Kv, dh], or the int8-quantized heap
    ({"q": int8 pages, "s": f32 [n_pages, Kv]}, kernels/kv_quant) —
    the kernel branch dispatches the fused-dequant quant twin, the XLA
    branch dequantizes inside the table-directed gather
    (nn.attention.gather_pages); page_table: [B, max_pages] int32;
    positions: [B] int32. Returns [B, H, dh] float32."""
    if use_kernel is None:
        use_kernel = on_tpu()
    if use_kernel:
        if isinstance(k_pages, dict):
            return K.paged_decode_attention_quant(
                q, k_pages["q"], k_pages["s"], v_pages["q"],
                v_pages["s"], page_table, positions, window=window,
                interpret=not on_tpu())
        return K.paged_decode_attention(q, k_pages, v_pages, page_table,
                                        positions, window=window,
                                        interpret=not on_tpu())
    return R.paged_attention_ref(q, k_pages, v_pages, page_table,
                                 positions, window=window)
