"""Kernels: the prefill attention kernel of both KV layouts,
`flash_attention_rows`. The least time its causal work needs at the
chip's peaks (`attn_prefill_kernel_work` of the architecture's module,
bench/archs), over the kernel's summed device time, traced window. One
kernel execution per layer per prefill call, paired as in
`sparse_ffn_roofline`; where the trace holds another count the reading
is left out."""
from bench import trace as TR
from bench.metrics.prefill_step_ms import PREFILL

KERNEL = "flash_attention_rows"
OPERANDS = ("pos0s", "lengths", "q", "k", "v")


def read(ctx):
    return TR.kernel_roofline(ctx, PREFILL, KERNEL, OPERANDS,
                              ctx.arch.attn_prefill_kernel_work,
                              lambda rows: bool(rows))
