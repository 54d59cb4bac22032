"""Kernels: `sparse_ffn_batched` in prefill. The least time its work
needs at the chip's peaks (`sparse_ffn_kernel_work` of the
architecture's module, bench/archs), over the kernel's summed device
time, traced window. The traced prefill calls are paired with
the prefill programs' executions in order; each call that ran a sparse
row runs the kernel once per layer, and each kernel execution's bytes
leave out the operands its name shows in on-chip memory. Where the
trace holds other counts, the reading is not sound and is left out."""
from bench import trace as TR
from bench.metrics.prefill_step_ms import PREFILL

KERNEL = "sparse_ffn_batched"
OPERANDS = ("ids", "cnt", "x", "wg", "wu", "wd")


def read(ctx):
    return TR.kernel_roofline(ctx, PREFILL, KERNEL, OPERANDS,
                              ctx.arch.sparse_ffn_kernel_work,
                              lambda rows: any(not r[2] for r in rows))
