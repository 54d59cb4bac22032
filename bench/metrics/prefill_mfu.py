"""Model step: prefill model operations of the calls in the traced
window (`prefill_row_flops` of the architecture's module, bench/archs:
for the dense one projections, causal attention, the FFN as the plan
runs it, predictor, compensator, the output head at each prompt's last
token) over the traced window's seconds at the chip's bf16 peak."""


def read(ctx):
    ops = sum(ctx.arch.prefill_row_flops(ctx.shapes, *r)
              for c in ctx.traced_calls if c.kind == "prefill"
              for r in c.rows)
    if not ops:
        return None
    return 100.0 * ops / (ctx.trace_s * ctx.peak["flops_bf16"])
