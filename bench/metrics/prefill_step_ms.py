"""Runtime: device time of one execution of a jitted prefill entry
(prefill_block, prefill_blocks, prefill_blocks_paged), traced window."""
from bench import trace as TR

PREFILL = r"_prefill_blocks?(_paged)?_impl"


def read(ctx):
    mods = TR.modules(ctx.trace, PREFILL)
    return 1e3 * sum(m[2] for m in mods) / 1e9 / len(mods) if mods else None
