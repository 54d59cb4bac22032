"""Runtime: device time of one execution of the jitted decode entry
(decode_step or decode_step_paged), traced window."""
from bench import trace as TR

DECODE = r"_decode(_paged)?_impl"


def read(ctx):
    mods = TR.modules(ctx.trace, DECODE)
    return 1e3 * sum(m[2] for m in mods) / 1e9 / len(mods) if mods else None
