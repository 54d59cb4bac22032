"""Device: share of the traced window in which no operation ran."""
from bench import trace as TR


def read(ctx):
    return 100.0 * (1.0 - TR.busy_s(ctx.trace) / ctx.trace_s)
