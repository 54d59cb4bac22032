"""95th percentile of every gap between successive output tokens, over
all requests due in the window (not a median of per-request figures)."""
from bench.stats import itl_s, percentile


def read(ctx):
    gaps = itl_s(ctx.window)
    return 1e3 * percentile(gaps, 95) if gaps else None
