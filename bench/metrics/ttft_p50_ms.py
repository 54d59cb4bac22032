"""Median time to first token: due time to the host holding the token."""
from bench.stats import percentile, ttfts_s


def read(ctx):
    return 1e3 * percentile(ttfts_s(ctx.window), 50)
