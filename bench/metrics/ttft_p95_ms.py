"""95th percentile of time to first token over every request due in the
window; one with no first token by the drain cap counts as infinitely
late."""
from bench.stats import percentile, ttfts_s


def read(ctx):
    return 1e3 * percentile(ttfts_s(ctx.window), 95)
