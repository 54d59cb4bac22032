"""Load generator: how late requests were submitted after they were due
(host clock), 99th percentile over the window's requests."""
from bench.stats import percentile


def read(ctx):
    lags = [t.submitted - t.due for t in ctx.window.tracks.values()
            if t.submitted is not None]
    return 1e3 * percentile(lags, 99) if lags else None
