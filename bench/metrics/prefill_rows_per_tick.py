"""Scheduler: prefill blocks per prefill tick over the window (the
scheduler's own counters)."""


def read(ctx):
    c = ctx.window.counters
    if not c["n_prefill_ticks"]:
        return None
    return c["n_prefill_blocks"] / c["n_prefill_ticks"]
