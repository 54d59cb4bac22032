"""Prompt tokens of the prefill blocks dispatched in the window (the
window ends when the device has finished them) over its seconds."""


def read(ctx):
    n = sum(r[1] for c in ctx.window.calls if c.kind == "prefill"
            for r in c.rows)
    return n / ctx.window.seconds
