"""FastForward: share of live prefill rows that ran the sparse FFN (not
a dense first or last block), over the window's prefill calls."""


def read(ctx):
    rows = [r for c in ctx.window.calls if c.kind == "prefill"
            for r in c.rows]
    if not rows:
        return None
    return 100.0 * sum(not r[2] for r in rows) / len(rows)
