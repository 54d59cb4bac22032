"""Process start to the window's start: imports, weights, pool, warmup
(compiling or loading every program the window runs), traffic."""


def read(ctx):
    return ctx.setup_s
