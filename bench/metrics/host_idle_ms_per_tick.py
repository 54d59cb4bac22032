"""Scheduler: device idle time the scheduler's host work causes, per
tick. The device's idle gaps in the traced window, cut at the program
spans' edges, whose innermost open span is a `sched.*` span other than
`sched.readback` (which waits on the device), summed, over the traced
`sched.tick` count."""
from bench import program_spans as PS


def read(ctx):
    spans = PS.recorded(ctx)
    ticks = len(PS.ticks_of(spans))
    idle = PS.idle_by_span(ctx.trace, spans) if ticks else None
    if idle is None:
        return None
    host = sum(ns for name, ns in idle.items()
               if name not in ("none", PS.READBACK))
    return host / 1e6 / ticks
