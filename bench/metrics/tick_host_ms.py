"""Scheduler: the host's own time per tick. Mean over the traced
`sched.tick` spans of their duration less their `sched.readback`
spans, which wait for the device."""
from bench import program_spans as PS


def read(ctx):
    own = PS.tick_host_s(PS.recorded(ctx))
    return 1e3 * sum(own) / len(own) if own else None
