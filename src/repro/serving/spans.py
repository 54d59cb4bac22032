"""Host spans of the serving path, on the device trace's clock.

A span is one interval of host work: `(name, start, end, parent, args)`,
where `start` and `end` come from the scheduler's clock, `parent` is the
index (in the list `drain()` returns) of the span it was opened inside,
or None, and `args` holds its arguments (a request's `rid`, a readback's
`kind`). Every name starts with `sched.` (a phase of a tick, or
`submit`) or `req.` (a request's queued, prefill and decode intervals).

Spans are on while `enable()` holds, or while a JAX profiler trace runs:
the scheduler checks which at the top of each tick (`step`). A profile
that starts while spans are off begins a fresh record, so spans no
`drain()` took from an earlier profile are dropped. When on, each
`span` also enters a `jax.profiler.TraceAnnotation` of its name, so it
appears on the profiler's host plane, on the clock of the device
operations it waited for. When off, `span` returns one shared object
after a single flag check: no allocation, no clock read.

`emit` records a span that has already ended (the request spans, whose
ends the scheduler stamps as it goes); those reach the list only, with
no parent.
"""
from __future__ import annotations

import time

from jax.profiler import StepTraceAnnotation, TraceAnnotation

_on = False          # record spans now
_forced = False      # enable() was called
_clock = time.perf_counter
_spans: list = []    # finished spans; None marks one still open
_open: list = []     # indices of the open spans, innermost last


class _Off:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("name", "args", "step", "ann", "idx", "parent", "start")

    def __init__(self, name, args, step):
        self.name, self.args, self.step = name, args, step

    def __enter__(self):
        self.ann = (StepTraceAnnotation(self.name, step_num=self.step)
                    if self.step is not None
                    else TraceAnnotation(self.name, **self.args))
        self.ann.__enter__()
        self.idx = len(_spans)
        self.parent = _open[-1] if _open else None
        _spans.append(None)
        _open.append(self.idx)
        self.start = _clock()
        return self

    def __exit__(self, *exc):
        end = _clock()
        _open.pop()
        _spans[self.idx] = (self.name, self.start, end, self.parent,
                            self.args)
        return self.ann.__exit__(*exc)


def span(name: str, **args):
    """Context manager: one span around the block."""
    if not _on:
        return _OFF
    return _Span(name, args, None)


def step(name: str, step_num: int, clock):
    """The span of one scheduler tick (a `StepTraceAnnotation` numbered
    `step_num`). Decides whether spans are on until the next tick, and
    makes `clock` theirs."""
    global _on, _clock, _spans
    was_on = _on
    _on = _forced or TraceAnnotation.is_enabled()
    if not _on:
        return _OFF
    if not was_on and not _forced:
        _spans = []
    _clock = clock
    return _Span(name, {"step_num": step_num}, step_num)


def emit(name: str, start: float, end: float, **args) -> None:
    """Record a span that ran from `start` to `end` (scheduler clock)."""
    if _on:
        _spans.append((name, start, end, None, args))


def enable() -> None:
    """Record spans from now on."""
    global _on, _forced
    _on = _forced = True


def disable() -> None:
    """Record no more spans, unless a profiler trace runs."""
    global _on, _forced
    _on = _forced = False


def drain() -> list:
    """The spans recorded so far, in the order they were opened (an
    emitted one: when it was emitted); the record is emptied. Call it
    between ticks, with no span open."""
    global _spans
    if _open:
        raise RuntimeError(f"drain() inside {len(_open)} open span(s)")
    out, _spans = _spans, []
    return out
