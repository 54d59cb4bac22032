"""The program's own spans (repro.serving.spans) in a traced run.

The scheduler records spans while a profiler trace runs, on its own
clock: `sched.*` for the phases of a tick, `req.*` for a request's
queued, prefill and decode intervals. A reader drains them from the
program once per window. The benchmark's `tick` annotation
(bench/loop.py) encloses each `sched.tick` span, so the two pair in
order and give the offset from the program's clock to the trace's.

Where the program records no span (it has no recorder, or no trace
ran), the readers here return None: there is nothing to read.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional

from bench import trace as TR

TICK, READBACK = "sched.tick", "sched.readback"

_drained = None        # (window, its spans): one drain per window


def recorded(ctx) -> List[tuple]:
    """The spans the program recorded in the window of `ctx`:
    (name, start_s, end_s, parent index, args) in the order they
    opened; [] where the program has no span recorder."""
    global _drained
    if _drained is None or _drained[0] is not ctx.window:
        try:
            from repro.serving import spans
        except ImportError:
            return []
        _drained = (ctx.window, spans.drain())
    return _drained[1]


def ticks_of(spans) -> List[int]:
    return [i for i, s in enumerate(spans) if s[0] == TICK]


def offset_ns(trace: TR.Trace, spans) -> Optional[float]:
    """Program clock (seconds) to trace clock (ns): the median, over the
    `sched.tick` spans paired in order with the benchmark's `tick`
    annotations, of annotation start less span start. Where the counts
    differ by a few (a tick at either edge of the trace), the pairing
    whose durations agree best is taken; None where nothing pairs."""
    ticks = [spans[i] for i in ticks_of(spans)]
    anns = sorted((h for h in trace.host if h[0] == "tick"),
                  key=lambda h: h[1])
    short, long_ = sorted((ticks, anns), key=len)
    extra = len(long_) - len(short)
    if not short or extra > 3:
        return None
    best = None
    for skip in range(extra + 1):
        part = long_[skip:skip + len(short)]
        t, a = (short, part) if short is ticks else (part, short)
        miss = sum(abs(x[2] - (y[2] - y[1]) * 1e9) for x, y in zip(a, t))
        if best is None or miss < best[0]:
            best = (miss, t, a)
    _, t, a = best
    diffs = sorted(x[1] - y[1] * 1e9 for x, y in zip(a, t))
    return diffs[len(diffs) // 2]


def _innermost(intervals, points) -> List[Optional[str]]:
    """For each of the sorted `points`, the name of the innermost of the
    properly nested `intervals` ((start, end, name), sorted by start,
    outer first) that holds it; None where none does."""
    out, stack, i = [], [], 0
    for m in points:
        while i < len(intervals) and intervals[i][0] <= m:
            iv = intervals[i]
            while stack and stack[-1][1] <= iv[0]:
                stack.pop()
            stack.append(iv)
            i += 1
        while stack and stack[-1][1] <= m:
            stack.pop()
        out.append(stack[-1][2] if stack else None)
    return out


def idle_by_span(trace: TR.Trace, spans) -> Optional[Dict[str, int]]:
    """Device idle time (ns, first chip: `TR.gaps_ns`) by the innermost
    `sched.*` span open over it: each gap is cut at the spans' edges and
    each piece named by the innermost span open in it; "none" where no
    program span is open. None where the spans do not pair with the
    trace. (A gap often runs from the end of a read of device output
    into the next call's dispatch: naming it whole by its midpoint
    would give it to either side.)"""
    off = offset_ns(trace, spans)
    if off is None:
        return None
    ivs = sorted(((s[1] * 1e9 + off, s[2] * 1e9 + off, s[0])
                  for s in spans if s[0].startswith("sched.")),
                 key=lambda iv: (iv[0], -iv[1]))
    edges = sorted({t for iv in ivs for t in iv[:2]})
    pieces = []
    for a, b in sorted(TR.gaps_ns(trace)):
        cuts = ([a] + edges[bisect.bisect_right(edges, a):
                            bisect.bisect_left(edges, b)] + [b])
        pieces += zip(cuts, cuts[1:])
    names = _innermost(ivs, [(a + b) / 2 for a, b in pieces])
    out: Dict[str, int] = {}
    for (a, b), name in zip(pieces, names):
        out[name or "none"] = out.get(name or "none", 0) + (b - a)
    return out


def tick_host_s(spans) -> List[float]:
    """Per `sched.tick` span: its duration less that of the
    `sched.readback` spans inside it (seconds)."""
    ticks = ticks_of(spans)
    wait = dict.fromkeys(ticks, 0.0)
    for s in spans:
        if s[0] != READBACK:
            continue
        top = s[3]
        while top is not None and spans[top][0] != TICK:
            top = spans[top][3]
        if top is not None:
            wait[top] += s[2] - s[1]
    return [spans[i][2] - spans[i][1] - wait[i] for i in ticks]

