"""The measured window: the serving path's own scheduler, driven by the
benchmark's clock.

`Recorder` wraps the runtime's public prefill and decode entries (the
calls `ContinuousBatchingScheduler.tick` makes) without changing what
they compute. It notes each call's rows for the FLOP count and the
moment the host has its result: the scheduler reads prefill logits and
decode token ids with `np.asarray`, and the wrapped result stamps the
clock when that read returns. Those stamps are the times at which
tokens reach the host.

`open_loop` is `serving.scheduler.drive_stream` cut to a window:
requests are submitted when due, each is timed from its due time, and
at the window's end arrivals stop and in-flight requests drain up to a
cap. `closed_loop` keeps a fixed number of clients busy, each sending
its next request when the last one finishes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import numpy as np

clock = time.perf_counter


def _annotate(name: str, on: bool):
    if on:
        return jax.profiler.TraceAnnotation(name)
    return _Null()


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class _Stamped:
    """A device array whose first host read stamps the clock."""

    def __init__(self, arr, rec, kind):
        self._arr, self._rec, self._kind = arr, rec, kind

    def __array__(self, dtype=None, copy=None):
        with _annotate(f"readback_{self._kind}", self._rec.annotate):
            out = np.asarray(self._arr)
        self._rec.readback[self._kind] = clock()
        return out if dtype is None else out.astype(dtype)

    def __getattr__(self, name):
        return getattr(self._arr, name)


@dataclasses.dataclass
class Call:
    kind: str             # "prefill" | "decode"
    t: float              # host clock at dispatch
    rows: list            # prefill: [(pos0, n_tokens, is_dense, final)]
    #                       per live row; decode: [live rows]
    width: int            # rows in the compiled call (live + padding)


class Recorder:
    """Wraps `runtime`'s prefill/decode entries in place."""

    def __init__(self, runtime, block_size: int):
        self.calls: List[Call] = []
        self.readback: Dict[str, float] = {}
        self.annotate = False
        self.N = block_size
        for name in ("prefill_block", "prefill_blocks",
                     "prefill_blocks_paged"):
            setattr(runtime, name, self._prefill(getattr(runtime, name),
                                                 name))
        for name in ("decode_step", "decode_step_paged"):
            setattr(runtime, name, self._decode(getattr(runtime, name)))

    def _prefill(self, fn, name):
        def wrapped(cache, tokens, *args, **kw):
            if name == "prefill_block":
                _slot, pos0, is_dense, length = args[:4]
                pos0s, dense, lens = [pos0], [is_dense], [length]
                active = [True]
            else:       # (slots | tables), pos0s, is_dense, lengths, active
                pos0s, dense, lens, active = args[1:5]
            rows = [(int(p), int(min(self.N, ln - p)), bool(d),
                     bool(p + self.N >= ln))
                    for p, d, ln, a in zip(pos0s, dense, lens, active) if a]
            self.calls.append(Call("prefill", clock(), rows,
                                   len(np.asarray(tokens))))
            with _annotate("prefill_call", self.annotate):
                cache, logits = fn(cache, tokens, *args, **kw)
            return cache, _Stamped(logits, self, "prefill")
        return wrapped

    def _decode(self, fn):
        def wrapped(cache, tokens, *args, **kw):
            # args end with `active`: (positions, active) in the slot
            # layout, (page_table, positions, active) in the paged one
            self.calls.append(Call("decode", clock(),
                                   [int(np.sum(args[-1]))],
                                   len(np.asarray(tokens))))
            with _annotate("decode_call", self.annotate):
                logits, greedy, cache = fn(cache, tokens, *args, **kw)
            return logits, _Stamped(greedy, self, "decode"), cache
        return wrapped


@dataclasses.dataclass
class Track:
    """One request as the client saw it."""
    rid: int
    prompt: list
    due: float                       # absolute host clock
    submitted: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float                     # arrivals stop; device synced
    tracks: Dict[int, Track]
    calls: List[Call]                # calls dispatched inside the window
    counters: Dict[str, int]         # scheduler counters over the window
    drained_at: Optional[float] = None

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0


def _observe(sched, rec, tracks, in_flight, t_tick):
    """After a tick: stamp each request's new tokens with the read that
    brought them to the host (the first token with the prefill logits,
    the rest with decode token ids)."""
    t_pre = rec.readback.get("prefill")
    t_dec = rec.readback.get("decode", t_tick)
    live = {st.req.rid: st for st in sched.active.values()}
    for rid in list(in_flight):
        tr = tracks[rid]
        if rid in live:
            out = live[rid].out
        elif rid in sched.finished:
            out = sched.finished[rid].tokens
            tr.done = True
            in_flight.discard(rid)
        else:
            continue                              # still queued
        for i in range(len(tr.tokens), len(out)):
            first = i == 0 and t_pre is not None
            tr.token_times.append(t_pre if first else t_dec)
        tr.tokens = list(out)


def _sync(sched):
    jax.block_until_ready(sched.pool.cache)


def _tick(sched, rec, tracks, in_flight):
    rec.readback.clear()
    with _annotate("tick", rec.annotate):
        sched.tick()
    _observe(sched, rec, tracks, in_flight, clock())


COUNTERS = ("n_ticks", "n_prefill_ticks", "n_prefill_blocks",
            "n_decode_steps")


def _counters(sched) -> Dict[str, int]:
    return {c: int(getattr(sched, c)) for c in COUNTERS}


def _delta(a, b):
    return {k: b[k] - a[k] for k in a}


def _make_request(p, due):
    from repro.serving.scheduler import Request
    return Request(rid=p.rid, prompt=p.prompt, max_new=p.max_new,
                   arrival_time=due)


def open_loop(sched, rec, planned, seconds, drain_cap_s, profiler=None):
    """Submit each planned request when due (its latency counts from
    then), tick while work is pending, stop arrivals at `seconds`, then
    drain in-flight requests for at most `drain_cap_s`. A profiler
    (bench.trace.Profiler) is polled through the window and stopped at
    its end."""
    tracks: Dict[int, Track] = {}
    in_flight: set = set()
    pending = list(planned)
    t0 = clock()
    n_calls0 = len(rec.calls)
    c0 = _counters(sched)
    i = 0
    t_stop = t0 + seconds
    while True:
        now = clock()
        if now >= t_stop:
            break
        if profiler is not None:
            profiler.poll(now, t0)
        while i < len(pending) and t0 + pending[i].due_s <= now:
            p = pending[i]
            due = t0 + p.due_s
            tracks[p.rid] = Track(p.rid, p.prompt, due)
            sched.submit(_make_request(p, due))
            tracks[p.rid].submitted = clock()
            in_flight.add(p.rid)
            i += 1
        if sched.drained:
            nxt = t0 + pending[i].due_s if i < len(pending) else t_stop
            with _annotate("idle_wait", rec.annotate):
                time.sleep(max(0.0, min(nxt, t_stop) - clock()))
            continue
        _tick(sched, rec, tracks, in_flight)
    _sync(sched)
    t_end = clock()
    if profiler is not None:
        profiler.stop()
    win = Window(t0, t_end, tracks, list(rec.calls[n_calls0:]),
                 _delta(c0, _counters(sched)))
    cap = clock() + drain_cap_s
    while in_flight and clock() < cap:
        _tick(sched, rec, tracks, in_flight)
    win.drained_at = clock() if not in_flight else None
    return win


def closed_loop(sched, rec, planned, seconds, clients, profiler=None):
    """`clients` requests in flight at all times: each finished request
    is replaced at once by the next planned one. Stops at `seconds`."""
    tracks: Dict[int, Track] = {}
    in_flight: set = set()
    pending = iter(planned)

    def send():
        p = next(pending)
        now = clock()
        tracks[p.rid] = Track(p.rid, p.prompt, now)
        sched.submit(_make_request(p, now))
        tracks[p.rid].submitted = clock()
        in_flight.add(p.rid)

    t0 = clock()
    n_calls0 = len(rec.calls)
    c0 = _counters(sched)
    for _ in range(clients):
        send()
    t_stop = t0 + seconds
    while clock() < t_stop:
        if profiler is not None:
            profiler.poll(clock(), t0)
        _tick(sched, rec, tracks, in_flight)
        for _ in range(clients - len(in_flight)):
            send()
    _sync(sched)
    t_end = clock()
    if profiler is not None:
        profiler.stop()
    return Window(t0, t_end, tracks, list(rec.calls[n_calls0:]),
                  _delta(c0, _counters(sched)))
