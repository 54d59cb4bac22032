"""From a profiler trace to numbers: device busy time, time per jitted
entry, time per Pallas kernel, and what the host did in device idle
gaps.

A trace is reduced to plain event lists first (`Trace`), which can be
written to JSON and read back, so the reduction is tested on a small
recorded trace without a chip.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import time
from typing import Dict, List, Optional, Tuple

import jax

from bench.flops import least_time

Event = Tuple[str, int, int, str]   # (name, start_ns, duration_ns, label)

#: device-plane lines: jitted programs, and the operations inside them
MODULES, OPS = "XLA Modules", "XLA Ops"


@dataclasses.dataclass
class Trace:
    devices: List[Dict[str, List[Event]]]   # per chip: line -> events
    host: List[Event]                       # benchmark annotations
    window_ns: Tuple[int, int]              # traced window, trace clock

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        ev = lambda es: [tuple(e) for e in es]
        return cls([{k: ev(v) for k, v in dev.items()}
                    for dev in d["devices"]], ev(d["host"]),
                   tuple(d["window_ns"]))


class Profiler:
    """Traces the window from `start_s` after its start to its end. The
    device is synced when tracing starts (and the window syncs at its
    end), so the trace holds whole calls: every call dispatched while
    tracing ran while tracing, and no other."""

    def __init__(self, directory: str, start_s: float, sync):
        self.dir, self.start_s, self.sync = directory, start_s, sync
        self.t_start: Optional[float] = None
        self.t_stop: Optional[float] = None

    def poll(self, now: float, t0: float) -> None:
        if self.t_start is None and now >= t0 + self.start_s:
            self.sync()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0   # no event per Python call
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t_start = time.perf_counter()

    def stop(self) -> None:
        """At the window's end, after the device is synced."""
        if self.t_start is None or self.t_stop is not None:
            return
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    @property
    def seconds(self) -> float:
        return self.t_stop - self.t_start


ANNOTATIONS = ("tick", "prefill_call", "decode_call", "readback_prefill",
               "readback_decode", "idle_wait")


def _label(ev) -> str:
    """Every text stat of an event (HLO op, its long name, the source
    op_name), where a kernel's `jit(<name>)/pallas_call` shows."""
    return " ".join(v for _, v in ev.stats if isinstance(v, str))


def load(directory: str) -> Trace:
    """Read the newest trace under `directory`."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(directory, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no trace under {directory}")
    pd = ProfileData.from_file(paths[-1])
    devices, host = [], []
    for plane in pd.planes:
        if re.match(r"/device:TPU:\d+$", plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (MODULES, OPS):
                    lines[line.name] = [
                        (e.name, int(e.start_ns), int(e.duration_ns),
                         _label(e)) for e in line.events]
            devices.append(lines)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in ANNOTATIONS:
                        host.append((e.name, int(e.start_ns),
                                     int(e.duration_ns), ""))
    starts = [e[1] for d in devices for es in d.values() for e in es]
    ends = [e[1] + e[2] for d in devices for es in d.values() for e in es]
    window = (min(starts), max(ends)) if starts else (0, 0)
    return Trace(devices, host, window)


def union_ns(events) -> int:
    """Length of the union of the events' intervals."""
    total, end = 0, None
    for _, s, d, _ in sorted(events, key=lambda e: e[1]):
        e = s + d
        if end is None or s >= end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    if not trace.devices:
        return 0.0
    return sum(union_ns(d.get(OPS, [])) for d in trace.devices) \
        / len(trace.devices) / 1e9


def modules(trace: Trace, pattern: str) -> List[Event]:
    """Executions of the jitted programs whose name matches `pattern`
    (first chip)."""
    rx = re.compile(pattern)
    return [e for e in trace.devices[0].get(MODULES, [])
            if rx.search(e[0])] if trace.devices else []


def within(events, outer) -> List[Event]:
    """The events that lie inside one of the `outer` intervals."""
    spans = sorted((s, s + d) for _, s, d, _ in outer)
    out, j = [], 0
    for ev in sorted(events, key=lambda e: e[1]):
        s, e = ev[1], ev[1] + ev[2]
        while j < len(spans) and spans[j][1] < s:
            j += 1
        if j < len(spans) and spans[j][0] <= s and e <= spans[j][1]:
            out.append(ev)
    return out


def kernel_events(trace: Trace, kernel: str) -> List[Event]:
    """Executions of the Pallas kernel `kernel` (first chip): device
    operations whose own HLO instruction is the kernel's custom call,
    named `<kernel>.<n>` (`%<kernel>.<n> = ... custom-call(...)` in the
    long form), or whose source op is `jit(<kernel>)/pallas_call`. The
    other operations of the kernel's jitted wrapper (transposes, casts)
    and the operations that consume its output, whose text names it
    too, are not the kernel."""
    own = re.compile(r"%?" + re.escape(kernel) + r"(\.[\w.]*)?(\s|$)")
    src = re.compile(r"jit\(" + re.escape(kernel) + r"\)/pallas_call(\s|$)")
    return [e for e in trace.devices[0].get(OPS, [])
            if own.match(e[0]) or src.search(e[3])] if trace.devices else []


_SHAPE = re.compile(r"\b\w+\[[\d,]*\]\{([^}]*)\}")


def on_chip_operands(name: str, operands) -> Optional[frozenset]:
    """Which of a custom call's `operands` (its operand names in order,
    then "out") the compiled program placed in on-chip memory, read from
    the long form of the operation's name: `%k.1 = f32[..]{..S(1)}
    custom-call(s32[..]{..} %a, bf16[..]{..S(1)} %b, ...)`, where a
    layout ending in memory space S(1) is on the chip. None where the
    name is not in that form."""
    head, sep, rest = name.partition(" custom-call(")
    if not sep:
        return None
    out = _SHAPE.findall(head)
    ins = _SHAPE.findall(rest.partition("), custom_call_target")[0])
    if len(out) != 1 or len(ins) != len(operands):
        return None
    spaces = list(zip(operands, ins)) + [("out", out[0])]
    return frozenset(k for k, layout in spaces if "S(1)" in layout)


def kernel_roofline(ctx, program: str, kernel: str, operands, work,
                    runs) -> Optional[float]:
    """Percent of its roofline that `kernel` reached over the traced
    window: the least time of its work at the chip's peaks over its
    summed device time. The traced prefill calls (`ctx.traced_calls`, in
    order of dispatch) are paired with the executions of the programs named
    by `program`, in order of start; a call for which `runs(rows)` holds
    must hold one kernel execution per layer, any other none. Each
    execution's work is `work(shapes, rows, on_chip, layer)`, `layer`
    its place among the call's executions (bench/archs).
    None where the counts do not pair up or nothing ran."""
    calls = [c for c in ctx.traced_calls if c.kind == "prefill"]
    mods = sorted(modules(ctx.trace, program), key=lambda e: e[1])
    if not calls or len(mods) != len(calls):
        return None
    events = sorted(kernel_events(ctx.trace, kernel), key=lambda e: e[1])
    starts = [e[1] for e in events]
    least = spent = 0.0
    for call, (_, m0, md, _) in zip(calls, mods):
        inside = [e for e in events[bisect.bisect_left(starts, m0):
                                    bisect.bisect_right(starts, m0 + md)]
                  if e[1] + e[2] <= m0 + md]
        if len(inside) != (ctx.shapes.L if runs(call.rows) else 0):
            return None
        for layer, e in enumerate(inside):
            on_chip = on_chip_operands(e[0], operands) or frozenset()
            least += least_time(*work(ctx.shapes, call.rows, on_chip,
                                      layer), ctx.peak)
            spent += e[2] / 1e9
    return 100.0 * least / spent if spent else None


def top_ops(trace: Trace, n: int = 10):
    """[[name, seconds]] of the device operations that took most time,
    summed by name (first chip)."""
    tot: Dict[str, int] = {}
    for name, _, d, _ in (trace.devices[0].get(OPS, [])
                          if trace.devices else []):
        tot[name] = tot.get(name, 0) + d
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def gaps_ns(trace: Trace):
    """(start, end) of every interval between device operations (first
    chip)."""
    ops = sorted(trace.devices[0].get(OPS, []) if trace.devices else [],
                 key=lambda e: e[1])
    out, end = [], None
    for _, s, d, _ in ops:
        if end is not None and s > end:
            out.append((end, s))
        end = s + d if end is None else max(end, s + d)
    return out


def idle_gaps(trace: Trace, n: int = 10):
    """[[what the host was doing, seconds]]: device idle time between
    operations (first chip), each gap named by the innermost benchmark
    annotation open at its midpoint ("none" where there is none) and
    summed by name, largest first."""
    host = sorted(trace.host, key=lambda h: h[1])
    tot: Dict[str, int] = {}
    for a, b in gaps_ns(trace):
        mid = (a + b) // 2
        open_ = [h for h in host if h[1] <= mid < h[1] + h[2]]
        name = min(open_, key=lambda h: h[2])[0] if open_ else "none"
        tot[name] = tot.get(name, 0) + (b - a)
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def describe(trace: Trace) -> dict:
    """What the trace holds, for the log: chips, events per line and
    host annotation, and the jitted programs that ran (first chip)."""
    mods: Dict[str, int] = {}
    for e in (trace.devices[0].get(MODULES, []) if trace.devices else []):
        mods[e[0]] = mods.get(e[0], 0) + 1
    host: Dict[str, int] = {}
    for h in trace.host:
        host[h[0]] = host.get(h[0], 0) + 1
    return {"chips": len(trace.devices),
            "events": [{k: len(v) for k, v in d.items()}
                       for d in trace.devices],
            "host": host,
            "modules": sorted(mods.items(), key=lambda kv: -kv[1])[:8]}
