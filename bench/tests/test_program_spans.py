"""The readers of the program's spans, on a small trace and span list
written out by hand."""
import types

import pytest

from bench import program_spans as PS
from bench import trace as TR
from bench.metrics import host_idle_ms_per_tick, tick_host_ms

# a prefill program (a scan: `while` holding three ops) and a decode
# program; ns on the trace's clock
MODS = [("jit__prefill_blocks_impl", 0, 100, ""),
        ("jit__decode_impl", 300, 50, "")]
OPS = [("%gather.1", 0, 10, ""), ("%while.4", 10, 80, ""),
       ("%dynamic-slice_bitcast_fusion.14", 12, 20, ""),
       ("%fusion.2", 32, 18, ""), ("%sparse_ffn_batched.3", 50, 30, ""),
       ("%fusion.9", 90, 10, ""), ("%fusion.5", 300, 40, ""),
       ("%convert_fusion.1", 340, 10, "")]
# the benchmark's annotations: two ticks, each around a program tick
HOST = [("tick", 95, 130, ""), ("readback_decode", 200, 20, ""),
        ("tick", 395, 60, "")]
# program spans on its own clock (seconds): the trace's clock less 1 s
S = 1e-9
SPANS = [
    ("sched.tick", 1 + 100 * S, 1 + 220 * S, None, {"step_num": 1}),
    ("sched.admit", 1 + 100 * S, 1 + 110 * S, 0, {}),
    ("sched.prefill.dispatch", 1 + 110 * S, 1 + 150 * S, 0, {}),
    ("sched.emit", 1 + 150 * S, 1 + 215 * S, 0, {}),
    ("sched.readback", 1 + 198 * S, 1 + 212 * S, 3, {"kind": "prefill"}),
    ("req.queued", 0.5, 1 + 105 * S, None, {"rid": 7}),
    ("req.queued", 0.25, 0.75, None, {"rid": 8}),
    ("req.queued", 0.9, 1.0, None, {"rid": 8}),
    ("req.queued", 0.0, 1.0, None, {"rid": -1}),
    ("sched.tick", 1 + 400 * S, 1 + 450 * S, None, {"step_num": 2}),
]


def trace():
    return TR.Trace([{TR.MODULES: MODS, TR.OPS: OPS}], HOST, (0, 450))


def ctx(spans=SPANS, ops=OPS):
    window = types.SimpleNamespace()
    PS._drained = (window, list(spans))
    return types.SimpleNamespace(
        window=window,
        trace=TR.Trace([{TR.MODULES: MODS, TR.OPS: ops}], HOST, (0, 450)))


def test_offset_pairs_program_ticks_with_annotations():
    assert PS.offset_ns(trace(), SPANS) == pytest.approx(-1e9 - 5)
    # an annotation the program has no tick for, at either edge
    host = [("tick", 0, 20, "")] + HOST
    t = TR.Trace(trace().devices, host, (0, 450))
    assert PS.offset_ns(t, SPANS) == pytest.approx(-1e9 - 5)
    assert PS.offset_ns(trace(), []) is None


def test_idle_split_by_the_innermost_program_span():
    # one device gap, 100-300, cut at the program spans' edges (trace
    # clock: admit 95-105, dispatch 105-145, emit 145-210 holding the
    # readback 193-207, tick to 215)
    idle = PS.idle_by_span(trace(), SPANS)
    assert idle == {"sched.admit": 5, "sched.prefill.dispatch": 40,
                    "sched.emit": 51, PS.READBACK: 14, PS.TICK: 5,
                    "none": 85}
    # a device op at 150-160 splits the gap in two
    ops = OPS + [("%fusion.7", 150, 10, "")]
    t = TR.Trace([{TR.MODULES: MODS, TR.OPS: ops}], HOST, (0, 450))
    assert PS.idle_by_span(t, SPANS) == {
        "sched.admit": 5, "sched.prefill.dispatch": 40, "sched.emit": 41,
        PS.READBACK: 14, PS.TICK: 5, "none": 85}
    assert host_idle_ms_per_tick.read(ctx()) \
        == pytest.approx((5 + 40 + 51 + 5) / 1e6 / 2)
    assert host_idle_ms_per_tick.read(ctx(ops=ops)) \
        == pytest.approx((5 + 40 + 41 + 5) / 1e6 / 2)


def test_tick_host_time_less_readback():
    assert PS.tick_host_s(SPANS) == pytest.approx([106 * S, 50 * S])
    assert tick_host_ms.read(ctx()) == pytest.approx(1e3 * 78 * S)


def test_a_program_without_spans_gives_no_reading():
    for reader in (tick_host_ms, host_idle_ms_per_tick):
        assert reader.read(ctx(spans=[])) is None
