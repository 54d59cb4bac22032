"""Program spans (serving/spans.py): the recorder, and the scheduler's
phase and request spans and prefill row counters on a fake clock."""
import re
import tracemalloc

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.registry import get_model
from repro.nn.param import init_params
from repro.serving import ContinuousBatchingScheduler, Request, spans
from repro.serving.runtime import make_runtime


@pytest.fixture(autouse=True)
def spans_off():
    spans.disable()
    spans.drain()
    yield
    spans.disable()
    spans.drain()


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_config("tinyllama-1.1b", reduced=True)
    params = init_params(get_model(cfg).specs(cfg), jax.random.key(0))
    return cfg, params


def make_prompts(cfg, lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, int(n)).tolist() for n in lengths]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ------------------------------------------------------------- recorder


def traced_memory(loop):
    """(current, peak) bytes tracemalloc sees while `loop` runs."""
    loop()                      # warm the call paths
    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        loop()
        return tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()


def test_off_records_nothing_and_allocates_nothing():
    off = spans.span("sched.admit")

    def calls():
        for _ in range(1000):
            with spans.span("sched.readback", kind="decode"):
                pass
            spans.emit("req.queued", 0.0, 1.0, rid=3)

    def bare():
        for _ in range(1000):
            with off:
                pass

    assert traced_memory(calls) == traced_memory(bare)
    assert spans.span("sched.readback", kind="decode") is off
    clock_reads = []
    step = spans.step("sched.tick", 1, lambda: clock_reads.append(1))
    assert step is off and not clock_reads
    assert spans.drain() == []


def test_on_records_nesting_parents_and_args():
    clk = FakeClock()
    spans.enable()
    with spans.step("sched.tick", 7, clk):
        clk.t = 1.0
        with spans.span("sched.decode.build"):
            clk.t = 2.0
        with spans.span("sched.readback", kind="decode"):
            clk.t = 3.0
            spans.emit("req.prefill", 0.5, 3.0, rid=4)
        clk.t = 4.0
    with pytest.raises(RuntimeError):
        with spans.span("sched.submit", rid=5):
            spans.drain()
    got = spans.drain()
    assert got == [
        ("sched.tick", 0.0, 4.0, None, {"step_num": 7}),
        ("sched.decode.build", 1.0, 2.0, 0, {}),
        ("sched.readback", 2.0, 3.0, 0, {"kind": "decode"}),
        ("req.prefill", 0.5, 3.0, None, {"rid": 4}),
        ("sched.submit", 4.0, 4.0, None, {"rid": 5}),
    ]
    assert spans.drain() == []
    spans.disable()
    with spans.span("sched.admit"):
        pass
    assert spans.drain() == []


def test_spans_follow_a_running_profiler(tmp_path):
    clk = FakeClock()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.step("sched.tick", 1, clk):
            with spans.span("sched.admit"):
                pass
    finally:
        jax.profiler.stop_trace()
    with spans.step("sched.tick", 2, clk):
        pass
    assert [s[0] for s in spans.drain()] == ["sched.tick", "sched.admit"]


def test_a_profile_starts_a_fresh_record(tmp_path):
    clk = FakeClock()

    def profiled_tick(step_num):
        jax.profiler.start_trace(str(tmp_path / str(step_num)))
        try:
            with spans.step("sched.tick", step_num, clk):
                pass
        finally:
            jax.profiler.stop_trace()
        with spans.step("sched.tick", step_num + 1, clk):
            pass

    profiled_tick(1)
    profiled_tick(3)            # nothing drained the first profile's tick
    assert [s[4]["step_num"] for s in spans.drain()] == [3]
    # spans that enable() records stay when a profile starts
    spans.enable()
    with spans.step("sched.tick", 5, clk):
        pass
    profiled_tick(6)
    assert [s[4]["step_num"] for s in spans.drain()] == [5, 6, 7]


def test_span_names_stay_out_of_the_benchmark_annotations():
    from repro.serving import scheduler
    src = open(scheduler.__file__).read()
    names = set(re.findall(r'spans\.(?:span|step|emit)\(\s*"([^"]+)"', src))
    assert {"sched.tick", "sched.admit", "sched.prefill.build",
            "sched.prefill.dispatch", "sched.decode.build",
            "sched.decode.dispatch", "sched.readback", "sched.emit",
            "sched.submit", "req.queued", "req.prefill",
            "req.decode"} == names
    bench = {"tick", "prefill_call", "decode_call", "readback_prefill",
             "readback_decode", "idle_wait"}
    assert all(n.startswith(("sched.", "req.")) for n in names)
    assert not names & bench


# ------------------------------------------------------------ scheduler


def run_traced(sched, clk):
    """Tick to the end, one clock second per tick; returns each rid's
    queued intervals as seen from outside (arrival or preemption to
    admission), from the active set between ticks."""
    active, queued = set(), {}
    for r in sched.queue:
        queued[r.rid] = [[r.arrival_time, None]]
    while not sched.drained:
        sched.tick()
        now = {st.req.rid for st in sched.active.values()}
        for rid in now - active:
            queued[rid][-1][1] = clk.t
        for rid in active - now:
            if rid not in sched.finished:
                queued[rid].append([clk.t, None])
        active = now
        clk.t += 1.0
    return queued


def queued_spans(recorded):
    out = {}
    for name, s, e, parent, args in recorded:
        if name == "req.queued":
            out.setdefault(args["rid"], []).append([s, e])
    return out


def test_queued_spans_behind_a_one_slot_pool(dense_setup):
    cfg, params = dense_setup
    clk = FakeClock()
    runtime = make_runtime(cfg, params)
    sched = ContinuousBatchingScheduler(runtime, n_slots=1, cache_len=64,
                                        clock=clk)
    for i, p in enumerate(make_prompts(cfg, [20, 12, 30])):
        sched.submit(Request(rid=i, prompt=p, max_new=3,
                             arrival_time=0.0))
    spans.enable()
    seen = run_traced(sched, clk)
    recorded = spans.drain()
    got = queued_spans(recorded)
    assert got == seen
    # one block each: first token and second at the admitting tick, the
    # third a tick later; the next request is seated the tick after
    assert got == {0: [[0.0, 0.0]], 1: [[0.0, 2.0]], 2: [[0.0, 4.0]]}
    names = [s[0] for s in recorded]
    assert names.count("sched.tick") == sched.n_ticks
    by_rid = {}
    for name, s, e, _, args in recorded:
        if name.startswith("req."):
            by_rid.setdefault(args["rid"], []).append((name, s, e))
    for rid, (q, pre, dec) in by_rid.items():
        assert [q[0], pre[0], dec[0]] == ["req.queued", "req.prefill",
                                          "req.decode"]
        assert q[2] == pre[1] and pre[2] == dec[1]
        out = sched.finished[rid]
        assert pre[2] - 0.0 == out.ttft_seconds
        assert dec[2] - 0.0 == out.finish_seconds


def test_preempted_request_has_two_queued_spans(dense_setup):
    cfg, params = dense_setup
    clk = FakeClock()
    runtime = make_runtime(cfg.with_(kv_layout="paged", kv_page_size=8),
                           params)
    # an oversubscribed heap: decode growth preempts the youngest
    sched = ContinuousBatchingScheduler(runtime, n_slots=4, cache_len=64,
                                        n_pages=10, clock=clk)
    for i, p in enumerate(make_prompts(cfg, [30, 30, 28, 26], seed=3)):
        sched.submit(Request(rid=i, prompt=p, max_new=16,
                             arrival_time=0.0))
    spans.enable()
    seen = run_traced(sched, clk)
    got = queued_spans(spans.drain())
    assert sched.n_preemptions >= 1
    assert got == seen
    assert sum(len(v) for v in got.values()) == 4 + sched.n_preemptions
    assert any(len(v) == 2 for v in got.values())


def test_prefill_row_counters_and_tick_phases(dense_setup):
    cfg, params = dense_setup
    runtime = make_runtime(cfg, params)
    sched = ContinuousBatchingScheduler(runtime, n_slots=3, cache_len=160,
                                        prefill_batch=2)
    sched.warmup()
    assert sched.n_prefill_rows_dense == sched.n_prefill_rows_sparse == 0
    for i, p in enumerate(make_prompts(cfg, [100, 40, 130, 20])):
        sched.submit(Request(rid=i, prompt=p, max_new=4))
    spans.enable()
    sched.run()
    recorded = spans.drain()
    assert sched.n_prefill_rows_sparse > 0
    assert (sched.n_prefill_rows_dense + sched.n_prefill_rows_sparse
            == sched.n_prefill_blocks == int(sched.plan_prefill_blocks.sum()))
    ticks = [i for i, s in enumerate(recorded) if s[0] == "sched.tick"]
    assert [recorded[i][4]["step_num"] for i in ticks] \
        == list(range(1, sched.n_ticks + 1))
    for name, s, e, parent, args in recorded:
        if name.startswith("sched.") and name not in ("sched.tick",
                                                      "sched.submit"):
            top = parent
            while recorded[top][3] is not None:
                top = recorded[top][3]
            assert recorded[top][0] == "sched.tick"
            assert recorded[top][1] <= s <= e <= recorded[top][2]
    kinds = {s[4]["kind"] for s in recorded if s[0] == "sched.readback"}
    assert kinds == {"prefill", "decode"}
    assert sum(s[0] == "sched.prefill.dispatch" for s in recorded) \
        == sched.n_prefill_ticks
    assert sum(s[0] == "sched.decode.dispatch" for s in recorded) \
        == sched.n_decode_steps
    assert sum(s[0] == "sched.submit" for s in recorded) == 0
