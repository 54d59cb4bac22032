"""Every seed gets the same work at the same times; the seed draws the
tokens."""
import itertools
import json
import pathlib

from bench import traffic

TRAFFIC = pathlib.Path(__file__).parents[1] / "traffic"


def load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


def test_open_loop_same_work_other_order():
    """Every seed gets the same lengths, outputs and arrival times, in
    the same order; only the tokens differ."""
    t = load("code-context")
    a = traffic.plan(t, 49152, 2**40 + 3, 30.0)
    b = traffic.plan(t, 49152, 2**40 + 4, 30.0)
    assert [len(p.prompt) for p in a] == [len(p.prompt) for p in b]
    assert [p.max_new for p in a] == [p.max_new for p in b]
    assert [p.due_s for p in a] == [p.due_s for p in b]
    assert [p.prompt for p in a] != [p.prompt for p in b]
    assert len(set(len(p.prompt) for p in a)) > 1   # a mix, not one size
    assert len(a) == int(t["rate_per_s"] * 30.0)


def test_open_loop_arrivals_inside_the_window():
    t = load("code-context")
    dues = [p.due_s for p in traffic.plan(t, 49152, 7, 30.0)]
    assert dues == sorted(dues) and dues[0] == 0.0 and dues[-1] < 30.0


def test_lengths_within_the_mix():
    t = load("code-context")
    ps = traffic.plan(t, 49152, 9, 30.0)
    assert all(1024 <= len(p.prompt) <= 8192 for p in ps)
    assert all(16 <= p.max_new <= 64 for p in ps)
    assert all(0 <= tok < 49152 for p in ps for tok in p.prompt)


def test_seeds_beyond_32_bits_differ():
    t = load("code-context")
    a = traffic.plan(t, 49152, 5, 10.0)
    b = traffic.plan(t, 49152, 5 + 2**32, 10.0)
    assert [p.prompt for p in a] != [p.prompt for p in b]


def test_closed_loop_repeats_whole_strata():
    t = load("doc-batch")
    ps = list(itertools.islice(traffic.plan(t, 32064, 11, 30.0), 128))
    block = traffic.STRATUM_BLOCK
    first = sorted(len(p.prompt) for p in ps[:block])
    second = sorted(len(p.prompt) for p in ps[block:2 * block])
    assert first == second
    other = list(itertools.islice(traffic.plan(t, 32064, 12, 30.0), 128))
    assert [len(p.prompt) for p in other] == [len(p.prompt) for p in ps]
