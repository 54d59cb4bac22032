"""Self-speculative decode A/B: sparse-draft / dense-verify vs plain
one-token-per-tick decode on a generation-heavy stream.

The draft model is the SAME weights under a sparser registered
SparsityPlan (the turbo tier), so speculation costs zero extra
parameters and zero extra compiles beyond the two chunk entries
(`draft_steps` / `verify_chunk`). Each speculative decode tick drafts
k tokens per active row under the draft plan, verifies all k+1
positions in ONE chunk-scored call under each request's own plan, and
emits the longest agreeing prefix plus the verifier's bonus token —
greedy output is BIT-identical to speculation off (asserted here), the
draft plan buys latency only.

Writes the ``speculative_decode`` section of
``results/BENCH_prefill.json``: per-verify-tier acceptance rate and
emitted tokens per speculated row-tick, decode ticks and wall-clock
both ways, and the acceptance booleans (bit-identity; tokens per
decode tick strictly above the non-speculative baseline — i.e.
strictly fewer decode ticks for the same emitted tokens).

Wall-clock on the reduced CPU config is dispatch-overhead-bound and
noisy (each speculative tick runs 2 jitted calls instead of 1, and the
chunk scan serializes k+1 tiny steps); the structural win is the tick
count, which is deterministic. The analytical framing: a speculative
tick costs 1 draft pass (k steps at the draft tier's FLOP fraction)
plus 1 verify chunk (k+1 steps at the verify tier) and advances
~(1 + k * acceptance) tokens — on accelerators where per-tick launch
overhead dominates small-batch decode, fewer ticks is the win.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np
import jax

from benchmarks.common import write_bench_json
from repro.configs import get_config
from repro.core.fastforward import resolve_plan
from repro.models.registry import get_model
from repro.nn.param import init_params
from repro.serving import (ContinuousBatchingScheduler, Request,
                           SpeculativeConfig, drive_stream)
from repro.serving.runtime import make_runtime

SLOTS = 4
PREFILL_BATCH = 4
REQUESTS = 12
PROMPT_RANGE = (24, 64)       # short prompts ...
MAX_NEW_RANGE = (32, 56)      # ... long generations: decode dominates
SPEC_K = 4
DRAFT_TIER = "turbo"
EFFORTS = ("balanced", "turbo")   # verify-tier mix across the stream


def _workload(cfg, seed=11, requests=REQUESTS):
    """Generation-heavy burst: everyone arrives at ~t=0, so decode runs
    with full rows and the per-tick comparison is about speculation,
    not admission timing."""
    rng = np.random.default_rng(seed)
    prompts = [list(rng.integers(0, cfg.vocab,
                                 rng.integers(*PROMPT_RANGE)))
               for _ in range(requests)]
    max_news = [int(v) for v in rng.integers(*MAX_NEW_RANGE,
                                             size=requests)]
    arrivals = np.sort(rng.exponential(0.001, size=requests))
    return [Request(rid=i, prompt=prompts[i], max_new=max_news[i],
                    arrival_time=arrivals[i],
                    effort=EFFORTS[i % len(EFFORTS)])
            for i in range(requests)]


def _drive(runtime, requests, cache_len, speculative):
    sched = ContinuousBatchingScheduler(
        runtime, n_slots=SLOTS, cache_len=cache_len,
        prefill_batch=PREFILL_BATCH, speculative=speculative)
    counts0 = sched.warmup()
    t0 = time.perf_counter()
    drive_stream(sched, requests)
    wall = time.perf_counter() - t0
    flat = None
    if None not in counts0.values():
        flat = runtime.compile_counts() == counts0
        assert flat, "recompiled mid-stream"
    outs = sched.finished
    assert len(outs) == len(requests)
    gen = sum(len(o.tokens) for o in outs.values())
    return sched, outs, gen, wall, flat


def run(csv=True, requests=REQUESTS):
    cfg = get_config("tinyllama-1.1b", reduced=True)
    params = init_params(get_model(cfg).specs(cfg), jax.random.key(0))
    plans = tuple(
        dataclasses.replace(resolve_plan(cfg, effort=e), name=e)
        for e in EFFORTS)
    runtime = make_runtime(cfg, params, plans=plans)
    reqs = _workload(cfg, requests=requests)
    N = runtime.block_size
    cache_len = (-(-max(len(r.prompt) for r in reqs) // N) * N
                 + max(r.max_new for r in reqs))

    spec = SpeculativeConfig(k=SPEC_K, draft=DRAFT_TIER)
    off_sched, off_outs, off_gen, off_wall, off_flat = _drive(
        runtime, reqs, cache_len, None)
    on_sched, on_outs, on_gen, on_wall, on_flat = _drive(
        runtime, reqs, cache_len, spec)

    identical = all(off_outs[r.rid].tokens == on_outs[r.rid].tokens
                    for r in reqs)
    ss = on_sched.speculative_stats()
    off_tpt = off_gen / max(off_sched.n_decode_steps, 1)
    on_tpt = on_gen / max(on_sched.n_decode_steps, 1)
    section = {
        "config": {"slots": SLOTS, "prefill_batch": PREFILL_BATCH,
                   "requests": len(reqs), "k": SPEC_K,
                   "draft_tier": DRAFT_TIER, "efforts": list(EFFORTS),
                   "prompt_range": list(PROMPT_RANGE),
                   "max_new_range": list(MAX_NEW_RANGE),
                   "arch": cfg.name, "reduced": True},
        "off": {"decode_ticks": off_sched.n_decode_steps,
                "tokens": off_gen,
                "tokens_per_decode_tick": round(off_tpt, 3),
                "wall_s": round(off_wall, 3)},
        "on": {"decode_ticks": on_sched.n_decode_steps,
               "tokens": on_gen,
               "tokens_per_decode_tick": round(on_tpt, 3),
               "wall_s": round(on_wall, 3),
               "spec_ticks": ss["spec_ticks"],
               "per_tier": [
                   {k: row[k] for k in ("name", "draft_plan", "row_ticks",
                                        "drafted", "accepted",
                                        "acceptance_rate", "emitted",
                                        "tokens_per_row_tick")}
                   for row in ss["plans"] if row["row_ticks"]]},
        "decode_tick_ratio": round(off_sched.n_decode_steps
                                   / max(on_sched.n_decode_steps, 1), 3),
        # acceptance: same emitted tokens from strictly fewer decode
        # ticks (tokens/tick strictly above the baseline), bit-identical
        # greedy outputs, flat jit cache after warmup both ways
        "outputs_bit_identical": bool(identical),
        "tokens_per_tick_above_baseline": bool(on_tpt > off_tpt),
        "compile_counts_flat": (None if off_flat is None or on_flat is None
                                else bool(off_flat and on_flat)),
        "note": ("wall-clock on the reduced CPU config is dispatch-"
                 "overhead-bound (2 jitted calls + a k+1-step scan per "
                 "speculative tick); the structural, deterministic win "
                 "is the decode-tick count"),
    }
    write_bench_json("speculative_decode", section)

    rows = [
        ("spec_decode_ticks_off", f"{off_sched.n_decode_steps}",
         f"{off_gen} tokens, {off_tpt:.2f} tok/tick"),
        ("spec_decode_ticks_on", f"{on_sched.n_decode_steps}",
         f"{on_gen} tokens, {on_tpt:.2f} tok/tick, k={SPEC_K} "
         f"draft={DRAFT_TIER}"),
        ("spec_decode_tick_ratio", f"{section['decode_tick_ratio']:.2f}",
         "off/on decode ticks (target > 1.0)"),
        ("spec_outputs_bit_identical", f"{identical}",
         "acceptance: greedy outputs identical speculation on vs off"),
        ("spec_tokens_per_tick_above_baseline",
         f"{section['tokens_per_tick_above_baseline']}",
         "acceptance: tokens per decode tick strictly above baseline"),
    ]
    for row in (ss["plans"] if ss else []):
        if not row["row_ticks"]:
            continue
        rows.append((
            f"spec_acceptance_{row['name']}",
            f"{row['acceptance_rate']}",
            f"draft={row['draft_plan']}, {row['accepted']}/"
            f"{row['drafted']} drafts accepted, "
            f"{row['tokens_per_row_tick']} tok/row-tick"))
    if csv:
        for r in rows:
            print(",".join(str(x) for x in r))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--requests", type=int, default=REQUESTS,
                   help="stream length (CI smoke uses a reduced count)")
    args = p.parse_args()
    run(requests=args.requests)
