"""Serving launcher: static batch or continuous-batching request stream.

Static one-shot batch (legacy behaviour):

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --requests 4 --max-new 16

Continuous-batching stream simulator (Poisson arrivals; batched
multi-request prefill ticks; reports TTFT p50/p99, tokens/sec, slot
churn, and asserts zero jit recompilation after warmup):

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --stream --requests 16 --rate 20 --slots 4 \
      --prefill-batch 4

EOS workload (--eos-id): every request stops the moment it greedily
emits that token — mid-generation — so slots free early and admission
churns under the batched prefill path:

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --stream --requests 16 --eos-id 7

Paged KV cache (--kv-layout paged): requests hold page tables into a
shared page heap instead of max-length slots — admission gates on free
pages, allocation is lazy per prefill block, and an oversubscribed heap
(--pool-pages) preempts the youngest request when dry:

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --stream --requests 16 --kv-layout paged \
      --page-size 16 --slots 8 --pool-pages 48

Prefix sharing (--prefix-cache, paged layout only): finished prompt
blocks are published to a host-side prefix index; later requests with
the same page-aligned (prompt-prefix, SparsityPlan) key map those pages
read-only (refcounted), are charged only their unshared footprint at
admission, and start prefill at the first unshared block. Greedy
outputs are bit-identical with sharing on or off:

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --stream --kv-layout paged --prefix-cache \
      --trace benchmarks/traces/sample_shared_prefix.jsonl

Real-traffic trace replay (--trace): arrival-time / prompt-len /
gen-len records (jsonl, see repro.serving.trace) drive the SAME stream
loop as the Poisson simulator:

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --stream --trace benchmarks/traces/sample_trace.jsonl

SLO-tiered sparsity (--effort): requests select a SparsityPlan effort
tier ("dense" / "balanced" / "turbo"); a comma list round-robins tiers
across the stream (mixed-effort traffic through the pre-compiled
per-plan executables — the no-recompilation assertion still holds),
and trace records may carry their own `effort` field:

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --stream --requests 16 --effort balanced,turbo

Overload resilience (--deadline-ms / --degrade / --chaos-seed):
requests carry deadlines (expiry frees resources mid-flight with
status="timed_out"; provably-unmeetable deadlines are shed at submit),
--degrade routes new admissions to sparser pre-compiled tiers while
load watermarks trip, and --chaos-seed runs the whole stream under
deterministic fault injection (forced preemptions, synthetic pool
pressure, slow ticks — serving/faults.py). A robustness line reports
per-status counts, goodput, and degradation/fault stats:

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --stream --requests 16 --rate 200 --deadline-ms 60000 \
      --degrade --chaos-seed 0

Tiered KV memory (--kv-quant / --swap-pages N, paged layout only):
--kv-quant stores paged K/V as int8 with per-(page, kv-head) f32
scales (kernels/kv_quant) — ~4x the pages at equal device bytes,
dequantized on the fly in the paged attention kernels. --swap-pages N
attaches a host-memory swap tier of N pages (serving/kv_tier.py):
page pressure swaps the youngest request's exclusive pages out to
host instead of preempt-and-recompute, and parked requests resume
bit-identically; preemption remains the fallback when the tier is
full. A tier stats line reports swap traffic and occupancy:

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --stream --requests 16 --kv-layout paged \
      --pool-pages 24 --kv-quant --swap-pages 64

Self-speculative decode (--speculate K[,draft_tier]): decode ticks
draft K tokens per active request under the (sparser) draft tier's
pre-compiled executables, then verify all K+1 positions in ONE chunked
call under each request's own plan, emitting the longest agreeing
prefix plus the verifier's bonus token. Greedy output is BIT-identical
to speculation off — the draft plan affects only latency. A stats line
reports per-tier acceptance rate and tokens per row-tick:

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
      --reduced --stream --requests 16 --effort balanced,turbo \
      --speculate 4,turbo
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import ALL, get_config
from repro.core import scheduler as SCHED
from repro.core.fastforward import EFFORT_TIERS, resolve_plan
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import get_model
from repro.nn.param import init_params
from repro.serving import (AdmissionController, ContinuousBatchingScheduler,
                           FaultInjector, Request, StaticEngine,
                           drive_stream, load_trace, parse_speculate_arg)
from repro.serving.runtime import make_runtime
from repro.serving.trace import trace_stats
from repro.training.checkpoint import load_checkpoint


def build_params(cfg, checkpoint=None, seed: int = 0):
    """Checkpoint params, or random weights made from `seed`."""
    model = get_model(cfg)
    if checkpoint:
        params, meta = load_checkpoint(checkpoint)
        print(f"loaded checkpoint ({meta})")
        return params
    return init_params(model.specs(cfg), jax.random.key(seed))


@functools.partial(jax.jit, static_argnames=("cfg",))
def _attn_probs_pass(params, cfg, tokens):
    """Jitted Eq. 23 capture: ONE compiled forward pass whose lax.scan
    over the stacked layer params emits every layer's post-softmax
    attention probs [L, B, H, T, T]. cfg is a frozen (hashable)
    dataclass, so it rides as a static argument; the moe/dense FFN
    branch is python-static (stacked param structure is uniform across
    layers). One compile per calibration prompt SHAPE — fine offline,
    and ~n_layers fewer dispatches per prompt than the old per-layer
    python loop."""
    from repro.models import dense as D
    from repro.nn import attention as A
    from repro.nn import layers as L
    from repro.core import fastforward as FF
    x = L.embed(params["embed"], tokens).astype(cfg.dtype)
    B, T = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    mask = A.causal_mask(T, T)
    is_moe = "moe" in params["layers"]
    if is_moe:
        from repro.models import moe as M

    def layer(x, lp):
        xn = D.apply_norm(cfg, lp["ln1"], x)
        q = A.project_q(lp["attn"], xn, pos, cfg.rope_theta)
        k, v = A.project_kv(lp["attn"], xn, pos, cfg.rope_theta)
        Kv = k.shape[2]
        rep = q.shape[2] // Kv
        qg = q.reshape(B, T, Kv, rep, -1)
        s = jnp.einsum("btgrk,bsgk->bgrts", qg, k) / np.sqrt(q.shape[-1])
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)                  # [B,Kv,rep,T,T]
        o = jnp.einsum("bgrts,bsgk->btgrk", p.astype(v.dtype), v)
        o = o.reshape(B, T, q.shape[2], -1)
        x = x + A.output_proj(lp["attn"], o)
        xn2 = D.apply_norm(cfg, lp["ln2"], x)
        if is_moe:
            y, _ = M.moe_block(lp["moe"], cfg, xn2, mode="dense")
            x = x + y.astype(x.dtype)
        else:
            x = x + FF.ff_dense(lp["ffn"], cfg, xn2).astype(x.dtype)
        return x, p.reshape(B, -1, T, T)

    _, probs = jax.lax.scan(layer, x, params["layers"])
    return probs


def collect_attn_probs(params, cfg, tokens):
    """Per-layer post-softmax attention probs [L, B, H, T, T] — the
    Eq. 23 calibration input for `calibrate_layer_importance`. Thin
    wrapper over the jitted single-pass capture (`_attn_probs_pass`);
    offline only, never on the serving path."""
    return _attn_probs_pass(params, cfg, jnp.asarray(tokens))


def make_prompts(cfg, n, prompt_len, rng, min_len=None):
    """n random-token prompts, lengths uniform in [min_len, prompt_len]
    (min_len defaults to prompt_len // 2)."""
    lo = max(1, prompt_len // 2) if min_len is None else min_len
    return [list(rng.integers(0, cfg.vocab,
                              size=rng.integers(lo, prompt_len + 1)))
            for _ in range(n)]


def poisson_requests(cfg, n, *, rate, prompt_len, max_new, seed,
                     min_prompt_len=None, temperature=0.0, eos_id=None,
                     deadline_ms=None, efforts=()):
    """The seeded Poisson request plan of `--stream`: n random prompts
    (see make_prompts), exponential inter-arrival gaps at `rate` req/s,
    max_new uniform in [max_new // 4, max_new], efforts round-robined.
    Returns (requests, cache_len) — cache_len covers the longest
    possible prompt in whole blocks plus the generation budget."""
    rng = np.random.default_rng(seed)
    prompts = make_prompts(cfg, n, prompt_len, rng, min_len=min_prompt_len)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
    max_news = rng.integers(max(1, max_new // 4), max_new + 1, size=n)
    requests = [
        Request(rid=i, prompt=prompts[i], max_new=int(max_news[i]),
                temperature=temperature, arrival_time=arrivals[i],
                eos_id=eos_id, deadline_ms=deadline_ms,
                effort=efforts[i % len(efforts)] if efforts else None)
        for i in range(n)]
    N = cfg.ff.block_size
    return requests, -(-prompt_len // N) * N + max(max_new, 2)


def stream_plans(cfg, requests, *, importance=None, degrade=False,
                 speculative=None):
    """The SparsityPlans a stream registers, one per effort tier it
    uses (None when FastForward is off). The default ("balanced" ==
    the cfg budget) is plans[0]; requests without an effort take it.
    Every (plan, width bucket) pair is pre-compiled by warmup, so a
    mixed-tier stream never recompiles."""
    if not cfg.ff.enabled:
        return None
    names = ["balanced"] + [e for e in dict.fromkeys(
        r.effort for r in requests if r.effort) if e != "balanced"]
    if degrade:
        # degradation needs ladder room: register every tier (all
        # pre-compiled by warmup, so escalation costs zero compiles)
        names += [e for e in EFFORT_TIERS if e not in names]
    if speculative is not None and speculative.draft not in names:
        # the draft tier must be a registered (pre-compiled) plan
        names.append(speculative.draft)
    # register under the bare tier names: calibrated plans resolve as
    # "<tier>-layerwise", but requests address them by tier
    return tuple(
        dataclasses.replace(
            resolve_plan(cfg, effort=e, importance=importance), name=e)
        for e in names)


def serve_static(cfg, params, args):
    rng = np.random.default_rng(0)
    prompts = make_prompts(cfg, args.requests, args.prompt_len, rng)
    eng = StaticEngine(cfg, params)
    res = eng.generate(prompts, max_new=args.max_new,
                       temperature=args.temperature)
    print(f"mode={'dense' if args.dense else 'fastforward'} "
          f"sparsity={0.0 if args.dense else cfg.ff.sparsity}")
    print(f"prefill: {res.prefill_seconds*1e3:.1f} ms "
          f"({res.prompt_tokens} prompt tokens)")
    print(f"decode:  {res.decode_seconds*1e3:.1f} ms "
          f"({res.generated_tokens} tokens)")
    for i, row in enumerate(res.tokens):
        print(f"req{i}: {row.tolist()}")


def serve_stream(cfg, params, args):
    """Request stream (Poisson plan or trace replay) through the
    continuous-batching scheduler."""
    efforts = ([e.strip() for e in args.effort.split(",") if e.strip()]
               if args.effort and cfg.ff.enabled else [])
    N = cfg.ff.block_size

    if args.trace:
        # records without their own `effort` round-robin the CLI tiers
        requests = load_trace(args.trace, cfg.vocab, seed=args.seed,
                              eos_id=args.eos_id,
                              temperature=args.temperature,
                              deadline_ms=args.deadline_ms)
        for i, r in enumerate(requests):
            if r.effort is None and efforts:
                r.effort = efforts[i % len(efforts)]
        tstats = trace_stats(requests)
        print(f"trace {args.trace}: {tstats}")
        max_prompt = max(len(r.prompt) for r in requests)
        cache_len = (-(-max_prompt // N) * N
                     + max(max(r.max_new for r in requests), 2))
    else:
        requests, cache_len = poisson_requests(
            cfg, args.requests, rate=args.rate, prompt_len=args.prompt_len,
            max_new=args.max_new, seed=args.seed,
            temperature=args.temperature, eos_id=args.eos_id,
            deadline_ms=args.deadline_ms, efforts=efforts)

    # --calibrate N: run Eq. 23 layer-importance calibration over the
    # first N prompts of the stream (dense offline forward passes) and
    # feed it to resolve_plan, so the registered plans carry Algorithm-1
    # layer-wise budgets instead of uniform ones.
    importance = None
    if args.calibrate and cfg.ff.enabled:
        first = sorted(requests, key=lambda r: r.arrival_time or 0.0)
        samples = [np.asarray(r.prompt, np.int32)[None]
                   for r in first[:args.calibrate]]
        importance = SCHED.calibrate_layer_importance(
            lambda t: collect_attn_probs(params, cfg, jnp.asarray(t)),
            samples, N)
        print(f"calibrated layer importance on {len(samples)} prompts: "
              f"{[round(float(s), 4) for s in importance]}")

    speculative = (parse_speculate_arg(args.speculate)
                   if args.speculate else None)

    plans = stream_plans(cfg, requests, importance=importance,
                         degrade=args.degrade, speculative=speculative)
    runtime = make_runtime(cfg, params, plans=plans)

    admission = (AdmissionController(plans or ())
                 if args.degrade else None)
    faults = (FaultInjector(seed=args.chaos_seed)
              if args.chaos_seed is not None else None)
    sched = ContinuousBatchingScheduler(
        runtime, n_slots=args.slots, cache_len=cache_len, seed=args.seed,
        prefill_batch=args.prefill_batch, page_size=args.page_size,
        n_pages=args.pool_pages, admission=admission, faults=faults,
        prefix_cache=args.prefix_cache, speculative=speculative,
        swap_pages=args.swap_pages)

    # warmup compiles every entry point through the scheduler's own pool
    counts0 = sched.warmup()
    print(f"warmup done, jit compile counts: {counts0}")

    wall = drive_stream(sched, requests)

    counts1 = runtime.compile_counts()
    if counts1 != counts0:
        raise AssertionError(
            f"jit recompilation during serving: {counts0} -> {counts1}")

    outs = sched.finished
    # latency stats over requests that produced a first token only —
    # shed/cancelled/timed-out-in-prefill outputs carry ttft None
    ttfts = np.array([o.ttft_seconds for o in outs.values()
                      if o.ttft_seconds is not None])
    gen = sum(len(o.tokens) for o in outs.values())
    offered = tstats["offered_rate_req_s"] if args.trace else args.rate
    print(f"served {len(outs)} requests in {wall:.2f}s wall "
          f"({offered:.1f} req/s offered)")
    if len(ttfts):
        print(f"TTFT p50 {np.percentile(ttfts, 50)*1e3:8.1f} ms | "
              f"p99 {np.percentile(ttfts, 99)*1e3:8.1f} ms "
              f"({len(ttfts)} of {len(outs)} produced a first token)")
    print(f"throughput {gen / wall:8.1f} generated tok/s "
          f"({gen} tokens)")
    # robustness line: terminal-status mix, goodput (deadline-met ok
    # fraction), degradation + fault stats
    n_ok = sum(o.status == "ok" for o in outs.values())
    deadlines = {r.rid: r.deadline_ms for r in requests}
    met = sum(o.status == "ok"
              and (deadlines.get(o.rid) is None
                   or o.finish_seconds <= deadlines[o.rid] / 1e3)
              for o in outs.values())
    print(f"robustness: ok {n_ok} | shed {sched.n_shed} | timed_out "
          f"{sched.n_timed_out} | cancelled {sched.n_cancelled} | "
          f"degraded {sched.n_degraded} | preemptions "
          f"{sched.n_preemptions} | goodput {met}/{len(outs)} "
          f"({met / max(len(outs), 1):.0%} finished ok within deadline)")
    if admission is not None:
        print(f"admission: {admission.stats()}")
    if faults is not None:
        print(f"faults: {faults.stats()}")
    reuse = max(0, sched.pool.total_acquires - args.slots)
    print(f"slots: {args.slots} | max in use {sched.pool.max_in_use} | "
          f"acquires {sched.pool.total_acquires} (slot reuse x{reuse})")
    if sched.paged:
        pool = sched.pool
        print(f"paged KV: {pool.n_pages - 1} usable pages x "
              f"{pool.page_size} tok | peak in use "
              f"{pool.max_pages_in_use} | allocs "
              f"{pool.total_page_allocs} / frees {pool.total_page_frees} "
              f"| stranded@peak {pool.stranded_tokens_at_peak} tok | "
              f"preemptions {sched.n_preemptions}")
        if args.kv_quant:
            print(f"kv quant: int8 pages + per-(page, kv-head) f32 "
                  f"scales (kernels/kv_quant)")
    ts = sched.tier_stats()
    if ts is not None:
        print(f"kv tier: {ts['capacity_pages']} host pages | swap outs "
              f"{ts['swap_outs']} ({ts['pages_swapped_out']} pages) / "
              f"ins {ts['swap_ins']} ({ts['pages_swapped_in']} pages) | "
              f"peak host used {ts['peak_used']} | host puts "
              f"{ts['total_host_puts']} / frees {ts['total_host_frees']} "
              f"| parked now {ts['parked']}")
    if sched.prefix_index is not None:
        ps = sched.prefix_stats()
        print(f"prefix sharing: hit rate {ps['hit_rate']:.0%} "
              f"({ps['hits']}/{ps['lookups']} admissions) | "
              f"{ps['requests_hit']} requests skipped "
              f"{ps['blocks_skipped']} prefill blocks | pages shared "
              f"{ps['pages_shared']} / published {ps['pages_published']} "
              f"/ cached now {ps['pages_cached']} | cow {ps['cow_pages']} "
              f"| evictions {ps['evictions']}")
    sp = sched.sparsity_stats()
    for row in sp["plans"]:
        print(f"sparsity[{row['name']}]: keep/layer "
              f"{row['keep_per_layer']} | ffn flop frac "
              f"{row['ffn_flop_frac']:.3f} | {row['prefill_blocks']} "
              f"prefill blocks, {row['decode_tokens']} decode tokens")
        if row["attn_flop_frac"] is not None:
            print(f"  attn[{row['name']}]: keep/layer "
                  f"{row['attn_keep_per_layer']} | attn block frac "
                  f"{row['attn_flop_frac']:.3f}")
    if sp["aggregate_ffn_flop_frac"] is not None:
        print(f"sparsity aggregate ffn flop frac (work-weighted): "
              f"{sp['aggregate_ffn_flop_frac']:.3f}")
    if sp.get("aggregate_attn_flop_frac") is not None:
        print(f"sparsity aggregate attn block frac (work-weighted): "
              f"{sp['aggregate_attn_flop_frac']:.3f}")
    ss = sched.speculative_stats()
    if ss is not None:
        print(f"speculation k={ss['k']} draft={ss['draft']}: "
              f"{ss['spec_ticks']} speculative decode ticks")
        for row in ss["plans"]:
            if row["row_ticks"] == 0:
                continue
            acc = (f"{row['acceptance_rate']:.2%} "
                   f"({row['accepted']}/{row['drafted']} drafts)"
                   if row["acceptance_rate"] is not None
                   else "n/a (0 drafts)")
            print(f"  spec[{row['name']}<-{row['draft_plan']}]: "
                  f"acceptance {acc} | "
                  f"{row['tokens_per_row_tick']:.2f} tok/row-tick "
                  f"({row['emitted']} emitted in {row['row_ticks']} "
                  f"row ticks)")
    print(f"ticks {sched.n_ticks} | prefill blocks "
          f"{sched.n_prefill_blocks} in {sched.n_prefill_ticks} prefill "
          f"ticks (P<={sched.prefill_batch}) | decode steps "
          f"{sched.n_decode_steps}")
    if args.eos_id is not None:
        print(f"eos_id={args.eos_id}: {sched.n_eos_stops} of {len(outs)} "
              f"requests stopped early (slots freed mid-generation)")
    print(f"no recompilation after warmup: OK {counts1}")


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=ALL, default="tinyllama-1.1b")
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--requests", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=96)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--dense", action="store_true",
                   help="disable FastForward sparsity (baseline)")
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--stream", action="store_true",
                   help="continuous-batching Poisson request stream")
    p.add_argument("--rate", type=float, default=20.0,
                   help="stream mode: mean arrival rate (req/s)")
    p.add_argument("--slots", type=int, default=4,
                   help="stream mode: KV slot pool capacity")
    p.add_argument("--prefill-batch", type=int, default=4,
                   help="stream mode: max requests advancing one "
                        "prefill block per tick in one jitted call "
                        "(1 = PR-1 single-block ticks)")
    p.add_argument("--eos-id", type=int, default=None,
                   help="stream mode: requests stop at this token "
                        "mid-generation, freeing their slot early "
                        "(EOS admission-churn workload)")
    p.add_argument("--kv-layout", choices=("slot", "paged"), default=None,
                   help="KV cache layout: one max-length slot per "
                        "request (default) or block-granular paged "
                        "allocation (PagedKVPool)")
    p.add_argument("--page-size", type=int, default=None,
                   help="paged layout: tokens per KV page (default "
                        "cfg.kv_page_size, then the prefill block size; "
                        "must divide the block size)")
    p.add_argument("--pool-pages", type=int, default=None,
                   help="paged layout: total heap pages incl. the "
                        "reserved null page (default: full backing — "
                        "smaller values oversubscribe and exercise "
                        "preemption)")
    p.add_argument("--kv-quant", action="store_true",
                   help="paged layout: store K/V pages as int8 with "
                        "per-(page, kv-head) f32 scales, dequantized "
                        "on the fly in the paged attention kernels "
                        "(kernels/kv_quant) — ~4x pages at equal "
                        "device bytes")
    p.add_argument("--swap-pages", type=int, default=0, metavar="N",
                   help="paged stream mode: host swap tier capacity in "
                        "pages (serving/kv_tier.py) — page pressure "
                        "swaps the youngest request's exclusive pages "
                        "to host instead of preempt-and-recompute; "
                        "0 disables tiering")
    p.add_argument("--prefix-cache", action="store_true",
                   help="paged layout: refcounted prefix sharing — "
                        "admission maps the longest cached page-aligned "
                        "(prompt, plan) prefix read-only into new "
                        "requests, charges only the unshared footprint, "
                        "and skips the covered prefill blocks "
                        "(serving/prefix_index.py)")
    p.add_argument("--trace", default=None,
                   help="stream mode: replay a jsonl arrival trace "
                        "(see repro.serving.trace) instead of the "
                        "Poisson plan; --requests/--rate/--prompt-len/"
                        "--max-new are ignored")
    p.add_argument("--effort", default=None,
                   help="stream mode: SparsityPlan effort tier(s) — "
                        f"one of {'/'.join(EFFORT_TIERS)} or a comma "
                        "list round-robined across requests "
                        "(SLO-tiered sparsity; trace records may carry "
                        "their own 'effort')")
    p.add_argument("--calibrate", type=int, default=0, metavar="N",
                   help="stream mode: calibrate Eq. 23 layer importance "
                        "on the first N prompts (offline dense passes) "
                        "and resolve Algorithm-1 layer-wise plans from "
                        "it instead of uniform budgets")
    p.add_argument("--attn-sparsity", type=float, default=None,
                   help="enable the block-sparse prefill attention "
                        "budget (fraction of KV blocks dropped at "
                        "'balanced'); plans become dual-budget and "
                        "effort tiers scale both")
    p.add_argument("--deadline-ms", type=float, default=None,
                   help="stream mode: end-to-end deadline per request "
                        "(trace records carrying their own deadline_ms "
                        "keep it); expiry frees resources mid-flight "
                        "with status=timed_out, provably-unmeetable "
                        "deadlines are shed at submit")
    p.add_argument("--degrade", action="store_true",
                   help="stream mode: hysteretic graceful degradation — "
                        "route new admissions to sparser effort tiers "
                        "while queue/free-space watermarks are tripped "
                        "(AdmissionController; all tiers pre-compiled)")
    p.add_argument("--speculate", default=None, metavar="K[,TIER]",
                   help="stream mode: self-speculative decode — draft "
                        "K tokens per tick under the (sparser) TIER "
                        "plan (default turbo), verify all K+1 in one "
                        "chunked call under each request's own plan. "
                        "Greedy output is bit-identical to speculation "
                        "off; trace records may cap it per-request "
                        "with a 'speculate' field")
    p.add_argument("--attn-threshold", type=float, default=None,
                   help="opt-in FlashPrefill-style adaptive attention "
                        "block counts: keep the fewest top-scored KV "
                        "blocks reaching this proxy-softmax mass, "
                        "capped by the plan budget (1.0 = keep all, "
                        "bit-identical to the fixed budget)")
    p.add_argument("--chaos-seed", type=int, default=None,
                   help="stream mode: run under deterministic fault "
                        "injection with this seed (forced preemptions, "
                        "synthetic pool pressure, slow ticks — "
                        "serving/faults.py)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()
    if args.max_new < 1:
        p.error("--max-new must be >= 1")
    if args.requests < 1:
        p.error("--requests must be >= 1")

    cfg = get_config(args.arch, reduced=args.reduced)
    if args.dense:
        cfg = cfg.with_ff(enabled=False)
    if args.attn_sparsity is not None:
        cfg = cfg.with_ff(attn_sparsity=args.attn_sparsity)
    if args.attn_threshold is not None:
        cfg = cfg.with_ff(attn_threshold=args.attn_threshold)
    if args.kv_layout:
        cfg = cfg.with_(kv_layout=args.kv_layout)
    if args.trace and not args.stream:
        p.error("--trace requires --stream")
    if args.calibrate and not args.stream:
        p.error("--calibrate requires --stream")
    if args.kv_quant:
        if cfg.kv_layout != "paged":
            p.error("--kv-quant requires --kv-layout paged")
        cfg = cfg.with_(kv_quant=True)
    if args.swap_pages:
        if args.swap_pages < 0:
            p.error("--swap-pages must be >= 0")
        if cfg.kv_layout != "paged":
            p.error("--swap-pages requires --kv-layout paged")
        if not args.stream:
            p.error("--swap-pages requires --stream")
    if args.prefix_cache and cfg.kv_layout != "paged":
        p.error("--prefix-cache requires --kv-layout paged")
    if args.prefix_cache and not args.stream:
        p.error("--prefix-cache requires --stream")
    if ((args.deadline_ms is not None or args.degrade
         or args.chaos_seed is not None) and not args.stream):
        p.error("--deadline-ms/--degrade/--chaos-seed require --stream")
    if args.speculate is not None:
        if not args.stream:
            p.error("--speculate requires --stream")
        if not cfg.ff.enabled:
            p.error("--speculate needs SparsityPlan tiers "
                    "(incompatible with --dense)")
    enable_compile_cache()
    params = build_params(cfg, args.checkpoint, seed=args.seed)
    if args.stream:
        serve_stream(cfg, params, args)
    else:
        serve_static(cfg, params, args)


if __name__ == "__main__":
    main()
