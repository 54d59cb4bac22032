"""Continuous batching vs static batching under staggered arrivals,
batched vs single-block prefill ticks, and overload resilience
(deadline goodput with and without graceful effort degradation).

The static engine's pathologies under a request stream are structural:

  * head-of-line batching — a round starts with whatever has arrived
    and everyone else waits for the full round to finish;
  * lockstep decode — the round runs to the LONGEST request's max_new,
    so finished rows burn decode FLOPs producing nothing;
  * right-padding — short prompts pay the longest prompt's prefill.

The continuous-batching scheduler admits each request into a freed KV
slot on the next tick, so slots never idle while work is queued. On
top of that, the batched prefill path (`prefill_blocks`) advances one
128-token block of up to P distinct requests per tick in ONE jitted
call, instead of PR-1's one-block-of-one-request tick — under a
backlog, prefill wall-clock per block drops and TTFT with it.

Emits ``name,value,derived`` CSV rows (harness contract) and writes
the machine-readable ``results/BENCH_prefill.json`` sections
``serving`` (tok/s, TTFT p50/p99, continuous-vs-static and
batched-vs-single-prefill ratios, measured FastForward-vs-dense
speedup), ``kv_memory`` (slot vs paged KV pool at equal device
bytes: peak concurrent requests, peak pages, stranded tokens at the
occupancy peak, preemptions), ``prefix_sharing`` (refcounted
prefix cache OFF vs ON on the same paged heap: hit rate, prefill
blocks skipped, sustained concurrency and TTFT p50 both ways,
bit-identity of greedy outputs), ``kv_tiering`` (int8-quantized page
heap vs f32 at equal device bytes: sustained concurrency; host
swap-out vs preempt-and-recompute on the same undersized heap:
re-prefilled blocks, TTFT p99, bit-identity) and ``overload`` (goodput = fraction of
requests finishing ok within deadline at 1x/2x/4x the sustainable
arrival rate, degrade-on vs degrade-off) so the perf trajectory is
tracked PR-over-PR.

The overload section runs on a SIMULATED clock: scheduling decisions
are real (the actual scheduler, admission controller, and jitted model
calls run), but time advances by an analytical per-tick cost model
priced from each plan's FFN FLOP fraction — like the repo's
`analytical` sections, this isolates the policy effect (shedding FLOPs
instead of requests) from CPU wall-clock noise, so the degrade-on vs
degrade-off goodput comparison is deterministic and meaningful on a
shared CI machine.
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
from repro.serving import (AdmissionConfig, AdmissionController,
                           ContinuousBatchingScheduler, Request,
                           StaticEngine, drive_stream)
from repro.serving.runtime import make_runtime

SLOTS = 8                     # lockstep waste grows with round width
PREFILL_BATCH = 8             # P: blocks of distinct requests per tick
REQUESTS = 32
PROMPT_RANGE = (128, 288)      # tokens: 4-9 FastForward blocks (reduced
                              # block_size=32) -> compute-bound prefill
                              # dominates, the paper's regime; the
                              # sparse gather hot path runs on every
                              # interior block
MAX_NEW_RANGE = (4, 96)       # varied -> lockstep decode waste
BURST = 8                     # requests arriving together: a burst
                              # fills the admission queue, so several
                              # requests prefill SIMULTANEOUSLY — the
                              # regime batched prefill is built for
GAP_S = 0.08                  # gap between bursts


def _workload(cfg, seed=0, requests=REQUESTS):
    rng = np.random.default_rng(seed)
    prompts = [list(rng.integers(0, cfg.vocab,
                                 rng.integers(*PROMPT_RANGE)))
               for _ in range(requests)]
    max_news = [int(v) for v in rng.integers(*MAX_NEW_RANGE,
                                             size=requests)]
    # bursts with per-request jitter: a burst lands together (deep
    # prefill backlog) but not perfectly aligned — the static engine's
    # rounds start with whoever has arrived, stragglers wait a full
    # round (head-of-line), while the continuous scheduler admits them
    # on the next tick
    arrivals = np.repeat(
        np.cumsum(rng.exponential(GAP_S, size=-(-requests // BURST))),
        BURST)[:requests] + rng.exponential(GAP_S / 4, size=requests)
    # jitter makes raw arrivals non-monotonic; _run_static serves FIFO
    # by index, so sort to keep "request i arrives i-th" true for both
    # engines (drive_stream sorts internally — the comparison must too)
    return prompts, max_news, np.sort(arrivals)


def _run_static(cfg, params, prompts, max_news, arrivals):
    """FIFO rounds of exactly SLOTS rows (short rounds padded with a
    dummy request — the shape-stable static server); a round decodes to
    the max max_new in the round (lockstep), counting only requested
    tokens as useful. Requests can only join between rounds.

    Shapes (batch, pad_to, cache_len) are pinned so every round after
    warmup reuses one jit executable: the measured gap is scheduling
    efficiency, NOT recompilation overhead."""
    eng = StaticEngine(cfg, params)
    N = cfg.ff.block_size
    pad_to = -(-max(len(p) for p in prompts) // N) * N
    cache_len = pad_to + max(max_news)
    # warm with the exact serving shapes
    eng.generate([prompts[0]] * SLOTS, max_new=2, pad_to=pad_to,
                 cache_len=cache_len)
    t0 = time.perf_counter()
    done = 0
    useful = 0
    ttfts = []
    while done < len(prompts):
        now = time.perf_counter() - t0
        ready = [i for i in range(done, len(prompts)) if arrivals[i] <= now]
        if not ready:
            time.sleep(max(0.0, arrivals[done] - now))
            continue
        batch = list(range(done, done + min(len(ready), SLOTS)))
        rows = [prompts[i] for i in batch]
        while len(rows) < SLOTS:                  # shape-stable padding
            rows.append(prompts[batch[0]])
        t_round0 = time.perf_counter() - t0
        res = eng.generate(rows, max_new=max(max_news[i] for i in batch),
                           pad_to=pad_to, cache_len=cache_len)
        # first token of the round lands after its prefill, NOT after
        # the full lockstep decode — charge TTFT fairly
        t_first = t_round0 + res.prefill_seconds
        for i in batch:
            useful += max_news[i]
            ttfts.append(t_first - arrivals[i])
        done = batch[-1] + 1
    wall = time.perf_counter() - t0
    return useful, wall, np.array(ttfts)


def _run_continuous(cfg, params, prompts, max_news, arrivals,
                    prefill_batch=PREFILL_BATCH):
    runtime = make_runtime(cfg, params)
    N = runtime.block_size
    cache_len = (-(-max(len(p) for p in prompts) // N) * N
                 + max(max_news))
    sched = ContinuousBatchingScheduler(runtime, n_slots=SLOTS,
                                        cache_len=cache_len,
                                        prefill_batch=prefill_batch)
    counts0 = sched.warmup()

    requests = [Request(rid=i, prompt=prompts[i], max_new=max_news[i],
                        arrival_time=arrivals[i])
                for i in range(len(prompts))]
    wall = drive_stream(sched, requests)
    compiles_flat = None
    if None not in counts0.values():
        compiles_flat = runtime.compile_counts() == counts0
        assert compiles_flat, "recompiled mid-stream"
    outs = sched.finished
    useful = sum(len(o.tokens) for o in outs.values())
    ttfts = np.array([o.ttft_seconds for o in outs.values()])
    return useful, wall, ttfts, sched, compiles_flat


def _stats(tok, wall, ttft):
    return {
        "tokens_per_s": round(tok / wall, 1),
        "ttft_p50_ms": round(float(np.percentile(ttft, 50)) * 1e3, 2),
        "ttft_p99_ms": round(float(np.percentile(ttft, 99)) * 1e3, 2),
    }


# ------------------------------------------------- kv memory (paged pool)

KV_SLOTS = 4                  # slot-pool capacity the byte budget buys
KV_PAGE = 16                  # tokens per page (divides block_size 32)
KV_PROMPT_RANGE = (48, 112)   # short-heavy: the fragmentation regime —
                              # every slot strands cache_len - need
KV_MAX_NEW_RANGE = (4, 32)
KV_REQUESTS = 20


def _kv_memory_workload(cfg, seed=2):
    """One deep burst of short-heavy requests: everyone arrives at once,
    so concurrency is limited ONLY by the KV pool — exactly the
    capacity question the slot-vs-paged comparison asks."""
    rng = np.random.default_rng(seed)
    prompts = [list(rng.integers(0, cfg.vocab,
                                 rng.integers(*KV_PROMPT_RANGE)))
               for _ in range(KV_REQUESTS)]
    max_news = [int(v) for v in rng.integers(*KV_MAX_NEW_RANGE,
                                             size=KV_REQUESTS)]
    arrivals = np.sort(rng.exponential(0.002, size=KV_REQUESTS))
    return prompts, max_news, arrivals


def _run_kv_memory(cfg, params):
    """Slot vs paged pool at EQUAL device pool bytes.

    The byte budget is KV_SLOTS full-length slots. The slot engine can
    therefore hold at most KV_SLOTS requests in flight however short
    they are; the paged engine spends the same bytes as a page heap
    ((n_pages - 1) * page_size == KV_SLOTS * cache_len tokens; the
    reserved null page is paid on top honestly) across up to 4x as many
    table slots, so in-flight concurrency tracks the LIVE footprint.
    Writes the `kv_memory` section: peak concurrency, peak pages,
    stranded (allocated-but-dead) tokens at the occupancy peak, and
    throughput for both layouts."""
    prompts, max_news, arrivals = _kv_memory_workload(cfg)
    N = cfg.ff.block_size
    cache_len = -(-max(len(p) for p in prompts) // N) * N + max(max_news)
    cache_len = -(-cache_len // KV_PAGE) * KV_PAGE       # page-aligned
    pool_tokens = KV_SLOTS * cache_len
    requests = [Request(rid=i, prompt=prompts[i], max_new=max_news[i],
                        arrival_time=arrivals[i])
                for i in range(len(prompts))]

    def drive(cfg_run, n_slots, n_pages=None):
        runtime = make_runtime(cfg_run, params)
        sched = ContinuousBatchingScheduler(
            runtime, n_slots=n_slots, cache_len=cache_len,
            prefill_batch=PREFILL_BATCH, page_size=KV_PAGE,
            n_pages=n_pages)
        sched.warmup()
        wall = drive_stream(sched, requests)
        outs = sched.finished
        assert len(outs) == len(requests)
        gen = sum(len(o.tokens) for o in outs.values())
        return sched, wall, gen

    s_sched, s_wall, s_gen = drive(cfg, KV_SLOTS)
    p_sched, p_wall, p_gen = drive(
        cfg.with_(kv_layout="paged"), n_slots=4 * KV_SLOTS,
        n_pages=pool_tokens // KV_PAGE + 1)

    pool = p_sched.pool
    section = {
        "config": {"pool_tokens": pool_tokens, "cache_len": cache_len,
                   "page_size": KV_PAGE, "slot_n_slots": KV_SLOTS,
                   "paged_n_slots": 4 * KV_SLOTS,
                   "paged_usable_pages": pool.n_pages - 1,
                   "requests": len(requests),
                   "prompt_range": list(KV_PROMPT_RANGE),
                   "max_new_range": list(KV_MAX_NEW_RANGE)},
        "slot": {
            "max_concurrent_requests": s_sched.pool.max_in_use,
            "stranded_tokens_at_peak": s_sched.pool.stranded_tokens_at_peak,
            "tokens_per_s": round(s_gen / s_wall, 1),
        },
        "paged": {
            "max_concurrent_requests": pool.max_in_use,
            "peak_pages_in_use": pool.max_pages_in_use,
            "stranded_tokens_at_peak": pool.stranded_tokens_at_peak,
            "page_allocs": pool.total_page_allocs,
            "page_frees": pool.total_page_frees,
            "preemptions": p_sched.n_preemptions,
            "tokens_per_s": round(p_gen / p_wall, 1),
        },
        # acceptance: block-granular allocation must buy strictly more
        # in-flight requests from the same device bytes
        "paged_more_concurrent":
            bool(pool.max_in_use > s_sched.pool.max_in_use),
        "note": (
            "capacity comparison at equal pool bytes; paged tokens_per_s "
            "on CPU pays the gather-based page-table attention copy and "
            "the 4x wider decode batch — the TPU side of that path is "
            "the kernels/paged_attention Pallas kernel"),
    }
    write_bench_json("kv_memory", section)
    return section


# ---------------------------------------- prefix sharing (refcounted)

PS_PAGE = 16                  # tokens per page (npb = 2)
PS_GROUPS = 2                 # distinct "system prompts"
PS_GROUP_SIZE = 6             # requests per group (1 leader + 5)
PS_PREFIX_BLOCKS = 4          # shared prefix: 128 tok = 8 pages
PS_TAIL_BLOCKS = 1            # unique tail: 32 tok
PS_MAX_NEW = 24               # decode dwell: followers stay in flight
                              # long enough for concurrency to mean
                              # something
PS_POOL_PAGES = 40            # usable heap pages, BOTH runs: at 12
                              # pages/request full footprint, sharing
                              # off sustains ~3 in flight; sharing on
                              # charges only the ~4 unshared pages


def _prefix_sharing_workload(cfg, seed=7):
    """Shared-system-prompt traffic: PS_GROUPS families, each one
    leader then a simultaneous burst of followers with identical
    128-token prefixes and unique 32-token tails. Leaders get ~0.4 s
    of air so their prefix blocks are published before the follower
    burst asks for them — the steady state a production prefix cache
    serves. The burst lands together so sustained concurrency is
    limited ONLY by what the heap admits."""
    rng = np.random.default_rng(seed)
    N = cfg.ff.block_size
    prompts, arrivals = [], []
    for g in range(PS_GROUPS):
        grng = np.random.default_rng((seed, 100 + g))
        prefix = grng.integers(0, cfg.vocab,
                               PS_PREFIX_BLOCKS * N).tolist()
        t0 = g * 0.9
        for j in range(PS_GROUP_SIZE):
            tail = rng.integers(0, cfg.vocab, PS_TAIL_BLOCKS * N).tolist()
            prompts.append(prefix + tail)
            arrivals.append(t0 if j == 0 else t0 + 0.4)
    order = np.argsort(arrivals, kind="stable")
    return ([prompts[i] for i in order],
            [PS_MAX_NEW] * len(prompts),
            np.array([arrivals[i] for i in order]))


def _run_prefix_sharing(cfg, params):
    """Refcounted prefix sharing OFF vs ON on the SAME paged heap (equal
    pool bytes, equal workload). Off: every admission charges its full
    12-page footprint, so the 40-page heap sustains ~3 requests and
    followers queue behind strangers' prefill. On: followers map the
    leader's published prefix read-only, charge only the ~4 unshared
    pages, and start prefill at the first unshared block. Writes the
    `prefix_sharing` section: hit rate, blocks skipped, pages saved at
    peak, TTFT p50 and sustained concurrency both ways, plus the
    bit-identity and compile-flatness acceptance booleans."""
    cfg = cfg.with_(kv_layout="paged")
    prompts, max_news, arrivals = _prefix_sharing_workload(cfg)
    N = cfg.ff.block_size
    cache_len = -(-max(len(p) for p in prompts) // N) * N + PS_MAX_NEW
    requests = [Request(rid=i, prompt=prompts[i], max_new=max_news[i],
                        arrival_time=arrivals[i])
                for i in range(len(prompts))]

    def drive(prefix_cache):
        runtime = make_runtime(cfg, params)
        sched = ContinuousBatchingScheduler(
            runtime, n_slots=len(requests), cache_len=cache_len,
            prefill_batch=PREFILL_BATCH, page_size=PS_PAGE,
            n_pages=PS_POOL_PAGES + 1, prefix_cache=prefix_cache)
        counts0 = sched.warmup()
        wall = drive_stream(sched, requests)
        flat = None
        if None not in counts0.values():
            flat = runtime.compile_counts() == counts0
        outs = sched.finished
        assert len(outs) == len(requests)
        gen = sum(len(o.tokens) for o in outs.values())
        ttfts = np.array([outs[r.rid].ttft_seconds for r in requests])
        return sched, wall, gen, ttfts, flat

    off_sched, off_wall, off_gen, off_ttft, off_flat = drive(False)
    on_sched, on_wall, on_gen, on_ttft, on_flat = drive(True)

    identical = all(
        off_sched.finished[r.rid].tokens == on_sched.finished[r.rid].tokens
        for r in requests)
    ps = on_sched.prefix_stats()
    pool_on, pool_off = on_sched.pool, off_sched.pool
    section = {
        "config": {"page_size": PS_PAGE, "usable_pages": PS_POOL_PAGES,
                   "cache_len": cache_len, "groups": PS_GROUPS,
                   "group_size": PS_GROUP_SIZE,
                   "prefix_tokens": PS_PREFIX_BLOCKS * N,
                   "tail_tokens": PS_TAIL_BLOCKS * N,
                   "max_new": PS_MAX_NEW, "requests": len(requests)},
        "sharing_off": {
            "max_concurrent_requests": pool_off.max_in_use,
            "peak_pages_in_use": pool_off.max_pages_in_use,
            "ttft_p50_ms": round(float(np.percentile(off_ttft, 50)) * 1e3,
                                 2),
            "tokens_per_s": round(off_gen / off_wall, 1),
            "prefill_blocks": off_sched.n_prefill_blocks,
        },
        "sharing_on": {
            "max_concurrent_requests": pool_on.max_in_use,
            "peak_pages_in_use": pool_on.max_pages_in_use,
            "ttft_p50_ms": round(float(np.percentile(on_ttft, 50)) * 1e3,
                                 2),
            "tokens_per_s": round(on_gen / on_wall, 1),
            "prefill_blocks": on_sched.n_prefill_blocks,
            "hit_rate": round(ps["hit_rate"], 3),
            "hits": ps["hits"], "lookups": ps["lookups"],
            "requests_hit": ps["requests_hit"],
            "blocks_skipped": ps["blocks_skipped"],
            "pages_shared": ps["pages_shared"],
            "pages_published": ps["pages_published"],
            "cow_pages": ps["cow_pages"],
            "evictions": ps["evictions"],
        },
        # acceptance: from the SAME heap bytes, sharing must buy
        # strictly more sustained concurrency and a lower TTFT p50
        # while keeping greedy outputs bit-identical and the jit cache
        # flat after warmup
        "sharing_more_concurrent":
            bool(pool_on.max_in_use > pool_off.max_in_use),
        "sharing_lower_ttft_p50": bool(
            np.percentile(on_ttft, 50) < np.percentile(off_ttft, 50)),
        "hit_rate_nonzero": bool(ps["hit_rate"] > 0),
        "outputs_bit_identical": bool(identical),
        "compile_counts_flat": (None if off_flat is None or on_flat is None
                                else bool(off_flat and on_flat)),
        "note": ("equal-pool-bytes A/B on the refcounted paged heap; "
                 "followers map the leader's published prefix read-only "
                 "(copy-on-write only at a misaligned tail), so pages "
                 "saved = prefix pages x (group size - 1) at peak"),
    }
    write_bench_json("prefix_sharing", section)
    return section


# ------------------------------------- kv tiering (int8 quant + swap)

KT_PAGE = 16                  # tokens per page (divides block_size 32)
KT_F32_PAGES = 12             # usable f32 pages: the device byte budget
                              # both quant arms must fit in
KT_SLOTS = 16                 # slot table generous in every arm so the
                              # page heap is the ONLY capacity limit
KT_SWAP_PAGES = 64            # host tier pages for the swap arm
KT_HEAP_PAGES = 16            # usable device pages for the swap-vs-
                              # preempt A/B: long decodes overflow it


def _kv_page_bytes(cfg, page_size):
    """Device bytes of one (layer, K-or-V) page in each storage mode.

    f32 stores page_size x n_kv_heads x head_dim floats; int8 stores the
    same elements as one byte each plus a per-(page, kv-head) f32 scale
    — the 4 * n_kv_heads scale bytes are charged honestly, so the
    equal-byte page ratio lands just under 4x."""
    elems = page_size * cfg.n_kv_heads * cfg.head_dim
    return 4 * elems, elems + 4 * cfg.n_kv_heads


def _run_kv_tiering(cfg, params):
    """The `kv_tiering` section: two A/Bs on the paged heap.

    (a) int8 quantization at EQUAL device bytes — the byte budget is
    KT_F32_PAGES f32 pages; the quant arm spends the same bytes as
    ~3.97x as many int8 pages (scales charged), so under a deep burst
    it must sustain >= 2x the concurrent requests. Outputs are allclose
    (not bit-identical) with quant on — tested at logits level in
    tests/test_kv_quant.py — so this A/B is a capacity claim only.

    (b) swap-out vs preempt-and-recompute on the SAME undersized heap —
    the long-decode trace (benchmarks/traces/sample_longdecode.jsonl)
    overflows KT_HEAP_PAGES via decode growth; with swap_pages=0 the
    only valve is youngest-first preemption which re-runs finished
    prefill, with a host tier the victim's pages move device->host and
    back. Greedy outputs must be bit-identical, the swap arm must
    re-prefill strictly fewer blocks, and TTFT p99 should drop (the
    blocks count is the deterministic acceptance gate; p99 wall-clock
    is recorded but noisy on shared CPU)."""
    from repro.serving.trace import load_trace
    cfg = cfg.with_(kv_layout="paged")
    f32_pb, i8_pb = _kv_page_bytes(cfg, KT_PAGE)
    quant_pages = KT_F32_PAGES * f32_pb // i8_pb

    # --- (a) quant concurrency at equal device bytes: one deep burst,
    # concurrency limited only by the heap (KT_SLOTS slots both arms)
    prompts, max_news, arrivals = _kv_memory_workload(cfg, seed=3)
    N = cfg.ff.block_size
    cache_len = -(-max(len(p) for p in prompts) // N) * N + max(max_news)
    cache_len = -(-cache_len // KT_PAGE) * KT_PAGE
    requests = [Request(rid=i, prompt=prompts[i], max_new=max_news[i],
                        arrival_time=arrivals[i])
                for i in range(len(prompts))]

    def drive(cfg_run, n_pages, reqs, clen, swap_pages=0):
        runtime = make_runtime(cfg_run, params)
        sched = ContinuousBatchingScheduler(
            runtime, n_slots=KT_SLOTS, cache_len=clen,
            prefill_batch=PREFILL_BATCH, page_size=KT_PAGE,
            n_pages=n_pages, swap_pages=swap_pages)
        counts0 = sched.warmup()
        wall = drive_stream(sched, reqs)
        flat = None
        if None not in counts0.values():
            flat = runtime.compile_counts() == counts0
        outs = sched.finished
        assert len(outs) == len(reqs)
        gen = sum(len(o.tokens) for o in outs.values())
        ttfts = np.array([outs[r.rid].ttft_seconds for r in reqs])
        return sched, wall, gen, ttfts, flat

    f_sched, f_wall, f_gen, _, f_flat = drive(
        cfg, KT_F32_PAGES + 1, requests, cache_len)
    q_sched, q_wall, q_gen, _, q_flat = drive(
        cfg.with_(kv_quant=True), quant_pages + 1, requests, cache_len)

    # --- (b) swap vs preempt: identical heap, identical long-decode
    # trace, the ONLY knob is the host tier
    import os
    trace = os.path.join(os.path.dirname(__file__), "traces",
                         "sample_longdecode.jsonl")
    t_reqs = load_trace(trace, vocab=cfg.vocab)
    t_cache = (-(-max(len(r.prompt) for r in t_reqs) // N) * N
               + max(r.max_new for r in t_reqs))
    t_cache = -(-t_cache // KT_PAGE) * KT_PAGE
    p_sched, p_wall, p_gen, p_ttft, p_flat = drive(
        cfg, KT_HEAP_PAGES + 1, t_reqs, t_cache)
    s_sched, s_wall, s_gen, s_ttft, s_flat = drive(
        cfg, KT_HEAP_PAGES + 1, t_reqs, t_cache,
        swap_pages=KT_SWAP_PAGES)

    identical = all(
        p_sched.finished[r.rid].tokens == s_sched.finished[r.rid].tokens
        for r in t_reqs)
    ts = s_sched.tier_stats()
    flats = [f_flat, q_flat, p_flat, s_flat]
    section = {
        "config": {
            "page_size": KT_PAGE, "slots": KT_SLOTS,
            "f32_page_bytes": f32_pb, "int8_page_bytes": i8_pb,
            "device_bytes_budget": KT_F32_PAGES * f32_pb,
            "f32_usable_pages": KT_F32_PAGES,
            "int8_usable_pages": quant_pages,
            "burst_requests": len(requests),
            "swap_heap_pages": KT_HEAP_PAGES,
            "swap_host_pages": KT_SWAP_PAGES,
            "trace": "benchmarks/traces/sample_longdecode.jsonl",
            "trace_requests": len(t_reqs),
        },
        "quant_off": {
            "max_concurrent_requests": f_sched.pool.max_in_use,
            "peak_pages_in_use": f_sched.pool.max_pages_in_use,
            "preemptions": f_sched.n_preemptions,
            "tokens_per_s": round(f_gen / f_wall, 1),
        },
        "quant_on": {
            "max_concurrent_requests": q_sched.pool.max_in_use,
            "peak_pages_in_use": q_sched.pool.max_pages_in_use,
            "preemptions": q_sched.n_preemptions,
            "tokens_per_s": round(q_gen / q_wall, 1),
        },
        "preempt": {
            "preemptions": p_sched.n_preemptions,
            "prefill_blocks": p_sched.n_prefill_blocks,
            "ttft_p99_ms": round(float(np.percentile(p_ttft, 99)) * 1e3,
                                 2),
            "tokens_per_s": round(p_gen / p_wall, 1),
        },
        "swap": {
            "preemptions": s_sched.n_preemptions,
            "prefill_blocks": s_sched.n_prefill_blocks,
            "swap_outs": ts["swap_outs"], "swap_ins": ts["swap_ins"],
            "pages_swapped_out": ts["pages_swapped_out"],
            "pages_swapped_in": ts["pages_swapped_in"],
            "peak_host_pages_used": ts["peak_used"],
            "ttft_p99_ms": round(float(np.percentile(s_ttft, 99)) * 1e3,
                                 2),
            "tokens_per_s": round(s_gen / s_wall, 1),
        },
        # acceptance: equal device bytes must buy >= 2x sustained
        # concurrency with int8 pages, and the host tier must beat
        # preemption on re-prefilled blocks with bit-identical output
        "quant_2x_concurrent": bool(
            q_sched.pool.max_in_use >= 2 * f_sched.pool.max_in_use),
        "swap_fewer_prefill_blocks": bool(
            s_sched.n_prefill_blocks < p_sched.n_prefill_blocks),
        "swap_fewer_preemptions": bool(
            s_sched.n_preemptions < p_sched.n_preemptions),
        "swap_lower_ttft_p99": bool(
            np.percentile(s_ttft, 99) < np.percentile(p_ttft, 99)),
        "swap_outputs_bit_identical": bool(identical),
        "compile_counts_flat": (None if any(f is None for f in flats)
                                else bool(all(flats))),
        "note": (
            "quant A/B is a capacity comparison at equal device bytes "
            "(int8 outputs allclose, not bit-identical — see "
            "tests/test_kv_quant.py for the tolerance); swap A/B is "
            "deterministic on prefill blocks and preemptions, "
            "ttft_p99_ms is single-run wall-clock and noisy on a "
            "shared CPU"),
    }
    write_bench_json("kv_tiering", section)
    return section


# --------------------------------------------- overload (degrade A/B)

OV_REQUESTS = 40
OV_SLOTS = 4
OV_PREFILL_BATCH = 4
OV_PROMPT_BLOCKS = 4          # 4 blocks x 32 tok (reduced block size)
OV_MAX_NEW = 8
OV_DEADLINE_MS = 1200.0
OV_BASE_GAP_S = 0.05          # 1x offered rate: one request / 50 ms
# cost model: sim seconds a tick costs, priced from the plan mix of the
# work it actually did. ALPHA is the non-FFN fraction of block time
# (attention, norms, dispatch) that sparsity cannot remove.
OV_TICK_S = 0.002
OV_BLOCK_S = 0.012
OV_TOKEN_S = 0.002
OV_ALPHA = 0.3


def _run_overload(cfg, params):
    """Deadline goodput at 1x/2x/4x offered load, degrade-on vs
    degrade-off, on a simulated clock (see module docstring). The
    scheduler, admission controller, deadline/shed machinery, and
    jitted model calls are all real; only elapsed time is modeled, with
    each plan's block/token cost scaled by ALPHA + (1-ALPHA) *
    flop_frac — degrading to a sparser tier makes ticks cheaper exactly
    as the analytical speedup sections say it should. Acceptance: at
    >= 2x overload, degrade-on achieves STRICTLY higher goodput."""
    plans = tuple(
        dataclasses.replace(resolve_plan(cfg, effort=e), name=e)
        for e in ("dense", "balanced", "turbo"))
    runtime = make_runtime(cfg, params, plans=plans)
    fracs = np.array([p.flop_frac() for p in plans])
    eff = OV_ALPHA + (1 - OV_ALPHA) * fracs
    N = runtime.block_size
    prompt_len = OV_PROMPT_BLOCKS * N
    cache_len = prompt_len + OV_MAX_NEW
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, prompt_len).tolist()
               for _ in range(OV_REQUESTS)]

    def one_run(rate_x, degrade):
        clk = [0.0]
        admission = AdmissionController(plans, AdmissionConfig(
            queue_high=4, queue_low=1, dwell_ticks=2,
            degrade=degrade))
        sched = ContinuousBatchingScheduler(
            runtime, n_slots=OV_SLOTS, cache_len=cache_len,
            prefill_batch=OV_PREFILL_BATCH, admission=admission,
            clock=lambda: clk[0],
            sleep=lambda dt: clk.__setitem__(0, clk[0] + dt))
        sched.warmup()
        prev_pb = sched.plan_prefill_blocks.copy()
        prev_dt = sched.plan_decode_tokens.copy()

        def advance(s):
            # price the tick by the work it did, per plan
            dpb = s.plan_prefill_blocks - prev_pb
            ddt = s.plan_decode_tokens - prev_dt
            prev_pb[:] = s.plan_prefill_blocks
            prev_dt[:] = s.plan_decode_tokens
            clk[0] += (OV_TICK_S + float((dpb * eff).sum()) * OV_BLOCK_S
                       + float((ddt * eff).sum()) * OV_TOKEN_S)

        gap = OV_BASE_GAP_S / rate_x
        requests = [Request(rid=i, prompt=prompts[i], max_new=OV_MAX_NEW,
                            arrival_time=i * gap,
                            deadline_ms=OV_DEADLINE_MS)
                    for i in range(OV_REQUESTS)]
        sim_s = drive_stream(sched, requests, after_tick=advance)
        outs = sched.finished
        assert len(outs) == OV_REQUESTS
        met = sum(o.status == "ok"
                  and o.finish_seconds <= OV_DEADLINE_MS / 1e3
                  for o in outs.values())
        return {
            "goodput": round(met / OV_REQUESTS, 3),
            "n_ok": sum(o.status == "ok" for o in outs.values()),
            "n_shed": sched.n_shed,
            "n_timed_out": sched.n_timed_out,
            "n_degraded": sched.n_degraded,
            "peak_degradation_level": admission.peak_level,
            "sim_seconds": round(sim_s, 3),
        }

    runs = {}
    for rate_x in (1, 2, 4):
        runs[f"{rate_x}x"] = {
            "degrade_on": one_run(rate_x, degrade=True),
            "degrade_off": one_run(rate_x, degrade=False),
        }
    strictly_better = all(
        runs[k]["degrade_on"]["goodput"] > runs[k]["degrade_off"]["goodput"]
        for k in ("2x", "4x"))
    section = {
        "config": {
            "requests": OV_REQUESTS, "slots": OV_SLOTS,
            "prefill_batch": OV_PREFILL_BATCH,
            "prompt_len": prompt_len, "max_new": OV_MAX_NEW,
            "deadline_ms": OV_DEADLINE_MS,
            "base_rate_req_s": round(1 / OV_BASE_GAP_S, 1),
            "cost_model": {"tick_s": OV_TICK_S, "block_s": OV_BLOCK_S,
                           "token_s": OV_TOKEN_S, "non_ffn_alpha": OV_ALPHA,
                           "plan_flop_fracs": [round(float(f), 3)
                                               for f in fracs]},
        },
        "runs": runs,
        # acceptance: under overload, shedding FLOPs (graceful
        # degradation to sparser pre-compiled tiers) must beat shedding
        # requests/deadlines outright
        "degrade_strictly_better_at_overload": bool(strictly_better),
        "note": ("simulated-clock cost model (see module docstring): "
                 "real scheduler + admission decisions, analytical "
                 "per-plan tick pricing — deterministic, so degrade-on "
                 "vs degrade-off is a policy comparison, not CPU noise"),
    }
    write_bench_json("overload", section)
    return section


def run(csv=True, requests=REQUESTS):
    cfg = get_config("tinyllama-1.1b", reduced=True)
    params = init_params(get_model(cfg).specs(cfg), jax.random.key(0))
    prompts, max_news, arrivals = _workload(cfg, requests=requests)

    s_tok, s_wall, s_ttft = _run_static(cfg, params, prompts, max_news,
                                        arrivals)
    c1_tok, c1_wall, c1_ttft, _, _ = _run_continuous(
        cfg, params, prompts, max_news, arrivals, prefill_batch=1)
    c_tok, c_wall, c_ttft, sched, flat = _run_continuous(
        cfg, params, prompts, max_news, arrivals,
        prefill_batch=PREFILL_BATCH)
    # measured FastForward speedup: same batched scheduler, dense FFN
    d_tok, d_wall, d_ttft, _, _ = _run_continuous(
        cfg.with_ff(enabled=False), params, prompts, max_news, arrivals,
        prefill_batch=PREFILL_BATCH)

    static, single = _stats(s_tok, s_wall, s_ttft), _stats(c1_tok, c1_wall,
                                                           c1_ttft)
    batched, dense = _stats(c_tok, c_wall, c_ttft), _stats(d_tok, d_wall,
                                                           d_ttft)
    ratios = {
        "continuous_vs_static_tokens_per_s":
            round(batched["tokens_per_s"] / static["tokens_per_s"], 3),
        "batched_vs_single_tokens_per_s":
            round(batched["tokens_per_s"] / single["tokens_per_s"], 3),
        "batched_vs_single_ttft_p50":
            round(single["ttft_p50_ms"] / batched["ttft_p50_ms"], 3),
        "fastforward_vs_dense_tokens_per_s":
            round(batched["tokens_per_s"] / dense["tokens_per_s"], 3),
    }
    write_bench_json("serving", {
        "config": {"slots": SLOTS, "prefill_batch": PREFILL_BATCH,
                   "requests": len(prompts),
                   "prompt_range": list(PROMPT_RANGE),
                   "max_new_range": list(MAX_NEW_RANGE),
                   "burst": BURST, "burst_gap_s": GAP_S,
                   "arch": cfg.name, "reduced": True},
        "static": static,
        "continuous_single_prefill": single,
        "continuous_batched_prefill": dict(
            batched,
            prefill_ticks=sched.n_prefill_ticks,
            prefill_blocks=sched.n_prefill_blocks,
            blocks_per_tick=round(sched.n_prefill_blocks
                                  / max(sched.n_prefill_ticks, 1), 2)),
        "continuous_batched_dense": dense,
        "ratios": ratios,
        # the caveat the CSV output prints, carried into the artifact:
        # single-run wall-clock on a shared CPU swings this ratio well
        # below/above 1.0 run-to-run (PR-over-PR values 0.9-1.2x are
        # machine noise, not regressions)
        "ratios_note": (
            "fastforward_vs_dense_tokens_per_s is overhead-bound and "
            "noisy on the reduced CPU config; the compute-bound speedup "
            "is the analytical_speedup_vs_dense section"),
        "compile_counts_flat": flat,
    })

    kv = _run_kv_memory(cfg, params)
    px = _run_prefix_sharing(cfg, params)
    kt = _run_kv_tiering(cfg, params)
    ov = _run_overload(cfg, params)

    rows = [
        ("static_tokens_per_s", f"{static['tokens_per_s']:.1f}",
         f"{len(prompts)} reqs, {SLOTS}-wide rounds, lockstep decode"),
        ("static_ttft_p50_ms", f"{static['ttft_p50_ms']:.1f}", ""),
        ("static_ttft_p99_ms", f"{static['ttft_p99_ms']:.1f}", ""),
        ("continuous_single_tokens_per_s", f"{single['tokens_per_s']:.1f}",
         "PR-1 one-block-per-tick prefill"),
        ("continuous_single_ttft_p50_ms", f"{single['ttft_p50_ms']:.1f}",
         ""),
        ("continuous_tokens_per_s", f"{batched['tokens_per_s']:.1f}",
         f"{SLOTS} KV slots, P={PREFILL_BATCH} batched prefill, "
         f"{sched.pool.total_acquires} acquires, "
         f"{sched.n_prefill_blocks} prefill blocks in "
         f"{sched.n_prefill_ticks} prefill ticks, "
         f"{sched.n_decode_steps} decode steps"),
        ("continuous_ttft_p50_ms", f"{batched['ttft_p50_ms']:.1f}", ""),
        ("continuous_ttft_p99_ms", f"{batched['ttft_p99_ms']:.1f}", ""),
        ("throughput_ratio", f"{ratios['continuous_vs_static_tokens_per_s']:.2f}",
         "continuous/static tokens-per-sec (target >= 1.3x)"),
        ("batched_prefill_ratio",
         f"{ratios['batched_vs_single_tokens_per_s']:.2f}",
         "batched/single-prefill tokens-per-sec (target > 1.0)"),
        ("batched_ttft_ratio",
         f"{ratios['batched_vs_single_ttft_p50']:.2f}",
         "single/batched TTFT p50 (target > 1.0)"),
        ("fastforward_vs_dense_ratio",
         f"{ratios['fastforward_vs_dense_tokens_per_s']:.2f}",
         "sparse/dense tok/s, batched serving path (noisy on the "
         "overhead-bound CPU reduced config; the compute-bound "
         "speedup is the analytical_speedup_vs_dense section)"),
        ("kv_slot_max_concurrent",
         f"{kv['slot']['max_concurrent_requests']}",
         f"{kv['config']['pool_tokens']}-token pool as "
         f"{kv['config']['slot_n_slots']} full-length slots; "
         f"stranded@peak {kv['slot']['stranded_tokens_at_peak']} tok"),
        ("kv_paged_max_concurrent",
         f"{kv['paged']['max_concurrent_requests']}",
         f"same bytes as {kv['config']['paged_usable_pages']} x "
         f"{kv['config']['page_size']}-token pages; peak "
         f"{kv['paged']['peak_pages_in_use']} pages, stranded@peak "
         f"{kv['paged']['stranded_tokens_at_peak']} tok, "
         f"{kv['paged']['preemptions']} preemptions "
         f"(target: > slot concurrency)"),
        ("prefix_hit_rate",
         f"{px['sharing_on']['hit_rate']:.2f}",
         f"{px['sharing_on']['hits']}/{px['sharing_on']['lookups']} "
         f"admissions mapped a cached prefix, "
         f"{px['sharing_on']['blocks_skipped']} prefill blocks skipped "
         f"({px['sharing_off']['prefill_blocks']} -> "
         f"{px['sharing_on']['prefill_blocks']})"),
        ("prefix_max_concurrent_on",
         f"{px['sharing_on']['max_concurrent_requests']}",
         f"vs {px['sharing_off']['max_concurrent_requests']} sharing "
         f"off at the same {px['config']['usable_pages']}-page heap; "
         f"peak pages {px['sharing_on']['peak_pages_in_use']} vs "
         f"{px['sharing_off']['peak_pages_in_use']} "
         f"(target: strictly more requests in flight)"),
        ("prefix_ttft_p50_ms_on",
         f"{px['sharing_on']['ttft_p50_ms']:.1f}",
         f"vs {px['sharing_off']['ttft_p50_ms']:.1f} sharing off "
         f"(target: lower — followers skip the shared prefill)"),
        ("prefix_outputs_bit_identical",
         f"{px['outputs_bit_identical']}",
         "acceptance: greedy outputs identical sharing on vs off"),
        ("kv_quant_max_concurrent",
         f"{kt['quant_on']['max_concurrent_requests']}",
         f"vs {kt['quant_off']['max_concurrent_requests']} f32 at the "
         f"same {kt['config']['device_bytes_budget']} device bytes "
         f"({kt['config']['int8_usable_pages']} int8 vs "
         f"{kt['config']['f32_usable_pages']} f32 pages; "
         f"target: >= 2x)"),
        ("kv_swap_prefill_blocks",
         f"{kt['swap']['prefill_blocks']}",
         f"vs {kt['preempt']['prefill_blocks']} preempt-only on the "
         f"same {kt['config']['swap_heap_pages']}-page heap "
         f"({kt['swap']['swap_outs']} swap outs / "
         f"{kt['swap']['swap_ins']} ins, "
         f"{kt['preempt']['preemptions']} -> "
         f"{kt['swap']['preemptions']} preemptions; target: fewer — "
         f"swapped requests resume instead of re-prefilling)"),
        ("kv_swap_ttft_p99_ms",
         f"{kt['swap']['ttft_p99_ms']:.1f}",
         f"vs {kt['preempt']['ttft_p99_ms']:.1f} preempt-only "
         f"(wall-clock, noisy on shared CPU)"),
        ("kv_swap_outputs_bit_identical",
         f"{kt['swap_outputs_bit_identical']}",
         "acceptance: greedy outputs identical swap on vs off"),
        ("overload_goodput_2x_degrade_on",
         f"{ov['runs']['2x']['degrade_on']['goodput']:.3f}",
         f"deadline-met fraction at 2x offered rate, "
         f"{ov['runs']['2x']['degrade_on']['n_degraded']} degraded, "
         f"{ov['runs']['2x']['degrade_on']['n_timed_out']} timed out "
         f"(simulated clock)"),
        ("overload_goodput_2x_degrade_off",
         f"{ov['runs']['2x']['degrade_off']['goodput']:.3f}",
         f"{ov['runs']['2x']['degrade_off']['n_timed_out']} timed out, "
         f"{ov['runs']['2x']['degrade_off']['n_shed']} shed"),
        ("overload_goodput_4x_degrade_on",
         f"{ov['runs']['4x']['degrade_on']['goodput']:.3f}", ""),
        ("overload_goodput_4x_degrade_off",
         f"{ov['runs']['4x']['degrade_off']['goodput']:.3f}", ""),
        ("overload_degrade_strictly_better",
         f"{ov['degrade_strictly_better_at_overload']}",
         "acceptance: degrade-on goodput strictly higher at >= 2x"),
    ]
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
