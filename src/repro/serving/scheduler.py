"""Continuous-batching scheduler: chunked FastForward prefill
interleaved with batched decode over a KV slot pool.

The paper's 128-token prefill block is exactly one schedulable unit of
work, so each scheduler tick does

  1. ADMIT   — move queued requests into free KV slots (mid-flight:
               a slot freed by a finishing request is re-filled on the
               very next tick, while other requests keep decoding);
  2. PREFILL — process one FastForward block of EACH of up to
               `prefill_batch` requests still prefilling (oldest
               first), in ONE jitted `prefill_blocks` call with
               per-row slot/offset/is_dense/length vectors
               (dense-first/last semantics preserved *per sequence*,
               unlike the static right-padded batch where the padded
               batch's last block is dense instead). The batch width P
               is static: short ticks pad with inactive rows whose
               slot ids are unused by this call's live rows.
               prefill_batch=1 keeps the original one-block-per-tick
               `prefill_block` path (baseline for benchmarks/tests);
  3. DECODE  — one batched decode step over every slot in the decode
               phase (fixed batch = n_slots, active-slot mask).

All device work goes through the jitted ModelRuntime entry points, so
after the first tick of each kind there is zero recompilation —
`ModelRuntime.compile_counts()` is the enforcement hook.

Requests carrying an `eos_id` finish the moment they emit it —
mid-generation — and their slot returns to the free list on the same
tick, so EOS-heavy streams churn admission under the batched prefill
path (`n_eos_stops` counts early exits).

KV layouts (cfg.kv_layout): with the default "slot" layout every
admitted request reserves a full max-cache_len KVSlotPool slot. With
"paged" the pool is a PagedKVPool page heap: ADMIT gates on free PAGES
(enough for one prefill block), pages are allocated lazily — one block
per prefill tick, one page at a time as decode crosses a page boundary
— and when the heap runs dry the scheduler PREEMPTS the youngest
request (release its pages, requeue it for re-prefill from scratch;
greedy output is deterministic so the final tokens are unchanged, only
its latency suffers — `n_preemptions` counts evictions). Only
strictly-younger requests are ever evicted, so the oldest always makes
progress and a stream that fits the heap per-request always drains.
Page tables are traced values, so the paged entries compile once per
width bucket exactly like the slot entries.

PREFIX SHARING (paged only, `prefix_cache=True`): page ownership is
refcounted (PagedKVPool) and a host-side PrefixIndex maps page-aligned
(SparsityPlan, token-chain) keys to cached pages. Admission looks up
the longest cached chain for the queue head, maps those pages into its
table as shared READERS (`pool.share`), copy-on-writes the partial
tail of the restart block, charges the gate only the UNSHARED page
footprint, and starts prefill at the first unshared block — the TTFT
win: shared prompt blocks never run. Each completed prompt block
(never the last — its pages see the request's own decode-adjacent
partial fills) is published back to the index. Release paths decrement
refcounts; cached pages whose last reader left park on a reclaimable
LRU, evicted (`PrefixIndex.drop_page`, whole subtrees) before the
scheduler resorts to preemption. Shared KV is bit-identical to
recomputing it — block b's KV depends only on the token chain before
it and the plan — so greedy output with sharing on equals sharing off,
and requests under DIFFERENT plans never share (plan keys the trie
root).

OVERLOAD SEMANTICS (the robustness contract, as load-bearing as the
bit-equivalence contract): requests carry optional deadlines
(`ttft_deadline_ms`, `deadline_ms`) and every request finishes with a
`RequestOutput.status` in {ok, timed_out, shed, cancelled}. The
pressure valves fire in a fixed order — SHED at submit (a request that
cannot fit the pool, or provably cannot meet its deadline, costs zero
device work), DEGRADE at admission (an `AdmissionController` routes
new admissions to sparser pre-compiled SparsityPlan tiers while
watermarks are tripped; the decision STICKS for the request's
lifetime, so preemption re-admits under the same tier and stays
output-transparent), PREEMPT under page pressure (work already done is
discarded last, youngest first). Deadline expiry and client
cancellation (`cancel(rid)`) free slots/pages idempotently mid-flight,
and a stall watchdog raises `SchedulerStallError` with a full state
dump when `stall_ticks` consecutive ticks make no observable progress
— a livelocked scheduler fails loudly instead of spinning. A seeded
`FaultInjector` (serving/faults.py, `faults=`) can drive all of these
paths deterministically.
"""
from __future__ import annotations

import dataclasses
import pprint
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.serving import spans
from repro.serving.admission import AdmissionController
from repro.serving.cache_pool import KVSlotPool
from repro.serving.kv_tier import HostKVTier
from repro.serving.page_pool import PagedKVPool
from repro.serving.prefix_index import PrefixIndex
from repro.serving.runtime import ModelRuntime
from repro.serving.speculative import SpeculativeConfig, accept_drafts


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int = 32
    temperature: float = 0.0
    eos_id: Optional[int] = None
    arrival_time: Optional[float] = None   # None -> stamped at submit()
    # SparsityPlan name registered on the runtime (effort tier, e.g.
    # "balanced"/"turbo"/"dense"); None -> the runtime's default plan.
    # The per-request sparsity knob: SLO-tiered traffic mixes tiers in
    # one stream with zero recompilation (plans are pre-compiled).
    effort: Optional[str] = None
    # deadlines, measured from arrival_time. Expiry frees the request's
    # resources mid-flight with status="timed_out"; at submit, a
    # provably-unmeetable deadline sheds instead (status="shed").
    ttft_deadline_ms: Optional[float] = None   # arrival -> first token
    deadline_ms: Optional[float] = None        # arrival -> last token
    # trace replay: the client cancels this many seconds after arrival
    # (drive_stream issues the cancel; see serving/trace.py)
    cancel_after_s: Optional[float] = None
    # per-request cap on the speculative draft length (tokens drafted
    # per decode tick); None -> the scheduler's SpeculativeConfig.k,
    # 0 -> speculation off for this request. Only latency-relevant:
    # greedy output is bit-identical for every value (the verify plan
    # is always the request's own plan).
    speculate: Optional[int] = None
    # scheduler-internal: plan index pinned at FIRST admission (the
    # degradation decision sticks, so preemption re-admits under the
    # SAME tier and stays output-transparent). Not a user field.
    assigned_plan_idx: Optional[int] = None
    # scheduler-internal: start of the current queued interval (arrival,
    # or the preemption that requeued it) — the `req.queued` span's.
    queued_at: Optional[float] = None


@dataclasses.dataclass
class RequestOutput:
    rid: int
    tokens: List[int]
    prompt_len: int
    ttft_seconds: Optional[float]  # arrival -> first token (None when
    #                                none was produced: shed, cancelled
    #                                or timed out during prefill)
    finish_seconds: float        # arrival -> terminal state
    # terminal status: "ok" | "timed_out" | "shed" | "cancelled".
    # Non-ok outputs keep whatever tokens were produced before the
    # terminal event (timed_out/cancelled may be partial; shed is
    # always empty).
    status: str = "ok"
    reason: Optional[str] = None   # human-readable cause for non-ok
    effort: Optional[str] = None   # REALIZED plan name (degradation
    #                                may have made it sparser than the
    #                                request asked)


@dataclasses.dataclass
class _ActiveState:
    req: Request
    slot: int
    seq: int                     # admission order (FIFO prefill)
    n_blocks: int
    rng: np.random.Generator     # per-request sampling stream, seeded
    #                              (scheduler seed, rid): a preempted
    #                              request re-admits with a FRESH copy,
    #                              so its re-run replays identical
    #                              temperature draws — preemption is
    #                              output-transparent for sampled
    #                              requests too, and one request's
    #                              draws never shift another's
    plan_idx: int = 0            # index into scheduler.plans (effort)
    blocks_done: int = 0
    phase: str = "prefill"       # prefill | decode
    out: List[int] = dataclasses.field(default_factory=list)
    next_token: int = 0          # last sampled token (decode input)
    pos: int = 0                 # next KV write position
    admitted_at: Optional[float] = None
    first_token_time: Optional[float] = None
    # prefix sharing: the prompt's page-aligned token tuples (None when
    # the cache is off) — lookup happens at admission, publish per
    # completed block
    page_keys: Optional[List[tuple]] = None


class SchedulerStallError(RuntimeError):
    """The scheduler made no observable progress for `stall_ticks`
    consecutive ticks while work was pending — a livelock. Carries the
    full scheduler-state dump (`.state`) that is also formatted into
    the message, so the failure is diagnosable from the raise alone."""

    def __init__(self, message: str, state: dict):
        super().__init__(message)
        self.state = state


class ContinuousBatchingScheduler:
    """Admits requests from a queue into KV slots mid-flight and
    interleaves chunked blockwise prefill with batched decode."""

    def __init__(self, runtime: ModelRuntime, n_slots: int = 8,
                 cache_len: int = 2048, seed: int = 0,
                 prefill_batch: int = 4, clock=time.perf_counter,
                 sleep=time.sleep, page_size: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 admission: Optional[AdmissionController] = None,
                 faults=None, stall_ticks: int = 1000,
                 prefix_cache: bool = False,
                 speculative: Optional[SpeculativeConfig] = None,
                 swap_pages: int = 0):
        self.runtime = runtime
        layout = getattr(runtime.cfg, "kv_layout", "slot")
        self.kv_layout = layout
        self.paged = layout == "paged"
        if self.paged:
            psz = int(page_size or runtime.cfg.kv_page_size
                      or runtime.block_size)
            if runtime.block_size % psz:
                raise ValueError(
                    f"page_size={psz} must divide the prefill block size "
                    f"{runtime.block_size} (a block scatters whole pages)")
            max_pages = -(-cache_len // psz)
            # page-align the per-request capacity so the gathered
            # attention views keep one fixed [*, max_pages*psz] width
            cache_len = max_pages * psz
            if n_pages is None:
                # default: full backing (every slot can reach max_pages
                # simultaneously — no preemption) + the null page. Pass
                # a smaller n_pages to oversubscribe the heap.
                n_pages = n_slots * max_pages + 1
            self.pool = PagedKVPool.create(runtime, n_pages, psz, n_slots,
                                           max_pages)
            self._npb = runtime.block_size // psz   # pages per block
        elif layout == "slot":
            self.pool = KVSlotPool.create(runtime, n_slots, cache_len)
        else:
            raise ValueError(f"unknown kv_layout={layout!r}; expected "
                             f"'slot' or 'paged'")
        if prefix_cache and not self.paged:
            raise ValueError("prefix_cache requires kv_layout='paged' "
                             "(the slot layout has no shareable pages)")
        # memory tiering (serving/kv_tier.py): a host swap tier of
        # swap_pages pages behind the device heap. Under page pressure
        # the scheduler swaps out the youngest request's exclusive
        # pages (device->host, request PARKED keeping its slot) before
        # resorting to preempt-and-recompute; parked requests resume
        # oldest-first as pages free up, with bit-identical KV bytes.
        if swap_pages and not self.paged:
            raise ValueError("swap_pages requires kv_layout='paged' "
                             "(the slot layout has no swappable pages)")
        self.host_tier = (HostKVTier(swap_pages) if swap_pages else None)
        if self.host_tier is not None:
            self.pool.attach_host_tier(self.host_tier)
        self.parked: Dict[int, _ActiveState] = {}   # slot -> state
        self.n_swap_outs = 0          # park events (requests swapped out)
        self.n_swap_ins = 0           # resume events
        self.prefix_cache = bool(prefix_cache)
        self.prefix_index = (PrefixIndex(self.pool) if self.prefix_cache
                             else None)
        # prefix-sharing counters (hit accounting lives here — the
        # index counts lookups/publishes, the pool counts mappings)
        self.n_prefix_hits = 0        # admissions that skipped >=1 block
        self.n_shared_blocks = 0      # prompt blocks never prefilled
        self.n_slots = n_slots
        self.cache_len = cache_len
        # max width of the batched prefill entry: up to this many
        # requests advance one block per tick in ONE jitted call. Must
        # not exceed n_slots (pad rows need distinct unused slot ids).
        self.prefill_batch = max(1, min(prefill_batch, n_slots))
        # width buckets (powers of two up to prefill_batch): each tick
        # picks the smallest bucket holding its live rows, so a thin
        # backlog never pays the full P-wide padded batch. One
        # executable per bucket, all pre-compiled by warmup().
        self.prefill_widths = []
        w = 1
        while w < self.prefill_batch:
            self.prefill_widths.append(w)
            w *= 2
        self.prefill_widths.append(self.prefill_batch)
        # registered SparsityPlans (effort tiers) — plan identity joins
        # the prefill batching key next to is_dense, and decode carries
        # per-slot plan_ids (one executable; see runtime)
        self.plans = tuple(getattr(runtime, "plans", ()) or ())
        self.plan_index = dict(getattr(runtime, "plan_index", {}) or {})
        n_plans = max(len(self.plans), 1)
        self.plan_prefill_blocks = np.zeros(n_plans, np.int64)
        self.plan_prefill_sparse_rows = np.zeros(n_plans, np.int64)
        self.plan_decode_tokens = np.zeros(n_plans, np.int64)
        # self-speculative decoding (serving/speculative.py): drafts
        # come from the SAME weights under the named (sparser) plan, so
        # both executables are already registered. Per-VERIFY-plan
        # draft index, clamped so a degraded request's draft is never
        # DENSER than its verify plan (flop_frac orders the tiers).
        self.speculative = speculative
        self.n_spec_ticks = 0
        self.spec_row_ticks = np.zeros(n_plans, np.int64)
        self.spec_drafted = np.zeros(n_plans, np.int64)
        self.spec_accepted = np.zeros(n_plans, np.int64)
        self.spec_emitted = np.zeros(n_plans, np.int64)
        if speculative is not None and speculative.k > 0:
            if speculative.draft not in self.plan_index:
                raise ValueError(
                    f"speculative draft plan {speculative.draft!r} is not "
                    f"a registered SparsityPlan "
                    f"(have {sorted(self.plan_index)}); pass plans= to "
                    f"make_runtime / serve.py --effort")
            di = self.plan_index[speculative.draft]
            dff = self.plans[di].flop_frac()
            self._draft_plan_for = np.array(
                [di if self.plans[i].flop_frac() >= dff else i
                 for i in range(len(self.plans))], np.int32)
        else:
            self._draft_plan_for = np.zeros(n_plans, np.int32)
        # overload-resilience layer: admission controller (deadline
        # shedding + hysteretic tier degradation, serving/admission.py)
        # and deterministic fault injector (serving/faults.py). The
        # injector wraps the clock so injected slow ticks advance
        # observed time for the deadline/timeout paths.
        self.admission = admission
        self.faults = faults
        if faults is not None:
            clock = faults.wrap_clock(clock)
        self.clock = clock
        # idle wait between stream arrivals (drive_stream). Injected
        # alongside `clock` so a fake/simulated clock brings a matching
        # sleep: waiting on wall time for a delta measured on a fake
        # clock would block a deterministic stream test on real seconds.
        self.sleep = sleep
        self.seed = seed
        self.queue: deque[Request] = deque()
        self.active: Dict[int, _ActiveState] = {}   # slot -> state
        self.finished: Dict[int, RequestOutput] = {}
        self._admit_seq = 0
        # tick counters (benchmarks / tests)
        self.n_ticks = 0
        self.n_prefill_blocks = 0
        self.n_prefill_ticks = 0
        self.n_decode_steps = 0
        # prefill rows run by FFN branch: dense (a first or last prompt
        # block) or sparse (per plan below), so the realized FFN FLOP
        # fraction counts each at what it computed
        self.n_prefill_rows_dense = 0
        self.n_prefill_rows_sparse = 0
        self.n_eos_stops = 0
        self.n_preemptions = 0
        # robustness counters (terminal statuses + degradation)
        self.n_shed = 0
        self.n_timed_out = 0
        self.n_cancelled = 0
        self.n_degraded = 0
        # stall watchdog: raise after this many consecutive ticks with
        # no observable progress while work is pending (see tick())
        self.stall_ticks = stall_ticks
        self._stall_count = 0
        self._last_sig = None
        self._total_emitted = 0
        # fastest prefill tick ever observed — the LOWER BOUND the
        # predictive deadline shed is proved against (None until a
        # nonzero duration is measured; fake clocks never shed
        # predictively)
        self._min_prefill_tick_s: Optional[float] = None

    # --------------------------------------------------------- submit

    def submit(self, req: Request) -> None:
        """Validate and enqueue (span `sched.submit`). Malformed
        requests (empty prompt, max_new < 1, unknown effort) are CALLER
        bugs and still raise; a well-formed request the pool can never
        hold, or that provably cannot meet its deadline, is SHED
        instead — it finishes
        immediately with status="shed" and a reason, so one oversized
        request in a stream no longer kills the whole replay (and can
        never livelock admission waiting for pages that cannot exist)."""
        with spans.span("sched.submit", rid=req.rid):
            self._submit(req)

    def _submit(self, req: Request) -> None:
        if not req.prompt:
            raise ValueError(f"request {req.rid} has an empty prompt")
        if req.max_new < 1:
            raise ValueError(f"request {req.rid}: max_new must be >= 1 "
                             f"(the first token is sampled from prefill "
                             f"logits and always emitted)")
        if req.speculate is not None and req.speculate < 0:
            raise ValueError(f"request {req.rid}: speculate must be >= 0, "
                             f"got {req.speculate}")
        if req.effort is not None and req.effort not in self.plan_index:
            raise ValueError(
                f"request {req.rid}: effort {req.effort!r} is not a "
                f"registered SparsityPlan "
                f"(have {sorted(self.plan_index)}); pass plans= to "
                f"make_runtime / serve.py --effort")
        if req.arrival_time is None:
            req.arrival_time = self.clock()
        need = max(self._n_blocks(req) * self.runtime.block_size,
                   len(req.prompt) + req.max_new)
        if not self.pool.fits(need):
            if self.paged:
                reason = (
                    f"needs {need} cache positions "
                    f"({self.pool.pages_for(need)} pages) but the paged "
                    f"pool backs at most {self.pool.n_pages - 1} usable "
                    f"pages of {self.pool.page_size} tokens per request "
                    f"(table width {self.pool.max_pages} pages) — grow "
                    f"n_pages/--pool-pages or cache_len")
            else:
                reason = (f"needs {need} cache positions but the pool's "
                          f"cache_len is {self.cache_len}")
            self._finish_queued(req, "shed", reason)
            return
        reason = AdmissionController.shed_reason(
            req, now=self.clock(), n_blocks=self._n_unshared_blocks(req),
            min_block_s=self._min_prefill_tick_s)
        if reason is not None:
            self._finish_queued(req, "shed", reason)
            return
        req.queued_at = req.arrival_time
        self.queue.append(req)

    def _n_blocks(self, req: Request) -> int:
        N = self.runtime.block_size
        return -(-len(req.prompt) // N)

    def _n_unshared_blocks(self, req: Request) -> int:
        """Prompt blocks this request would actually RUN if admitted
        right now — with the prefix cache on, blocks covered by the
        currently-cached chain are subtracted (the shed bound charges
        only unshared work). Quasi-provable rather than provable: the
        cached chain can only GROW a request's coverage while it queues
        (evictions only fire under pressure, in which case the request
        was going to wait anyway), so shedding against today's coverage
        never sheds a request that sharing would have saved."""
        n_blocks = self._n_blocks(req)
        if self.prefix_index is None:
            return n_blocks
        plan_idx = (req.assigned_plan_idx
                    if req.assigned_plan_idx is not None
                    else self.plan_index.get(req.effort, 0))
        matched = self.prefix_index.lookup(
            self._plan_name(plan_idx), self._page_keys(req), record=False)
        return n_blocks - min(len(matched) // self._npb, n_blocks - 1)

    def _page_keys(self, req: Request) -> List[tuple]:
        """Page-aligned token tuples of the SHAREABLE prompt prefix
        (everything before the last block — a request's final prompt
        block is never shared: its pages hold the partial fill and the
        decode-adjacent state)."""
        return PrefixIndex.page_keys(
            req.prompt, self.pool.page_size,
            (self._n_blocks(req) - 1) * self._npb)

    # ----------------------------------------------------------- tick

    @property
    def drained(self) -> bool:
        return not self.queue and not self.active and not self.parked

    def tick(self) -> int:
        """One scheduling step; returns the number of tokens emitted.

        Order of the overload valves: fault injection (chaos runs),
        admission-pressure observation, deadline expiry (frees
        resources BEFORE admission so an expired request's pages seat
        the next one), swap-in resume (parked requests claim freed
        pages BEFORE new admissions — they are older than anything
        queued), admit (with degradation), prefill, decode, and
        finally the stall watchdog — `stall_ticks` consecutive ticks
        with pending work and no observable progress raise
        `SchedulerStallError` with a full state dump.

        Spans (serving/spans.py): `sched.tick`, numbered by `n_ticks`,
        holds `sched.admit`, then per phase `sched.prefill.build` /
        `sched.decode.build` (batch and host arrays), `.dispatch` (the
        runtime call), `sched.readback` (host reads of device output)
        and `sched.emit` (tokens, finishes, the watchdog)."""
        self.n_ticks += 1
        with spans.step("sched.tick", self.n_ticks, self.clock):
            return self._tick()

    def _tick(self) -> int:
        with spans.span("sched.admit"):
            if self.faults is not None:
                self.faults.on_tick(self)
            if self.admission is not None:
                self.admission.observe(len(self.queue), self._free_frac())
            self._expire_deadlines()
            self._resume_swapped()
            self._admit()
        t0 = self.clock()
        before = self.n_prefill_ticks
        emitted = self._prefill_blocks()
        if self.n_prefill_ticks > before:
            dt = self.clock() - t0
            # fastest observed prefill tick: the provable lower bound
            # behind predictive deadline shedding (fake clocks measure
            # 0.0 and therefore never enable it)
            if dt > 0 and (self._min_prefill_tick_s is None
                           or dt < self._min_prefill_tick_s):
                self._min_prefill_tick_s = dt
        # sample occupancy/stranding stats mid-tick too: short requests
        # can admit, prefill, decode, and release within ONE tick, and
        # the peak the kv_memory benchmark compares is the post-prefill
        # moment, not the post-release one
        self.pool.note_tick()
        emitted += self._decode_all()
        self.pool.note_tick()
        self._total_emitted += emitted
        with spans.span("sched.emit"):
            self._watchdog()
        return emitted

    def _free_frac(self) -> float:
        """Free-resource fraction for the admission watermarks:
        available pages of the paged heap (truly free PLUS reclaimable
        cached-idle pages — they surrender to eviction on demand, so
        counting them as pressure would make a popular cached prefix
        read as an overloaded heap), free slots of the slot pool. With
        a host tier attached its free capacity counts too: swap-out
        absorbs pressure that would otherwise preempt, so admission
        watermarks gate on BOTH tiers' headroom."""
        if self.paged:
            usable = self.pool.n_pages - 1
            avail = self.pool.n_available_pages
            if self.host_tier is not None:
                usable += self.host_tier.capacity_pages
                avail += self.host_tier.n_free
            return avail / usable if usable else 0.0
        return self.pool.n_free / self.n_slots

    def _watchdog(self) -> None:
        if self.drained:
            self._stall_count = 0
            self._last_sig = None
            return
        # every way the scheduler can make progress moves one of these:
        # admissions/finishes change the queue/finished lengths, prefill
        # moves n_prefill_blocks, decode moves _total_emitted, and
        # preemption/swap churn moves n_preemptions/n_swap_outs/ins
        sig = (len(self.queue), len(self.active), len(self.finished),
               self.n_prefill_blocks, self.n_preemptions,
               self.n_swap_outs, self.n_swap_ins,
               self._total_emitted)
        if sig == self._last_sig:
            self._stall_count += 1
        else:
            self._stall_count = 0
            self._last_sig = sig
        if self._stall_count >= self.stall_ticks:
            state = self.dump_state()
            raise SchedulerStallError(
                f"scheduler stalled: no progress for {self._stall_count} "
                f"consecutive ticks with work pending\n"
                f"{pprint.pformat(state, width=78)}", state)

    def run(self, max_ticks: int = 1_000_000) -> Dict[int, RequestOutput]:
        """Drive ticks until every submitted request has finished (any
        terminal status). Raises SchedulerStallError — with a full
        scheduler-state dump — instead of spinning when ticks stop
        making progress."""
        for _ in range(max_ticks):
            if self.drained:
                break
            self.tick()
        if not self.drained:
            state = self.dump_state()
            raise SchedulerStallError(
                f"scheduler not drained after {max_ticks} ticks\n"
                f"{pprint.pformat(state, width=78)}", state)
        if self.faults is not None:
            self.faults.finalize(self)
        return self.finished

    # ----------------------------------------------------- state dump

    def dump_state(self) -> dict:
        """Full host-side scheduler state (watchdog raises carry it;
        also handy interactively). Device buffers are summarized, not
        dumped."""
        pool_state = {
            "layout": self.kv_layout,
            "n_free_slots": self.pool.n_free,
            "acquires": self.pool.total_acquires,
            "releases": self.pool.total_releases,
        }
        if self.paged:
            pool_state.update(
                n_free_pages=self.pool.n_free_pages,
                n_reclaimable_pages=self.pool.n_reclaimable,
                usable_pages=self.pool.n_pages - 1,
                pages_in_use=self.pool.n_pages_in_use)
        if self.host_tier is not None:
            pool_state["host_tier"] = self.host_tier.stats()
            pool_state["n_swapped_pages"] = self.pool.n_swapped_pages
        if self.prefix_index is not None:
            pool_state["prefix_index"] = self.prefix_stats()
        return {
            "tick": self.n_ticks,
            "queue": [
                {"rid": r.rid, "prompt_len": len(r.prompt),
                 "blocks": self._n_blocks(r), "effort": r.effort,
                 "deadline_ms": r.deadline_ms}
                for r in self.queue],
            "active": [
                {"rid": st.req.rid, "slot": st.slot, "seq": st.seq,
                 "phase": st.phase, "blocks_done": st.blocks_done,
                 "n_blocks": st.n_blocks, "pos": st.pos,
                 "out_tokens": len(st.out),
                 "plan": self._plan_name(st.plan_idx)}
                for st in sorted(self.active.values(),
                                 key=lambda s: s.seq)],
            "parked": [
                {"rid": st.req.rid, "slot": st.slot, "seq": st.seq,
                 "phase": st.phase, "pos": st.pos,
                 "out_tokens": len(st.out)}
                for st in sorted(self.parked.values(),
                                 key=lambda s: s.seq)],
            "pool": pool_state,
            "counters": {
                "finished": len(self.finished),
                "emitted": self._total_emitted,
                "prefill_blocks": self.n_prefill_blocks,
                "decode_steps": self.n_decode_steps,
                "spec_ticks": self.n_spec_ticks,
                "preemptions": self.n_preemptions,
                "swap_outs": self.n_swap_outs,
                "swap_ins": self.n_swap_ins,
                "shed": self.n_shed, "timed_out": self.n_timed_out,
                "cancelled": self.n_cancelled,
                "degraded": self.n_degraded,
            },
            "admission": (self.admission.stats()
                          if self.admission is not None else None),
            "faults": (self.faults.stats()
                       if self.faults is not None else None),
        }

    def warmup(self) -> dict:
        """Compile every serving executable by running one throwaway
        request through this scheduler's own pool (no second KV
        allocation) — the single-block prefill + decode pair — and then
        one ALL-INACTIVE `prefill_blocks` call per batched width bucket
        (inactive rows scatter back their own gathered KV, so the pool
        is untouched), then reset counters/stats. After this, a
        churning request mix never compiles again. Returns the
        post-warmup compile counts."""
        if self.active or self.queue or self.finished:
            raise RuntimeError("warmup() must run before real traffic")
        # chaos must not perturb compilation: the injector is suspended
        # for the duration of warmup and re-attached after
        faults, self.faults = self.faults, None
        N = self.runtime.block_size
        self.submit(Request(rid=-1, prompt=[1] * min(N, self.cache_len - 2),
                            max_new=2))
        self.run()
        # one executable per (plan, width bucket): every registered
        # effort tier is pre-compiled, so a stream MIXING tiers stays on
        # the zero-recompilation contract. Decode needs no per-plan
        # pass — the plan tuple is closed over and traced plan_ids
        # select per-row counts, so the throwaway request's single
        # decode step compiled the one executable.
        for i, plan in enumerate(self.plans or (None,)):
            for w in self.prefill_widths:
                if w == 1:
                    if i == 0:
                        continue  # compiled by the throwaway request
                    if not self.paged:
                        # width-1 slot bucket is the single-block entry;
                        # slot 0 is free during warmup, its KV garbage
                        # is overwritten by any real prefill from pos 0
                        self.pool.cache, _ = self.runtime.prefill_block(
                            self.pool.cache, np.zeros((1, N), np.int32),
                            0, 0, False, 1, plan=plan)
                        continue
                if self.paged:
                    # all-inactive rows carry all-null page tables: their
                    # writes are self-copies of the reserved null page
                    self.pool.cache, _ = self.runtime.prefill_blocks_paged(
                        self.pool.cache, np.zeros((w, N), np.int32),
                        np.zeros((w, self.pool.max_pages), np.int32),
                        np.zeros(w, np.int32), np.zeros(w, bool),
                        np.ones(w, np.int32), np.zeros(w, bool),
                        plan=plan)
                else:
                    self.pool.cache, _ = self.runtime.prefill_blocks(
                        self.pool.cache, np.zeros((w, N), np.int32),
                        np.arange(w, dtype=np.int32), np.zeros(w, np.int32),
                        np.zeros(w, bool), np.ones(w, np.int32),
                        np.zeros(w, bool), plan=plan)
        if self.speculative is not None and self.speculative.k > 0:
            # pre-compile the speculative protocol entries with an all-
            # inactive call each (masked writes are self-copies — slot
            # KV untouched, paged all-null tables sink into the null
            # page). The throwaway request already compiled the chunk
            # entry but its max_new=2 never drafts (the bonus token
            # claims the last emission), so the draft entry needs this.
            kd = self.speculative.k
            z = np.zeros(self.n_slots, np.int32)
            f = np.zeros(self.n_slots, bool)
            ch = np.zeros((self.n_slots, kd + 1), np.int32)
            if self.paged:
                _, self.pool.cache = self.runtime.draft_steps_paged(
                    self.pool.cache, z, self.pool.page_table, z, f, z, kd)
                _, _, self.pool.cache = self.runtime.verify_chunk_paged(
                    self.pool.cache, ch, self.pool.page_table, z, f, z + 1)
            else:
                _, self.pool.cache = self.runtime.draft_steps(
                    self.pool.cache, z, z, f, z, kd)
                _, _, self.pool.cache = self.runtime.verify_chunk(
                    self.pool.cache, ch, z, f, z + 1)
        self.finished.clear()
        self._admit_seq = 0
        self.n_ticks = self.n_prefill_blocks = self.n_decode_steps = 0
        self.n_prefill_ticks = self.n_eos_stops = 0
        self.n_prefill_rows_dense = self.n_prefill_rows_sparse = 0
        self.n_preemptions = 0
        self.n_shed = self.n_timed_out = self.n_cancelled = 0
        self.n_degraded = 0
        self._stall_count = 0
        self._last_sig = None
        self._total_emitted = 0
        if self.admission is not None:
            self.admission.reset()
        self.faults = faults
        self.plan_prefill_blocks[:] = 0
        self.plan_prefill_sparse_rows[:] = 0
        self.plan_decode_tokens[:] = 0
        self.n_spec_ticks = 0
        self.spec_row_ticks[:] = 0
        self.spec_drafted[:] = 0
        self.spec_accepted[:] = 0
        self.spec_emitted[:] = 0
        self.pool.total_acquires = self.pool.total_releases = 0
        self.pool.max_in_use = 0
        self.pool.stranded_tokens_at_peak = 0
        if self.paged:
            self.pool.total_page_allocs = self.pool.total_page_frees = 0
            self.pool.max_pages_in_use = 0
        if self.paged and self.host_tier is not None:
            # pre-compile both swap byte-movers with a null round trip:
            # read the null page's zeros, write them straight back —
            # the pool is untouched and every later swap batch (any
            # page count, chunked to width _npb) reuses these two
            # executables
            ids = np.zeros(self._npb, np.int32)
            # payload crosses to HOST numpy exactly like a real swap:
            # the jit cache keys device arrays and numpy arrays
            # differently, so warming with a device payload would leave
            # the first real swap-in to compile a second executable
            payload = jax.tree.map(
                np.asarray, self.runtime.read_pages(self.pool.cache, ids))
            self.pool.cache = self.runtime.write_pages(
                self.pool.cache, ids, payload)
            self.n_swap_outs = self.n_swap_ins = 0
            self.pool.total_pages_swapped_out = 0
            self.pool.total_pages_swapped_in = 0
            tier = self.host_tier
            tier.total_host_puts = tier.total_host_frees = 0
            tier.peak_used = 0
        if self.prefix_index is not None:
            # pre-compile the COW copy entry (all-null self-copy: page
            # 0 copied onto itself), then drop the throwaway request's
            # published blocks and zero the sharing stats — real
            # traffic starts from an empty, fully-counted cache
            z = np.zeros(self._npb, np.int32)
            self.pool.cache = self.runtime.copy_pages(self.pool.cache,
                                                      z, z)
            self.prefix_index.clear()
            self.prefix_index.n_lookups = self.prefix_index.n_hits = 0
            self.prefix_index.n_published = 0
            self.prefix_index.n_evictions = 0
            self.n_prefix_hits = self.n_shared_blocks = 0
            self.pool.total_page_shares = 0
            self.pool.n_cow_pages = 0
            self.pool.total_page_allocs = self.pool.total_page_frees = 0
        return self.runtime.compile_counts()

    # ------------------------------------------------------- internals

    def _peek_plan_idx(self, req: Request) -> int:
        """The plan this request would be admitted under RIGHT NOW
        (pinned index if re-admitting, else the current degradation
        level applied to its effort tier). Pure — safe to call before
        the admission gate; the n_degraded counter moves only when the
        request is actually seated."""
        if req.assigned_plan_idx is not None:
            return req.assigned_plan_idx
        plan_idx = self.plan_index.get(req.effort, 0)
        if self.admission is not None and self.plans:
            plan_idx = self.admission.degraded_plan(plan_idx)
        return plan_idx

    def _admit(self) -> None:
        while self.queue:
            shared: List[int] = []
            keys: Optional[List[tuple]] = None
            if self.paged:
                # paged admission gates on available PAGES: seat a
                # request only when the heap can back its whole UNSHARED
                # prompt footprint on top of what already-seated prefills
                # are still owed (allocation is lazy, so the free count
                # alone would let a burst over-admit and thrash
                # re-prefill). Decode growth past the prompt is
                # deliberately NOT reserved — that would re-create the
                # slot pool's worst-case reservation and its stranded
                # bytes; the preemption path absorbs it.
                req0 = self.queue[0]
                n_blocks = self._n_blocks(req0)
                if self.prefix_index is not None:
                    # record=False: the same head can be re-probed for
                    # many gated ticks — stats count admissions below
                    keys = self._page_keys(req0)
                    shared = self.prefix_index.lookup(
                        self._plan_name(self._peek_plan_idx(req0)), keys,
                        record=False)
                owed = sum(
                    max(s.n_blocks * self._npb
                        - int(self.pool.allocated[s.slot]), 0)
                    for s in self.active.values() if s.phase == "prefill")
                # parked requests resume BEFORE admission and need
                # exactly their swapped page counts back — charge the
                # gate so new admissions don't strand them parked
                owed += self.pool.n_swapped_pages
                # whole blocks the shared chain covers are never
                # prefilled; a partial tail block still re-runs (its
                # tail pages COW-detach), so it is charged in full
                m_aligned = len(shared) - len(shared) % self._npb
                need = n_blocks * self._npb - m_aligned
                # matched refcount-zero pages sit on the reclaimable
                # list, so n_available_pages counts them as capacity —
                # but mapping them consumes that capacity, so charge
                # them out of the gate (or a full-but-cached heap would
                # admit work it cannot back)
                matched_idle = sum(
                    1 for p in shared if self.pool.refcount[p] == 0)
                avail = self.pool.n_available_pages - matched_idle
                if avail - owed < need:
                    return
            slot = self.pool.acquire()
            if slot is None:
                return
            req = self.queue.popleft()
            if req.assigned_plan_idx is not None:
                # re-admission after preemption: the degradation
                # decision was made at FIRST admission and sticks, so
                # preemption stays output-transparent even if the
                # controller's level moved meanwhile
                plan_idx = req.assigned_plan_idx
            else:
                plan_idx = self._peek_plan_idx(req)
                if plan_idx != self.plan_index.get(req.effort, 0):
                    self.n_degraded += 1
                req.assigned_plan_idx = plan_idx
            now = self.clock()
            spans.emit("req.queued", req.queued_at, now, rid=req.rid)
            st = _ActiveState(
                req=req, slot=slot, seq=self._admit_seq,
                n_blocks=self._n_blocks(req),
                plan_idx=plan_idx,
                # rid folded to uint32: seed sequences reject negative
                # entries (the warmup throwaway request carries rid=-1)
                rng=np.random.default_rng(
                    (self.seed, req.rid % (1 << 32))),
                admitted_at=now, page_keys=keys)
            self.active[slot] = st
            self._admit_seq += 1
            if self.prefix_index is not None:
                self.prefix_index.n_lookups += 1
                if shared:
                    self.prefix_index.n_hits += 1
                    self._map_prefix(st, shared)

    def _map_prefix(self, st: _ActiveState, shared: List[int]) -> None:
        """Seat an admitted request on its matched prefix chain: map
        the shared pages read-only, copy-on-write the partial tail of
        the restart block, and fast-forward blocks_done past the fully-
        covered blocks — those prompt blocks never run (the TTFT win)."""
        N = self.runtime.block_size
        self.pool.share(st.slot, shared)
        tail = len(shared) % self._npb
        if tail:
            self._cow_tail(st, tail)
        start = int(self.pool.allocated[st.slot]) // self._npb
        st.blocks_done = start
        self.pool.lengths[st.slot] = start * N
        if start > 0:
            self.n_prefix_hits += 1
            self.n_shared_blocks += start

    def _cow_tail(self, st: _ActiveState, tail: int) -> None:
        """Detach the last `tail` shared pages (a chain that ends mid-
        block: partial subtree eviction is the only producer — publishes
        are whole-block). The restart block's prefill scatters over ALL
        its pages, so keeping them shared would write pages other
        requests read; COW gives the writer private bit-identical
        copies instead, preserving "writes only touch exclusively-owned
        pages" without special cases. Dry-heap fallback: unmap the rest
        of the tail (those positions simply re-prefill)."""
        pool = self.pool
        base = int(pool.allocated[st.slot])
        srcs: List[int] = []
        dsts: List[int] = []
        for j in range(base - tail, base):
            while (pool.n_free_pages == 0
                   and self.prefix_index.evict_lru()):
                pass
            res = pool.cow(st.slot, j)
            if res is None:
                pool.unmap_tail(st.slot, base - j)
                break
            srcs.append(res[0])
            dsts.append(res[1])
        if srcs:
            # one fixed-width jitted device copy per admission: pad
            # with 0 -> 0 null self-copies so every COW count hits the
            # single pre-warmed executable
            src = np.zeros(self._npb, np.int32)
            dst = np.zeros(self._npb, np.int32)
            src[:len(srcs)] = srcs
            dst[:len(dsts)] = dsts
            self.pool.cache = self.runtime.copy_pages(
                self.pool.cache, src, dst)

    # ------------------------------------------- lifecycle: cancel/expiry

    def _plan_name(self, plan_idx: int) -> Optional[str]:
        return self.plans[plan_idx].name if self.plans else None

    def _count_status(self, status: str) -> None:
        if status == "shed":
            self.n_shed += 1
        elif status == "timed_out":
            self.n_timed_out += 1
        elif status == "cancelled":
            self.n_cancelled += 1

    def _finish_queued(self, req: Request, status: str,
                       reason: str) -> None:
        """Terminal state for a request that never held resources
        (shed at submit, expired/cancelled while queued)."""
        now = self.clock()
        if req.queued_at is not None:
            spans.emit("req.queued", req.queued_at, now, rid=req.rid)
        arrival = (req.arrival_time if req.arrival_time is not None
                   else now)
        self.finished[req.rid] = RequestOutput(
            rid=req.rid, tokens=[], prompt_len=len(req.prompt),
            ttft_seconds=None, finish_seconds=now - arrival,
            status=status, reason=reason, effort=None)
        self._count_status(status)

    def _finish_abnormal(self, st: _ActiveState, status: str,
                         reason: str) -> None:
        """Terminal state for an ACTIVE request (timeout/cancel):
        records whatever tokens were produced and frees the slot and —
        paged — every page, idempotently (the pool guards double
        release)."""
        now = self.clock()
        if st.first_token_time is not None:
            spans.emit("req.decode", st.first_token_time, now,
                       rid=st.req.rid)
        self.finished[st.req.rid] = RequestOutput(
            rid=st.req.rid, tokens=list(st.out),
            prompt_len=len(st.req.prompt),
            ttft_seconds=(st.first_token_time - st.req.arrival_time
                          if st.first_token_time is not None else None),
            finish_seconds=now - st.req.arrival_time,
            status=status, reason=reason,
            effort=self._plan_name(st.plan_idx))
        if self.active.get(st.slot) is st:
            del self.active[st.slot]
        elif self.parked.get(st.slot) is st:
            del self.parked[st.slot]
        self.pool.release(st.slot)   # frees host-tier pages too if parked
        self._count_status(status)

    def cancel(self, rid: int, reason: str = "client cancelled") -> bool:
        """Mid-flight cancellation: finish `rid` with
        status="cancelled" wherever it currently lives — still queued
        (zero work done) or active (slot/pages freed idempotently,
        partial tokens kept). Returns False when the request is
        unknown or already finished (cancelling twice is a no-op)."""
        for r in self.queue:
            if r.rid == rid:
                self.queue.remove(r)
                self._finish_queued(r, "cancelled", reason)
                return True
        for st in list(self.active.values()) + list(self.parked.values()):
            if st.req.rid == rid:
                self._finish_abnormal(st, "cancelled", reason)
                return True
        return False

    def _expire_deadlines(self) -> None:
        """Enforce per-request deadlines (tick-entry hook): expired
        requests finish with status="timed_out" and free their
        resources immediately — BEFORE admission, so the pages a dead
        request held can seat the next queued one on the same tick."""
        now = self.clock()

        def expired(req: Request, phase: str) -> Optional[str]:
            waited = now - req.arrival_time
            if (req.deadline_ms is not None
                    and waited >= req.deadline_ms / 1e3):
                return (f"end-to-end deadline {req.deadline_ms:g} ms "
                        f"expired ({phase})")
            if (req.ttft_deadline_ms is not None and phase != "decode"
                    and waited >= req.ttft_deadline_ms / 1e3):
                return (f"ttft deadline {req.ttft_deadline_ms:g} ms "
                        f"expired ({phase})")
            return None

        for r in [r for r in self.queue
                  if r.deadline_ms is not None
                  or r.ttft_deadline_ms is not None]:
            reason = expired(r, "queued")
            if reason is not None:
                self.queue.remove(r)
                self._finish_queued(r, "timed_out", reason)
        for st in list(self.active.values()):
            if self.active.get(st.slot) is not st:
                continue
            reason = expired(st.req, st.phase)
            if reason is not None:
                self._finish_abnormal(st, "timed_out", reason)
        # parked (swapped-out) requests age on the same deadlines: an
        # expired one frees BOTH tiers' pages right here
        for st in list(self.parked.values()):
            if self.parked.get(st.slot) is not st:
                continue
            reason = expired(st.req, st.phase)
            if reason is not None:
                self._finish_abnormal(st, "timed_out", reason)

    # ---------------------------------------------- paged page pressure

    def _preempt(self, st: _ActiveState) -> None:
        """Evict a request: release its slot (and — paged — its pages),
        requeue it at the FRONT of the queue for re-prefill from
        scratch (preempted requests are older than anything still
        queued). Preemption is output-transparent: greedy decode is
        deterministic, the request re-admits under its PINNED plan
        (assigned_plan_idx), and temperature sampling replays its own
        (seed, rid) RNG stream on re-admission — only TTFT/latency
        suffer. Layout-independent (the FaultInjector forces it on the
        slot layout too). Parked (swapped-out) victims release their
        host-tier pages too (pool.release covers both tiers)."""
        if self.active.get(st.slot) is st:
            del self.active[st.slot]
        elif self.parked.get(st.slot) is st:
            del self.parked[st.slot]
        self.pool.release(st.slot)
        st.req.queued_at = self.clock()
        self.queue.appendleft(st.req)
        self.n_preemptions += 1

    def _swap_out(self, st: _ActiveState) -> bool:
        """Park `st`: move its exclusive (refcount-1, uncached) pages'
        payloads to the host tier through the fixed-width jitted
        `read_pages` entry, free the device pages, and remove it from
        the active set — it keeps its slot (and its shared/cached
        mappings, which are swap-exempt) and resumes with bit-identical
        KV bytes once the heap recovers. Returns False — changing
        nothing — when tiering is off, the tier is full, or `st` has no
        exclusive pages to move (the caller then falls back to true
        preemption)."""
        tier = self.host_tier
        if tier is None:
            return False
        swappable = self.pool.swappable_pages(st.slot)
        if not swappable or not tier.can_hold(len(swappable)):
            return False
        js = [j for j, _ in swappable]
        pages = [p for _, p in swappable]
        hid = tier.put(self._read_page_payloads(pages))
        self.pool.swap_out_commit(st.slot, js, hid)
        del self.active[st.slot]
        self.parked[st.slot] = st
        self.n_swap_outs += 1
        return True

    def _read_page_payloads(self, pages: List[int]) -> list:
        """Device->host copy of `pages` payloads, one per-page numpy
        pytree each, through the single pre-warmed fixed-width
        `read_pages` executable (chunks of _npb page ids, padded with
        the null page — harmless extra reads)."""
        W = self._npb
        payloads = []
        for i in range(0, len(pages), W):
            chunk = pages[i:i + W]
            ids = np.zeros(W, np.int32)
            ids[:len(chunk)] = chunk
            pages_dev = self.runtime.read_pages(self.pool.cache, ids)
            with spans.span("sched.readback", kind="swap"):
                got = jax.tree.map(np.asarray, pages_dev)
            for j in range(len(chunk)):
                payloads.append(jax.tree.map(lambda a: a[:, j].copy(),
                                             got))
        return payloads

    def _write_page_payloads(self, pages: List[int],
                             payloads: list) -> None:
        """Host->device scatter of swap-in payloads onto freshly
        allocated `pages`, through the single pre-warmed fixed-width
        `write_pages` executable (padding pairs page 0 with an all-zero
        payload — rewriting the null page's own bytes)."""
        W = self._npb
        zero = jax.tree.map(np.zeros_like, payloads[0])
        for i in range(0, len(pages), W):
            chunk = pages[i:i + W]
            ids = np.zeros(W, np.int32)
            ids[:len(chunk)] = chunk
            group = list(payloads[i:i + W])
            group += [zero] * (W - len(chunk))
            stacked = jax.tree.map(lambda *xs: np.stack(xs, axis=1),
                                   *group)
            self.pool.cache = self.runtime.write_pages(self.pool.cache,
                                                       ids, stacked)

    def _resume_swapped(self) -> None:
        """Swap parked requests back in, OLDEST first, before any new
        admission (a parked request predates everything still queued).
        Each resume allocates fresh device pages (evicting cached-idle
        prefixes if that unblocks it), scatters the host payloads back,
        and releases the host pages. Stops at the first parked request
        that cannot be re-backed this tick — younger parked requests
        never jump an older one."""
        if not self.parked:
            return
        for st in sorted(self.parked.values(), key=lambda s: s.seq):
            while True:
                res = self.pool.swap_in_alloc(st.slot)
                if res is not None:
                    break
                if (self.prefix_index is not None
                        and self.prefix_index.evict_lru()):
                    continue
                return          # heap still dry: retry next tick
            hid, _js, pages = res
            self._write_page_payloads(pages, self.host_tier.get(hid))
            self.pool.swap_in_commit(st.slot)
            del self.parked[st.slot]
            self.active[st.slot] = st
            self.n_swap_ins += 1

    def _ensure_pages(self, st: _ActiveState, n_total: int) -> bool:
        """Grow st's page table to n_total pages. While the free heap
        is dry, the pressure valves fire cheapest-first: (1) evict
        cached-but-unreferenced prefixes (LRU, a whole index subtree
        per victim — reclaiming cold cache costs nothing live); (2)
        SWAP OUT the youngest strictly-younger active request's
        exclusive pages to the host tier (its work is preserved — it
        parks and resumes with bit-identical KV); (3) only when the
        host tier is full or useless, PREEMPT that victim outright
        (discard-and-recompute); (4) as a last resort preempt the
        youngest strictly-younger PARKED request (frees its host pages
        and shared mappings). Never evicts older requests (the oldest
        always progresses, so any stream whose requests individually
        fit the heap drains). Returns False when st cannot be grown
        this tick (it is skipped, not evicted — retried next tick)."""
        while True:
            if self.pool.ensure(st.slot, n_total):
                return True
            if (self.prefix_index is not None
                    and self.prefix_index.evict_lru()):
                continue
            # only victims actually HOLDING pages: evicting a just-
            # admitted zero-page request frees nothing and churns
            # admission for no gain. Under sharing a victim's release
            # may free nothing PHYSICALLY (pages still read elsewhere
            # or parked cached) — its cached pages become reclaimable,
            # so the next loop iteration evicts them.
            victim = max((s for s in self.active.values()
                          if s.seq > st.seq
                          and self.pool.allocated[s.slot] > 0),
                         key=lambda s: s.seq, default=None)
            if victim is not None:
                if not self._swap_out(victim):
                    self._preempt(victim)
                continue
            parked_victim = max(
                (s for s in self.parked.values() if s.seq > st.seq),
                key=lambda s: s.seq, default=None)
            if parked_victim is None:
                return False
            self._preempt(parked_victim)

    def _plan_of(self, st: _ActiveState):
        return self.plans[st.plan_idx] if self.plans else None

    def _block_meta(self, st: _ActiveState):
        """(chunk tokens, pos0, is_dense) for a state's next block."""
        N = self.runtime.block_size
        ff = self.runtime.cfg.ff
        b = st.blocks_done
        is_dense = ((ff.dense_first_block and b == 0) or
                    (ff.dense_last_block and b == st.n_blocks - 1))
        return st.req.prompt[b * N:(b + 1) * N], b * N, is_dense

    def _finish_block(self, st: _ActiveState, logits_row) -> int:
        """Book-keeping after a state's block was processed; samples the
        first token (TTFT) when it was the final prompt block. Returns
        tokens emitted (0 or 1)."""
        N = self.runtime.block_size
        st.blocks_done += 1
        self.n_prefill_blocks += 1
        self.plan_prefill_blocks[st.plan_idx] += 1
        self.pool.lengths[st.slot] = min(st.blocks_done * N,
                                         len(st.req.prompt))
        if self.prefix_index is not None and st.blocks_done < st.n_blocks:
            # publish the just-completed block's pages (never the LAST
            # prompt block — excluded by the guard above AND by the
            # page_keys cap). First writer wins on existing nodes; a
            # COWed restart block re-publishes under the same keys and
            # is skipped there.
            b = st.blocks_done - 1
            self.prefix_index.publish(
                self._plan_name(st.plan_idx), st.page_keys,
                self.pool.page_table[st.slot],
                b * self._npb, (b + 1) * self._npb)
        if st.blocks_done < st.n_blocks:
            return 0
        tok = self._sample(logits_row(), st)
        st.first_token_time = self.clock()
        spans.emit("req.prefill", st.admitted_at, st.first_token_time,
                   rid=st.req.rid)
        st.out.append(tok)
        st.next_token = tok
        st.pos = len(st.req.prompt)
        st.phase = "decode"
        self._maybe_finish(st)
        return 1

    def _prefill_blocks(self) -> int:
        """Batched prefill: one block of EACH of up to `prefill_batch`
        distinct prefilling requests (oldest first, `_prefill_batch`) in
        one jitted call (`_prefill_inputs`); the logits cross to the
        host only when some row's block was its last."""
        with spans.span("sched.prefill.build"):
            batch = self._prefill_batch()
            if batch:
                entry, args, plan = self._prefill_inputs(batch)
        if not batch:
            return 0
        with spans.span("sched.prefill.dispatch"):
            self.pool.cache, logits = entry(self.pool.cache, *args,
                                            plan=plan)
        self.n_prefill_ticks += 1
        lead, (_, _, dense) = batch[0]
        if dense:
            self.n_prefill_rows_dense += len(batch)
        else:
            self.n_prefill_rows_sparse += len(batch)
            self.plan_prefill_sparse_rows[lead.plan_idx] += len(batch)
        host = []                 # [rows, V], pulled at most once

        def row(i):
            def get():
                if not host:
                    with spans.span("sched.readback", kind="prefill"):
                        out = np.asarray(logits)
                    host.append(out.reshape(-1, out.shape[-1]))
                return host[0][i]
            return get

        with spans.span("sched.emit"):
            return sum(self._finish_block(st, row(i))
                       for i, (st, _) in enumerate(batch))

    def _prefill_batch(self) -> list:
        """[(state, _block_meta)] of the states whose next block this
        tick's prefill call runs, oldest first.

        Two batch-shaping policies keep the batched tick cheap:

          * density-homogeneous batching — only rows whose next block
            needs the SAME FFN branch as the oldest request's ride in
            one call (skipped rows go next tick; the oldest is always
            included, so no starvation). The per-row is_dense vector is
            then all-equal and `ff_blocks_sparse`'s any()-gated conds
            execute exactly ONE branch — a mixed batch would pay for
            both;
          * width bucketing (`_prefill_inputs`) — the batch is padded
            up to the smallest pre-compiled width bucket (not always to
            P), so a backlog of 1-2 requests doesn't pay a P-wide
            padded call.
        """
        states = sorted(
            (s for s in self.active.values() if s.phase == "prefill"),
            key=lambda s: s.seq)                        # FIFO
        # one _block_meta per state per tick: the same meta drives both
        # the density filter and the batch fill (re-deriving it would
        # re-slice each prompt chunk). In the paged layout each
        # candidate must also grow its page table to cover this block —
        # a dry heap preempts strictly-younger requests (which may be
        # later entries of `states`, hence the is-still-active guard);
        # a state that cannot be grown is skipped this tick, not evicted.
        batch = []
        lead_dense = None
        lead_plan = None
        for st in states:
            if len(batch) == self.prefill_batch:
                break
            if self.active.get(st.slot) is not st:
                continue                    # preempted earlier this tick
            meta = self._block_meta(st)
            if lead_dense is not None and meta[2] != lead_dense:
                continue                    # density-homogeneous batch
            if lead_plan is not None and st.plan_idx != lead_plan:
                continue                    # plan-homogeneous batch: the
                #                             plan is a jit STATIC arg, so
                #                             one call runs ONE plan
                #                             (skipped rows go next tick;
                #                             the oldest always leads)
            if self.paged and not self._ensure_pages(
                    st, (st.blocks_done + 1) * self._npb):
                continue
            if lead_dense is None:
                lead_dense = meta[2]
                lead_plan = st.plan_idx
            batch.append((st, meta))
        return batch

    def _prefill_inputs(self, batch: list):
        """(runtime entry, its arguments after the cache, plan) of the
        one call that runs `batch`. A lone row in the slot layout takes
        the original one-block-per-tick `prefill_block` entry (PR-1:
        one request, one [1, N] call — the prefill_batch=1 baseline the
        batched path is benchmarked and bit-compared against); the
        paged layout has no single-request entry and uses the width-1
        `prefill_blocks_paged` bucket. Otherwise the rows are padded up
        to the smallest width bucket holding them: inactive rows park
        on slot ids unused by this call's live rows (their KV writes
        are discarded device-side) or, paged, carry all-null tables
        (write-sink self-copies)."""
        N = self.runtime.block_size
        lead, (_, _, lead_dense) = batch[0]
        plan = self._plan_of(lead)
        if len(batch) == 1 and not self.paged:
            chunk, pos0, is_dense = batch[0][1]
            tok_blk = np.zeros((1, N), np.int32)
            tok_blk[0, :len(chunk)] = chunk
            return (self.runtime.prefill_block,
                    (tok_blk, lead.slot, pos0, is_dense,
                     len(lead.req.prompt)), plan)
        P = next(w for w in self.prefill_widths if w >= len(batch))
        tokens = np.zeros((P, N), np.int32)
        slots = np.zeros(P, np.int32)
        pos0s = np.zeros(P, np.int32)
        is_dense = np.full(P, lead_dense, bool)
        lengths = np.ones(P, np.int32)
        active = np.zeros(P, bool)
        for i, (st, (chunk, pos0, _)) in enumerate(batch):
            tokens[i, :len(chunk)] = chunk
            slots[i] = st.slot
            pos0s[i] = pos0
            lengths[i] = len(st.req.prompt)
            active[i] = True
        if self.paged:
            tables = np.zeros((P, self.pool.max_pages), np.int32)
            for i, (st, _) in enumerate(batch):
                tables[i] = self.pool.page_table[st.slot]
            return (self.runtime.prefill_blocks_paged,
                    (tokens, tables, pos0s, is_dense, lengths, active),
                    plan)
        used = {st.slot for st, _ in batch}
        spare = (s for s in range(self.n_slots) if s not in used)
        for i in range(len(batch), P):
            slots[i] = next(spare)
        return (self.runtime.prefill_blocks,
                (tokens, slots, pos0s, is_dense, lengths, active), plan)

    def _decode_all(self) -> int:
        if self.speculative is not None and self.speculative.k > 0:
            return self._decode_all_speculative()
        with spans.span("sched.decode.build"):
            call = self._decode_inputs()
        if call is None:
            return 0
        decoding, tokens, positions, active, plan_ids = call
        with spans.span("sched.decode.dispatch"):
            if self.paged:
                logits, greedy, self.pool.cache = \
                    self.runtime.decode_step_paged(
                        self.pool.cache, tokens, self.pool.page_table,
                        positions, active, plan_ids=plan_ids)
            else:
                logits, greedy, self.pool.cache = self.runtime.decode_step(
                    self.pool.cache, tokens, positions, active,
                    plan_ids=plan_ids)
        self.n_decode_steps += 1
        with spans.span("sched.readback", kind="decode"):
            greedy = np.asarray(greedy)
            # logits cross to host only if someone actually samples
            logits_np = (np.asarray(logits)
                         if any(s.req.temperature > 0 for s in decoding)
                         else None)
        emitted = 0
        with spans.span("sched.emit"):
            for st in decoding:
                tok = (int(greedy[st.slot]) if st.req.temperature <= 0
                       else self._sample(logits_np[st.slot], st))
                st.out.append(tok)
                st.next_token = tok
                st.pos += 1
                self.pool.lengths[st.slot] = st.pos
                self.plan_decode_tokens[st.plan_idx] += 1
                emitted += 1
                self._maybe_finish(st)
        return emitted

    def _decode_inputs(self):
        """(decoding states, tokens, positions, active, plan_ids) of
        this tick's batched decode step; None when no row decodes."""
        decoding = [s for s in self.active.values() if s.phase == "decode"]
        if self.paged:
            # each decoding row must own the page covering its write
            # position before the batched step; a dry heap preempts the
            # youngest request (possibly one of `decoding` — hence the
            # is-still-active guard). Oldest-first, so an early grow
            # never evicts an already-granted older row.
            psz = self.pool.page_size
            ready = []
            for st in sorted(decoding, key=lambda s: s.seq):
                if self.active.get(st.slot) is not st:
                    continue
                if not self._ensure_pages(st, st.pos // psz + 1):
                    continue               # stalled this tick, retried
                ready.append(st)
            decoding = ready
        if not decoding:
            return None
        tokens = np.zeros(self.n_slots, np.int32)
        positions = np.zeros(self.n_slots, np.int32)
        active = np.zeros(self.n_slots, bool)
        plan_ids = np.zeros(self.n_slots, np.int32)
        for st in decoding:
            tokens[st.slot] = st.next_token
            positions[st.slot] = st.pos
            active[st.slot] = True
            plan_ids[st.slot] = st.plan_idx
        return decoding, tokens, positions, active, plan_ids

    def _spec_draft_limit(self, st: _ActiveState) -> int:
        """How many tokens this row may draft THIS tick (0 .. k):
        capped by the request's own `speculate` field, the tokens it
        can still emit (the bonus token claims one), and the cache
        positions left (the chunk writes p .. p+lim). Temperature > 0
        rows never draft — their tick must replay the exact
        non-speculative sampling step (the chunk's step-0 logits ARE
        that step's logits, so lim = 0 degenerates to it)."""
        if st.req.temperature > 0:
            return 0
        lim = self.speculative.k
        if st.req.speculate is not None:
            lim = min(lim, st.req.speculate)
        lim = min(lim, st.req.max_new - len(st.out) - 1)
        lim = min(lim, self.cache_len - 1 - st.pos)
        return max(lim, 0)

    def _decode_all_speculative(self) -> int:
        """One speculative decode tick (serving/speculative.py):
        draft `n_draft[row]` tokens per row under its draft plan, score
        all n_draft+1 positions in ONE chunk entry under its own
        (verify) plan — REWRITING the draft's KV — then emit the
        longest agreeing prefix plus the verifier's bonus token.

        Rollback of rejected writes: the slot layout just never
        advances `pool.lengths`/`st.pos` past the accepted position
        (stale bytes beyond it are rewritten before any later step can
        attend them — the mask is `kj <= position`); the paged layout
        additionally truncates tail pages past the accepted position
        (`unmap_tail`) so alloc/free accounting stays exact. Tail
        pages are always exclusively-owned decode growth: published
        prefix pages cover only pre-last-block prompt positions
        (< prompt_len <= pos), so truncation can never touch them."""
        with spans.span("sched.decode.build"):
            k = self.speculative.k
            psz = self.pool.page_size if self.paged else 0
            decoding = []
            n_draft = np.zeros(self.n_slots, np.int32)
            for st in sorted((s for s in self.active.values()
                              if s.phase == "decode"), key=lambda s: s.seq):
                if self.active.get(st.slot) is not st:
                    continue       # preempted by an earlier row's grow
                lim = self._spec_draft_limit(st)
                if self.paged:
                    # base coverage (the committed token's page) may
                    # evict/preempt exactly like the non-speculative
                    # tick; the SPECULATIVE extra pages are only taken
                    # from the free heap — never preempting live work
                    # just to draft
                    if not self._ensure_pages(st, st.pos // psz + 1):
                        continue           # stalled this tick, retried
                    while lim > 0 and not self.pool.ensure(
                            st.slot, (st.pos + lim) // psz + 1):
                        lim -= 1
                n_draft[st.slot] = lim
                decoding.append(st)
            if not decoding:
                return 0
            tokens = np.zeros(self.n_slots, np.int32)
            positions = np.zeros(self.n_slots, np.int32)
            active = np.zeros(self.n_slots, bool)
            verify_ids = np.zeros(self.n_slots, np.int32)
            draft_ids = np.zeros(self.n_slots, np.int32)
            for st in decoding:
                tokens[st.slot] = st.next_token
                positions[st.slot] = st.pos
                active[st.slot] = True
                verify_ids[st.slot] = st.plan_idx
                draft_ids[st.slot] = self._draft_plan_for[st.plan_idx]
            chunk = np.zeros((self.n_slots, k + 1), np.int32)
            chunk[:, 0] = tokens
        if int(n_draft.max()) > 0:
            with spans.span("sched.decode.dispatch"):
                if self.paged:
                    drafts, self.pool.cache = \
                        self.runtime.draft_steps_paged(
                            self.pool.cache, tokens, self.pool.page_table,
                            positions, active, n_draft, k,
                            plan_ids=draft_ids)
                else:
                    drafts, self.pool.cache = self.runtime.draft_steps(
                        self.pool.cache, tokens, positions, active,
                        n_draft, k, plan_ids=draft_ids)
            with spans.span("sched.readback", kind="decode"):
                chunk[:, 1:] = np.asarray(drafts)
        with spans.span("sched.decode.dispatch"):
            if self.paged:
                logits0, greedy, self.pool.cache = \
                    self.runtime.verify_chunk_paged(
                        self.pool.cache, chunk, self.pool.page_table,
                        positions, active, n_draft + 1,
                        plan_ids=verify_ids)
            else:
                logits0, greedy, self.pool.cache = self.runtime.verify_chunk(
                    self.pool.cache, chunk, positions, active, n_draft + 1,
                    plan_ids=verify_ids)
        self.n_decode_steps += 1
        self.n_spec_ticks += 1
        with spans.span("sched.readback", kind="decode"):
            greedy = np.asarray(greedy)
            logits0_np = (np.asarray(logits0)
                          if any(s.req.temperature > 0 for s in decoding)
                          else None)
        emitted = 0
        with spans.span("sched.emit"):
            for st in decoding:
                nd = int(n_draft[st.slot])
                if st.req.temperature > 0:
                    # exact non-speculative sampling tick: nd == 0, and
                    # the chunk's step-0 logits are the decode_step logits
                    toks = [self._sample(logits0_np[st.slot], st)]
                else:
                    n_acc, accepted = accept_drafts(
                        chunk[st.slot, 1:], greedy[st.slot], nd)
                    toks = [int(t) for t in accepted]
                    self.spec_row_ticks[st.plan_idx] += 1
                    self.spec_drafted[st.plan_idx] += nd
                    self.spec_accepted[st.plan_idx] += n_acc
                row_emitted = 0
                for tok in toks:
                    st.out.append(tok)
                    st.next_token = tok
                    st.pos += 1
                    self.pool.lengths[st.slot] = st.pos
                    self.plan_decode_tokens[st.plan_idx] += 1
                    row_emitted += 1
                    self._maybe_finish(st)
                    if self.active.get(st.slot) is not st:
                        break   # EOS/max_new released the slot (and,
                        #         paged, every page) — nothing left to
                        #         roll back
                if st.req.temperature <= 0:
                    self.spec_emitted[st.plan_idx] += row_emitted
                emitted += row_emitted
                if self.paged and self.active.get(st.slot) is st:
                    # truncate tail pages past the accepted position:
                    # pages the rejected drafts grew go back to the free
                    # heap with exact alloc/free accounting. st.pos >= 1
                    # always (decode starts at pos = prompt_len >= 1).
                    keep = (st.pos - 1) // psz + 1
                    trim = int(self.pool.allocated[st.slot]) - keep
                    if trim > 0:
                        self.pool.unmap_tail(st.slot, trim)
        return emitted

    # ----------------------------------------------------- plan stats

    def sparsity_stats(self) -> dict:
        """Realized sparsity accounting (serve.py stats line): per
        registered plan, the per-layer keep fractions, analytical FFN
        FLOP fraction, and how much work (prefill blocks, the sparse
        ones among them, decode tokens) actually ran under it; plus the
        work-weighted aggregates of the whole stream. In the FFN's, a
        dense prefill block (a prompt's first or last) counts at 1.0,
        sparse blocks and decode tokens at their plan's fraction."""
        N = self.runtime.block_size
        out = {"plans": [], "aggregate_ffn_flop_frac": None,
               "aggregate_attn_flop_frac": None}
        if not self.plans:
            return out
        weights = (self.plan_prefill_blocks * N
                   + self.plan_decode_tokens).astype(np.float64)
        sparse = (self.plan_prefill_sparse_rows * N
                  + self.plan_decode_tokens).astype(np.float64)
        fracs = np.array([p.flop_frac() for p in self.plans])
        # dual-budget plans also carry an attention-block budget; plans
        # without one run dense attention (fraction 1.0)
        afracs = np.array([p.attn_flop_frac() if p.has_attn else 1.0
                           for p in self.plans])
        if weights.sum() > 0:
            out["aggregate_ffn_flop_frac"] = float(
                (self.n_prefill_rows_dense * N + (sparse * fracs).sum())
                / weights.sum())
            out["aggregate_attn_flop_frac"] = float(
                (weights * afracs).sum() / weights.sum())
        for i, p in enumerate(self.plans):
            out["plans"].append({
                "name": p.name,
                "keep_per_layer": [round(float(f), 4)
                                   for f in p.keep_fracs],
                "ffn_flop_frac": round(p.flop_frac(), 4),
                "attn_keep_per_layer": (
                    [round(float(f), 4) for f in p.attn_keep_fracs]
                    if p.has_attn else None),
                "attn_flop_frac": (round(p.attn_flop_frac(), 4)
                                   if p.has_attn else None),
                "prefill_blocks": int(self.plan_prefill_blocks[i]),
                "sparse_prefill_blocks": int(
                    self.plan_prefill_sparse_rows[i]),
                "decode_tokens": int(self.plan_decode_tokens[i]),
            })
        return out

    def speculative_stats(self) -> Optional[dict]:
        """Speculation accounting (serve.py stats line + the
        speculative_decode bench section); None when speculation is
        off. Per VERIFY plan: which draft plan served it (after the
        never-denser clamp), drafted/accepted counts, acceptance rate,
        and emitted tokens per speculated row-tick (1.0 would be the
        non-speculative tick; the speculative win is this number minus
        one, bought for one draft pass)."""
        if self.speculative is None or self.speculative.k == 0:
            return None
        out = {"k": self.speculative.k, "draft": self.speculative.draft,
               "spec_ticks": int(self.n_spec_ticks), "plans": []}
        for i, p in enumerate(self.plans):
            drafted = int(self.spec_drafted[i])
            accepted = int(self.spec_accepted[i])
            rows = int(self.spec_row_ticks[i])
            out["plans"].append({
                "name": p.name,
                "draft_plan": self._plan_name(int(self._draft_plan_for[i])),
                "row_ticks": rows,
                "drafted": drafted,
                "accepted": accepted,
                "acceptance_rate": (round(accepted / drafted, 4)
                                    if drafted else None),
                "emitted": int(self.spec_emitted[i]),
                "tokens_per_row_tick": (
                    round(int(self.spec_emitted[i]) / rows, 4) if rows
                    else None),
            })
        return out

    def prefix_stats(self) -> Optional[dict]:
        """Prefix-sharing accounting (serve.py stats line + the
        prefix_sharing bench section); None when the cache is off."""
        if self.prefix_index is None:
            return None
        s = self.prefix_index.stats()
        s.update(
            requests_hit=self.n_prefix_hits,
            blocks_skipped=self.n_shared_blocks,
            pages_shared=self.pool.total_page_shares,
            cow_pages=self.pool.n_cow_pages,
        )
        return s

    def tier_stats(self) -> Optional[dict]:
        """Memory-tiering accounting (serve.py stats line + the
        kv_tiering bench section); None when the host tier is off."""
        if self.host_tier is None:
            return None
        s = self.host_tier.stats()
        s.update(
            swap_outs=self.n_swap_outs,
            swap_ins=self.n_swap_ins,
            pages_swapped_out=self.pool.total_pages_swapped_out,
            pages_swapped_in=self.pool.total_pages_swapped_in,
            parked=len(self.parked),
        )
        return s

    def _maybe_finish(self, st: _ActiveState) -> None:
        hit_eos = (st.req.eos_id is not None and st.out
                   and st.out[-1] == st.req.eos_id)
        done = len(st.out) >= st.req.max_new or hit_eos
        if not done:
            return
        if hit_eos and len(st.out) < st.req.max_new:
            self.n_eos_stops += 1     # early exit frees the slot now
        now = self.clock()
        spans.emit("req.decode", st.first_token_time, now, rid=st.req.rid)
        self.finished[st.req.rid] = RequestOutput(
            rid=st.req.rid, tokens=list(st.out),
            prompt_len=len(st.req.prompt),
            ttft_seconds=st.first_token_time - st.req.arrival_time,
            finish_seconds=now - st.req.arrival_time,
            status="ok", effort=self._plan_name(st.plan_idx))
        del self.active[st.slot]
        self.pool.release(st.slot)

    def _sample(self, logits: np.ndarray, st: _ActiveState) -> int:
        if st.req.temperature <= 0:
            return int(np.argmax(logits))
        # Gumbel-max with the REQUEST's own host RNG stream (seeded
        # (scheduler seed, rid)): draws are independent of batch
        # composition, admission order, and preemption re-runs
        g = st.rng.gumbel(size=logits.shape)
        return int(np.argmax(logits.astype(np.float64)
                             / st.req.temperature + g))


def drive_stream(sched: ContinuousBatchingScheduler,
                 requests: List[Request], after_tick=None) -> float:
    """Drive a timed request stream to completion.

    Each request's `arrival_time` is an OFFSET in seconds from stream
    start; requests are submitted as their arrival time passes (the
    scheduler keeps ticking — mid-flight admission), and the loop
    sleeps instead of spinning while the pool is idle between
    arrivals. The caller's Request objects are never mutated (absolute
    deadlines are stamped onto copies), so the same list can drive
    several schedulers for A/B runs.

    Requests carrying `cancel_after_s` are cancelled by this loop that
    many seconds after their arrival (the trace-replay form of a
    client disconnect). `after_tick(sched)`, when given, runs after
    every tick — the hook the overload benchmark uses to advance its
    simulated clock by a per-tick cost model. When the scheduler
    carries a FaultInjector, its still-stolen resources are restored
    at stream end so leak accounting over the whole stream is exact.

    Returns the clock seconds for the whole stream. Used by
    launch/serve.py --stream and the continuous-batching benchmark so
    both exercise the identical serving loop."""
    clock = sched.clock
    t0 = clock()
    # ascending stable sort + popleft keeps FIFO order for tied arrivals
    pending = deque(
        dataclasses.replace(r, prompt=list(r.prompt),
                            arrival_time=t0 + (r.arrival_time or 0.0))
        for r in sorted(requests, key=lambda r: r.arrival_time or 0.0))
    cancels = deque(sorted(
        (r.arrival_time + r.cancel_after_s, r.rid)
        for r in pending if r.cancel_after_s is not None))
    while pending or not sched.drained:
        now = clock()
        while pending and pending[0].arrival_time <= now:
            sched.submit(pending.popleft())
        while cancels and cancels[0][0] <= now:
            _, rid = cancels.popleft()
            # False (already finished) is fine: a cancel that loses the
            # race to completion is a no-op, as for a real client
            sched.cancel(rid, reason="client cancelled (cancel_after_s)")
        if sched.drained:
            if not pending:
                break
            # route the idle wait through the scheduler's injected sleep:
            # the delta is measured on sched.clock, so a simulated clock
            # must come with a simulated sleep (time.sleep on a fake-
            # clock delta would block on real wall time)
            sched.sleep(max(0.0, pending[0].arrival_time - clock()))
            continue
        sched.tick()
        if after_tick is not None:
            after_tick(sched)
    if sched.faults is not None:
        sched.faults.finalize(sched)
    return clock() - t0
