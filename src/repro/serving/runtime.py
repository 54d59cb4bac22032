"""ModelRuntime: the uniform jitted interface the serving stack drives.

The continuous-batching scheduler (repro.serving.scheduler) never
touches model internals — it sees four operations:

  init_cache(n_slots, cache_len)  allocate the pooled KV buffers
  prefill_blocks(...)             one 128-token FastForward block of EACH
                                  of P requests (fixed [P, N] batch with
                                  per-row slot/pos0/is_dense/length and
                                  an `active` pad mask) — the batched
                                  prefill hot path
  prefill_block(...)              one block of ONE request (the original
                                  one-block-per-tick entry; kept as the
                                  P=1 path and the batched path's
                                  equivalence baseline)
  decode_step(...)                one token for ALL slots (active mask)
  logits_at(hidden, lengths)      read logits at each row's last prompt
                                  token (static-batch path)

  init_cache_paged / prefill_blocks_paged / decode_step_paged
                                  paged-KV-layout twins (cfg.kv_layout
                                  = "paged"): the cache is a shared
                                  page pool and requests address it
                                  through traced [*, max_pages] page
                                  tables (serving/page_pool.py)
  draft_steps / verify_chunk      speculative-decode protocol entries
  (+ _paged twins)                (serving/speculative.py): k argmax-
                                  feedback draft steps under the draft
                                  plan, then ONE chunk-scored pass over
                                  the fixed [n_slots, k+1] batch under
                                  each row's verify plan — both are
                                  lax.scan loops over the model's own
                                  decode_step body (models/chunked.py),
                                  warmed at warmup so compile counts
                                  stay flat (k is the only static)

Every operation is jitted once with fixed shapes — the prefill entries
trace over (slot, pos0, is_dense, length, active) as *values* and P is
a static batch width (inactive rows pad short ticks), so a churning
request set never triggers recompilation: the same executables serve
the whole stream (asserted via `compile_counts`).

SparsityPlan (repro.core.scheduler): the runtime registers a tuple of
plans (effort tiers) at construction. Each prefill entry takes the
plan as a jit STATIC argument — the scheduler batches only same-plan
rows, and warmup pre-compiles every (plan, width bucket) pair — while
decode keeps ONE executable: the plan tuple is closed over statically
and traced [n_slots] `plan_ids` select each row's per-layer tile
counts, so a slot pool mixing effort tiers decodes in one call.

Adapters: `DenseRuntime` (dense family incl. VLM text stack) and
`MoeRuntime`. Both rely on the per-row-offset block prefill steps the
model modules expose (models/dense.py, models/moe.py: `prefill_block`
and the batched `prefill_blocks`).
"""
from __future__ import annotations

from typing import Protocol, runtime_checkable

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import fastforward as FF
from repro.models.base import ModelConfig
from repro.models.registry import get_model
from repro.nn import attention as A
from repro.nn import layers as L


@runtime_checkable
class ModelRuntime(Protocol):
    """What the scheduler/engine require of a servable model."""

    cfg: ModelConfig
    block_size: int

    def init_cache(self, n_slots: int, cache_len: int): ...

    def prefill_block(self, cache, tokens, slot, pos0, is_dense, length,
                      plan=None):
        """Process one block-size chunk of one request.

        cache: pooled KV pytree (leaves [L, n_slots, S, Kv, dh]);
        tokens: [1, N] int32 (zero-padded past `length`); slot/pos0/
        length: int32 scalars; is_dense: bool scalar (dense first/last
        block). Returns (cache, logits [V]) — logits are read at token
        `length-1-pos0` within the block and only meaningful on the
        request's final block."""
        ...

    def prefill_blocks(self, cache, tokens, slots, pos0s, is_dense,
                       lengths, active, plan=None):
        """Process one block-size chunk of EACH of P distinct requests
        in a single jitted call (the batched prefill hot path).

        cache: pooled KV pytree (leaves [L, n_slots, S, Kv, dh]);
        tokens: [P, N] int32 (row p zero-padded past lengths[p]);
        slots/pos0s/lengths: [P] int32; is_dense: [P] bool (dense
        first/last block PER SEQUENCE); active: [P] bool — P is static,
        so short ticks pad with inactive rows whose slot ids are unused
        by the live rows of THIS call (their KV writes become self-
        copies at scatter-back). Returns (cache, logits [P, V]) —
        row p's logits are read at its token `lengths[p]-1-pos0s[p]`
        and only meaningful on that request's final block."""
        ...

    def decode_step(self, cache, tokens, positions, active,
                    plan_ids=None):
        """One generation step for the whole slot pool. tokens/positions:
        [n_slots] int32; active: [n_slots] bool (inactive rows neither
        write KV nor produce meaningful logits); plan_ids: optional
        [n_slots] int32 indices into the registered plan tuple (per-
        request effort tiers through one executable). Returns
        (logits [n_slots, V], greedy [n_slots] int32, cache)."""
        ...

    def init_cache_paged(self, n_pages: int, page_size: int):
        """Allocate the paged KV pool (leaves [L, n_pages, psz, Kv, dh];
        page 0 reserved as the null page — see serving/page_pool.py)."""
        ...

    def prefill_blocks_paged(self, cache, tokens, page_tables, pos0s,
                             is_dense, lengths, active, plan=None):
        """Paged-layout twin of `prefill_blocks`: cache is the WHOLE
        page pool (no slot gather/scatter — each row's block K/V
        scatters onto the pages its [P, max_pages] table owns, and
        attention gathers the table-mapped view). Tables are traced
        values, so churning tables/offsets reuse one executable per
        width bucket — including width 1, which replaces the slot
        layout's separate `prefill_block` entry."""
        ...

    def decode_step_paged(self, cache, tokens, page_table, positions,
                          active, plan_ids=None):
        """Paged-layout twin of `decode_step`: page_table is the full
        [n_slots, max_pages] table array; each active row's token writes
        into the page covering its position (kernels/paged_attention
        dispatch on the read side)."""
        ...

    def logits_at(self, hidden, lengths):
        """hidden: [B, T, D] pre-final-norm; lengths: [B]. -> [B, V]."""
        ...

    def compile_counts(self) -> dict: ...


class _JittedRuntime:
    """Shared jit plumbing for model modules exposing the
    prefill_block/decode_step/init_cache triple."""

    def __init__(self, cfg: ModelConfig, params, shards: int = 1,
                 plans=None):
        self.cfg = cfg
        self.params = params
        self.model = get_model(cfg)
        self.shards = shards
        self.block_size = cfg.ff.block_size
        # registered SparsityPlans (per-request effort tiers). plans[0]
        # is the DEFAULT (requests without an effort). Each plan is a
        # jit STATIC argument of the prefill entries (one executable
        # per (plan, width bucket), all pre-compiled by warmup), while
        # decode stays ONE executable: the plan tuple is closed over
        # and traced [n_slots] plan_ids select per-row counts.
        if plans is not None:
            self.plans = tuple(plans)
        else:
            default = FF.resolve_plan(cfg, shards=shards)
            self.plans = (default,) if default is not None else ()
        if len({p.name for p in self.plans}) != len(self.plans):
            raise ValueError("SparsityPlan names must be unique: "
                             f"{[p.name for p in self.plans]}")
        self.plan_index = {p.name: i for i, p in enumerate(self.plans)}
        # the scheduler always replaces its cache reference with the
        # returned one, so the pooled KV buffers are donated: on
        # accelerators the update is in-place instead of a full-pool
        # copy per tick (CPU ignores donation)
        self._prefill_block = jax.jit(self._prefill_block_impl,
                                      donate_argnums=(1,),
                                      static_argnames=("plan",))
        self._prefill_blocks = jax.jit(self._prefill_blocks_impl,
                                       donate_argnums=(1,),
                                       static_argnames=("plan",))
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1,))
        self._prefill_blocks_paged = jax.jit(
            self._prefill_blocks_paged_impl, donate_argnums=(1,),
            static_argnames=("plan",))
        self._decode_paged = jax.jit(self._decode_paged_impl,
                                     donate_argnums=(1,))
        # speculative-decode protocol entries: the draft length is the
        # only static (one compile per k, pre-warmed); everything else
        # — tokens, positions, per-row validity, plan ids — is traced,
        # so the churning request mix reuses one executable per layout
        self._draft = jax.jit(self._draft_impl, donate_argnums=(1,),
                              static_argnames=("n_steps",))
        self._verify = jax.jit(self._verify_impl, donate_argnums=(1,))
        self._draft_paged = jax.jit(self._draft_paged_impl,
                                    donate_argnums=(1,),
                                    static_argnames=("n_steps",))
        self._verify_paged = jax.jit(self._verify_paged_impl,
                                     donate_argnums=(1,))
        # COW page copy (prefix sharing): cache donated like every
        # other cache-threading entry; src/dst are traced fixed-width
        # int32 vectors (scheduler pads with null self-copies), so all
        # COW batches share one executable
        self._copy_pages = jax.jit(self._copy_pages_impl,
                                   donate_argnums=(0,))
        # host-tier swap entries (serving/kv_tier.py): read gathers page
        # payloads for device->host swap-out (no donation — the pool
        # keeps the cache; freed pages are simply reusable afterwards),
        # write scatters host payloads back on swap-in (cache donated).
        # Page-id vectors are traced fixed width (scheduler pads with
        # page 0 — null-page reads / zero-payload null writes), so every
        # swap batch reuses one executable per direction.
        self._read_pages = jax.jit(self._read_pages_impl)
        self._write_pages = jax.jit(self._write_pages_impl,
                                    donate_argnums=(0,))
        self._logits_at = jax.jit(self._logits_at_impl)

    # -- plan plumbing -------------------------------------------------

    @property
    def default_plan(self):
        return self.plans[0] if self.plans else None

    def _norm_plan(self, plan):
        return plan if plan is not None else self.default_plan

    def _decode_plan_args(self, plan_ids):
        """(plan, plan_ids) for the model decode call: a single
        registered plan ignores the ids (the bit-compat simple path);
        several plans ride as a static tuple + traced per-row ids."""
        if len(self.plans) > 1:
            return self.plans, plan_ids
        return self.default_plan, None

    # -- model hooks (overridable per family) -------------------------

    def _model_prefill_block(self, params, tokens, sub_cache, pos0,
                             is_dense, lengths, plan):
        return self.model.prefill_block(
            params, self.cfg, tokens, sub_cache, pos0, is_dense=is_dense,
            lengths=lengths, shards=self.shards, plan=plan)

    def _model_prefill_blocks(self, params, tokens, sub_cache, pos0s,
                              is_dense, lengths, active, plan):
        return self.model.prefill_blocks(
            params, self.cfg, tokens, sub_cache, pos0s, is_dense=is_dense,
            lengths=lengths, active=active, shards=self.shards, plan=plan)

    def _model_decode_step(self, params, tokens, cache, positions, active,
                           plan_ids):
        # slot caches hold absolute positions, so sliding-window models
        # get the window as an attention mask in the ragged decode path
        plan, ids = self._decode_plan_args(plan_ids)
        return self.model.decode_step(
            params, self.cfg, tokens, cache, positions,
            shards=self.shards, window=self.cfg.sliding_window,
            active=active, plan=plan, plan_ids=ids)

    def _model_prefill_blocks_paged(self, params, tokens, cache, tables,
                                    pos0s, is_dense, lengths, active,
                                    plan):
        return self.model.prefill_blocks(
            params, self.cfg, tokens, cache, pos0s, is_dense=is_dense,
            lengths=lengths, active=active, page_tables=tables,
            shards=self.shards, plan=plan)

    def _model_decode_step_paged(self, params, tokens, cache, table,
                                 positions, active, plan_ids):
        plan, ids = self._decode_plan_args(plan_ids)
        return self.model.decode_step(
            params, self.cfg, tokens, cache, positions,
            shards=self.shards, window=self.cfg.sliding_window,
            active=active, page_table=table, plan=plan, plan_ids=ids)

    def _model_decode_draft(self, params, tokens, cache, positions,
                            active, n_draft, plan_ids, n_steps,
                            table=None):
        plan, ids = self._decode_plan_args(plan_ids)
        return self.model.decode_draft(
            params, self.cfg, tokens, cache, positions, n_steps,
            shards=self.shards, window=self.cfg.sliding_window,
            active=active, n_draft=n_draft, page_table=table,
            plan=plan, plan_ids=ids)

    def _model_decode_chunk(self, params, tokens, cache, positions,
                            active, n_valid, plan_ids, table=None):
        plan, ids = self._decode_plan_args(plan_ids)
        return self.model.decode_chunk(
            params, self.cfg, tokens, cache, positions,
            shards=self.shards, window=self.cfg.sliding_window,
            active=active, n_valid=n_valid, page_table=table,
            plan=plan, plan_ids=ids)

    # -- jitted impls --------------------------------------------------

    def _prefill_block_impl(self, params, cache, tokens, slot, pos0,
                            is_dense, length, plan=None):
        kc = jax.lax.dynamic_slice_in_dim(cache["k"], slot, 1, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(cache["v"], slot, 1, axis=1)
        sub, hidden = self._model_prefill_block(
            params, tokens, {"k": kc, "v": vc}, pos0, is_dense,
            jnp.reshape(length, (1,)), plan)
        cache = {
            "k": jax.lax.dynamic_update_slice_in_dim(
                cache["k"], sub["k"], slot, axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(
                cache["v"], sub["v"], slot, axis=1),
        }
        # logits at the request's last prompt token — only meaningful
        # when this block is the final one (length-1 falls inside it)
        idx = jnp.clip(length - 1 - pos0, 0, hidden.shape[1] - 1)
        h = self._final_norm(params, hidden[0, idx])
        return cache, L.unembed(params["lm_head"], h)

    def _prefill_blocks_impl(self, params, cache, tokens, slots, pos0s,
                             is_dense, lengths, active, plan=None):
        # gather each live row's slot from the pool, run one batched
        # per-row-offset block step, then scatter the updated rows back.
        # Slot ids within one call are DISTINCT (the scheduler pads
        # inactive rows with slots unused by this call's live rows), so
        # the scatter is write-disjoint; inactive rows write back their
        # own gathered KV — a deterministic self-copy.
        kc = jnp.take(cache["k"], slots, axis=1)
        vc = jnp.take(cache["v"], slots, axis=1)
        sub, hidden = self._model_prefill_blocks(
            params, tokens, {"k": kc, "v": vc}, pos0s, is_dense, lengths,
            active, plan)
        sel = active[None, :, None, None, None]
        cache = {
            "k": cache["k"].at[:, slots].set(
                jnp.where(sel, sub["k"], kc)),
            "v": cache["v"].at[:, slots].set(
                jnp.where(sel, sub["v"], vc)),
        }
        # per-row logits at each request's last prompt token — only
        # meaningful for rows whose final block is this one
        idx = jnp.clip(lengths - 1 - pos0s, 0, hidden.shape[1] - 1)
        h = jnp.take_along_axis(
            hidden, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        h = self._final_norm(params, h)
        return cache, L.unembed(params["lm_head"], h)

    def _decode_impl(self, params, cache, tokens, positions, active,
                     plan_ids):
        logits, cache = self._model_decode_step(
            params, tokens, cache, positions, active, plan_ids)
        # device-side greedy argmax: the scheduler's hot loop transfers
        # [n_slots] token ids, not [n_slots, V] logits (logits are only
        # pulled to host when a request samples with temperature > 0)
        return logits, jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    def _prefill_blocks_paged_impl(self, params, cache, tokens, tables,
                                   pos0s, is_dense, lengths, active,
                                   plan=None):
        # no slot gather/scatter: the whole page pool rides through the
        # model, which scatters each row's block onto the pages its
        # table owns (write-disjoint — pages are exclusively owned; pad
        # rows carry all-null tables and self-copy the null page)
        cache, hidden = self._model_prefill_blocks_paged(
            params, tokens, cache, tables, pos0s, is_dense, lengths,
            active, plan)
        idx = jnp.clip(lengths - 1 - pos0s, 0, hidden.shape[1] - 1)
        h = jnp.take_along_axis(
            hidden, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        h = self._final_norm(params, h)
        return cache, L.unembed(params["lm_head"], h)

    def _decode_paged_impl(self, params, cache, tokens, table, positions,
                           active, plan_ids):
        logits, cache = self._model_decode_step_paged(
            params, tokens, cache, table, positions, active, plan_ids)
        return logits, jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    def _draft_impl(self, params, cache, tokens, positions, active,
                    n_draft, plan_ids, n_steps):
        return self._model_decode_draft(params, tokens, cache, positions,
                                        active, n_draft, plan_ids,
                                        n_steps)

    def _verify_impl(self, params, cache, tokens, positions, active,
                     n_valid, plan_ids):
        return self._model_decode_chunk(params, tokens, cache, positions,
                                        active, n_valid, plan_ids)

    def _draft_paged_impl(self, params, cache, tokens, table, positions,
                          active, n_draft, plan_ids, n_steps):
        return self._model_decode_draft(params, tokens, cache, positions,
                                        active, n_draft, plan_ids,
                                        n_steps, table=table)

    def _verify_paged_impl(self, params, cache, tokens, table, positions,
                           active, n_valid, plan_ids):
        return self._model_decode_chunk(params, tokens, cache, positions,
                                        active, n_valid, plan_ids,
                                        table=table)

    def _copy_pages_impl(self, cache, src, dst):
        return A.copy_kv_pages(cache, src, dst)

    def _read_pages_impl(self, cache, pages):
        return jax.tree.map(lambda a: jnp.take(a, pages, axis=1), cache)

    def _write_pages_impl(self, cache, pages, payload):
        return jax.tree.map(lambda a, p: a.at[:, pages].set(p),
                            cache, payload)

    def _logits_at_impl(self, params, hidden, lengths):
        idx = jnp.clip(lengths - 1, 0, hidden.shape[1] - 1)
        h = jnp.take_along_axis(
            hidden, idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        h = self._final_norm(params, h)
        return L.unembed(params["lm_head"], h)

    def _final_norm(self, params, h):
        from repro.models.dense import apply_norm
        return apply_norm(self.cfg, params["ln_f"], h)

    # -- public API ----------------------------------------------------

    def init_cache(self, n_slots: int, cache_len: int):
        return self.model.init_cache(self.cfg, n_slots, cache_len)

    def prefill_block(self, cache, tokens, slot, pos0, is_dense, length,
                      plan=None):
        return self._prefill_block(
            self.params, cache, jnp.asarray(tokens, jnp.int32),
            np.int32(slot), np.int32(pos0), np.bool_(is_dense),
            np.int32(length), plan=self._norm_plan(plan))

    def prefill_blocks(self, cache, tokens, slots, pos0s, is_dense,
                       lengths, active, plan=None):
        return self._prefill_blocks(
            self.params, cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(slots, jnp.int32), jnp.asarray(pos0s, jnp.int32),
            jnp.asarray(is_dense, bool), jnp.asarray(lengths, jnp.int32),
            jnp.asarray(active, bool), plan=self._norm_plan(plan))

    def decode_step(self, cache, tokens, positions, active,
                    plan_ids=None):
        if plan_ids is None:
            plan_ids = np.zeros(len(np.asarray(tokens)), np.int32)
        return self._decode(
            self.params, cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32),
            jnp.asarray(active, bool), jnp.asarray(plan_ids, jnp.int32))

    def init_cache_paged(self, n_pages: int, page_size: int):
        # same spec factory as the slot cache with (batch, cache_len) ->
        # (n_pages, page_size): a page pool IS a slot pool whose "slots"
        # are page_size long and table-composed per request. With
        # cfg.kv_quant each K/V leaf becomes the int8 heap
        # {"q": int8 [L, n_pages, psz, Kv, dh], "s": f32 [L, n_pages, Kv]}
        # — zero-init, so page 0 (the null page) starts all-zeros with
        # scale 0 in both representations. lax.scan and jax.tree.map
        # thread dict leaves transparently, so the model modules are
        # untouched.
        cache = self.model.init_cache(self.cfg, n_pages, page_size)
        if not self.cfg.kv_quant:
            return cache
        def quantize_leaf(a):
            L_, np_, psz, kv, _dh = a.shape
            return {"q": jnp.zeros(a.shape, jnp.int8),
                    "s": jnp.zeros((L_, np_, kv), jnp.float32)}
        return {k: quantize_leaf(v) for k, v in cache.items()}

    def prefill_blocks_paged(self, cache, tokens, page_tables, pos0s,
                             is_dense, lengths, active, plan=None):
        return self._prefill_blocks_paged(
            self.params, cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(page_tables, jnp.int32),
            jnp.asarray(pos0s, jnp.int32), jnp.asarray(is_dense, bool),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(active, bool),
            plan=self._norm_plan(plan))

    def decode_step_paged(self, cache, tokens, page_table, positions,
                          active, plan_ids=None):
        if plan_ids is None:
            plan_ids = np.zeros(len(np.asarray(tokens)), np.int32)
        return self._decode_paged(
            self.params, cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(page_table, jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.asarray(active, bool),
            jnp.asarray(plan_ids, jnp.int32))

    def draft_steps(self, cache, tokens, positions, active, n_draft, k,
                    plan_ids=None):
        """k argmax-feedback draft steps for the whole slot pool under
        the draft plan(s) in plan_ids. tokens: [n_slots] committed next
        tokens; n_draft: [n_slots] per-row valid draft counts (<= k —
        rows stop writing KV past their count); k is STATIC. Returns
        (drafts [n_slots, k] int32, cache)."""
        if plan_ids is None:
            plan_ids = np.zeros(len(np.asarray(tokens)), np.int32)
        return self._draft(
            self.params, cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.asarray(active, bool),
            jnp.asarray(n_draft, jnp.int32),
            jnp.asarray(plan_ids, jnp.int32), n_steps=int(k))

    def verify_chunk(self, cache, tokens, positions, active, n_valid,
                     plan_ids=None):
        """ONE chunk-scored pass over the fixed [n_slots, k+1] batch
        under each row's own (verify) plan, REWRITING the draft's KV at
        positions p .. p+k-1. n_valid: [n_slots] per-row valid chunk
        widths (n_draft + 1). Returns (logits0 [n_slots, V],
        greedy [n_slots, k+1] int32, cache)."""
        if plan_ids is None:
            plan_ids = np.zeros(len(np.asarray(positions)), np.int32)
        return self._verify(
            self.params, cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.asarray(active, bool),
            jnp.asarray(n_valid, jnp.int32),
            jnp.asarray(plan_ids, jnp.int32))

    def draft_steps_paged(self, cache, tokens, page_table, positions,
                          active, n_draft, k, plan_ids=None):
        if plan_ids is None:
            plan_ids = np.zeros(len(np.asarray(tokens)), np.int32)
        return self._draft_paged(
            self.params, cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(page_table, jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.asarray(active, bool),
            jnp.asarray(n_draft, jnp.int32),
            jnp.asarray(plan_ids, jnp.int32), n_steps=int(k))

    def verify_chunk_paged(self, cache, tokens, page_table, positions,
                           active, n_valid, plan_ids=None):
        if plan_ids is None:
            plan_ids = np.zeros(len(np.asarray(positions)), np.int32)
        return self._verify_paged(
            self.params, cache, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(page_table, jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.asarray(active, bool),
            jnp.asarray(n_valid, jnp.int32),
            jnp.asarray(plan_ids, jnp.int32))

    def copy_pages(self, cache, src_pages, dst_pages):
        """Device COW copy src -> dst across every cache leaf (page
        axis 1). Fixed-width traced indices: the scheduler pads short
        batches with 0 -> 0 null self-copies so one executable covers
        every COW count."""
        return self._copy_pages(cache, jnp.asarray(src_pages, jnp.int32),
                                jnp.asarray(dst_pages, jnp.int32))

    def read_pages(self, cache, pages):
        """Gather page payloads [*, W, ...] across every cache leaf
        (page axis 1) for device->host swap-out. pages: [W] int32,
        FIXED width (pad with 0 -> harmless null-page reads). The cache
        is NOT donated: swap-out only copies bytes out; the pool then
        recycles the still-resident source pages."""
        return self._read_pages(cache, jnp.asarray(pages, jnp.int32))

    def write_pages(self, cache, pages, payload):
        """Scatter host payloads back into the heap on swap-in (the
        inverse of `read_pages`; cache donated). Padding pairs page 0
        with an all-zero payload — rewriting the null page's own
        content — so one executable serves every swap-in width."""
        return self._write_pages(cache, jnp.asarray(pages, jnp.int32),
                                 payload)

    def logits_at(self, hidden, lengths):
        return self._logits_at(self.params, hidden,
                               jnp.asarray(lengths, jnp.int32))

    def _entries(self) -> dict:
        return {
            "prefill_block": self._prefill_block,
            "prefill_blocks": self._prefill_blocks,
            "decode_step": self._decode,
            "prefill_blocks_paged": self._prefill_blocks_paged,
            "decode_step_paged": self._decode_paged,
            "draft_steps": self._draft,
            "verify_chunk": self._verify,
            "draft_steps_paged": self._draft_paged,
            "verify_chunk_paged": self._verify_paged,
            "copy_pages": self._copy_pages,
            "read_pages": self._read_pages,
            "write_pages": self._write_pages,
            "logits_at": self._logits_at,
        }

    def compile_counts(self) -> dict:
        """Distinct compilations per jitted entry point. After warmup
        (one prefill tick + one decode step) these must not grow —
        the serving loop's zero-recompilation invariant. The batched
        `prefill_blocks` entry is covered too: its [P, N] batch width
        is static, so a churning mix of requests, offsets, and pad rows
        reuses one executable."""
        return {name: int(fn._cache_size())
                for name, fn in self._entries().items()}

    def compiled_text(self, entry: str, *args, **kwargs) -> str:
        """Optimized HLO text of `entry` (a `compile_counts` key)
        compiled for these arguments (arrays or ShapeDtypeStructs, the
        params excluded) — the program a call with such arguments runs,
        e.g. to check which Pallas kernels (`tpu_custom_call`) it
        contains."""
        return (self._entries()[entry].lower(self.params, *args, **kwargs)
                .compile().as_text())


class DenseRuntime(_JittedRuntime):
    """Dense llama-family models (and the VLM text stack)."""

    ARCHS = ("dense", "vlm")

    def __init__(self, cfg: ModelConfig, params, shards: int = 1,
                 mesh=None, plans=None):
        if cfg.arch not in self.ARCHS:
            raise ValueError(f"DenseRuntime cannot drive arch={cfg.arch}")
        self.mesh = mesh
        super().__init__(cfg, params, shards, plans=plans)

    def _model_prefill_block(self, params, tokens, sub_cache, pos0,
                             is_dense, lengths, plan):
        from repro.models import dense
        return dense.prefill_block(
            params, self.cfg, tokens, sub_cache, pos0, is_dense=is_dense,
            lengths=lengths, shards=self.shards, plan=plan,
            mesh=self.mesh)

    def _model_prefill_blocks(self, params, tokens, sub_cache, pos0s,
                              is_dense, lengths, active, plan):
        from repro.models import dense
        return dense.prefill_blocks(
            params, self.cfg, tokens, sub_cache, pos0s, is_dense=is_dense,
            lengths=lengths, active=active, shards=self.shards,
            plan=plan, mesh=self.mesh)

    def _model_prefill_blocks_paged(self, params, tokens, cache, tables,
                                    pos0s, is_dense, lengths, active,
                                    plan):
        from repro.models import dense
        return dense.prefill_blocks(
            params, self.cfg, tokens, cache, pos0s, is_dense=is_dense,
            lengths=lengths, active=active, page_tables=tables,
            shards=self.shards, plan=plan, mesh=self.mesh)


class MoeRuntime(_JittedRuntime):
    """Mixture-of-experts models (qwen2-moe, kimi-k2). Dropless routed
    dispatch is dispatch-group invariant: a token routes identically in
    the [1, N] single-block, [P, N] batched-prefill, and [n_slots, 1]
    decode entries, so blockwise serving reproduces the full-sequence
    forward token-for-token. The sorted-segment buffers are sized by
    the fixed batch shapes (N*K rows) — no recompilation as requests
    churn, same contract as the dense runtime."""

    ARCHS = ("moe",)

    def __init__(self, cfg: ModelConfig, params, shards: int = 1,
                 plans=None):
        if cfg.arch not in self.ARCHS:
            raise ValueError(f"MoeRuntime cannot drive arch={cfg.arch}")
        super().__init__(cfg, params, shards, plans=plans)


def make_runtime(cfg: ModelConfig, params, shards: int = 1,
                 mesh=None, plans=None) -> ModelRuntime:
    """Dispatch cfg.arch -> runtime adapter. plans: optional tuple of
    SparsityPlans to register (plans[0] is the default tier; requests
    pick one by name — the per-request serving knob)."""
    if cfg.arch in DenseRuntime.ARCHS:
        return DenseRuntime(cfg, params, shards=shards, mesh=mesh,
                            plans=plans)
    if cfg.arch in MoeRuntime.ARCHS:
        return MoeRuntime(cfg, params, shards=shards, plans=plans)
    raise ValueError(
        f"no serving runtime for arch={cfg.arch}; supported: "
        f"{DenseRuntime.ARCHS + MoeRuntime.ARCHS}")
