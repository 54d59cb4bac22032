"""Dense llama-family decoder LM (tinyllama, qwen2.5, granite, phi3, and
the paper's own llama3-8b), with FastForward FFN integration.

Three entry points, one per input-shape kind:
  forward      — full-sequence teacher-forced training forward
                 (FastForward mask path, Algorithm 1 budgets)
  prefill      — paper §3.1 blockwise prompt processing, scan over
                 128-token blocks (FastForward gather path)
  decode_step  — single-token generation with KV cache (ring buffer in
                 sliding-window/long mode)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.base import ModelConfig
from repro.nn import param as PM
from repro.nn import layers as L
from repro.nn import attention as A
from repro.core import fastforward as FF
from repro.core import sparse_ffn as S
from repro.models import chunked as CH
from repro.distributed.sharding import constrain


# ------------------------------------------------------------------ specs


def norm_spec(cfg: ModelConfig, dtype):
    return (L.layernorm_spec(cfg.d_model, dtype) if cfg.norm == "layernorm"
            else L.rmsnorm_spec(cfg.d_model, dtype))


def apply_norm(cfg: ModelConfig, params, x):
    return (L.layernorm(params, x) if cfg.norm == "layernorm"
            else L.rmsnorm(params, x))


def layer_spec(cfg: ModelConfig, dtype):
    return {
        "ln1": norm_spec(cfg, dtype),
        "attn": A.attention_spec(cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.head_dim, cfg.qkv_bias, dtype),
        "ln2": norm_spec(cfg, dtype),
        "ffn": FF.fastforward_ffn_spec(cfg, dtype=dtype),
    }


def specs(cfg: ModelConfig):
    dtype = cfg.dtype
    return {
        "embed": L.embedding_spec(cfg.vocab, cfg.d_model, dtype),
        "layers": PM.stack_specs(layer_spec(cfg, dtype), cfg.n_layers),
        "ln_f": norm_spec(cfg, dtype),
        "lm_head": L.embedding_spec(cfg.vocab, cfg.d_model, dtype),
    }


# ---------------------------------------------------------------- forward


def _ffn_apply_masked(cfg: ModelConfig, fp, x, budget, k_tiles=None):
    if cfg.ff.enabled:
        return FF.ff_masked_sequence(fp, cfg, x, budget, k_tiles=k_tiles)
    return FF.ff_dense(fp, cfg, x)


def forward(params, cfg: ModelConfig, batch, budgets=None, plan=None):
    """batch: {"tokens": [B,T]} (+"inputs_embeds" for VLM reuse).
    budgets: optional [L] keep-fractions (mask path, Algorithm 1);
    plan: optional SparsityPlan — its exact integer per-layer counts
    ride the scan instead (the mask-path oracle of the plan-taking
    gather/kernel paths). Returns (logits [B,T,V], aux dict)."""
    tokens = batch["tokens"]
    if "inputs_embeds" in batch:
        x = batch["inputs_embeds"].astype(cfg.dtype)
    else:
        x = L.embed(params["embed"], tokens).astype(cfg.dtype)
    B, T = x.shape[:2]
    x = constrain(x, ("batch", None, None))
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    counts = None
    if plan is not None:
        counts = plan.counts_array()
        budgets = jnp.asarray(plan.keep_fracs, jnp.float32)
    elif budgets is None:
        budgets = jnp.asarray(FF.layer_budgets(cfg), jnp.float32)

    def body(x, layer_in):
        if counts is None:
            lp, budget = layer_in
            k_l = None
        else:
            lp, budget, k_l = layer_in
        xn = apply_norm(cfg, lp["ln1"], x)
        h = A.attend_full(lp["attn"], xn, pos, causal=True,
                          window=cfg.sliding_window,
                          rope_theta=cfg.rope_theta,
                          chunk=cfg.attn_chunk)
        x = x + h
        xn2 = apply_norm(cfg, lp["ln2"], x)
        y = _ffn_apply_masked(cfg, lp["ffn"], xn2, budget, k_tiles=k_l)
        x = constrain(x + y, ("batch", None, None))
        return x, None

    if cfg.remat:
        body = jax.checkpoint(body)
    xs = ((params["layers"], budgets) if counts is None
          else (params["layers"], budgets, counts))
    x, _ = jax.lax.scan(body, x, xs)
    x = apply_norm(cfg, params["ln_f"], x)
    logits = L.unembed(params["lm_head"], x)
    logits = constrain(logits, ("batch", None, "vocab"))
    return logits, {}


# ------------------------------------------------------------------ cache


def cache_spec(cfg: ModelConfig, batch: int, cache_len: int, dtype=None):
    dtype = dtype or cfg.dtype
    kv = (cfg.n_layers, batch, cache_len, cfg.n_kv_heads, cfg.head_dim)
    ax = ("layers", "batch", "kv_seq", "kv_heads", None)
    return {
        "k": PM.ParamSpec(kv, ax, init="zeros", dtype=dtype),
        "v": PM.ParamSpec(kv, ax, init="zeros", dtype=dtype),
    }


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype=None):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_spec(cfg, batch, cache_len, dtype),
                        is_leaf=PM.is_spec)


# ---------------------------------------------------------------- prefill


def _scan_inputs(params):
    """Split the stacked layer leaves for a serving layer scan:
    (layers, layer ids, FFN stacks). The scan slices `layers`, which
    lacks the FFN matrices, and carries the layer index `jnp.arange(L)`;
    the body reads W_gate/W_up/W_down out of the whole [L, ...] `stacks`
    at that index. The sparse FFN kernel DMAs its tiles straight out of
    a stack; handed a per-layer slice, which a Pallas call cannot read
    in place, XLA would copy all three whole matrices every layer."""
    ffn = dict(params["layers"]["ffn"])
    stacks = {k: ffn.pop(k) for k in S.FFN_MATRICES if k in ffn}
    layers = dict(params["layers"], ffn=ffn)
    return layers, jnp.arange(stacks["wu"].shape[0]), stacks


def prefill_block(params, cfg: ModelConfig, tok_blk, cache, pos0, *,
                  is_dense=None, lengths=None, shards: int = 1,
                  plan=None, k_tiles=None, mesh=None):
    """One N-token FastForward block at sequence offset `pos0`.

    This is the schedulable unit of prefill work used both by the
    full-prompt `prefill` scan below and by the continuous-batching
    runtime (repro.serving.runtime), which interleaves single blocks of
    different requests with batched decode.

    tok_blk: [B, N]; cache: KV pytree with leaves [L, B, S, Kv, dh];
    pos0: scalar int32 (may be traced) — every row processes the block
    at the same offset (per-request chunked prefill uses B == 1);
    is_dense: traced bool forcing the dense FFN path (paper's dense
    first/last block), None when FastForward is disabled;
    lengths: optional [B] true prompt lengths (right-pad masking);
    plan: SparsityPlan (static; None resolves the uniform cfg plan —
    the backward-compat shim; a layer-wise plan rides its [L] counts
    through the layer scan so each layer consumes its own K on the
    gather/kernel path); k_tiles: deprecated int shim.
    Returns (cache, hidden [B, N, D]) with hidden pre-final-norm."""
    ff = cfg.ff
    if plan is None and k_tiles is not None:
        plan = k_tiles
    plan = FF._as_plan(cfg, plan, shards=shards) if ff.enabled else None
    counts = (None if plan is None or plan.is_uniform
              else plan.counts_array())
    attn_counts = (plan.attn_counts_array()
                   if plan is not None and plan.has_attn else None)
    N = tok_blk.shape[1]
    x = L.embed(params["embed"], tok_blk).astype(cfg.dtype)
    positions = pos0 + jnp.arange(N)[None, :]
    layers, layer_ids, stacks = _scan_inputs(params)

    def layer_body(x, layer_in):
        lp, l, kc, vc, *rest = layer_in
        fp = {**lp["ffn"], **stacks}
        rest = list(rest)
        k_l = rest.pop(0) if counts is not None else None
        a_l = rest.pop(0) if attn_counts is not None else None
        attn_sel = (None if a_l is None
                    else (plan.attn_k_max, plan.attn_tiles, a_l))
        xn = apply_norm(cfg, lp["ln1"], x)
        k_new, v_new = A.project_kv(lp["attn"], xn, positions,
                                    cfg.rope_theta)
        kc, vc = A.write_kv_block(kc, vc, k_new, v_new, pos0)
        h = A.attend_block_cached(lp["attn"], xn, kc, vc, pos0,
                                  window=cfg.sliding_window,
                                  rope_theta=cfg.rope_theta,
                                  lengths=lengths, attn_sel=attn_sel,
                                  attn_threshold=ff.attn_threshold or None)
        x = x + h
        xn2 = apply_norm(cfg, lp["ln2"], x)
        if plan is not None and cfg.shardmap_ffn and mesh is not None:
            # the shardmap gather is shard-balanced -> uniform width
            y = jax.lax.cond(
                is_dense,
                lambda xx: FF.ff_dense(fp, cfg, xx, l),
                lambda xx: S.ffn_block_sparse_shardmap(
                    S.ffn_at(fp, l), cfg, xx, plan.k_max, mesh), xn2)
        elif plan is not None:
            y = FF.ff_block_sparse(fp, cfg, xn2, plan, shards, is_dense,
                                   k_valid=k_l, layer=l)
        else:
            y = FF.ff_dense(fp, cfg, xn2, l)
        return x + y, (kc, vc)

    xs = (layers, layer_ids, cache["k"], cache["v"])
    if counts is not None:
        xs = xs + (counts,)
    if attn_counts is not None:
        xs = xs + (attn_counts,)
    x, (ks, vs) = jax.lax.scan(layer_body, x, xs)
    return {"k": ks, "v": vs}, x


def prefill_blocks(params, cfg: ModelConfig, tok_blks, cache, pos0s, *,
                   is_dense=None, lengths=None, active=None,
                   page_tables=None, shards: int = 1, plan=None,
                   k_tiles=None, mesh=None):
    """One N-token FastForward block of EACH of P distinct requests, at
    per-row sequence offsets — the batched schedulable prefill unit of
    the continuous-batching runtime (serving/runtime.py
    `prefill_blocks`).

    Unlike `prefill_block` (one request, scalar pos0), every per-row
    quantity is a vector: tok_blks [P, N]; cache: KV pytree with leaves
    [L, P, S, Kv, dh] (row p = request p's slot rows, gathered by the
    runtime); pos0s [P] int32 per-row block offsets (vectorized RoPE
    positions); is_dense [P] bool — the paper's dense first/last block
    PER SEQUENCE (rows mix dense and sparse within one call, see
    FF.ff_blocks_sparse); lengths [P] true prompt lengths (right-pad
    masking of the final partial block). active: optional [P] bool —
    in the slot layout dense rows are mutually independent, so inactive
    padding rows just compute garbage that the RUNTIME discards at
    scatter-back; the paged layout uses it to mask page writes.

    page_tables: optional [P, max_pages] int32 — switches to the PAGED
    KV layout: cache leaves are the whole page pool
    [L, n_pages, psz, Kv, dh], each row's block K/V scatters onto the
    pages its table owns, and attention gathers the table-mapped
    contiguous view (nn/attention paged variants; bit-identical math).

    plan: SparsityPlan (static — joins the scheduler's batching key, so
    every row of one call shares it; its [L] counts ride the layer scan
    when layer-wise); k_tiles: deprecated int shim.
    Returns (cache, hidden [P, N, D]) with hidden pre-final-norm."""
    if page_tables is None:
        del active  # rows are independent in the dense family
    ff = cfg.ff
    if plan is None and k_tiles is not None:
        plan = k_tiles
    plan = FF._as_plan(cfg, plan, shards=shards) if ff.enabled else None
    counts = (None if plan is None or plan.is_uniform
              else plan.counts_array())
    attn_counts = (plan.attn_counts_array()
                   if plan is not None and plan.has_attn else None)
    N = tok_blks.shape[1]
    x = L.embed(params["embed"], tok_blks).astype(cfg.dtype)
    layers, layer_ids, stacks = _scan_inputs(params)

    def layer_body(x, layer_in):
        lp, l, kc, vc, *rest = layer_in
        fp = {**lp["ffn"], **stacks}
        rest = list(rest)
        k_l = rest.pop(0) if counts is not None else None
        a_l = rest.pop(0) if attn_counts is not None else None
        attn_sel = (None if a_l is None
                    else (plan.attn_k_max, plan.attn_tiles, a_l))
        xn = apply_norm(cfg, lp["ln1"], x)
        positions = pos0s[:, None] + jnp.arange(N)[None, :]
        k_new, v_new = A.project_kv(lp["attn"], xn, positions,
                                    cfg.rope_theta)
        if page_tables is None:
            kc, vc = A.write_kv_rows(kc, vc, k_new, v_new, pos0s)
            h = A.attend_block_rows(lp["attn"], xn, kc, vc, pos0s,
                                    window=cfg.sliding_window,
                                    rope_theta=cfg.rope_theta,
                                    lengths=lengths, attn_sel=attn_sel,
                                    attn_threshold=(ff.attn_threshold
                                                    or None))
        else:
            kc, vc = A.write_kv_rows_paged(kc, vc, k_new, v_new,
                                           page_tables, pos0s,
                                           active=active)
            h = A.attend_block_rows_paged(lp["attn"], xn, kc, vc,
                                          page_tables, pos0s,
                                          window=cfg.sliding_window,
                                          rope_theta=cfg.rope_theta,
                                          lengths=lengths,
                                          attn_sel=attn_sel,
                                          attn_threshold=(ff.attn_threshold
                                                          or None))
        x = x + h
        xn2 = apply_norm(cfg, lp["ln2"], x)
        if plan is not None:
            y = FF.ff_blocks_sparse(fp, cfg, xn2, plan, shards, is_dense,
                                    k_valid=k_l, layer=l)
        else:
            y = FF.ff_dense(fp, cfg, xn2, l)
        return x + y, (kc, vc)

    xs = (layers, layer_ids, cache["k"], cache["v"])
    if counts is not None:
        xs = xs + (counts,)
    if attn_counts is not None:
        xs = xs + (attn_counts,)
    x, (ks, vs) = jax.lax.scan(layer_body, x, xs)
    return {"k": ks, "v": vs}, x


def prefill(params, cfg: ModelConfig, batch, cache, shards: int = 1,
            lengths=None, collect_hidden: bool = False, plan=None,
            mesh=None):
    """Blockwise prompt processing (paper §3.1): scan over N-token blocks.

    batch: {"tokens": [B,T]}, T % block_size == 0. cache length >= T.
    lengths: optional [B] true prompt lengths for right-padded batches
    (positions beyond a row's length are never attended).
    collect_hidden: also return the full hidden sequence [B,T,D]
    (pre-final-norm) so the engine can read logits at lengths-1.
    plan: SparsityPlan (None -> uniform cfg plan, the compat shim).
    Returns (cache, logits_last) or (cache, logits_last, hidden)."""
    tokens = batch["tokens"]
    ff = cfg.ff
    B, T = tokens.shape
    N = ff.block_size
    nb = T // N
    blocks = tokens.reshape(B, nb, N).transpose(1, 0, 2)  # [nb, B, N]
    plan = FF._as_plan(cfg, plan, shards=shards) if ff.enabled else None

    def block_step(cache, blk_in):
        blk_idx, tok_blk = blk_in
        is_dense = jnp.zeros((), bool)
        if ff.dense_first_block:
            is_dense = is_dense | (blk_idx == 0)
        if ff.dense_last_block:
            is_dense = is_dense | (blk_idx == nb - 1)
        cache, x = prefill_block(
            params, cfg, tok_blk, cache, blk_idx * N, is_dense=is_dense,
            lengths=lengths, shards=shards, plan=plan, mesh=mesh)
        out = x if collect_hidden else x[:, -1, :]
        return cache, out

    cache, outs = jax.lax.scan(
        block_step, cache, (jnp.arange(nb), blocks))
    if collect_hidden:
        hidden = outs.transpose(1, 0, 2, 3).reshape(B, T, -1)
        x_last = apply_norm(cfg, params["ln_f"], hidden[:, -1, :])
        logits = L.unembed(params["lm_head"], x_last)
        return cache, logits, hidden
    x_last = apply_norm(cfg, params["ln_f"], outs[-1])
    logits = L.unembed(params["lm_head"], x_last)
    return cache, logits


# ------------------------------------------------- fused prefill (ours)


def prefill_fused(params, cfg: ModelConfig, batch, cache, shards: int = 1,
                  mesh=None):
    """Beyond-paper prefill (EXPERIMENTS.md §Perf): processes ALL prompt
    blocks in parallel instead of the paper's sequential 128-token scan.

    - attention: full-sequence causal, online-softmax chunked (no [T,S]
      score materialization, no 2x masked-block waste);
    - FFN: the same per-block FastForward gather path, vmapped over
      blocks instead of scanned (identical math, no serialization);
    - KV cache written wholesale per layer.
    """
    tokens = batch["tokens"]
    ff = cfg.ff
    B, T = tokens.shape
    N = ff.block_size
    nb = T // N
    x = L.embed(params["embed"], tokens).astype(cfg.dtype)
    pos = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    plan = FF._as_plan(cfg, None, shards=shards) if ff.enabled else None
    chunk = cfg.attn_chunk or 512

    def sparse_all_blocks(fp, xn2):
        xb = xn2.reshape(B * nb, N, -1)
        if cfg.shardmap_ffn and mesh is not None:
            from repro.core.sparse_ffn import ffn_block_sparse_shardmap
            y = ffn_block_sparse_shardmap(fp, cfg, xb, plan.k_max, mesh)
        else:
            y = FF.ff_block_sparse(fp, cfg, xb, plan, shards)
        y = y.reshape(B, nb, N, -1)
        # dense first/last block (paper ablation Table 5): recompute the
        # two boundary blocks densely — cheap relative to nb blocks.
        if ff.dense_first_block:
            y = y.at[:, 0].set(FF.ff_dense(fp, cfg, xn2[:, :N]))
        if ff.dense_last_block:
            y = y.at[:, -1].set(FF.ff_dense(fp, cfg, xn2[:, -N:]))
        return y.reshape(B, T, -1)

    def layer_body(x, lp):
        xn = apply_norm(cfg, lp["ln1"], x)
        h = A.attend_full(lp["attn"], xn, pos, causal=True,
                          window=cfg.sliding_window,
                          rope_theta=cfg.rope_theta, chunk=chunk)
        k_new, v_new = A.project_kv(lp["attn"], xn, pos, cfg.rope_theta)
        x = x + h
        xn2 = apply_norm(cfg, lp["ln2"], x)
        if ff.enabled:
            y = sparse_all_blocks(lp["ffn"], xn2)
        else:
            y = FF.ff_dense(lp["ffn"], cfg, xn2)
        return x + y, (k_new, v_new)

    x, (ks, vs) = jax.lax.scan(layer_body, x, params["layers"])
    S_cache = cache["k"].shape[2]
    if S_cache == T:
        cache = {"k": ks.astype(cache["k"].dtype),
                 "v": vs.astype(cache["v"].dtype)}
    else:
        cache = {
            "k": jax.lax.dynamic_update_slice_in_dim(
                cache["k"], ks.astype(cache["k"].dtype), 0, axis=2),
            "v": jax.lax.dynamic_update_slice_in_dim(
                cache["v"], vs.astype(cache["v"].dtype), 0, axis=2),
        }
    x_last = apply_norm(cfg, params["ln_f"], x[:, -1, :])
    logits = L.unembed(params["lm_head"], x_last)
    return cache, logits


# ------------------------------------------------------------ decode step


def decode_step(params, cfg: ModelConfig, token, cache, position,
                shards: int = 1, window: Optional[int] = None,
                active=None, page_table=None, plan=None, plan_ids=None):
    """token: [B] int32; cache from init_cache; position: scalar int32
    OR [B] int32 for ragged batches (per-sequence decode positions).
    window: ring-buffer size when the cache is a sliding window.
    active: optional [B] bool (ragged path only) — rows with
    active[b] == False never write their KV (their logits are garbage
    and must be ignored); used by the serving slot pool so one
    fixed-capacity jitted step serves a churning request set.
    page_table: optional [B, max_pages] int32 — paged KV layout (cache
    leaves [L, n_pages, psz, Kv, dh]): the token writes into the page
    covering its position and attention indexes the pool through the
    table (kernels/paged_attention dispatch). Implies ragged.

    plan: SparsityPlan, or a STATIC tuple of them for mixed-effort
    serving (None -> uniform cfg plan, the compat shim). plan_ids:
    optional traced [B] int32 indexing into the tuple — each row
    decodes under its OWN plan (per-request effort) through this one
    executable: the tile-id width is the max k_max across the tuple
    and per-row traced counts mask/skip the rest."""
    ff = cfg.ff
    B = token.shape[0]
    ragged = jnp.ndim(position) == 1
    x = L.embed(params["embed"], token[:, None]).astype(cfg.dtype)
    positions = (position[:, None] if ragged
                 else jnp.full((B, 1), position))
    if ff.enabled and ff.apply_to_decode:
        plans = (plan if isinstance(plan, tuple)
                 else (FF._as_plan(cfg, plan, shards=shards),))
        plans = tuple(p for p in plans if p is not None)
    else:
        plans = ()
    # single uniform plan -> counts_lp None: no counts ride, no masking
    # — the executable is the pre-plan decode step (bit-compat path)
    sel_plan, counts_lp = FF.decode_plan_setup(plans)
    layers, layer_ids, stacks = _scan_inputs(params)

    def layer_body(x, layer_in):
        if counts_lp is None:
            lp, l, kc, vc = layer_in
            k_row = None
        else:
            lp, l, kc, vc, k_row = layer_in     # [n_plans] this layer
        fp = {**lp["ffn"], **stacks}
        xn = apply_norm(cfg, lp["ln1"], x)
        k_new, v_new = A.project_kv(lp["attn"], xn, positions,
                                    cfg.rope_theta)
        if page_table is not None:
            kc, vc = A.write_kv_tok_paged(kc, vc, k_new, v_new,
                                          page_table, position,
                                          active=active)
            h = A.attend_decode_ragged_paged(
                lp["attn"], xn, kc, vc, page_table, position,
                window=window, rope_theta=cfg.rope_theta)
        elif ragged:
            # full-length cache: `window` is an attention mask here, not
            # a ring-buffer size (writes stay at absolute positions)
            kc, vc = A.write_kv_tok(kc, vc, k_new, v_new, position,
                                    active=active)
            h = A.attend_decode_ragged(lp["attn"], xn, kc, vc, position,
                                       window=window,
                                       rope_theta=cfg.rope_theta)
        else:
            if window:
                kc, vc = A.write_kv_ring(kc, vc, k_new, v_new, position,
                                         window)
            else:
                kc, vc = A.write_kv_block(kc, vc, k_new, v_new, position)
            h = A.attend_decode(lp["attn"], xn, kc, vc, position,
                                window=window, rope_theta=cfg.rope_theta)
        x = x + h
        xn2 = apply_norm(cfg, lp["ln2"], x)
        if sel_plan is not None:
            y = FF.ff_decode_sparse(
                fp, cfg, xn2, sel_plan, shards,
                k_valid=FF.decode_k_valid(k_row, plan_ids), layer=l)
        else:
            y = FF.ff_dense(fp, cfg, xn2, l)
        return x + y, (kc, vc)

    xs = (layers, layer_ids, cache["k"], cache["v"])
    if counts_lp is not None:
        xs = xs + (counts_lp,)
    x, (ks, vs) = jax.lax.scan(layer_body, x, xs)
    x = apply_norm(cfg, params["ln_f"], x)
    logits = L.unembed(params["lm_head"], x[:, 0, :])
    return logits, {"k": ks, "v": vs}


def decode_chunk(params, cfg: ModelConfig, tokens, cache, position, **kw):
    """Chunk-scored multi-token decode: a lax.scan over THIS module's
    decode_step (speculative verify entry — see models/chunked.py)."""
    return CH.chunk_scored(decode_step, params, cfg, tokens, cache,
                           position, **kw)


def decode_draft(params, cfg: ModelConfig, token, cache, position,
                 n_steps, **kw):
    """Argmax-feedback draft proposals over THIS module's decode_step
    (speculative draft entry — see models/chunked.py)."""
    return CH.draft_steps(decode_step, params, cfg, token, cache,
                          position, n_steps, **kw)
