"""FastForward FFN module: predictor + tile-sparse FFN + compensator.

This is the drop-in replacement for a transformer FFN. All model
definitions route their FFN through `ff_apply_*` when cfg.ff.enabled.

DESIGN — SparsityPlan contract (the scheduler × kernel composition):

Every FLOP-reducing entry point below takes a `SparsityPlan`
(repro.core.scheduler) instead of the old `k_tiles=` scalar, so the
paper's layer-wise schedule (§3.4, Algorithm 1) reaches the gather
path and the batched Pallas kernel — not just the semantic mask path.

  * RESOLUTION — `resolve_plan(cfg, effort, importance)` builds the
    plan once per model: Algorithm 1 budgets -> integer per-layer tile
    counts (largest-remainder corrected) when importance is supplied
    and cfg.ff.layerwise_schedule is on; otherwise the uniform
    ceil(keep * n_tiles) rule the legacy `k_tiles_for` used, so
    configs that only set cfg.ff.sparsity resolve to a bit-identical
    policy. Named effort tiers ("dense" / "balanced" / "turbo") scale
    the global keep-fraction — the per-request serving knob.
  * PADDING — the static tile-id width is `plan.k_max`; the plan's [L]
    counts ride the layer scan as traced values (`k_valid`), so the
    scan stays shape-homogeneous while each layer consumes its own K.
    The gather path masks tiles past a layer's count; the Pallas
    kernels `pl.when`-skip them (per-row counts at decode carry
    per-request effort through one executable).
  * BATCHING-KEY MEMBERSHIP — the plan is a frozen hashable dataclass:
    the serving runtime takes it as a jit static argument, the
    scheduler admits only same-plan rows into one batched prefill
    call (alongside the density-homogeneous is_dense key), and warmup
    pre-compiles every (plan, width-bucket) pair — zero recompilation
    across mixed-effort traffic.
  * SHARDS — balanced per-shard tile selection needs a shard-multiple
    K; layer-wise counts fall back to global top-k selection (the
    prefix of a sharded selection is not the top-k_l), so sharded
    gathers keep uniform plans (shardmap path unchanged).

DESIGN — block-sparse prefill attention (dual-budget plans):

When cfg.ff.attn_sparsity > 0, `resolve_plan` attaches a SECOND budget
to the same SparsityPlan: per-layer kept-KV-block counts on a virtual
grid of cfg.ff.attn_tiles slots (same Algorithm-1 waterfill when
importance is supplied, same largest-remainder pinning, same frozen
jit-static contract). The effort tiers scale BOTH budgets — "dense"
disables both, "turbo" halves both — and the counts ride the layer
scan as a second traced `k_valid` next to the FFN counts.

  * SCORING PROXY (pooled QK) — per 128-token query block, each
    causally-valid KV block is scored by a mean-pooled dot product:
    q is mean-pooled over the block's query rows (and GQA head group),
    k over each KV block's key rows, and score[b, j] =
    mean_h <q̄_bh, k̄_bhj> / sqrt(dh). One [B, n_blocks] score matrix
    per layer — O(S·d) instead of the O(N·S·d) it gates.
  * THRESHOLD SEMANTICS — selection is top-k on the proxy scores, NOT
    a value threshold: the plan's virtual-grid count a_l maps to a
    per-row kept count c_b = clip(ceil(a_l * nv_b / attn_tiles),
    min(2, nv_b), nv_b) where nv_b is the row's causally-valid block
    count — so the kept FRACTION is the plan's a_l / attn_tiles,
    invariant to where the query block sits on the causal ramp. The
    sink block (block 0, attention-sink mass) and the diagonal block
    (the query block's own keys) are force-included via score bias —
    they are the two blocks the proxy is least reliable about and the
    paper's Eq. 23 importance analysis singles out. At a_l ==
    attn_tiles every valid block is kept and the masked XLA path is
    bit-identical to dense attention.
  * KERNEL CONTRACT — kernels/block_sparse_attention consumes the
    selection as scalar-prefetched block-id + count operands; dead
    selection slots (k >= c_b) are `pl.when`-skipped AND their slab
    DMA is index-map-clamped to the last live block (no bytes move).
    The XLA twin masks the same selection on the gathered view;
    interpret-mode tests pin kernel == online-softmax twin bitwise.

Deprecation shims: `k_tiles_for` survives for callers that only need
the uniform width, and plan-taking entry points accept a bare int
(wrapped via `SparsityPlan.uniform_counts`).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.base import ModelConfig
from repro.nn.param import ParamSpec
from repro.core import predictor as P
from repro.core import compensator as C
from repro.core import sparse_ffn as S
from repro.core import scheduler as SCHED
from repro.core.scheduler import SparsityPlan


def fastforward_ffn_spec(cfg: ModelConfig, d_ff: Optional[int] = None,
                         dtype=None):
    """Spec for one layer's FFN (+ predictor/compensator when enabled)."""
    d_ff = d_ff or cfg.d_ff
    dtype = dtype or cfg.dtype
    sp = S.ffn_spec(cfg.d_model, d_ff, cfg.gated, dtype)
    if cfg.ff.enabled:
        sp["pred"] = P.predictor_spec(
            cfg.d_model, d_ff, cfg.ff.predictor_r(cfg.d_model), dtype)
        if cfg.ff.use_compensator:
            sp["comp"] = C.compensator_spec(
                cfg.d_model, cfg.ff.compensator_r(cfg.d_model), dtype)
    return sp


def _compensate(params, cfg: ModelConfig, x, y):
    if cfg.ff.enabled and cfg.ff.use_compensator and "comp" in params:
        return y + C.compensate(params["comp"], x)
    return y


def ff_dense(params, cfg: ModelConfig, x, layer=None):
    """Dense FFN; with `layer`, params' matrices are the stacked [L, ...]
    leaves and layer `layer` is computed (`S.ffn_at`)."""
    return S.ffn_dense(S.ffn_at(params, layer), x, cfg.act)


# ------------------------------------------------- full-sequence (mask)


def ff_masked_sequence(params, cfg: ModelConfig, x, keep_frac,
                       dense_first=None, dense_last=None, k_tiles=None):
    """Mask path over a full sequence, blocked at cfg.ff.block_size.

    x: [B, T, D] with T % block_size == 0. keep_frac: scalar (may be a
    traced per-layer budget from Algorithm 1). k_tiles: optional traced
    int32 tile count overriding keep_frac — a SparsityPlan's per-layer
    count, making this the exact mask-path oracle of the gather/kernel
    paths. Semantically faithful to the paper; FLOPs are NOT reduced
    (see gather path for that).
    """
    ff = cfg.ff
    B, T, D = x.shape
    N = ff.block_size
    nb = T // N
    xb = x.reshape(B, nb, N, D)
    scores = jax.nn.sigmoid(P.neuron_scores(params["pred"], xb))
    mask = S.neuron_mask_from_scores(scores, keep_frac, ff.tile,
                                     k_tiles=k_tiles)
    dense_first = ff.dense_first_block if dense_first is None else dense_first
    dense_last = ff.dense_last_block if dense_last is None else dense_last
    blk = jnp.arange(nb)
    force = jnp.zeros((nb,), bool)
    if dense_first:
        force = force | (blk == 0)
    if dense_last:
        force = force | (blk == nb - 1)
    mask = jnp.where(force[None, :, None], jnp.ones_like(mask), mask)
    y = S.ffn_masked(params, xb, mask[:, :, None, :], cfg.act)
    # forced blocks already run with an all-ones mask (== dense FFN), so
    # only the compensator needs per-block gating: it must not fire on
    # dense blocks (they have zero sparsification error). Gating the
    # compensator term — instead of re-running a full dense FFN pass
    # over every block just to overwrite the forced ones — halves the
    # mask-path FLOPs whenever use_compensator is on.
    if cfg.ff.enabled and cfg.ff.use_compensator and "comp" in params:
        comp = C.compensate(params["comp"], xb)
        y = y + jnp.where(force[None, :, None, None],
                          jnp.zeros_like(comp), comp)
    return y.reshape(B, T, D)


# ------------------------------------------------------ per-block gather


def ff_block_sparse(params, cfg: ModelConfig, x_block, plan,
                    shards: int = 1, is_dense=None, k_valid=None,
                    layer=None):
    """Gather path for one prompt block: x_block [B, N, D] -> [B, N, D].

    plan: SparsityPlan (static — its k_max is the jit tile-id width; a
    bare int k_tiles is accepted as a deprecation shim). k_valid:
    optional traced int32 — THIS layer's valid tile count (the plan's
    [L] counts riding the layer scan); None keeps all k_max tiles
    (uniform plans take this path, bit-identical to the pre-plan API).
    `is_dense` (traced bool) switches to the dense FFN via lax.cond —
    used for the always-dense first/last blocks inside the
    blockwise-prefill scan. A [B] is_dense VECTOR (rows from distinct
    requests, each at its own boundary) delegates to the per-row
    `ff_blocks_sparse` path.

    layer: optional traced int32 — params' W_gate/W_up/W_down are the
    model's stacked [L, ...] leaves (predictor and compensator stay one
    layer's) and this is the layer to compute: the sparse kernel reads
    its tiles out of the stacks in place, the dense branch takes the
    layer's slices inside its cond (`S.ffn_at`). None: one layer's
    params throughout.
    """
    if is_dense is not None and jnp.ndim(is_dense) == 1:
        return ff_blocks_sparse(params, cfg, x_block, plan, shards,
                                is_dense, k_valid=k_valid, layer=layer)
    ff = cfg.ff
    plan = _as_plan(cfg, plan, shards=shards)
    sel_shards = 1 if k_valid is not None else shards
    scores = jax.nn.sigmoid(P.neuron_scores(params["pred"], x_block))
    ids = S.balanced_topk_tiles(scores, plan.k_max, ff.tile,
                                sel_shards)                    # [B, K]

    def sparse(x):
        y = S.ffn_sparse_batched(params, x, ids, ff.tile, cfg.act,
                                 k_valid=k_valid, layer=layer)
        return _compensate(params, cfg, x, y)

    if is_dense is None:
        return sparse(x_block)
    return jax.lax.cond(is_dense,
                        lambda x: ff_dense(params, cfg, x, layer),
                        sparse, x_block)


def ff_blocks_sparse(params, cfg: ModelConfig, x_blocks, plan,
                     shards: int = 1, is_dense=None, k_valid=None,
                     layer=None):
    """Gather path for a batch of blocks from DISTINCT requests with
    per-row dense forcing: x_blocks [P, N, D], is_dense [P] bool.

    The batched-prefill twin of `ff_block_sparse`: each row selects its
    own `plan.k_max` tiles (batched kernel / gather path via
    ffn_sparse_batched; `k_valid` — traced scalar or [P] — limits how
    many of them actually compute, carrying the plan's per-layer
    counts), and the paper's dense-first/last semantics hold PER ROW —
    a row whose block is a sequence boundary takes the dense FFN while
    its batchmates stay sparse. Each path runs under a `lax.cond` on
    whether ANY row needs it, so an all-sparse steady-state batch never
    pays dense FLOPs (and an all-dense batch skips predictor + gather).
    The compensator fires only on sparse rows. `layer`: stacked FFN
    matrices, as in `ff_block_sparse`.
    """
    ff = cfg.ff
    plan = _as_plan(cfg, plan, shards=shards)
    sel_shards = 1 if k_valid is not None else shards

    def sparse(x):
        scores = jax.nn.sigmoid(P.neuron_scores(params["pred"], x))
        ids = S.balanced_topk_tiles(scores, plan.k_max, ff.tile,
                                    sel_shards)
        y = S.ffn_sparse_batched(params, x, ids, ff.tile, cfg.act,
                                 k_valid=k_valid, layer=layer)
        return _compensate(params, cfg, x, y)

    if is_dense is None:
        return sparse(x_blocks)
    zeros = lambda x: jnp.zeros(x.shape, x.dtype)
    y_sp = jax.lax.cond(jnp.any(~is_dense), sparse, zeros, x_blocks)
    y_dn = jax.lax.cond(jnp.any(is_dense),
                        lambda x: ff_dense(params, cfg, x, layer),
                        zeros, x_blocks)
    return jnp.where(is_dense[:, None, None], y_dn, y_sp)


def ff_decode_sparse(params, cfg: ModelConfig, x_tok, plan,
                     shards: int = 1, k_valid=None, layer=None):
    """Decode-time sparsity (paper Table 3): block == current token.
    k_valid: traced scalar or [B] — per-row counts carry per-REQUEST
    effort tiers through the one batched decode executable. `layer`:
    stacked FFN matrices, as in `ff_block_sparse`."""
    return ff_block_sparse(params, cfg, x_tok, plan, shards,
                           k_valid=k_valid, layer=layer)


# ----------------------------------------------------------- scheduling


#: Named effort tiers — the per-request serving knob. Each maps the
#: config's global keep-fraction (1 - cfg.ff.sparsity) to the tier's:
#: "dense" disables sparsification (keep 1.0, still on the gather path
#: so it batches/compiles like any plan), "balanced" is the config
#: budget, "turbo" halves it (floor: 1 tile/layer via SparsityPlan).
EFFORT_TIERS = ("dense", "balanced", "turbo")


def effort_keep(cfg: ModelConfig, effort: Optional[str]) -> float:
    keep = 1.0 - cfg.ff.sparsity
    eff = effort or "balanced"
    if eff == "dense":
        return 1.0
    if eff == "balanced":
        return keep
    if eff == "turbo":
        return keep * 0.5
    raise ValueError(f"unknown effort tier {effort!r}; expected one of "
                     f"{EFFORT_TIERS}")


def effort_attn_keep(cfg: ModelConfig, effort: Optional[str]) -> float:
    """The attention-block twin of `effort_keep`: tiers scale the
    global attention keep-fraction (1 - cfg.ff.attn_sparsity) the same
    way they scale the FFN budget, so one tier governs BOTH."""
    keep = 1.0 - cfg.ff.attn_sparsity
    eff = effort or "balanced"
    if eff == "dense":
        return 1.0
    if eff == "balanced":
        return keep
    if eff == "turbo":
        return keep * 0.5
    raise ValueError(f"unknown effort tier {effort!r}; expected one of "
                     f"{EFFORT_TIERS}")


def resolve_plan(cfg: ModelConfig, effort: Optional[str] = None,
                 importance=None, d_ff: Optional[int] = None,
                 shards: int = 1) -> Optional[SparsityPlan]:
    """Resolve cfg (+ optional effort tier / calibration importance)
    into the SparsityPlan every FLOP-reducing path consumes.

    Returns None when FastForward is disabled. With `importance` (and
    cfg.ff.layerwise_schedule, the default) the per-layer counts come
    from Algorithm 1 under the tier's global budget; otherwise the
    uniform ceil rule — bit-identical to the legacy `k_tiles_for`
    scalar, which is the backward-compat shim for configs that only
    set cfg.ff.sparsity."""
    if not cfg.ff.enabled:
        return None
    d_ff = d_ff or cfg.d_ff or cfg.n_shared_experts * cfg.d_ff_expert
    if not d_ff:
        return None
    n_tiles = max(d_ff // cfg.ff.tile, 1)
    eff = effort or "balanced"
    keep = effort_keep(cfg, eff)
    if (importance is not None and cfg.ff.layerwise_schedule
            and eff != "dense"):
        plan = SparsityPlan.from_importance(
            importance, keep, n_tiles, cfg.ff.tile,
            name=f"{eff}-layerwise")
    else:
        plan = SparsityPlan.uniform(cfg.n_layers, n_tiles, cfg.ff.tile,
                                    keep, shards=shards, name=eff)
    # dual-budget: the same tier scales the attention-block budget
    # (dense tier -> attn_keep 1.0 -> with_attention no-ops, so the
    # plan stays the pre-dual-budget object and its executables)
    if cfg.ff.attn_sparsity > 0:
        plan = plan.with_attention(
            effort_attn_keep(cfg, eff), cfg.ff.attn_tiles,
            importance=(importance if cfg.ff.layerwise_schedule
                        else None))
    return plan


def _as_plan(cfg: ModelConfig, plan, shards: int = 1,
             d_ff: Optional[int] = None) -> Optional[SparsityPlan]:
    """Normalize a plan argument: None -> cfg-resolved uniform plan
    (compat shim), bare int k_tiles -> uniform_counts shim."""
    if plan is None:
        return resolve_plan(cfg, d_ff=d_ff, shards=shards)
    if isinstance(plan, (int, np.integer)):
        d_ff = d_ff or cfg.d_ff or cfg.n_shared_experts * cfg.d_ff_expert
        n_tiles = max(d_ff // cfg.ff.tile, 1)
        return SparsityPlan.uniform_counts(cfg.n_layers, n_tiles,
                                           cfg.ff.tile, int(plan))
    return plan


def decode_plan_setup(plans):
    """Shared decode-time plan plumbing for the model `decode_step`s.

    plans: tuple of resolved SparsityPlans (possibly empty/None-free —
    callers filter). Returns (sel_plan, counts_lp):
      * sel_plan — the plan whose k_max is the static tile-id width
        (max across the tuple; only k_max/tile are consumed);
      * counts_lp — [L, n_plans] int32 per-layer counts to ride the
        layer scan (each step gathers its row by traced plan_ids), or
        None on the single-uniform-plan fast path, which keeps the
        executable bit-identical to the pre-plan decode step.
    """
    if not plans:
        return None, None
    sel_plan = max(plans, key=lambda p: p.k_max)
    if len(plans) == 1 and plans[0].is_uniform:
        return sel_plan, None
    return sel_plan, jnp.asarray(
        np.stack([p.tile_counts for p in plans], axis=1), jnp.int32)


def decode_k_valid(k_row, plan_ids):
    """This layer's traced valid-count from a `decode_plan_setup`
    counts row: per-request [B] under traced plan_ids, scalar
    otherwise, None when no counts ride (uniform fast path)."""
    if k_row is None:
        return None
    if plan_ids is not None:
        return k_row[plan_ids]
    return k_row[0]


def layer_budgets(cfg: ModelConfig, importance=None):
    """Per-layer keep fractions: Algorithm 1 when enabled+calibrated,
    else uniform (1 - sparsity). (Mask-path budgets; the gather path
    consumes the same schedule as SparsityPlan integer counts.)"""
    keep = 1.0 - cfg.ff.sparsity
    if cfg.ff.layerwise_schedule and importance is not None:
        return SCHED.allocate_budgets(importance, keep)
    return SCHED.uniform_budgets(cfg.n_layers, keep)


def k_tiles_for(cfg: ModelConfig, d_ff: Optional[int] = None,
                shards: int = 1) -> int:
    """DEPRECATED shim: static uniform tile count (the pre-SparsityPlan
    scalar). Equals resolve_plan(cfg, d_ff=..., shards=...).k_max —
    kept for callers that only need the uniform width."""
    d_ff = d_ff or cfg.d_ff
    n_tiles = d_ff // cfg.ff.tile
    keep = 1.0 - cfg.ff.sparsity
    k = max(int(np.ceil(keep * n_tiles)), 1)
    if shards > 1 and n_tiles % shards == 0:
        per = max(int(np.ceil(k / shards)), 1)
        k = per * shards
    return min(k, n_tiles)
