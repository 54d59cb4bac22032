"""SparsityPlan: Algorithm 1 on the FLOP-reducing path.

Covers the plan object itself (largest-remainder rounding, effort
tiers, width re-derivation), the per-layer/per-row `k_valid` masking
on the gather path and the batched Pallas kernel (interpret mode) vs
the mask-path oracle, the backward-compat shim (cfg-only configs are
bit-identical to an explicit uniform plan), and the serving contract:
mixed-effort streams keep compile_counts flat and every request's
greedy output depends only on its OWN plan.
"""
import functools
import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import fastforward as FF
from repro.core import predictor as P
from repro.core import scheduler as SCHED
from repro.core import sparse_ffn as S
from repro.core.scheduler import SparsityPlan
from repro.models.base import ModelConfig, FastForwardConfig
from repro.models.registry import get_model
from repro.nn.param import init_params
from repro.serving import (ContinuousBatchingScheduler, Engine, Request,
                           StaticEngine, load_trace)
from repro.serving.runtime import make_runtime
from repro.serving.trace import trace_stats


CFG = ModelConfig(name="t", arch="dense", n_layers=4, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=512, vocab=101,
                  remat=False,
                  ff=FastForwardConfig(enabled=True, tile=64,
                                       block_size=32))


@pytest.fixture(scope="module")
def ffn_params():
    return init_params(FF.fastforward_ffn_spec(CFG), jax.random.key(0))


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_config("tinyllama-1.1b", reduced=True)
    params = init_params(get_model(cfg).specs(cfg), jax.random.key(0))
    return cfg, params


def make_prompts(cfg, lengths, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, int(n)).tolist() for n in lengths]


# --------------------------------------------- budgets_to_tiles (satellite)


def test_budgets_to_tiles_largest_remainder_regression():
    """Independent per-layer round() drifts the realized total away
    from the global budget; largest-remainder pins it exactly."""
    budgets = np.array([0.3, 0.55, 0.55, 0.6])
    n_tiles = 8
    target = int(round(budgets.sum() * n_tiles))          # 16
    old = np.maximum(1, np.round(budgets * n_tiles)).astype(int)
    assert old.sum() != target                            # the bug
    counts = SCHED.budgets_to_tiles(budgets, n_tiles)
    assert counts.sum() == target
    assert counts.min() >= 1 and counts.max() <= n_tiles


def test_budgets_to_tiles_total_exact_random():
    rng = np.random.default_rng(0)
    for n_tiles in (2, 4, 8, 16):
        for L in (1, 3, 7, 22):
            b = rng.uniform(0.0, 1.0, size=L)
            counts = SCHED.budgets_to_tiles(b, n_tiles)
            target = int(np.clip(round(b.sum() * n_tiles), L, L * n_tiles))
            assert counts.sum() == target
            assert counts.min() >= 1 and counts.max() <= n_tiles


def test_budgets_to_tiles_respects_floor_and_cap():
    # all-zero budgets still keep one tile per layer
    counts = SCHED.budgets_to_tiles(np.zeros(5), 8)
    assert np.all(counts == 1)
    # all-one budgets cap at n_tiles
    counts = SCHED.budgets_to_tiles(np.ones(5), 8)
    assert np.all(counts == 8)


# ---------------------------------------- allocate_budgets (satellite)


def test_allocate_budgets_all_zero_importance_is_uniform():
    b = SCHED.allocate_budgets(np.zeros(6), 0.4)
    np.testing.assert_allclose(b, 0.4, atol=1e-9)


def test_allocate_budgets_single_spike_clips_and_redistributes():
    s = np.zeros(4)
    s[2] = 7.0
    b = SCHED.allocate_budgets(s, 0.5)
    assert b[2] == 1.0                       # the spike is clipped dense
    # the remaining budget is spread over the zero-importance layers
    np.testing.assert_allclose(b.sum(), 0.5 * 4, atol=1e-9)
    others = np.delete(b, 2)
    np.testing.assert_allclose(others, others[0], atol=1e-9)


# ------------------------------------------------- plan construction


def test_uniform_plan_matches_k_tiles_for():
    """The compat shim: cfg-only resolution == the legacy scalar."""
    for sparsity in (0.25, 0.5, 0.75):
        for shards in (1, 2):
            cfg = CFG.with_ff(sparsity=sparsity)
            plan = FF.resolve_plan(cfg, shards=shards)
            assert plan.is_uniform
            assert plan.k_max == FF.k_tiles_for(cfg, shards=shards)


def test_effort_tiers():
    bal = FF.resolve_plan(CFG, effort="balanced")
    dense = FF.resolve_plan(CFG, effort="dense")
    turbo = FF.resolve_plan(CFG, effort="turbo")
    assert dense.k_max == CFG.d_ff // CFG.ff.tile        # all tiles
    assert turbo.k_max < bal.k_max <= dense.k_max
    assert turbo.flop_frac() < bal.flop_frac() < dense.flop_frac()
    with pytest.raises(ValueError):
        FF.resolve_plan(CFG, effort="warp")


def test_layerwise_plan_from_importance():
    importance = np.array([1.0, 1.0, 1.0, 5.0])
    plan = FF.resolve_plan(CFG, importance=importance)
    assert not plan.is_uniform
    assert plan.tile_counts[3] > plan.tile_counts[0]
    # equal global budget: total tiles match the uniform budget exactly
    n_tiles = CFG.d_ff // CFG.ff.tile
    assert sum(plan.tile_counts) == round(0.5 * CFG.n_layers * n_tiles)


def test_with_tiles_rederivation():
    plan = SparsityPlan.from_budgets([0.25, 0.5, 0.5, 0.75], 8, 64)
    small = plan.with_tiles(4)
    assert small.n_tiles == 4 and small.n_layers == 4
    assert sum(small.tile_counts) == round(np.sum(plan.keep_fracs) * 4)
    # uniform plans reapply the legacy ceil rule (MoE shared expert)
    uni = SparsityPlan.uniform(4, 8, 64, keep=0.55)
    assert uni.with_tiles(4).k_max == int(np.ceil(0.55 * 4))
    assert plan.with_tiles(8) is plan


def test_plan_is_hashable_static_key():
    a = FF.resolve_plan(CFG, effort="balanced")
    b = FF.resolve_plan(CFG, effort="balanced")
    c = FF.resolve_plan(CFG, effort="turbo")
    assert a == b and hash(a) == hash(b) and a != c


# ---------------------------- k_valid: gather + kernel vs mask oracle


def test_k_valid_gather_matches_mask_oracle(ffn_params):
    """Masked top-k_max prefix == the top-k mask path, per count."""
    x = jax.random.normal(jax.random.key(5), (2, 32, 64))
    scores = jax.nn.sigmoid(P.neuron_scores(ffn_params["pred"], x))
    n_tiles = 8
    ids = S.balanced_topk_tiles(scores, n_tiles, 64)      # [2, 8]
    for k in (1, 3, 5, 8):
        y_g = S.ffn_sparse_batched(ffn_params, x, ids, 64, "silu",
                                   k_valid=jnp.int32(k))
        mask = S.mask_from_tile_ids(ids[:, :k], n_tiles, 64)
        y_m = S.ffn_masked(ffn_params, x, mask[:, None, :], "silu")
        np.testing.assert_allclose(np.asarray(y_g), np.asarray(y_m),
                                   rtol=1e-4, atol=1e-5)


def test_k_valid_batched_kernel_interpret_cross_check():
    """Per-row counts on the batched Pallas kernel (interpret mode) vs
    the XLA gather path vs per-row prefix gathers — distinct counts
    per row, the mixed-effort decode contract."""
    from repro.kernels.sparse_ffn.ops import sparse_ffn_batched_op
    from repro.kernels.sparse_ffn.ref import sparse_ffn_batched_ref
    rng = np.random.default_rng(7)
    B, N, D, F, tile = 3, 32, 64, 512, 64
    x = jnp.asarray(rng.normal(size=(B, N, D)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(D, F)) * 0.1, jnp.float32)
    wu = jnp.asarray(rng.normal(size=(D, F)) * 0.1, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(F, D)) * 0.1, jnp.float32)
    ids = jnp.asarray(
        np.stack([rng.choice(F // tile, size=5, replace=False)
                  for _ in range(B)]), jnp.int32)
    counts = jnp.asarray([1, 3, 5], jnp.int32)            # distinct rows
    y_int = sparse_ffn_batched_op(x, wg, wu, wd, ids, tile=tile,
                                  use_kernel=True, k_valid=counts)
    y_cpu = sparse_ffn_batched_op(x, wg, wu, wd, ids, tile=tile,
                                  use_kernel=False, k_valid=counts)
    np.testing.assert_allclose(np.asarray(y_int), np.asarray(y_cpu),
                               rtol=1e-5, atol=1e-5)
    for b in range(B):
        y_row = sparse_ffn_batched_ref(x[b:b + 1], wg, wu, wd,
                                       ids[b:b + 1, :int(counts[b])],
                                       tile)
        np.testing.assert_allclose(np.asarray(y_cpu[b]),
                                   np.asarray(y_row[0]),
                                   rtol=1e-5, atol=1e-5)


def test_k_valid_full_count_is_noop(ffn_params):
    """k_valid == K must be bit-identical to no masking (the uniform
    fast path and the masked path agree exactly at full width)."""
    x = jax.random.normal(jax.random.key(9), (2, 32, 64))
    scores = jax.nn.sigmoid(P.neuron_scores(ffn_params["pred"], x))
    ids = S.balanced_topk_tiles(scores, 4, 64)
    y0 = S.ffn_sparse_batched(ffn_params, x, ids, 64, "silu")
    y1 = S.ffn_sparse_batched(ffn_params, x, ids, 64, "silu",
                              k_valid=jnp.int32(4))
    assert np.array_equal(np.asarray(y0), np.asarray(y1))


# ------------------------------------- model level: layer-wise plans


def test_dense_prefill_layerwise_plan_matches_mask_forward(dense_setup):
    """Non-uniform per-layer counts on the gather path (blockwise
    prefill) vs the mask-path forward oracle carrying the SAME exact
    counts — the paper's scheduler x kernel composition."""
    cfg, params = dense_setup
    model = get_model(cfg)
    n_tiles = cfg.d_ff // cfg.ff.tile                     # 4
    plan = SparsityPlan(name="lw", tile_counts=(1, 3), n_tiles=n_tiles,
                        tile=cfg.ff.tile, keep=0.5)
    assert not plan.is_uniform
    rng = np.random.default_rng(3)
    T = 4 * cfg.ff.block_size
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (2, T)), jnp.int32)
    batch = {"tokens": tokens}
    logits_mask, _ = model.forward(params, cfg, batch, plan=plan)
    cache = model.init_cache(cfg, 2, T)
    _, logits_gather = model.prefill(params, cfg, batch, cache, plan=plan)
    np.testing.assert_allclose(np.asarray(logits_gather),
                               np.asarray(logits_mask[:, -1]),
                               rtol=2e-3, atol=2e-4)
    # the plan must actually bite: a uniform plan at the same k_max
    # gives a different answer
    _, logits_uni = model.prefill(
        params, cfg, batch, model.init_cache(cfg, 2, T),
        plan=SparsityPlan.uniform_counts(cfg.n_layers, n_tiles,
                                         cfg.ff.tile, plan.k_max))
    assert np.abs(np.asarray(logits_gather)
                  - np.asarray(logits_uni)).max() > 1e-4


def test_moe_forward_plan_without_shared_expert():
    """A pure-routed MoE (no shared expert — nothing for FastForward to
    sparsify) must tolerate forward(plan=...): shared_plan resolves to
    None and the routed path runs dense (code-review regression)."""
    from repro.models import moe
    cfg = ModelConfig(name="m", arch="moe", n_layers=2, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=0, vocab=64,
                      n_experts=4, top_k=2, n_shared_experts=0,
                      d_ff_expert=64, remat=False,
                      ff=FastForwardConfig(enabled=True, tile=16,
                                           block_size=8))
    params = init_params(moe.specs(cfg), jax.random.key(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab, (1, 16)),
        jnp.int32)
    # an explicit plan (e.g. a serving tier resolved for another model
    # width): shared_plan() maps it to None — must not dereference it
    plan = SparsityPlan.uniform(cfg.n_layers, 4, cfg.ff.tile, keep=0.5)
    logits, _ = moe.forward(params, cfg, {"tokens": tokens}, plan=plan)
    assert logits.shape == (1, 16, cfg.vocab)


# --------------------------------------------- serving: compat shim


def test_engine_shim_bit_identical_to_explicit_uniform_plan(dense_setup):
    """Configs that only set cfg.ff.sparsity (no plan anywhere) must
    produce bit-identical greedy output to an explicitly-constructed
    uniform SparsityPlan — and both match the pre-redesign static
    engine path."""
    cfg, params = dense_setup
    prompts = make_prompts(cfg, [40, 70, 33])
    implicit = Engine(cfg, params).generate(prompts, max_new=8)
    explicit = Engine(cfg, params,
                      plans=(FF.resolve_plan(cfg),)).generate(
                          prompts, max_new=8)
    static = StaticEngine(cfg, params).generate(prompts, max_new=8)
    assert np.array_equal(implicit.tokens, explicit.tokens)
    assert np.array_equal(implicit.tokens, static.tokens)


# --------------------------------- serving: mixed-effort invariants


@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
def test_mixed_effort_stream_compile_flat(dense_setup, kv_layout):
    """A stream mixing two effort tiers never recompiles after warmup:
    every (plan, width bucket) prefill executable is pre-compiled and
    decode rides traced plan_ids through ONE executable."""
    cfg, params = dense_setup
    cfg = cfg.with_(kv_layout=kv_layout)
    plans = (FF.resolve_plan(cfg, effort="balanced"),
             FF.resolve_plan(cfg, effort="turbo"))
    runtime = make_runtime(cfg, params, plans=plans)
    sched = ContinuousBatchingScheduler(runtime, n_slots=3,
                                        cache_len=160, prefill_batch=2)
    counts0 = sched.warmup()
    prompts = make_prompts(cfg, [40, 70, 33, 90, 64, 50])
    for i, prompt in enumerate(prompts):
        sched.submit(Request(rid=i, prompt=prompt, max_new=6,
                             effort=("turbo" if i % 2 else "balanced")))
    outs = sched.run()
    assert len(outs) == len(prompts)
    counts1 = runtime.compile_counts()
    if None not in counts0.values():
        assert counts1 == counts0, (counts0, counts1)
    stats = sched.sparsity_stats()
    assert [p["name"] for p in stats["plans"]] == ["balanced", "turbo"]
    assert all(p["prefill_blocks"] > 0 for p in stats["plans"])
    # dense first/last prompt blocks count at 1.0, sparse blocks and
    # decode tokens at their plan's fraction
    N = runtime.block_size
    n_dense, n_sparse = sched.n_prefill_rows_dense, sched.n_prefill_rows_sparse
    assert n_dense > 0 and n_sparse > 0
    assert n_dense + n_sparse == sched.n_prefill_blocks
    rows = stats["plans"]
    assert sum(p["sparse_prefill_blocks"] for p in rows) == n_sparse
    done = N * n_dense + sum(
        p.flop_frac() * (N * r["sparse_prefill_blocks"] + r["decode_tokens"])
        for p, r in zip(plans, rows))
    work = N * (n_dense + n_sparse) + sum(r["decode_tokens"] for r in rows)
    assert stats["aggregate_ffn_flop_frac"] == pytest.approx(done / work,
                                                             rel=1e-12)


def test_effort_output_independent_of_batch_mix(dense_setup):
    """A request's greedy output depends only on its OWN plan: turbo
    requests in a mixed balanced/turbo stream emit exactly what they
    emit in a pure-turbo engine (per-row decode counts + plan-
    homogeneous prefill batching keep rows independent)."""
    cfg, params = dense_setup
    bal = FF.resolve_plan(cfg, effort="balanced")
    tur = FF.resolve_plan(cfg, effort="turbo")
    prompts = make_prompts(cfg, [40, 70, 33, 90])
    mixed = Engine(cfg, params, plans=(bal, tur))
    sched = mixed.scheduler(n_slots=4, cache_len=160)
    for i, prompt in enumerate(prompts):
        sched.submit(Request(rid=i, prompt=prompt, max_new=8,
                             effort=("turbo" if i % 2 else None)))
    outs = sched.run()

    pure_bal = Engine(cfg, params, plans=(bal,)).generate(
        [prompts[0], prompts[2]], max_new=8)
    pure_tur = Engine(cfg, params, plans=(tur,)).generate(
        [prompts[1], prompts[3]], max_new=8)
    assert outs[0].tokens == pure_bal.tokens[0].tolist()
    assert outs[2].tokens == pure_bal.tokens[1].tolist()
    assert outs[1].tokens == pure_tur.tokens[0].tolist()
    assert outs[3].tokens == pure_tur.tokens[1].tolist()
    # and the tiers genuinely differ
    assert outs[0].tokens != outs[1].tokens


def test_layerwise_plan_serves(dense_setup):
    """A NON-uniform plan drives the whole continuous-batching stack
    (batched prefill + ragged decode) with flat compile counts."""
    cfg, params = dense_setup
    n_tiles = cfg.d_ff // cfg.ff.tile
    plan = SparsityPlan(name="lw", tile_counts=(1, 3), n_tiles=n_tiles,
                        tile=cfg.ff.tile, keep=0.5)
    runtime = make_runtime(cfg, params, plans=(plan,))
    sched = ContinuousBatchingScheduler(runtime, n_slots=2,
                                        cache_len=160, prefill_batch=2)
    counts0 = sched.warmup()
    for i, prompt in enumerate(make_prompts(cfg, [70, 40, 90])):
        sched.submit(Request(rid=i, prompt=prompt, max_new=5))
    outs = sched.run()
    assert all(len(o.tokens) == 5 for o in outs.values())
    if None not in counts0.values():
        assert runtime.compile_counts() == counts0


def test_unknown_effort_rejected(dense_setup):
    cfg, params = dense_setup
    runtime = make_runtime(cfg, params)
    sched = ContinuousBatchingScheduler(runtime, n_slots=2, cache_len=96)
    with pytest.raises(ValueError, match="effort"):
        sched.submit(Request(rid=0, prompt=[1, 2, 3], max_new=2,
                             effort="turbo"))


# ------------------------------------- dual-budget attention (tentpole)


def test_dual_budget_plan_construction():
    """with_attention attaches the attention-block budget: counts in
    [1, attn_tiles], fields survive hashing/equality, the dense tier
    no-ops, and with_tiles (MoE shared-expert re-derivation) carries
    the attention budget across the FFN width change."""
    cfg = CFG.with_ff(attn_sparsity=0.5, attn_tiles=8)
    bal = FF.resolve_plan(cfg, effort="balanced")
    tur = FF.resolve_plan(cfg, effort="turbo")
    dense = FF.resolve_plan(cfg, effort="dense")
    assert bal.has_attn and tur.has_attn
    assert not dense.has_attn               # attn_keep 1.0 -> no-op
    assert len(bal.attn_counts) == cfg.n_layers
    assert all(1 <= c <= 8 for c in bal.attn_counts)
    assert bal.attn_k_max == 4 and tur.attn_k_max == 2
    assert tur.attn_flop_frac() < bal.attn_flop_frac() < 1.0
    # attention budget joins the plan identity (jit static key)
    bal2 = FF.resolve_plan(cfg, effort="balanced")
    assert bal == bal2 and hash(bal) == hash(bal2)
    assert bal != FF.resolve_plan(CFG.with_ff(attn_sparsity=0.0),
                                  effort="balanced")
    # width re-derivation keeps the attention budget untouched
    small = bal.with_tiles(4)
    assert small.attn_counts == bal.attn_counts
    assert small.attn_tiles == bal.attn_tiles
    np.testing.assert_allclose(np.asarray(bal.attn_keep_fracs), 0.5)
    np.testing.assert_array_equal(np.asarray(bal.attn_counts_array()),
                                  np.asarray(bal.attn_counts))


def test_dual_budget_layerwise_importance():
    importance = np.array([1.0, 1.0, 1.0, 5.0])
    cfg = CFG.with_ff(attn_sparsity=0.5, attn_tiles=8)
    plan = FF.resolve_plan(cfg, importance=importance)
    assert plan.has_attn
    assert plan.attn_counts[3] > plan.attn_counts[0]
    assert sum(plan.attn_counts) == round(0.5 * cfg.n_layers * 8)


# ------------------------- model level: FFN weights read from the stacks


SCAN_ENTRIES = ["prefill_blocks_slot", "prefill_blocks_paged", "decode_step"]


def _scan_entry(cfg, entry):
    """One of the dense model's serving layer scans as a function of the
    params, on fixed random inputs: three rows, the first a dense
    (boundary) block in prefill, non-zero caches, a layer-wise plan
    whose counts leave dead tile slots."""
    from repro.models import dense
    N, L = cfg.ff.block_size, cfg.n_layers
    Kv, dh = cfg.n_kv_heads, cfg.head_dim
    plan = FF.resolve_plan(cfg, importance=np.linspace(0.2, 1.0, L))
    assert not plan.is_uniform
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (3, N)), jnp.int32)
    pos0s = jnp.asarray([0, N, 2 * N], jnp.int32)
    is_dense = jnp.asarray([True, False, False])
    lengths = jnp.asarray([N, 2 * N, 3 * N - 5], jnp.int32)
    active = jnp.ones((3,), bool)

    def kv(*shape):
        return jnp.asarray(rng.normal(size=(L,) + shape), cfg.dtype)

    if entry == "prefill_blocks_slot":
        cache = {"k": kv(3, 4 * N, Kv, dh), "v": kv(3, 4 * N, Kv, dh)}
        return lambda p: dense.prefill_blocks(
            p, cfg, toks, cache, pos0s, is_dense=is_dense,
            lengths=lengths, plan=plan)
    if entry == "prefill_blocks_paged":
        cache = {"k": kv(10, N, Kv, dh), "v": kv(10, N, Kv, dh)}
        tables = jnp.asarray([[1, 0, 0], [2, 3, 0], [4, 5, 6]], jnp.int32)
        return lambda p: dense.prefill_blocks(
            p, cfg, toks, cache, pos0s, is_dense=is_dense,
            lengths=lengths, active=active, page_tables=tables, plan=plan)
    cache = {"k": kv(3, 4 * N, Kv, dh), "v": kv(3, 4 * N, Kv, dh)}
    return lambda p: dense.decode_step(
        p, cfg, toks[:, 0], cache, lengths, active=active, plan=plan)


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("entry", SCAN_ENTRIES)
def test_layer_scans_read_ffn_stacks_bit_identical(dense_setup, entry,
                                                   use_kernel,
                                                   monkeypatch):
    """The slot and paged batched prefill and the decode step, whose
    layer scans hand the FFN the whole W_gate/W_up/W_down stacks and
    the layer index, give the same outputs and caches, bit for bit, as
    when every FFN call gets that layer's 2-D weights (the per-layer
    feed the scans used before) — dense rows mixed in, on the XLA
    oracle and on the batched kernel in interpret mode."""
    from repro.kernels.sparse_ffn import ops
    cfg, params = dense_setup
    monkeypatch.setattr(ops, "sparse_ffn_batched_op", functools.partial(
        ops.sparse_ffn_batched_op, use_kernel=use_kernel))
    run = _scan_entry(cfg, entry)
    stacked = run(params)
    pred_stack = jax.tree.leaves(params["layers"]["ffn"]["pred"])[0]

    def scanned_layer(fp):
        """The layer the scan is at, found from the predictor weights
        it sliced, not from the index it passes."""
        hit = pred_stack == jax.tree.leaves(fp["pred"])[0]
        return jnp.argmax(jnp.all(hit, axis=tuple(range(1, hit.ndim))))

    for name in ("ff_blocks_sparse", "ff_decode_sparse"):
        def per_layer(fp, *a, layer=None, _fn=getattr(FF, name), **kw):
            assert layer is not None and fp["wu"].ndim == 3
            return _fn(S.ffn_at(fp, scanned_layer(fp)), *a, **kw)
        monkeypatch.setattr(FF, name, per_layer)
    sliced = run(params)
    for got, want in zip(jax.tree.leaves(stacked), jax.tree.leaves(sliced),
                         strict=True):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _pallas_calls(jaxpr):
    """Every pallas_call equation of a jaxpr, inner jaxprs (scan and
    cond bodies, nested jits) included."""
    from jax.extend import core as jcore
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(sub, jcore.ClosedJaxpr):
                    yield from _pallas_calls(sub.jaxpr)
                elif isinstance(sub, jcore.Jaxpr):
                    yield from _pallas_calls(sub)


@pytest.mark.parametrize("entry", SCAN_ENTRIES)
def test_sparse_ffn_kernel_operands_are_the_stacks(dense_setup, entry,
                                                   monkeypatch):
    """Lowered with the kernel path on, every sparse FFN pallas_call of
    the layer scans takes the whole stacked [L, ...] W_gate, W_up and
    W_down as its weight operands (ids, cnt, x, wg, wu, wd): no
    per-layer slice of them feeds the kernel, so XLA has no copy to
    make."""
    from repro.kernels.sparse_ffn import ops
    cfg, params = dense_setup
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    jaxpr = jax.make_jaxpr(_scan_entry(cfg, entry))(params)
    calls = list(_pallas_calls(jaxpr.jaxpr))
    assert calls
    ffn = params["layers"]["ffn"]
    want = [ffn[k].shape for k in ("wg", "wu", "wd")]
    assert want[0][0] == cfg.n_layers
    for eqn in calls:
        assert len(eqn.invars) == 6
        assert [v.aval.shape for v in eqn.invars[3:]] == want
        # the layer index rides in cnt, after the per-row counts
        assert eqn.invars[1].aval.shape == (eqn.invars[2].aval.shape[0]
                                            + 1,)


def test_attn_budget_full_keep_bit_identical_prefill(dense_setup):
    """A hand-built FULL attention budget (every virtual slot kept) on
    the blockwise prefill path must be BIT-identical to the plan
    without one — the masked XLA path keeps every causally-valid key."""
    import dataclasses
    cfg, params = dense_setup
    model = get_model(cfg)
    base = FF.resolve_plan(cfg)
    full = dataclasses.replace(base, attn_counts=(8,) * cfg.n_layers,
                               attn_tiles=8, attn_keep=1.0)
    rng = np.random.default_rng(11)
    T = 4 * cfg.ff.block_size
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (2, T)), jnp.int32)
    cache = model.init_cache(cfg, 2, T)
    _, logits_base = model.prefill(params, cfg, {"tokens": tokens},
                                   cache, plan=base)
    _, logits_full = model.prefill(params, cfg, {"tokens": tokens},
                                   model.init_cache(cfg, 2, T),
                                   plan=full)
    np.testing.assert_array_equal(np.asarray(logits_full),
                                  np.asarray(logits_base))
    # half budget changes the answer (the budget actually bites)
    half = dataclasses.replace(base, attn_counts=(4,) * cfg.n_layers,
                               attn_tiles=8, attn_keep=0.5)
    _, logits_half = model.prefill(params, cfg, {"tokens": tokens},
                                   model.init_cache(cfg, 2, T),
                                   plan=half)
    assert not np.array_equal(np.asarray(logits_half),
                              np.asarray(logits_base))


@pytest.mark.parametrize("kv_layout", ["slot", "paged"])
def test_long_context_mixed_tier_greedy_equivalence(dense_setup,
                                                    kv_layout):
    """Long-context (>= 4K tokens, reduced config) batch-composition
    invariance under DUAL budgets: a mixed balanced/turbo stream with
    block-sparse attention on emits, per request, exactly what a
    pure-tier engine emits — and compile counts stay flat across the
    mixed stream (zero recompilation with attention budgets riding the
    scan)."""
    cfg, params = dense_setup
    cfg = cfg.with_(kv_layout=kv_layout).with_ff(attn_sparsity=0.5,
                                                 attn_tiles=8)
    bal = FF.resolve_plan(cfg, effort="balanced")
    tur = FF.resolve_plan(cfg, effort="turbo")
    assert bal.has_attn and tur.has_attn
    N = cfg.ff.block_size
    prompts = make_prompts(cfg, [4096 + N, 4096], seed=13)
    cache_len = -(-max(len(p) for p in prompts) // N) * N + 8
    mixed = Engine(cfg, params, plans=(bal, tur), prefill_batch=2)
    sched = mixed.scheduler(n_slots=2, cache_len=cache_len)
    counts0 = sched.warmup()
    sched.submit(Request(rid=0, prompt=prompts[0], max_new=4))
    sched.submit(Request(rid=1, prompt=prompts[1], max_new=4,
                         effort="turbo"))
    outs = sched.run()
    if None not in counts0.values():
        assert sched.runtime.compile_counts() == counts0

    pure_bal = Engine(cfg, params, plans=(bal,)).generate(
        [prompts[0]], max_new=4)
    pure_tur = Engine(cfg, params, plans=(tur,)).generate(
        [prompts[1]], max_new=4)
    assert outs[0].tokens == pure_bal.tokens[0].tolist()
    assert outs[1].tokens == pure_tur.tokens[0].tolist()


# ----------------------------------------------------- trace effort


def test_trace_effort_field(tmp_path):
    path = tmp_path / "t.jsonl"
    recs = [
        {"arrival_s": 0.0, "prompt_len": 8, "gen_len": 2,
         "effort": "turbo"},
        {"arrival_s": 0.1, "prompt_len": 4, "gen_len": 2},
    ]
    path.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    reqs = load_trace(str(path), vocab=100)
    assert reqs[0].effort == "turbo" and reqs[1].effort is None
    stats = trace_stats(reqs)
    assert stats["efforts"] == ["default", "turbo"]
    # loader-level default effort applies only to records without one
    reqs = load_trace(str(path), vocab=100, effort="balanced")
    assert reqs[0].effort == "turbo" and reqs[1].effort == "balanced"
