"""Layer-wise vs uniform SparsityPlan at EQUAL global budget.

The paper's headline composition (§3.4): the layer-wise scheduler
(Algorithm 1) reallocates a fixed global tile budget toward important
layers. Since the SparsityPlan redesign that schedule runs on the
FLOP-reducing gather/Pallas path, so this benchmark drives the SAME
continuous-batching serving stack twice — once under a uniform plan,
once under a layer-wise plan holding the identical total tile count —
and reports tok/s, TTFT p50, and analytical FFN FLOPs per token.

On the reduced CPU config wall-clock is overhead-bound (the XLA gather
path masks invalid tiles rather than skipping them — the Pallas kernel
is the TPU side of the FLOP skip), so the load-bearing numbers are the
equal-budget accounting (`total_tiles` must match) and the analytical
FLOPs; tok/s is tracked for trend only.

Writes the ``layerwise_vs_uniform`` section of
``results/BENCH_prefill.json`` and emits ``name,value,derived`` CSV
rows (harness contract).
"""
from __future__ import annotations

import numpy as np
import jax

from benchmarks.common import write_bench_json
from repro.configs import get_config
from repro.core.fastforward import resolve_plan
from repro.core.scheduler import SparsityPlan
from repro.models.registry import get_model
from repro.nn.param import init_params
from repro.serving import ContinuousBatchingScheduler, Request, drive_stream
from repro.serving.runtime import make_runtime

SLOTS = 4
PREFILL_BATCH = 4
REQUESTS = 24
PROMPT_RANGE = (96, 256)       # 3-8 blocks (reduced block_size 32):
                               # interior sparse blocks dominate
MAX_NEW_RANGE = (4, 24)
RATE = 120.0                   # deep backlog: prefill-bound


def _workload(cfg, seed=0, requests=REQUESTS):
    rng = np.random.default_rng(seed)
    prompts = [list(rng.integers(0, cfg.vocab,
                                 rng.integers(*PROMPT_RANGE)))
               for _ in range(requests)]
    max_news = [int(v) for v in rng.integers(*MAX_NEW_RANGE,
                                             size=requests)]
    arrivals = np.sort(np.cumsum(rng.exponential(1.0 / RATE,
                                                 size=requests)))
    return prompts, max_news, arrivals


def _ffn_flops_per_token(cfg, plan) -> float:
    """Analytical gated-FFN FLOPs/token under a plan (3 matmuls)."""
    dense = 3 * 2 * cfg.d_model * cfg.d_ff
    return dense * plan.flop_frac()


def _drive(cfg, params, plan, prompts, max_news, arrivals):
    runtime = make_runtime(cfg, params, plans=(plan,))
    N = runtime.block_size
    cache_len = (-(-max(len(p) for p in prompts) // N) * N
                 + max(max_news))
    sched = ContinuousBatchingScheduler(runtime, n_slots=SLOTS,
                                        cache_len=cache_len,
                                        prefill_batch=PREFILL_BATCH)
    counts0 = sched.warmup()
    requests = [Request(rid=i, prompt=prompts[i], max_new=max_news[i],
                        arrival_time=arrivals[i])
                for i in range(len(prompts))]
    wall = drive_stream(sched, requests)
    if None not in counts0.values():
        assert runtime.compile_counts() == counts0, "recompiled"
    outs = sched.finished
    gen = sum(len(o.tokens) for o in outs.values())
    ttfts = np.array([o.ttft_seconds for o in outs.values()])
    return {
        "tokens_per_s": round(gen / wall, 1),
        "ttft_p50_ms": round(float(np.percentile(ttfts, 50)) * 1e3, 2),
        "ttft_p99_ms": round(float(np.percentile(ttfts, 99)) * 1e3, 2),
        "ffn_flops_per_token": round(_ffn_flops_per_token(cfg, plan)),
        "ffn_flop_frac": round(plan.flop_frac(), 4),
        "total_tiles": int(sum(plan.tile_counts)),
        "tile_counts": list(plan.tile_counts),
    }


def run(csv=True, requests=REQUESTS):
    cfg = get_config("tinyllama-1.1b", reduced=True)
    model = get_model(cfg)
    params = init_params(model.specs(cfg), jax.random.key(0))
    prompts, max_news, arrivals = _workload(cfg, requests=requests)

    uniform = resolve_plan(cfg)                      # ceil(keep * n)
    # synthetic ramp importance (offline Algorithm 1 calibration stands
    # in for calibrate_layer_importance on the reduced config): later
    # layers matter more -> the waterfill shifts tiles toward them
    importance = np.linspace(1.0, 3.0, cfg.n_layers)
    n_tiles = cfg.d_ff // cfg.ff.tile
    layerwise = SparsityPlan.from_importance(
        importance, keep=float(np.mean(uniform.keep_fracs)),
        n_tiles=n_tiles, tile=cfg.ff.tile, name="balanced-layerwise")

    res_u = _drive(cfg, params, uniform, prompts, max_news, arrivals)
    res_l = _drive(cfg, params, layerwise, prompts, max_news, arrivals)
    # equal global budget: largest-remainder rounding pins the totals
    assert res_l["total_tiles"] == res_u["total_tiles"], (res_u, res_l)

    payload = {
        "uniform": res_u,
        "layerwise": res_l,
        "importance": [round(float(v), 3) for v in importance],
        "equal_budget_total_tiles": res_u["total_tiles"],
        "requests": len(prompts),
    }
    path = write_bench_json("layerwise_vs_uniform", payload)

    rows = [
        ("plan_uniform_tok_s", res_u["tokens_per_s"],
         f"ttft_p50={res_u['ttft_p50_ms']}ms"),
        ("plan_layerwise_tok_s", res_l["tokens_per_s"],
         f"ttft_p50={res_l['ttft_p50_ms']}ms "
         f"counts={res_l['tile_counts']}"),
        ("plan_equal_budget_tiles", res_u["total_tiles"],
         "layerwise total == uniform total"),
        ("plan_ffn_flops_per_token", res_l["ffn_flops_per_token"],
         f"uniform={res_u['ffn_flops_per_token']}"),
    ]
    if csv:
        for name, value, derived in rows:
            print(f"{name},{value},{derived}")
        print(f"# wrote {path}")
    return payload


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--requests", type=int, default=REQUESTS,
                   help="reduced CI smoke uses a smaller stream")
    args = p.parse_args()
    run(requests=args.requests)
