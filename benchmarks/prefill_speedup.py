"""Paper Fig. 6-7: FFN-module and end-to-end compute-bound prefill
speedup from FastForward sparsity.

Compute-bound speedup = FLOPs(dense) / FLOPs(sparse) — the paper's Fig 7
metric ("corresponding to a 45% reduction in FLOPs at 50% sparsity").
The sparse cost honestly includes the dense first/last blocks, the
expert predictor, and the error compensator. Validates: peak e2e
speedup ~1.45x at 50% sparsity in the 2k-8k context range, decaying at
long context as quadratic attention dominates (paper Fig. 7).
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import write_bench_json
from benchmarks.flops_crossover import GEOMETRIES, layer_flops


def predictor_flops(d_model, d_ff, T, block):
    r = max(d_model // 16, 8)
    r = 1 << (r - 1).bit_length()
    n_blocks = T // block
    per_block = 2 * block * d_model + 2 * (d_model * r + r * d_ff)
    return n_blocks * per_block


def compensator_flops(d_model, T):
    rp = d_model // 8
    return 2 * T * d_model * rp * 2


def e2e_speedup(d_model, d_ff, T, sparsity, block=128,
                dense_first_last=True, with_overheads=True):
    f = layer_flops(d_model, d_ff, T)
    dense = f["attn"] + f["ffn"]
    n_blocks = max(T // block, 1)
    dense_blocks = 2 if (dense_first_last and n_blocks > 2) else 0
    frac_sparse_tokens = (n_blocks - dense_blocks) / n_blocks
    keep = 1.0 - sparsity
    ffn_sparse = f["ffn"] * ((1 - frac_sparse_tokens)
                             + frac_sparse_tokens * keep)
    over = 0.0
    if with_overheads:
        over = predictor_flops(d_model, d_ff, T, block) \
            + compensator_flops(d_model, T)
    sparse = f["attn"] + ffn_sparse + over
    return dense / sparse


def ffn_module_speedup(d_model, d_ff, T, sparsity, block=128):
    """Fig. 6 analog: FFN sublayer only."""
    f = layer_flops(d_model, d_ff, T)["ffn"]
    n_blocks = max(T // block, 1)
    dense_blocks = min(2, n_blocks)
    frac = (n_blocks - dense_blocks) / n_blocks
    keep = 1.0 - sparsity
    sparse = f * ((1 - frac) + frac * keep) \
        + predictor_flops(d_model, d_ff, T, block)
    return f / sparse


# ------------------------- dual-budget attention (block-sparse prefill)


def attention_flop_fraction(T, a_l, attn_tiles, blk=128):
    """Analytical fraction of QUADRATIC attention FLOPs a block-sparse
    prefill keeps at context T under per-layer budget count a_l (virtual
    attn_tiles grid). Mirrors `select_kv_blocks` exactly: query block i
    sees nv = i+1 causally-valid KV blocks and keeps
    clip(ceil(a_l * nv / attn_tiles), min(2, nv), nv) of them — the
    forced sink+diagonal floor is why the realized fraction sits above
    a_l/attn_tiles at short contexts and converges to it as the causal
    ramp grows."""
    nc = max(T // blk, 1)
    nv = np.arange(1, nc + 1, dtype=np.float64)
    kept = np.ceil(a_l * nv / attn_tiles)
    kept = np.clip(kept, np.minimum(2.0, nv), nv)
    return float(kept.sum() / nv.sum())


def dual_budget_fracs(d_model, d_ff, T, sparsity, a_l, attn_tiles,
                      blk=128):
    """(ffn_only, dual) total-layer FLOP fractions vs dense at context
    T. The attention budget scales only the quadratic QK^T/AV term;
    projections and the FFN budget are shared by both plans — so the
    gap between the two IS the attention win, and it grows with T."""
    f = layer_flops(d_model, d_ff, T)
    dense = f["attn"] + f["ffn"]
    keep_ffn = 1.0 - sparsity
    af = attention_flop_fraction(T, a_l, attn_tiles, blk)
    proj = f["attn"] - f["attn_quad"]
    ffn_only = (f["attn"] + keep_ffn * f["ffn"]) / dense
    dual = (proj + af * f["attn_quad"] + keep_ffn * f["ffn"]) / dense
    return ffn_only, dual


def run_attention_sparsity(csv=True, requests=16):
    """`attention_sparsity` section: (a) the reduced serving stack
    driven twice at MATCHED FFN budget — once FFN-only, once with the
    dual-budget plan's block-sparse attention on — reporting tok/s +
    TTFT p50; (b) the analytical attention-FLOP fraction and total
    FLOP fraction vs context 1K-16K (llama-8b geometry), asserting the
    dual budget beats the FFN-only plan at 8K+."""
    import jax
    from benchmarks.sparsity_plan import _drive, _workload
    from repro.configs import get_config
    from repro.core.fastforward import resolve_plan
    from repro.models.registry import get_model
    from repro.nn.param import init_params

    cfg = get_config("tinyllama-1.1b", reduced=True)
    cfg_attn = cfg.with_ff(attn_sparsity=0.5, attn_tiles=8)
    params = init_params(get_model(cfg).specs(cfg), jax.random.key(0))
    prompts, max_news, arrivals = _workload(cfg, requests=requests)

    ffn_only = resolve_plan(cfg)
    dual = resolve_plan(cfg_attn)
    # matched global FFN budget: the attention budget rides on TOP of
    # the identical tile schedule, so the serving delta isolates it
    assert dual.tile_counts == ffn_only.tile_counts
    assert dual.has_attn and not ffn_only.has_attn

    res_f = _drive(cfg, params, ffn_only, prompts, max_news, arrivals)
    res_d = _drive(cfg_attn, params, dual, prompts, max_news, arrivals)
    res_d["attn_counts"] = list(dual.attn_counts)
    res_d["attn_block_frac"] = round(dual.attn_flop_frac(), 4)

    # analytical curve, paper geometry: balanced tier (keep 0.5) on the
    # default virtual grid
    d, dff, _ = GEOMETRIES["llama-8b"]
    attn_tiles, a_l, s = 16, 8, 0.5
    contexts = [1024, 2048, 4096, 8192, 16384]
    curve = {}
    for T in contexts:
        fo, du = dual_budget_fracs(d, dff, T, s, a_l, attn_tiles)
        curve[str(T)] = {
            "attn_flop_frac": round(
                attention_flop_fraction(T, a_l, attn_tiles), 4),
            "total_frac_ffn_only": round(fo, 4),
            "total_frac_dual": round(du, 4),
        }
    # acceptance: the dual budget's total FLOP fraction must sit below
    # the FFN-only plan's at 8K+ where quadratic attention dominates
    for T in (8192, 16384):
        c = curve[str(T)]
        assert c["total_frac_dual"] < c["total_frac_ffn_only"], c

    payload = {
        "serving_matched_ffn_budget": {
            "ffn_only": res_f, "dual_budget": res_d,
            "requests": len(prompts),
            "note": "reduced CPU config: the XLA masked path pays dense "
                    "attention bytes (the Pallas kernel is the TPU side "
                    "of the skip), so tok/s is tracked for trend; the "
                    "load-bearing numbers are the analytical fractions",
        },
        "analytical_llama8b_s50": {
            "attn_tiles": attn_tiles, "a_l": a_l, "ffn_sparsity": s,
            "per_context": curve,
        },
    }
    path = write_bench_json("attention_sparsity", payload)
    rows = [
        ("attn_ffn_only_tok_s", res_f["tokens_per_s"],
         f"ttft_p50={res_f['ttft_p50_ms']}ms"),
        ("attn_dual_budget_tok_s", res_d["tokens_per_s"],
         f"ttft_p50={res_d['ttft_p50_ms']}ms "
         f"attn_counts={res_d['attn_counts']}"),
        ("attn_total_frac_8k", curve["8192"]["total_frac_dual"],
         f"ffn_only={curve['8192']['total_frac_ffn_only']}"),
        ("attn_total_frac_16k", curve["16384"]["total_frac_dual"],
         f"ffn_only={curve['16384']['total_frac_ffn_only']}"),
    ]
    if csv:
        for name, value, derived in rows:
            print(f"{name},{value},{derived}")
        print(f"# wrote {path}")
    return payload


def run(csv=True, requests=16):
    rows = []
    contexts = [512, 1024, 2048, 4096, 8192, 16384, 32768]
    peak = {}
    for name, (d, dff, L) in GEOMETRIES.items():
        for s in (0.3, 0.4, 0.5):
            sp = [e2e_speedup(d, dff, T, s) for T in contexts]
            peak[(name, s)] = max(sp)
            rows.append((f"e2e_speedup_{name}_s{int(s*100)}",
                         f"{max(sp):.3f}",
                         ";".join(f"{T}:{v:.3f}"
                                  for T, v in zip(contexts, sp))))
        ffn_sp = ffn_module_speedup(d, dff, 4096, 0.5)
        rows.append((f"ffn_speedup_{name}_s50_4k", f"{ffn_sp:.3f}", ""))
    if csv:
        for r in rows:
            print(",".join(str(x) for x in r))
    # machine-readable section: compute-bound speedup vs dense (the
    # paper's Fig 6-7 metric) per geometry at 50% sparsity
    write_bench_json("analytical_speedup_vs_dense", {
        "e2e_peak_s50": {name: round(peak[(name, 0.5)], 3)
                         for name, _ in GEOMETRIES.items()},
        "ffn_module_s50_4k": {
            name: round(ffn_module_speedup(d, dff, 4096, 0.5), 3)
            for name, (d, dff, L) in GEOMETRIES.items()},
        "note": "FLOPs(dense)/FLOPs(sparse) incl. dense first/last "
                "blocks, predictor, compensator (paper Fig. 7)",
    })
    # paper-claim validation: up to ~1.45x at 50% on the 8B model,
    # peaking mid-context, decaying at 32K
    p8 = peak[("llama-8b", 0.5)]
    assert 1.30 < p8 < 1.55, f"peak 8B e2e speedup {p8} vs paper's 1.45x"
    sp_curve = [e2e_speedup(4096, 14336, T, 0.5) for T in contexts]
    t_peak = contexts[int(np.argmax(sp_curve))]
    assert 2048 <= t_peak <= 16384, f"peak at {t_peak}, paper says 2k-8k"
    assert sp_curve[-1] < max(sp_curve), "speedup must decay at 32K"
    run_attention_sparsity(csv=csv, requests=requests)
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--requests", type=int, default=16,
                   help="reduced CI smoke uses a smaller stream")
    args = p.parse_args()
    run(requests=args.requests)
