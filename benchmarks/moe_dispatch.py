"""Dropless vs capacity routed-expert dispatch: cost + invariance.

The dropless sort-based grouped dispatch replaced capacity scatter
routing as the serving default (it is dispatch-group invariant, which
the blockwise serving equivalences require). This benchmark tracks
what that buys and costs on CPU XLA:

  * wall-clock per routed-experts call at prefill-block and full-
    sequence shapes, dropless (ragged_dot grouped path) vs capacity
    (scatter + [E, C, D] buffer einsum);
  * the dispatched-row accounting: capacity computes E*C padded rows
    (C = ceil(N*K*cf/E), so ~cf x the active rows, MORE under the
    8-row layout round-up at small dispatch groups), dropless computes
    exactly the N*K routed rows plus tile padding;
  * a dispatch-group invariance probe (full sequence vs per-block
    max-abs routed-output delta) for both modes, on an engineered-
    overflow input (identical rows all routing to the same experts, so
    the one-group capacity drops rows the per-block capacities keep) —
    capacity comes out nonzero, dropless is the number the de-xfailed
    equivalence tests pin to zero.

Emits ``name,value,derived`` CSV rows (harness contract) and writes
the ``moe_dispatch`` section of results/BENCH_prefill.json.
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import write_bench_json
from repro.configs import get_config
from repro.models.moe import capacity, moe_ffn_spec, routed_experts
from repro.nn.param import init_params


def _timed(fn, *args, iters=20):
    y = jax.block_until_ready(fn(*args))          # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        y = fn(*args)
    jax.block_until_ready(y)
    return (time.perf_counter() - t0) / iters


def run(arch: str = "qwen2-moe-a2.7b", seq: int = 512, block: int = 128,
        iters: int = 20, seed: int = 0):
    cfg = get_config(arch, reduced=True)
    mp = init_params(moe_ffn_spec(cfg, cfg.dtype), jax.random.key(seed))
    x = jax.random.normal(jax.random.key(seed + 1), (1, seq, cfg.d_model),
                          cfg.dtype)
    modes = {m: cfg.with_(moe_dispatch=m) for m in ("dropless", "capacity")}
    fns = {m: jax.jit(lambda xx, c=c: routed_experts(mp, c, xx)[0])
           for m, c in modes.items()}
    # engineered-overflow probe input: identical rows all route to the
    # same top-k experts, so the full-sequence capacity drops rows that
    # the per-block capacities keep — random input rarely overflows at
    # cf=1.25 and would report a vacuous 0.0 for capacity mode
    x_ovf = jnp.tile(
        jax.random.normal(jax.random.key(seed + 2), (1, 1, cfg.d_model),
                          cfg.dtype), (1, seq, 1))

    out = {"arch": arch, "seq": seq, "block": block,
           "n_experts": cfg.n_experts, "top_k": cfg.top_k,
           "capacity_factor": cfg.capacity_factor}
    rows = []
    for m, fn in fns.items():
        t_full = _timed(fn, x, iters=iters)
        # fn is jitted: block-shaped calls hit their own cached
        # executable, no extra wrapper needed
        t_blk = sum(
            _timed(fn, x[:, o:o + block], iters=iters)
            for o in range(0, seq, block))
        # invariance probe: full-sequence vs concatenated per-block on
        # the overflow input
        y_full = np.asarray(fn(x_ovf))
        y_blk = np.concatenate(
            [np.asarray(fn(x_ovf[:, o:o + block]))
             for o in range(0, seq, block)], axis=1)
        delta = float(np.abs(y_full - y_blk).max())
        out[m] = {"seconds_full": t_full, "seconds_blockwise": t_blk,
                  "block_vs_full_delta_max": delta}
        rows += [(f"{m}_full_ms", t_full * 1e3, ""),
                 (f"{m}_blockwise_ms", t_blk * 1e3, ""),
                 (f"{m}_block_vs_full_delta", delta, "")]

    # dispatched-row accounting (shape-level, exact)
    K = cfg.top_k
    active = seq * K
    cap_rows = cfg.n_experts * capacity(seq, cfg)
    cap_rows_blk = (seq // block) * cfg.n_experts * capacity(block, cfg)
    out["rows"] = {"active": active, "capacity_full": cap_rows,
                   "capacity_blockwise": cap_rows_blk}
    rows += [("active_rows", active, ""),
             ("capacity_padded_rows_full", cap_rows,
              f"{cap_rows / active:.2f}x active"),
             ("capacity_padded_rows_blockwise", cap_rows_blk,
              f"{cap_rows_blk / active:.2f}x active")]

    assert out["dropless"]["block_vs_full_delta_max"] == 0.0, \
        "dropless dispatch must be dispatch-group invariant"
    assert out["capacity"]["block_vs_full_delta_max"] > 0.0, \
        "overflow probe failed to trigger a capacity drop"
    for name, value, derived in rows:
        print(f"{name},{value},{derived}")
    path = write_bench_json("moe_dispatch", out)
    print(f"# wrote {path}")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", default="qwen2-moe-a2.7b")
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--block", type=int, default=128)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()
    run(arch=args.arch, seq=args.seq, block=args.block, iters=args.iters)
