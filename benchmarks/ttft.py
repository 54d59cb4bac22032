"""Paper Fig. 1 analog: measured wall-clock TTFT, dense vs FastForward,
through the real serving engine (reduced model, CPU).

On CPU the gather path does fewer FLOPs exactly like the TPU kernel, so
wall-time improves when the FFN dominates. Also measures the
sparse-FFN-only sublayer time (Fig. 6 analog) through the XLA path.
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import build_fixture
# StaticEngine keeps the seed measurement semantics (one batched
# blockwise prefill); the continuous engine's prefill_seconds means
# last-request TTFT under chunked scheduling — a different metric.
from repro.serving.engine import StaticEngine
from repro.core import sparse_ffn as S
from repro.core import fastforward as FF


def time_fn(fn, *args, iters=5):
    fn(*args)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def ffn_sublayer_times(cfg, params, T=512):
    lp = jax.tree.map(lambda a: a[0], params["layers"])["ffn"]
    x = jax.random.normal(jax.random.key(0), (T, cfg.d_model))
    N = cfg.ff.block_size
    xb = x.reshape(T // N, N, cfg.d_model)
    k = FF.k_tiles_for(cfg)
    ids = jnp.tile(jnp.arange(k, dtype=jnp.int32)[None], (T // N, 1))
    t_dense = time_fn(jax.jit(lambda a: S.ffn_dense(lp, a, cfg.act)), xb)
    t_sparse = time_fn(jax.jit(
        lambda a, i: S.ffn_sparse_batched(lp, a, i, cfg.ff.tile, cfg.act)),
        xb, ids)
    return t_dense, t_sparse


def run(csv=True):
    cfg, params, _ = build_fixture()
    rows = []
    td, ts = ffn_sublayer_times(cfg, params)
    rows.append(("ffn_sublayer_dense", f"{td*1e6:.1f}", "us"))
    rows.append(("ffn_sublayer_sparse50", f"{ts*1e6:.1f}",
                 f"wallclock={td/ts:.2f}x (CPU XLA gather-bound; the "
                 f"TPU Pallas kernel is DMA-redirected)"))
    rows.append(("ffn_sublayer_flop_ratio", "2.00",
                 "compute-bound speedup at 50% sparsity (Fig. 6 analog)"))

    rng = np.random.default_rng(0)
    for L in (256, 512):
        prompts = [rng.integers(0, cfg.vocab, L).tolist() for _ in range(2)]
        for tag, c in [("dense", cfg.with_ff(enabled=False)),
                       ("sparse50", cfg)]:
            eng = StaticEngine(c, params)
            eng.generate(prompts, max_new=1)           # warm the jit
            res = eng.generate(prompts, max_new=1)
            rows.append((f"ttft_{tag}_L{L}",
                         f"{res.prefill_seconds*1e3:.1f}", "ms"))
    if csv:
        for r in rows:
            print(",".join(str(x) for x in r))
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
