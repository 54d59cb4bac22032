#!/usr/bin/env python3
"""Smoke run of the serving path on one TPU chip, TinyLlama-1.1B at full
width (22 layers, d_model 2048, 32/4 heads, d_ff 5632, bf16), random
weights made from --seed.

  python chip_smoke.py [--seed 0]

One process drives the same calls as `repro.launch.serve --stream`
(make_runtime -> ContinuousBatchingScheduler.warmup -> drive_stream):

  (a) slot KV layout, FastForward on: 6 Poisson requests, prompts of
      1024-4096 tokens, max_new 16 — dense first/last blocks, sparse
      middle blocks and batched decode;
  (b) paged KV layout with block-sparse prefill attention
      (attn_sparsity 0.5) and int8 KV pages: 4 requests;
  (c) every Pallas kernel family at full width against its ref.py
      oracle (repro.kernels.full_width).

Each stream phase asserts that every request ends ok, that every
logits row it produced is finite, that no entry point recompiled after
warmup, and that the compiled prefill and decode steps contain Pallas
kernels (`tpu_custom_call`). Times printed are from this one smoke run,
not a benchmark. The last line of stdout is
{"ok": true, "device": {...}}; without a TPU, or when any check fails,
the script exits non-zero and does not print it.
"""
import argparse
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax                                      # noqa: E402
import jax.numpy as jnp                         # noqa: E402
import numpy as np                              # noqa: E402

from repro.configs import get_config            # noqa: E402
from repro.kernels.full_width import serving_kernel_cases  # noqa: E402
from repro.launch import serve                  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving import ContinuousBatchingScheduler, drive_stream  # noqa
from repro.serving.runtime import make_runtime  # noqa: E402

SLOTS = PREFILL_BATCH = 4
PROMPT_MIN, PROMPT_MAX, MAX_NEW = 1024, 4096, 16
RATE = 8.0                                      # req/s, Poisson arrivals


def check(ok, what):
    """A failed smoke check; raised, not asserted, so `python -O` keeps
    every check."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def watch_logits(runtime):
    """Wrap the runtime's prefill/decode entries so every logits row of
    an active request is checked for finiteness on the device; returns
    the list of per-call flags, read once at the end of the phase."""
    flags = []

    def watch(fn, out_at, active_at):
        def wrapped(*args, **kw):
            out = fn(*args, **kw)
            logits = out[out_at]
            ok = jnp.isfinite(logits).all(axis=-1)
            if active_at is not None:      # rows past the live batch
                ok = jnp.where(jnp.asarray(args[active_at]), ok, True)
            flags.append(jnp.all(ok))
            return out
        return wrapped

    # (logits index in the result, `active` index in the arguments)
    for name, out_at, active_at in (
            ("prefill_block", 1, None), ("prefill_blocks", 1, 6),
            ("prefill_blocks_paged", 1, 6), ("decode_step", 0, 3),
            ("decode_step_paged", 0, 4)):
        setattr(runtime, name, watch(getattr(runtime, name), out_at,
                                     active_at))
    return flags


def kernel_calls(runtime, sched):
    """Names of the Pallas kernels (`tpu_custom_call`s) in the compiled
    batched-prefill and decode programs, compiled for the shapes the
    stream ran them with."""
    P, N, n = sched.prefill_batch, runtime.block_size, sched.n_slots
    cache = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         sched.pool.cache)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    b = lambda *s: jax.ShapeDtypeStruct(s, jnp.bool_)
    plan = runtime.default_plan
    if sched.paged:
        mp = sched.pool.max_pages
        prefill = runtime.compiled_text(
            "prefill_blocks_paged", cache, i32(P, N), i32(P, mp), i32(P),
            b(P), i32(P), b(P), plan=plan)
        decode = runtime.compiled_text(
            "decode_step_paged", cache, i32(n), i32(n, mp), i32(n), b(n),
            i32(n))
    else:
        prefill = runtime.compiled_text(
            "prefill_blocks", cache, i32(P, N), i32(P), i32(P), b(P),
            i32(P), b(P), plan=plan)
        decode = runtime.compiled_text(
            "decode_step", cache, i32(n), i32(n), b(n), i32(n))
    def names(text):
        return sorted({m for line in text.splitlines()
                       if "tpu_custom_call" in line
                       for m in re.findall(r"jit\((\w+)\)/pallas_call",
                                           line)})
    return {"prefill": names(prefill), "decode": names(decode)}


def stream_phase(name, cfg, params, *, n_requests, seed, kernels):
    """kernels: {"prefill": [...], "decode": [...]} Pallas kernels each
    compiled step must contain — one missing means it fell back."""
    print(f"--- phase {name}: kv_layout={cfg.kv_layout} "
          f"attn_sparsity={cfg.ff.attn_sparsity} kv_quant={cfg.kv_quant} "
          f"| {n_requests} requests, prompts {PROMPT_MIN}-{PROMPT_MAX} "
          f"tokens, max_new <= {MAX_NEW}", flush=True)
    requests, cache_len = serve.poisson_requests(
        cfg, n_requests, rate=RATE, prompt_len=PROMPT_MAX,
        min_prompt_len=PROMPT_MIN, max_new=MAX_NEW, seed=seed)
    runtime = make_runtime(cfg, params,
                           plans=serve.stream_plans(cfg, requests))
    sched = ContinuousBatchingScheduler(
        runtime, n_slots=SLOTS, cache_len=cache_len, seed=seed,
        prefill_batch=PREFILL_BATCH)
    flags = watch_logits(runtime)

    t0 = time.perf_counter()
    counts0 = sched.warmup()
    warmup_s = time.perf_counter() - t0
    wall = drive_stream(sched, requests)
    counts1 = runtime.compile_counts()

    outs = sched.finished
    statuses = {rid: o.status for rid, o in outs.items()}
    check(sorted(outs) == [r.rid for r in requests]
          and all(s == "ok" for s in statuses.values()),
          f"request statuses {statuses}")
    check(all(bool(f) for f in flags), "non-finite logits")
    check(counts1 == counts0, f"recompiled: {counts0} -> {counts1}")
    calls = kernel_calls(runtime, sched)
    for step, want in kernels.items():
        check(set(want) <= set(calls[step]),
              f"{step} kernels {calls[step]}, expected {want}")

    ttft = np.array([o.ttft_seconds for o in outs.values()])
    gen = sum(len(o.tokens) for o in outs.values())
    prompt = sum(o.prompt_len for o in outs.values())
    print(f"  warmup (compiles every entry point) {warmup_s:.2f} s | "
          f"compile counts {counts1}", flush=True)
    print(f"  one smoke run, not a benchmark: {len(outs)} requests ok, "
          f"{prompt} prompt + {gen} generated tokens in {wall:.2f} s wall"
          f" | TTFT p50 {np.percentile(ttft, 50) * 1e3:.1f} ms, max "
          f"{ttft.max() * 1e3:.1f} ms | {gen / wall:.1f} generated tok/s",
          flush=True)
    print(f"  {len(flags)} prefill/decode calls, all logits finite | "
          f"no recompilation after warmup | Pallas kernels in compiled "
          f"prefill {calls['prefill']}, decode {calls['decode']}",
          flush=True)
    return warmup_s


def kernel_phase(cases, seed):
    print(f"--- phase c: {len(cases)} kernels at full width vs ref.py "
          "oracles (oracles at f32 'highest' matmul precision)", flush=True)
    key = jax.random.key(seed)
    for name, case in cases.items():
        args = jax.jit(case.make_args)(key)
        got = jax.tree.leaves(jax.jit(case.kernel)(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.tree.leaves(jax.jit(case.oracle)(*args))
        errs = []
        for g, w, (atol, rtol) in zip(got, want, case.tol, strict=True):
            g = np.asarray(g, np.float32)
            w = np.asarray(w, np.float32)
            check(g.shape == w.shape and np.isfinite(g).all(),
                  f"{name}: shape {g.shape} vs {w.shape} or non-finite")
            np.testing.assert_allclose(g, w, atol=atol, rtol=rtol,
                                       err_msg=name)
            errs.append(f"max|err| {np.abs(g - w).max():.3g} "
                        f"(atol {atol}, rtol {rtol})")
        print(f"  {name}: ok, {'; '.join(errs)}", flush=True)


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()
    backend = jax.default_backend()
    if backend != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX backend is {backend!r})")
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}"
          f" | compile cache {enable_compile_cache()}", flush=True)

    t0 = time.perf_counter()
    cfg = get_config("tinyllama-1.1b")
    params = serve.build_params(cfg, seed=args.seed)
    jax.block_until_ready(params)
    print(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, "
          f"{cfg.param_dtype} | params made in "
          f"{time.perf_counter() - t0:.2f} s", flush=True)

    compile_s = stream_phase(
        "a", cfg, params, n_requests=6, seed=args.seed, kernels={
            "prefill": ["flash_attention_rows", "sparse_ffn_batched"],
            "decode": ["sparse_ffn_batched"]})
    paged = cfg.with_(kv_layout="paged", kv_quant=True).with_ff(
        attn_sparsity=0.5)
    compile_s += stream_phase(
        "b", paged, params, n_requests=4, seed=args.seed, kernels={
            "prefill": ["block_sparse_prefill_quant", "quantize_pages",
                        "sparse_ffn_batched"],
            "decode": ["paged_decode_attention_quant", "quantize_pages",
                       "sparse_ffn_batched"]})
    del params
    t1 = time.perf_counter()
    kernel_phase(serving_kernel_cases(
        cfg, get_config("qwen2-moe-a2.7b"), batch=SLOTS,
        cache_len=PROMPT_MAX + MAX_NEW), args.seed)
    print(f"warmup (compile) total {compile_s:.2f} s | kernel phase "
          f"{time.perf_counter() - t1:.2f} s | whole run "
          f"{time.perf_counter() - t0:.2f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
