#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator it is started on.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell (BENCHMARK.json "workloads") names
a configuration file and a traffic file; the serving path of
`repro.launch.serve --stream` runs it: random weights from the seed,
`make_runtime`, `ContinuousBatchingScheduler.warmup()`, then the
scheduler's ticks for `--seconds`, driven by bench/loop.py. Afterwards
a sample of the served requests is checked against the plain reference
(bench/check.py). The last line of stdout is the result:

  {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
   "device": {...}, ["breakdown": {...},] "checks": {...}}

With --trace 0 the metrics are the cell's end-to-end metrics; with
--trace 1 the tail of the window is profiled and the metrics are its
per-layer metrics. Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import json              # noqa: E402
import pathlib           # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
#: JAX's persistent compilation cache, at a fixed path in the checkout
CACHE = ROOT / ".bench_cache"
#: traced runs profile this many seconds at the end of the window
TRACE_S = 8.0


def configure_jax():
    """Compile cache in the checkout, whatever the environment says, so
    that two checkouts share nothing and a second run loads every
    program the first compiled."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def device_or_exit(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.exit(f"bench: needs {chips} TPU chip(s); JAX sees "
                 f"{len(devs)} {devs[0].platform} device(s)")
    return devs


def checks_of(limits, gap, counts0, counts1, kernels, expected):
    recompiles = sum(counts1[k] - counts0[k] for k in counts0)
    missing = sum(len(set(want) - set(kernels.get(step, ())))
                  for step, want in expected.items())
    checks = {
        "max_logit_gap": {"value": gap["max_logit_gap"],
                          "limit": limits["max_logit_gap"]["limit"]},
        "recompiles": {"value": recompiles, "limit": 0},
        "kernels_missing": {"value": missing, "limit": 0},
        "tokens_checked": {"value": gap["n_tokens"], "at_least": 1},
    }
    ok = (gap["max_logit_gap"] is not None
          and gap["max_logit_gap"] <= checks["max_logit_gap"]["limit"]
          and recompiles == 0 and missing == 0 and gap["n_tokens"] >= 1)
    return ok, checks


def run_cell(cell, seed: int, seconds: float, traced: bool, devs) -> dict:
    """One run of `cell` on `devs`: set-up, window, readings, check.
    Returns the result line."""
    from bench import check, harness, traffic
    from bench import trace as TR
    import jax
    log = harness.log
    server = harness.setup(cell, seed,
                           check_kernels=devs[0].platform == "tpu")
    planned = traffic.plan(cell.traffic, cell.config["vocab_size"], seed,
                           seconds)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s | sizes {server.sizes} | compile counts "
        f"{server.counts0} | kernels {server.kernels}")

    profiler = tdir = None
    if traced:
        server.rec.annotate = True
        tdir = CACHE / "trace" / cell.name
        shutil.rmtree(tdir, ignore_errors=True)
        profiler = TR.Profiler(
            str(tdir), max(0.0, seconds - TRACE_S),
            lambda: jax.block_until_ready(server.sched.pool.cache))
    window = harness.run_window(server, planned, seconds, profiler)
    counts1 = server.runtime.compile_counts()
    stats = devs[0].memory_stats() or {}
    tracks = list(window.tracks.values())
    failed = (sum(not t.token_times for t in tracks)
              if cell.traffic["loop"] == "open"
              else sum(t.done and not t.tokens for t in tracks))
    log(f"window {window.seconds:.3f} s | {len(tracks)} requests, "
        f"{sum(t.done for t in tracks)} finished, drained "
        f"{window.drained_at is not None} | counters {window.counters}")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    extra = {}
    if traced:
        trace = TR.load(str(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        log("trace: " + json.dumps(TR.describe(trace)))
        ctx = harness.context(server, window, setup_s, profiler, trace)
        metrics = harness.read_metrics(cell.per_layer, ctx)
        device["busy_s"] = TR.busy_s(trace)
        device["window_s"] = profiler.seconds
        extra["breakdown"] = {"device_ops": TR.top_ops(trace),
                              "idle_gaps": TR.idle_gaps(trace)}
    else:
        ctx = harness.context(server, window, setup_s)
        metrics = harness.read_metrics(cell.end_to_end, ctx)

    kernels, counts0 = server.kernels, server.counts0
    server.close()
    del ctx
    want = cell.limits["sample"]
    picked = check.sample(tracks, seed, want["min_tokens"],
                          want["max_requests"], want["max_positions"])
    t0 = time.perf_counter()
    gap = check.served_gap(cell.config, seed, picked)
    log(f"reference: {gap['n_requests']} requests, "
        f"{time.perf_counter() - t0:.3f} s | not compared: " + json.dumps(
            {k: v for k, v in gap.items()
             if k not in ("ref_logits", "max_logit_gap")}))
    expected = (cell.config["serving"]["kernels"]
                if devs[0].platform == "tpu" else {})
    ok, checks = checks_of(cell.limits, gap, counts0, counts1, kernels,
                           expected)
    for name, c in checks.items():
        bound = (f"<= {c['limit']}" if "limit" in c
                 else f">= {c['at_least']}")
        log(f"check {name} {c['value']} {bound}")
    return {"correct": bool(ok), "attempted": len(tracks),
            "failed": int(failed), "metrics": metrics, "device": device,
            **extra, "checks": checks}


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args()
    from bench import spec
    cell = spec.load_cell(args.workload)
    jax = configure_jax()
    devs = device_or_exit(jax, cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
