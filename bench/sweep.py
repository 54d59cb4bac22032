#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: the same set-up, windows at several
offered rates, in one process on the chip.

  python3 bench/sweep.py --workload <name> --rates 0.5,0.8,1.1 \
      --seconds 30 --seed <n> [--out <file.jsonl>]

Each rate gets fresh weights and an empty pool from the same seed and
runs the cell's traffic with only `rate_per_s` changed. One JSON line
per rate: offered rate, requests due and answered, the TTFT and
inter-token tails, prompt tokens per second, and how many requests were
still waiting when the window closed (a backlog that grows with the
rate is past the knee). The rate chosen goes into the traffic file by
hand, as a number.
"""
import argparse
import dataclasses
import json
import math
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
#: past the knee the backlog only grows: drain each window this long at
#: most, and count what is still unanswered as infinitely late
DRAIN_CAP_S = 15.0


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--out", default=None)
    args = p.parse_args()

    from bench import run as R
    from bench import spec
    cell = spec.load_cell(args.workload)
    jax = R.configure_jax()
    R.device_or_exit(jax, cell.chips)
    from bench import harness, stats, traffic
    server = harness.setup(cell, args.seed, check_kernels=False)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        harness.reseed(server, args.seed)
        t = dict(cell.traffic, rate_per_s=rate, drain_cap_s=DRAIN_CAP_S)
        server.cell = dataclasses.replace(cell, traffic=t)
        planned = traffic.plan(t, cell.config["vocab_size"], args.seed,
                               args.seconds)
        win = harness.run_window(server, planned, args.seconds)
        ttft = stats.ttfts_s(win)
        itl = stats.itl_s(win)
        waiting = sum(1 for tr in win.tracks.values()
                      if not tr.token_times or tr.token_times[0] > win.t_end)
        row = {
            "rate_per_s": rate, "due": len(win.tracks),
            "answered": sum(math.isfinite(x) for x in ttft),
            "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50),
            "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
            "itl_p50_ms": 1e3 * stats.percentile(itl, 50) if itl else None,
            "itl_p95_ms": 1e3 * stats.percentile(itl, 95) if itl else None,
            "prompt_tok_s": sum(r[1] for c in win.calls
                                if c.kind == "prefill" for r in c.rows)
            / win.seconds,
            "waiting_at_close": waiting,
            "drained": win.drained_at is not None}
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in rows)


if __name__ == "__main__":
    main()
