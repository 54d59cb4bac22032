#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

  python3 bench/calibrate.py --workload <name> --seeds 11,12,... \
      --control-seeds 11,12,13 --seconds 20 [--out <file.jsonl>]

One process, one set-up. For every seed: fresh weights and an empty
pool, a window of the cell's own traffic and load, the sample a run
checks, and the gaps of the served tokens against the reference (the
program's readings: mean, widest, share off the reference's choice).
For the control seeds also the control: the reference in fp8 at the
same positions, the gaps of the tokens it puts first, and whether
run.py's check (`checks_of`, the cell's limits) passes it: it must
not. Neither the recompile nor the kernel check is read here. The
limit in bench/limits/<cell>.json is set between the largest program
reading and the smallest control reading (PERF.md).
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", default=None)
    p.add_argument("--dump", default=None,
                   help="directory for per-token readings, one .npz a seed")
    args = p.parse_args()

    from bench import run as R
    from bench import spec
    cell = spec.load_cell(args.workload)
    jax = R.configure_jax()
    R.device_or_exit(jax, cell.chips)
    from bench import check, harness, traffic
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    want = cell.limits["sample"]
    server = harness.setup(cell, seeds[0], check_kernels=False)
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        harness.reseed(server, seed)
        planned = traffic.plan(cell.traffic, cell.config["vocab_size"],
                               seed, args.seconds)
        win = harness.run_window(server, planned, args.seconds)
        picked = check.sample(list(win.tracks.values()), seed,
                              want["min_tokens"], want["max_requests"],
                              want["max_positions"])
        # the reference runs with the program's weights and pool freed
        server.runtime.params = None
        server.sched = None
        served = check.served_gap(cell.config, seed, picked)
        row = {"seed": seed, "requests": served["n_requests"],
               "program": {k: served[k] for k in check.summary([])}}
        row["correct"] = R.checks_of(cell.limits, served, {}, {}, {},
                                     {})[0]
        per_token = {}
        if seed in controls:
            control = check.control_gap(cell.config, seed, picked,
                                        served["ref_logits"])
            witness = check.control_gap(cell.config, seed, picked,
                                        served["ref_logits"], "bf16")
            per_token.update(control=control.pop("gaps"),
                             bf16=witness.pop("gaps"))
            row["control"], row["bf16"] = control, witness
            # the control in the program's place, through run.py's check
            row["control_correct"] = R.checks_of(
                cell.limits, row["control"], {}, {}, {}, {})[0]
        if args.dump:
            import numpy as np
            top2 = [np.sort(r, axis=-1)[:, -2:] for r in
                    served["ref_logits"]]
            per_token.update(
                program=check.gaps(served["ref_logits"],
                                   [np.asarray(t.tokens) for t in picked]),
                margin=[t[:, 1] - t[:, 0] for t in top2])
            pathlib.Path(args.dump).mkdir(parents=True, exist_ok=True)
            np.savez(pathlib.Path(args.dump) / f"{seed}.npz",
                     **{f"{k}_{i}": a for k, v in per_token.items()
                        for i, a in enumerate(v)})
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.writelines(json.dumps(r) + "\n" for r in rows)


if __name__ == "__main__":
    main()
