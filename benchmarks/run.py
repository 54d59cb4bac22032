"""Benchmark harness — one module per paper table/figure.

Prints ``name,value,derived`` CSV rows per benchmark (harness contract).

  flops_crossover   paper §2.3, Fig. 1-2 (FFN/attention crossover)
  prefill_speedup   paper Fig. 6-7 (compute-bound speedup)
  ttft              paper Fig. 1 (measured TTFT, dense vs sparse)
  fidelity_proxy    paper Table 2-3 (quality vs sparsity)
  ablations         paper Tables 4-7 (schedule/blocks/comp/predictor)
  roofline          ours: dry-run roofline summary (§Roofline)
  continuous_batching  ours: continuous vs static batching under
                       staggered arrivals (serving runtime)
"""
from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from benchmarks import (flops_crossover, prefill_speedup, ttft,
                            fidelity_proxy, ablations, roofline,
                            continuous_batching)
    suites = [
        ("flops_crossover", flops_crossover),
        ("prefill_speedup", prefill_speedup),
        ("ttft", ttft),
        ("fidelity_proxy", fidelity_proxy),
        ("ablations", ablations),
        ("roofline", roofline),
        ("continuous_batching", continuous_batching),
    ]
    failures = 0
    for name, mod in suites:
        print(f"# --- {name} ---", flush=True)
        t0 = time.time()
        try:
            mod.run(csv=True)
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"# {name} FAILED", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
