"""The one request generator: it reads a traffic file and the seed.

A traffic file is data only:

  {"loop": "open", "rate_per_s": 1.2,            # Poisson arrivals
   "prompt": {"dist": "loguniform", "min": 1024, "max": 8192},
   "output": {"dist": "uniform", "min": 16, "max": 64},
   "drain_cap_s": 60}
  {"loop": "closed", "clients": 12, ...}         # a client sends its next
                                                 # request when one ends

Every seed gets the same requests at the same times: prompt lengths,
output lengths and inter-arrival gaps are stratified draws, one per
equal-probability stratum of each distribution, in one fixed shuffled
order (`ORDER_SEED`). The seed draws the tokens (and the weights), not
the work: in an open loop near its knee the order of long and short
prompts decides the queueing, and with the order drawn from the seed
the TTFT median of a 51 s window moved by 74% between seeds while two
runs of one seed agreed within 0.2% (PERF.md).
"""
from __future__ import annotations

import dataclasses

import numpy as np

#: a closed loop's requests come in stratified blocks of this many, so
#: that any prefix of them holds the mix's proportions
STRATUM_BLOCK = 64
#: the one order of lengths and gaps, whatever the seed
ORDER_SEED = 20260217


@dataclasses.dataclass
class Planned:
    rid: int
    prompt: list
    max_new: int
    due_s: float          # offset from the window's start (open loop)


def _quantiles(dist: dict, u: np.ndarray) -> np.ndarray:
    lo, hi = float(dist["min"]), float(dist["max"])
    if dist["dist"] == "uniform":
        x = lo + u * (hi - lo + 1)           # integers lo..hi, inclusive
        return np.clip(np.floor(x), lo, hi).astype(np.int64)
    if dist["dist"] == "loguniform":
        x = np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
        return np.clip(np.round(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def stratified(dist: dict, n: int, rng) -> np.ndarray:
    """n draws, one per stratum of probability 1/n, shuffled."""
    u = (np.arange(n) + 0.5) / n
    return rng.permutation(_quantiles(dist, u))


def plan(traffic: dict, vocab: int, seed: int, seconds: float):
    """The run's requests, in the order they are sent: for an open loop
    the list of every arrival due in the window; for a closed loop an
    endless iterator, since its clients send as fast as they are
    served."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(ORDER_SEED)
    if traffic["loop"] == "closed":
        return _closed(traffic, vocab, rng, order)
    if traffic["loop"] != "open":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    n = max(1, int(traffic["rate_per_s"] * seconds))
    prompts = stratified(traffic["prompt"], n, order)
    outs = stratified(traffic["output"], n, order)
    u = (np.arange(n) + 0.5) / n
    gaps = order.permutation(-np.log1p(-u) / traffic["rate_per_s"])
    # n gaps span about n / rate; scale them onto the window so the last
    # arrival is due inside it
    dues = np.cumsum(gaps) * min(1.0, seconds / max(gaps.sum(), 1e-9))
    dues = dues - dues[0]
    return [Planned(rid=i,
                    prompt=rng.integers(0, vocab, size=int(p)).tolist(),
                    max_new=int(m), due_s=float(d))
            for i, (p, m, d) in enumerate(zip(prompts, outs, dues))]


def _closed(traffic: dict, vocab: int, rng, order):
    rid = 0
    while True:
        prompts = stratified(traffic["prompt"], STRATUM_BLOCK, order)
        outs = stratified(traffic["output"], STRATUM_BLOCK, order)
        for p, m in zip(prompts, outs):
            yield Planned(rid=rid,
                          prompt=rng.integers(0, vocab, size=int(p)).tolist(),
                          max_new=int(m), due_s=0.0)
            rid += 1
