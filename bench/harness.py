"""Set-up, window and readings of one cell, shared by `run.py` (one run,
the driver's contract) and the tools that run many seeds in one process
(`calibrate.py`, `sweep.py`)."""
from __future__ import annotations

import dataclasses
import gc
import importlib
import re
import sys
from typing import Optional

import jax
import jax.numpy as jnp

from bench import archs, loop, spec, weights
from bench import trace as TR
from bench.peaks import peaks


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def kernel_calls(runtime, sched) -> dict:
    """Names of the Pallas kernels (`tpu_custom_call`s) in the compiled
    batched-prefill and decode programs at the shapes the window runs
    (the widest prefill bucket)."""
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


@dataclasses.dataclass
class Server:
    cell: spec.Cell
    cfg: object
    sizes: spec.Sizes
    runtime: object
    sched: object
    rec: loop.Recorder
    counts0: dict
    kernels: dict

    def close(self):
        """Drop every device buffer the program holds."""
        self.runtime.params = None
        self.sched = None
        self.runtime = None
        self.rec = None
        gc.collect()


def _new_scheduler(cfg, sz, runtime, seed):
    from repro.serving import ContinuousBatchingScheduler
    return ContinuousBatchingScheduler(
        runtime, n_slots=sz.n_slots, cache_len=sz.cache_len, seed=seed,
        prefill_batch=sz.prefill_batch, page_size=sz.page_size or None,
        n_pages=sz.n_pages or None)


def setup(cell: spec.Cell, seed: int, check_kernels: bool = True) -> Server:
    """Weights from the seed, the runtime, its scheduler and pool, and
    warmup, which compiles (or loads) every program the window runs."""
    from repro.serving.runtime import make_runtime
    cfg = spec.model_config(cell.config)
    sz = spec.sizes(cell.config, cell.traffic)
    params = weights.make_params(cell.config, seed)
    runtime = make_runtime(cfg, params)
    rec = loop.Recorder(runtime, cfg.ff.block_size)
    sched = _new_scheduler(cfg, sz, runtime, seed)
    counts0 = sched.warmup()
    rec.calls.clear()
    kernels = kernel_calls(runtime, sched) if check_kernels else {}
    return Server(cell, cfg, sz, runtime, sched, rec, counts0, kernels)


def reseed(server: Server, seed: int) -> None:
    """New weights and a fresh pool for another seed, on the programs
    already compiled."""
    server.runtime.params = None
    server.sched = None
    gc.collect()
    server.runtime.params = weights.make_params(server.cell.config, seed)
    server.sched = _new_scheduler(server.cfg, server.sizes, server.runtime,
                                  seed)
    server.sched.warmup()
    server.rec.calls.clear()


def run_window(server: Server, planned, seconds: float,
               profiler: Optional[TR.Profiler] = None) -> loop.Window:
    t = server.cell.traffic
    if t["loop"] == "open":
        return loop.open_loop(server.sched, server.rec, planned, seconds,
                              t["drain_cap_s"], profiler)
    return loop.closed_loop(server.sched, server.rec, planned, seconds,
                            t["clients"], profiler)


@dataclasses.dataclass
class Ctx:
    """What a metric reader may read. `arch` is the configuration's
    bench/archs module, `shapes` its Shapes."""
    config: dict
    traffic: dict
    arch: object
    shapes: object
    window: loop.Window
    peak: dict
    setup_s: float
    trace: Optional[TR.Trace] = None
    trace_s: Optional[float] = None
    traced_calls: Optional[list] = None


def context(server: Server, window, setup_s, profiler=None, trace=None):
    kind = jax.devices()[0].device_kind
    config = server.cell.config
    arch = archs.module(config)
    ctx = Ctx(config, server.cell.traffic, arch, arch.Shapes(config),
              window, peaks(kind), setup_s)
    if trace is not None:
        ctx.trace, ctx.trace_s = trace, profiler.seconds
        ctx.traced_calls = [c for c in window.calls
                            if profiler.t_start <= c.t <= profiler.t_stop]
    return ctx


def read_metrics(entries, ctx) -> dict:
    """Each metric's reader is bench/metrics/<name>.py; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        mod = importlib.import_module(f"bench.metrics.{m['name']}")
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
