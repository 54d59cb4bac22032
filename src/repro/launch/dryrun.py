"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production mesh; report memory analysis, HLO cost analysis, and
collective bytes for the roofline (EXPERIMENTS.md §Dry-run / §Roofline).

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k
  python -m repro.launch.dryrun --all --out results.jsonl
  python -m repro.launch.dryrun --all --multi-pod --out results_mp.jsonl
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the
# device count on first initialization). --xla_force_host_platform_
# device_count is dry-run-only: tests and benches see 1 device.

import argparse
import json
import re
import sys
import time
import traceback

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED, ALL, get_config
from repro.models.registry import get_model
from repro.nn import param as PM
from repro.distributed.sharding import param_shardings
from repro.training.optimizer import opt_state_specs
from repro.training.train import make_loss_fn
from repro.training.optimizer import make_optimizer
from repro.launch.mesh import (PRODUCTION_DEVICE_KIND,
                               make_production_mesh, peaks)
from repro.launch import shapes as SH

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {"pred": 0.125, "s4": 0.5, "s8": 1, "u8": 1, "s16": 2,
                "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "f32": 4, "f64": 8, "u64": 8, "s64": 8, "c64": 8,
                "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _type_bytes(dtype: str, dims: str) -> float:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str):
    """Sum operand bytes of every collective op in the (SPMD, per-device)
    HLO module. Returns {op_kind: bytes} + total."""
    out = {k: 0.0 for k in COLLECTIVES}
    counts = {k: 0 for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for kind in COLLECTIVES:
            # match op invocations like: "... = bf16[..] all-gather(bf16[..] %x)"
            marker = f" {kind}("
            alt = f" {kind}-start("
            if marker in stripped or alt in stripped:
                idx = stripped.index(marker if marker in stripped else alt)
                operands = stripped[idx:]
                types = _SHAPE_RE.findall(operands)
                b = sum(_type_bytes(t, d) for t, d in types)
                out[kind] += b
                counts[kind] += 1
                break
    total = sum(out.values())
    return out, counts, total


def model_flops(cfg, shape: SH.ShapeSpec) -> float:
    """6*N_active*D for training, 2*N_active*D for inference (global)."""
    model = get_model(cfg)
    n_params = PM.count_params(model.specs(cfg))
    if cfg.arch == "moe":
        # active params: replace full expert count with top_k (+shared)
        e, k = cfg.n_experts, cfg.top_k
        expert_p = 3 * cfg.d_model * cfg.d_ff_expert * cfg.n_layers
        n_params = n_params - e * expert_p + k * expert_p
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_params * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_params * tokens
    return 2.0 * n_params * shape.global_batch  # decode: 1 token/seq


VARIANTS = {
    "baseline": lambda cfg: cfg,
    # §Perf iteration 1: online-softmax chunked attention (train)
    "opt_attn_chunk": lambda cfg: cfg.with_(attn_chunk=512),
    # §Perf iteration 2: shard_map tile-sparse FFN (prefill)
    "opt_shardmap_ffn": lambda cfg: cfg.with_(shardmap_ffn=True),
    # §Perf iteration 3 (beyond-paper): fused parallel-block prefill
    "opt_fused_prefill": lambda cfg: cfg.with_(fused_prefill=True,
                                               attn_chunk=512),
    "opt_fused_shardmap": lambda cfg: cfg.with_(fused_prefill=True,
                                                attn_chunk=512,
                                                shardmap_ffn=True),
    # §Perf (beyond-paper): shard_map flash-decode over seq-sharded KV
    "opt_flash_decode": lambda cfg: _with_flag(cfg, "_flash_decode"),
    "opt_microbatch4": lambda cfg: _with_micro(cfg, 4),
    "opt_microbatch16": lambda cfg: _with_micro(cfg, 16),
    "opt_micro16_chunk": lambda cfg: _with_micro(
        cfg.with_(attn_chunk=512), 16),
}


def _with_micro(cfg, n):
    object.__setattr__(cfg, "_n_microbatches", n)  # frozen dataclass aux
    return cfg


def _with_flag(cfg, name):
    object.__setattr__(cfg, name, True)
    return cfg


def build_lowering(cfg, shape_name: str, mesh, fused_prefill: bool = False):
    """Returns (lowered, meta) for the (arch, shape) pair on mesh."""
    shape = SH.SHAPES[shape_name]
    model = get_model(cfg)
    shards = mesh.shape.get("model", 1)
    expert_axis = "data" if cfg.arch == "moe" else None

    specs = model.specs(cfg)
    pshard = param_shardings(specs, mesh, expert_axis=expert_axis)
    aparams = PM.abstract_params(specs, pshard)

    if shape.kind == "train":
        loss_fn = make_loss_fn(cfg)
        _, opt_update = make_optimizer(cfg.optimizer, 1e-4)
        ospecs = opt_state_specs(specs, cfg.optimizer)
        oshard = param_shardings(ospecs, mesh, expert_axis=expert_axis)
        aopt = PM.abstract_params(ospecs, oshard)
        astep = jax.ShapeDtypeStruct((), jnp.int32)
        abatch = SH.batch_specs(cfg, shape, mesh)

        n_micro = getattr(cfg, "_n_microbatches", 1)

        def grads_of(params, batch):
            if n_micro <= 1:
                return jax.value_and_grad(loss_fn, has_aux=True)(params,
                                                                 batch)
            # §Perf: gradient accumulation — peak activation memory
            # scales with the microbatch, not the global batch.
            micro = jax.tree.map(
                lambda a: a.reshape((n_micro, a.shape[0] // n_micro)
                                    + a.shape[1:]), batch)

            def acc(carry, mb):
                g_sum, l_sum = carry
                (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mb)
                return (jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_sum, g),
                    l_sum + l), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (g, l), _ = jax.lax.scan(acc, (g0, jnp.float32(0)), micro)
            scale = 1.0 / n_micro
            g = jax.tree.map(lambda x: x * scale, g)
            return (l * scale, {"loss": l * scale}), g

        def step(state, batch):
            (loss, metrics), grads = grads_of(state["params"], batch)
            params, opt = opt_update(state["params"], grads, state["opt"],
                                     state["step"])
            return ({"params": params, "opt": opt,
                     "step": state["step"] + 1}, metrics)

        astate = {"params": aparams, "opt": aopt, "step": astep}
        out_sh = ({"params": pshard, "opt": oshard,
                   "step": None}, None)
        with jax.sharding.set_mesh(mesh):
            lowered = jax.jit(step).lower(astate, abatch)
        return lowered, {"shape": shape}

    if shape.kind == "prefill":
        abatch = SH.batch_specs(cfg, shape, mesh)
        acache = SH.cache_abstract(cfg, shape, mesh)
        use_fused = cfg.fused_prefill and cfg.arch == "dense"
        kw = {}
        if cfg.shardmap_ffn and cfg.arch in ("dense", "vlm"):
            kw["mesh"] = mesh

        def step(params, batch, cache):
            fn = model.prefill_fused if use_fused else model.prefill
            return fn(params, cfg, batch, cache, shards=shards, **kw)

        with jax.sharding.set_mesh(mesh):
            lowered = jax.jit(step).lower(aparams, abatch, acache)
        return lowered, {"shape": shape}

    # decode
    acache = SH.cache_abstract(cfg, shape, mesh)
    tok = SH.token_specs_decode(cfg, shape, mesh)
    window = cfg.decode_window(shape.seq_len) or None
    if cfg.arch == "ssm":
        window = None

    # flash-decode covers the non-ring case (full-context cache, i.e.
    # window None or == seq_len); the ring-buffer path keeps the baseline.
    use_flash = (getattr(cfg, "_flash_decode", False)
                 and cfg.arch == "dense"
                 and (not window or window == shape.seq_len))

    def step(params, token, cache, position):
        if use_flash:
            from repro.distributed.decode import decode_step_seqsharded
            return decode_step_seqsharded(params, cfg, token, cache,
                                          position, mesh, shards=shards)
        return model.decode_step(params, cfg, token, cache, position,
                                 shards=shards, window=window)

    with jax.sharding.set_mesh(mesh):
        lowered = jax.jit(step).lower(aparams, tok["token"], acache,
                                      tok["position"])
    return lowered, {"shape": shape, "window": window}


def analyse(lowered, cfg, shape_name: str, mesh, compile_seconds=None):
    from repro.launch.hlo_analysis import analyze_hlo
    shape = SH.SHAPES[shape_name]
    compiled = lowered.compile()
    chips = int(np.prod(list(mesh.shape.values())))
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    # XLA's cost_analysis counts while bodies ONCE; analyze_hlo scales by
    # known_trip_count and derives dot flops / collective payload bytes
    # from the per-device SPMD module (see hlo_analysis.py).
    hm = analyze_hlo(text)
    flops_dev = hm.flops
    bytes_dev = hm.traffic_bytes
    coll_by_kind = hm.collective_bytes
    coll_counts = hm.collective_counts
    coll_dev = hm.collective_total

    peak = peaks(PRODUCTION_DEVICE_KIND)
    compute_term = flops_dev / peak["flops_bf16"]
    memory_term = bytes_dev / peak["hbm_bytes_s"]
    collective_term = coll_dev / peak["ici_bytes_s_per_link"]
    terms = {"compute": compute_term, "memory": memory_term,
             "collective": collective_term}
    bottleneck = max(terms, key=terms.get)
    mflops = model_flops(cfg, shape)
    hlo_flops_global = flops_dev * chips

    rec = {
        "arch": cfg.name,
        "shape": shape_name,
        "mesh": "x".join(str(v) for v in mesh.shape.values()),
        "chips": chips,
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll_dev,
        "collective_by_kind": coll_by_kind,
        "collective_counts": coll_counts,
        "arg_bytes_per_device": mem.argument_size_in_bytes,
        "out_bytes_per_device": mem.output_size_in_bytes,
        "temp_bytes_per_device": mem.temp_size_in_bytes,
        "peak_bytes_per_device": (mem.argument_size_in_bytes
                                  + mem.output_size_in_bytes
                                  + mem.temp_size_in_bytes),
        "compute_term_s": compute_term,
        "memory_term_s": memory_term,
        "collective_term_s": collective_term,
        "bottleneck": bottleneck,
        "model_flops_global": mflops,
        "hlo_flops_global": hlo_flops_global,
        "useful_flops_ratio": mflops / hlo_flops_global if hlo_flops_global else 0.0,
        "compile_seconds": compile_seconds,
    }
    return rec, compiled


def run_one(arch: str, shape_name: str, multi_pod: bool,
            variant: str = "baseline"):
    cfg = VARIANTS[variant](get_config(arch))
    shape = SH.SHAPES[shape_name]
    skip = SH.shape_supported(cfg, shape)
    if skip:
        return {"arch": arch, "shape": shape_name, "skipped": skip}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    lowered, _ = build_lowering(cfg, shape_name, mesh)
    t1 = time.time()
    rec, compiled = analyse(lowered, cfg, shape_name, mesh)
    rec["variant"] = variant
    rec["lower_seconds"] = t1 - t0
    rec["compile_seconds"] = time.time() - t1
    return rec


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=ALL)
    p.add_argument("--shape", choices=list(SH.SHAPES))
    p.add_argument("--all", action="store_true")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--variant", default="baseline", choices=list(VARIANTS))
    p.add_argument("--out", default=None)
    args = p.parse_args()

    pairs = []
    if args.all:
        for a in ASSIGNED:
            for s in SH.SHAPES:
                pairs.append((a, s))
    else:
        assert args.arch and args.shape
        pairs = [(args.arch, args.shape)]

    out_f = open(args.out, "a") if args.out else None
    n_fail = 0
    for arch, shape_name in pairs:
        try:
            rec = run_one(arch, shape_name, args.multi_pod, args.variant)
            status = rec.get("skipped") and "SKIP" or "OK"
        except Exception as e:  # noqa: BLE001 — report and continue
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape_name,
                   "error": f"{type(e).__name__}: {e}"}
            status = "FAIL"
            n_fail += 1
        mesh_tag = "2x16x16" if args.multi_pod else "16x16"
        rec["mesh"] = rec.get("mesh", mesh_tag)
        print(f"[{status}] {arch:24s} {shape_name:12s} mesh={mesh_tag} "
              + (f"bottleneck={rec.get('bottleneck')} "
                 f"peakMB={rec.get('peak_bytes_per_device', 0)/1e6:.0f} "
                 f"compile={rec.get('compile_seconds', 0):.0f}s"
                 if status == "OK" else rec.get("skipped", rec.get("error", ""))),
              flush=True)
        if out_f:
            out_f.write(json.dumps(rec) + "\n")
            out_f.flush()
    if out_f:
        out_f.close()
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
