"""What the benchmark needs to know of one architecture, one module each:
bench/archs/<arch>.py, found by the configuration file's `arch` key. A
configuration of a new architecture enters as new files: this module,
its plain reference (bench/references), its configuration, traffic and
limits, and entries in BENCHMARK.json. The generic code (spec, weights,
flops, harness, the metric readers) holds no key or leaf path of any
one architecture.

A module defines:

  model_config(config) -> repro ModelConfig
      every number as the configuration file states it; refuses a file
      the architecture's runtime cannot serve as stated.
  kv_bytes_per_token(config) -> int
      the bytes the program's KV pool holds for one token position,
      summed over layers: per layer kind, or a latent width, as the
      architecture caches it.
  RULES: {leaf path: rule}
      one rule per leaf of the program's parameter tree, the path
      inside one layer for stacked "layers/..." leaves. A rule is
      `rule(root, path, shape, layer) -> float32 array` of the
      per-layer shape (bench/weights.py: `ones`, `normal`, the
      FastForward tile weighting `tile_weighted` / `tile_ranked` on
      the leaves the module names, or the module's own draw, such as
      one key per expert, so that a chip's share of the experts holds
      the numbers those experts hold whole).
  Shapes(config)
      the sizes the work counts read; `L` is the number of layers, each
      of which runs every prefill kernel once per call, in layer order.
  prefill_row_flops(shapes, pos0, n, dense, final) -> float
      model operations of one prefill row (bench/flops.py's terms):
      every layer, router and routed experts included where the
      architecture has them, plus the output head on a final block.
  sparse_ffn_kernel_work(shapes, rows, on_chip, layer) -> (ops, bytes)
  attn_prefill_kernel_work(shapes, rows, on_chip, layer) -> (ops, bytes)
      the least work of one execution of the prefill kernels
      `sparse_ffn_batched` and `flash_attention_rows` in layer `layer`,
      which may differ by layer kind (a leading dense layer's FFN, a
      windowed layer's keys).
"""
from __future__ import annotations

import importlib


def module(config: dict):
    """The architecture module of a configuration file."""
    arch = config["arch"]
    name = f"{__name__}.{arch}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ValueError(
            f"{config['name']}: no benchmark module for arch {arch!r}: add "
            f"bench/archs/{arch}.py (the interface is in "
            "bench/archs/__init__.py)") from None
