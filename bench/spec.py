"""What one cell is: its entry in BENCHMARK.json, its configuration file,
its traffic file and its limits file, each found by name, and the
deployment sizes derived from them.

Nothing here is specific to one configuration, one architecture or one
traffic mix: a new cell is new files and a new entry in BENCHMARK.json.
What depends on the architecture is in bench/archs/<arch>.py, found by
the configuration's `arch` key; a cell whose `arch` has no module there
fails when it loads.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

from bench import archs

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple     # metric entries of BENCHMARK.json this cell reports
    per_layer: tuple


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark_path=None) -> Cell:
    bench = load_json(benchmark_path or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    archs.module(config)      # an unknown arch fails here, naming its file
    traffic = load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    limits = load_json(BENCH_DIR / "limits" / f"{name}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        limits=limits,
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if _reports(m, name)))


def model_config(config: dict):
    """The program's ModelConfig for a configuration file, as its
    architecture module (bench/archs) reads it."""
    return archs.module(config).model_config(config)


@dataclasses.dataclass(frozen=True)
class Sizes:
    cache_len: int        # positions one request may hold
    n_slots: int          # decode batch (slot rows or page tables)
    prefill_batch: int    # P, rows of one batched prefill call
    n_pages: int          # paged layout: heap pages incl. the null page
    page_size: int


def sizes(config: dict, traffic: dict) -> Sizes:
    """The deployment's pool for this traffic: the longest request the
    mix can draw sets cache_len; the configuration's KV budget and
    largest decode batch set the rest. Slot layout: each slot holds a
    whole cache_len row, and a batched prefill gathers P more rows.
    Paged layout: the budget is whole pages of one prefill block."""
    s = config["serving"]
    N = config["fastforward"]["block_size"]
    longest_prompt = traffic["prompt"]["max"]
    longest_out = traffic["output"]["max"]
    cache_len = -(-longest_prompt // N) * N + max(longest_out, 2)
    per_tok = archs.module(config).kv_bytes_per_token(config)
    budget = s["kv_budget_bytes"]
    P = s["max_prefill_batch"]
    if s["kv_layout"] == "slot":
        rows = budget // (cache_len * per_tok)
        n_slots = min(s["max_decode_batch"], rows - P)
        if n_slots < P:
            raise ValueError(f"KV budget {budget} B holds {rows} rows of "
                             f"{cache_len} tokens: too few for P={P}")
        return Sizes(cache_len, n_slots, P, 0, 0)
    n_pages = budget // (N * per_tok) + 1
    return Sizes(cache_len, s["max_decode_batch"], P, n_pages, N)
