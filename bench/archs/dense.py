"""The "dense" architecture: a pre-norm decoder with RMSNorm, grouped-
query attention with RoPE and no bias, an untied head, and a SwiGLU FFN
that FastForward makes sparse. The module's interface is in
bench/archs/__init__.py; operations and bytes follow bench/flops.py's
terms."""
from __future__ import annotations

import math

from bench import weights as W


def model_config(config: dict):
    """The program's ModelConfig for a configuration file: every number
    as the file states it."""
    from repro.models.base import FastForwardConfig, ModelConfig
    ff = config["fastforward"]
    if config["hidden_act"] != "silu" or config["attention_bias"] \
            or config["tie_word_embeddings"]:
        raise ValueError(f"{config['name']}: the dense runtime serves "
                         "untied silu SwiGLU without attention bias")
    return ModelConfig(
        name=config["name"], arch=config["arch"],
        n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_head=config["head_dim"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        rope_theta=config["rope_theta"], act="silu", gated=True,
        qkv_bias=False, kv_layout=config["serving"]["kv_layout"],
        param_dtype=config["torch_dtype"], remat=False,
        source=config["source"],
        ff=FastForwardConfig(
            enabled=ff["enabled"], sparsity=ff["sparsity"],
            block_size=ff["block_size"], tile=ff["tile"],
            predictor_dim=ff["predictor_dim"],
            compensator_dim=ff["compensator_dim"],
            dense_first_block=ff["dense_first_block"],
            dense_last_block=ff["dense_last_block"],
            apply_to_decode=ff["apply_to_decode"],
            use_compensator=ff["use_compensator"]))


def kv_bytes_per_token(config: dict) -> int:
    """K and V of every layer, in the served dtype (bf16: 2 bytes)."""
    return (2 * config["num_hidden_layers"] * config["num_key_value_heads"]
            * config["head_dim"] * 2)


#: key path of each layer's FFN tile importances
TILES = "layers/ffn/tiles"

RULES = {
    "embed/table": W.normal((), 1.0),
    "lm_head/table": W.normal((1,), 1.0),
    "ln_f/scale": W.ones,
    "layers/ln1/scale": W.ones,
    "layers/ln2/scale": W.ones,
    # q.k / sqrt(dh) has std gain**2: ~2.25 keeps softmax focused on a
    # handful of keys without making it one-hot
    "layers/attn/wq": W.normal((0,), 1.5),
    "layers/attn/wk": W.normal((0,), 1.5),
    "layers/attn/wv": W.normal((0,), 1.0),
    "layers/attn/wo": W.normal((0, 1), 1.0),
    "layers/ffn/wg": W.normal((0,), 1.0),
    "layers/ffn/wu": W.normal((0,), 1.0),
    "layers/ffn/wd": W.tile_weighted(TILES, (0,), 1.0),
    # predictor logits over a block's tokens have std 1 (Eq. 12)
    "layers/ffn/pred/q_pred": W.normal((), 1.0),
    "layers/ffn/pred/w1": W.normal((0,), 1.0),
    "layers/ffn/pred/w2": W.tile_ranked(TILES, (0,), 1.0),
    "layers/ffn/comp/w1": W.normal((0,), 1.0),
    "layers/ffn/comp/w2": W.normal((0,), 0.5),
}


class Shapes:
    def __init__(self, config: dict):
        ff = config["fastforward"]
        self.L = config["num_hidden_layers"]
        self.D = config["hidden_size"]
        self.F = config["intermediate_size"]
        self.H = config["num_attention_heads"]
        self.Kv = config["num_key_value_heads"]
        self.dh = config["head_dim"]
        self.V = config["vocab_size"]
        self.N = ff["block_size"]
        self.tile = ff["tile"]
        self.r = ff["predictor_dim"]
        self.rc = ff["compensator_dim"]
        n_tiles = self.F // self.tile
        # tiles a sparse row keeps: ceil((1 - sparsity) * n_tiles), >= 1
        self.k_tiles = min(max(math.ceil((1 - ff["sparsity"]) * n_tiles),
                               1), n_tiles)
        self.act_bytes = 2          # bf16 activations, weights and KV


def attn_causal_flops(s: Shapes, pos0: int, n: int) -> float:
    """QK^T and PV of n queries at positions pos0..pos0+n-1, each over
    the keys 0..its own position (one layer)."""
    keys = n * pos0 + n * (n + 1) // 2
    return 4.0 * s.H * s.dh * keys


def proj_flops(s: Shapes, n: int) -> float:
    """q, k, v and output projections of n tokens (one layer)."""
    return 2.0 * n * s.D * (2 * s.H * s.dh + 2 * s.Kv * s.dh)


def sparse_ffn_flops(s: Shapes, n: int) -> float:
    """Gate, up and down over the kept tiles only (one layer)."""
    return 6.0 * n * s.D * s.k_tiles * s.tile


def predictor_flops(s: Shapes, n: int) -> float:
    """Attention pooling over n tokens, then D->r->F (one unit)."""
    return 4.0 * n * s.D + 2.0 * s.D * s.r + 2.0 * s.r * s.F


def compensator_flops(s: Shapes, n: int) -> float:
    return 4.0 * n * s.D * s.rc


def prefill_row_flops(s: Shapes, pos0: int, n: int, dense: bool,
                      final: bool) -> float:
    """Model operations of one prefill row: every layer, plus the
    output head when this is the row's final block (one position)."""
    if dense:
        ffn = 6.0 * n * s.D * s.F
    else:
        ffn = (sparse_ffn_flops(s, n) + predictor_flops(s, n)
               + compensator_flops(s, n))
    per_layer = proj_flops(s, n) + attn_causal_flops(s, pos0, n) + ffn
    return s.L * per_layer + (2.0 * s.D * s.V if final else 0.0)


def sparse_ffn_kernel_work(s: Shapes, rows, on_chip=frozenset(),
                           layer: int = 0) -> tuple:
    """(operations, least bytes) of one `sparse_ffn_batched` prefill
    call for one layer, the same in every layer. rows: (pos0, n, is_dense, final) of the live
    rows; the sparse ones are N tokens each (middle blocks are whole).
    Least bytes: the kept tiles of W_gate, W_up and W_down read once
    (rows that chose the same tiles share them), each row's input in
    bf16 and its float32 output. `on_chip` names the operands ("x",
    "wg", "wu", "wd", "out") that the compiled program placed in the
    chip's on-chip memory before the call: the kernel moves none of
    their bytes through HBM (the copy that placed them ran earlier, in
    other device time)."""
    sparse = [r for r in rows if not r[2]]
    if not sparse:
        return 0.0, 0.0
    flops = sum(sparse_ffn_flops(s, s.N) for _ in sparse)
    tiles = s.D * s.k_tiles * s.tile * s.act_bytes
    part = {"x": len(sparse) * s.N * s.D * s.act_bytes,
            "wg": tiles, "wu": tiles, "wd": tiles,
            "out": len(sparse) * s.N * s.D * 4}
    return flops, float(sum(v for k, v in part.items() if k not in on_chip))


def attn_prefill_kernel_work(s: Shapes, rows, on_chip=frozenset(),
                             layer: int = 0) -> tuple:
    """(operations, least bytes) of one prefill attention call for one
    layer, the same in every layer, rows as above: causal attention of each live row's n queries
    over its keys. Least bytes: q in bf16, the K and V the row's
    queries reach, and the float32 output; none of an operand's ("q",
    "k", "v", "out") that sits in on-chip memory, as above."""
    flops = sum(attn_causal_flops(s, r[0], r[1]) for r in rows)
    part = {"q": 0, "k": 0, "v": 0, "out": 0}
    for pos0, n, _, _ in rows:
        part["q"] += n * s.H * s.dh * s.act_bytes
        part["out"] += n * s.H * s.dh * 4
        part["k"] += (pos0 + n) * s.Kv * s.dh * s.act_bytes
        part["v"] += (pos0 + n) * s.Kv * s.dh * s.act_bytes
    return float(flops), float(sum(v for k, v in part.items()
                                   if k not in on_chip))
