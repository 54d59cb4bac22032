"""Plain reference of the "dense" architecture: a pre-norm decoder with
RMSNorm, grouped-query attention with rotate-half RoPE, a SwiGLU FFN and
FastForward's sparse FFN (arXiv:2602.00397), in float32 jax.numpy at
"highest" matmul precision, one sequence and one layer at a time. No
kernel, no KV cache, no batching; it imports nothing of the program and
draws its own weights from the seed, by the rules the program's tree
is drawn by (bench/archs/dense.py, bench/weights.py).

FastForward, as the configuration states it:
  * the prompt is cut into blocks of `block_size` tokens; the first and
    the last block run the dense FFN;
  * every other block pools its FFN inputs with a softmax over
    q_pred . x / sqrt(D) (Eq. 12), scores the neurons with
    relu(a W1) W2 (Eq. 13), sums sigmoid(score) over each tile of
    `tile` neurons and keeps the top ceil((1 - sparsity) * tiles);
  * the kept neurons' SwiGLU output, plus the compensator
    relu(x C1) C2 (Eq. 20), is the block's FFN output;
  * with apply_to_decode, each generated token is its own block of one.

`precision="fp8"` is the control: the same mathematics with every
matmul operand rounded to float8 e4m3 (weights scaled per tensor,
activations per row), the step below the bf16 the configuration states.
`precision="bf16"` rounds the same operands to bfloat16 instead: the
configuration's own precision, a witness of how far rounding alone
moves the served tokens.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.archs import dense as A

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


class Dims:
    def __init__(self, config: dict):
        ff = config["fastforward"]
        self.L = config["num_hidden_layers"]
        self.D = config["hidden_size"]
        self.F = config["intermediate_size"]
        self.H = config["num_attention_heads"]
        self.Kv = config["num_key_value_heads"]
        self.dh = config["head_dim"]
        self.V = config["vocab_size"]
        self.eps = config["rms_norm_eps"]
        self.theta = config["rope_theta"]
        self.N = ff["block_size"]
        self.tile = ff["tile"]
        self.r = ff["predictor_dim"]
        self.rc = ff["compensator_dim"]
        self.ff = ff
        n_tiles = self.F // self.tile
        self.n_tiles = n_tiles
        self.k = min(max(math.ceil((1 - ff["sparsity"]) * n_tiles), 1),
                     n_tiles)

    def layer_shapes(self):
        D, F, H, Kv, dh = self.D, self.F, self.H, self.Kv, self.dh
        return (("ln1/scale", (D,)), ("ln2/scale", (D,)),
                ("attn/wq", (D, H, dh)), ("attn/wk", (D, Kv, dh)),
                ("attn/wv", (D, Kv, dh)), ("attn/wo", (H, dh, D)),
                ("ffn/wg", (D, F)), ("ffn/wu", (D, F)), ("ffn/wd", (F, D)),
                ("ffn/pred/q_pred", (D,)), ("ffn/pred/w1", (D, self.r)),
                ("ffn/pred/w2", (self.r, F)),
                ("ffn/comp/w1", (D, self.rc)), ("ffn/comp/w2", (self.rc, D)))

    def key(self):
        return (self.L, self.D, self.F, self.H, self.Kv, self.dh, self.V,
                self.eps, self.theta, self.N, self.tile, self.r, self.rc,
                self.k, self.ff["dense_first_block"],
                self.ff["dense_last_block"], self.ff["use_compensator"])


# ------------------------------------------------------------- weights


@functools.partial(jax.jit, static_argnames=("shapes",))
def _draw_layer(key_data, i, shapes):
    root = jax.random.wrap_key_data(key_data)
    return {p: W.draw(A.RULES, root, "layers/" + p, s, jnp.bfloat16,
                      layer=i)
            for p, s in shapes}


@functools.partial(jax.jit, static_argnames=("path", "shape"))
def _draw_top(key_data, path, shape):
    return W.draw(A.RULES, jax.random.wrap_key_data(key_data), path,
                  shape, jnp.bfloat16)


# ---------------------------------------------------------- arithmetic


def _q8(x, per_row: bool):
    """Round to float8 e4m3 under a power-free absmax scale."""
    x = x.astype(jnp.float32)
    amax = (jnp.max(jnp.abs(x), axis=-1, keepdims=True) if per_row
            else jnp.max(jnp.abs(x)))
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _bf(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _low(x, per_row: bool, low):
    """An operand in the run's precision: `low` is "fp8", "bf16" or
    None (float32)."""
    if low == "bf16":
        return _bf(x)
    return _q8(x, per_row) if low == "fp8" else x


def _mm(spec, a, b, low):
    """einsum of an activation `a` with a weight `b`."""
    if low:
        a, b = _low(a, True, low), _low(b, False, low)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta):
    """x [T, h, dh]: rotate-half RoPE at integer positions."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(freqs)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, low, chunk: int = 512):
    """Causal GQA attention of every query over the keys at or before
    it. q [T, H, dh]; k, v [T, Kv, dh]; padding sits after the real
    tokens, where causality keeps it from every real query."""
    T, H, dh = q.shape
    Kv = k.shape[1]
    rep = H // Kv
    if low:
        q, k, v = (_low(t, True, low) for t in (q, k, v))
    qg = q.reshape(T // chunk, chunk, Kv, rep, dh)
    kj = jnp.arange(T)

    def one(args):
        c, qc = args
        qi = c * chunk + jnp.arange(chunk)
        s = jnp.einsum("cgrd,sgd->grcs", qc, k, precision=HI) / math.sqrt(dh)
        s = jnp.where(kj[None, None, None, :] <= qi[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if low:
            p = _low(p, True, low)
        return jnp.einsum("grcs,sgd->cgrd", p, v, precision=HI)

    o = jax.lax.map(one, (jnp.arange(T // chunk), qg))
    return o.reshape(T, H, dh)


@functools.partial(jax.jit, static_argnames=("dims_key", "low"))
def _layer(w, x, pos, unit, sparse, dims_key, low):
    """One decoder layer over one padded sequence. unit[t]: the index of
    the first token of t's FastForward unit (its block, or t itself for
    a generated or padding token); sparse[t]: t runs the sparse FFN."""
    (L, D, F, H, Kv, dh, V, eps, theta, N, tile, r, rc, k_keep, _, _,
     use_comp) = dims_key
    f = lambda a: a.astype(jnp.float32)
    T = x.shape[0]
    h = _rms(x, f(w["ln1/scale"]), eps)
    q = _rope(_mm("td,dhk->thk", h, f(w["attn/wq"]), low), pos, theta)
    kk = _rope(_mm("td,dhk->thk", h, f(w["attn/wk"]), low), pos, theta)
    vv = _mm("td,dhk->thk", h, f(w["attn/wv"]), low)
    o = _attention(q, kk, vv, low)
    x = x + _mm("thk,hkd->td", o, f(w["attn/wo"]), low)

    h = _rms(x, f(w["ln2/scale"]), eps)
    # predictor: softmax pooling within each unit (Eq. 12), scores (13)
    logit = jnp.einsum("td,d->t", h, f(w["ffn/pred/q_pred"]),
                       precision=HI) / math.sqrt(D)
    m = jax.ops.segment_max(logit, unit, T)
    e = jnp.exp(logit - m[unit])
    wts = e / jax.ops.segment_sum(e, unit, T)[unit]
    pooled = jax.ops.segment_sum(wts[:, None] * h, unit, T)     # [T, D]
    s = _mm("ud,dr->ur", pooled, f(w["ffn/pred/w1"]), low)
    s = _mm("ur,rf->uf", jax.nn.relu(s), f(w["ffn/pred/w2"]), low)
    tiles = jax.nn.sigmoid(s).reshape(T, F // tile, tile).sum(-1)
    _, keep = jax.lax.top_k(tiles, k_keep)                      # [T, k]
    kept = jnp.zeros((T, F // tile), bool).at[
        jnp.arange(T)[:, None], keep].set(True)
    mask = jnp.where(sparse[:, None], kept[unit], True)         # [T, tiles]
    g = _mm("td,df->tf", h, f(w["ffn/wg"]), low)
    u = _mm("td,df->tf", h, f(w["ffn/wu"]), low)
    a = jax.nn.silu(g) * u
    a = a * jnp.repeat(mask, tile, axis=-1)
    y = _mm("tf,fd->td", a, f(w["ffn/wd"]), low)
    if use_comp:
        c = jax.nn.relu(_mm("td,dr->tr", h, f(w["ffn/comp/w1"]), low))
        c = _mm("tr,rd->td", c, f(w["ffn/comp/w2"]), low)
        y = y + jnp.where(sparse[:, None], c, 0.0)
    return x + y


@functools.partial(jax.jit, static_argnames=("eps", "low"))
def _head(x, scale, table, eps, low):
    h = _rms(x, scale.astype(jnp.float32), eps)
    return _mm("td,vd->tv", h, table.astype(jnp.float32), low)


def _bucket(n: int) -> int:
    b = 512
    while b < n:
        b *= 2
    return b


def units(dims: Dims, prompt_len: int, T: int):
    """(unit, sparse) per position of a padded sequence whose first
    prompt_len tokens are the prompt."""
    ff, N = dims.ff, dims.N
    unit = np.arange(T, dtype=np.int32)
    sparse = np.zeros(T, bool)
    nb = -(-prompt_len // N)
    for b in range(nb):
        lo, hi = b * N, min((b + 1) * N, prompt_len)
        unit[lo:hi] = lo
        dense = ((ff["dense_first_block"] and b == 0)
                 or (ff["dense_last_block"] and b == nb - 1))
        sparse[lo:hi] = not dense
    sparse[prompt_len:] = bool(ff["apply_to_decode"])
    return unit, sparse


def logits(config: dict, seed: int, seqs, precision: str = "f32"):
    """seqs: [(tokens, prompt_len, reads)] with `reads` the positions
    whose next-token logits are wanted. Returns one float32 array
    [len(reads), V] per sequence. Positions past the prompt are
    generated tokens (FastForward's decode-time blocks of one)."""
    dims = Dims(config)
    if precision not in ("f32", "bf16", "fp8"):
        raise ValueError(f"unknown precision {precision!r}")
    low = None if precision == "f32" else precision
    key = jax.random.key_data(W.root_key(seed))
    embed = _draw_top(key, "embed/table", (dims.V, dims.D))
    xs, meta = [], []
    for tokens, prompt_len, reads in seqs:
        n = len(tokens)
        T = _bucket(n)
        ids = np.zeros(T, np.int32)
        ids[:n] = tokens
        unit, sparse = units(dims, prompt_len, T)
        sparse[n:] = False
        xs.append(jnp.take(embed, jnp.asarray(ids), axis=0)
                  .astype(jnp.float32))
        meta.append((jnp.arange(T, dtype=jnp.int32), jnp.asarray(unit),
                     jnp.asarray(sparse), np.asarray(reads)))
    del embed
    shapes = dims.layer_shapes()
    for i in range(dims.L):
        w = _draw_layer(key, i, shapes)
        xs = [_layer(w, x, pos, unit, sp, dims.key(), low)
              for x, (pos, unit, sp, _) in zip(xs, meta)]
        del w
    scale = _draw_top(key, "ln_f/scale", (dims.D,))
    table = _draw_top(key, "lm_head/table", (dims.V, dims.D))
    return [np.asarray(_head(x[jnp.asarray(reads)], scale, table, dims.eps,
                             low))
            for x, (_, _, _, reads) in zip(xs, meta)]
