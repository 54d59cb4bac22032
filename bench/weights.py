"""Random weights for a benchmark configuration, made from the seed.

Every leaf has its own key, `fold_in(fold_in(root(seed), crc32(path)),
layer)`, so the plain reference can draw any one layer of any leaf on
its own, and the program's stacked [L, ...] leaf holds the same numbers
layer by layer. The values are rounded to the served dtype (bf16) in
both places.

Scales keep every branch of the residual stream at the size of its
input, so that attention, the FFN and the compensator all move the
logits and a fault in any of them shows in the comparison:
std = gain / sqrt(fan_in) over each leaf's contraction dims. The
program's own initializer (`nn.param`) is not used: its fan-in rule
reads the wrong axis of the 3-D attention leaves, which makes attention
one-hot and the FFN a rounding error beside it, and it zeroes the
compensator's output projection, so an undistilled compensator would
add nothing that a check could see.

FFN tiles differ in weight, as a trained model's neurons do, and the
predictor knows it, as a distilled one would: each layer draws one
importance z_t ~ N(0, 1) per tile of `TILE` neurons, scales the tile's
rows of W_down by exp(IMPORTANCE * z_t) (normalised to keep the FFN's
output size) and shifts the tile's columns of the predictor's W_2 by
PREDICTOR_BIAS * z_t / r. The predictor then ranks tiles by importance
first and by the input within a few ranks of the cut, and the tiles at
the cut weigh little. With independent tiles of equal weight, a tile
swapped at a near-tie of the top-k moves a layer's FFN output by a
fifth, and bf16 rounding alone swaps one in most decode steps: the
served tokens would then part from the float32 reference about as far
under bf16 as under fp8, and no comparison could tell the two apart
(PERF.md).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

#: leaf path (inside one layer for "layers/...") -> (fan-in axes, gain).
#: Axes index the per-layer shape. "ones" leaves are norm scales.
RULES = {
    "embed/table": ((), 1.0),
    "lm_head/table": ((1,), 1.0),
    "ln_f/scale": "ones",
    "layers/ln1/scale": "ones",
    "layers/ln2/scale": "ones",
    # q.k / sqrt(dh) has std gain**2: ~2.25 keeps softmax focused on a
    # handful of keys without making it one-hot
    "layers/attn/wq": ((0,), 1.5),
    "layers/attn/wk": ((0,), 1.5),
    "layers/attn/wv": ((0,), 1.0),
    "layers/attn/wo": ((0, 1), 1.0),
    "layers/ffn/wg": ((0,), 1.0),
    "layers/ffn/wu": ((0,), 1.0),
    "layers/ffn/wd": ((0,), 1.0),
    # predictor logits over a block's tokens have std 1 (Eq. 12)
    "layers/ffn/pred/q_pred": ((), 1.0),
    "layers/ffn/pred/w1": ((0,), 1.0),
    "layers/ffn/pred/w2": ((0,), 1.0),
    "layers/ffn/comp/w1": ((0,), 1.0),
    "layers/ffn/comp/w2": ((0,), 0.5),
}


#: neurons per importance group: the FastForward tile of every
#: configuration here (`fastforward.tile`)
TILE = 128
#: log-std of a tile's weight in W_down
IMPORTANCE = 1.5
#: a tile's shift of the predictor's neuron logits, in units of the
#: mean of relu(a W_1) (whose spread the random part of W_2 matches)
PREDICTOR_BIAS = 2.0


def root_key(seed: int):
    """A key from any non-negative seed, all 64 bits of it (x64 off
    would fold a larger seed onto its low 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _path_key(root, path: str):
    return jax.random.fold_in(root, zlib.crc32(path.encode()))


def _std(path: str, shape) -> float:
    axes, gain = RULES[path]
    fan_in = int(np.prod([shape[a] for a in axes])) if axes else 1
    return gain / np.sqrt(fan_in)


def draw(root, path: str, shape, dtype, layer=None):
    """One leaf (or one layer of a stacked leaf) as the program is
    served it: path as in RULES, shape without the layer axis."""
    rule = RULES[path]
    if rule == "ones":
        return jnp.ones(shape, dtype)
    key = _path_key(root, path)
    if layer is not None:
        key = jax.random.fold_in(key, layer)
    z = jax.random.normal(key, shape, jnp.float32)
    w = z * _std(path, shape)
    if path == "layers/ffn/wd":                  # [F, D]
        lam = jnp.exp(IMPORTANCE * _tiles(root, shape[0], layer)
                      - IMPORTANCE ** 2)
        w = w * lam[:, None]
    elif path == "layers/ffn/pred/w2":           # [r, F]
        w = w + PREDICTOR_BIAS / shape[0] * _tiles(root, shape[1],
                                                   layer)[None, :]
    return w.astype(dtype)


def _tiles(root, n_neurons: int, layer):
    """Each neuron's tile importance z_t (one layer)."""
    key = jax.random.fold_in(_path_key(root, "layers/ffn/tiles"), layer)
    z = jax.random.normal(key, (-(-n_neurons // TILE),), jnp.float32)
    return jnp.repeat(z, TILE)[:n_neurons]


def _tree_path(kp) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in kp)


def builder(specs):
    """A jitted function from key data (`jax.random.key_data`) to the
    program's parameter tree for `specs` (a ParamSpec tree). The seed
    enters as data, so every seed runs the same compiled program."""
    from repro.nn.param import is_spec

    def one(root, kp, spec):
        path = _tree_path(kp)
        stacked = path.startswith("layers/")
        shape = tuple(spec.shape[1:]) if stacked else tuple(spec.shape)

        if path not in RULES:
            raise KeyError(f"no weight rule for leaf {path!r}: add it to "
                           "bench/weights.RULES and the reference")

        def leaf(layer):
            return draw(root, path, shape, spec.dtype, layer)

        if not stacked:
            return leaf(None)
        return jax.vmap(leaf)(jnp.arange(spec.shape[0]))

    @jax.jit
    def build(key_data):
        root = jax.random.wrap_key_data(key_data)
        return jax.tree_util.tree_map_with_path(
            lambda kp, s: one(root, kp, s), specs, is_leaf=is_spec)

    return build


def make_params(specs, seed: int):
    """The parameter tree for `specs`, made on the default device in one
    jitted call."""
    return builder(specs)(jax.random.key_data(root_key(seed)))
