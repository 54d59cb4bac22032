"""Random weights for a benchmark configuration, made from the seed.

Every leaf has its own key, `fold_in(fold_in(root(seed), crc32(path)),
layer)`, so the plain reference can draw any one layer of any leaf on
its own, and the program's stacked [L, ...] leaf holds the same numbers
layer by layer. Each leaf is drawn by its architecture's rule
(bench/archs/<arch>.py `RULES`); the values are rounded to the served
dtype (bf16) in both places.

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
output size, `tile_weighted`) and shifts the tile's columns of the
predictor's W_2 by PREDICTOR_BIAS * z_t / r (`tile_ranked`). The
predictor then ranks tiles by importance first and by the input within
a few ranks of the cut, and the tiles at the cut weigh little. With
independent tiles of equal weight, a tile swapped at a near-tie of the
top-k moves a layer's FFN output by a fifth, and bf16 rounding alone
swaps one in most decode steps: the served tokens would then part from
the float32 reference about as far under bf16 as under fp8, and no
comparison could tell the two apart (PERF.md).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from bench import archs

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


def leaf_key(root, path: str, layer=None):
    """The key of one leaf, or of one layer of a stacked leaf."""
    key = _path_key(root, path)
    return key if layer is None else jax.random.fold_in(key, layer)


def std(shape, axes, gain: float):
    """gain / sqrt(fan_in), fan_in the product of `axes` of `shape`."""
    fan_in = int(np.prod([shape[a] for a in axes])) if axes else 1
    return gain / np.sqrt(fan_in)


# ----------------------------------------------------------------- rules
# rule(root, path, shape, layer) -> float32 array of `shape`


def ones(root, path, shape, layer):
    """A norm scale."""
    return jnp.ones(shape, jnp.float32)


def normal(axes, gain: float = 1.0):
    """N(0, 1) * std(shape, axes, gain), from the leaf's own key."""
    def rule(root, path, shape, layer):
        z = jax.random.normal(leaf_key(root, path, layer), shape,
                              jnp.float32)
        return z * std(shape, axes, gain)
    return rule


def tile_weighted(tiles: str, axes, gain: float = 1.0):
    """W_down [F, D]: `normal`, each tile's rows scaled by
    exp(IMPORTANCE * z_t - IMPORTANCE**2), z_t drawn at `tiles`."""
    base = normal(axes, gain)

    def rule(root, path, shape, layer):
        z = _tile_importance(root, tiles, shape[0], layer)
        lam = jnp.exp(IMPORTANCE * z - IMPORTANCE ** 2)
        return base(root, path, shape, layer) * lam[:, None]
    return rule


def tile_ranked(tiles: str, axes, gain: float = 1.0):
    """The predictor's W_2 [r, F]: `normal`, each tile's columns shifted
    by PREDICTOR_BIAS * z_t / r, z_t drawn at `tiles`."""
    base = normal(axes, gain)

    def rule(root, path, shape, layer):
        return base(root, path, shape, layer) + PREDICTOR_BIAS / shape[0] \
            * _tile_importance(root, tiles, shape[1], layer)[None, :]
    return rule


def _tile_importance(root, tiles: str, n_neurons: int, layer):
    """Each neuron's tile importance z_t (one layer)."""
    key = jax.random.fold_in(_path_key(root, tiles), layer)
    z = jax.random.normal(key, (-(-n_neurons // TILE),), jnp.float32)
    return jnp.repeat(z, TILE)[:n_neurons]


def draw(rules, root, path: str, shape, dtype, layer=None):
    """One leaf (or one layer of a stacked leaf) as the program is
    served it: path as in `rules`, shape without the layer axis."""
    return rules[path](root, path, shape, layer).astype(dtype)


def _tree_path(kp) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in kp)


def builder(arch, specs):
    """A jitted function from key data (`jax.random.key_data`) to the
    program's parameter tree for `specs` (a ParamSpec tree), each leaf
    drawn by the rules of `arch` (a bench/archs module). The seed
    enters as data, so every seed runs the same compiled program."""
    from repro.nn.param import is_spec

    def one(root, kp, spec):
        path = _tree_path(kp)
        stacked = path.startswith("layers/")
        shape = tuple(spec.shape[1:]) if stacked else tuple(spec.shape)

        if path not in arch.RULES:
            raise KeyError(f"no weight rule for leaf {path!r}: add it to "
                           f"{arch.__name__}.RULES and the reference")

        def leaf(layer):
            return draw(arch.RULES, root, path, shape, spec.dtype, layer)

        if not stacked:
            return leaf(None)
        return jax.vmap(leaf)(jnp.arange(spec.shape[0]))

    @jax.jit
    def build(key_data):
        root = jax.random.wrap_key_data(key_data)
        return jax.tree_util.tree_map_with_path(
            lambda kp, s: one(root, kp, s), specs, is_leaf=is_spec)

    return build


def make_params(config: dict, seed: int):
    """The program's parameter tree for a configuration file, made on
    the default device in one jitted call."""
    from repro.models.registry import get_model
    arch = archs.module(config)
    cfg = arch.model_config(config)
    return builder(arch, get_model(cfg).specs(cfg))(
        jax.random.key_data(root_key(seed)))
