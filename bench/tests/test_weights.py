"""The program's stacked leaves and the reference's per-layer draws are
the same numbers; seeds beyond 32 bits are distinct."""
import json
import pathlib

import jax
import jax.numpy as jnp

from bench import weights
from bench.archs import dense as A
from bench.references import dense as RD

DATA = pathlib.Path(__file__).parent / "data"


def test_program_tree_equals_reference_draws():
    config = json.loads((DATA / "tiny.json").read_text())
    params = weights.make_params(config, 2**33 + 1)
    key = jax.random.key_data(weights.root_key(2**33 + 1))
    dims = RD.Dims(config)
    for i in range(dims.L):
        layer = RD._draw_layer(key, i, dims.layer_shapes())
        for path, _ in dims.layer_shapes():
            leaf = params["layers"]
            for k in path.split("/"):
                leaf = leaf[k]
            assert jnp.array_equal(leaf[i], layer[path]), (path, i)
    assert jnp.array_equal(params["lm_head"]["table"],
                           RD._draw_top(key, "lm_head/table",
                                        (dims.V, dims.D)))


def test_high_seed_bits_change_the_weights():
    a = weights.draw(A.RULES, weights.root_key(3), "layers/ffn/wg", (8, 8),
                     jnp.float32, layer=0)
    b = weights.draw(A.RULES, weights.root_key(3 + 2**32),
                     "layers/ffn/wg", (8, 8), jnp.float32, layer=0)
    assert not jnp.array_equal(a, b)


def test_predictor_ranks_tiles_by_their_weight_in_w_down():
    root = weights.root_key(2**33 + 5)
    wd = weights.draw(A.RULES, root, "layers/ffn/wd", (1024, 64),
                      jnp.float32, 3)
    w2 = weights.draw(A.RULES, root, "layers/ffn/pred/w2",
                      (256, 1024), jnp.float32, 3)
    n = 1024 // weights.TILE
    tile_norm = jnp.linalg.norm(wd.reshape(n, -1), axis=-1)
    tile_bias = w2.reshape(256, n, weights.TILE).mean(axis=(0, 2))
    rank = lambda v: jnp.argsort(jnp.argsort(v))
    # Spearman's rank correlation: the two orders agree but for near-ties
    assert jnp.corrcoef(rank(tile_norm), rank(tile_bias))[0, 1] > 0.9
    assert tile_norm.max() / tile_norm.min() > 3   # tiles differ in weight
