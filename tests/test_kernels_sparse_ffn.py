"""Per-kernel validation: sparse/dense gated FFN Pallas kernels
(interpret mode) vs pure-jnp oracles, swept over shapes and dtypes."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.sparse_ffn.kernel import (sparse_ffn, sparse_ffn_batched,
                                             dense_ffn)
from repro.kernels.sparse_ffn.ref import (sparse_ffn_ref,
                                          sparse_ffn_batched_ref,
                                          dense_ffn_ref)
from repro.kernels.sparse_ffn.ops import sparse_ffn_batched_op, sparse_ffn_op


def make_inputs(N, D, F, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    x = (jax.random.normal(ks[0], (N, D)) * 0.5).astype(dtype)
    wg = (jax.random.normal(ks[1], (D, F)) / np.sqrt(D)).astype(dtype)
    wu = (jax.random.normal(ks[2], (D, F)) / np.sqrt(D)).astype(dtype)
    wd = (jax.random.normal(ks[3], (F, D)) / np.sqrt(F)).astype(dtype)
    return x, wg, wu, wd


@pytest.mark.parametrize("N,D,F,tile,k", [
    (128, 128, 512, 128, 2),
    (128, 256, 1024, 128, 5),
    (256, 128, 1024, 128, 8),    # k == all tiles -> dense equivalence
    (128, 384, 768, 128, 3),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sparse_kernel_matches_ref(N, D, F, tile, k, dtype):
    x, wg, wu, wd = make_inputs(N, D, F, dtype)
    n_tiles = F // tile
    ids = jnp.asarray(
        np.random.default_rng(1).choice(n_tiles, size=k, replace=False),
        jnp.int32)
    y_k = sparse_ffn(x, wg, wu, wd, ids, tile=tile, interpret=True)
    y_r = sparse_ffn_ref(x, wg, wu, wd, ids, tile)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=tol, atol=tol)


def test_sparse_all_tiles_equals_dense():
    x, wg, wu, wd = make_inputs(128, 128, 512, jnp.float32)
    ids = jnp.arange(4, dtype=jnp.int32)
    y_s = sparse_ffn(x, wg, wu, wd, ids, tile=128, interpret=True)
    y_d = dense_ffn_ref(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_d),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tile", [128, 256])
def test_dense_kernel_matches_ref(tile):
    x, wg, wu, wd = make_inputs(128, 256, 512, jnp.float32)
    y_k = dense_ffn(x, wg, wu, wd, tile=tile, interpret=True)
    y_r = dense_ffn_ref(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-5, atol=1e-5)


def make_batched_ids(B, n_tiles, k, seed=1):
    """Per-row DISTINCT tile selections (no two rows share a set)."""
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        np.stack([rng.choice(n_tiles, size=k, replace=False)
                  for _ in range(B)]), jnp.int32)


@pytest.mark.parametrize("B,N,D,F,tile,k", [
    (2, 128, 128, 512, 128, 2),
    (4, 128, 256, 1024, 128, 5),
    (3, 32, 128, 512, 64, 3),      # reduced-config-like small block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batched_kernel_matches_gather_and_mask(B, N, D, F, tile, k, dtype):
    """Interpret-mode batched Pallas kernel (per-b scalar-prefetched
    tile ids) vs the XLA gather path vs the mask path, with DISTINCT
    tile ids per block — the serving multi-request prefill contract."""
    from repro.core import sparse_ffn as S
    x, wg, wu, wd = make_inputs(N, D, F, dtype)
    xb = jnp.stack([jnp.roll(x, b, axis=0) * (1.0 + 0.25 * b)
                    for b in range(B)]).astype(dtype)
    ids = make_batched_ids(B, F // tile, k)
    assert len({tuple(np.asarray(r)) for r in ids}) == B

    y_kernel = sparse_ffn_batched(xb, wg, wu, wd, ids, tile=tile,
                                  block_n=min(N, 128), interpret=True)
    y_gather = sparse_ffn_batched_ref(xb, wg, wu, wd, ids, tile)
    params = {"wg": wg, "wu": wu, "wd": wd}
    mask = S.mask_from_tile_ids(ids, F // tile, tile)      # [B, F]
    y_mask = S.ffn_masked(params, xb, mask[:, None, :])

    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_gather),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(y_mask).astype(np.float32),
                               np.asarray(y_gather), rtol=tol, atol=tol)


def test_batched_kernel_rows_are_independent():
    """Row b of the batched kernel equals the single-block kernel run
    on (x[b], ids[b]) — no cross-row leakage through the grid."""
    x, wg, wu, wd = make_inputs(128, 128, 512, jnp.float32)
    B = 3
    xb = jnp.stack([x * (b + 1) for b in range(B)])
    ids = make_batched_ids(B, 4, 2, seed=3)
    y_b = sparse_ffn_batched(xb, wg, wu, wd, ids, tile=128, interpret=True)
    for b in range(B):
        y_1 = sparse_ffn(xb[b], wg, wu, wd, ids[b], tile=128,
                         interpret=True)
        np.testing.assert_allclose(np.asarray(y_b[b]), np.asarray(y_1),
                                   rtol=1e-5, atol=1e-5)


def test_batched_op_cpu_path_matches_interpret_kernel():
    """ops dispatch: the CPU fused-gather path and the forced
    interpret-mode batched kernel agree (the cross-check the serving
    path relies on when validating off-TPU)."""
    x, wg, wu, wd = make_inputs(128, 128, 512, jnp.float32)
    xb = jnp.stack([x, x * 0.5])
    ids = make_batched_ids(2, 4, 2, seed=5)
    y_cpu = sparse_ffn_batched_op(xb, wg, wu, wd, ids, tile=128,
                                  use_kernel=False)
    y_int = sparse_ffn_batched_op(xb, wg, wu, wd, ids, tile=128,
                                  use_kernel=True)
    np.testing.assert_allclose(np.asarray(y_cpu), np.asarray(y_int),
                               rtol=1e-5, atol=1e-5)


def test_ops_dispatch_batched():
    x, wg, wu, wd = make_inputs(128, 128, 512, jnp.float32)
    xb = jnp.stack([x, x * 0.5])
    ids = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    y = sparse_ffn_op(xb, wg, wu, wd, ids, tile=128, use_kernel=False)
    y0 = sparse_ffn_ref(x, wg, wu, wd, ids[0], 128)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(y0),
                               rtol=1e-6, atol=1e-6)


def test_k_valid_dead_slot_ids_never_read():
    """DMA-skip contract: dead selection slots (k >= k_valid) are
    index-map-clamped to the last LIVE tile, so their ids are never
    dereferenced — two selections differing ONLY in dead-slot ids must
    produce bit-identical interpret output (single and batched)."""
    x, wg, wu, wd = make_inputs(128, 128, 512, jnp.float32)
    ids_a = jnp.asarray([0, 2, 1, 3], jnp.int32)
    ids_b = jnp.asarray([0, 2, 3, 1], jnp.int32)      # dead tail differs
    y_a = sparse_ffn(x, wg, wu, wd, ids_a, k_valid=jnp.int32(2),
                     tile=128, interpret=True)
    y_b = sparse_ffn(x, wg, wu, wd, ids_b, k_valid=jnp.int32(2),
                     tile=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(y_a), np.asarray(y_b))
    # and the clamped index map changes nothing vs the live prefix alone
    y_live = sparse_ffn(x, wg, wu, wd, ids_a[:2], tile=128,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(y_a), np.asarray(y_live),
                               rtol=1e-6, atol=1e-6)

    xb = jnp.stack([x, x * 0.5, x * 2.0])
    idsb_a = jnp.asarray([[0, 1, 2, 3], [1, 2, 0, 3], [2, 3, 0, 1]],
                         jnp.int32)
    idsb_b = jnp.asarray([[0, 3, 1, 2], [1, 2, 3, 0], [2, 3, 0, 1]],
                         jnp.int32)                   # same live prefixes
    counts = jnp.asarray([1, 2, 4], jnp.int32)
    yb_a = sparse_ffn_batched(xb, wg, wu, wd, idsb_a, k_valid=counts,
                              tile=128, interpret=True)
    yb_b = sparse_ffn_batched(xb, wg, wu, wd, idsb_b, k_valid=counts,
                              tile=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(yb_a), np.asarray(yb_b))


@pytest.mark.parametrize("impl", ["kernel", "oracle"])
@pytest.mark.parametrize("layer,n_tok,counts", [
    (0, 128, None),          # first layer, every tile live
    (2, 128, (1, 3)),        # last layer, per-row k_valid, dead steps
    (1, 1, (3, 2)),          # decode-shaped: one token per row
])
def test_stacked_weights_equal_sliced_bitwise(impl, layer, n_tok, counts):
    """Stacked operands: the batched kernel (interpret mode) and its
    oracle on whole [L, D, F] / [L, F, D] stacks at traced `layer` give
    bit for bit what they give on that layer's 2-D slices — the tiles
    are read in place, same bytes, same arithmetic."""
    L, B, D, F, tile, k = 3, 2, 128, 512, 128, 3
    ks = jax.random.split(jax.random.key(7), 4)
    wg = (jax.random.normal(ks[0], (L, D, F)) / np.sqrt(D)).astype(
        jnp.bfloat16)
    wu = (jax.random.normal(ks[1], (L, D, F)) / np.sqrt(D)).astype(
        jnp.bfloat16)
    wd = (jax.random.normal(ks[2], (L, F, D)) / np.sqrt(F)).astype(
        jnp.bfloat16)
    x = jax.random.normal(ks[3], (B, n_tok, D)).astype(jnp.bfloat16)
    ids = make_batched_ids(B, F // tile, k, seed=layer)
    k_valid = None if counts is None else jnp.asarray(counts, jnp.int32)
    if impl == "kernel":
        run = functools.partial(sparse_ffn_batched, tile=tile,
                                block_n=min(n_tok, 128), interpret=True)
        stacked = run(x, wg, wu, wd, ids, k_valid, jnp.int32(layer))
        sliced = run(x, wg[layer], wu[layer], wd[layer], ids, k_valid)
    else:
        stacked = sparse_ffn_batched_ref(x, wg, wu, wd, ids, tile,
                                         k_valid=k_valid,
                                         layer=jnp.int32(layer))
        sliced = sparse_ffn_batched_ref(x, wg[layer], wu[layer],
                                        wd[layer], ids, tile,
                                        k_valid=k_valid)
    np.testing.assert_array_equal(np.asarray(stacked), np.asarray(sliced))
    # another layer's weights give another answer: the index is honoured
    other = (layer + 1) % L
    assert not np.array_equal(
        np.asarray(stacked),
        np.asarray(sparse_ffn_batched_ref(x, wg[other], wu[other],
                                          wd[other], ids, tile,
                                          k_valid=k_valid)))


@pytest.mark.parametrize("rows,in_place", [(2, True), (12, False)])
def test_batched_op_reads_stacks_in_place_below_reread_cap(rows, in_place,
                                                          monkeypatch):
    """The kernel path hands the kernel the whole stacks while the call's
    rows read at most STACKED_MAX_REREAD matrices' worth of tiles (here
    rows * 2 tiles of 64 over d_ff 256: 1 and 6), the layer's 2-D slices
    past it; the output is the same bits either way."""
    from jax.extend import core as jcore
    from repro.kernels.sparse_ffn import ops
    L, D, F, tile, k = 2, 128, 256, 64, 2
    ks = jax.random.split(jax.random.key(3), 4)
    wg, wu = (jax.random.normal(kk, (L, D, F)) / np.sqrt(D)
              for kk in ks[:2])
    wd = jax.random.normal(ks[2], (L, F, D)) / np.sqrt(F)
    x = jax.random.normal(ks[3], (rows, 1, D))
    ids = make_batched_ids(rows, F // tile, k)
    layer = jnp.int32(1)
    op = functools.partial(ops.sparse_ffn_batched_op, tile=tile,
                           use_kernel=True, layer=layer)
    np.testing.assert_array_equal(
        np.asarray(op(x, wg, wu, wd, ids)),
        np.asarray(sparse_ffn_batched(x, wg[1], wu[1], wd[1], ids,
                                      tile=tile, block_n=1,
                                      interpret=True)))

    def pallas_calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for v in eqn.params.values():
                if isinstance(v, jcore.ClosedJaxpr):
                    yield from pallas_calls(v.jaxpr)

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    (call,) = pallas_calls(jax.make_jaxpr(op)(x, wg, wu, wd, ids).jaxpr)
    assert [v.aval.ndim for v in call.invars[3:]] == [3 if in_place
                                                      else 2] * 3


def test_kernel_flop_scaling():
    """The kernel's HLO cost must scale with K (the point of the paper)."""
    x, wg, wu, wd = make_inputs(128, 256, 2048, jnp.float32)
    ids2 = jnp.arange(2, dtype=jnp.int32)
    ids8 = jnp.arange(8, dtype=jnp.int32)
    # interpret-mode pallas doesn't expose cost; compare against the
    # analytical count through the ref path lowering instead.
    c2 = jax.jit(lambda *a: sparse_ffn_ref(*a, 128)).lower(
        x, wg, wu, wd, ids2).compile().cost_analysis()
    c8 = jax.jit(lambda *a: sparse_ffn_ref(*a, 128)).lower(
        x, wg, wu, wd, ids8).compile().cost_analysis()
    assert c8["flops"] > 3.5 * c2["flops"]


@pytest.mark.parametrize("backend,kernel_path", [
    ("tpu", True), ("cpu", False), ("gpu", None)])
def test_backend_predicate_never_interprets_off_cpu(backend, kernel_path,
                                                    monkeypatch):
    """TPU runs the compiled kernels, CPU the XLA twins (kernels only
    interpreted there); any other backend is refused rather than
    quietly interpreted."""
    from repro.kernels import backend as BK
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if kernel_path is None:
        with pytest.raises(RuntimeError, match="no kernel path"):
            BK.on_tpu()
    else:
        assert BK.on_tpu() is kernel_path
