"""Pallas TPU kernel: block-sparse gated FFN with scalar-prefetch tiles.

TPU adaptation of FastForward's CUDA row-gather (DESIGN.md §3): the
selected neuron-tile indices are scalar-prefetched; each grid step DMAs
one [D, tile] slab of W_gate/W_up and one [tile, D] slab of W_down from
HBM into VMEM (BlockSpec.index_map redirects by tile id), computes the
gated product for the token block on the MXU, and accumulates into a
single VMEM output block. FLOPs scale exactly with K/n_tiles.

Grid: (num_token_blocks, K). VMEM working set per step:
  x block   [bn, D]      (bn = token block rows, default 128)
  wg, wu    [D, tile]
  wd        [tile, D]
  out       [bn, D] (accumulator, revisited across the K axis)
All MXU dims are multiples of 128 when D and tile are.

Stacked operands (`sparse_ffn_batched` only): the weights may be the
model's whole stacked leaves, W_gate/W_up [L, D, F] and W_down
[L, F, D], with a traced `layer`. The leading block dimension is
squeezed and every index map selects layer `layer`, so each grid step
DMAs its [D, tile] / [tile, D] slab straight out of that layer's part
of the stack. A layer scan then hands the kernel the stacks it closes
over, not per-layer slices: a Pallas call cannot read a slice in place,
so XLA would copy all three whole matrices every layer before the
kernel reads the kept half of them. Same bytes moved, same arithmetic
in the same order: the output is bit-identical to the 2-D call on the
sliced weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _live(ids, cnt, k, b=None):
    """Index-map helper: clamp dead grid steps (k >= cnt) to the last
    live tile id so they re-request an already-resident slab — Pallas
    elides the repeat fetch, making dead steps DMA-free as well as
    (via pl.when) MXU-free. ids: [K] SMEM ref, or [B, K] with row b;
    cnt: scalar; k: grid index. The id is read element by element:
    Mosaic loads only scalars from SMEM, never a row."""
    kk = jnp.minimum(k, jnp.maximum(cnt - 1, 0))
    return ids[kk] if b is None else ids[b, kk]


def _sparse_ffn_kernel(ids_ref, cnt_ref, x_ref, wg_ref, wu_ref, wd_ref,
                       o_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # SparsityPlan per-layer counts: tiles past this layer's count are
    # dead grid steps — the MXU body is skipped, and the index_map
    # clamps their slab requests to the last LIVE tile, so Pallas's
    # revisit-elision sees an unchanged block and moves no bytes
    @pl.when(k < cnt_ref[0])
    def _step():
        x = x_ref[...].astype(jnp.float32)
        hg = jax.lax.dot(x, wg_ref[...].astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        hu = jax.lax.dot(x, wu_ref[...].astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        h = hg * jax.nn.sigmoid(hg) * hu
        y = jax.lax.dot(h, wd_ref[...].astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        o_ref[...] += y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "block_n", "interpret"))
def sparse_ffn(x, wg, wu, wd, tile_ids, k_valid=None, *, tile: int = 128,
               block_n: int = 128, interpret: bool = False):
    """x: [N, D]; wg/wu: [D, F]; wd: [F, D]; tile_ids: [K] int32 (global
    tile ids). Returns [N, D] float32. N % block_n == 0, F % tile == 0.

    k_valid: optional traced int32 scalar — only the first k_valid of
    the K selected tiles are computed (grid steps past it are
    `pl.when`-skipped). None keeps all K (uniform plans)."""
    N, D = x.shape
    F = wg.shape[1]
    K = tile_ids.shape[0]
    assert N % block_n == 0 and F % tile == 0
    cnt = (jnp.full((1,), K, jnp.int32) if k_valid is None
           else jnp.reshape(jnp.asarray(k_valid, jnp.int32), (1,)))

    grid = (N // block_n, K)

    kernel = pl.pallas_call(
        _sparse_ffn_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_n, D), lambda n, k, ids, cnt: (n, 0)),
                # dead steps (k >= cnt) clamp to the last live tile id:
                # the revisited slab is already resident, no DMA issued
                pl.BlockSpec((D, tile),
                             lambda n, k, ids, cnt: (0, _live(ids, cnt[0], k))),
                pl.BlockSpec((D, tile),
                             lambda n, k, ids, cnt: (0, _live(ids, cnt[0], k))),
                pl.BlockSpec((tile, D),
                             lambda n, k, ids, cnt: (_live(ids, cnt[0], k), 0)),
            ],
            out_specs=pl.BlockSpec((block_n, D),
                                   lambda n, k, ids, cnt: (n, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((N, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )
    return kernel(tile_ids, cnt, x, wg, wu, wd)


def _sparse_ffn_batched_kernel(ids_ref, cnt_ref, x_ref, wg_ref, wu_ref,
                               wd_ref, o_ref):
    b = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # per-ROW valid counts (SparsityPlan layer counts during prefill,
    # per-request effort tiers at decode): row b's tiles past
    # cnt_ref[b] are dead grid steps — the MXU body is skipped and the
    # index_map pins their slab requests to row b's last live tile, so
    # the dead steps DMA nothing new
    @pl.when(k < cnt_ref[b])
    def _step():
        x = x_ref[0].astype(jnp.float32)
        hg = jax.lax.dot(x, wg_ref[...].astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        hu = jax.lax.dot(x, wu_ref[...].astype(jnp.float32),
                         preferred_element_type=jnp.float32)
        h = hg * jax.nn.sigmoid(hg) * hu
        y = jax.lax.dot(h, wd_ref[...].astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        o_ref[0] += y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "block_n", "interpret"))
def sparse_ffn_batched(x, wg, wu, wd, tile_ids, k_valid=None, layer=None, *,
                       tile: int = 128, block_n: int = 128,
                       interpret: bool = False):
    """Batched twin of `sparse_ffn` for multi-request prefill: every
    batch row selects its OWN K weight tiles.

    x: [B, N, D]; wg/wu: [D, F]; wd: [F, D]; tile_ids: [B, K] int32
    (global tile ids, per row). Returns [B, N, D] float32.

    Grid (B, N//block_n, K): the whole [B, K] id matrix is scalar-
    prefetched, and each grid step's BlockSpec index_map reads
    ids[b, k] — so the W_gate/W_up/W_down slab DMAs are redirected per
    batch row, exactly the serving layout where the scheduler packs one
    128-token block of B distinct requests into one jitted call.

    k_valid: optional traced [B] int32 per-row valid tile counts — row
    b's grid steps with k >= k_valid[b] skip the MXU body (`pl.when`),
    so a layer-wise SparsityPlan's cheap layers and low-effort requests
    spend FLOPs proportional to their OWN counts while K stays static.
    None keeps all K tiles for every row (uniform plans).

    Stacked operands: wg/wu [L, D, F] and wd [L, F, D] with `layer` a
    traced int32 scalar read layer `layer` of the stacks in place (see
    the module docstring); 2-D weights take no `layer`. The layer index
    rides as one trailing element of the `cnt` scalar-prefetch array
    ([B + 1]), not as an operand of its own: the custom call keeps its
    six operands in the order (ids, cnt, x, wg, wu, wd), which the
    benchmark's roofline reader names by position.
    """
    B, N, D = x.shape
    F = wg.shape[-1]
    K = tile_ids.shape[1]
    stacked = wg.ndim == 3
    if stacked != (layer is not None):
        raise ValueError("sparse_ffn_batched: a layer index goes with "
                         "stacked [L, ...] weights and only with them; "
                         f"got weights of rank {wg.ndim}, layer {layer}")
    assert tile_ids.shape[0] == B
    assert N % block_n == 0 and F % tile == 0
    cnt = (jnp.full((B,), K, jnp.int32) if k_valid is None
           else jnp.broadcast_to(jnp.asarray(k_valid, jnp.int32), (B,)))
    if stacked:
        cnt = jnp.concatenate(
            [cnt, jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))])
    # stacked weights: squeezed layer dimension, indexed by cnt[B]
    lead = (pl.squeezed,) if stacked else ()
    at_layer = (lambda cnt: (cnt[B],)) if stacked else (lambda cnt: ())

    grid = (B, N // block_n, K)

    kernel = pl.pallas_call(
        _sparse_ffn_batched_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_n, D),
                             lambda b, n, k, ids, cnt: (b, n, 0)),
                # dead steps clamp to row b's last live tile id — the
                # revisited slab is already resident, no DMA issued
                pl.BlockSpec(lead + (D, tile),
                             lambda b, n, k, ids, cnt: at_layer(cnt)
                             + (0, _live(ids, cnt[b], k, b))),
                pl.BlockSpec(lead + (D, tile),
                             lambda b, n, k, ids, cnt: at_layer(cnt)
                             + (0, _live(ids, cnt[b], k, b))),
                pl.BlockSpec(lead + (tile, D),
                             lambda b, n, k, ids, cnt: at_layer(cnt)
                             + (_live(ids, cnt[b], k, b), 0)),
            ],
            out_specs=pl.BlockSpec((1, block_n, D),
                                   lambda b, n, k, ids, cnt: (b, n, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, N, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )
    return kernel(tile_ids, cnt, x, wg, wu, wd)


def _dense_ffn_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref):
    f = pl.program_id(1)

    @pl.when(f == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)
    hg = jax.lax.dot(x, wg_ref[...].astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    hu = jax.lax.dot(x, wu_ref[...].astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    h = hg * jax.nn.sigmoid(hg) * hu
    o_ref[...] += jax.lax.dot(h, wd_ref[...].astype(jnp.float32),
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile", "block_n", "interpret"))
def dense_ffn(x, wg, wu, wd, *, tile: int = 512, block_n: int = 128,
              interpret: bool = False):
    """Dense gated-FFN twin of the sparse kernel (the paper's baseline);
    walks ALL F/tile tiles instead of a selected subset."""
    N, D = x.shape
    F = wg.shape[1]
    assert N % block_n == 0 and F % tile == 0
    grid = (N // block_n, F // tile)
    kernel = pl.pallas_call(
        _dense_ffn_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, D), lambda n, f: (n, 0)),
            pl.BlockSpec((D, tile), lambda n, f: (0, f)),
            pl.BlockSpec((D, tile), lambda n, f: (0, f)),
            pl.BlockSpec((tile, D), lambda n, f: (f, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, D), lambda n, f: (n, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )
    return kernel(x, wg, wu, wd)
