"""Every Pallas kernel family, called the way the serving path calls it
for a model at full published width.

One table serves two checks: tests/test_tpu_compile.py compiles each
case for a described v5e chip (shapes only), and chip_smoke.py runs
each case on the chip and compares it with its ref.py oracle. Widths
come from the configs: the dense families from `dense_cfg` (TinyLlama-
1.1B in both checks), grouped_matmul from `moe_cfg`'s experts
(qwen2-moe-a2.7b). `batch` is the prefill batch and the slot count,
`cache_len` the serving cache length; pages are one prefill block.

Each case's `make_args(key)` builds valid operands (in-range ids,
causally consistent offsets); `jax.eval_shape` of it gives the shapes.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.kernels.block_sparse_attention import kernel as BSK
from repro.kernels.block_sparse_attention import ref as BSR
from repro.kernels.flash_attention import kernel as FAK
from repro.kernels.grouped_matmul import kernel as GMK
from repro.kernels.grouped_matmul import ref as GMR
from repro.kernels.kv_quant import kernel as KQK
from repro.kernels.kv_quant import ref as KQR
from repro.kernels.paged_attention import kernel as PAK
from repro.kernels.paged_attention import ref as PAR
from repro.kernels.sparse_ffn import kernel as SFK
from repro.kernels.sparse_ffn import ref as SFR


@dataclasses.dataclass(frozen=True)
class KernelCase:
    kernel: Callable        # the compiled kernel path
    oracle: Callable        # its ref.py oracle, same operands
    make_args: Callable     # key -> operand tuple
    tol: tuple              # (atol, rtol) per output leaf


def _f32(*xs):
    return tuple(x.astype(jnp.float32) for x in xs)


def serving_kernel_cases(dense_cfg, moe_cfg, *, batch: int,
                         cache_len: int) -> dict:
    B, D, F = batch, dense_cfg.d_model, dense_cfg.d_ff
    H, Kv, dh = dense_cfg.n_heads, dense_cfg.n_kv_heads, dense_cfg.head_dim
    N, tile = dense_cfg.ff.block_size, dense_cfg.ff.tile
    n_tiles = F // tile
    k_ffn = n_tiles // 2                  # the default 0.5-sparsity plan
    nc = -(-cache_len // N)               # cache blocks (= pages per row)
    S = nc * N
    n_pages = B * nc + 1                  # full backing + null page 0
    k_attn = max(2, -(-nc // 2))          # selection width at 0.5 budget
    dt = dense_cfg.dtype
    bf16 = (2e-2, 2e-2)                   # bf16 operands, f32 accumulate

    def normal(key, shape, scale=1.0, dtype=dt):
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dtype)

    def ffn_weights(key, lead=()):
        kg, ku, kd = jax.random.split(key, 3)
        return (normal(kg, lead + (D, F), D ** -0.5),
                normal(ku, lead + (D, F), D ** -0.5),
                normal(kd, lead + (F, D), F ** -0.5))

    def sparse_ffn_args(n_tok, n_layers=None):
        """n_layers: the weights are [n_layers, ...] stacks, as the dense
        model's layer scans pass them, read at their last layer."""
        def make(key):
            kx, kw, ki, kc = jax.random.split(key, 4)
            ids = jax.vmap(lambda k: jax.random.permutation(
                k, n_tiles)[:k_ffn])(jax.random.split(ki, B))
            k_valid = jax.random.randint(kc, (B,), 1, k_ffn + 1)
            lead = () if n_layers is None else (n_layers,)
            layer = () if n_layers is None else (jnp.int32(n_layers - 1),)
            return ((normal(kx, (B, n_tok, D)),) + ffn_weights(kw, lead)
                    + (ids.astype(jnp.int32), k_valid) + layer)
        return make

    def sparse_ffn_case(n_tok, n_layers=None):
        return KernelCase(
            kernel=lambda x, wg, wu, wd, ids, kv, *layer:
            SFK.sparse_ffn_batched(x, wg, wu, wd, ids, kv, *layer, tile=tile,
                                   block_n=min(n_tok, 128)),
            oracle=lambda x, wg, wu, wd, ids, kv, *layer:
            SFR.sparse_ffn_batched_ref(x, wg, wu, wd, ids, tile, kv, *layer),
            make_args=sparse_ffn_args(n_tok, n_layers), tol=(bf16,))

    def dense_ffn_args(key):
        kx, kw = jax.random.split(key)
        return (normal(kx, (N, D)),) + ffn_weights(kw)

    def rows_args(key):
        """Query block of each row at its own block offset, its last
        block partial (lengths inside the block)."""
        kq, kk, kv, kb, kl = jax.random.split(key, 5)
        blocks = jax.random.randint(kb, (B,), 0, nc)
        pos0s = (blocks * N).astype(jnp.int32)
        lengths = pos0s + jax.random.randint(kl, (B,), 1, N + 1)
        return (normal(kq, (B, N, H, dh)), normal(kk, (B, S, Kv, dh)),
                normal(kv, (B, S, Kv, dh)), pos0s,
                lengths.astype(jnp.int32))

    def pages(key, quant):
        kp = normal(key, (n_pages, N, Kv, dh), dtype=jnp.float32)
        kp = kp.at[0].set(0.0)                     # the null page
        return KQR.quantize_pages_ref(kp) if quant else (kp.astype(dt),)

    def decode_args(quant):
        def make(key):
            kq, kk, kv, kp = jax.random.split(key, 4)
            table = (1 + jnp.arange(B * nc, dtype=jnp.int32)).reshape(B, nc)
            positions = jax.random.randint(kp, (B,), 0, S).astype(jnp.int32)
            return ((normal(kq, (B, H, dh)),) + pages(kk, quant)
                    + pages(kv, quant) + (table, positions))
        return make

    def selection(key):
        """Per row: a query block far enough in to have k_attn causally
        valid KV blocks, and a sorted live selection of them that keeps
        the sink block 0 and the diagonal block."""
        kb, ks, kc = jax.random.split(key, 3)
        cur = jax.random.randint(kb, (B,), k_attn - 1, nc)
        counts = jax.random.randint(kc, (B,), 2, k_attn + 1)

        def row(k, c, cnt):
            score = jax.random.uniform(k, (nc,))
            score = jnp.where(jnp.arange(nc) > c, -1.0, score)
            score = score.at[0].set(2.0).at[c].set(2.0)
            top = jax.lax.top_k(score, k_attn)[1]
            live = jnp.arange(k_attn) < cnt
            return jnp.sort(jnp.where(live, top, nc + jnp.arange(k_attn)))

        ids = jax.vmap(row)(jax.random.split(ks, B), cur, counts)
        ids = jnp.where(ids >= nc, 0, ids).astype(jnp.int32)
        pos0s = (cur * N).astype(jnp.int32)
        return ids, counts.astype(jnp.int32), pos0s, pos0s + N

    def bsp_args(key):
        """Slot layout: the per-row cache reshaped into a block pool."""
        kq, kk, kv, ksel = jax.random.split(key, 4)
        ids, counts, pos0s, lengths = selection(ksel)
        pool_ids = ids + nc * jnp.arange(B, dtype=jnp.int32)[:, None]
        return (normal(kq, (B, N, H, dh)), normal(kk, (B * nc, N, Kv, dh)),
                normal(kv, (B * nc, N, Kv, dh)), pool_ids, ids * N,
                counts, pos0s, lengths)

    def bsp_quant_args(key):
        """Paged layout: int8 page pool, ids through each row's table."""
        kq, kk, kv, ksel = jax.random.split(key, 4)
        ids, counts, pos0s, lengths = selection(ksel)
        pool_ids = 1 + ids + nc * jnp.arange(B, dtype=jnp.int32)[:, None]
        return ((normal(kq, (B, N, H, dh)),) + pages(kk, True)
                + pages(kv, True)
                + (pool_ids, ids * N, counts, pos0s, lengths))

    def bsp_quant_oracle(q, kq, ks, vq, vs, *rest):
        return BSR.block_sparse_attention_twin(
            q.astype(jnp.float32), KQR.dequantize_pages_ref(kq, ks),
            KQR.dequantize_pages_ref(vq, vs), *rest)

    E, De, Fe = moe_cfg.n_experts, moe_cfg.d_model, moe_cfg.d_ff_expert
    M = B * N * moe_cfg.top_k             # sorted (token, expert) rows

    def gmm_args(key):
        kx, kw, kg = jax.random.split(key, 3)
        # the last N rows stay past sum(sizes): sentinel-routed rows
        cuts = jnp.sort(jax.random.randint(kg, (E - 1,), 0, M - N + 1))
        bounds = jnp.concatenate([jnp.array([0]), cuts,
                                  jnp.array([M - N])])
        sizes = jnp.diff(bounds).astype(jnp.int32)
        return (normal(kx, (M, De), dtype=moe_cfg.dtype),
                normal(kw, (E, De, Fe), De ** -0.5, moe_cfg.dtype), sizes)

    return {
        "sparse_ffn_batched": sparse_ffn_case(N),
        "sparse_ffn_batched_decode": sparse_ffn_case(1),
        "sparse_ffn_batched_stacked": sparse_ffn_case(N, n_layers=2),
        "sparse_ffn_batched_stacked_decode": sparse_ffn_case(1, n_layers=2),
        "dense_ffn": KernelCase(
            kernel=lambda *a: SFK.dense_ffn(*a, tile=tile, block_n=N),
            oracle=SFR.dense_ffn_ref, make_args=dense_ffn_args,
            tol=(bf16,)),
        "flash_attention_rows": KernelCase(
            kernel=lambda *a: FAK.flash_attention_rows(*a, block_k=N),
            oracle=lambda q, k, v, p0, ln: BSR.dense_oracle(
                *_f32(q, k, v), p0, ln),
            make_args=rows_args, tol=(bf16,)),
        "paged_decode_attention": KernelCase(
            kernel=lambda *a: PAK.paged_decode_attention(*a),
            oracle=lambda q, k, v, t, p: PAR.paged_attention_ref(
                *_f32(q, k, v), t, p),
            make_args=decode_args(False), tol=(bf16,)),
        "paged_decode_attention_quant": KernelCase(
            kernel=lambda *a: PAK.paged_decode_attention_quant(*a),
            oracle=lambda q, kq, ks, vq, vs, t, p: PAR.paged_attention_ref(
                q.astype(jnp.float32), {"q": kq, "s": ks},
                {"q": vq, "s": vs}, t, p),
            make_args=decode_args(True), tol=(bf16,)),
        "block_sparse_prefill": KernelCase(
            kernel=lambda *a: BSK.block_sparse_prefill(*a),
            oracle=lambda q, kb, vb, *rest: BSR.block_sparse_attention_twin(
                *_f32(q, kb, vb), *rest),
            make_args=bsp_args, tol=(bf16,)),
        "block_sparse_prefill_quant": KernelCase(
            kernel=lambda *a: BSK.block_sparse_prefill_quant(*a),
            oracle=bsp_quant_oracle, make_args=bsp_quant_args,
            tol=(bf16,)),
        "grouped_matmul": KernelCase(
            kernel=lambda *a: GMK.grouped_matmul(*a, block_m=128),
            oracle=GMR.grouped_matmul_ref, make_args=gmm_args,
            tol=(bf16,)),
        # int8 codes may differ by one at a rounding tie; scales and
        # dequantized values are elementwise f32 math
        "quantize_pages": KernelCase(
            kernel=lambda x: KQK.quantize_pages(x),
            oracle=KQR.quantize_pages_ref,
            make_args=lambda key: (normal(key, (B, N, Kv, dh),
                                          dtype=jnp.float32),),
            tol=((1, 0), (0, 1e-6))),
        "dequantize_pages": KernelCase(
            kernel=lambda q, s: KQK.dequantize_pages(q, s),
            oracle=KQR.dequantize_pages_ref,
            make_args=lambda key: pages(key, True), tol=((0, 1e-6),)),
    }
