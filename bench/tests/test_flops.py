"""The operation and byte counts, on hand-worked shapes."""
import json
import pathlib

import pytest

from bench import flops as FL
from bench.archs import dense as A

CONFIGS = pathlib.Path(__file__).parents[1] / "configs"


def granite():
    return A.Shapes(json.loads((CONFIGS / "granite-8b.json").read_text()))


def test_kept_tiles_at_half_sparsity():
    s = granite()
    assert s.F // s.tile == 112 and s.k_tiles == 56


def test_sparse_ffn_is_half_the_dense_ffn():
    s = granite()
    assert A.sparse_ffn_flops(s, 128) == pytest.approx(
        0.5 * 6 * 128 * 4096 * 14336)


def test_causal_attention_counts_keys_up_to_each_query():
    s = granite()
    # queries at 256..383 see 257..384 keys: 128*256 + 128*129/2 keys
    keys = 128 * 256 + 128 * 129 // 2
    assert A.attn_causal_flops(s, 256, 128) == 4 * 32 * 128 * keys


def test_prompt_with_dense_first_and_last_blocks():
    """A 4-block prompt: blocks 0 and 3 run the dense FFN, 1 and 2 the
    kept tiles plus predictor and compensator; the head runs once."""
    s = granite()
    D, F, L, N = 4096, 14336, 18, 128
    rows = [(0, N, True, False), (N, N, False, False),
            (2 * N, N, False, False), (3 * N, 100, True, True)]
    total = sum(A.prefill_row_flops(s, *r) for r in rows)
    proj = 2 * D * (2 * 32 * 128 + 2 * 8 * 128)
    ffn_dense = 6 * D * F
    ffn_sparse = 6 * D * 56 * 128
    pred = 4 * N * D + 2 * D * 256 + 2 * 256 * F
    comp = 4 * N * D * 512
    n_tok = 3 * N + 100
    keys = sum(p * n + n * (n + 1) // 2 for p, n, _, _ in rows)
    want = L * (proj * n_tok + 4 * 32 * 128 * keys
                + ffn_dense * (N + 100) + ffn_sparse * 2 * N
                + 2 * (pred + comp)) + 2 * D * 49152
    assert total == pytest.approx(want)


def test_sparse_kernel_work_counts_only_sparse_rows():
    s = granite()
    rows = [(128, 128, False, False), (0, 128, True, False)]
    flops, nbytes = A.sparse_ffn_kernel_work(s, rows)
    assert flops == 6 * 128 * 4096 * 56 * 128
    assert nbytes == 3 * 4096 * 56 * 128 * 2 + 128 * 4096 * 6
    assert A.sparse_ffn_kernel_work(s, rows[1:]) == (0.0, 0.0)


def test_least_time_takes_the_binding_peak():
    peak = {"flops_bf16": 100.0, "hbm_bytes_s": 10.0}
    assert FL.least_time(1000.0, 50.0, peak) == 10.0
    assert FL.least_time(100.0, 50.0, peak) == 5.0


def test_on_chip_operands_move_no_bytes():
    s = granite()
    rows = [(128, 128, False, False)]
    _, all_hbm = A.sparse_ffn_kernel_work(s, rows)
    _, wu_on_chip = A.sparse_ffn_kernel_work(s, rows, frozenset({"wu"}))
    assert all_hbm - wu_on_chip == s.D * s.k_tiles * s.tile * 2
    _, kv = A.attn_prefill_kernel_work(s, rows)
    _, v_only = A.attn_prefill_kernel_work(s, rows, frozenset({"k"}))
    assert kv - v_only == 256 * s.Kv * s.dh * 2
