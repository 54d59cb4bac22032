"""int8-quantized KV page heap (kernels/kv_quant + the quant-aware
paged attention paths): quantization scheme properties (idempotence,
error bound, zero-page exactness), Pallas-kernel-vs-oracle bit
equivalence in interpret mode, quant-heap attention kernels vs the
dequantized-heap reference, the dict-leaf gather/write plumbing in
nn/attention, and the end-to-end serving contract — quant logits
allclose to f32 within the documented tolerance, page accounting and
compile counts unchanged with the quantized heap on.

Tolerance note: per kernels/kv_quant/ref.py each dequantized K/V
element differs from the source by <= 0.5 * absmax / 127 (~0.4% of a
page's per-head dynamic range). Attention and the FFN stack amplify
that, so end-to-end logits comparisons use a deliberately generous
tolerance (see E2E_*); greedy TOKENS may legitimately diverge on
near-flat logits (random-init weights), which is why the end-to-end
contract is at the logits level, not token level.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.kernels.kv_quant import kernel as KQK
from repro.kernels.kv_quant import ops as KQ
from repro.kernels.kv_quant import ref as KQR
from repro.kernels.paged_attention import kernel as PK
from repro.kernels.paged_attention import ref as PR
from repro.models.registry import get_model
from repro.nn import attention as A
from repro.nn.param import init_params
from repro.serving import ContinuousBatchingScheduler, Request
from repro.serving.runtime import make_runtime

PAGE = 8                       # divides the reduced block size (32)

# end-to-end logits tolerance for the 2-layer reduced model (see the
# module docstring): ~0.4% per-element KV error through attention +
# FFN + unembed stays well inside this
E2E_RTOL, E2E_ATOL = 0.05, 0.25


def _pages(rng, P=6, psz=4, Kv=2, dh=8, scale=3.0):
    x = rng.standard_normal((P, psz, Kv, dh)) * scale
    x[0] = 0.0                              # the reserved null page
    return jnp.asarray(x, jnp.float32)


# --------------------------------------------------------- quant scheme


def test_quant_roundtrip_error_bound_and_zero_pages():
    rng = np.random.default_rng(0)
    x = _pages(rng)
    q, s = KQR.quantize_pages_ref(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    y = KQR.dequantize_pages_ref(q, s)
    # documented bound: 0.5 * absmax / 127 per (page, kv-head)
    absmax = np.max(np.abs(np.asarray(x)), axis=(1, 3))
    bound = 0.5 * absmax / 127.0
    err = np.max(np.abs(np.asarray(y - x)), axis=(1, 3))
    assert (err <= bound + 1e-7).all()
    # all-zero pages: scale 0, dequant EXACTLY zero (null-page contract)
    assert float(s[0].max()) == 0.0
    np.testing.assert_array_equal(np.asarray(y[0]), 0.0)


def test_quantize_dequantize_roundtrip_stable():
    """Requantizing a dequantized page reproduces q bit-exactly and s
    to within one f32 ulp — the decode token write path (dequantize ->
    modify -> requantize) relies on this bound for already-written
    tokens (see ref.py round-trip stability note)."""
    rng = np.random.default_rng(1)
    q, s = KQR.quantize_pages_ref(_pages(rng))
    y = KQR.dequantize_pages_ref(q, s)
    q2, s2 = KQR.quantize_pages_ref(y)
    np.testing.assert_array_equal(np.asarray(q2), np.asarray(q))
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s),
                               rtol=2 ** -23, atol=0)


def test_kernel_interpret_bit_matches_oracle():
    rng = np.random.default_rng(2)
    x = _pages(rng, P=5, psz=8, Kv=3, dh=16)
    qk, sk = KQK.quantize_pages(x, interpret=True)
    qr, sr = KQR.quantize_pages_ref(x)
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))
    yk = KQK.dequantize_pages(qk, sk, interpret=True)
    yr = KQR.dequantize_pages_ref(qr, sr)
    np.testing.assert_array_equal(np.asarray(yk), np.asarray(yr))
    # the op-layer dispatch reaches both paths
    qo, so = KQ.quantize_pages_op(x, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(qo), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(so), np.asarray(sr))
    np.testing.assert_array_equal(
        np.asarray(KQ.dequantize_pages_op(qo, so, use_kernel=False)),
        np.asarray(yr))


# ------------------------------------------- quant attention kernels


def _decode_setup(seed=0, B=3, H=4, Kv=2, dh=8, psz=4, max_pages=6,
                  positions=(9, 5, 18)):
    rng = np.random.default_rng(seed)
    positions = np.asarray(positions, np.int32)
    n_pages = 1 + int(sum(p // psz + 1 for p in positions))
    q = jnp.asarray(rng.standard_normal((B, H, dh)), jnp.float32)
    kp = _pages(rng, P=n_pages, psz=psz, Kv=Kv, dh=dh)
    vp = _pages(rng, P=n_pages, psz=psz, Kv=Kv, dh=dh)
    table = np.zeros((B, max_pages), np.int32)
    nxt = 1
    for b, p in enumerate(positions):
        n = p // psz + 1
        table[b, :n] = np.arange(nxt, nxt + n)
        nxt += n
    return q, kp, vp, jnp.asarray(table), jnp.asarray(positions)


def test_quant_decode_kernel_matches_dequant_reference():
    """paged_decode_attention_quant over the int8 heap == the f32
    kernel over the DEQUANTIZED heap (same bytes reach the math)."""
    q, kp, vp, tbl, pos = _decode_setup(seed=3)
    kq, ks = KQR.quantize_pages_ref(kp)
    vq, vs = KQR.quantize_pages_ref(vp)
    kd = KQR.dequantize_pages_ref(kq, ks)
    vd = KQR.dequantize_pages_ref(vq, vs)
    for window in (None, 7):
        got = PK.paged_decode_attention_quant(
            q, kq, ks, vq, vs, tbl, pos, window=window, interpret=True)
        want = PK.paged_decode_attention(q, kd, vd, tbl, pos,
                                         window=window, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        ref = PR.paged_attention_ref(q, kd, vd, tbl, pos, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_quant_bsa_kernel_matches_dequant_reference():
    """block_sparse_prefill_quant over int8 slabs == block_sparse_prefill
    over the dequantized slabs (selection indices held identical)."""
    from repro.kernels.block_sparse_attention import kernel as BK
    rng = np.random.default_rng(4)
    B, N, H, Kv, dh, blk, P, K = 2, 8, 4, 2, 8, 4, 9, 3
    q = jnp.asarray(rng.standard_normal((B, N, H, dh)), jnp.float32)
    kb = _pages(rng, P=P, psz=blk, Kv=Kv, dh=dh)
    vb = _pages(rng, P=P, psz=blk, Kv=Kv, dh=dh)
    pool_ids = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    blk_pos = jnp.asarray([[0, 1, 2], [0, 1, 0]], jnp.int32)
    counts = jnp.asarray([3, 2], jnp.int32)
    pos0s = jnp.asarray([8, 4], jnp.int32)
    lengths = jnp.asarray([16, 12], jnp.int32)
    kq, ks = KQR.quantize_pages_ref(kb)
    vq, vs = KQR.quantize_pages_ref(vb)
    got = BK.block_sparse_prefill_quant(
        q, kq, ks, vq, vs, pool_ids, blk_pos, counts, pos0s, lengths,
        interpret=True)
    want = BK.block_sparse_prefill(
        q, KQR.dequantize_pages_ref(kq, ks),
        KQR.dequantize_pages_ref(vq, vs), pool_ids, blk_pos, counts,
        pos0s, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# -------------------------------------- dict-leaf gather/write plumbing


def test_gather_pages_quant_dequantizes_exactly():
    rng = np.random.default_rng(5)
    x = _pages(rng, P=7, psz=PAGE)
    q, s = KQR.quantize_pages_ref(x)
    tbl = jnp.asarray([[1, 3, 0], [2, 6, 5]], jnp.int32)
    got = A.gather_pages({"q": q, "s": s}, tbl)
    want = A.gather_pages(KQR.dequantize_pages_ref(q, s), tbl)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert A.kv_page_size({"q": q, "s": s}) == PAGE


def test_quant_block_write_roundtrips():
    """write_kv_rows_paged on dict leaves lands exactly the quantized
    bytes of the written rows (fresh pages -> one clean quantization,
    no rescale drift)."""
    rng = np.random.default_rng(6)
    B, N, Kv, dh, mp = 2, 16, 2, 4, 4
    psz, n_pages = PAGE, 1 + 2 * mp
    zero = jnp.zeros((n_pages, psz, Kv, dh), jnp.float32)
    pool = {"q": jnp.zeros(zero.shape, jnp.int8),
            "s": jnp.zeros((n_pages, Kv), jnp.float32)}
    table = np.zeros((B, mp), np.int32)
    table[0, :2] = [1, 2]
    table[1, 2:4] = [3, 4]                  # row 1 writes its 3rd block
    k_new = jnp.asarray(rng.standard_normal((B, N, Kv, dh)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((B, N, Kv, dh)), jnp.float32)
    pool2, _ = A.write_kv_rows_paged(
        dict(pool), {"q": pool["q"], "s": pool["s"]}, k_new, v_new,
        jnp.asarray(table), jnp.asarray([0, 16], jnp.int32),
        active=jnp.asarray([True, True]))
    got = A.gather_pages(pool2, jnp.asarray(table))
    qx, sx = KQR.quantize_pages_ref(
        k_new.reshape(B * 2, psz, Kv, dh))
    want_rows = KQR.dequantize_pages_ref(qx, sx).reshape(B, N, Kv, dh)
    np.testing.assert_array_equal(np.asarray(got[0, :N]),
                                  np.asarray(want_rows[0]))
    np.testing.assert_array_equal(np.asarray(got[1, 16:16 + N]),
                                  np.asarray(want_rows[1]))
    # untouched pages (incl. the null page) still dequantize to zero
    np.testing.assert_array_equal(np.asarray(got[1, :16]), 0.0)


def test_quant_token_write_zeroes_stale_tail():
    """The decode token write dequantizes the page, inserts the token,
    ZEROES every slot past the write offset, and requantizes — so stale
    bytes beyond the logical end can never poison the page's absmax.
    Writing a small token after a large one must not inherit the large
    token's scale on the untouched tail."""
    rng = np.random.default_rng(7)
    B, Kv, dh, mp = 1, 2, 4, 2
    n_pages = 3
    pool = {"q": jnp.zeros((n_pages, PAGE, Kv, dh), jnp.int8),
            "s": jnp.zeros((n_pages, Kv), jnp.float32)}
    table = jnp.asarray([[1, 0]], jnp.int32)
    big = jnp.asarray(rng.standard_normal((B, 1, Kv, dh)) * 50,
                      jnp.float32)
    small = jnp.asarray(rng.standard_normal((B, 1, Kv, dh)) * 0.1,
                        jnp.float32)

    def write(pool, tok, pos):
        k2, _ = A.write_kv_tok_paged(
            pool, {"q": pool["q"], "s": pool["s"]}, tok, tok, table,
            jnp.asarray([pos], jnp.int32), active=jnp.asarray([True]))
        return k2

    # position 3 first (slots 0..2 stay zero), then REWRITE pos 0 small:
    # the rewrite's zeroed tail drops slot 3's big value from the page,
    # so the fresh scale reflects only the small token
    pool = write(pool, big, 3)
    s_big = float(np.max(np.asarray(pool["s"][1])))
    pool = write(pool, small, 0)
    s_small = float(np.max(np.asarray(pool["s"][1])))
    assert s_small < s_big / 10
    got = A.gather_pages(pool, table)
    np.testing.assert_allclose(np.asarray(got[0, 0]),
                               np.asarray(small[0, 0]),
                               rtol=0.02, atol=1e-3)
    # slots past the write offset are exact zeros
    np.testing.assert_array_equal(np.asarray(got[0, 1:]), 0.0)


# ----------------------------------------------------------- end to end


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_config("tinyllama-1.1b", reduced=True)
    params = init_params(get_model(cfg).specs(cfg), jax.random.key(0))
    return cfg, params


def _paged(cfg, quant=False):
    return cfg.with_(kv_layout="paged", kv_page_size=PAGE,
                     kv_quant=quant)


def test_quant_prefill_decode_logits_allclose(dense_setup):
    """End-to-end contract: the quantized paged heap's prefill and
    decode logits match the f32 paged heap within the documented
    (generous) tolerance — same runtime stack the scheduler drives."""
    cfg, params = dense_setup
    rng = np.random.default_rng(8)
    N = cfg.ff.block_size
    toks = rng.integers(0, cfg.vocab, (1, N)).astype(np.int32)
    mp = N // PAGE + 1                      # + 1 page of decode headroom

    def run(quant):
        runtime = make_runtime(_paged(cfg, quant), params)
        cache = runtime.init_cache_paged(1 + mp, PAGE)
        table = np.zeros((1, mp), np.int32)
        table[0, :] = np.arange(1, mp + 1)
        cache, logits_p = runtime.prefill_blocks_paged(
            cache, toks, table, [0], [True], [N], [True])
        logits_d, greedy, _ = runtime.decode_step_paged(
            cache, np.asarray(logits_p).argmax(-1).astype(np.int32),
            table, [N], [True])
        return np.asarray(logits_p), np.asarray(logits_d)

    lp32, ld32 = run(False)
    lpq, ldq = run(True)
    assert not np.array_equal(lpq, lp32)    # quantization really engaged
    np.testing.assert_allclose(lpq, lp32, rtol=E2E_RTOL, atol=E2E_ATOL)
    np.testing.assert_allclose(ldq, ld32, rtol=E2E_RTOL, atol=E2E_ATOL)


def test_quant_scheduler_accounting_and_compile_flat(dense_setup):
    """A churny quant-heap stream (tight heap -> preemptions): page
    accounting stays exact, tables reset at drain, and compile counts
    stay flat — the quantized heap changes BYTES, not executables."""
    cfg, params = dense_setup
    runtime = make_runtime(_paged(cfg, quant=True), params)
    sched = ContinuousBatchingScheduler(runtime, n_slots=3, cache_len=96,
                                        n_pages=14)
    counts = sched.warmup()
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab, int(n)).tolist()
               for n in (40, 36, 33, 20, 18)]
    for i, p in enumerate(prompts):
        sched.submit(Request(rid=i, prompt=p, max_new=24))
    outs = sched.run()
    assert sorted(outs) == list(range(5))
    assert all(len(o.tokens) == 24 for o in outs.values())
    pool = sched.pool
    assert pool.n_free_pages == pool.n_pages - 1
    assert (pool.page_table == 0).all()
    assert pool.total_page_allocs == pool.total_page_frees
    assert runtime.compile_counts() == counts
    # the int8 heap really is the storage: dict leaves, int8 q
    leaf = next(iter(pool.cache.values()))
    assert isinstance(leaf, dict) and leaf["q"].dtype == jnp.int8
