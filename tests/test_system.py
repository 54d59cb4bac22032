"""End-to-end behaviour tests: distillation improves fidelity, the
engine serves FastForward-sparsified models, checkpoints round-trip,
and the ablation orderings the paper reports hold qualitatively."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.registry import get_model
from repro.nn.param import init_params
from repro.core import distill as DI
from repro.core import sparse_ffn as S
from repro.serving.engine import Engine
from repro.training.checkpoint import save_checkpoint, load_checkpoint
from repro.data.synthetic import batches


@pytest.fixture(scope="module")
def trained_ffn():
    """A small FFN with tile-structured weights (so flocking exists at
    the kernel's tile granularity) and a distilled predictor+compensator:
    tile t's gate weights respond to input direction t; each block's
    input lives in two of those directions."""
    cfg = get_config("tinyllama-1.1b", reduced=True)
    tile = cfg.ff.tile
    n_tiles = cfg.d_ff // tile
    from repro.core.fastforward import fastforward_ffn_spec
    ffn = init_params(fastforward_ffn_spec(cfg), jax.random.key(0))
    rng = np.random.default_rng(5)
    Q, _ = np.linalg.qr(rng.standard_normal((cfg.d_model, cfg.d_model)))
    basis = Q[:, :n_tiles].T
    wg = np.asarray(ffn["wg"]) * 0.3
    for t in range(n_tiles):
        wg[:, t * tile:(t + 1) * tile] += np.outer(
            basis[t], np.abs(rng.standard_normal(tile)) + 0.5) * 2.0
    ffn = dict(ffn)
    ffn["wg"] = jnp.asarray(wg, jnp.float32)

    def block_gen(seed=3):
        r = np.random.default_rng(seed)
        while True:
            g1 = r.integers(0, n_tiles, size=8)
            g2 = (g1 + 1 + r.integers(0, n_tiles - 1, size=8)) % n_tiles
            amp = 2.0 + r.standard_normal((8, cfg.ff.block_size, 1))
            sig = (basis[g1][:, None, :] + basis[g2][:, None, :]) * amp
            noise = r.standard_normal(
                (8, cfg.ff.block_size, cfg.d_model)) * 0.5
            yield jnp.asarray(sig + noise, jnp.float32)

    tp, hist = DI.train_fastforward_layer(
        ffn, block_gen(), cfg, jax.random.key(1), steps=150, lr=2e-3)
    return cfg, ffn, tp, hist, block_gen


def test_distillation_losses_decrease(trained_ffn):
    cfg, ffn, tp, hist, _ = trained_ffn
    first = np.mean([h["pred_bce"] for h in hist[:10]])
    last = np.mean([h["pred_bce"] for h in hist[-10:]])
    assert last < first, (first, last)
    # compensator: compare within the predicted-mask phase (the phase
    # switch at warmup_frac raises the raw error level by design)
    switch = int(len(hist) * 0.3)
    c_first = np.mean([h["comp_mse"] for h in hist[switch:switch + 10]])
    c_last = np.mean([h["comp_mse"] for h in hist[-10:]])
    assert c_last <= c_first * 1.1


def test_trained_predictor_beats_random(trained_ffn):
    cfg, ffn, tp, _, block_gen = trained_ffn
    gen = block_gen()
    x = next(gen)
    keep = 1.0 - cfg.ff.sparsity
    agree = float(DI.predictor_agreement(tp, ffn, x, keep, cfg.ff.tile))
    assert agree > 0.7, agree   # random selection would land near 0.5


def test_compensator_improves_fidelity(trained_ffn):
    cfg, ffn, tp, _, block_gen = trained_ffn
    from repro.core import compensator as C
    x = next(block_gen())
    keep = 1.0 - cfg.ff.sparsity
    mask = DI.predicted_mask(tp, x, keep, cfg.ff.tile)
    y_dense = S.ffn_dense(ffn, x, cfg.act)
    y_sparse = S.ffn_masked(ffn, x, mask[..., None, :], cfg.act)
    e_raw = float(jnp.mean((y_sparse - y_dense) ** 2))
    y_comp = y_sparse + C.compensate(tp["comp"], x)
    e_comp = float(jnp.mean((y_comp - y_dense) ** 2))
    assert e_comp < e_raw, (e_comp, e_raw)


def test_predictor_ordering_matches_paper(trained_ffn):
    """Paper Table 7 ordering: per-block dynamic (oracle) >= trained
    predictor > first-block static, measured as output fidelity."""
    cfg, ffn, tp, _, block_gen = trained_ffn
    gen = block_gen()
    keep = 1.0 - cfg.ff.sparsity
    tile = cfg.ff.tile

    def fid(mask_fn, n=8):
        errs = []
        first = next(gen)
        m_first, _ = DI.oracle_mask(ffn, first, keep, tile, cfg.act)
        for _ in range(n):
            x = next(gen)
            m = mask_fn(x, m_first)
            y_d = S.ffn_dense(ffn, x, cfg.act)
            y_s = S.ffn_masked(ffn, x, m[..., None, :], cfg.act)
            errs.append(float(jnp.mean((y_s - y_d) ** 2)
                              / jnp.mean(y_d ** 2)))
        return np.mean(errs)

    e_oracle = fid(lambda x, mf: DI.oracle_mask(ffn, x, keep, tile,
                                                cfg.act)[0])
    e_trained = fid(lambda x, mf: DI.predicted_mask(tp, x, keep, tile))
    e_static = fid(lambda x, mf: jnp.broadcast_to(mf[:1], mf.shape))
    assert e_oracle <= e_trained * 1.05
    assert e_trained < e_static, (e_trained, e_static)


def test_engine_sparse_and_dense_serve():
    cfg = get_config("tinyllama-1.1b", reduced=True)
    params = init_params(get_model(cfg).specs(cfg), jax.random.key(0))
    prompts = [list(np.random.default_rng(1).integers(0, cfg.vocab, 70))]
    res_sparse = Engine(cfg, params).generate(prompts, max_new=4)
    res_dense = Engine(cfg.with_ff(enabled=False), params).generate(
        prompts, max_new=4)
    assert res_sparse.tokens.shape == res_dense.tokens.shape == (1, 4)


def test_checkpoint_roundtrip(tmp_path):
    cfg = get_config("granite-8b", reduced=True)
    model = get_model(cfg)
    params = init_params(model.specs(cfg), jax.random.key(0))
    save_checkpoint(str(tmp_path / "ck"), params, {"arch": cfg.name})
    loaded, meta = load_checkpoint(str(tmp_path / "ck"))
    assert meta["arch"] == cfg.name
    flat_a = jax.tree.leaves(params)
    flat_b = jax.tree.leaves(loaded)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_synthetic_data_is_deterministic():
    g1 = batches(256, 2, 32, seed=5)
    g2 = batches(256, 2, 32, seed=5)
    b1, b2 = next(g1), next(g2)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])


def test_compile_cache_dir_env_or_fixed_checkout_path(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set in code;
    unset, the cache is the fixed .jax_cache inside the checkout."""
    import pathlib
    from repro.launch import compile_cache as CC
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert CC.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = pathlib.Path(CC.enable_compile_cache())
        assert path == CC.DEFAULT_DIR and path.name == ".jax_cache"
        assert (path.parent / "src" / "repro").is_dir()
        assert jax.config.jax_compilation_cache_dir == str(path)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
