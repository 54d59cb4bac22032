"""What depends on the architecture is found by the configuration's
`arch` key (bench/archs): the dense module gives each benchmark
configuration the ModelConfig, weights, pool sizes and work counts the
benchmark gave it before the move (values recorded at that commit), and
a module of another architecture enters without an edit to the generic
code."""
import hashlib
import json
import pathlib
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import archs, spec, weights
from bench.archs import dense

BENCH = pathlib.Path(__file__).parents[1]
DATA = pathlib.Path(__file__).parent / "data"


def config(name):
    path = DATA / name if name.startswith("tiny") else BENCH / "configs" / name
    return json.loads(path.read_text())


# ------------------------------------------------------------- identity

def test_model_configs_as_recorded():
    from repro.models.base import FastForwardConfig, ModelConfig
    granite = ModelConfig(
        name="granite-8b", arch="dense", n_layers=18, d_model=4096,
        n_heads=32, n_kv_heads=8, d_ff=14336, vocab=49152, d_head=128,
        ff=FastForwardConfig(enabled=True, predictor_dim=256,
                             compensator_dim=512),
        param_dtype="bfloat16", remat=False,
        source="https://arxiv.org/abs/2405.04324")
    phi3 = ModelConfig(
        name="phi3-mini-3.8b", arch="dense", n_layers=32, d_model=3072,
        n_heads=32, n_kv_heads=32, d_ff=8192, vocab=32064, d_head=96,
        ff=FastForwardConfig(enabled=True, predictor_dim=256,
                             compensator_dim=384),
        kv_layout="paged", param_dtype="bfloat16", remat=False,
        source="https://huggingface.co/microsoft/Phi-3-mini-128k-instruct")
    assert spec.model_config(config("granite-8b.json")) == granite
    assert spec.model_config(config("phi3-mini-3.8b.json")) == phi3


@pytest.mark.parametrize("cell,want", [
    ("granite-8b.code-context", spec.Sizes(8256, 3, 2, 0, 0)),
    ("phi3-mini.doc-batch", spec.Sizes(4128, 32, 4, 60, 128)),
])
def test_pool_sizes_as_recorded(cell, want):
    c = spec.load_cell(cell)
    assert spec.sizes(c.config, c.traffic) == want


def tree_digest(params) -> str:
    h = hashlib.sha256()
    for kp, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        for part in (weights._tree_path(kp), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_tiny_weight_tree_as_recorded():
    params = weights.make_params(config("tiny.json"), 2**33 + 29)
    assert tree_digest(params) == (
        "eed9f77f4d634245dc88ae2bb9d52c5f9c9dd62409adf2db4a2696903805f1ea")


ROWS = [(0, 128, True, False), (128, 128, False, False),
        (256, 128, False, False), (384, 100, True, True),
        (1024, 128, False, True)]

#: per configuration: prefill_row_flops of each of ROWS; then
#: sparse_ffn_kernel_work of ROWS with nothing, W_gate, and x and the
#: output on the chip; then attn_prefill_kernel_work of ROWS with
#: nothing, K, and q and the output on the chip
RECORDED = {
    "granite-8b.json": (
        [1007457140736.0, 625949540352.0, 630781378560.0, 798390288384.0,
         660175060992.0],
        [(67645734912.0, 185597952.0), (67645734912.0, 126877696.0),
         (67645734912.0, 176160768.0)],
        [(4205740032.0, 24887296.0), (4205740032.0, 19963904.0),
         (4205740032.0, 9846784.0)]),
    "phi3-mini-3.8b.json": (
        [930959327232.0, 647726366720.0, 654168817664.0, 742057967616.0,
         693020524544.0],
        [(28991029248.0, 82575360.0), (28991029248.0, 57409536.0),
         (28991029248.0, 75497472.0)],
        [(3154305024.0, 40820736.0), (3154305024.0, 26050560.0),
         (3154305024.0, 29540352.0)]),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_work_counts_as_recorded(name):
    rows_flops, ffn, attn = RECORDED[name]
    arch = archs.module(config(name))
    assert arch is dense
    s = arch.Shapes(config(name))
    assert [arch.prefill_row_flops(s, *r) for r in ROWS] == rows_flops
    assert [arch.sparse_ffn_kernel_work(s, ROWS, frozenset(oc))
            for oc in ((), ("wg",), ("x", "out"))] == ffn
    assert [arch.attn_prefill_kernel_work(s, ROWS, frozenset(oc))
            for oc in ((), ("k",), ("q", "out"))] == attn


# ----------------------------------------------------- a new architecture

def per_expert(axes, gain, first=0):
    """[E, ...]: expert e of the leaf from its own key, first + e, so a
    chip holding experts first.. draws what they hold whole."""
    def rule(root, path, shape, layer):
        key = weights.leaf_key(root, path, layer)
        z = jax.vmap(lambda e: jax.random.normal(
            jax.random.fold_in(key, e), shape[1:], jnp.float32))(
                first + jnp.arange(shape[0]))
        return z * weights.std(shape[1:], axes, gain)
    return rule


def moe_module():
    """A bench/archs module for qwen2-moe-style layers: GQA with bias,
    routed experts top-k, a shared expert under FastForward."""
    from repro.models.base import FastForwardConfig, ModelConfig
    m = types.ModuleType("bench.archs.moe")

    def model_config(c):
        ff = c["fastforward"]
        return ModelConfig(
            name=c["name"], arch=c["arch"], n_layers=c["num_hidden_layers"],
            d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
            n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
            d_ff=0, vocab=c["vocab_size"], qkv_bias=c["attention_bias"],
            n_experts=c["num_experts"], top_k=c["num_experts_per_tok"],
            d_ff_expert=c["moe_intermediate_size"],
            n_shared_experts=(c["shared_expert_intermediate_size"]
                              // c["moe_intermediate_size"]),
            rope_theta=c["rope_theta"], param_dtype=c["torch_dtype"],
            remat=False, source=c["source"],
            ff=FastForwardConfig(**{k: ff[k] for k in (
                "enabled", "sparsity", "block_size", "tile",
                "predictor_dim", "compensator_dim")}))

    def kv_bytes_per_token(c):
        return (2 * c["num_hidden_layers"] * c["num_key_value_heads"]
                * c["head_dim"] * 2)

    tiles = "layers/moe/shared/tiles"
    rules = {"embed/table": weights.normal((), 1.0),
             "lm_head/table": weights.normal((1,), 1.0),
             "ln_f/scale": weights.ones, "layers/ln1/scale": weights.ones,
             "layers/ln2/scale": weights.ones,
             "layers/moe/router": weights.normal((0,), 1.0),
             "layers/moe/shared_gate": weights.normal((0,), 1.0),
             "layers/moe/shared/wd": weights.tile_weighted(tiles, (0,)),
             "layers/moe/shared/pred/w2": weights.tile_ranked(tiles, (0,))}
    for p in ("wq", "wk", "wv"):
        rules[f"layers/attn/{p}"] = weights.normal((0,), 1.0)
        rules[f"layers/attn/b{p[1]}"] = weights.normal((), 0.1)
    rules["layers/attn/wo"] = weights.normal((0, 1), 1.0)
    for p in ("wg_e", "wu_e", "wd_e"):
        rules[f"layers/moe/{p}"] = per_expert((0,), 1.0)
    for p in ("wg", "wu", "pred/w1", "comp/w1", "comp/w2"):
        rules[f"layers/moe/shared/{p}"] = weights.normal((0,), 1.0)
    rules["layers/moe/shared/pred/q_pred"] = weights.normal((), 1.0)

    class Shapes:
        def __init__(self, c):
            self.L, self.D = c["num_hidden_layers"], c["hidden_size"]
            self.E, self.K = c["num_experts"], c["num_experts_per_tok"]
            self.Fe = c["moe_intermediate_size"]
            self.Fs = c["shared_expert_intermediate_size"]
            self.V, self.N = c["vocab_size"], c["fastforward"]["block_size"]
            self.H, self.dh = c["num_attention_heads"], c["head_dim"]

    def prefill_row_flops(s, pos0, n, dense_, final):
        router = 2.0 * n * s.D * s.E
        routed = 6.0 * n * s.K * s.D * s.Fe
        shared = 6.0 * n * s.D * s.Fs * (1.0 if dense_ else 0.5)
        return (s.L * (router + routed + shared)
                + (2.0 * s.D * s.V if final else 0.0))

    def sparse_ffn_kernel_work(s, rows, on_chip=frozenset(), layer=0):
        """The shared expert's kept half, sparse rows; bytes left out."""
        return 3.0 * s.N * s.D * s.Fs * sum(not r[2] for r in rows), 0.0

    def attn_prefill_kernel_work(s, rows, on_chip=frozenset(), layer=0):
        keys = sum(n * p0 + n * (n + 1) // 2 for p0, n, _, _ in rows)
        return 4.0 * s.H * s.dh * keys, 0.0

    m.model_config, m.kv_bytes_per_token, m.RULES = (
        model_config, kv_bytes_per_token, rules)
    m.Shapes, m.prefill_row_flops = Shapes, prefill_row_flops
    m.sparse_ffn_kernel_work = sparse_ffn_kernel_work
    m.attn_prefill_kernel_work = attn_prefill_kernel_work
    return m


@pytest.fixture
def moe(monkeypatch):
    m = moe_module()
    monkeypatch.setitem(sys.modules, "bench.archs.moe", m)
    return m


def test_another_architecture_enters_as_its_own_module(moe):
    from repro.models.registry import get_model
    from repro.nn.param import is_spec
    from bench import trace as TR
    from bench.metrics import prefill_mfu, sparse_ffn_roofline
    c = config("tiny-moe.json")
    assert archs.module(c) is moe
    cfg = spec.model_config(c)
    assert (cfg.arch, cfg.n_experts, cfg.top_k, cfg.n_shared_experts,
            cfg.d_ff_expert, cfg.qkv_bias) == ("moe", 4, 2, 2, 128, True)

    seed = 2**33 + 41
    params = weights.make_params(c, seed)
    shapes = lambda tree, **kw: {
        weights._tree_path(kp): tuple(a.shape) for kp, a in
        jax.tree_util.tree_flatten_with_path(tree, **kw)[0]}
    want = shapes(get_model(cfg).specs(cfg), is_leaf=is_spec)
    assert shapes(params) == want
    assert {"layers/attn/bk", "layers/moe/router", "layers/moe/wg_e",
            "layers/moe/shared/wd", "layers/moe/shared_gate"} <= set(want)
    # a chip's share of the experts holds what those experts hold whole
    # (drawn under jit, as the program's tree and the reference are)
    path = "layers/moe/wg_e"
    share = jax.jit(lambda kd: weights.draw(
        {path: per_expert((0,), 1.0, first=2)}, jax.random.wrap_key_data(kd),
        path, (2, 128, 128), jnp.bfloat16, layer=1))(
            jax.random.key_data(weights.root_key(seed)))
    assert jnp.array_equal(params["layers"]["moe"]["wg_e"][1, 2:], share)

    traffic = json.loads((DATA / "tiny-traffic.json").read_text())
    per_tok = 2 * 2 * 4 * 32 * 2
    assert spec.sizes(c, traffic) == spec.Sizes(
        544 + 8, 4, 2, 20000000 // (32 * per_tok) + 1, 32)

    # the generic readers count the work by the module: the model step,
    # and a kernel's executions in a traced prefill call (one a layer)
    rows = [(0, 32, True, False), (32, 32, False, False),
            (64, 20, True, True)]
    k = "%sparse_ffn_batched.3 = f32[1]{0} custom-call(bf16[1]{0} %x)"
    events = {TR.MODULES: [("jit__prefill_blocks_impl", 0, 10**5, "")],
              TR.OPS: [(k, 10, 10**4, ""), (k, 2 * 10**4, 10**4, "")]}
    ctx = types.SimpleNamespace(
        arch=moe, shapes=moe.Shapes(c), trace_s=1.0,
        peak={"flops_bf16": 1e12, "hbm_bytes_s": 1e11},
        traced_calls=[types.SimpleNamespace(kind="prefill", rows=rows)],
        trace=TR.Trace([events], [], (0, 10**5)))
    D, E, K, Fe, Fs = 128, 4, 2, 128, 256
    ops = 2 * (2 * 84 * D * E + 6 * 84 * K * D * Fe
               + 6 * 52 * D * Fs + 3 * 32 * D * Fs) + 2 * D * 512
    assert prefill_mfu.read(ctx) == pytest.approx(100.0 * ops / 1e12)
    # two executions of 10 us each, 3*32*D*Fs operations each at 1e12/s
    assert sparse_ffn_roofline.read(ctx) == pytest.approx(
        100.0 * 3 * 32 * D * Fs / 1e12 / 1e-5)


def test_unknown_arch_fails_when_the_cell_loads(tmp_path):
    c = dict(config("tiny.json"), arch="hyena")
    (tmp_path / "c.json").write_text(json.dumps(c))
    bench = {"configs": [{"name": "c", "file": str(tmp_path / "c.json")}],
             "workloads": [{"name": "c.x", "config": "c", "traffic": "x",
                            "chips": 1}],
             "end_to_end": [], "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(ValueError, match=r"add bench/archs/hyena\.py"):
        spec.load_cell("c.x", tmp_path / "BENCHMARK.json")
