"""Sharding rules + a subprocess dry-run on a small host mesh.

The 512-device production dry-run is exercised by launch/dryrun.py; here
we verify (a) rule resolution incl. divisibility fallbacks, (b) the
shard_map flash-decode numerics, and (c) that a REDUCED arch lowers &
compiles on an 8-device mesh in a fresh subprocess (device count must be
set before jax init, so it cannot run in-process)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.nn.param import ParamSpec
from repro.distributed import sharding as SHD


class FakeMesh:
    """Duck-typed mesh for rule resolution (no jax devices touched)."""

    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def test_rule_resolution_divisibility():
    mesh = FakeMesh({"data": 16, "model": 16})
    # divisible -> sharded
    assert SHD.pspec_for(("embed", "mlp"), (2048, 5632), mesh) == \
        P(None, "model")
    # 6 heads not divisible by 16 -> replicated
    assert SHD.pspec_for(("embed", "heads", "head_dim"), (384, 6, 64),
                         mesh) == P(None, None, None)
    # batch resolves to all data axes
    assert SHD.pspec_for(("batch", "seq"), (256, 4096), mesh) == \
        P(("data",), None)


def test_rule_resolution_multipod():
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    assert SHD.pspec_for(("batch", "seq"), (256, 4096), mesh) == \
        P(("pod", "data"), None)
    # batch=1 (long_500k) cannot shard 32-ways -> replicated
    assert SHD.pspec_for(("batch", "seq"), (1, 1), mesh) == P(None, None)


def test_no_duplicate_mesh_axes():
    mesh = FakeMesh({"data": 4, "model": 4})
    # two logical axes mapping to "model": second occurrence dropped
    spec = SHD.pspec_for(("mlp", "vocab"), (64, 64), mesh)
    flat = [e for e in spec if e is not None]
    assert len(flat) == len(set(flat)) == 1


def test_param_shardings_tree():
    mesh = FakeMesh({"data": 2, "model": 4})
    specs = {"w": ParamSpec((64, 128), ("embed", "mlp"))}
    # param_shardings needs a real Mesh for NamedSharding; just check
    # pspec resolution here
    assert SHD.pspec_for(("embed", "mlp"), (64, 128), mesh) == \
        P(None, "model")


SUBPROCESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.models.registry import get_model
    from repro.nn import param as PM
    from repro.distributed.sharding import param_shardings
    from repro.launch.mesh import make_host_mesh
    from repro.training.train import make_loss_fn

    cfg = get_config("{arch}", reduced=True)
    mesh = make_host_mesh(2, 4)
    model = get_model(cfg)
    specs = model.specs(cfg)
    shard = param_shardings(specs, mesh)
    aparams = PM.abstract_params(specs, shard)
    loss_fn = make_loss_fn(cfg)
    batch = {{
        "tokens": jax.ShapeDtypeStruct((4, 64), jnp.int32),
        "labels": jax.ShapeDtypeStruct((4, 64), jnp.int32),
    }}
    with jax.sharding.set_mesh(mesh):
        lowered = jax.jit(lambda p, b: loss_fn(p, b)[0]).lower(
            aparams, batch)
        compiled = lowered.compile()
    ca = compiled.cost_analysis()
    print(json.dumps({{"flops": ca["flops"],
                       "devices": len(jax.devices())}}))
""")


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "qwen2-moe-a2.7b"])
def test_subprocess_dryrun_host_mesh(arch):
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run(
        [sys.executable, "-c", SUBPROCESS_SCRIPT.format(arch=arch)],
        capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 8
    assert rec["flops"] > 0


def test_peaks_keyed_by_device_kind():
    from repro.launch.mesh import PRODUCTION_DEVICE_KIND, peaks
    v5e = peaks("TPU v5 lite")
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bytes_s"] == 819e9
    assert peaks(PRODUCTION_DEVICE_KIND) is v5e
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")
