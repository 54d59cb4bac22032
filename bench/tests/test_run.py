"""A whole run of a tiny cell on the CPU, past the harness's look for a
chip: a sound run reads correct, and each fault a served cell can have
(a token altered where it is produced, half of a batched prefill left
out), and the fp8 control, read not correct."""
import json
import pathlib

import jax
import pytest

from bench import check, peaks, spec

DATA = pathlib.Path(__file__).parent / "data"
SEED = 2**33 + 17       # above 32 bits, like the driver's seeds
LIMIT = json.loads((DATA / "tiny-limits.json").read_text())


def tiny_cell(layout="slot", **traffic_changes):
    config = json.loads((DATA / "tiny.json").read_text())
    config["serving"]["kv_layout"] = layout
    traffic = json.loads((DATA / "tiny-traffic.json").read_text())
    traffic.update(traffic_changes)
    return spec.Cell("tiny", 1, config, traffic, LIMIT, (), ())


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        {"flops_bf16": 1e12, "hbm_bytes_s": 1e11})


def run(cell):
    """A closed loop does not drain at the window's end: give it time
    to finish requests on a busy CPU."""
    from bench import run as R
    seconds = 2.0 if cell.traffic["loop"] == "open" else 6.0
    return R.run_cell(cell, SEED, seconds, False, jax.devices())


@pytest.mark.parametrize("layout,traffic", [
    ("slot", {}), ("paged", {}), ("paged", {"loop": "closed", "clients": 4})])
def test_sound_run_is_correct(layout, traffic):
    res = run(tiny_cell(layout, **traffic))
    assert res["correct"], res["checks"]
    assert res["checks"]["tokens_checked"]["value"] > 0
    assert list(res)[-1] == "checks"


def _alter_decode(monkeypatch):
    """Every decode step serves token id + 1 for each row."""
    from repro.serving.runtime import _JittedRuntime
    orig = _JittedRuntime.decode_step

    def altered(self, *a, **kw):
        logits, greedy, cache = orig(self, *a, **kw)
        return logits, (greedy + 1) % self.cfg.vocab, cache
    monkeypatch.setattr(_JittedRuntime, "decode_step", altered)


def _alter_prefill(monkeypatch):
    """The first token, sampled from the prefill logits, is another
    token: the logits are rolled by one over the vocabulary."""
    from repro.serving.runtime import _JittedRuntime
    for name in ("prefill_block", "prefill_blocks"):
        orig = getattr(_JittedRuntime, name)

        def altered(self, *a, _orig=orig, **kw):
            cache, logits = _orig(self, *a, **kw)
            return cache, jax.numpy.roll(logits, 1, axis=-1)
        monkeypatch.setattr(_JittedRuntime, name, altered)


def _drop_half_prefill_batch(monkeypatch):
    """Each batched prefill runs the first half of its live rows only:
    the rest are marked inactive, so their blocks' KV is never written
    and their logits are those of a padding row."""
    import numpy as np
    from repro.serving.runtime import _JittedRuntime
    orig = _JittedRuntime.prefill_blocks

    def dropped(self, cache, tokens, slots, pos0s, is_dense, lengths,
                active, **kw):
        active = np.array(active)
        live = np.flatnonzero(active)
        active[live[(len(live) + 1) // 2:]] = False
        return orig(self, cache, tokens, slots, pos0s, is_dense, lengths,
                    active, **kw)
    monkeypatch.setattr(_JittedRuntime, "prefill_blocks", dropped)


@pytest.mark.parametrize("fault", [_alter_decode, _alter_prefill,
                                   _drop_half_prefill_batch])
def test_altered_token_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    # a closed loop of four clients keeps the batched prefill full
    res = run(tiny_cell(loop="closed", clients=4))
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        LIMIT["max_logit_gap"]["limit"]


def test_fp8_control_exceeds_limit():
    """The reference in fp8, at the positions of a sound run, reads
    above the limit the program's runs stay under."""
    from bench import harness, traffic
    cell = tiny_cell()
    server = harness.setup(cell, SEED, check_kernels=False)
    planned = traffic.plan(cell.traffic, cell.config["vocab_size"], SEED,
                           2.0)
    window = harness.run_window(server, planned, 2.0)
    server.close()
    tracks = list(window.tracks.values())
    picked = check.sample(tracks, SEED, 40, 12, 40000)
    served = check.served_gap(cell.config, SEED, picked)
    control = check.control_gap(cell.config, SEED, picked,
                                served["ref_logits"])
    limit = LIMIT["max_logit_gap"]["limit"]
    assert served["max_logit_gap"] <= limit
    assert control["max_logit_gap"] > limit
