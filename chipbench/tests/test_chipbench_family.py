"""Model families: one added as data alone, one missing, and the dense
family, moved out of the harness, giving what the harness gave before.

The recorded numbers were read with the harness as it was before the
dense family had a file of its own (reference, weight view and counts in
``reference.py``, ``weights.py`` and ``counts.py``): the move must keep
every weight's bytes, every reading of ``correct`` and every count.
"""
from __future__ import annotations

import hashlib
import json
import time

import jax
import numpy as np
import pytest

import smoke_root
from chipbench import bench, calibrate, cell, weights

SEED = 2**31 + 12345
dense = bench.family("dense")

# sha256 over every leaf's bytes, in tree order, of the smoke config's
# weights made from SEED
WEIGHTS_SHA256 = (
    "a1fe17535b5bb4631e6b3c1daaec2f5109193876eaa943ab983bd82118dba3c8")
# calibrate.readings of the smoke cell on seeds 1-3: max_gap, then the
# int8 and float8 controls
READINGS = [
    (0.008292198181152344, 0.019732952117919922, 0.20185017585754395),
    (0.0, 0.014623165130615234, 0.38998937606811523),
    (0.0, 0.04075217247009277, 0.29631757736206055),
]
WORK = {
    ("phi3-mini-3.8b", "prefill", 4, 1536): (
        {"proj": 44530220924928.0, "qk": 928316915712.0,
         "av": 928316915712.0, "lm_head": 788004864.0},
        {"weights.layers": 7247757312.0, "weights.lm_head": 197001216.0,
         "weights.norms": 399360.0, "kv.write": 2415919104.0,
         "embed.rows": 37748736.0}),
    ("phi3-mini-3.8b", "decode", 4, 1089): (
        {"proj": 28991029248.0, "qk": 856424448.0, "av": 856424448.0,
         "lm_head": 788004864.0},
        {"weights.layers": 7247757312.0, "weights.lm_head": 197001216.0,
         "weights.norms": 399360.0, "kv.read": 1712848896.0,
         "embed.rows": 24576.0}),
    ("yi-34b-tp4", "decode", 16, 600): (
        {"proj": 535528734720.0, "qk": 4128768000.0, "av": 4128768000.0,
         "lm_head": 14680064000.0},
        {"weights.layers": 33470545920.0, "weights.lm_head": 917504000.0,
         "weights.norms": 874496.0, "kv.read": 1179648000.0,
         "embed.rows": 229376.0}),
}


def _on_cpu(monkeypatch):
    jax.config.update("jax_enable_compilation_cache", False)
    monkeypatch.setattr(cell, "device_check", lambda chips: (
        jax.devices()[:chips],
        {"bf16_flop_per_s": 1e12, "hbm_bytes_per_s": 1e11}))


# --- a family added as data alone ---

@pytest.mark.parametrize("reference_bias", [True, False])
def test_family_added_as_data_decides_correct(tmp_path, monkeypatch,
                                              reference_bias):
    """The dense family refuses q, k and v biases; a family file and a
    config that names it serve them, and ``correct`` follows the family's
    reference: true where it adds the biases, false where it leaves them
    out."""
    _on_cpu(monkeypatch)
    root = smoke_root.make(tmp_path, reference_bias=reference_bias)
    c = bench.load_cell(smoke_root.BIAS_CELL, root)
    cfg = bench.model_config(c.config, c.family)
    assert cfg.qkv_bias
    with pytest.raises(bench.SpecError, match="plain dense decoder"):
        bench.model_config(c.config, dense)
    r = cell.run(smoke_root.BIAS_CELL, SEED, 0.3, False, time.perf_counter(),
                 root)
    gap = r["check"]["max_gap"]["value"]
    if reference_bias:
        assert r["correct"], r["check"]
    else:
        assert not r["correct"] and gap > smoke_root.SMOKE_LIMIT, r["check"]


def test_config_naming_a_missing_family_is_refused(tmp_path):
    root = smoke_root.make(tmp_path)
    path = root / "chipbench" / "configs" / "smoke-dense.json"
    c = json.loads(path.read_text())
    c["family"] = "no-such-family"
    path.write_text(json.dumps(c))
    with pytest.raises(bench.SpecError,
                       match="chipbench/families/no-such-family.py"):
        bench.load_cell(smoke_root.CELL, root)


# --- the dense family gives what the harness gave before ---

def test_weights_are_the_same_bytes():
    config = json.loads((smoke_root.DATA / "smoke-dense.json").read_text())
    cfg = bench.model_config(config, dense)
    params_abs, _ = cell._abstract_params(cfg)
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    params = weights.make(params_abs, jax.tree.map(lambda _: one, params_abs),
                          SEED, cfg.d_model, dense.WEIGHTS)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256


def test_readings_of_correct_are_the_same(tmp_path, monkeypatch):
    _on_cpu(monkeypatch)
    root = smoke_root.make(tmp_path)
    got = [(r["max_gap"], r["control.int8"], r["control.float8"])
           for r in calibrate.readings(smoke_root.CELL, [1, 2, 3], {1, 2, 3},
                                       ["int8", "float8"], root)]
    assert got == READINGS


@pytest.mark.parametrize("config,step,batch,length", list(WORK))
def test_work_counts_are_the_same(config, step, batch, length):
    path = smoke_root.REPO / "chipbench" / "configs" / f"{config}.json"
    work = getattr(dense, step)(json.loads(path.read_text()), batch, length)
    assert (work.flops, work.bytes) == WORK[config, step, batch, length]
