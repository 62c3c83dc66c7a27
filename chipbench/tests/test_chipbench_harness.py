"""The harness on the CPU at smoke size: a cell added as data only, the
device check, and ``correct`` coming out false for each fault planted in
the timed path."""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import smoke_root
from chipbench import bench, cell

REPO = smoke_root.REPO
SEED = 2**31 + 12345  # past 32 signed bits, as the driver's seeds are


@pytest.fixture
def root(tmp_path, monkeypatch):
    """A root with the smoke cell; the device check passes the CPU."""
    jax.config.update("jax_enable_compilation_cache", False)
    monkeypatch.setattr(cell, "device_check", lambda chips: (
        jax.devices()[:chips],
        {"bf16_flop_per_s": 1e12, "hbm_bytes_per_s": 1e11}))
    return smoke_root.make(tmp_path)


def _run(root, trace=False, seed=SEED):
    return cell.run(smoke_root.CELL, seed, 0.3, trace, time.perf_counter(),
                    root)


def test_cell_added_as_data_runs_correct(root):
    r = _run(root)
    assert r["correct"], r["check"]
    assert list(r) == ["correct", "attempted", "failed", "metrics",
                       "device", "check"]
    assert set(r["metrics"]) == {"setup_s", "prefill_ms", "decode_ms",
                                 "tok_s"}
    assert r["attempted"] % 4 == 0 and r["attempted"] > 0
    assert r["failed"] == 0
    assert r["check"]["max_gap"]["limit"] == smoke_root.SMOKE_LIMIT


def test_traced_run_reads_the_data_only_metric(root):
    r = _run(root, trace=True)
    assert r["correct"], r["check"]
    # no TPU plane on the CPU: trace readers find nothing and are left out
    assert r["metrics"] == {"smoke.served_batches": {
        "value": 1.0, "unit": "batches"}}
    assert "busy_s" in r["device"] and "window_s" in r["device"]


def test_config_that_contradicts_the_program_is_refused(root):
    path = root / "chipbench" / "configs" / "smoke-dense.json"
    c = json.loads(path.read_text())
    c["intermediate_size"] = 512
    path.write_text(json.dumps(c))
    with pytest.raises(bench.SpecError, match="intermediate_size"):
        c = bench.load_cell(smoke_root.CELL, root)
        bench.model_config(c.config, c.family)


def test_device_check_refuses_the_cpu():
    with pytest.raises(cell.NoChip, match="no TPU"):
        cell.device_check(1)


@pytest.mark.parametrize("layout", ["repo", "benchmark_files_only"])
def test_run_without_a_chip_prints_no_result(tmp_path, layout):
    where = REPO
    if layout == "benchmark_files_only":
        import shutil

        shutil.copy(REPO / "BENCHMARK.json", tmp_path)
        shutil.copytree(REPO / "chipbench", tmp_path / "chipbench")
        where = tmp_path
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "phi3-mini.decode", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=where, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "correct" not in p.stdout


# --- faults planted underneath the timed path -------------------------------

def _unchanged_state(real):
    def decode_step(cfg, params, tok, cache):
        logits, _ = real(cfg, params, tok, cache)
        return logits, cache
    return decode_step


def _half_batch(real):
    def decode_step(cfg, params, tok, cache):
        logits, cache = real(cfg, params, tok, cache)
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:h]]), cache
    return decode_step


def _altered_token(real):
    def decode_step(cfg, params, tok, cache):
        logits, cache = real(cfg, params, tok, cache)
        third = cache["pos"] == 27  # the 4th decode step
        return jnp.where(third, jnp.roll(logits, 1, axis=-1), logits), cache
    return decode_step


def _no_exchange(real_mlp):
    """A row-parallel MLP whose all-reduce is left out: the down
    projection keeps one of four shards' partial sums."""
    def mlp(cfg, p, x):
        dt = cfg.jdtype
        q = cfg.d_ff // 4
        g = jax.nn.silu(x @ p["wg"].astype(dt)[:, :q])
        u = x @ p["wu"].astype(dt)[:, :q]
        return (g * u) @ p["wd"].astype(dt)[:q]
    return mlp


@pytest.mark.parametrize("fault,target,make", [
    ("state_unchanged", "decode_step", _unchanged_state),
    ("half_batch", "decode_step", _half_batch),
    ("token_altered", "decode_step", _altered_token),
    ("exchange_left_out", "mlp", _no_exchange),
])
def test_fault_in_timed_path_is_not_correct(root, monkeypatch, fault,
                                            target, make):
    from repro.models import lm

    monkeypatch.setattr(lm, target, make(getattr(lm, target)))
    r = _run(root)
    assert not r["correct"], (fault, r["check"])
    gap = r["check"]["max_gap"]["value"]
    assert math.isfinite(gap) and gap > smoke_root.SMOKE_LIMIT
