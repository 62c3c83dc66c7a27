"""The tensor-parallel served path on four CPU devices against the float32
reference, at the published head counts of Yi-34B (56 query heads, 8 KV
heads, two to a device) and smoke widths.  ``serve_tp4.py`` runs in a
subprocess, since the device count is fixed before JAX starts."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import smoke_root

HERE = Path(__file__).resolve().parent
# float32 on both sides: only the order of summation differs, across the
# all-reduces too (the tolerance of test_reference_matches_float32_...)
F32_TOL = 1e-4


@pytest.fixture(scope="module")
def readings():
    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.environ.get("HOME", str(HERE)),
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run([sys.executable, str(HERE / "serve_tp4.py")],
                       capture_output=True, text=True, timeout=600, env=env,
                       cwd=HERE)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cache_is_sharded_over_kv_heads(readings):
    assert readings["cache_spec"][2] == "model"
    assert readings["cache_shape"][2] == 8  # (L, B, Hkv, slots, Dh)


def test_tensor_parallel_matches_float32_reference(readings):
    assert readings["tp_vs_ref"] < F32_TOL


def test_tensor_parallel_matches_one_device(readings):
    assert readings["one_vs_ref"] < F32_TOL
    assert readings["tp_vs_one"] < F32_TOL


def test_swapped_kv_heads_fail_the_comparison(readings):
    assert readings["fault_vs_ref"] > 100 * F32_TOL


def test_bf16_tensor_parallel_run_is_correct(readings):
    assert readings["bf16_max_gap"] < smoke_root.SMOKE_LIMIT


def test_decode_step_counts_two_all_reduces_a_layer(readings):
    """One after attention's output projection and one after the MLP in
    each layer, one after the vocabulary-sharded embedding lookup, each of
    the (B, 1, d_model) float32 residual; nothing else crosses devices."""
    n = 2 * readings["layers"] + 1
    each = readings["batch"] * readings["d_model"] * 4
    assert readings["collectives"] == {"all-reduce": [n, n * each]}
