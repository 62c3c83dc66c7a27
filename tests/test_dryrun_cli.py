"""Deliverable (e) in CI: one real dry-run cell through the CLI.

Runs in a subprocess because dryrun.py must set
--xla_force_host_platform_device_count=512 before jax initializes (the
test process itself runs single-device)."""
import json
import subprocess
import sys
from pathlib import Path

import pytest


@pytest.mark.parametrize("mesh", ["pod", "multipod"])
def test_dryrun_cell_compiles(tmp_path, mesh):
    cmd = [sys.executable, "-m", "repro.launch.dryrun",
           "--arch", "mamba2-130m", "--shape", "decode_32k",
           "--mesh", mesh, "--out", str(tmp_path)]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=420,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-1500:]
    out = list(tmp_path.glob("*.json"))
    assert len(out) == 1
    d = json.loads(out[0].read_text())
    assert "error" not in d, d.get("error")
    assert d["n_devices"] == (512 if mesh == "multipod" else 256)
    # memory fits the target chip and the roofline inputs are present
    assert d["memory_per_device"]["peak_live_bytes"] < 16 * 2 ** 30
    assert d["hlo"]["per_device_flops"] > 0
    assert d["hlo"]["total_collective_bytes"] > 0
