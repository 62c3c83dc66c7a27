"""The control: the reference computed in a lower precision, put in the
program's place, reads above the limit that sound bf16 runs stay under.
At smoke size on the CPU; the chip readings at the cells' sizes are in
PERF.md (``chipbench/calibrate.py``)."""
from __future__ import annotations

import jax
import pytest

import smoke_root
from chipbench import calibrate, cell


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    jax.config.update("jax_enable_compilation_cache", False)
    root = smoke_root.make(tmp_path_factory.mktemp("root"))
    real = cell.device_check
    cell.device_check = lambda chips: (jax.devices()[:chips], {})
    try:
        return list(calibrate.readings(smoke_root.CELL, [1, 2, 3], {1, 2, 3},
                                       ["int8", "float8"], root))
    finally:
        cell.device_check = real


@pytest.mark.parametrize("seed_index", [0, 1, 2])
def test_sound_program_reads_under_the_limit(readings, seed_index):
    assert readings[seed_index]["max_gap"] <= smoke_root.SMOKE_LIMIT


def test_control_reads_over_the_limit(readings):
    got = [r["control.float8"] for r in readings]
    assert min(got) > smoke_root.SMOKE_LIMIT, got
