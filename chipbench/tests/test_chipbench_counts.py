"""The yardstick's counts against the figures worked out by hand for the
benchmark's configurations."""
from __future__ import annotations

import json

import pytest

import smoke_root
from chipbench import bench, counts

CONFIGS = smoke_root.REPO / "chipbench" / "configs"


def _dims(name):
    return counts.Dims.from_config(
        json.loads((CONFIGS / f"{name}.json").read_text()))


def test_phi3_prefill_needs_46_39_tflop():
    w = counts.prefill(_dims("phi3-mini-3.8b"), batch=4, prompt=1536)
    assert w.flops["proj"] == pytest.approx(44.53e12, rel=1e-3)
    assert w.flops["qk"] + w.flops["av"] == pytest.approx(1.857e12, rel=1e-3)
    # the lm_head at the last position of each prompt only
    assert w.flops["lm_head"] == 2 * 4 * 3072 * 32064
    assert w.total_flops == pytest.approx(46.39e12, rel=1e-3)


def test_phi3_decode_weight_bytes_exclude_the_embedding_table():
    d = _dims("phi3-mini-3.8b")
    w = counts.decode(d, batch=4, filled=1089)
    weights = sum(v for k, v in w.bytes.items() if k.startswith("weights."))
    assert weights == pytest.approx(7.44e9, rel=2e-3)
    assert w.bytes["embed.rows"] == 4 * 3072 * 2  # the rows read, no more


@pytest.mark.parametrize("batch,filled,gb", [(8, 1088.5, 3.42),
                                             (4, 1088.5, 1.71)])
def test_decode_charges_the_filled_cache_only(batch, filled, gb):
    w = counts.decode(_dims("phi3-mini-3.8b"), batch, filled)
    assert w.bytes["kv.read"] == pytest.approx(gb * 1e9, rel=2e-3)


def test_yi_tp4_decode_bytes_per_chip():
    w = counts.decode(_dims("yi-34b-tp4"), batch=16, filled=576.5)
    per_chip = w.per_chip(4)
    weights = sum(v for k, v in per_chip.bytes.items()
                  if k.startswith("weights."))
    assert weights == pytest.approx(8.6e9, rel=5e-3)
    assert per_chip.bytes["kv.read"] == pytest.approx(0.283e9, rel=5e-3)


def test_least_time_names_its_bound():
    peak = bench.peaks("TPU v5 lite")
    d = _dims("phi3-mini-3.8b")
    t, bound = counts.least_time(counts.decode(d, 4, 1100), peak)
    assert bound == "hbm" and t == pytest.approx(0.0112, rel=0.03)
    t, bound = counts.least_time(counts.prefill(d, 4, 1536), peak)
    assert bound == "flops" and t == pytest.approx(0.2355, rel=1e-3)


def test_peaks_are_keyed_by_device_kind():
    peak = bench.peaks("TPU v5 lite")
    assert peak["ici_bits_per_s"] / 8 == 200e9
    assert (peak["bf16_flop_per_s"], peak["hbm_bytes_per_s"]) == (
        197e12, 819e9)
    with pytest.raises(bench.SpecError, match="no published peaks"):
        bench.peaks("cpu")
