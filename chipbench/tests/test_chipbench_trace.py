"""The trace reduction: interval arithmetic, and a small trace recorded on
the chip (``record_trace.py``) reduced end to end."""
from __future__ import annotations

import gzip

import jax
import pytest

import smoke_root
from chipbench import trace


def test_union_merges_and_measures_inside_windows():
    u = trace.Union([(5, 10), (0, 3), (8, 12), (20, 25)])
    assert u.iv == [(0, 3), (5, 12), (20, 25)]
    assert u.within(0, 30) == 3 + 7 + 5
    assert u.within(2, 6) == 1 + 1
    assert u.within(12, 20) == 0
    assert u.gaps(0, 30) == [(3, 5), (12, 20), (25, 30)]
    assert u.gaps(6, 11) == []


@pytest.mark.parametrize("name,is_collective", [
    ("all-reduce.3", True), ("all-reduce-start.1", True),
    ("all-gather-done", True), ("reduce-scatter.2", True),
    ("collective-permute.7", True), ("fusion.12", False),
    ("convolution.4", False), ("reduce.1", False)])
def test_collective_names(name, is_collective):
    assert bool(trace.COLLECTIVE.search(name)) == is_collective


def _xspace(devices, host):
    """A text-proto XSpace: ``devices`` maps chip -> [(name, start_us,
    dur_us)] on its ``XLA Ops`` line, ``host`` is [(span, start_us,
    dur_us)] on one host thread."""
    def plane(pid, name, line, events):
        names = sorted({n for n, _, _ in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * 1e6)} "
            f"duration_ps: {int(d * 1e6)} }}\n" for n, s, d in events)
        meta = "".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
            for n, i in ids.items())
        return (f'planes {{ id: {pid} name: "{name}"\n'
                f'lines {{ id: 1 name: "{line}" timestamp_ns: 0\n{evs}}}\n'
                f'{meta}}}\n')
    text = "".join(plane(c + 1, f"/device:TPU:{c}", "XLA Ops", evs)
                   for c, evs in devices.items())
    text += plane(99, "/host:CPU", "python", host)
    return jax.profiler.ProfileData.from_text_proto(text)


@pytest.fixture
def synthetic():
    host = [("window", 0, 100), ("prefill", 0, 40), ("sample", 30, 10),
            ("decode", 50, 20), ("sample", 65, 5), ("decode", 70, 30)]
    dev0 = [("fusion.1", 0, 25), ("all-reduce.2", 25, 5),
            ("fusion.1", 50, 10), ("all-reduce.2", 60, 2),
            ("fusion.3", 75, 20)]
    dev1 = [("fusion.1", 0, 30), ("fusion.1", 50, 12), ("fusion.3", 75, 10)]
    return trace.reduce(_xspace({0: dev0, 1: dev1}, host))


def test_reduce_busy_and_collectives_per_span(synthetic):
    r = synthetic
    assert r["chips"] == 2
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s"] == pytest.approx((62 + 52) / 2 * 1e-6)
    pre, = r["spans"]["prefill"]
    assert pre["seconds"] == pytest.approx(40e-6)
    assert pre["busy_s"] == pytest.approx(30e-6)
    assert pre["collective_s"] == pytest.approx(2.5e-6)
    d1, d2 = r["spans"]["decode"]
    assert d1["busy_s"] == pytest.approx(12e-6)
    assert d1["collective_s"] == pytest.approx(1e-6)
    assert d2["busy_s"] == pytest.approx(15e-6)
    assert len(r["spans"]["sample"]) == 2


def test_reduce_breakdown_names_ops_and_idle_gaps(synthetic):
    ops = dict(synthetic["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx((35 + 42) / 2 * 1e-6)
    assert ops["all-reduce.2"] == pytest.approx(3.5e-6)
    gaps = synthetic["breakdown"]["idle_gaps"]
    # chip 0 idles 30-50 (its middle in no span), 62-75 (in a decode's
    # sample), 95-100 (in a decode); each named by the span at its middle
    assert gaps == [["between spans", pytest.approx(20e-6)],
                    ["sample", pytest.approx(13e-6)],
                    ["decode", pytest.approx(5e-6)]]


def test_reduce_without_a_device_plane_is_empty():
    assert trace.reduce(_xspace({}, [("decode", 0, 10)])) == {}


def test_ops_are_named_by_instruction_and_parents_left_out():
    loop = "%while.4 = (s32[], bf16[4]) while((s32[], bf16[4]) %t), body=%b"
    inner = "%fusion.2 = bf16[4] fusion(bf16[4] %all-reduce.1), kind=kLoop"
    assert trace.op_name(inner) == "fusion.2"
    assert not trace.COLLECTIVE.search(trace.op_name(inner))
    r = trace.reduce(_xspace({0: [(loop, 0, 50), (inner, 5, 10),
                                  ("%all-reduce.1 = bf16[4] all-reduce()",
                                   20, 10)]},
                             [("decode", 0, 60)]))
    assert [n for n, _ in r["breakdown"]["device_ops"]] == [
        "fusion.2", "all-reduce.1"]
    assert r["spans"]["decode"][0]["busy_s"] == pytest.approx(50e-6)
    assert r["spans"]["decode"][0]["collective_s"] == pytest.approx(10e-6)


@pytest.fixture(scope="module")
def recorded():
    """One chip, from record_trace.py: 3 rounds of a prefill span (with a
    sample span inside) running one program, then a decode span running
    another; the device's clock there runs ~1.5 ms behind the host's."""
    raw = gzip.decompress(
        (smoke_root.DATA / "trace_1chip.xplane.pb.gz").read_bytes())
    return trace.reduce(jax.profiler.ProfileData.from_serialized_xspace(raw))


def test_recorded_trace_puts_each_program_in_its_span(recorded):
    r = recorded
    assert r["chips"] == 1
    assert [len(r["spans"][n]) for n in ("prefill", "decode", "sample")] == [
        3, 3, 3]
    for name in ("prefill", "decode"):
        for span in r["spans"][name]:
            # one program of about 2 us each, wholly inside its span
            assert 1.5e-6 < span["busy_s"] < 3e-6
            assert span["collective_s"] == 0.0
    assert all(s["busy_s"] == 0.0 for s in r["spans"]["sample"])
    assert r["busy_s"] == pytest.approx(
        sum(s["busy_s"] for n in ("prefill", "decode")
            for s in r["spans"][n]))
    assert r["window_s"] == pytest.approx(0.0238, abs=1e-4)


def test_recorded_trace_breakdown(recorded):
    ops = [n for n, _ in recorded["breakdown"]["device_ops"]]
    assert ops[0] == "tanh_multiply_fusion"
    assert "convolution_reduce_fusion" in ops
    assert len(recorded["breakdown"]["idle_gaps"]) == trace.TOP


def test_device_offset_is_the_tightest_enqueue_to_start_gap():
    # run 7 enqueued at 1.5 ms host time, started at 0 on the device clock
    assert trace._device_offset({7: 0}, {7: 1_500_000}) == 1_500_000
    # each run bounds the offset from below; the tightest, run 8's, sets it
    assert trace._device_offset({7: 0, 8: 5}, {7: 1_000, 8: 2_000}) == 1_995
    assert trace._device_offset({7: 0}, {}) == 0
