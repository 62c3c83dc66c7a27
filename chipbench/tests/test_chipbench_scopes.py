"""The served step's device time by the program's named scopes: the join
of a trace with the compiled programs' op_name metadata, the per-scope
metrics, garbage-collection spans among the idle gaps, and the scoped run
of a cell."""
from __future__ import annotations

import gzip
import json

import jax
import pytest

import smoke_root
from chipbench import scopes, trace

PREFILL_HLO = """HloModule jit_serve_prefill, entry_computation_layout={()->f32[4]}

ENTRY %main.1 (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0), metadata={op_name="params"}
  %fusion.1 = f32[4]{0} fusion(%p.1), kind=kOutput, metadata={op_name="jit(serve_prefill)/layers/while/body/qkv/dot_general" source_file="x.py" source_line=3}
  %while.2 = f32[4]{0} while(%fusion.1), body=%b, metadata={op_name="jit(serve_prefill)/layers/while/body/attend/while"}
  %copy.3 = f32[4]{0} copy(%p.1)
  ROOT %fusion.4 = f32[4]{0} fusion(%while.2), kind=kOutput, metadata={op_name="jit(serve_prefill)/layers/while/body/attend/while/body/mlp/dot_general"}
  %copy.5 = f32[4]{0} copy(%fusion.4)
}
"""
DECODE_HLO = """HloModule jit_serve_decode, entry_computation_layout={()->f32[4]}

%body.3 (t.1: f32[4]) -> f32[4] {
  %dynamic-slice.8 = f32[4]{0} dynamic-slice(%t.1), metadata={op_name="jit(serve_decode)/layers/while/body/dynamic_slice"}
  ROOT %fusion.9 = f32[4]{0} fusion(%dynamic-slice.8), kind=kLoop, metadata={op_name="jit(serve_decode)/layers/while/body/closed_call/attend/mul"}
  %copy.10 = f32[4]{0} copy(%fusion.9)
}

ENTRY %main.2 (p.1: f32[4]) -> f32[4] {
  %while.7 = f32[4]{0} while(%p.1), body=%body.3, metadata={op_name="jit(serve_decode)/layers/while"}
  ROOT %fusion.11 = f32[4]{0} fusion(%while.7), kind=kOutput, metadata={op_name="jit(serve_decode)/lm_head/dot_general"}
}
"""
# the benchmark's sample program: its HLO is given, its ops stay out
SAMPLE_HLO = """HloModule jit__lambda, entry_computation_layout={()->f32[]}

ENTRY %main.3 (p.1: f32[4]) -> f32[] {
  ROOT %fusion.1 = f32[] fusion(%p.1), kind=kLoop, metadata={op_name="jit(<lambda>)/attend/reduce_sum"}
}
"""
PROGRAMS = [PREFILL_HLO, DECODE_HLO, SAMPLE_HLO]


def _op(name, kind="fusion"):
    return f"%{name} = f32[4]{{0}} {kind}(f32[4]{{0}} %p.1)"


def _decode_ops(t):
    """One decode step starting at ``t`` us: the layer scan (a slice of
    the stacked cache, an ``attend`` op, a metadata-free copy), then the
    lm_head."""
    return [(_op("while.7", "while"), t, 20),
            (_op("dynamic-slice.8", "dynamic-slice"), t, 5),
            (_op("fusion.9"), t + 5, 10),
            (_op("copy.10", "copy"), t + 15, 5),
            (_op("fusion.11"), t + 20, 2)]


DEVICE_OPS = [
    (_op("fusion.1"), 0, 20),  # own op_name: qkv
    (_op("while.2", "while"), 20, 25),  # an attend scan
    (_op("copy.3", "copy"), 22, 8),  # no metadata: attend, from the scan
    (_op("fusion.4"), 30, 14),  # its own scope wins over the scan's
    (_op("copy.5", "copy"), 45, 3),  # no metadata, no scan: unscoped
    (_op("fusion.1"), 50, 5),  # the sample program's fusion.1
    *_decode_ops(70),
    (_op("fusion.1"), 95, 2),
    *_decode_ops(110),
    (_op("fusion.1"), 135, 2),
]
RUNS = [("jit_serve_prefill(11)", 0, 48), ("jit__lambda(12)", 50, 5),
        ("jit_serve_decode(13)", 70, 22), ("jit__lambda(12)", 95, 2),
        ("jit_serve_decode(13)", 110, 22), ("jit__lambda(12)", 135, 2)]
HOST = [("window", 0, 200), ("prefill", 0, 60), ("sample", 50, 10),
        ("decode", 70, 30), ("sample", 95, 5), ("gc", 101, 8),
        ("decode", 110, 30), ("sample", 135, 5)]


def _xspace(lines_by_plane):
    """A text-proto XSpace: plane name -> line name -> [(event, start_us,
    dur_us)]."""
    text = ""
    for pid, (plane, lines) in enumerate(lines_by_plane.items(), 1):
        names = sorted({n for evs in lines.values() for n, _, _ in evs})
        ids = {n: i + 1 for i, n in enumerate(names)}
        body = ""
        for lid, (line, evs) in enumerate(lines.items(), 1):
            body += (f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0\n'
                     + "".join(
                         f"events {{ metadata_id: {ids[n]} offset_ps: "
                         f"{int(s * 1e6)} duration_ps: {int(d * 1e6)} }}\n"
                         for n, s, d in evs) + "}\n")
        meta = "".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: '
            f'"{n.replace(chr(34), chr(92) + chr(34))}" }} }}\n'
            for n, i in ids.items())
        text += f'planes {{ id: {pid} name: "{plane}"\n{body}{meta}}}\n'
    return jax.profiler.ProfileData.from_text_proto(text)


@pytest.fixture(scope="module")
def profile():
    return _xspace({
        "/device:TPU:0": {"XLA Modules": RUNS, "XLA Ops": DEVICE_OPS},
        "/host:CPU": {"python": HOST}})


@pytest.fixture(scope="module")
def split(profile):
    return scopes.split(profile, PROGRAMS)


def test_op_names_reads_each_module_and_instruction():
    names = scopes.op_names(PROGRAMS)
    assert set(names) == {"jit_serve_prefill", "jit_serve_decode",
                          "jit__lambda"}
    assert names["jit_serve_decode"]["fusion.11"] == \
        "jit(serve_decode)/lm_head/dot_general"
    assert "copy.10" not in names["jit_serve_decode"]
    assert names["jit_serve_prefill"]["p.1"] == "params"
    assert scopes.module_name("jit_serve_decode(13)") == "jit_serve_decode"


@pytest.mark.parametrize("paths,want", [
    (["jit(f)/layers/while/body/qkv/dot_general"], "qkv"),
    # the deepest leaf scope on the op's own path
    (["jit(f)/attend/while/body/mlp/dot"], "mlp"),
    # its own scope before an enclosing op's
    (["jit(f)/layers/while/body/norm/mul", "jit(f)/attend/while"], "norm"),
    # no metadata: the innermost enclosing op with a leaf scope
    (["", "jit(f)/layers/while/body/attend/while", "jit(f)/layers/while"],
     "attend"),
    (["", "jit(f)/layers/while"], "layers"),
    (["jit(f)/layers/while/body/dynamic_slice"], "layers"),
    (["", ""], "unscoped"),
    (["params"], "unscoped"),
])
def test_bucket(paths, want):
    assert scopes.bucket(paths) == want


def test_each_op_lands_in_its_bucket(split):
    pre, = split["spans"]["prefill"]
    us = {k: v * 1e6 for k, v in pre["scopes"].items()}
    assert us == pytest.approx({**dict.fromkeys(scopes.BUCKETS, 0.0),
                                "qkv": 20, "attend": 8, "mlp": 14,
                                "unscoped": 3})
    for step in split["spans"]["decode"]:
        us = {k: v * 1e6 for k, v in step["scopes"].items()}
        # the sample program's fusion.1 and its attend metadata stay out
        assert us == pytest.approx({**dict.fromkeys(scopes.BUCKETS, 0.0),
                                    "layers": 10, "attend": 10,
                                    "lm_head": 2})
        assert step["program_busy_s"] == pytest.approx(22e-6)
    # the busy union counts the scan's own time between its ops
    assert pre["program_busy_s"] == pytest.approx(48e-6)
    assert set(split["spans"]) == {"prefill", "decode"}


def test_buckets_sum_to_the_step_programs_leaf_time(split):
    leaves = {n: 0 for n in scopes.PROGRAMS.values()}
    ops = [(s * 1000, (s + d) * 1000, n) for n, s, d in DEVICE_OPS]
    for s, e, _ in trace._leaves(ops):
        for module, start, dur in RUNS:
            span = scopes.PROGRAMS.get(scopes.module_name(module))
            if span and start * 1000 <= s < (start + dur) * 1000:
                leaves[span] += (e - s) / 1e9
    for span, total in leaves.items():
        assert sum(sum(s["scopes"].values())
                   for s in split["spans"][span]) == pytest.approx(total)


def test_a_repeated_event_is_one_leaf_as_in_the_trace():
    ops = [(0, 10, "while.1"), (2, 4, "fusion.2"), (2, 4, "fusion.2"),
           (5, 9, "copy.3")]
    walked = [(ev, leaf) for ev, leaf, _ in scopes._walk(ops)]
    assert sorted(ev for ev, leaf in walked if leaf) == \
        sorted(trace._leaves(ops))
    assert [leaf for _, leaf in walked] == [False, True, False, True]


def test_idle_gap_inside_a_collection_reads_gc(split, profile):
    # the device idles from the sample's end at 97 us to the next step at
    # 110; the gap's middle falls in the collection from 101 to 109
    gaps = dict((round(d * 1e6), n) for n, d in split["idle_gaps"])
    assert gaps[13] == "gc"
    assert split["gc_spans_s"] == pytest.approx([8e-6])
    # the trace's own reduction does not read gc spans
    plain = dict((round(d * 1e6), n)
                 for n, d in trace.reduce(profile)["breakdown"]["idle_gaps"])
    assert plain[13] == "between spans"


def test_metrics_give_ms_per_traced_step(split):
    assert scopes.metrics(split) == pytest.approx({
        "decode.attend_ms": 0.010, "decode.matmul_ms": 0.002,
        "decode.unscoped_ms": 0.010, "prefill.attend_ms": 0.008,
        "prefill.matmul_ms": 0.034})


@pytest.mark.parametrize("name", sorted(scopes.METRICS))
def test_metrics_find_nothing_without_the_step_programs(profile, name):
    # a program whose steps have other names, or no compiled text
    unnamed = [t.replace("jit_serve_", "jit_") for t in PROGRAMS]
    for got in (scopes.split(profile, unnamed), scopes.split(profile, []),
                {}):
        assert scopes.metrics(got)[name] is None


def test_summary_shares(split):
    got = scopes.summary(split)
    assert got["decode"]["spans"] == 2
    assert got["decode"]["leaf_ms"] == pytest.approx(0.022)
    assert got["decode"]["program_busy_ms"] == pytest.approx(0.022)
    assert got["decode"]["scoped_pct"] == pytest.approx(100 * 12 / 22)
    assert got["prefill"]["scoped_pct"] == pytest.approx(100 * 42 / 45)


def test_scope_names_are_the_programs():
    from repro.obs.serving import GC_SPAN, LAYERS, SCOPES

    assert scopes.LEAF_SCOPES == SCOPES
    assert scopes.LAYERS == LAYERS
    assert scopes.GC == GC_SPAN


def test_scoped_run_reads_the_step_programs_and_collections(
        tmp_path, monkeypatch):
    """At smoke size on the CPU: the traced window compiles nothing, the
    step programs are read after it without compiling, and each batch
    carries its collections.  The CPU trace has no TPU plane, so nothing
    is split."""
    import time

    from chipbench import cell, scoped_run

    jax.config.update("jax_enable_compilation_cache", False)
    monkeypatch.setattr(cell, "device_check", lambda chips: (
        jax.devices()[:chips],
        {"bf16_flop_per_s": 1e12, "hbm_bytes_per_s": 1e11}))
    root = smoke_root.make(tmp_path)
    r = scoped_run.run(smoke_root.CELL, 2**31 + 7, 0.3, time.perf_counter(),
                       root)
    assert r["programs"] == ["jit_serve_prefill", "jit_serve_decode"]
    assert r["compilations"] == {"window": 0, "programs": 0}
    assert r["batches"] and r["batches"][0]["traced"]
    assert all(len(b["gc"]) == 3 and b["gc_pause_ms"] >= 0
               for b in r["batches"])
    assert r["gc_window"]["collections"] == [
        sum(b["gc"][g] for b in r["batches"]) for g in range(3)]
    assert r["scopes"] == {} and r["idle_gaps"] == []
    assert all(v is None for k, v in r["metrics"].items()
               if k != "decode.gc_pause_ms")
    assert r["metrics"]["decode.gc_pause_ms"] >= 0


@pytest.fixture(scope="module")
def recorded():
    """One chip, from record_scopes_trace.py: 3 rounds of ``serve_prefill``
    (a matmul in an ``mlp`` scope) with the sample program in a span inside
    its span, ``serve_decode`` (a 4-step scan in ``layers`` whose body is
    in ``attend``), and a forced garbage collection; the split and the
    trace's own reduction."""
    name = "trace_scopes_1chip"
    raw = gzip.decompress(
        (smoke_root.DATA / f"{name}.xplane.pb.gz").read_bytes())
    programs = json.loads((smoke_root.DATA / f"{name}.hlo.json").read_text())
    profile = jax.profiler.ProfileData.from_serialized_xspace(raw)
    return scopes.split(profile, programs), trace.reduce(profile)


def test_recorded_trace_by_scope(recorded):
    split, reduced = recorded
    spans = split["spans"]
    assert [len(reduced["spans"][n]) for n in ("prefill", "decode",
                                               "sample")] == [3, 3, 3]
    assert [len(spans[n]) for n in ("prefill", "decode")] == [3, 3]
    assert len(split["gc_spans_s"]) == 3
    for span, whole in zip(spans["prefill"], reduced["spans"]["prefill"]):
        got = span["scopes"]
        # the matmul's fusion, ~1.1 us, and the copy of its input that
        # XLA adds, which carries no metadata
        assert {k for k, v in got.items() if v} == {"mlp", "unscoped"}
        assert 1e-6 < got["mlp"] < 1.3e-6
        # the sample program runs inside the prefill span, in no bucket
        assert span["program_busy_s"] < whole["busy_s"]
    for span in spans["decode"]:
        got = span["scopes"]
        assert {k for k, v in got.items() if v} == {"attend", "unscoped"}
        assert got["attend"] == pytest.approx(4 * 0.63e-6, rel=0.02)
    for span in spans["prefill"] + spans["decode"]:
        assert sum(span["scopes"].values()) == pytest.approx(
            span["program_busy_s"], rel=0.02)
    assert scopes.metrics(split)["decode.attend_ms"] == pytest.approx(
        1e3 * spans["decode"][0]["scopes"]["attend"], rel=0.01)


def test_recorded_trace_names_collections_among_idle_gaps(recorded):
    # each round's forced collection idles the device for 12-23 ms
    gaps = recorded[0]["idle_gaps"]
    assert [n for n, _ in gaps[:3]] == ["gc"] * 3
    assert all(d > 0.01 for _, d in gaps[:3])
