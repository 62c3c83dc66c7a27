"""Observability subsystem: zero-overhead contract, event merging, exports.

The load-bearing contract is that tracing is *observational*: with
``tracer=None`` (the default) every hot path executes the exact pre-tracing
instruction stream, and with a live tracer the search returns bit-identical
optima and counter stats while additionally emitting a coherent event
stream whose per-criterion prune attribution sums to the ``n_pruned_*``
fields of ``MapperStats`` (the ISSUE-7 acceptance criterion).
"""
import json

import pytest

from repro.core.arch import Arch, MemLevel
from repro.core.einsum import matmul
from repro.core.mapper import tcm_map
from repro.core.presets import small_matmul_suite, tpu_v4i_like
from repro.core.search import MapperStats, stats_from_dict
from repro.obs import (NULL_TRACER, NullTracer, Tracer, active, from_chrome,
                       profile, read_jsonl, read_trace, to_chrome,
                       write_chrome, write_jsonl)
from repro.obs.__main__ import main as obs_main

EIN = matmul("mm", 4, 4, 4)
ARCH = Arch("a", (MemLevel("DRAM", float("inf"), 100, 100, 1e8),
                  MemLevel("GLB", 12, 1, 1, 1e9)), mac_energy=0.5)

NON_TIMING = lambda st: {k: v for k, v in st.to_dict().items()  # noqa: E731
                         if not k.startswith("t_")}


def prune_sums(events):
    """Sum the per-criterion attribution over all step counter events."""
    out = {"expanded": 0, "pruned_dominated": 0, "pruned_bound": 0,
           "pruned_invalid": 0}
    for ev in events:
        if ev.get("cat") == "step":
            for k in out:
                out[k] += ev.get("args", {}).get(k, 0)
    return out


# --------------------------------------------------------------------------
# tracer primitives
# --------------------------------------------------------------------------


def test_null_tracer_is_inert():
    nt = NullTracer()
    with nt.span("x", cat="driver", a=1):
        nt.instant("i")
        nt.counter("c", v=2)
        nt.complete("done", 0.0)
        nt.extend([{"ph": "i"}])
    assert nt.events == [] and NULL_TRACER.events == []
    assert not nt.enabled


def test_active_normalizes():
    tr = Tracer()
    assert active(None) is None
    assert active(NullTracer()) is None
    assert active(NULL_TRACER) is None
    assert active(tr) is tr


def test_tracer_event_shapes():
    tr = Tracer()
    with tr.span("outer", cat="phase", k=1):
        tr.instant("tick", cat="incumbent", objective=2.0)
        tr.counter("expand", cat="step", expanded=3)
    kinds = {ev["ph"] for ev in tr.events}
    assert kinds == {"X", "i", "C"}
    for ev in tr.events:
        assert set(ev) >= {"ph", "name", "cat", "ts", "pid", "tid", "args"}
        json.dumps(ev)  # JSON-safe (crosses process + file boundaries)
    span = [e for e in tr.events if e["ph"] == "X"][0]
    assert span["dur"] >= 0 and span["args"] == {"k": 1}


# --------------------------------------------------------------------------
# zero-overhead / bit-identical contract (the tentpole invariant)
# --------------------------------------------------------------------------


def test_serial_traced_bit_identical_and_attributed():
    best_u, st_u = tcm_map(EIN, ARCH)
    tr = Tracer()
    best_t, st_t = tcm_map(EIN, ARCH, tracer=tr)
    assert (best_t.energy, best_t.latency, best_t.edp) == \
        (best_u.energy, best_u.latency, best_u.edp)
    assert best_t.mapping == best_u.mapping
    assert NON_TIMING(st_t) == NON_TIMING(st_u)
    # acceptance criterion: per-criterion prune counts sum to MapperStats
    sums = prune_sums(tr.events)
    assert sums["expanded"] == st_t.n_expanded
    assert sums["pruned_dominated"] == st_t.n_pruned_dominated
    assert sums["pruned_bound"] == st_t.n_pruned_bound
    assert sums["pruned_invalid"] == st_t.n_pruned_invalid
    # one driver span closes the trace; phase spans nest under it
    drivers = [e for e in tr.events if e.get("cat") == "driver"]
    assert [d["name"] for d in drivers] == ["tcm_map:mm"]
    assert {e["name"] for e in tr.events if e.get("cat") == "phase"} >= \
        {"enumerate", "search"}


def test_null_tracer_matches_none():
    best_n, st_n = tcm_map(EIN, ARCH, tracer=NullTracer())
    best_u, st_u = tcm_map(EIN, ARCH)
    assert best_n.edp == best_u.edp and best_n.mapping == best_u.mapping
    assert NON_TIMING(st_n) == NON_TIMING(st_u)


def test_pool_unshared_traced_bit_identical():
    best_u, st_u = tcm_map(EIN, ARCH, share_incumbents=False)
    tr = Tracer()
    best_t, st_t = tcm_map(EIN, ARCH, workers=2, share_incumbents=False,
                           tracer=tr)
    assert (best_t.energy, best_t.latency, best_t.edp) == \
        (best_u.energy, best_u.latency, best_u.edp)
    assert best_t.mapping == best_u.mapping
    assert NON_TIMING(st_t) == NON_TIMING(st_u)
    # worker buffers merged: prune attribution still sums exactly
    sums = prune_sums(tr.events)
    assert sums["expanded"] == st_t.n_expanded
    assert sums["pruned_bound"] == st_t.n_pruned_bound


def test_pool_shared_traced_value_parity_and_self_consistent():
    best_u, _ = tcm_map(EIN, ARCH)
    tr = Tracer()
    best_t, st_t = tcm_map(EIN, ARCH, workers=2, tracer=tr)
    assert (best_t.energy, best_t.latency, best_t.edp) == \
        (best_u.energy, best_u.latency, best_u.edp)
    # shared-pool prune counters are scheduling-dependent, but the trace
    # must stay self-consistent with the stats of ITS OWN run
    sums = prune_sums(tr.events)
    assert sums["expanded"] == st_t.n_expanded
    assert sums["pruned_bound"] == st_t.n_pruned_bound
    assert sums["pruned_dominated"] == st_t.n_pruned_dominated
    assert sums["pruned_invalid"] == st_t.n_pruned_invalid


def test_pool_events_merge_in_unit_order():
    tr = Tracer()
    tcm_map(EIN, ARCH, workers=2, share_incumbents=False, tracer=tr)
    units = [e for e in tr.events if e.get("cat") == "unit"]
    assert units, "no unit spans in pool trace"
    indices = [u["args"]["index"] for u in units]
    assert indices == sorted(indices), \
        "worker event buffers must merge in deterministic unit order"


def test_incumbent_timeline_present():
    suite = small_matmul_suite()
    tr = Tracer()
    best, _ = tcm_map(suite["P0"], tpu_v4i_like(), tracer=tr)
    incs = [e for e in tr.events if e.get("cat") == "incumbent"]
    assert incs, "shared-incumbent search must record tightenings"
    assert incs[0]["name"] == "seeded"  # beam-dive seeds the global bound
    objs = [e["args"]["objective"] for e in incs]
    assert objs == sorted(objs, reverse=True)  # monotone tightening
    assert objs[-1] == pytest.approx(best.edp)


# --------------------------------------------------------------------------
# MapperStats wire format (satellite: canonical to_dict / from_dict)
# --------------------------------------------------------------------------


def test_stats_dict_roundtrip():
    _, st = tcm_map(EIN, ARCH)
    wire = st.to_dict()
    json.dumps(wire)  # JSON-safe
    back = stats_from_dict(wire)
    assert isinstance(back, MapperStats)
    assert back.to_dict() == wire
    # forward compatible: unknown keys are dropped, missing keys default
    wire2 = dict(wire, someday_a_new_field=7)
    assert stats_from_dict(wire2).to_dict() == wire
    assert stats_from_dict({"n_expanded": 3}).n_expanded == 3


# --------------------------------------------------------------------------
# exports
# --------------------------------------------------------------------------


def _traced_events():
    tr = Tracer()
    tcm_map(EIN, ARCH, tracer=tr)
    return tr.events


def test_jsonl_roundtrip(tmp_path):
    events = _traced_events()
    p = tmp_path / "t.jsonl"
    write_jsonl(events, p)
    back = read_jsonl(p)
    assert len(back) == len(events)
    assert sorted(map(json.dumps, back)) == sorted(map(json.dumps, events))
    assert read_trace(p) == back  # auto-detect: JSONL


def test_chrome_roundtrip(tmp_path):
    events = _traced_events()
    doc = to_chrome(events)
    assert doc["otherData"]["producer"] == "repro.obs"
    body = [r for r in doc["traceEvents"] if r["ph"] != "M"]
    meta = [r for r in doc["traceEvents"] if r["ph"] == "M"]
    assert len(body) == len(events)
    assert meta and meta[0]["args"]["name"] == "mapper driver"
    assert min(r["ts"] for r in body) == 0.0  # rebased, microseconds
    for r in body:  # Perfetto-loadable: every record fully keyed
        assert set(r) >= {"ph", "name", "cat", "ts", "pid", "tid"}
    back = from_chrome(doc)
    assert len(back) == len(events)
    for a, b in zip(back, sorted(events, key=lambda e: e["ts"])):
        assert a["name"] == b["name"] and a["cat"] == b["cat"]
        assert a["ts"] == pytest.approx(b["ts"], abs=1e-5)
    p = tmp_path / "t.json"
    write_chrome(events, p)
    assert len(read_trace(p)) == len(events)  # auto-detect: Chrome


def test_tracer_save_picks_format(tmp_path):
    tr = Tracer()
    tr.instant("x")
    tr.save(tmp_path / "a.jsonl")
    tr.save(tmp_path / "a.json")
    assert (tmp_path / "a.jsonl").read_text().startswith('{"ph":"i"')
    assert json.loads((tmp_path / "a.json").read_text())["traceEvents"]


# --------------------------------------------------------------------------
# profile report + CLI
# --------------------------------------------------------------------------


def test_profile_report_contents():
    suite = small_matmul_suite()
    tr = Tracer()
    _, st = tcm_map(suite["P0"], tpu_v4i_like(), tracer=tr)
    rep = profile(tr.events)
    assert rep.n_events == len(tr.events)
    assert rep.prune.expanded == st.n_expanded
    assert rep.prune.pruned_total == (st.n_pruned_dominated
                                      + st.n_pruned_bound
                                      + st.n_pruned_invalid)
    assert rep.units and rep.incumbents
    assert rep.units == sorted(rep.units, key=lambda u: -u["dur"])
    text = rep.render(top_k=3)
    assert "phase breakdown" in text
    assert "prune attribution" in text
    assert "incumbent timeline" in text
    assert "most expensive work units" in text


def test_profile_empty():
    rep = profile([])
    assert rep.n_events == 0 and "0 events" in rep.render()


def test_obs_cli(tmp_path, capsys):
    events = _traced_events()
    src = tmp_path / "t.jsonl"
    write_jsonl(events, src)
    assert obs_main(["report", str(src), "--top", "2"]) == 0
    assert "phase breakdown" in capsys.readouterr().out
    assert obs_main([str(src)]) == 0  # bare path implies report
    assert "phase breakdown" in capsys.readouterr().out
    chrome = tmp_path / "t.json"
    assert obs_main(["chrome", str(src), "-o", str(chrome)]) == 0
    assert len(from_chrome(json.loads(chrome.read_text()))) == len(events)
    jl = tmp_path / "back.jsonl"
    assert obs_main(["jsonl", str(chrome), "-o", str(jl)]) == 0
    assert len(read_jsonl(jl)) == len(events)


# --------------------------------------------------------------------------
# consumers: netmap cache/fusion, dse, gap
# --------------------------------------------------------------------------


def test_netmap_trace_cache_and_fusion_events(tmp_path):
    from repro.configs import get_config
    from repro.netmap import MappingCache, map_network

    cfg = get_config("qwen1_5_0_5b", smoke=True)
    arch = tpu_v4i_like()
    cache = MappingCache(root=tmp_path)
    tr_cold = Tracer()
    rep_cold = map_network(cfg, arch, mode="decode", batch=1, seq=16,
                           cache=cache, tracer=tr_cold)
    cold = [e for e in tr_cold.events if e.get("cat") == "cache"]
    assert cold and all(e["name"] in ("miss", "negative") for e in cold)
    fusion = [e for e in tr_cold.events if e.get("cat") == "fusion"]
    assert fusion and all(e["name"] in ("adopted", "rejected")
                          for e in fusion)
    drivers = [e for e in tr_cold.events if e.get("cat") == "driver"
               and e["name"].startswith("map_network:")]
    assert len(drivers) == 1
    assert drivers[0]["args"]["edp"] == pytest.approx(rep_cold.total_edp)

    tr_warm = Tracer()
    rep_warm = map_network(cfg, arch, mode="decode", batch=1, seq=16,
                           cache=cache, tracer=tr_warm)
    warm = [e for e in tr_warm.events if e.get("cat") == "cache"]
    assert warm and all(e["name"] in ("hit", "negative") for e in warm)
    assert rep_warm.total_edp == rep_cold.total_edp
    assert cache.hits > 0 and 0 < cache.hit_rate <= 1.0


def test_dse_trace_events():
    from repro.core.einsum import batched_matmul
    from repro.dse import explore_space, get_space

    tr = Tracer()
    rep = explore_space(get_space("edge-small"),
                        [batched_matmul("fqk", 8, 4, 32, 64),
                         batched_matmul("fav", 8, 4, 64, 32)],
                        collect_mappings=False, tracer=tr)
    dse = [e for e in tr.events if e.get("cat") == "dse"]
    points = [e for e in dse if e["ph"] == "X"]
    instants = [e for e in dse if e["ph"] == "i"]
    assert len(instants) == rep.n_points  # one outcome instant per point
    assert sum(1 for e in instants if e["name"] == "pruned_roofline") == \
        rep.n_pruned_roofline
    assert sum(1 for e in instants if e["name"] == "evaluated") == \
        rep.n_evaluated
    # evaluated + bound-cut + infeasible points get an evaluation span
    assert len(points) == rep.n_points - rep.n_pruned_roofline
    drv = [e for e in tr.events if e.get("cat") == "driver"
           and e["name"].startswith("explore_space:")]
    assert drv and drv[0]["args"]["n_evaluated"] == rep.n_evaluated


def test_gap_trace_baseline_spans():
    from repro.gap.runner import run_gap

    tr = Tracer()
    rep = run_gap({"mm": EIN}, {"a": ARCH}, budgets=[40],
                  baselines=["random"], tracer=tr)
    assert not rep.violations
    spans = [e for e in tr.events if e["name"] == "baseline:random"]
    assert len(spans) == 1
    assert spans[0]["args"]["budgets"] == [40]
    assert spans[0]["args"]["final_gap"] >= 1.0
    # the exact optimum's search telemetry rides along
    assert any(e["name"] == "tcm_map:mm" for e in tr.events)


# --------------------------------------------------------------------------
# the served step: garbage-collection watch and device scopes
# --------------------------------------------------------------------------


def test_gc_watch_counts_a_forced_collection():
    import gc

    from repro.obs.serving import GcWatch

    watch = GcWatch().install()
    try:
        before = watch.snapshot()
        gc.collect()  # a full collection, with the profiler off
        got = watch.snapshot() - before
    finally:
        watch.uninstall()
    assert got.collections[2] >= 1
    assert got.pause_s > 0.0


def test_gc_watch_installs_once_and_uninstalls():
    import gc

    from repro.obs.serving import GcWatch

    watch = GcWatch()
    n = len(gc.callbacks)
    assert watch.install().install() is watch
    assert len(gc.callbacks) == n + 1
    watch.uninstall()
    assert len(gc.callbacks) == n
    before = watch.snapshot()
    gc.collect()
    assert watch.snapshot() == before


def _gc_spans_and_counts(tmp_path):
    """The ``gc`` spans on a profile of one forced collection, and what the
    process's watch counted meanwhile.  The watch is the one the serving
    process installs; it is left installed if it was."""
    import gc
    from pathlib import Path

    import jax

    from repro.obs.serving import GC_SPAN, WATCH

    installed = WATCH._callback in gc.callbacks
    WATCH.install()
    try:
        before = WATCH.snapshot()
        with jax.profiler.trace(str(tmp_path)):
            gc.collect()
        got = WATCH.snapshot() - before
    finally:
        if not installed:
            WATCH.uninstall()
    profile = jax.profiler.ProfileData.from_file(
        str(next(Path(tmp_path).rglob("*.xplane.pb"))))
    spans = [ev for plane in profile.planes
             if plane.name.startswith("/host:") for line in plane.lines
             for ev in line.events if ev.name == GC_SPAN]
    return spans, got


def test_gc_watch_puts_each_collection_on_the_trace(tmp_path):
    spans, got = _gc_spans_and_counts(tmp_path)
    # the forced collection at least; none the watch did not count
    assert 1 <= len(spans) <= sum(got.collections)


def test_serve_main_installs_the_one_watch(tmp_path, capsys):
    import gc

    from repro.launch import serve as serve_mod
    from repro.obs.serving import WATCH, GcWatch

    serve_mod.main(["--arch", "qwen1.5-0.5b", "--smoke", "--batch", "1",
                    "--prompt-len", "8", "--gen", "2"])
    assert "gc: " in capsys.readouterr().out
    watches = [cb for cb in gc.callbacks
               if isinstance(getattr(cb, "__self__", None), GcWatch)]
    assert watches == [WATCH._callback]
    # so a profile after it holds one span per collection, not two
    spans, got = _gc_spans_and_counts(tmp_path)
    assert 1 <= len(spans) <= sum(got.collections)


@pytest.fixture(scope="module")
def served_programs():
    """The compiled text of the smoke-width prefill and decode steps."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.mesh import make_elastic_mesh
    from repro.models import lm
    from repro.serving.engine import make_serve_steps
    from repro.models.lm import abstract_init

    cfg = get_config("phi3-mini-3.8b", smoke=True)
    B, P, slots = 2, 16, 32
    mesh = make_elastic_mesh(target_model=1, devices=jax.devices()[:1])
    params_abs, specs = abstract_init(cfg, jax.random.PRNGKey(0))
    cache_abs = jax.eval_shape(lambda: lm.init_cache(cfg, B, slots))
    batch_abs = {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32)}
    prefill, decode, (param_sh, batch_sh, cache_sh, tok_sh) = \
        make_serve_steps(cfg, mesh, specs, cache_abs, batch_abs)

    def placed(tree, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shardings)

    params, cache = placed(params_abs, param_sh), placed(cache_abs, cache_sh)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=tok_sh)
    return {name: step.lower(params, x, cache).compile().as_text()
            for name, step, x in (
                ("prefill", prefill, placed(batch_abs, batch_sh)),
                ("decode", decode, tok))}


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_served_step_keeps_every_scope(served_programs, step):
    import re

    from repro.obs.serving import LAYERS, SCOPES

    text = served_programs[step]
    assert text.startswith(f"HloModule jit_serve_{step},")
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    scopes = {part for path in paths for part in path.split("/")}
    assert set(SCOPES) | {LAYERS} <= scopes


# a scan's loop as the TPU compiler leaves it (no known_trip_count: the
# bound is the constant its condition compares with), a loop that keeps
# its trip count, and an async all-gather outside both
COLLECTIVES_HLO = """HloModule jit_step, is_scheduled=true

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %s = f32[] add(%x, %y)
}

%cond (p: (s32[], f32[8,4])) -> pred[] {
  %n = s32[] constant(30)
  %p = (s32[], f32[8,4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

%body (p.1: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %p.1 = (s32[], f32[8,4]) parameter(0)
  %i.1 = s32[] get-tuple-element(%p.1), index=0
  %x.1 = f32[8,4] get-tuple-element(%p.1), index=1
  %ar.1 = f32[8,4] all-reduce(%x.1), replica_groups={}, to_apply=%add
  %ar.2 = f32[8,4] all-reduce(%ar.1), replica_groups={}, to_apply=%add
  ROOT %t.1 = (s32[], f32[8,4]) tuple(%i.1, %ar.2)
}

%cond.2 (p.2: (s32[], f32[8,4])) -> pred[] {
  %p.2 = (s32[], f32[8,4]) parameter(0)
  ROOT %c.2 = pred[] constant(true)
}

%body.2 (p.3: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %p.3 = (s32[], f32[8,4]) parameter(0)
  %i.3 = s32[] get-tuple-element(%p.3), index=0
  %x.3 = f32[8,4] get-tuple-element(%p.3), index=1
  %cp.3 = f32[8,4] collective-permute(%x.3), source_target_pairs={{0,1}}
  ROOT %t.3 = (s32[], f32[8,4]) tuple(%i.3, %cp.3)
}

ENTRY %main (a: f32[8,4]) -> f32[8,4] {
  %a = f32[8,4] parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[8,4]) tuple(%z, %a)
  %w = (s32[], f32[8,4]) while(%init), condition=%cond, body=%body
  %w.2 = (s32[], f32[8,4]) while(%w), condition=%cond.2, body=%body.2, backend_config={"known_trip_count":{"n":"3"}}
  %r = f32[8,4] get-tuple-element(%w.2), index=1
  %ag = f32[32,4] all-gather-start(%r), dimensions={0}
  ROOT %agd = f32[32,4] all-gather-done(%ag)
}
"""


def test_collectives_count_each_loop_body_per_trip():
    """``collectives`` counts a loop body's collectives once per trip,
    whether the trip count is kept or only the condition's bound is, and
    an async start once (its done not again); bytes are operands."""
    from repro.obs.serving import collectives

    each = 8 * 4 * 4
    assert collectives(COLLECTIVES_HLO) == {
        "all-gather": (1, each),
        "all-reduce": (60, 60 * each),
        "collective-permute": (3, 3 * each)}


def test_serve_prints_collectives_on_a_mesh_of_four(tmp_path):
    """On four (CPU) devices ``launch/serve.py`` prints each compiled
    step's collectives and cache relayouts; in a process of its own, since
    the device count is fixed before JAX starts."""
    import os
    import subprocess
    import sys

    env = {"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "qwen1.5-0.5b",
         "--smoke", "--batch", "2", "--prompt-len", "8", "--gen", "3",
         "--model-parallel", "4"],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-1500:]
    lines = [ln for ln in r.stdout.splitlines()
             if ln.startswith("collectives per ")]
    assert [ln.split(" step:")[0] for ln in lines] == [
        "collectives per prefill", "collectives per decode"]
    assert all("all-reduce" in ln for ln in lines), lines
    assert [ln.split(" step:")[0] for ln in r.stdout.splitlines()
            if ln.startswith("cache relayouts per ")] == [
        "cache relayouts per prefill", "cache relayouts per decode"]
    # 8 + 3 slots are one chunk, which every step visits
    assert [ln for ln in r.stdout.splitlines()
            if ln.startswith("attend chunk share per ")] == [
        "attend chunk share per prefill step: 1.000",
        "attend chunk share per decode step: 1.000"]


def test_a_data_bounded_loop_counts_once_per_trip_of_the_loop_around_it():
    """A loop whose trip count is data (its condition compares the counter
    with a value, not a constant, as attention's KV loop over a stacked
    cache does) counts as one trip: its body once per trip of the loop
    around it, here a scan of 3."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.launch.hlo_parse import parse_hlo, run_counts, summarize

    def f(x, n):
        def outer(c, _):
            return lax.fori_loop(0, n, lambda i, y: jnp.tanh(y @ x), c), None
        return lax.scan(outer, x, None, length=3)[0]

    x = jnp.ones((8, 8), jnp.float32)
    text = jax.jit(f).lower(x, 5).compile().as_text()
    comps = parse_hlo(text)
    body, = [name for name, c in comps.items() if name != "__entry__"
             and any(kind == "dot" for _, kind, _, _ in c.instructions)]
    assert run_counts(comps)[body] == 3
    assert summarize(text).flops == 3 * 2 * 8 ** 3


@pytest.mark.parametrize("fill,rows,slots,share", [
    (0, 1536, 2048, 6 / 12),   # phi3 prefill: the causal lower triangle
    (1024, 1, 2048, 3 / 4),    # phi3 decode, fill 1024-1151
    (1151, 1, 2048, 3 / 4),
    (1536, 1, 2048, 1.0),      # the prefill cell's decode steps
    (512, 1, 4096, 2 / 8),     # Yi decode, fill 512-639
    (639, 1, 4096, 2 / 8),
    (0, 512, 4096, 1 / 8),     # Yi prefill
])
def test_attend_chunk_share_of_each_cells_steps(fill, rows, slots, share):
    """``attend_chunk_share`` counts the chunks each query block visits,
    by the loop's own bound, against every allocated chunk."""
    from repro.obs.serving import attend_chunk_share

    assert attend_chunk_share(fill, rows, slots) == share


# a stacked K leaf (4 layers of (2, 3, 8, 16)) among a step's parameters,
# a layer loop of 4 trips that slices a layer out (a buffer each trip) and
# writes its rows back in place, a whole-leaf copy after it, a weight and
# a flat buffer of a layer's element count that are no K/V
RELAYOUTS_HLO = r"""HloModule jit_step, is_scheduled=true

%slice_layer (p.0: bf16[4,2,3,8,16], i.0: s32[]) -> bf16[2,3,8,16] {
  %p.0 = bf16[4,2,3,8,16]{4,3,2,1,0} parameter(0)
  %i.0 = s32[] parameter(1)
  %z.0 = s32[] constant(0)
  %ds.0 = bf16[1,2,3,8,16]{4,3,2,1,0} dynamic-slice(%p.0, %i.0, %z.0, %z.0, %z.0, %z.0), dynamic_slice_sizes={1,2,3,8,16}
  ROOT %b.0 = bf16[2,3,8,16]{3,2,1,0} bitcast(%ds.0)
}

%write_row (p.1: bf16[4,2,3,8,16], r.1: bf16[1,2,3,1,16], i.1: s32[]) -> bf16[4,2,3,8,16] {
  %p.1 = bf16[4,2,3,8,16]{4,3,2,1,0} parameter(0)
  %r.1 = bf16[1,2,3,1,16]{4,3,2,1,0} parameter(1)
  %i.1 = s32[] parameter(2)
  %z.1 = s32[] constant(0)
  ROOT %dus.1 = bf16[4,2,3,8,16]{4,3,2,1,0} dynamic-update-slice(%p.1, %r.1, %i.1, %z.1, %z.1, %z.1, %z.1)
}

%cond (c: (s32[], bf16[4,2,3,8,16])) -> pred[] {
  %c = (s32[], bf16[4,2,3,8,16]) parameter(0)
  ROOT %t = pred[] constant(true)
}

%body (s: (s32[], bf16[4,2,3,8,16])) -> (s32[], bf16[4,2,3,8,16]) {
  %s = (s32[], bf16[4,2,3,8,16]) parameter(0)
  %i = s32[] get-tuple-element(%s), index=0
  %st = bf16[4,2,3,8,16]{4,3,2,1,0} get-tuple-element(%s), index=1
  %layer = bf16[2,3,8,16]{3,2,1,0} fusion(%st, %i), kind=kLoop, calls=%slice_layer
  %flat = bf16[768]{0} reshape(%layer)
  %row = bf16[1,2,3,1,16]{4,3,2,1,0} slice(%st), slice={[0:1], [0:2], [0:3], [0:1], [0:16]}
  %st.1 = bf16[4,2,3,8,16]{4,3,2,1,0} fusion(%st, %row, %i), kind=kLoop, calls=%write_row
  ROOT %out = (s32[], bf16[4,2,3,8,16]) tuple(%i, %st.1)
}

ENTRY %main (k: bf16[4,2,3,8,16], w: bf16[2,3,8,16]) -> bf16[4,2,3,8,16] {
  %k = bf16[4,2,3,8,16]{4,3,2,1,0} parameter(0), metadata={op_name="cache[\'groups\'][0][0][\'attn\'][\'k\']"}
  %w = bf16[2,3,8,16]{3,2,1,0} parameter(1), metadata={op_name="params[\'wk\']"}
  %z = s32[] constant(0)
  %init = (s32[], bf16[4,2,3,8,16]) tuple(%z, %k)
  %loop = (s32[], bf16[4,2,3,8,16]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"4"}}
  %st.2 = bf16[4,2,3,8,16]{4,3,2,1,0} get-tuple-element(%loop), index=1
  ROOT %whole = bf16[4,2,3,8,16]{3,4,2,1,0} copy(%st.2)
}
"""


def test_cache_relayouts_counts_each_layer_buffer_per_trip():
    """``cache_relayouts`` counts the layer sliced out in each of the
    loop's 4 trips and the whole-leaf copy; not the fused slice inside,
    the in-place row write, the cache parameter, a weight of a layer's
    shape, or a flat buffer without a d_head dim."""
    from repro.obs.serving import cache_relayouts

    layer = 2 * 3 * 8 * 16 * 2
    assert cache_relayouts(RELAYOUTS_HLO) == (4 + 1, 4 * layer + 4 * layer)


def test_served_cache_keeps_its_format_and_logits():
    """On the CPU, the cache made by ``jax.jit(init_cache, out_shardings=
    cache_sh)``, as the benchmark makes it, keeps the format that
    ``make_serve_steps`` gives its K/V through a reset, a prefill and
    decode steps, and the smoke-width logits match the per-layer-scan
    reference of ``test_stacked_cache``."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.layout import Format
    from test_stacked_cache import _per_layer_scan_group

    from repro.configs import get_config
    from repro.launch.mesh import make_elastic_mesh
    from repro.models import lm
    from repro.serving.engine import make_serve_steps

    cfg = get_config("phi3-mini-3.8b", smoke=True)
    B, P, steps = 2, 12, 3
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, P)), jnp.int32)
    fed = jnp.asarray(rng.integers(0, cfg.vocab, (B, steps)), jnp.int32)
    params, specs = lm.init(cfg, jax.random.PRNGKey(5))
    init_cache = partial(lm.init_cache, cfg, B, P + steps)
    mesh = make_elastic_mesh(target_model=1, devices=jax.devices()[:1])
    prefill, decode, (param_sh, batch_sh, cache_sh, tok_sh) = \
        make_serve_steps(cfg, mesh, specs, jax.eval_shape(init_cache),
                         {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32)})
    kv = [(i, f.layout.major_to_minor)
          for i, f in enumerate(jax.tree.leaves(cache_sh))
          if isinstance(f, Format)]
    assert kv

    def formats(cache):
        leaves = jax.tree.leaves(cache)
        return [(i, leaves[i].format.layout.major_to_minor) for i, _ in kv]

    reset = jax.jit(lambda c: jax.tree.map(
        lambda a: (jnp.zeros_like(a)
                   if jnp.issubdtype(a.dtype, jnp.integer) else a), c),
        out_shardings=cache_sh, donate_argnums=0)
    cache = jax.jit(init_cache, out_shardings=cache_sh)()
    assert formats(cache) == kv
    cache = reset(cache)
    assert formats(cache) == kv
    params = jax.device_put(params, param_sh)
    last, cache = prefill(params, jax.device_put({"tokens": tokens},
                                                 batch_sh), cache)
    served = [last]
    for t in range(steps):
        assert formats(cache) == kv
        out, cache = decode(params, jax.device_put(fed[:, t:t + 1], tok_sh),
                            cache)
        served.append(out)
    assert formats(cache) == kv

    def reference():
        c = init_cache()
        out, c = jax.jit(lambda p, b, c: lm.prefill(cfg, p, b, c))(
            params, {"tokens": tokens}, c)
        want = [out]
        step = jax.jit(lambda p, t, c: lm.decode_step(cfg, p, t, c))
        for t in range(steps):
            out, c = step(params, fed[:, t:t + 1], c)
            want.append(out)
        return want

    with pytest.MonkeyPatch.context() as m:
        m.setattr(lm, "_apply_group", _per_layer_scan_group)
        want = reference()
    for got, ref in zip(served, want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=0, atol=1e-6)
