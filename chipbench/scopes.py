"""Attribute the served step programs' device time to the program's scopes.

The program wraps each layer kind of its served step in a
``jax.named_scope`` (``repro.obs.serving``).  XLA keeps the scope path in
each instruction's ``metadata={op_name="jit(serve_decode)/layers/while/
body/attend/..."}``, and the profiler trace names each device op by its
instruction (``trace.op_name``) and each program run by its module (the
device's ``XLA Modules`` line).  Given the compiled text of the step
programs, this module joins the two.  Each leaf device op (the rule of
``trace._leaves``) of ``jit_serve_prefill`` inside a ``prefill`` span, or
of ``jit_serve_decode`` inside a ``decode`` span, goes to one bucket:

1. the deepest leaf scope on its own ``op_name``, else on that of the
   innermost device op that encloses it in time and has one (an
   ``attend`` scan's ``while`` lends ``attend`` to the ops it runs);
2. else ``layers``, where that is on either path (the layer scan's slices
   and stacking of the cache);
3. else ``unscoped`` (XLA's own copies, which carry no metadata).

Ops of other programs (the benchmark's sample and reset) are in no bucket.
The scope names are the program's, repeated here so that the yardstick
imports nothing of the program; a program without them (or without the
step names) leaves every span without scopes.  The trace is read as
``trace.reduce`` reads it, with the program's ``gc`` spans besides, which
name the idle gaps that fall inside a garbage collection.
``scoped_run.py`` runs a cell's traced window and prints this split.
"""
from __future__ import annotations

import bisect
import re
from collections import Counter, defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from chipbench import trace
from chipbench.trace import Union

LEAF_SCOPES = ("embed", "norm", "qkv", "kv_write", "attend", "attn_out",
               "mlp", "lm_head")
LAYERS, UNSCOPED = "layers", "unscoped"
BUCKETS = LEAF_SCOPES + (LAYERS, UNSCOPED)
MATMUL = ("qkv", "attn_out", "mlp", "lm_head")
PROGRAMS = {"jit_serve_prefill": "prefill", "jit_serve_decode": "decode"}
GC = "gc"
SPANS = trace.SPANS + (GC,)
# device ms per traced span under these buckets: the per-layer metrics
# that a harness holding this split would report
METRICS = {
    "decode.attend_ms": ("decode", ("attend",)),
    "decode.matmul_ms": ("decode", MATMUL),
    "decode.unscoped_ms": ("decode", (LAYERS, UNSCOPED)),
    "prefill.attend_ms": ("prefill", ("attend",)),
    "prefill.matmul_ms": ("prefill", MATMUL),
}

_MODULE = re.compile(r"HloModule ([^\s,]+)")
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([^\s=]+) = [^\n]*?op_name="([^"]*)"', re.MULTILINE)

Event = Tuple[int, int, str]


def op_names(texts: Iterable[str]) -> Dict[str, Dict[str, str]]:
    """module -> instruction -> op_name, from compiled HLO texts."""
    out = {}
    for text in texts:
        m = _MODULE.match(text)
        if m:
            out[m.group(1)] = dict(_INSTRUCTION.findall(text))
    return out


def module_name(event_name: str) -> str:
    """``jit_serve_decode(1234)`` -> ``jit_serve_decode``."""
    return (event_name[:event_name.rindex("(")]
            if event_name.endswith(")") else event_name)


def bucket(paths: Sequence[str]) -> str:
    """The bucket of an op whose own ``op_name`` is ``paths[0]``, followed
    by those of the ops enclosing it, innermost first."""
    for path in paths:
        for part in reversed(path.split("/")):
            if part in LEAF_SCOPES:
                return part
    if any(LAYERS in path.split("/") for path in paths):
        return LAYERS
    return UNSCOPED


def _read(profile):
    """Each chip's ops and program runs as (start, end, name) on the
    host's clock, and the host spans of ``SPANS``."""
    spans: Dict[str, List[Tuple[int, int]]] = defaultdict(list)
    ops, runs, modules, enqueued = {}, {}, {}, {}
    for plane in profile.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            ops[chip] = [
                (int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                 trace.op_name(ev.name))
                for ev in trace._events(plane, trace.OPS_LINE)]
            programs = list(trace._events(plane, trace.MODULES_LINE))
            runs[chip] = [(int(ev.start_ns),
                           int(ev.start_ns + ev.duration_ns), ev.name)
                          for ev in programs]
            modules[chip] = {int(dict(ev.stats)["run_id"]): int(ev.start_ns)
                             for ev in programs if "run_id" in dict(ev.stats)}
        elif plane.name.startswith("/host:"):
            for ev in trace._events(plane):
                if ev.name in SPANS:
                    spans[ev.name].append(
                        (int(ev.start_ns), int(ev.start_ns + ev.duration_ns)))
                elif ev.name == trace.ENQUEUE:
                    run = int(dict(ev.stats).get("run_id", -1))
                    enqueued[run] = min(enqueued.get(run, ev.start_ns),
                                        int(ev.start_ns))
    for chip in ops:
        d = trace._device_offset(modules[chip], enqueued)
        ops[chip] = [(s + d, e + d, n) for s, e, n in ops[chip]]
        runs[chip] = [(s + d, e + d, n) for s, e, n in runs[chip]]
    return ops, runs, spans


def _walk(events):
    """Each event in start order, with whether ``trace._leaves`` counts it
    a leaf and the non-leaf events still open at its start, innermost
    last."""
    leaves = Counter(trace._leaves(events))
    open_: List[Event] = []
    for ev in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        while open_ and open_[-1][1] <= ev[0]:
            open_.pop()
        leaf = leaves[ev] > 0
        if leaf:
            leaves[ev] -= 1
        yield ev, leaf, open_
        if not leaf:
            open_.append(ev)


def attribute(devices: Dict[int, List[Event]], runs: Dict[int, List[Event]],
              spans: Dict[str, List[Tuple[int, int]]],
              names: Dict[str, Dict[str, str]]
              ) -> Dict[str, List[Optional[Dict]]]:
    """Per span name of ``PROGRAMS``, per span in time order: the step
    program's leaf device time inside it by bucket (``scopes``) and the
    busy union of all its ops there (``program_busy_s``), in seconds
    averaged over the chips; None for a span where it ran nothing.

    ``devices`` and ``runs`` are each chip's ops and program runs as
    (start, end, name), on the host's clock; ``names`` is ``op_names``."""
    wanted = {m: s for m, s in PROGRAMS.items() if m in names}
    ivs = {s: sorted(spans.get(s, [])) for s in wanted.values()}
    starts = {s: [a for a, _ in iv] for s, iv in ivs.items()}
    totals = {s: [defaultdict(float) for _ in iv] for s, iv in ivs.items()}
    busy = {s: [0.0] * len(iv) for s, iv in ivs.items()}
    for chip, events in devices.items():
        run_list = sorted(runs.get(chip, []))
        run_starts = [s for s, _, _ in run_list]
        inside = defaultdict(list)  # (span name, index) -> intervals
        for (s, e, instr), leaf, enclosing in _walk(events):
            r = bisect.bisect_right(run_starts, s) - 1
            if r < 0 or s >= run_list[r][1]:
                continue
            module = module_name(run_list[r][2])
            span = wanted.get(module)
            if span is None:
                continue
            i = bisect.bisect_right(starts[span], s) - 1
            if i < 0 or s >= ivs[span][i][1]:
                continue
            end = min(e, ivs[span][i][1])
            inside[span, i].append((s, end))
            if leaf:
                ops = names[module]
                paths = [ops.get(instr, "")] + [
                    ops.get(n, "") for _, _, n in reversed(enclosing)]
                totals[span][i][bucket(paths)] += (end - s) / 1e9
        for (span, i), iv in inside.items():
            busy[span][i] += Union(iv).within(*ivs[span][i]) / 1e9
    n = len(devices)
    return {
        span: [{"program_busy_s": b / n,
                "scopes": {k: totals[span][i][k] / n for k in BUCKETS}}
               if b else None for i, b in enumerate(busy[span])]
        for span in busy}


def split(profile, programs: Sequence[str]) -> Dict:
    """The step programs' device time by scope in each ``prefill`` and
    ``decode`` span of a ``jax.profiler.ProfileData`` (``attribute``), the
    ``gc`` spans, and the longest idle gaps of the first chip in the
    window, each named by the innermost host span it fell in, ``gc``
    included; {} where the trace holds no device op."""
    devices, runs, spans = _read(profile)
    if not any(devices.values()) or not spans.get("window"):
        return {}
    w0, w1 = min(spans["window"])[0], max(e for _, e in spans["window"])
    first = Union((s, e) for s, e, _ in devices[min(devices)])
    return {
        "spans": attribute(devices, runs, spans, op_names(programs)),
        "gc_spans_s": [(e - s) / 1e9 for s, e in sorted(spans.get(GC, []))
                       if w0 <= s < w1],
        "idle_gaps": trace._idle_gaps(first, spans, w0, w1),
    }


def ms_per_span(got: Dict, span: str, buckets: Sequence[str]
                ) -> Optional[float]:
    """Device ms per ``span`` of a ``split`` under ``buckets``; None unless
    every such span holds the step program's scopes."""
    spans = (got.get("spans") or {}).get(span)
    if not spans or any(s is None for s in spans):
        return None
    return 1e3 * sum(s["scopes"][b] for s in spans
                     for b in buckets) / len(spans)


def metrics(got: Dict) -> Dict[str, Optional[float]]:
    """Each of ``METRICS`` from a ``split``."""
    return {name: ms_per_span(got, span, buckets)
            for name, (span, buckets) in METRICS.items()}


def summary(got: Dict) -> Dict[str, Dict]:
    """Per step program: ms per span in each bucket, the leaf ops' sum, the
    program's busy union and the share of the leaf ops under a leaf
    scope, in %."""
    out = {}
    for span in PROGRAMS.values():
        found = [s for s in (got.get("spans") or {}).get(span, []) if s]
        if not found:
            continue
        ms = {b: 1e3 * sum(s["scopes"][b] for s in found) / len(found)
              for b in BUCKETS}
        leaves = sum(ms.values())
        out[span] = {
            "spans": len(found), "ms": ms, "leaf_ms": leaves,
            "program_busy_ms": 1e3 * sum(s["program_busy_s"]
                                         for s in found) / len(found),
            "scoped_pct": 100 * sum(ms[b] for b in LEAF_SCOPES) / leaves
            if leaves else 0.0}
    return out
