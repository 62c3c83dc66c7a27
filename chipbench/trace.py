"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

The device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation that ran, their ``XLA Modules`` line one per
program run.  The host planes hold the benchmark's own
``TraceAnnotation`` spans (``window``, ``prompts``, ``prefill``,
``decode``, ``sample``), and the runtime's ``DoEnqueueProgram`` events,
which put the device's clock on the host's.  From those this module
gives:

- per span: its interval, the device's busy time inside it (the union of
  operation intervals, averaged over the chips) and the part of that in
  collective operations;
- the traced window's length and busy time;
- the operations that took most device time (innermost ops only, named
  by their HLO instruction), and the longest idle gaps on the first chip,
  each named by the innermost host span it fell in.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"  # host event naming the run it enqueues
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE)
SPANS = ("window", "prompts", "prefill", "decode", "sample")
TOP = 10

Interval = Tuple[int, int]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Union:
    """Sorted disjoint intervals, measured inside any window."""

    def __init__(self, intervals: Iterable[Interval]):
        self.iv = merge(intervals)
        self.starts = [s for s, _ in self.iv]
        self._prefix = [0]
        for s, e in self.iv:
            self._prefix.append(self._prefix[-1] + e - s)

    def _covered_until(self, t: int) -> int:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0
        s, e = self.iv[i - 1]
        return self._prefix[i - 1] + min(e, t) - s

    def within(self, s: int, e: int) -> int:
        return self._covered_until(e) - self._covered_until(s)

    def gaps(self, s: int, e: int) -> List[Interval]:
        out, t = [], s
        for a, b in self.iv:
            if b <= s or a >= e:
                continue
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if t < e:
            out.append((t, e))
        return out


def op_name(event_name: str) -> str:
    """The HLO instruction's name: TPU traces name an op by its whole
    text, ``%fusion.3 = bf16[...] fusion(...), kind=...``."""
    if event_name.startswith("%") and " = " in event_name:
        return event_name[1:event_name.index(" = ")]
    return event_name


def _events(plane, line_name=None):
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for ev in line.events:
                yield ev


def _device_offset(modules: Dict[int, int], enqueued: Dict[int, int]) -> int:
    """Nanoseconds to add to a device's clock to put it on the host's.

    The two clocks of a TPU trace differ by a millisecond or two.  A
    program cannot start before the host began to enqueue it, so the
    offset is the largest (enqueue start - program start) over the runs
    both sides name; the device started as soon as the host allowed in at
    least one of them, since the loop waits for every step."""
    gaps = [enqueued[r] - t for r, t in modules.items() if r in enqueued]
    return max(gaps) if gaps else 0


def reduce(profile) -> Dict:
    """Reduce a ``jax.profiler.ProfileData`` (see module docstring)."""
    spans: Dict[str, List[Interval]] = defaultdict(list)
    ops: Dict[int, List[Tuple[int, int, str]]] = {}
    modules: Dict[int, Dict[int, int]] = {}
    enqueued: Dict[int, int] = {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            ops[chip] = [
                (int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                 op_name(ev.name)) for ev in _events(plane, OPS_LINE)]
            modules[chip] = {
                int(dict(ev.stats)["run_id"]): int(ev.start_ns)
                for ev in _events(plane, MODULES_LINE)
                if "run_id" in dict(ev.stats)}
        elif plane.name.startswith("/host:"):
            for ev in _events(plane):
                if ev.name in SPANS:
                    spans[ev.name].append(
                        (int(ev.start_ns), int(ev.start_ns + ev.duration_ns)))
                elif ev.name == ENQUEUE:
                    run = int(dict(ev.stats).get("run_id", -1))
                    enqueued[run] = min(enqueued.get(run, ev.start_ns),
                                        int(ev.start_ns))
    devices = {}
    for chip, evs in ops.items():
        d = _device_offset(modules[chip], enqueued)
        devices[chip] = [(s + d, e + d, n) for s, e, n in evs]
    if not devices or not any(devices.values()):
        return {}
    chips = sorted(devices)
    busy = [Union((s, e) for s, e, _ in devices[c]) for c in chips]
    coll = [Union((s, e) for s, e, n in devices[c] if COLLECTIVE.search(n))
            for c in chips]

    def measure(unions, s, e):
        return sum(u.within(s, e) for u in unions) / len(unions) / 1e9

    out_spans = {
        name: [{"start_s": s / 1e9, "seconds": (e - s) / 1e9,
                "busy_s": measure(busy, s, e),
                "collective_s": measure(coll, s, e)}
               for s, e in sorted(ivs)]
        for name, ivs in spans.items() if name != "window"}
    if spans.get("window"):
        w0, w1 = min(spans["window"])[0], max(e for _, e in spans["window"])
    else:
        every = [iv for ivs in spans.values() for iv in ivs]
        if not every:
            return {}
        w0, w1 = min(s for s, _ in every), max(e for _, e in every)
    return {
        "chips": len(chips),
        "window_s": (w1 - w0) / 1e9,
        "busy_s": measure(busy, w0, w1),
        "spans": out_spans,
        "breakdown": {
            "device_ops": _top_ops(devices, chips, w0, w1),
            "idle_gaps": _idle_gaps(busy[0], spans, w0, w1),
        },
    }


def _leaves(events):
    """Events that hold no other: a ``while`` op's event spans its body's
    ops, which the line lists too."""
    ordered = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    return [ev for ev, nxt in zip(ordered, ordered[1:] + [None])
            if nxt is None or nxt[0] >= ev[1]]


def _top_ops(devices, chips, w0, w1) -> List[List]:
    total: Dict[str, int] = defaultdict(int)
    for c in chips:
        for s, e, name in _leaves(devices[c]):
            if s >= w0 and e <= w1:
                total[name] += e - s
    top = sorted(total.items(), key=lambda kv: -kv[1])[:TOP]
    return [[name, ns / len(chips) / 1e9] for name, ns in top]


def _idle_gaps(busy: Union, spans, w0, w1) -> List[List]:
    labelled = [(e - s, name, s, e) for name, ivs in spans.items()
                if name != "window" for s, e in ivs]

    def host_at(t):
        inside = [(d, n) for d, n, s, e in labelled if s <= t < e]
        return min(inside)[1] if inside else "between spans"

    gaps = sorted(busy.gaps(w0, w1), key=lambda g: g[0] - g[1])[:TOP]
    return [[host_at((s + e) // 2), (e - s) / 1e9] for s, e in gaps]
