"""The served step's trace vocabulary: device scopes and the ``gc`` span.

Device scopes are ``jax.named_scope`` names the model puts around each
layer kind of the served step.  XLA keeps them in every instruction's
``metadata={op_name=...}``, so a profiler trace of the step can be joined
with the compiled program to say which layer kind each device op belongs
to.  They are metadata only: the compiled program is otherwise the same.

  embed     the token embedding lookup (``models/lm.py _embed``)
  norm      every RMSNorm
  qkv       the q/k/v projections and RoPE
  kv_write  writes of the new K/V into the cache
  attend    every ``flash_attention`` call
  attn_out  the attention output projection
  mlp       the MLP or MoE
  lm_head   the output projection to the vocabulary

``LAYERS`` scopes the stack of layers (the ``lax.scan`` over stacked
parameters, or its unrolled loop), so ops of the stack that no leaf
scope claims, such as the scan's slices of the stacked cache, are still
told apart from ops outside it.

:class:`GcWatch` counts Python's garbage collections and their pauses, and
puts each collection on the profiler's host clock as a ``gc`` span, beside
the spans of whoever drives the served step.

:func:`collectives` counts the collectives one run of a compiled step
issues, by kind, from the program's text: on a mesh of several chips,
the all-reduces of tensor parallelism and anything that moves the cache.
:func:`cache_relayouts` counts, the same way, the buffers it makes that
hold a layer's K or V or a whole stacked K/V leaf: what reading the
cache where it lies avoids.  :func:`attend_chunk_share` says how much of
the cache a step's attention visits.

Nothing here imports JAX at import time.
"""
from __future__ import annotations

import gc
import math
import re
import time
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

SCOPES = EMBED, NORM, QKV, KV_WRITE, ATTEND, ATTN_OUT, MLP, LM_HEAD = (
    "embed", "norm", "qkv", "kv_write", "attend", "attn_out", "mlp",
    "lm_head")
LAYERS = "layers"
GC_SPAN = "gc"


@dataclass(frozen=True)
class GcCounts:
    """Collections per generation and their total pause, in seconds."""

    collections: Tuple[int, int, int] = (0, 0, 0)
    pause_s: float = 0.0

    def __sub__(self, earlier: "GcCounts") -> "GcCounts":
        return GcCounts(
            tuple(a - b for a, b in zip(self.collections,
                                        earlier.collections)),
            self.pause_s - earlier.pause_s)


class GcWatch:
    """A ``gc.callbacks`` hook: counts and times every collection since
    :meth:`install`, each inside a ``jax.profiler.TraceAnnotation``
    named ``gc``.  With the profiler off a collection costs one Python
    call more."""

    def __init__(self):
        self._collections = [0, 0, 0]
        self._pause_s = 0.0
        self._t0: Optional[float] = None
        self._span = None
        self._annotation = None

    def install(self) -> "GcWatch":
        """Hook into ``gc.callbacks``, once however often it is called."""
        if self._callback not in gc.callbacks:
            from jax.profiler import TraceAnnotation

            self._annotation = TraceAnnotation
            gc.callbacks.append(self._callback)
        return self

    def uninstall(self) -> None:
        if self._callback in gc.callbacks:
            gc.callbacks.remove(self._callback)

    def snapshot(self) -> GcCounts:
        return GcCounts(tuple(self._collections), self._pause_s)

    def _callback(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._span = self._annotation(GC_SPAN)
            self._span.__enter__()
            self._t0 = time.perf_counter()
        elif self._t0 is not None:  # "stop" of a collection seen starting
            self._pause_s += time.perf_counter() - self._t0
            self._collections[info["generation"]] += 1
            self._span.__exit__(None, None, None)
            self._t0 = self._span = None


WATCH = GcWatch()  # the serving process's watch: install it once, read it


def collectives(hlo_text: str) -> Dict[str, Tuple[int, int]]:
    """``{kind: (count, bytes)}`` of the collectives one run of a compiled
    step issues, from its optimized HLO text (``compiled.as_text()``),
    read by ``repro.launch.hlo_parse``: a loop body's collectives count
    once per trip, and bytes are their operands on one device.  Kinds
    that do not occur are left out."""
    from repro.launch.hlo_parse import summarize

    s = summarize(hlo_text)
    return {k: (round(n), round(s.collective_bytes[k]))
            for k, n in sorted(s.collective_count.items())}


_CALLEE = re.compile(r"calls=%?([\w\.\-]+)")
# kinds that make no buffer of their own (``copy-done``: its start has)
_NO_BUFFER = {"parameter", "tuple", "get-tuple-element", "bitcast", "while",
              "conditional", "call", "constant", "dynamic-update-slice",
              "copy-done", "optimization-barrier"}


def _dims(dims: str) -> Tuple[int, ...]:
    return tuple(int(d) for d in dims.split(",") if d)


def cache_relayouts(hlo_text: str) -> Tuple[int, int]:
    """``(count, bytes)`` of the buffers one run of a compiled step makes
    that hold one layer's K or V, or a whole stacked K/V leaf, from its
    optimized HLO text (``compiled.as_text()``): copies, fusions, slices,
    transposes and reshapes of the cache.  Loop bodies count once per
    trip, as :func:`collectives` counts them, and shapes are one
    device's.  The stacked K/V are the step's parameters named
    ``cache[...]['<leaf>']`` for a leaf of ``kv_cache.KV_LEAVES``; a
    buffer is one of theirs when it holds as many elements as a layer of
    a leaf, or the whole leaf, and has a d_head dim.  Writes into the
    stack in place (a ``dynamic-update-slice``, or a fusion whose root is
    one) make no buffer, nor do parameters, tuples and bitcasts."""
    from repro.launch.hlo_parse import _shape_bytes, parse_hlo, run_counts
    from repro.models.kv_cache import KV_LEAVES

    # a stacked K/V leaf's op_name among a step's parameters
    kv_param = re.compile(r"cache\[.*\[\\?'(?:%s)\\?'\]"
                          % "|".join(KV_LEAVES))
    comps = parse_hlo(hlo_text)
    d_head = {}  # elements of a layer of a leaf, or of the leaf -> d_head
    for _, kind, shapes, rest in comps["__entry__"].instructions:
        op = re.search(r'op_name="([^"]*)"', rest)
        if (kind == "parameter" and op and kv_param.fullmatch(op.group(1))
                and shapes):
            dims = _dims(shapes[0][1])
            d_head[math.prod(dims[1:])] = d_head[math.prod(dims)] = dims[-1]

    fused, in_place = set(), set()
    for c in comps.values():
        for _, kind, _, rest in c.instructions:
            callee = _CALLEE.search(rest)
            if kind == "fusion" and callee:
                fused.add(callee.group(1))
        if c.instructions and c.instructions[-1][1] == "dynamic-update-slice":
            in_place.add(c.name)  # its root, which prints last
    runs = run_counts(comps)
    count = nbytes = 0.0
    for name, c in comps.items():
        if name == "__entry__" or name in fused:
            continue
        for _, kind, shapes, rest in c.instructions:
            callee = _CALLEE.search(rest)
            if kind in _NO_BUFFER or (kind == "fusion" and callee
                                      and callee.group(1) in in_place):
                continue
            made = [_shape_bytes(dt, dims)[1] for dt, dims in shapes
                    if d_head.get(math.prod(_dims(dims))) in _dims(dims)]
            if kind.endswith("-start"):
                made = made[:1]  # the operand and its copy: one buffer
            count += runs.get(name, 0.0) * len(made)
            nbytes += runs.get(name, 0.0) * sum(made)
    return round(count), round(nbytes)


def attend_chunk_share(fill: int, rows: int, slots: int) -> float:
    """The share of (query block, KV chunk) pairs that one step's causal
    attention over a stacked cache visits, of those a loop over every
    allocated chunk would: ``rows`` new rows at fill ``fill`` in a cache
    of ``slots``, chunked as ``attention_block`` has ``flash_attention``
    chunk them.  It asks ``layers.visible_chunks``, the bound the loop
    itself takes."""
    from repro.models.layers import CHUNK, visible_chunks

    kv_chunk, q_chunk = min(CHUNK, slots), min(CHUNK, rows)
    blocks = -(-rows // q_chunk)
    seen = sum(int(visible_chunks(fill, qi, q_chunk, fill + rows, kv_chunk,
                                  slots, True)) for qi in range(blocks))
    return seen / (blocks * -(-slots // kv_chunk))
