"""One run of one cell: set up, serve a closed loop for the window, check.

The program is driven only through what ``repro.launch.serve`` uses:
``repro.models.lm.init`` (shapes and logical specs, under
``jax.eval_shape``), ``repro.launch.mesh.make_elastic_mesh``,
``repro.models.lm.init_cache`` and ``repro.serving.engine.make_serve_steps``,
whose ``prefill_step`` and ``decode_step`` the window times.  The weights
are the benchmark's own (``weights.make``), placed with the shardings
``make_serve_steps`` returns.

Each batch: B prompts drawn from the seed are prefilled and their first
tokens come to the host; then ``decode_steps`` greedy steps run, each timed
from dispatch until its B tokens are on the host, as a server that streams
tokens needs them.  Batches run back to back until ``seconds`` have passed;
the window ends with the last whole batch.
"""
from __future__ import annotations

import statistics
import sys
import tempfile
import time
from dataclasses import dataclass, field
from functools import partial
from pathlib import Path
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench, check, counts, reference, traffic, weights
from chipbench import trace as trace_mod

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """No accelerator of a kind the peak table knows, or too few chips."""


def device_check(chips: int):
    """The first ``chips`` devices and their published peaks."""
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r}, "
                     f"device {kind!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    try:
        peak = bench.peaks(kind)
    except bench.SpecError as e:
        raise NoChip(str(e)) from None
    return devs[:chips], peak


class _CompileCounter:
    """Counts backend compilations (and compile-cache loads) while on."""

    def __init__(self):
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **kw):
        if self.on and event == BACKEND_COMPILE:
            self.count += 1


_COUNTER: Optional[_CompileCounter] = None


def _compile_counter() -> _CompileCounter:
    # JAX's listeners are process-wide and cannot be removed: register once
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = _CompileCounter()
    return _COUNTER


def _abstract_params(cfg):
    """Parameter shapes and logical specs, with nothing allocated."""
    from repro.models import lm

    holder = {}

    def run(key):
        p, s = lm.init(cfg, key)
        holder["specs"] = s
        return p

    shapes = jax.eval_shape(run, jax.random.PRNGKey(0))
    return shapes, holder["specs"]


@dataclass
class Served:
    """What the window served, with the host clock's readings."""

    tokens: List[np.ndarray] = field(default_factory=list)  # (B, G+1) each
    prefill_s: List[float] = field(default_factory=list)
    decode_s: List[float] = field(default_factory=list)


class Server:
    """The program's compiled steps, its weights and one cache of slots."""

    def __init__(self, cell: bench.Cell, cfg, devices):
        from repro.launch.mesh import make_elastic_mesh
        from repro.models import lm
        from repro.serving.engine import make_serve_steps

        mix = cell.traffic
        self.mix = mix
        B, P = mix["batch"], mix["prompt_tokens"]
        mesh = make_elastic_mesh(
            target_model=cell.config["mesh"]["model"], devices=devices)
        init_cache = partial(lm.init_cache, cfg, B, mix["cache_slots"])
        batch_abs = {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32)}
        self.params_abs, specs = _abstract_params(cfg)
        self.prefill_step, self.decode_step, shardings = make_serve_steps(
            cfg, mesh, specs, jax.eval_shape(init_cache), batch_abs)
        self.param_sh, batch_sh, cache_sh, tok_sh = shardings
        self.tok_sh = batch_sh["tokens"]
        self.cache = jax.jit(init_cache, out_shardings=cache_sh)()
        self.sample = jax.jit(
            lambda logits: jnp.argmax(logits, -1).astype(jnp.int32)[:, None],
            out_shardings=tok_sh)
        # a new batch reuses the slots: only the fill counters go back to 0
        self.reset = jax.jit(
            lambda c: jax.tree.map(
                lambda a: (jnp.zeros_like(a)
                           if jnp.issubdtype(a.dtype, jnp.integer) else a), c),
            out_shardings=cache_sh, donate_argnums=0)
        self.params = None

    def load(self, seed: int, d_model: int):
        self.params = weights.make(self.params_abs, self.param_sh, seed,
                                   d_model)
        jax.block_until_ready(self.params)

    def serve(self, prompts: np.ndarray, steps: int,
              out: Optional[Served]) -> None:
        """Prefill ``prompts`` and decode ``steps`` tokens; record the
        served tokens (B, steps + 1) and the clock's readings in ``out``."""
        annotate = jax.profiler.TraceAnnotation
        with annotate("prompts"):
            batch = {"tokens": jax.device_put(prompts, self.tok_sh)}
        clock = time.perf_counter
        t0 = clock()
        with annotate("prefill"):
            self.cache = self.reset(self.cache)
            last, self.cache = self.prefill_step(self.params, batch,
                                                 self.cache)
            with annotate("sample"):
                tok = self.sample(last)
                served = [np.asarray(tok)]
        ends = [clock()]
        for _ in range(steps):
            with annotate("decode"):
                logits, self.cache = self.decode_step(self.params, tok,
                                                      self.cache)
                with annotate("sample"):
                    tok = self.sample(logits)
                    served.append(np.asarray(tok))
            ends.append(clock())
        if out is not None:
            out.prefill_s.append(ends[0] - t0)
            out.decode_s += list(np.diff(ends))
            out.tokens.append(np.concatenate(served, axis=1))


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _traced_works(cell: bench.Cell, batches: int) -> Dict[str, list]:
    """Needed work per chip of each step of ``batches`` batches, in the
    order served."""
    mix, dims = cell.traffic, counts.Dims.from_config(cell.config)
    B, P, G = mix["batch"], mix["prompt_tokens"], mix["decode_steps"]
    pre = counts.prefill(dims, B, P).per_chip(cell.chips)
    dec = [counts.decode(dims, B, P + i + 1).per_chip(cell.chips)
           for i in range(G)]
    return {"prefill": [pre] * batches, "decode": dec * batches}


def _records(cell, peak, reduced, batches) -> Dict:
    spans = reduced.get("spans", {})
    for name, works in (_traced_works(cell, batches).items()
                        if reduced else ()):
        got = spans.get(name, [])
        if len(got) != len(works):
            raise RuntimeError(f"trace holds {len(got)} {name} spans, "
                               f"the traced batches served {len(works)}")
        for span, work in zip(got, works):
            span["work"] = work
    return {"cell": cell.name, "chips": cell.chips, "peak": peak,
            "traffic": cell.traffic, "trace": reduced}


def end_to_end(cell: bench.Cell, served: Served, setup_s: float,
               window_s: float) -> Dict[str, float]:
    mix = cell.traffic
    tokens = len(served.tokens) * mix["batch"] * (mix["decode_steps"] + 1)
    values = {
        "setup_s": setup_s,
        "prefill_ms": 1e3 * statistics.fmean(served.prefill_s),
        "decode_ms": 1e3 * sum(served.decode_s) / len(served.decode_s),
        "tok_s": tokens / window_s,
    }
    out = {}
    for m in cell.end_to_end:
        if m["name"] not in values:
            raise bench.SpecError(f"no end-to-end metric {m['name']!r}")
        out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def per_layer(cell: bench.Cell, records: Dict) -> Dict[str, Dict]:
    out = {}
    for m in cell.per_layer:
        value = bench.metric_reader(cell.root, m["name"])(records)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def _window(server: Server, mix, seconds: float, prompts,
            trace_dir: Optional[str]) -> Served:
    """Whole batches until ``seconds`` have passed.  With ``trace_dir``
    the first ``trace_batches`` of them run under the profiler."""
    served = Served()
    t0 = time.perf_counter()

    def batch():
        b = len(served.tokens)
        server.serve(prompts(traffic.WINDOW, b), mix["decode_steps"], served)

    if trace_dir is not None:
        with jax.profiler.trace(trace_dir), \
                jax.profiler.TraceAnnotation("window"):
            for _ in range(mix["trace_batches"]):
                batch()
    while not served.tokens or time.perf_counter() - t0 < seconds:
        batch()
    return served


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, root: Path = bench.ROOT) -> Dict:
    """One run of ``workload``; returns the result line's object."""
    cell = bench.load_cell(workload, root)
    traffic.validate(cell.traffic)
    devices, peak = device_check(cell.chips)

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = _compile_counter()
    cfg = bench.model_config(cell.config)
    mix = cell.traffic
    server = Server(cell, cfg, devices)
    server.load(seed, cfg.d_model)

    def prompts(stream, b):
        return traffic.prompts(mix, cfg.vocab, seed, stream, b)

    # warm every program the window runs: reset, prefill, sample, decode
    server.serve(prompts(traffic.WARMUP, 0), 2, None)

    trace_dir = tempfile.TemporaryDirectory() if trace else None
    counter.count, counter.on = 0, True
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    served = _window(server, mix, seconds, prompts,
                     trace_dir.name if trace else None)
    window_s = time.perf_counter() - t_window
    counter.on = False
    batches = len(served.tokens)
    print(f"window: {batches} batches in {window_s:.3f} s, "
          f"{counter.count} compilations inside it", file=sys.stderr)
    G = mix["decode_steps"]
    for b in range(batches):
        steps = served.decode_s[b * G:(b + 1) * G]
        print(f"batch {b}: prefill {1e3 * served.prefill_s[b]:.3f} ms, "
              f"decode mean {1e3 * statistics.fmean(steps):.3f} ms, "
              f"max {1e3 * max(steps):.3f} ms", file=sys.stderr)

    memory_peak = _memory_peak(devices)
    server.cache = None
    # the closed loop serves each request it starts to its last token, or
    # the run dies: none is refused or left unfinished
    result = {"correct": False, "attempted": batches * mix["batch"],
              "failed": 0}
    if trace:
        profile = jax.profiler.ProfileData.from_file(
            str(next(Path(trace_dir.name).rglob("*.xplane.pb"))))
        reduced = trace_mod.reduce(profile)
        trace_dir.cleanup()
        records = _records(cell, peak, reduced, mix["trace_batches"])
        metrics = per_layer(cell, records)
    else:
        metrics = end_to_end(cell, served, setup_s, window_s)

    got = _check(cell, server, cfg, devices[0], seed, served, prompts)
    limits = cell.check["limits"]
    result["correct"] = all(
        limits.get(k) is not None and v <= limits[k] for k, v in got.items())
    result["metrics"] = metrics
    d0 = devices[0]
    result["device"] = {"platform": d0.platform, "kind": d0.device_kind,
                        "count": len(devices),
                        "memory_peak_bytes": memory_peak}
    if trace:
        result["device"]["busy_s"] = reduced.get("busy_s", 0.0)
        result["device"]["window_s"] = reduced.get("window_s", 0.0)
        if reduced:
            result["breakdown"] = reduced["breakdown"]
    result["check"] = {k: {"value": v, "limit": limits.get(k)}
                       for k, v in got.items()}
    return result


def _check(cell, server, cfg, device, seed, served, prompts) -> Dict:
    picks = check.sample(seed, len(served.tokens), cell.traffic["batch"],
                         cell.check["rows"])
    seqs, tokens = check.gather(lambda b: prompts(traffic.WINDOW, b),
                                served.tokens, picks)
    view = weights.ReferenceView(server.params, cfg.d_model, device)
    shape = reference.Shape.from_config(cell.config)
    return check.compare(view, shape, seqs, tokens, cell.check["ref_rows"])
