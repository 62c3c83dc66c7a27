"""Run one cell's traced window and split its step programs' device time by
the program's named scopes.

    python3 chipbench/scoped_run.py --workload phi3-mini.decode --seed 7 \\
        --seconds 30

Sets the cell up as ``run.py`` does (the same ``cell.Server``, weights,
warm-up and window, whose first ``trace_batches`` batches run under the
profiler), with the program's garbage-collection watch installed.  After
the window it reads the compiled text of the prefill and decode steps
(compiling nothing), reduces the trace with ``scopes.split`` and prints
one JSON object:

- ``batches``: each batch's host-clock prefill and mean and longest decode
  step, whether it was traced, and the collections (generations 0/1/2)
  and pause that fell in it; ``tracing_cost_pct``: the traced batches'
  mean step against the untraced ones', per step kind;
- ``scopes``: per step program, device ms per span in each scope, the leaf
  ops' sum, the program's busy union and the share under a leaf scope;
- ``metrics``: the per-scope metrics of ``scopes.METRICS``, and
  ``decode.gc_pause_ms``, the window's collection pause per decode step;
- ``gc_spans_s`` and ``idle_gaps`` (``gc`` names a gap inside a
  collection); ``compilations`` inside the window and while reading the
  programs, both 0 when all is well.

Exits 2 without a TPU of a kind ``peaks.json`` knows.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def programs(server) -> list:
    """The compiled text of the server's prefill and decode steps: after a
    call, lowering and compiling again compiles nothing."""
    import jax
    import jax.numpy as jnp

    B, P = server.mix["batch"], server.mix["prompt_tokens"]
    # tokens of either step are placed by their batch dimension alone; an
    # argument without its sharding would compile again
    batch = {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32,
                                            sharding=server.tok_sh)}
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=server.tok_sh)
    return [step.lower(server.params, x, server.cache).compile().as_text()
            for step, x in ((server.prefill_step, batch),
                            (server.decode_step, tok))]


def _batches(served, gc_marks, traced: int, steps: int) -> list:
    out = []
    for b, prefill_s in enumerate(served.prefill_s):
        decode = served.decode_s[b * steps:(b + 1) * steps]
        d = gc_marks[b + 1] - gc_marks[b]
        out.append({"traced": b < traced, "prefill_ms": 1e3 * prefill_s,
                    "decode_ms": 1e3 * statistics.fmean(decode),
                    "decode_max_ms": 1e3 * max(decode),
                    "gc": list(d.collections),
                    "gc_pause_ms": 1e3 * d.pause_s})
    return out


def _tracing_cost(batches) -> dict:
    out = {}
    for kind in ("prefill_ms", "decode_ms"):
        on = [b[kind] for b in batches if b["traced"]]
        off = [b[kind] for b in batches if not b["traced"]]
        if on and off:
            out[kind] = 100 * (statistics.fmean(on) / statistics.fmean(off)
                               - 1)
    return out


def run(workload: str, seed: int, seconds: float, t_start: float,
        root: Path) -> dict:
    import jax

    from chipbench import bench, cell, scopes, traffic
    from repro.launch.compile_cache import use_compile_cache
    from repro.obs.serving import WATCH

    c = bench.load_cell(workload, root)
    traffic.validate(c.traffic)
    devices, _ = cell.device_check(c.chips)
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = cell._compile_counter()
    watch = WATCH.install()
    cfg = bench.model_config(c.config)
    mix = c.traffic
    server = cell.Server(c, cfg, devices)
    server.load(seed, cfg.d_model)

    def prompts(stream, b):
        return traffic.prompts(mix, cfg.vocab, seed, stream, b)

    server.serve(prompts(traffic.WARMUP, 0), 2, None)
    gc_marks = [watch.snapshot()]
    serve = server.serve

    def watched(*args):
        serve(*args)
        gc_marks.append(watch.snapshot())

    server.serve = watched
    with tempfile.TemporaryDirectory() as tmp:
        counter.count, counter.on = 0, True
        t_window = time.perf_counter()
        served = cell._window(server, mix, seconds, prompts, tmp)
        window_s = time.perf_counter() - t_window
        in_window, counter.count = counter.count, 0
        texts = programs(server)
        counter.on = False
        profile = jax.profiler.ProfileData.from_file(
            str(next(Path(tmp).rglob("*.xplane.pb"))))
    got = scopes.split(profile, texts)
    batches = _batches(served, gc_marks, mix["trace_batches"],
                       mix["decode_steps"])
    window_gc = gc_marks[-1] - gc_marks[0]
    metrics = scopes.metrics(got)
    metrics["decode.gc_pause_ms"] = 1e3 * window_gc.pause_s / len(
        served.decode_s)
    return {
        "workload": workload, "seed": seed,
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "setup_s": t_window - t_start, "window_s": window_s,
        "programs": [t.split(",")[0].split()[-1] for t in texts],
        "compilations": {"window": in_window, "programs": counter.count},
        "batches": batches, "tracing_cost_pct": _tracing_cost(batches),
        "gc_window": {"collections": list(window_gc.collections),
                      "pause_ms": 1e3 * window_gc.pause_s},
        "scopes": scopes.summary(got), "metrics": metrics,
        "gc_spans_s": got.get("gc_spans_s", []),
        "idle_gaps": got.get("idle_gaps", []),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)

    from chipbench import bench, cell

    try:
        result = run(args.workload, args.seed, args.seconds, T_START, ROOT)
    except (cell.NoChip, bench.SpecError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
