"""Record the small scoped trace that ``test_chipbench_scopes.py`` reduces.

    python3 chipbench/tests/record_scopes_trace.py OUT_DIR

On the chip, named as the served step's programs are: ``serve_prefill``,
a sharded matmul in an ``mlp`` scope whose sum needs a collective across
the chips, followed by an unnamed sum (as the benchmark's ``sample``);
and ``serve_decode``, a scan in a ``layers`` scope whose body is an
elementwise step in an ``attend`` scope.  Each runs inside the
benchmark's own spans, and a forced garbage collection, watched by the
program's ``GcWatch``, ends each round.  Writes
``trace_scopes_<n>chip.xplane.pb.gz`` and the two programs' compiled
text, ``trace_scopes_<n>chip.hlo.json``, to OUT_DIR, and prints each
plane and line of the trace with a few events.
"""
from __future__ import annotations

import gc
import gzip
import json
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.obs.serving import WATCH  # noqa: E402

ROUNDS = 3
LAYERS = 4


def serve_prefill(a):
    with jax.named_scope("mlp"):
        return (a.T @ a).sum(0)  # contracts the sharded dim


def serve_decode(a):
    def layer(c, _):
        with jax.named_scope("attend"):
            return jnp.tanh(c) * 2.0, None

    with jax.named_scope("layers"):
        return lax.scan(layer, a, None, length=LAYERS)[0]


def main(out_dir: str) -> int:
    devices = jax.devices()
    n = len(devices)
    mesh = jax.make_mesh((n,), ("x",), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,))
    x = jax.device_put(jnp.ones((n * 256, 512), jnp.float32),
                       NamedSharding(mesh, P("x")))
    prefill, decode = jax.jit(serve_prefill), jax.jit(serve_decode)
    sample = jax.jit(lambda y: y.sum())
    np.asarray(sample(prefill(x))), np.asarray(decode(x))
    WATCH.install()
    annotate = jax.profiler.TraceAnnotation
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        with annotate("window"):
            for _ in range(ROUNDS):
                with annotate("prefill"):
                    y = prefill(x)
                    with annotate("sample"):
                        np.asarray(sample(y))
                with annotate("decode"):
                    np.asarray(decode(x))
                gc.collect()
        jax.profiler.stop_trace()
        raw = next(Path(tmp).rglob("*.xplane.pb")).read_bytes()
    out = Path(out_dir) / f"trace_scopes_{n}chip.xplane.pb.gz"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_bytes(gzip.compress(raw))
    # source paths relative to the checkout, not the recording machine's
    texts = [step.lower(x).compile().as_text().replace(f"{ROOT}/", "")
             for step in (prefill, decode)]
    out.with_name(f"trace_scopes_{n}chip.hlo.json").write_text(
        json.dumps(texts))
    profile = jax.profiler.ProfileData.from_serialized_xspace(raw)
    for plane in profile.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:4]:
                print(f"    {ev.name!r} start {ev.start_ns} dur "
                      f"{ev.duration_ns} stats {dict(ev.stats)!r}"[:300])
    print(f"wrote {out} ({len(raw)} bytes raw); {ROUNDS} prefill, decode, "
          f"sample and gc spans on {n} chips; gc {WATCH.snapshot()}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
