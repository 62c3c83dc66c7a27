"""Record the small trace that ``test_chipbench_trace.py`` reduces.

    python3 chipbench/tests/record_trace.py OUT_DIR

On the chip: a sharded matmul whose sum needs a collective across the
chips, and an elementwise step, each inside the benchmark's own spans,
with a host sleep between them.  Writes ``trace_<n>chip.xplane.pb.gz`` to
OUT_DIR and prints each plane and line of the trace with a few events,
and the expected span and op counts.
"""
from __future__ import annotations

import gzip
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

ROUNDS = 3
SLEEP_S = 0.005


def main(out_dir: str) -> int:
    devices = jax.devices()
    n = len(devices)
    mesh = jax.make_mesh((n,), ("x",), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,))
    x = jax.device_put(jnp.ones((n * 256, 512), jnp.float32),
                       NamedSharding(mesh, P("x")))
    gram = jax.jit(lambda a: (a.T @ a).sum(0))  # contracts the sharded dim
    act = jax.jit(lambda a: jnp.tanh(a) * 2.0)
    np.asarray(gram(x)), np.asarray(act(x))
    annotate = jax.profiler.TraceAnnotation
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        with annotate("window"):
            for _ in range(ROUNDS):
                with annotate("prefill"):
                    y = gram(x)
                    with annotate("sample"):
                        np.asarray(y)
                time.sleep(SLEEP_S)
                with annotate("decode"):
                    np.asarray(act(x))
        jax.profiler.stop_trace()
        path = next(Path(tmp).rglob("*.xplane.pb"))
        raw = path.read_bytes()
    out = Path(out_dir) / f"trace_{n}chip.xplane.pb.gz"
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_bytes(gzip.compress(raw))
    profile = jax.profiler.ProfileData.from_serialized_xspace(raw)
    for plane in profile.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:4]:
                print(f"    {ev.name!r} start {ev.start_ns} dur "
                      f"{ev.duration_ns} stats {dict(ev.stats)!r}"[:300])
    print(f"wrote {out} ({len(raw)} bytes raw); {ROUNDS} prefill, decode "
          f"and sample spans on {n} chips")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
