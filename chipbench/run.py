"""Run one benchmark cell once, in this process, on the chips it names.

    python3 chipbench/run.py --workload phi3-mini.decode --seed 7 \\
        --seconds 30 --trace 0

Reads ``BENCHMARK.json`` at the checkout's root.  ``--trace 0`` prints the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiler trace of the window's first batches.  The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, ``breakdown`` when traced, and last ``check``:
each number compared beside its limit); the last lines of standard error
repeat the check.  Exits non-zero, printing no result, when JAX finds no
TPU of a kind ``peaks.json`` knows or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import bench, cell

    try:
        result = cell.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START, ROOT)
    except (cell.NoChip, bench.SpecError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
