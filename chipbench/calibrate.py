"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/calibrate.py --workload phi3-mini.decode \\
        --seeds 101-112 --control-seeds 101-103 --control int8 float8

In one process, for each seed: make the weights, serve whole batches of
the cell's traffic through the same compiled steps a run times (enough
batches for the sample a run checks), draw the sample as a run does and
read ``max_gap``.  For the control seeds, read also the widest gap of the
reference computed in each ``--control`` precision in the program's place.
Prints one JSON line per seed, then the largest program reading (the
lower end of a limit), each control's smallest reading (the upper end),
and the limit: lower**(1/3) * upper**(2/3), nearer the upper end since
fresh seeds read higher than a dozen did, from the first control in
``--control`` order that reads at least three times the lower end.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _seeds(text: str):
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def readings(workload, seeds, control_seeds, controls, root=ROOT):
    from chipbench import bench, cell, check, reference, traffic, weights

    from repro.launch.compile_cache import use_compile_cache

    c = bench.load_cell(workload, root)
    devices, _ = cell.device_check(c.chips)
    use_compile_cache()
    cfg = bench.model_config(c.config)
    mix = c.traffic
    server = cell.Server(c, cfg, devices)
    shape = reference.Shape.from_config(c.config)
    half = mix["batch"] // 2
    batches = max(1, math.ceil(c.check["rows"] / 2 / half))
    for seed in seeds:
        server.load(seed, cfg.d_model)

        def prompts(b):
            return traffic.prompts(mix, cfg.vocab, seed, traffic.WINDOW, b)

        served = cell.Served()
        for b in range(batches):
            server.serve(prompts(b), mix["decode_steps"], served)
        picks = check.sample(seed, batches, mix["batch"], c.check["rows"])
        seqs, tokens = check.gather(prompts, served.tokens, picks)
        view = weights.ReferenceView(server.params, cfg.d_model, devices[0])
        got = check.compare(view, shape, seqs, tokens, c.check["ref_rows"],
                            controls if seed in control_seeds else ())
        del view  # it holds the weights: free them before the next seed's
        server.params = None
        got["seed"] = seed
        yield got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 101-112 or 1,5,9")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control", nargs="*", default=["int8"])
    args = ap.parse_args(argv)
    seeds = _seeds(args.seeds)
    control_seeds = set(_seeds(args.control_seeds)) if args.control_seeds \
        else set()
    rows = []
    for got in readings(args.workload, seeds, control_seeds, args.control):
        rows.append(got)
        print(json.dumps(got), flush=True)
    summary = {"workload": args.workload,
               "lower.max_gap": max(r["max_gap"] for r in rows)}
    lower = summary["lower.max_gap"]
    for p in args.control:
        ctl = [r[f"control.{p}"] for r in rows if f"control.{p}" in r]
        if ctl:
            summary[f"upper.control.{p}"] = upper = min(ctl)
            if upper >= 3 * lower and "limit.max_gap" not in summary:
                summary["control"] = p
                summary["limit.max_gap"] = round(
                    lower ** (1 / 3) * upper ** (2 / 3), 4)
    summary["seconds"] = time.perf_counter() - T_START
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
