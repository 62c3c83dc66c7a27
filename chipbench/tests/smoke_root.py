"""A benchmark root holding one extra CPU-sized cell, given only as data.

``make(tmp)`` copies ``BENCHMARK.json`` and the metric readers into
``tmp`` and adds: the configuration ``smoke-dense`` (the program's
``phi3-mini-3.8b`` entry at smoke widths, GQA rep 2), the traffic mix
``smoke``, the cell ``smoke.cell`` with its check limits, and the
per-layer metric ``smoke.served_batches``.  Nothing of the harness's
code changes.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

CELL = "smoke.cell"
# widest gap of sound bf16 runs at this size: 0 to 0.031 over seeds 1-12;
# the four faults of test_chipbench_harness read 1.2 to 5.3 on seeds 1-3
SMOKE_LIMIT = 0.15

METRIC = '''"""smoke.served_batches: batches the traced window served."""


def read(records):
    spans = (records.get("trace") or {}).get("spans", {})
    return records["traffic"]["trace_batches"] if spans else 1.0
'''


def make(tmp: Path, limit: float = SMOKE_LIMIT) -> Path:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (tmp / "chipbench" / "traffic").mkdir(parents=True)
    (tmp / "chipbench" / "checks").mkdir()
    (tmp / "chipbench" / "configs").mkdir()
    shutil.copytree(REPO / "chipbench" / "metrics",
                    tmp / "chipbench" / "metrics")
    shutil.copy(DATA / "smoke-dense.json",
                tmp / "chipbench" / "configs" / "smoke-dense.json")
    shutil.copy(DATA / "smoke-traffic.json",
                tmp / "chipbench" / "traffic" / "smoke.json")
    (tmp / "chipbench" / "checks" / f"{CELL}.json").write_text(json.dumps(
        {"rows": 4, "ref_rows": 2, "limits": {"max_gap": limit}}))
    (tmp / "chipbench" / "metrics" / "smoke_served_batches.py").write_text(
        METRIC)
    bench["configs"].append({
        "name": "smoke-dense", "source": "https://huggingface.co/x",
        "file": "chipbench/configs/smoke-dense.json", "reduced": [],
        "why": "CPU size"})
    bench["workloads"].append({
        "name": CELL, "config": "smoke-dense", "traffic": "smoke",
        "chips": 1, "why": "CPU size"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    bench["per_layer"].append({
        "name": "smoke.served_batches", "unit": "batches",
        "better": "higher", "source": "program_counter", "layer": "cache",
        "moves": "tok_s", "workloads": [CELL]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
