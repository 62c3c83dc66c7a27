"""A benchmark root holding two extra CPU-sized cells, given only as data.

``make(tmp)`` copies ``BENCHMARK.json``, the model families and the metric
readers into ``tmp`` and adds: the configuration ``smoke-dense`` (the
program's ``phi3-mini-3.8b`` entry at smoke widths, GQA rep 2); the model
family ``qkv_bias`` (``data/qkv_bias.py``) and its configuration
``smoke-qkv-bias`` (the program's ``qwen1.5-0.5b`` entry at the same
widths, with its q, k and v biases); the traffic mix ``smoke``; the cells
``smoke.cell`` and ``smoke.qkv-bias`` with their check limits; and the
per-layer metric ``smoke.served_batches``.  Nothing of the harness's code
changes.  ``reference_bias=False`` gives the ``qkv_bias`` family a
reference that leaves the biases out.
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
BIAS_CELL = "smoke.qkv-bias"
CONFIGS = {CELL: "smoke-dense", BIAS_CELL: "smoke-qkv-bias"}
# widest gap of sound bf16 runs at this size: 0 to 0.031 over seeds 1-12;
# the four faults of test_chipbench_harness read 1.2 to 5.3 on seeds 1-3
SMOKE_LIMIT = 0.15

METRIC = '''"""smoke.served_batches: batches the traced window served."""


def read(records):
    spans = (records.get("trace") or {}).get("spans", {})
    return records["traffic"]["trace_batches"] if spans else 1.0
'''


def make(tmp: Path, limit: float = SMOKE_LIMIT,
         reference_bias: bool = True) -> Path:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cb = tmp / "chipbench"
    (cb / "traffic").mkdir(parents=True)
    (cb / "checks").mkdir()
    (cb / "configs").mkdir()
    for part in ("families", "metrics"):
        shutil.copytree(REPO / "chipbench" / part, cb / part)
    family = (DATA / "qkv_bias.py").read_text()
    if not reference_bias:
        family = family.replace("REFERENCE_BIAS = True",
                                "REFERENCE_BIAS = False")
    (cb / "families" / "qkv_bias.py").write_text(family)
    shutil.copy(DATA / "smoke-traffic.json", cb / "traffic" / "smoke.json")
    (cb / "metrics" / "smoke_served_batches.py").write_text(METRIC)
    for cell, config in CONFIGS.items():
        shutil.copy(DATA / f"{config}.json", cb / "configs" / f"{config}.json")
        (cb / "checks" / f"{cell}.json").write_text(json.dumps(
            {"rows": 4, "ref_rows": 2, "limits": {"max_gap": limit}}))
        bench["configs"].append({
            "name": config, "source": "https://huggingface.co/x",
            "file": f"chipbench/configs/{config}.json", "reduced": [],
            "why": "CPU size"})
        bench["workloads"].append({
            "name": cell, "config": config, "traffic": "smoke",
            "chips": 1, "why": "CPU size"})
        for m in bench["end_to_end"]:
            if "workloads" in m:
                m["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "smoke.served_batches", "unit": "batches",
        "better": "higher", "source": "program_counter", "layer": "cache",
        "moves": "tok_s", "workloads": list(CONFIGS)})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
