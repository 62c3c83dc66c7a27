"""Finds a cell's data by name: ``BENCHMARK.json``, the configuration file,
the traffic mix, the correctness limits and the per-layer metric readers.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file of its own, found by the name ``BENCHMARK.json`` gives it:

    <config file>                          as BENCHMARK.json's ``file``
    chipbench/traffic/<traffic>.json       the mix the generator reads
    chipbench/checks/<workload>.json       sample size and limits of ``correct``
    chipbench/metrics/<metric>.py          ``read(records)``; '.' -> '_'
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, replace
from pathlib import Path
from typing import Callable, Dict, List, Mapping, Optional

ROOT = Path(__file__).resolve().parents[1]

# published config key -> repro ModelConfig field
PUBLISHED_TO_PROGRAM = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "num_hidden_layers": "n_layers",
    "vocab_size": "vocab",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "param_dtype",
}


class SpecError(ValueError):
    """The benchmark's data names something that is missing or
    contradicts the program's configuration."""


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    check: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    root: Path

    @property
    def longest(self) -> int:
        """Positions in the longest sequence: prompt and served tokens."""
        return self.traffic["prompt_tokens"] + self.traffic["decode_steps"]


def _read_json(path: Path) -> Dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing benchmark file {path}") from None


def _applies(metric: Mapping, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(root / configs[w["config"]]["file"])
    traffic = _read_json(root / "chipbench" / "traffic"
                         / f"{w['traffic']}.json")
    check = _read_json(root / "chipbench" / "checks" / f"{workload}.json")
    cell = Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=traffic, check=check,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        root=root)
    window = config.get("sliding_window")
    if window and cell.longest > window:
        raise SpecError(f"{workload}: {cell.longest} positions exceed the "
                        f"{window}-position sliding window, which the "
                        "program does not implement")
    if cell.longest > traffic["cache_slots"]:
        raise SpecError(f"{workload}: {cell.longest} positions do not fit "
                        f"{traffic['cache_slots']} cache slots")
    return cell


def model_config(config: Mapping):
    """The program's ``ModelConfig`` for a configuration file: the
    ``repro.configs`` entry with the file's overrides, checked against
    every published number the file states."""
    from repro.configs import get_config

    cfg = replace(get_config(config["program_arch"]), **config["overrides"])
    wrong = [f"{k}={config[k]!r} but the program has {p}="
             f"{getattr(cfg, p)!r}"
             for k, p in PUBLISHED_TO_PROGRAM.items()
             if k in config and getattr(cfg, p) != config[k]]
    d_head = config["hidden_size"] // config["num_attention_heads"]
    if cfg.d_head != config.get("head_dim", d_head):
        wrong.append(f"head_dim {d_head} but the program has "
                     f"d_head={cfg.d_head}")
    if cfg.dtype != config["torch_dtype"]:
        wrong.append(f"activations in {cfg.dtype}, not the served "
                     f"{config['torch_dtype']}")
    if (cfg.family, cfg.qkv_bias, cfg.n_experts, cfg.window) != (
            "dense", False, 0, 0):
        wrong.append("not the plain dense decoder the reference describes")
    if wrong:
        raise SpecError(f"{config['name']}: " + "; ".join(wrong))
    return cfg


def metric_reader(root: Path, name: str) -> Callable[[Mapping],
                                                      Optional[float]]:
    """``read(records)`` of per-layer metric ``name``, from its own file."""
    path = root / "chipbench" / "metrics" / f"{name.replace('.', '_')}.py"
    if not path.exists():
        raise SpecError(f"no reader for per-layer metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> Dict:
    table = _read_json(Path(__file__).with_name("peaks.json"))
    if device_kind not in table:
        raise SpecError(f"no published peaks for device kind "
                        f"{device_kind!r} (have {sorted(table)})")
    return table[device_kind]
