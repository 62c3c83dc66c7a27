"""Finds a cell's data by name: ``BENCHMARK.json``, the configuration file,
its model family, the traffic mix, the correctness limits and the
per-layer metric readers.

Everything that belongs to one configuration, model family, traffic mix,
cell or metric is a file of its own, found by the name ``BENCHMARK.json``
or the configuration gives it:

    <config file>                          as BENCHMARK.json's ``file``
    chipbench/families/<family>.py         the config's ``family``: below
    chipbench/traffic/<traffic>.json       the mix the generator reads
    chipbench/checks/<workload>.json       sample size and limits of ``correct``
    chipbench/metrics/<metric>.py          ``read(records)``; '.' -> '_'

A family module owns all that depends on the architecture:
``PUBLISHED_TO_PROGRAM`` (published key -> program field),
``contradictions(config, cfg)`` and ``refuse_length(config, longest)``;
its reference, ``Shape.from_config(config)`` and ``logits(view, shape,
tokens, first, precision)``, written with ``chipbench/reference.py``;
``ReferenceView(params, d_model, device)``, the program's weights in
published form; ``WEIGHTS``, rules for leaves of its own
(``chipbench/weights.py``); and ``prefill(config, batch, prompt)`` and
``decode(config, batch, filled)``, each a ``counts.Work``.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, replace
import sys
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Mapping, Optional

ROOT = Path(__file__).resolve().parents[1]


class SpecError(ValueError):
    """The benchmark's data names something that is missing or
    contradicts the program's configuration."""


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict
    family: ModuleType
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
    if "family" not in config:
        raise SpecError(f"configuration {w['config']!r} names no family")
    traffic = _read_json(root / "chipbench" / "traffic"
                         / f"{w['traffic']}.json")
    check = _read_json(root / "chipbench" / "checks" / f"{workload}.json")
    cell = Cell(
        name=workload, chips=int(w["chips"]), config=config,
        family=family(config["family"], root), traffic=traffic, check=check,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        root=root)
    refused = cell.family.refuse_length(config, cell.longest)
    if refused:
        raise SpecError(f"{workload}: {refused}")
    if cell.longest > traffic["cache_slots"]:
        raise SpecError(f"{workload}: {cell.longest} positions do not fit "
                        f"{traffic['cache_slots']} cache slots")
    return cell


def model_config(config: Mapping, family: ModuleType):
    """The program's ``ModelConfig`` for a configuration file: the
    ``repro.configs`` entry with the file's overrides, checked against
    every published number the file states, as ``family`` maps it, and
    against what the family's reference describes."""
    from repro.configs import get_config

    cfg = replace(get_config(config["program_arch"]), **config["overrides"])
    wrong = [f"{k}={config[k]!r} but the program has {p}="
             f"{getattr(cfg, p)!r}"
             for k, p in family.PUBLISHED_TO_PROGRAM.items()
             if k in config and getattr(cfg, p) != config[k]]
    d_head = config["hidden_size"] // config["num_attention_heads"]
    if cfg.d_head != config.get("head_dim", d_head):
        wrong.append(f"head_dim {d_head} but the program has "
                     f"d_head={cfg.d_head}")
    if cfg.dtype != config["torch_dtype"]:
        wrong.append(f"activations in {cfg.dtype}, not the served "
                     f"{config['torch_dtype']}")
    wrong += family.contradictions(config, cfg)
    if wrong:
        raise SpecError(f"{config['name']}: " + "; ".join(wrong))
    return cfg


def _load(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    # dataclasses look their module up while the file runs
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def family(name: str, root: Path = ROOT) -> ModuleType:
    """The module of model family ``name``, from its own file."""
    path = root / "chipbench" / "families" / f"{name}.py"
    if not path.exists():
        raise SpecError(f"no model family {name!r} at {path}")
    return _load(path, f"chipbench_family_{name}")


def metric_reader(root: Path, name: str) -> Callable[[Mapping],
                                                      Optional[float]]:
    """``read(records)`` of per-layer metric ``name``, from its own file."""
    path = root / "chipbench" / "metrics" / f"{name.replace('.', '_')}.py"
    if not path.exists():
        raise SpecError(f"no reader for per-layer metric {name!r} at {path}")
    return _load(path, f"chipbench_metric_{path.stem}").read


def peaks(device_kind: str) -> Dict:
    table = _read_json(Path(__file__).with_name("peaks.json"))
    if device_kind not in table:
        raise SpecError(f"no published peaks for device kind "
                        f"{device_kind!r} (have {sorted(table)})")
    return table[device_kind]
