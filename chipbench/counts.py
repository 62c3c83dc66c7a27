"""Operations and bytes the served work needs, and the least time a chip
needs for them.

Each family counts its own work from a configuration's sizes
(``prefill(config, batch, prompt)`` and ``decode(config, batch, filled)``
in ``chipbench/families/<family>.py``), per einsum; norms, RoPE and softmax
are elementwise and not counted.  Every family charges what serving needs,
not what a given program computes:

- the embedding is a lookup: no operations, and only the rows read count
  as bytes, never the whole table;
- prefill needs the lm_head at the last position of each prompt only;
- causal prefill attention needs the lower triangle of the score matrix;
- a decode step reads the cache up to its filled length, not the slots
  allocated.

Bytes are HBM traffic at the dtype the weights and cache are served in.
With ``chips`` > 1 the work is split evenly, as tensor parallelism does.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Mapping, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


@dataclass(frozen=True)
class Work:
    flops: Dict[str, float]
    bytes: Dict[str, float]

    @property
    def total_flops(self) -> float:
        return float(sum(self.flops.values()))

    @property
    def total_bytes(self) -> float:
        return float(sum(self.bytes.values()))

    def per_chip(self, chips: int) -> "Work":
        return Work({k: v / chips for k, v in self.flops.items()},
                    {k: v / chips for k, v in self.bytes.items()})


def least_time(work: Work, peak: Mapping) -> Tuple[float, str]:
    """The least time one chip needs for ``work`` (already per chip), and
    which bound sets it: ``"flops"`` or ``"hbm"``."""
    t_flops = work.total_flops / peak["bf16_flop_per_s"]
    t_hbm = work.total_bytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_hbm else (t_hbm, "hbm")
