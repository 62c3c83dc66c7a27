"""Operations and bytes the served work needs, from a configuration's sizes.

Counted per einsum of the dense decoder: the projections (q, k, v, o and
the three SwiGLU matrices), the attention score and value products, and
the lm_head.  Norms, RoPE and softmax are elementwise and not counted.
What serving needs, not what a given program computes:

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
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    d_head: int
    d_ff: int
    vocab: int
    weight_bytes: int  # bytes per weight element
    cache_bytes: int  # bytes per cached key or value element

    @classmethod
    def from_config(cls, c: Mapping) -> "Dims":
        """From a configuration file's published keys, as run."""
        heads = c["num_attention_heads"]
        d_head = c.get("head_dim") or c["hidden_size"] // heads
        dt = DTYPE_BYTES[c["torch_dtype"]]
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=heads, kv_heads=c["num_key_value_heads"],
                   d_head=d_head, d_ff=c["intermediate_size"],
                   vocab=c["vocab_size"], weight_bytes=dt, cache_bytes=dt)

    @property
    def q_dim(self) -> int:
        return self.heads * self.d_head

    @property
    def kv_dim(self) -> int:
        return self.kv_heads * self.d_head

    @property
    def layer_params(self) -> int:
        """Matrix elements of one decoder layer (norm gains excluded)."""
        d = self.d_model
        attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        return attn + 3 * d * self.d_ff


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


def _weight_bytes(d: Dims) -> Dict[str, float]:
    norms = (2 * d.layers + 1) * d.d_model
    return {
        "weights.layers": float(d.layers * d.layer_params * d.weight_bytes),
        "weights.lm_head": float(d.d_model * d.vocab * d.weight_bytes),
        "weights.norms": float(norms * d.weight_bytes),
    }


def prefill(d: Dims, batch: int, prompt: int) -> Work:
    """One prefill of ``batch`` prompts of ``prompt`` tokens."""
    tokens = batch * prompt
    causal_pairs = prompt * (prompt + 1) // 2
    attn = 2.0 * batch * d.heads * d.d_head * causal_pairs * d.layers
    flops = {
        "proj": 2.0 * d.layer_params * tokens * d.layers,
        "qk": attn,
        "av": attn,
        "lm_head": 2.0 * batch * d.d_model * d.vocab,
    }
    by = _weight_bytes(d)
    by["kv.write"] = float(
        d.layers * tokens * 2 * d.kv_dim * d.cache_bytes)
    by["embed.rows"] = float(tokens * d.d_model * d.weight_bytes)
    return Work(flops, by)


def decode(d: Dims, batch: int, filled: int) -> Work:
    """One decode step: ``batch`` new tokens, each attending ``filled``
    keys (the prompt, the tokens decoded so far and its own)."""
    attn = 2.0 * batch * d.heads * d.d_head * filled * d.layers
    flops = {
        "proj": 2.0 * d.layer_params * batch * d.layers,
        "qk": attn,
        "av": attn,
        "lm_head": 2.0 * batch * d.d_model * d.vocab,
    }
    by = _weight_bytes(d)
    by["kv.read"] = float(
        d.layers * batch * filled * 2 * d.kv_dim * d.cache_bytes)
    by["embed.rows"] = float(batch * d.d_model * d.weight_bytes)
    return Work(flops, by)


def least_time(work: Work, peak: Mapping) -> Tuple[float, str]:
    """The least time one chip needs for ``work`` (already per chip), and
    which bound sets it: ``"flops"`` or ``"hbm"``."""
    t_flops = work.total_flops / peak["bf16_flop_per_s"]
    t_hbm = work.total_bytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_hbm else (t_hbm, "hbm")
