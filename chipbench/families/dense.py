"""The dense decoder family: its program check, reference, weight view,
weight rules and work counts.

Written from the published descriptions of Llama-style decoders (Phi-3,
arXiv:2404.14219; Yi, arXiv:2403.04652): token embedding, then per layer
RMSNorm -> causal grouped-query attention with rotary position embedding
(rotate-half form, base ``rope_theta``) -> residual, RMSNorm -> SwiGLU MLP
-> residual; a final RMSNorm and an untied lm_head.  No bias anywhere.

``ReferenceView`` converts the program's weights to the published form
the reference reads.  The program multiplies its embedding rows by
sqrt(d_model) (a Gemma convention that Phi-3 and Yi do not have), so its
table holds the published table divided by sqrt(d_model), as a checkpoint
converter for this program would store it, and the view multiplies it
back.

The work counts are per einsum: the projections (q, k, v, o and the three
SwiGLU matrices), the attention score and value products, and the
lm_head, charged as ``chipbench/counts.py`` says.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Mapping, Optional, Protocol

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.counts import DTYPE_BYTES, Work
from chipbench.reference import (HIGHEST, PRECISIONS, _head, _mm, _quantise,
                                 _rmsnorm, _rope)

# --- the program check ---

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


def contradictions(config: Mapping, cfg) -> List[str]:
    """What of the program's ``cfg`` the reference does not describe."""
    if (cfg.family, cfg.qkv_bias, cfg.n_experts, cfg.window) != (
            "dense", False, 0, 0):
        return ["not the plain dense decoder the reference describes"]
    return []


def refuse_length(config: Mapping, longest: int) -> Optional[str]:
    """Why sequences of ``longest`` positions would not be the published
    model: full causal attention is exact only under a published window."""
    window = config.get("sliding_window")
    if window and longest > window:
        return (f"{longest} positions exceed the {window}-position sliding "
                "window, which the program does not implement")
    return None


# the dense family's leaves are all made by the shared rules of weights.py
WEIGHTS: Dict = {}

# --- the reference ---


@dataclass(frozen=True)
class Shape:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    d_head: int
    eps: float
    rope_theta: float

    @classmethod
    def from_config(cls, c) -> "Shape":
        heads = c["num_attention_heads"]
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=heads, kv_heads=c["num_key_value_heads"],
                   d_head=c.get("head_dim") or c["hidden_size"] // heads,
                   eps=float(c["rms_norm_eps"]),
                   rope_theta=float(c["rope_theta"]))


class Weights(Protocol):
    """Published-form weights, each on the device the reference runs on.

    ``layer(i)`` holds ``attn_norm, wq, wk, wv, wo, mlp_norm, w_gate,
    w_up, w_down`` with every matrix laid out (in_features, out_features).
    """

    def embed_rows(self, tokens) -> jax.Array: ...

    def layer(self, i: int) -> dict: ...

    final_norm: jax.Array
    lm_head: jax.Array


@partial(jax.jit, static_argnames=("shape", "precision"))
def _layer(x, w, *, shape: Shape, precision: str):
    n, S, _ = x.shape
    g, rep = shape.kv_heads, shape.heads // shape.kv_heads
    h = _rmsnorm(x, w["attn_norm"], shape.eps)
    q = _mm(h, w["wq"], precision).reshape(n, S, shape.heads, shape.d_head)
    k = _mm(h, w["wk"], precision).reshape(n, S, g, shape.d_head)
    v = _mm(h, w["wv"], precision).reshape(n, S, g, shape.d_head)
    q, k = _rope(q, shape.rope_theta), _rope(k, shape.rope_theta)
    q = q.reshape(n, S, g, rep, shape.d_head) / np.sqrt(shape.d_head)
    s = jnp.einsum("nqgrd,nkgd->ngrqk", _quantise(q, -1, precision),
                   _quantise(k, -1, precision), precision=HIGHEST)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("ngrqk,nkgd->nqgrd", _quantise(p, -1, precision),
                   _quantise(v, 1, precision), precision=HIGHEST)
    x = x + _mm(o.reshape(n, S, shape.heads * shape.d_head), w["wo"],
                precision)
    h = _rmsnorm(x, w["mlp_norm"], shape.eps)
    a = jax.nn.silu(_mm(h, w["w_gate"], precision)) * _mm(
        h, w["w_up"], precision)
    return x + _mm(a, w["w_down"], precision)


def logits(weights: Weights, shape: Shape, tokens, first: int,
           precision: str = "float32") -> jax.Array:
    """Logits (n, S - first, vocab) at positions ``first..S-1`` of the
    sequences ``tokens`` (n, S), each read from a full causal pass."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    x = weights.embed_rows(tokens).astype(jnp.float32)
    for i in range(shape.layers):
        x = _layer(x, weights.layer(i), shape=shape, precision=precision)
    return _head(x[:, first:], weights.final_norm, weights.lm_head,
                 eps=shape.eps, precision=precision)


# --- the published-form view ---


class ReferenceView:
    """The program's weights in the published form, one layer at a time
    on ``device``: what a checkpoint of the published model would hold."""

    def __init__(self, params: Mapping, d_model: int, device):
        self._p = params
        self._layers = params["groups"][0][0]
        self._scale = math.sqrt(d_model)
        self._dev = device
        self.final_norm = self._put(params["final_norm"]["scale"])
        self.lm_head = self._put(params["lm_head"])

    def _put(self, x):
        return jax.device_put(x, self._dev)

    def embed_rows(self, tokens):
        rows = jnp.take(self._p["embed"]["tok"], jnp.asarray(tokens), axis=0)
        return self._put(rows).astype(jnp.float32) * self._scale

    def layer(self, i: int) -> dict:
        p = self._layers
        return {k: self._put(v[i]) for k, v in (
            ("attn_norm", p["ln1"]["scale"]), ("wq", p["attn"]["wq"]),
            ("wk", p["attn"]["wk"]), ("wv", p["attn"]["wv"]),
            ("wo", p["attn"]["wo"]), ("mlp_norm", p["ln2"]["scale"]),
            ("w_gate", p["mlp"]["wg"]), ("w_up", p["mlp"]["wu"]),
            ("w_down", p["mlp"]["wd"]))}


# --- the work counts ---


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


def _weight_bytes(d: Dims) -> Dict[str, float]:
    norms = (2 * d.layers + 1) * d.d_model
    return {
        "weights.layers": float(d.layers * d.layer_params * d.weight_bytes),
        "weights.lm_head": float(d.d_model * d.vocab * d.weight_bytes),
        "weights.norms": float(norms * d.weight_bytes),
    }


def prefill(config: Mapping, batch: int, prompt: int) -> Work:
    """One prefill of ``batch`` prompts of ``prompt`` tokens."""
    d = Dims.from_config(config)
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


def decode(config: Mapping, batch: int, filled: int) -> Work:
    """One decode step: ``batch`` new tokens, each attending ``filled``
    keys (the prompt, the tokens decoded so far and its own)."""
    d = Dims.from_config(config)
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
