"""Plain float32 forward pass of the dense decoder family.

Written from the published descriptions of Llama-style decoders (Phi-3,
arXiv:2404.14219; Yi, arXiv:2403.04652): token embedding, then per layer
RMSNorm -> causal grouped-query attention with rotary position embedding
(rotate-half form, base ``rope_theta``) -> residual, RMSNorm -> SwiGLU MLP
-> residual; a final RMSNorm and an untied lm_head.  No bias anywhere.

It imports nothing of the program under test and holds no weights of its
own: a ``weights`` view hands it the published-form tensors.  It runs
layer by layer, one jitted layer at a time, over a block of sequences, so
that it fits beside the served weights.  Every product is a float32
einsum at ``highest`` precision, so the TPU does not round its operands.

``precision`` names what the matmul operands are rounded to first:
``"float32"`` is the reference itself; ``"int8"`` and ``"float8"`` are the
control, the same computation with every product's operands quantised
symmetrically per vector along the contracted axis (absmax scaling),
accumulated in float32.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Protocol

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("float32", "int8", "float8")


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


def _quantise(x, axis: int, precision: str):
    """``x`` rounded to ``precision`` per vector along ``axis``."""
    if precision == "float32":
        return x
    top = {"int8": 127.0, "float8": 448.0}[precision]
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    y = x / scale
    if precision == "int8":
        y = jnp.clip(jnp.round(y), -127, 127)
    else:
        y = y.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return y * scale


def _mm(x, w, precision):
    """x (..., k) @ w (k, n); operands rounded along k."""
    w = w.astype(jnp.float32)
    return jnp.einsum("...k,kn->...n", _quantise(x, -1, precision),
                      _quantise(w, 0, precision), precision=HIGHEST)


def _rmsnorm(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * g.astype(jnp.float32)


def _rope(x, theta):
    """x: (n, S, H, Dh) at positions 0..S-1."""
    S, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv  # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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


@partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, g, w, *, eps: float, precision: str):
    return _mm(_rmsnorm(x, g, eps), w, precision)


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
