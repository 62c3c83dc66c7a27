"""The dense decoder with biases on its q, k and v projections, as
Qwen1.5 has them: a family of the smoke root, given as a file of data.

Everything but the attention's input projections is the dense family's:
its published-key map, length check, shapes, published-form view (with
the three biases added) and work counts (a bias adds no product).  The
program serves it with ``qkv_bias``; its zero-initialised biases are made
N(0, 1) here, which moves the attention scores and values, so that a
reference that left them out reads far from the served tokens.
``REFERENCE_BIAS = False`` is that faulty reference.
"""
from __future__ import annotations

from functools import partial
from typing import List, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench
from chipbench.reference import (HIGHEST, PRECISIONS, _head, _mm, _quantise,
                                 _rmsnorm, _rope)

REFERENCE_BIAS = True
BIASES = ("bq", "bk", "bv")

dense = bench.family("dense")
PUBLISHED_TO_PROGRAM = dense.PUBLISHED_TO_PROGRAM
refuse_length = dense.refuse_length
Shape = dense.Shape
prefill, decode = dense.prefill, dense.decode

WEIGHTS = {name: (lambda z, shape, d_model: z) for name in BIASES}


def contradictions(config: Mapping, cfg) -> List[str]:
    if (cfg.family, cfg.qkv_bias, cfg.n_experts, cfg.window) != (
            "dense", True, 0, 0):
        return ["not the dense decoder with q, k and v biases the "
                "reference describes"]
    return []


class ReferenceView(dense.ReferenceView):
    def layer(self, i: int) -> dict:
        w = super().layer(i)
        w.update({k: self._put(self._layers["attn"][k][i]) for k in BIASES})
        return w


def _proj(h, w, b, precision):
    y = _mm(h, w, precision)
    return y + b.astype(jnp.float32) if REFERENCE_BIAS else y


@partial(jax.jit, static_argnames=("shape", "precision"))
def _layer(x, w, *, shape, precision: str):
    n, S, _ = x.shape
    g, rep = shape.kv_heads, shape.heads // shape.kv_heads
    h = _rmsnorm(x, w["attn_norm"], shape.eps)
    q = _proj(h, w["wq"], w["bq"], precision).reshape(
        n, S, shape.heads, shape.d_head)
    k = _proj(h, w["wk"], w["bk"], precision).reshape(n, S, g, shape.d_head)
    v = _proj(h, w["wv"], w["bv"], precision).reshape(n, S, g, shape.d_head)
    q, k = _rope(q, shape.rope_theta), _rope(k, shape.rope_theta)
    q = q.reshape(n, S, g, rep, shape.d_head) / np.sqrt(shape.d_head)
    s = jnp.einsum("nqgrd,nkgd->ngrqk", _quantise(q, -1, precision),
                   _quantise(k, -1, precision), precision=HIGHEST)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("ngrqk,nkgd->nqgrd", _quantise(p, -1, precision),
                   _quantise(v, 1, precision), precision=HIGHEST)
    x = x + _mm(o.reshape(n, S, shape.heads * shape.d_head), w["wo"],
                precision)
    h = _rmsnorm(x, w["mlp_norm"], shape.eps)
    a = jax.nn.silu(_mm(h, w["w_gate"], precision)) * _mm(
        h, w["w_up"], precision)
    return x + _mm(a, w["w_down"], precision)


def logits(weights, shape, tokens, first: int, precision: str = "float32"):
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    x = weights.embed_rows(tokens).astype(jnp.float32)
    for i in range(shape.layers):
        x = _layer(x, weights.layer(i), shape=shape, precision=precision)
    return _head(x[:, first:], weights.final_norm, weights.lm_head,
                 eps=shape.eps, precision=precision)
