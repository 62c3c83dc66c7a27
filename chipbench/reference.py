"""The float32 toolkit every family's reference is written with.

A family's reference (``chipbench/families/<family>.py``) is a plain
forward pass written from the published description of its models.  It
imports nothing of the program under test and holds no weights of its
own: the family's view hands it the published-form tensors.  It runs layer
by layer, one jitted layer at a time, over a block of sequences, so that
it fits beside the served weights.  Every product is a float32 einsum at
``highest`` precision, so the TPU does not round its operands.

``precision`` names what the matmul operands are rounded to first:
``"float32"`` is the reference itself; ``"int8"`` and ``"float8"`` are the
control, the same computation with every product's operands quantised
symmetrically per vector along the contracted axis (absmax scaling),
accumulated in float32.  Every family rounds through ``_quantise`` and
``_mm``, so the control is the same computation in each.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("float32", "int8", "float8")


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


@partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, g, w, *, eps: float, precision: str):
    """The final RMSNorm and the lm_head."""
    return _mm(_rmsnorm(x, g, eps), w, precision)
