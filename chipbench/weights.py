"""Weights made by the benchmark from the seed, and their published form.

``make`` builds every parameter of the program's layout in one jitted
call, on the device, in the dtype it is served in and with the program's
shardings.  The program only gets to say the shapes and the layout.

Scales are chosen so that every layer kind moves the logits, which is
what lets the correctness check see a fault in any of them:

- the published-form embedding has unit variance, the size of what one
  layer adds to the residual stream;
- q and k have per-element variance ``QK_GAIN``, so attention scores
  (scaled by 1/sqrt(d_head)) have variance ``QK_GAIN**2`` and the softmax
  picks out a few positions instead of averaging the whole prefix to
  nothing;
- every other matrix is N(0, 1/fan_in); norm gains are 1 + N(0, 0.1^2).

``ReferenceView`` converts to the published form the reference reads.
The program multiplies its embedding rows by sqrt(d_model) (a Gemma
convention that Phi-3 and Yi do not have), so its table holds the
published table divided by sqrt(d_model), as a checkpoint converter for
this program would store it, and the view multiplies it back.
"""
from __future__ import annotations

import math
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

QK_GAIN = 2.5
NORM_NOISE = 0.1


def key_for(seed: int, stream: int) -> jax.Array:
    """A PRNG key from any whole ``seed``, also past 32 bits."""
    words = np.random.SeedSequence([seed % 2**64, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _leaf(name: str, shape, dtype, key, d_model: int):
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "tok":
        w = z / math.sqrt(d_model)
    elif name == "scale":
        w = 1.0 + NORM_NOISE * z
    elif name in ("wq", "wk"):
        w = z * math.sqrt(QK_GAIN) / math.sqrt(shape[-2])
    elif name in ("wv", "wo", "wg", "wu", "wd", "lm_head"):
        w = z / math.sqrt(shape[-2])
    else:
        raise ValueError(f"no rule to make weight {name!r}")
    return w.astype(dtype)


def builder(params_abs, d_model: int):
    """A function of a key that makes every weight of ``params_abs``
    (shapes and dtypes)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_abs)

    def build(key):
        return treedef.unflatten([
            _leaf(_leaf_name(path), leaf.shape, leaf.dtype,
                  jax.random.fold_in(key, i), d_model)
            for i, (path, leaf) in enumerate(flat)])

    return build


def make(params_abs, shardings, seed: int, d_model: int):
    """Every weight from ``seed``, in one jitted call whose outputs carry
    ``shardings``."""
    build = builder(params_abs, d_model)
    return jax.jit(build, out_shardings=shardings)(key_for(seed, 0))


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
