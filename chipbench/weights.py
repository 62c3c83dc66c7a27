"""Weights made by the benchmark from the seed.

``make`` builds every parameter of the program's layout in one jitted
call, on the device, in the dtype it is served in and with the program's
shardings.  The program only gets to say the shapes and the layout.  A
family's ``WEIGHTS`` (leaf name -> rule) is asked first, for leaves of its
own; every other leaf is made by the shared rules below.

Scales are chosen so that every layer kind moves the logits, which is
what lets the correctness check see a fault in any of them:

- the published-form embedding has unit variance, the size of what one
  layer adds to the residual stream;
- q and k have per-element variance ``QK_GAIN``, so attention scores
  (scaled by 1/sqrt(d_head)) have variance ``QK_GAIN**2`` and the softmax
  picks out a few positions instead of averaging the whole prefix to
  nothing;
- every other matrix is N(0, 1/fan_in); norm gains are 1 + N(0, 0.1^2).

Each leaf draws from its own key, ``fold_in(key, i)`` with ``i`` its index
in the tree's order, so a rule changes no other leaf's bytes.
"""
from __future__ import annotations

import math
from typing import Callable, Mapping

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


Rule = Callable[..., jax.Array]  # (z, shape, d_model) -> float32 weight


def _leaf(name: str, shape, dtype, key, d_model: int,
          rules: Mapping[str, Rule]):
    z = jax.random.normal(key, shape, jnp.float32)
    if name in rules:
        w = rules[name](z, shape, d_model)
    elif name == "tok":
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


def builder(params_abs, d_model: int, rules: Mapping[str, Rule]):
    """A function of a key that makes every weight of ``params_abs``
    (shapes and dtypes), asking the family's ``rules`` first."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_abs)

    def build(key):
        return treedef.unflatten([
            _leaf(_leaf_name(path), leaf.shape, leaf.dtype,
                  jax.random.fold_in(key, i), d_model, rules)
            for i, (path, leaf) in enumerate(flat)])

    return build


def make(params_abs, shardings, seed: int, d_model: int,
         rules: Mapping[str, Rule]):
    """Every weight from ``seed``, in one jitted call whose outputs carry
    ``shardings``."""
    build = builder(params_abs, d_model, rules)
    return jax.jit(build, out_shardings=shardings)(key_for(seed, 0))

