"""The stacked K/V cache of a group of attention layers: its leaves and
their shape, the row a step writes, the chunk attention reads, and the
layout every served program keeps.  The layer scan carries the cache and
each layer updates it in place:

  k, v    (L, B, Hkv, slots, Dh)  roped keys and values, heads before
                                  slots: a chip's heads are one block, a
                                  chunk of slots one slice
  idx     int32[L]                each layer's fill: the rows written
  xk, xv  (L, B, Hkv, Sm, Dh)     an encoder-decoder's cross-attention
                                  memory K/V, written by the prefill

Full attention writes a step's rows at the fill; a ring (slots == window)
keeps the last ``window`` tokens, each at slot ``pos % window``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout

from repro.obs.serving import KV_WRITE

KV_LEAVES = K, V, XK, XV = ("k", "v", "xk", "xv")
FILL = "idx"
HEADS, SLOTS = 2, 3  # the axes of a stacked K/V leaf
LANES = 128  # a TPU tiles an array's two minor dims over (8, 128)


def zeros(cfg, layers: int, batch: int, slots: int):
    return jnp.zeros((layers, batch, cfg.n_kv_heads, slots, cfg.d_head),
                     cfg.jdtype)


def init(cfg, layers: int, batch: int, slots: int):
    """An empty self-attention cache: zero K/V, every fill 0."""
    return {FILL: jnp.zeros((layers,), jnp.int32),
            K: zeros(cfg, layers, batch, slots),
            V: zeros(cfg, layers, batch, slots)}


def init_memory(cfg, layers: int, batch: int, slots: int):
    """Zero memory K/V, held beside a decoder's self-attention cache."""
    return {XK: zeros(cfg, layers, batch, slots),
            XV: zeros(cfg, layers, batch, slots)}


def heads_first(x):
    """Rows (B, S, Hkv, Dh) in the order the cache holds them."""
    return jnp.swapaxes(x, 1, 2)


def with_memory(cache, k, v):
    return dict(cache, **{XK: k, XV: v})


def memory(cache):
    """The stacked memory K/V a decoder layer's cache holds, or None."""
    return (cache[XK], cache[XV]) if XK in cache else None


def kv(cache):
    return cache[K], cache[V]


def fill(cache, layer):
    """Rows layer ``layer`` has written: its next token's position."""
    return lax.dynamic_index_in_dim(cache[FILL], layer, keepdims=False)


def is_ring(cache, window: int) -> bool:
    return bool(window) and cache[K].shape[SLOTS] == window


def filled(idx, rows: int, ring: int = 0):
    """Slots holding K/V once ``rows`` rows are written at fill ``idx``."""
    return jnp.minimum(idx + rows, ring) if ring else idx + rows


def write(cache, layer, idx, k, v, window: int = 0):
    """Write layer ``layer``'s new roped K/V rows, each (B, S, Hkv, Dh),
    into the stack in place and advance its fill by S.  A ring's step
    writes at ``idx % window``; a prefill into a ring keeps its last
    ``window`` rows, each rolled to its slot ``pos % window``."""
    S = k.shape[1]
    ring = is_ring(cache, window)
    if ring and S > 1:
        assert S >= window, "prefill shorter than window"
        shift = (S - window) % window
        k = jnp.roll(k[:, S - window:], shift, axis=1)
        v = jnp.roll(v[:, S - window:], shift, axis=1)
        row = 0
    else:
        row = idx % window if ring else idx
    with jax.named_scope(KV_WRITE):
        ck, cv = (lax.dynamic_update_slice(
            c, heads_first(r)[None].astype(c.dtype), (layer, 0, 0, row, 0))
            for c, r in ((cache[K], k), (cache[V], v)))
    return {K: ck, V: cv, FILL: lax.dynamic_update_index_in_dim(
        cache[FILL], idx + S, layer, 0)}


def chunk(k, v, layer, size: int, ci):
    """Chunk ``ci`` of ``size`` slots of layer ``layer`` of the stacked K
    and V, sliced where it lies, and its slots' positions.  A last chunk
    that would run past the slots starts ``slots - size`` in; the rows it
    shares with the chunk before get position ``slots``, past any fill,
    so attention masks them."""
    _, B, Hkv, Sk, Dh = k.shape
    lo = ci * size
    start = jnp.minimum(lo, Sk - size)
    kblk, vblk = (lax.dynamic_slice(c, (layer, 0, 0, start, 0),
                                    (1, B, Hkv, size, Dh))[0]
                  for c in (k, v))
    k_pos = start + jnp.arange(size)
    if Sk % size:
        k_pos = jnp.where(k_pos >= lo, k_pos, Sk)
    return kblk, vblk, k_pos


def layout(d_head: int) -> Layout:
    """The order a stacked K/V leaf lies in, major to minor: d_head minor
    where it fills whole lanes, else slots minor, so that no d_head (96)
    is padded to the lane width.  Attention's products read a chunk of
    slots in place either way, and each layer loop keeps this layout."""
    if d_head % LANES == 0:
        return Layout(major_to_minor=(0, 1, 2, 3, 4))
    return Layout(major_to_minor=(0, 1, 2, 4, 3))


def is_kv(path) -> bool:
    """Whether a cache pytree path ends at a stacked K/V leaf."""
    return getattr(path[-1], "key", None) in KV_LEAVES
