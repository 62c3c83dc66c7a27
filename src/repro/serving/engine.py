"""Sharded serving steps: prefill and single-token decode.

``make_serve_steps`` builds jit'd prefill/decode with explicit shardings:
params per the logical rules; KV caches batch-sharded over ('pod','data')
and kv-heads over 'model' when divisible (else the KV sequence dim over
'model').  Both steps donate the cache: the layer scan carries the stacked
cache and writes only each step's new K/V rows into it, so XLA aliases the
donated input to the output cache and updates it in place, with no copy of
the whole cache.  The stacked K/V (L, B, Hkv, slots, Dh) keep one pinned
layout (:func:`kv_layout`) from the program that makes them through every
step, and attention reads each chunk of slots where it lies, so no step
relays the cache or a layer of it, on one chip or on several."""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import shardings_for
from repro.models import lm
from repro.models.config import ModelConfig
from repro.training.step import _abstract_init


def _axis_size(mesh: Mesh, names) -> int:
    if names is None:
        return 1
    if isinstance(names, str):
        names = (names,)
    n = 1
    for a in names:
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n


def _div(x: int, mesh: Mesh, names) -> bool:
    s = _axis_size(mesh, names)
    return s > 1 and x % s == 0


# the stacked K/V leaves of a cache (``lm._layer_cache``), each
# (L, B, Hkv, slots, Dh); recurrent states keep the default layout
KV_LEAVES = ("k", "v", "xk", "xv")
LANES = 128  # a TPU tiles an array's two minor dims over (8, 128)


def kv_layout(d_head: int) -> Layout:
    """The order a stacked K/V leaf lies in, major to minor: d_head minor
    where it fills whole lanes, else slots minor, so that no d_head (96)
    is padded to the lane width.  The steps' products read a chunk of
    slots in place either way, and each layer loop keeps this layout."""
    if d_head % LANES == 0:
        return Layout(major_to_minor=(0, 1, 2, 3, 4))
    return Layout(major_to_minor=(0, 1, 2, 4, 3))


def cache_shardings(cfg: ModelConfig, cache_abstract, mesh: Mesh):
    """Structural sharding for a cache pytree (built from abstract shapes).
    Each stacked K/V leaf gets a ``Format`` of its sharding and
    :func:`kv_layout`, so the program that makes the cache and every step
    that takes and returns it keep one layout."""
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def one(path, x):
        shp = x.shape
        nd = len(shp)
        if nd == 0:
            return NamedSharding(mesh, P())
        spec = [None] * nd
        # stacked layer caches are (L, B, ...); states (L, B, ...)
        bdim = 1 if nd >= 2 else 0
        if _div(shp[bdim], mesh, batch_axes):
            spec[bdim] = batch_axes
        if nd >= 4:
            # (L, B, H, S, D) or (L, B, H, N, P): the head dim first
            if _div(shp[2], mesh, "model"):
                spec[2] = "model"
            elif nd == 5 and _div(shp[3], mesh, "model"):
                # GQA with kv_heads < model size: shard the KV sequence dim
                # over 'model' instead (ring-attention-style cache layout)
                spec[3] = "model"
            if nd == 5 and spec[3] is None and shp[1] == 1 \
                    and _div(shp[3], mesh, batch_axes):
                # batch-1 long-context: shard the sequence dim over data
                spec[3] = batch_axes
        sharding = NamedSharding(mesh, P(*spec))
        if nd == 5 and getattr(path[-1], "key", None) in KV_LEAVES:
            return Format(kv_layout(shp[4]), sharding)
        return sharding

    return jax.tree_util.tree_map_with_path(one, cache_abstract)


def batch_shardings(mesh: Mesh, batch_abstract):
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def one(x):
        nd = len(x.shape)
        if nd == 0 or not _div(x.shape[0], mesh, batch_axes):
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(batch_axes, *([None] * (nd - 1))))

    return jax.tree.map(one, batch_abstract)


def make_serve_steps(cfg: ModelConfig, mesh: Mesh, specs, cache_abstract,
                     batch_abstract, mode: str = "tp"):
    # params placed as training.step.init_sharded places them: each
    # sharding gated on the param's shape dividing the mesh axes
    params_abs, _ = _abstract_init(cfg, jax.random.PRNGKey(0))
    param_sh = shardings_for(specs, mesh, mode, like=params_abs)
    cache_sh = cache_shardings(cfg, cache_abstract, mesh)
    batch_sh = batch_shardings(mesh, batch_abstract)

    # the names give the compiled modules theirs: jit_serve_prefill and
    # jit_serve_decode, which a profiler trace of the step shows
    def serve_prefill(params, batch, cache):
        return lm.prefill(cfg, params, batch, cache)

    def serve_decode(params, tok, cache):
        return lm.decode_step(cfg, params, tok, cache)

    tok_abstract = jax.ShapeDtypeStruct(
        (list(batch_abstract.values())[0].shape[0], 1), jnp.int32)
    tok_sh = batch_shardings(mesh, {"tok": tok_abstract})["tok"]

    prefill_step = jax.jit(
        serve_prefill,
        in_shardings=(param_sh, batch_sh, cache_sh),
        out_shardings=(None, cache_sh),
        donate_argnums=(2,),
    )
    decode_step = jax.jit(
        serve_decode,
        in_shardings=(param_sh, tok_sh, cache_sh),
        out_shardings=(None, cache_sh),
        donate_argnums=(2,),
    )
    return prefill_step, decode_step, (param_sh, batch_sh, cache_sh, tok_sh)


def decode_mapping_plan(cfg: ModelConfig, service, arch, batch: int,
                        kv_len: int, objective: str = "edp",
                        deadline_s: Optional[float] = None
                        ) -> Dict[str, Any]:
    """Per-decode-step mapping plan from the online mapper.

    Queries the :class:`repro.serve_map.MappingService` for every
    structurally unique einsum of one decode step at the *exact*
    ``(batch, kv_len)`` shape — the KV length grows by one every step, so
    consecutive steps collapse onto the service's shape buckets and only
    bucket-boundary crossings pay a search.  Returns ``{einsum name:
    MapResponse}``; each response carries the mapping, its provenance
    (hit/bucket/search) and a certified ``gap_bound``.

    Deliberately jax-free: safe to call from schedulers and admission
    controllers without touching the sharded execution path.
    """
    return service.map_model(cfg, arch, mode="decode", batch=batch,
                             seq=kv_len, objective=objective,
                             deadline_s=deadline_s)
