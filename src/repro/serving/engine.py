"""Sharded serving steps: prefill and single-token decode.

``make_serve_steps`` builds jit'd prefill/decode with explicit shardings:
params per the logical rules; KV caches batch-sharded over ('pod','data')
and kv-heads over 'model' when divisible (else the KV sequence dim over
'model').  Both steps donate the cache: the layer scan carries the stacked
cache and writes only each step's new K/V rows into it, so XLA aliases the
donated input to the output cache and updates it in place, with no copy of
the whole cache.  The stacked K/V keep one pinned layout
(``kv_cache.layout``) from the program that makes them through every
step, and attention reads each chunk of slots where it lies, so no step
relays the cache or a layer of it, on one chip or on several."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import shardings_for
from repro.models import kv_cache, lm
from repro.models.config import ModelConfig


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


def cache_shardings(cfg: ModelConfig, cache_abstract, mesh: Mesh):
    """Structural sharding for a cache pytree (built from abstract shapes).
    Each stacked K/V leaf gets a ``Format`` of its sharding and
    ``kv_cache.layout``, so the program that makes the cache and every step
    that takes and returns it keep one layout."""
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def one(path, x):
        shp = x.shape
        nd = len(shp)
        if nd == 0:
            return NamedSharding(mesh, P())
        spec = [None] * nd
        # stacked layer caches and states are (L, B, ...)
        bdim = 1 if nd >= 2 else 0
        if _div(shp[bdim], mesh, batch_axes):
            spec[bdim] = batch_axes
        if not kv_cache.is_kv(path):
            if nd >= 4 and _div(shp[2], mesh, "model"):
                spec[2] = "model"  # a state's heads: (L, B, H, N, P)
            return NamedSharding(mesh, P(*spec))
        heads, slots = kv_cache.HEADS, kv_cache.SLOTS
        if _div(shp[heads], mesh, "model"):
            spec[heads] = "model"
        elif _div(shp[slots], mesh, "model"):
            # GQA with kv_heads < model size: shard the slots over 'model'
            # instead (ring-attention-style cache layout)
            spec[slots] = "model"
        if spec[slots] is None and shp[1] == 1 \
                and _div(shp[slots], mesh, batch_axes):
            # batch-1 long-context: shard the slots over data
            spec[slots] = batch_axes
        return Format(kv_cache.layout(cfg.d_head),
                      NamedSharding(mesh, P(*spec)))

    return jax.tree_util.tree_map_with_path(one, cache_abstract)


def batch_shardings(mesh: Mesh, batch_abstract):
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def one(x):
        nd = len(x.shape)
        if nd == 0 or not _div(x.shape[0], mesh, batch_axes):
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(batch_axes, *([None] * (nd - 1))))

    return jax.tree.map(one, batch_abstract)


def init_params(cfg: ModelConfig, mesh: Mesh, seed: int = 0,
                mode: str = "tp"):
    """Params made on ``mesh`` from ``seed``, each sharding gated on the
    param's shape dividing the mesh axes; returns (params, specs)."""
    key = jax.random.PRNGKey(seed)

    def init_fn(key):
        params, _ = lm.init(cfg, key)
        return params

    params_shape, specs = lm.abstract_init(cfg, key)
    param_sh = shardings_for(specs, mesh, mode, like=params_shape)
    return jax.jit(init_fn, out_shardings=param_sh)(key), specs


def make_serve_steps(cfg: ModelConfig, mesh: Mesh, specs, cache_abstract,
                     batch_abstract, mode: str = "tp"):
    # params placed as init_params places them
    params_abs, _ = lm.abstract_init(cfg, jax.random.PRNGKey(0))
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
