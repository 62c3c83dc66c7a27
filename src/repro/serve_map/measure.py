"""Served mappings -> Pallas BlockSpec tiles -> kernel walltime.

The service's answers are *modeled*-optimal; this module times the kernels
they tile.  A mapping for the block-unit VMEM arch (``core.autotile``) is
requested **through the service** — exercising the full hot path:
bucketing, coalescing, hot index — and the grid blocks it implies become
the kernel's BlockSpec blocks.  The kernel is then timed (min over
repeats, after a compile warmup, with ``block_until_ready``) against the
default 128-cube tiling, and the report carries the measured-vs-modeled
ratio.

The kernels compile for the TPU unless the caller passes
``interpret=True``; a row timed in interpret mode says so in its
``interpret`` field, and its times are the Pallas interpreter's on the
host, not a device's.
"""
from __future__ import annotations

import time
from typing import Optional, Tuple

from repro.core.autotile import (MXU, VMEM_LIMIT_BYTES, _v5e_core,
                                 grid_tiles, matmul_vmem_bytes)
from repro.core.einsum import matmul

from .request import MapRequest
from .service import MappingService

__all__ = ["service_matmul_tiles", "measure_matmul",
           "measure_flash_attention"]


def service_matmul_tiles(service: MappingService, M: int, K: int, N: int,
                         *, vmem_bytes: int = VMEM_LIMIT_BYTES,
                         word_bytes: int = 2,
                         deadline_s: Optional[float] = None,
                         ) -> Tuple[Tuple[int, int, int], "object"]:
    """(bm, bk, bn) for ``Z[M,N] = A[M,K] @ B[K,N]`` via the service.

    The online twin of ``core.autotile.tcm_matmul_tiles``: same block-unit
    einsum and arch, but the mapping comes from ``service.map`` — so a
    repeated shape is a sub-millisecond hot-index hit and a novel decode
    shape can ride a bucket.  Returns the tiles plus the MapResponse (for
    provenance: source, gap_bound, modeled latency).
    """
    mb, kb, nb = max(M // MXU, 1), max(K // MXU, 1), max(N // MXU, 1)
    vmem_blocks = vmem_bytes // word_bytes // (MXU * MXU)
    ein = matmul(f"mm{M}x{K}x{N}", mb, kb, nb)
    arch = _v5e_core(vmem_blocks)
    resp = service.map(MapRequest(einsum=ein, arch=arch,
                                  objective="latency",
                                  deadline_s=deadline_s))
    tiles, _ = grid_tiles(resp.result, resp.served_einsum, M, K, N)
    if matmul_vmem_bytes(*tiles, word_bytes) > vmem_bytes:
        raise ValueError(f"served tile {tiles} for {M}x{K}x{N} exceeds "
                         f"{vmem_bytes} bytes of VMEM")
    return tiles, resp


def _time_best(fn, repeats: int = 3) -> float:
    """min-of-``repeats`` walltime; ``fn`` must return a jax array."""
    fn().block_until_ready()  # compile / interpreter warmup
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn().block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def measure_matmul(service: MappingService, M: int = 512, K: int = 512,
                   N: int = 512, *, repeats: int = 3,
                   interpret: bool = False) -> dict:
    """Time the service-tiled bf16 Pallas matmul vs the default 128-cube
    tiling.

    Shapes should be MXU-aligned powers of two (the service's buckets then
    pass them through unchanged and the tiles always divide the dims).
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.matmul import matmul_pallas

    (bm, bk, bn), resp = service_matmul_tiles(service, M, K, N)
    key = jax.random.PRNGKey(0)
    ka, kb_ = jax.random.split(key)
    a = jax.random.normal(ka, (M, K), dtype=jnp.bfloat16)
    b = jax.random.normal(kb_, (K, N), dtype=jnp.bfloat16)

    t_tcm = _time_best(
        lambda: matmul_pallas(a, b, bm=bm, bk=bk, bn=bn,
                              interpret=interpret), repeats)
    dflt = (min(M, MXU), min(K, MXU), min(N, MXU))
    t_dflt = _time_best(
        lambda: matmul_pallas(a, b, bm=dflt[0], bk=dflt[1], bn=dflt[2],
                              interpret=interpret), repeats)
    modeled_s = resp.result.latency
    return {
        "kernel": "matmul",
        "shape": [M, K, N],
        "tiles": [bm, bk, bn],
        "default_tiles": list(dflt),
        "map_source": resp.source,
        "map_latency_ms": resp.latency_s * 1e3,
        "gap_bound": resp.gap_bound,
        "measured_s": t_tcm,
        "default_s": t_dflt,
        "speedup_vs_default": t_dflt / t_tcm if t_tcm > 0 else 0.0,
        "modeled_s": modeled_s,
        "measured_vs_modeled": t_tcm / modeled_s if modeled_s > 0 else 0.0,
        "interpret": bool(interpret),
    }


def measure_flash_attention(service: MappingService, B: int = 1,
                            H: int = 4, Sq: int = 256, Sk: int = 256,
                            Dh: int = 128, *, causal: bool = False,
                            repeats: int = 3,
                            interpret: bool = False) -> dict:
    """Time flash attention with service-chosen (bq, bk) vs default 128s.

    The score matmul ``S = Q @ K^T`` (per head: M=Sq, K=Dh, N=Sk) drives
    the tiling: the service's bm becomes the query block ``bq`` and bn the
    kv block ``bk`` — the two grid choices ``flash_attention_pallas``
    exposes.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention import flash_attention_pallas

    (bm, _, bn), resp = service_matmul_tiles(service, Sq, Dh, Sk)
    bq, bkv = min(bm, Sq), min(bn, Sk)
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Sq, H, Dh), dtype=jnp.bfloat16)
    k = jax.random.normal(kk, (B, Sk, H, Dh), dtype=jnp.bfloat16)
    v = jax.random.normal(kv, (B, Sk, H, Dh), dtype=jnp.bfloat16)

    t_tcm = _time_best(
        lambda: flash_attention_pallas(q, k, v, causal=causal, bq=bq,
                                       bk=bkv, interpret=interpret),
        repeats)
    t_dflt = _time_best(
        lambda: flash_attention_pallas(q, k, v, causal=causal, bq=128,
                                       bk=128, interpret=interpret),
        repeats)
    modeled_s = resp.result.latency
    return {
        "kernel": "flash_attention",
        "shape": [B, H, Sq, Sk, Dh],
        "tiles": [bq, bkv],
        "default_tiles": [128, 128],
        "map_source": resp.source,
        "map_latency_ms": resp.latency_s * 1e3,
        "gap_bound": resp.gap_bound,
        "measured_s": t_tcm,
        "default_s": t_dflt,
        "speedup_vs_default": t_dflt / t_tcm if t_tcm > 0 else 0.0,
        "modeled_s": modeled_s,
        "measured_vs_modeled": t_tcm / modeled_s if modeled_s > 0 else 0.0,
        "interpret": bool(interpret),
    }
