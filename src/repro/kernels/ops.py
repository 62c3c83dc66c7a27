"""jit'd public wrappers for the Pallas kernels.

The kernels compile for the TPU.  Interpret mode runs only where a caller
passes ``interpret=True`` (the CPU tests do); nothing picks it from the
backend, so a host without a TPU fails instead of timing the interpreter.
``tcm_matmul`` asks the TCM mapper for the optimal VMEM tiling per shape
(cached), so the paper's search drives the kernel schedule.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.autotile import tcm_matmul_tiles, tcm_model_tiles
from .flash_attention import flash_attention_pallas
from .matmul import matmul_pallas


def model_blockspec_tiles(cfg, **kw):
    """All BlockSpec tiles for ``cfg``'s matmuls from one planner call.

    Thin kernel-side alias of ``core.autotile.tcm_model_tiles`` so kernel
    callers need not import the mapper; ``kw`` forwards mode/batch/seq/
    vmem_bytes/word_bytes/workers.
    """
    return tcm_model_tiles(cfg, **kw)


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("interpret",))
def tcm_matmul(a: jax.Array, b: jax.Array, interpret: bool = False):
    """TCM-autotiled matmul.  Shapes padded to the chosen tile grid."""
    M, K = a.shape
    _, N = b.shape
    bm, bk, bn = tcm_matmul_tiles(M, K, N, word_bytes=a.dtype.itemsize)
    ap = _pad_to(_pad_to(a, bm, 0), bk, 1)
    bp = _pad_to(_pad_to(b, bk, 0), bn, 1)
    out = matmul_pallas(ap, bp, bm=bm, bk=bk, bn=bn, interpret=interpret)
    return out[:M, :N]


@partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_op(q, k, v, causal: bool = True, bq: int = 128,
                       bk: int = 128, interpret: bool = False):
    return flash_attention_pallas(q, k, v, causal=causal, bq=bq, bk=bk,
                                  interpret=interpret)
