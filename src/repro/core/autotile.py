"""TCM as a compile-time Pallas BlockSpec autotuner.

The HBM->VMEM->MXU hierarchy of one TPU core is a two-level Arch for the
mapper.  MXU alignment (tiles in multiples of 128) is imposed as a mapspace
constraint by searching in units of 128x128 blocks — i.e. the rank shapes
are divided by 128 before the search and the chosen bounds are scaled back.
The optimal mapping's VMEM tile shapes become the kernel's BlockSpec blocks.

This is the paper's technique applied where a TPU programmer actually makes
tiling choices — the hardware-adaptation path described in DESIGN.md.
"""
from __future__ import annotations

from functools import lru_cache
from math import prod
from typing import Dict, Tuple

from .arch import Arch, MemLevel, SpatialFanout
from .einsum import matmul
from .looptree import Loop, Storage
from .mapper import tcm_map

MXU = 128

# Scoped VMEM one Pallas kernel may allocate on v5e.  The compiler's refusal
# names it: "Scoped allocation with size ... and limit 16.00M exceeded scoped
# vmem limit".
VMEM_LIMIT_BYTES = 16 * 2 ** 20


def _v5e_core(vmem_blocks: int) -> Arch:
    """Block-unit model of one v5e core: the 'word' is a 128x128 tile and a
    'MAC' is one 128x128x128 MXU block-matmul.

    HBM bw: 819 GB/s / (2B * 128^2)  = 2.5e7 blocks/s
    MXU:    197 TFLOP/s / (2*128^3)  = 4.7e7 block-matmuls/s
    VMEM bw ~ 10x HBM.

    Every operand must have a VMEM node: a Pallas kernel reads its blocks
    from VMEM, never straight from HBM.
    """
    return Arch(
        name="v5e-core-blocks",
        levels=(
            MemLevel("HBM", float("inf"), 40.0, 40.0, 2.5e7),
            MemLevel("VMEM", vmem_blocks, 1.0, 1.0, 2.5e8, mandatory=True),
        ),
        mac_energy=0.2,
        frequency=4.7e7,
    )


def _operand_blocks(best, einsum, level: int = 1
                    ) -> Dict[str, Dict[str, int]]:
    """Per operand, the block it holds at ``level``: for each of its rank
    vars, the product of that var's loops below the operand's own storage
    node."""
    nodes = list(best.mapping)
    out: Dict[str, Dict[str, int]] = {}
    for i, n in enumerate(nodes):
        if not (isinstance(n, Storage) and n.level == level):
            continue
        spec = einsum.tensor(n.tensor)
        blk = {v: 1 for v in einsum.rank_shapes if spec.relevant(v)}
        for below in nodes[i + 1:]:
            if isinstance(below, Loop) and below.var in blk:
                blk[below.var] *= below.bound
        out[n.tensor] = blk
    return out


def matmul_vmem_bytes(bm: int, bk: int, bn: int, word_bytes: int) -> int:
    """VMEM ``kernels.matmul.matmul_pallas`` allocates for one block:
    double-buffered A, B and output blocks plus the f32 accumulator."""
    return 2 * word_bytes * (bm * bk + bk * bn + bm * bn) + 4 * bm * bn


def grid_tiles(best, einsum, M: int, K: int, N: int
               ) -> Tuple[Tuple[int, int, int], int]:
    """The (bm, bk, bn) blocks ``matmul_pallas``'s (i, j, k) grid holds for
    a block-unit matmul mapping, and the VMEM blocks the mapping models.

    The grid keeps one block per rank var for all operands, so each var
    takes the largest block among the operands that carry it.  Within one
    loop nest the smaller blocks divide it: the kernel holds every
    operand's mapped block, and A's k block may exceed B's.
    """
    blk = _operand_blocks(best, einsum)
    modeled = sum(prod(b.values()) for b in blk.values())
    per_var = {v: max(b.get(v, 1) for b in blk.values())
               for v in ("m", "k", "n")}
    # a dim below one MXU block is a single block of the whole dim
    return (min(M, per_var["m"] * MXU), min(K, per_var["k"] * MXU),
            min(N, per_var["n"] * MXU)), modeled


def tcm_model_tiles(cfg, mode: str = "prefill", batch: int = 1,
                    seq: int = 1024, vmem_bytes: int = VMEM_LIMIT_BYTES,
                    word_bytes: int = 2, workers: int = None
                    ) -> Dict[str, Tuple[int, int, int]]:
    """BlockSpec tiles for every matmul of a whole model, in one call.

    Delegates to the network planner (``repro.netmap``): the model's layer
    einsums are extracted and deduplicated, and each unique (M, K, N) goes
    through :func:`tcm_matmul_tiles` (memoized).  Returns
    ``{"L<layer>.<op>": (bm, bk, bn)}`` keyed like the planner's report, so
    kernels can look up the tile for the exact op they are lowering.
    """
    from repro.netmap.planner import network_blockspec_tiles

    return network_blockspec_tiles(cfg, mode=mode, batch=batch, seq=seq,
                                   vmem_bytes=vmem_bytes,
                                   word_bytes=word_bytes, workers=workers)


@lru_cache(maxsize=None)
def tcm_matmul_tiles(M: int, K: int, N: int,
                     vmem_bytes: int = VMEM_LIMIT_BYTES,
                     word_bytes: int = 2,
                     workers: int = None) -> Tuple[int, int, int]:
    """Optimal (bm, bk, bn) VMEM tile for Z[M,N] = A[M,K] @ B[K,N].

    Only tiles ``matmul_pallas`` can hold are returned: the grid's blocks
    (:func:`grid_tiles`) must fit ``vmem_bytes`` with everything the kernel
    allocates (:func:`matmul_vmem_bytes`).  When the optimum does not fit,
    the search is repeated with the modeled VMEM cut below that mapping's
    use.  Raises ``ValueError`` when no
    mapping passes.  ``workers`` > 1 fans the mapper's search out over a
    process pool (same tiles either way; parity-tested).
    """
    mb = max(M // MXU, 1)
    kb = max(K // MXU, 1)
    nb = max(N // MXU, 1)
    ein = matmul("mm", mb, kb, nb)
    # capacity in 128x128-block units
    cap = vmem_bytes // word_bytes // (MXU * MXU)
    while cap >= len(ein.tensors):
        best, _ = tcm_map(ein, _v5e_core(cap), objective="latency",
                          workers=workers)
        if best is None:
            break
        tiles, modeled = grid_tiles(best, ein, M, K, N)
        if matmul_vmem_bytes(*tiles, word_bytes) <= vmem_bytes:
            return tiles
        cap = modeled - 1
    raise ValueError(f"no matmul_pallas tile for {M}x{K}x{N} fits "
                     f"{vmem_bytes} bytes of VMEM at {word_bytes} B/word")
