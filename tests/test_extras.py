"""Beyond-paper extras: gradient compression, TCM shard planner, autotile."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.autotile import (VMEM_LIMIT_BYTES, matmul_vmem_bytes,
                                 tcm_matmul_tiles)
from repro.core.shard_planner import plan_matmul
from repro.distributed.compression import (compress_decompress,
                                           init_error_feedback, quantized_psum)


def test_compression_roundtrip_small_error():
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.normal(size=(300, 7)), jnp.float32)}
    e = init_error_feedback(g)
    deq, e2 = compress_decompress(g, e)
    err = float(jnp.abs(deq["w"] - g["w"]).max())
    blk_scale = float(jnp.abs(g["w"]).max()) / 127.0
    assert err <= blk_scale + 1e-6  # one quantization step per block


def test_compression_error_feedback_converges():
    """Averaged over steps, error feedback keeps the cumulative applied
    gradient close to the cumulative true gradient."""
    rng = np.random.default_rng(1)
    g_true = jnp.asarray(rng.normal(size=(512,)), jnp.float32) * 0.01
    e = init_error_feedback({"g": g_true})
    applied = jnp.zeros_like(g_true)
    for _ in range(50):
        deq, e = compress_decompress({"g": g_true}, e)
        applied = applied + deq["g"]
    np.testing.assert_allclose(np.asarray(applied / 50),
                               np.asarray(g_true), atol=2e-4)


def test_quantized_psum_matches_psum():
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("d",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    x = jnp.asarray(np.random.default_rng(2).normal(size=(256,)), jnp.float32)

    def f(x):
        return quantized_psum(x, "d")

    out = jax.shard_map(f, mesh=mesh, in_specs=P("d"), out_specs=P("d"))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), atol=2e-2,
                               rtol=2e-2)


def test_shard_planner_small_model_prefers_data_parallel():
    """A small matmul should not tensor-parallelize (cell-B finding)."""
    plan = plan_matmul(M=4096, K=512, N=512, data=16, model=16)
    model_par = 1
    for v, f in plan.model_factor.items():
        model_par *= f
    data_par = 1
    for v, f in plan.data_factor.items():
        data_par *= f
    # the batch-like rank m should carry most of the parallelism
    assert plan.data_factor["m"] * plan.model_factor["m"] >= 16


# qwen1.5-0.5b projections at 8x1024 prefill and 8 decode tokens, + 4096^3
AUTOTILE_SHAPES = [(M, K, N) for M in (8 * 1024, 8)
                   for K, N in ((1024, 1024), (1024, 2816), (2816, 1024),
                                (1024, 151936))] + [(4096, 4096, 4096)]


@pytest.mark.parametrize("word_bytes", [2, 4])
@pytest.mark.parametrize("M,K,N", AUTOTILE_SHAPES)
def test_autotile_alignment_and_capacity(M, K, N, word_bytes):
    bm, bk, bn = tcm_matmul_tiles(M, K, N, word_bytes=word_bytes)
    for tile, dim in ((bm, M), (bk, K), (bn, N)):
        # MXU-aligned, or one block of a dim below the MXU; divides the dim
        assert tile % 128 == 0 or tile == dim < 128
        assert dim % tile == 0
    # everything matmul_pallas allocates fits the compiler's scoped VMEM
    assert matmul_vmem_bytes(bm, bk, bn, word_bytes) <= VMEM_LIMIT_BYTES


def test_autotile_raises_when_no_tile_fits():
    # three 128x128 bf16 blocks, double-buffered, exceed 64 KiB
    with pytest.raises(ValueError, match="no matmul_pallas tile"):
        tcm_matmul_tiles(1024, 1024, 1024, vmem_bytes=64 * 1024)
