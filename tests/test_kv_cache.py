"""The stacked K/V cache's format, as ``repro.models.kv_cache`` owns it:
every architecture's cache leaves and the formats the served steps give
them, the row write of full attention and of a ring, the chunk read, and
the layout rule."""
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format

from repro.configs import ARCHS, get_config
from repro.launch.mesh import make_elastic_mesh
from repro.models import kv_cache, lm
from repro.serving.engine import cache_shardings

B, SLOTS = 2, 75


@pytest.mark.parametrize("arch", ARCHS)
def test_every_cache_leaf_and_its_format(arch):
    """Each K/V leaf of ``lm.init_cache`` is (layers, B, n_kv_heads,
    slots, d_head), each fill index int32[layers], and ``cache_shardings``
    gives ``kv_cache.layout``'s ``Format`` to exactly the K/V leaves."""
    cfg = get_config(arch, smoke=True)
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, B, SLOTS))
    mesh = make_elastic_mesh(target_model=1, devices=jax.devices()[:1])
    shardings = jax.tree.leaves(cache_shardings(cfg, cache, mesh))
    groups = lm._stack_groups(cfg)
    kv_leaves = 0
    for (path, leaf), sh in zip(jax.tree_util.tree_flatten_with_path(
            cache)[0], shardings):
        if kv_cache.is_kv(path):
            kv_leaves += 1
            kinds, count = groups[path[1].idx]
            ring = kinds[path[2].idx] == "wattn"
            slots = min(cfg.window, SLOTS) if ring else SLOTS
            assert leaf.shape == (count, B, cfg.n_kv_heads, slots,
                                  cfg.d_head), path
            assert leaf.dtype == cfg.jdtype
            assert isinstance(sh, Format), path
            assert sh.layout == kv_cache.layout(cfg.d_head)
        else:
            assert not isinstance(sh, Format), path
        if getattr(path[-1], "key", None) == kv_cache.FILL:
            count = groups[path[1].idx][1]
            assert (leaf.shape, leaf.dtype) == ((count,), jnp.int32), path
    attends = sum(k in ("attn", "xattn", "wattn") for kinds, _ in groups
                  for k in kinds)
    assert (kv_leaves > 0) == (attends > 0)


CFG = SimpleNamespace(n_kv_heads=2, d_head=3, jdtype=jnp.float32)


def _rows(seed, S):
    """K and V rows (B, S, Hkv, Dh) with distinct values."""
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(B, S, 2, 3)), jnp.float32)
                 for _ in "kv")


def test_full_attention_write_lands_at_the_fill():
    """Rows are written at the layer's fill, heads before slots, and only
    that layer's fill advances."""
    cache = kv_cache.init(CFG, 3, B, 8)
    cache[kv_cache.FILL] = jnp.asarray([0, 2, 5], jnp.int32)
    k, v = _rows(0, 3)
    idx = kv_cache.fill(cache, 1)
    new = kv_cache.write(cache, 1, idx, k, v)
    ck, cv = kv_cache.kv(new)
    want_k = np.zeros((3, B, 2, 8, 3), np.float32)
    want_k[1, :, :, 2:5] = np.swapaxes(np.asarray(k), 1, 2)
    np.testing.assert_array_equal(np.asarray(ck), want_k)
    np.testing.assert_array_equal(np.asarray(cv)[1, :, :, 2:5],
                                  np.swapaxes(np.asarray(v), 1, 2))
    assert np.asarray(new[kv_cache.FILL]).tolist() == [0, 5, 5]
    assert int(kv_cache.filled(idx, 3)) == 5


def test_ring_write_wraps_around():
    """A prefill of 6 rows into a ring of 4 slots keeps the last 4, each
    at slot pos % 4; the next step's row overwrites the oldest."""
    window = 4
    cache = kv_cache.init(CFG, 1, B, window)
    assert kv_cache.is_ring(cache, window)
    assert not kv_cache.is_ring(cache, 0)
    k, v = _rows(1, 6)
    cache = kv_cache.write(cache, 0, kv_cache.fill(cache, 0), k, v, window)
    ck = np.asarray(kv_cache.kv(cache)[0])[0]  # (B, Hkv, slots, Dh)
    for pos in range(2, 6):
        np.testing.assert_array_equal(ck[:, :, pos % window],
                                      np.asarray(k)[:, pos])
    idx = kv_cache.fill(cache, 0)
    assert int(idx) == 6
    k1, v1 = _rows(2, 1)
    cache = kv_cache.write(cache, 0, idx, k1, v1, window)
    ck, cv = (np.asarray(a)[0] for a in kv_cache.kv(cache))
    np.testing.assert_array_equal(ck[:, :, 6 % window], np.asarray(k1)[:, 0])
    np.testing.assert_array_equal(cv[:, :, 6 % window], np.asarray(v1)[:, 0])
    np.testing.assert_array_equal(ck[:, :, 5 % window], np.asarray(k)[:, 5])
    assert int(kv_cache.fill(cache, 0)) == 7
    assert int(kv_cache.filled(idx, 1, window)) == window


def test_chunk_read_when_chunks_do_not_divide_the_slots():
    """With 10 slots in chunks of 4, the last chunk starts at slot 6; the
    two rows it shares with the chunk before get position 10 (masked)."""
    rng = np.random.default_rng(3)
    k, v = (jnp.asarray(rng.normal(size=(2, B, 2, 10, 3)), jnp.float32)
            for _ in "kv")
    for ci, start, pos in ((0, 0, [0, 1, 2, 3]), (1, 4, [4, 5, 6, 7]),
                           (2, 6, [10, 10, 8, 9])):
        kblk, vblk, k_pos = kv_cache.chunk(k, v, 1, 4, ci)
        np.testing.assert_array_equal(kblk, k[1, :, :, start:start + 4])
        np.testing.assert_array_equal(vblk, v[1, :, :, start:start + 4])
        assert np.asarray(k_pos).tolist() == pos


@pytest.mark.parametrize("d_head,minor", [(64, "slots"), (96, "slots"),
                                          (128, "d_head")])
def test_layout_puts_d_head_minor_only_where_it_fills_the_lanes(d_head,
                                                                minor):
    order = kv_cache.layout(d_head).major_to_minor
    assert sorted(order) == [0, 1, 2, 3, 4]
    assert order[-1] == {"slots": kv_cache.SLOTS, "d_head": 4}[minor]


def test_served_path_imports_neither_training_nor_the_mapper():
    code = ("import sys, repro.serving.engine, repro.launch.serve; "
            "print(sorted(m for m in sys.modules if m.startswith("
            "('repro.training', 'repro.optim', 'repro.serve_map', "
            "'repro.core'))))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip() == "[]", out.stdout
