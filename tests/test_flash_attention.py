"""flash_attention (pure-JAX, custom_vjp) vs naive attention: fwd + grads."""
import math

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.models import kv_cache, layers
from repro.models.layers import flash_attention


def naive_attention(q, k, v, causal=True, window=0, q_offset=0):
    B, Sq, Hq, Dh = q.shape
    _, Sk, Hkv, _ = k.shape
    rep = Hq // Hkv
    kk = jnp.repeat(k, rep, axis=2).astype(jnp.float32)
    vv = jnp.repeat(v, rep, axis=2).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kk)
    s = s / math.sqrt(Dh)
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask = mask & (k_pos[None, :] <= q_pos[:, None])
    if window:
        mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vv)


@pytest.mark.parametrize("shape", [
    # (B, Sq, Sk, Hq, Hkv, Dh, causal, window)
    (2, 64, 64, 4, 4, 16, True, 0),
    (2, 64, 64, 4, 2, 16, True, 0),     # GQA
    (1, 48, 48, 6, 2, 8, False, 0),     # non-causal, non-pow2 seq
    (2, 64, 64, 4, 1, 16, True, 24),    # local window + MQA
    (1, 1, 96, 4, 2, 16, True, 0),      # decode-style single query
])
def test_forward_matches_naive(shape):
    B, Sq, Sk, Hq, Hkv, Dh, causal, window = shape
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, Sq, Hq, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Sk, Hkv, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Sk, Hkv, Dh)), jnp.float32)
    q_off = Sk - Sq
    out = flash_attention(q, k, v, causal=causal, window=window,
                          q_offset=q_off, q_chunk=16, kv_chunk=16)
    ref = naive_attention(q, k, v, causal=causal, window=window,
                          q_offset=q_off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("hkv,causal,window", [(4, True, 0), (2, True, 0),
                                               (2, False, 0), (1, True, 24)])
def test_grads_match_naive(hkv, causal, window):
    B, S, Hq, Dh = 2, 64, 4, 16
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(B, S, Hq, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, hkv, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, hkv, Dh)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(Dh,)), jnp.float32)

    def f_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, window=window,
                            q_chunk=16, kv_chunk=16)
        return jnp.sum(jnp.tanh(o @ w))

    def f_naive(q, k, v):
        return jnp.sum(jnp.tanh(naive_attention(
            q, k, v, causal=causal, window=window) @ w))

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_kv_valid_masks_tail():
    B, S, H, Dh = 1, 32, 2, 8
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, H, Dh)), jnp.float32)
    # valid prefix of 20; garbage tail must not affect the result
    k_g = k.at[:, 20:].set(1e3)
    v_g = v.at[:, 20:].set(1e3)
    out1 = flash_attention(q, k, v, causal=False, kv_valid=20, q_chunk=8,
                           kv_chunk=8)
    out2 = flash_attention(q, k_g, v_g, causal=False, kv_valid=20, q_chunk=8,
                           kv_chunk=8)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


def _every_chunk_fwd(cfgt, q, k, v, q_off_f, kv_valid_f, layer):
    """The cached forward as it was before its KV loop took a bound: a
    scan over every allocated chunk of the stack, the chunks that no
    query sees masked to nothing."""
    causal, window, q_chunk, kv_chunk, Sk = cfgt
    assert not window
    B, Sq, Hkv, rep, Dh = q.shape
    q_off = q_off_f.astype(jnp.int32)
    kv_valid = kv_valid_f.astype(jnp.int32)
    qcs = jnp.moveaxis(q.reshape(B, Sq // q_chunk, q_chunk, Hkv, rep, Dh),
                       1, 0)

    def q_block(qi_blk):
        qi, qblk = qi_blk
        qb = (qblk * (1.0 / math.sqrt(Dh))).astype(q.dtype)
        q_pos = q_off + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, ci):
            m, l, acc = carry
            kblk, vblk, k_pos = kv_cache.chunk(k, v, layer, kv_chunk, ci)
            s = jnp.einsum("bqgrd,bgkd->bgrqk", qb, kblk,
                           preferred_element_type=jnp.float32)
            mask = k_pos[None, :] < kv_valid
            if causal:
                mask = mask & (k_pos[None, :] <= q_pos[:, None])
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            pv = jnp.einsum("bgrqk,bgkd->bgrqd", p.astype(q.dtype), vblk,
                            preferred_element_type=jnp.float32)
            return (m_new, l * corr + p.sum(axis=-1),
                    acc * corr[..., None] + pv), None

        init = (jnp.full((B, Hkv, rep, q_chunk), -jnp.inf, jnp.float32),
                jnp.zeros((B, Hkv, rep, q_chunk), jnp.float32),
                jnp.zeros((B, Hkv, rep, q_chunk, Dh), jnp.float32))
        (_, l, acc), _ = lax.scan(kv_step, init,
                                  jnp.arange(-(-Sk // kv_chunk)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.einsum("bgrqd->bqgrd", out).astype(q.dtype)

    outs = lax.map(q_block, (jnp.arange(Sq // q_chunk), qcs))
    return jnp.moveaxis(outs, 0, 1).reshape(B, Sq, Hkv, rep, Dh), None


@pytest.mark.parametrize("hq,hkv,slots,fill,rows,causal,kv_valid", [
    (2, 2, 2048, 0, 1536, True, None),     # causal prefill, 3 of 4 chunks
    (2, 2, 2048, 0, 700, True, None),      # a prompt the chunk does not divide
    (2, 2, 2048, 600, 700, True, None),    # a prefill after a fill
    (2, 2, 2048, 510, 1, True, None),      # decode just below a boundary
    (2, 2, 2048, 511, 1, True, None),      # the fill at a boundary
    (2, 2, 2048, 512, 1, True, None),      # just past it
    (6, 2, 2048, 1100, 1, True, None),     # GQA decode
    (4, 2, 2048, 0, 1024, True, None),     # GQA prefill
    (2, 2, 1024, 300, 1, False, 301),      # a ring cache still filling
    (2, 2, 1024, 5000, 1, False, 1024),    # a full ring
    (2, 2, 1000, 700, 1, True, None),      # overlapping last chunk, decode
    (2, 2, 1000, 0, 900, True, None),      # and prefill
])
def test_cached_loop_stops_at_the_last_visible_chunk(
        monkeypatch, hq, hkv, slots, fill, rows, causal, kv_valid):
    """The cached KV loop, stopping after the last chunk that a query
    block can see, gives what the loop over every allocated chunk gave:
    causal prefill and decode, GQA and the padded one-row MHA decode, a
    ring before and after it fills, and slots that the chunks do not
    divide (the last chunk overlaps the one before)."""
    B, L, Dh, layer = 2, 2, 16, 1
    rng = np.random.default_rng(slots + fill + rows)
    q = jnp.asarray(rng.normal(size=(B, rows, hq, Dh)), jnp.float32)
    ck, cv = (jnp.asarray(rng.normal(size=(L, B, hkv, slots, Dh)),
                          jnp.float32) for _ in "kv")
    valid = fill + rows if kv_valid is None else kv_valid

    def run():  # traced anew, with the forward the module holds now
        attend = jax.jit(partial(flash_attention, causal=causal))
        return np.asarray(attend(q, ck, cv, q_offset=jnp.int32(fill),
                                 kv_valid=jnp.int32(valid),
                                 layer=jnp.int32(layer)))

    got = run()
    monkeypatch.setattr(layers, "_flash_fwd_impl", _every_chunk_fwd)
    want = run()
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
