"""Core JAX layers: norms, RoPE, flash-style attention, MLP, MoE.

All layers are pure functions over explicit param pytrees.  Each param
creator returns ``(params, specs)`` where ``specs`` mirrors the params with
logical-axis tuples consumed by ``repro.distributed.sharding``.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.sharding import constrain, constrain_any
from repro.obs.serving import ATTEND, ATTN_OUT, MLP, NORM, QKV

from . import kv_cache

Params = Dict
Specs = Dict


def _init(key, shape, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------

def rmsnorm_params(d: int, dtype) -> Tuple[Params, Specs]:
    return {"scale": jnp.ones((d,), dtype)}, {"scale": ("embed",)}


@jax.named_scope(NORM)
def rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(dt)


@jax.named_scope(QKV)  # scoped with the q/k/v projections it rotates
def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, Dh); positions: (..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., :, None, :]  # (..., S, 1, half)
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (flash-style streaming over KV chunks; pure JAX reference path —
# the Pallas kernel in repro.kernels.flash_attention implements the same
# contract for the TPU target)
# ---------------------------------------------------------------------------

def attention_params(cfg, key) -> Tuple[Params, Specs]:
    ks = jax.random.split(key, 4)
    dt = cfg.jparam_dtype
    p = {
        "wq": _init(ks[0], (cfg.d_model, cfg.q_dim), dt),
        "wk": _init(ks[1], (cfg.d_model, cfg.kv_dim), dt),
        "wv": _init(ks[2], (cfg.d_model, cfg.kv_dim), dt),
        "wo": _init(ks[3], (cfg.q_dim, cfg.d_model), dt,
                    scale=1.0 / math.sqrt(cfg.q_dim)),
    }
    s = {
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv"),
        "wv": ("embed", "kv"),
        "wo": ("heads", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dt)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dt)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dt)
        s["bq"] = ("heads",)
        s["bk"] = ("kv",)
        s["bv"] = ("kv",)
    return p, s


def _mask_for(cfgt, q_pos, k_pos, kv_valid):
    causal, window, _, _, Sk = cfgt
    mask = k_pos[None, :] < kv_valid
    if causal:
        mask = mask & (k_pos[None, :] <= q_pos[:, None])
    if window:
        mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
    return mask  # (qc, kc)


CHUNK = 512  # flash_attention's query and KV chunk, in rows and slots


def visible_chunks(q_off, qi, q_chunk, kv_valid, kv_chunk, slots, causal):
    """How many KV chunks, from the first, the cached loop visits for query
    block ``qi``: those that hold a key some query of the block can see,
    below the fill ``kv_valid`` and, when causal, at or before the block's
    last position.  The chunks past it hold none; chunk 0 is always seen,
    so they would add exactly 0.  A window that binds comes with a ring
    cache, whose loop is not causal, so no chunk is cut from the front.
    Takes ints or traced scalars."""
    last = jnp.minimum(kv_valid, slots)
    if causal:
        last = jnp.minimum(last, q_off + (qi + 1) * q_chunk)
    return -(-last // kv_chunk)


def _kv_chunks(x, kv_chunk):
    """(B, Hkv, Sk, Dh) -> (Sk // kv_chunk, B, Hkv, kv_chunk, Dh): each
    chunk keeps the (slots, d_head) tiles of its heads."""
    B, Hkv, Sk, Dh = x.shape
    return jnp.moveaxis(x.reshape(B, Hkv, Sk // kv_chunk, kv_chunk, Dh), 2, 0)


def _flash_fwd_impl(cfgt, q, k, v, q_off_f, kv_valid_f, layer=None):
    causal, window, q_chunk, kv_chunk, Sk0 = cfgt
    B, Sq, Hkv, rep, Dh = q.shape
    nq = Sq // q_chunk
    scale = 1.0 / math.sqrt(Dh)
    q_off = q_off_f.astype(jnp.int32)
    kv_valid = kv_valid_f.astype(jnp.int32)
    if layer is None:  # k/v (B, Hkv, Skp, Dh), relaid into chunks
        nk = k.shape[2] // kv_chunk
        kv_xs = (_kv_chunks(k, kv_chunk), _kv_chunks(v, kv_chunk),
                 jnp.arange(nk))

        def read(xs):
            kblk, vblk, ci = xs
            return kblk, vblk, ci * kv_chunk + jnp.arange(kv_chunk)
    else:  # a stacked cache, each chunk read from it in the loop
        read = partial(kv_cache.chunk, k, v, layer, kv_chunk)
    qcs = jnp.moveaxis(q.reshape(B, nq, q_chunk, Hkv, rep, Dh), 1, 0)
    # context parallelism must survive the chunking reshape: shard the
    # *within-chunk* query dim over 'model' — otherwise SPMD runs all nq
    # chunk iterations redundantly on every model-group device (a measured
    # 16x compute waste)
    qcs = constrain(qcs, (None, "batch", "act_seq", None, None, None))

    def q_block(qi_blk):
        qi, qblk = qi_blk
        qb = (qblk * scale).astype(q.dtype)
        q_pos = q_off + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, inputs):
            m, l, acc = carry
            kblk, vblk, k_pos = read(inputs)
            s = jnp.einsum("bqgrd,bgkd->bgrqk", qb, kblk,
                           preferred_element_type=jnp.float32)
            mask = _mask_for(cfgt, q_pos, k_pos, kv_valid)
            s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = (acc * corr[..., None]
                       + jnp.einsum("bgrqk,bgkd->bgrqd",
                                    p.astype(q.dtype), vblk,
                                    preferred_element_type=jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, rep, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Hkv, rep, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Hkv, rep, q_chunk, Dh), jnp.float32)
        if layer is None:
            (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), kv_xs)
        else:  # no backward here, so the trip count may follow the data
            hi = visible_chunks(q_off, qi, q_chunk, kv_valid, kv_chunk, Sk0,
                                causal)
            m, l, acc = lax.fori_loop(0, hi, lambda ci, c: kv_step(c, ci)[0],
                                      (m0, l0, a0))
        l = jnp.maximum(l, 1e-30)
        out = jnp.einsum("bgrqd->bqgrd",
                         acc / l[..., None]).astype(q.dtype)
        lse = m + jnp.log(l)  # (B, Hkv, rep, qc)
        return out, lse

    outs, lses = lax.map(q_block, (jnp.arange(nq), qcs))
    out = jnp.moveaxis(outs, 0, 1)  # (B, nq, qc, Hkv, rep, Dh)
    lse = jnp.moveaxis(lses, 0, 1)  # (B, nq, Hkv, rep, qc)
    return out.reshape(B, Sq, Hkv, rep, Dh), lse


def _flash_bwd_impl(cfgt, res, dout):
    """Manual flash backward: recompute per-block probabilities from the
    saved logsumexp — nothing is stored per kv step (the autodiff-through-
    scan version keeps (m,l,acc) per step: O(S/kc * B*H*qc*Dh) — deadly)."""
    causal, window, q_chunk, kv_chunk, Sk0 = cfgt
    q, k, v, out, lse, q_off_f, kv_valid_f = res
    B, Sq, Hkv, rep, Dh = q.shape
    Skp = k.shape[2]
    nk = Skp // kv_chunk
    nq = Sq // q_chunk
    scale = 1.0 / math.sqrt(Dh)
    q_off = q_off_f.astype(jnp.int32)
    kv_valid = kv_valid_f.astype(jnp.int32)

    kcs = _kv_chunks(k, kv_chunk)
    vcs = _kv_chunks(v, kv_chunk)
    qcs = jnp.moveaxis(q.reshape(B, nq, q_chunk, Hkv, rep, Dh), 1, 0)
    qcs = constrain(qcs, (None, "batch", "act_seq", None, None, None))
    docs = jnp.moveaxis(dout.reshape(B, nq, q_chunk, Hkv, rep, Dh), 1, 0)
    docs = constrain(docs, (None, "batch", "act_seq", None, None, None))
    lses = jnp.moveaxis(lse.reshape(B, nq, Hkv, rep, q_chunk), 1, 0)
    # delta = rowsum(dout * out)
    delta = jnp.einsum("bsgrd,bsgrd->bgrs",
                       dout.astype(jnp.float32),
                       out.reshape(B, Sq, Hkv, rep, Dh).astype(jnp.float32))
    deltas = jnp.moveaxis(
        delta.reshape(B, Hkv, rep, nq, q_chunk), 3, 0)

    def q_step(carry, inputs):
        dk, dv = carry
        qi, qblk, doblk, lseblk, dltblk = inputs
        q_pos = q_off + qi * q_chunk + jnp.arange(q_chunk)
        qb = (qblk * scale).astype(q.dtype)

        def kv_step(inner, kin):
            dq_c, dk, dv = inner
            kblk, vblk, ci = kin
            k_pos = ci * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqgrd,bgkd->bgrqk", qb, kblk,
                           preferred_element_type=jnp.float32)
            mask = _mask_for(cfgt, q_pos, k_pos, kv_valid)
            p = jnp.where(mask[None, None, None],
                          jnp.exp(s - lseblk[..., None]), 0.0)
            pb = p.astype(q.dtype)
            dob = doblk.astype(q.dtype)
            dv_b = jnp.einsum("bgrqk,bqgrd->bgkd", pb, dob,
                              preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqgrd,bgkd->bgrqk", dob, vblk,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - dltblk[..., None])  # (B,g,r,qc,kc) f32
            dsb = ds.astype(q.dtype)
            # bgrqd, then transposed: XLA's CPU backend runs no bf16 dot
            # whose output puts q before the batch dim g
            dq_b = jnp.einsum("bgrqk,bgkd->bgrqd", dsb, kblk,
                              preferred_element_type=jnp.float32
                              ).transpose(0, 3, 1, 2, 4)
            dk_b = jnp.einsum("bgrqk,bqgrd->bgkd", dsb, qblk.astype(q.dtype),
                              preferred_element_type=jnp.float32)
            dq_c = dq_c + dq_b * scale
            start = ci * kv_chunk
            dk = lax.dynamic_update_slice(
                dk, lax.dynamic_slice(
                    dk, (0, 0, start, 0),
                    (B, Hkv, kv_chunk, Dh)) + dk_b * scale,
                (0, 0, start, 0))
            dv = lax.dynamic_update_slice(
                dv, lax.dynamic_slice(
                    dv, (0, 0, start, 0),
                    (B, Hkv, kv_chunk, Dh)) + dv_b,
                (0, 0, start, 0))
            return (dq_c, dk, dv), None

        dq0 = jnp.zeros((B, q_chunk, Hkv, rep, Dh), jnp.float32)
        (dq_c, dk, dv), _ = lax.scan(
            kv_step, (dq0, dk, dv), (kcs, vcs, jnp.arange(nk)))
        return (dk, dv), dq_c

    dk0 = jnp.zeros((B, Hkv, Skp, Dh), jnp.float32)
    dv0 = jnp.zeros((B, Hkv, Skp, Dh), jnp.float32)
    (dk, dv), dqs = lax.scan(
        q_step, (dk0, dv0),
        (jnp.arange(nq), qcs, docs, lses, deltas))
    dq = jnp.moveaxis(dqs, 0, 1).reshape(B, Sq, Hkv, rep, Dh)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfgt, q, k, v, q_off_f, kv_valid_f):
    out, _ = _flash_fwd_impl(cfgt, q, k, v, q_off_f, kv_valid_f)
    return out


def _flash_fwd(cfgt, q, k, v, q_off_f, kv_valid_f):
    out, lse = _flash_fwd_impl(cfgt, q, k, v, q_off_f, kv_valid_f)
    return out, (q, k, v, out, lse, q_off_f, kv_valid_f)


_flash.defvjp(_flash_fwd, _flash_bwd_impl)


@jax.named_scope(ATTEND)
def flash_attention(q, k, v, *, causal: bool, window: int = 0,
                    q_offset=0, q_chunk: int = CHUNK, kv_chunk: int = CHUNK,
                    kv_valid=None, layer=None):
    """Streaming softmax attention, chunked over q and kv, with a manual
    flash backward (custom_vjp).

    q: (B, Sq, Hq, Dh); k/v: (B, Sk, Hkv, Dh), or, with ``layer``, the
    stacked K/V of a ``kv_cache``: the KV loop then reads layer
    ``layer``'s chunks straight from the stack (``kv_cache.chunk``), with
    no copy of the layer and no backward, and stops after the last chunk
    a query block can see (``visible_chunks``).  GQA: Hq % Hkv == 0.
    ``q_offset`` is the absolute position of q[0] relative to k[0] (decode
    with a cache passes the fill index).  Peak live block is
    (B, Hkv, rep, q_chunk, kv_chunk) in f32.  Returns (B, Sq, Hq, Dh).
    """
    B, Sq, Hq, Dh = q.shape
    if layer is None:
        k, v = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
    Hkv, Sk = k.shape[-3:-1]
    rep = Hq // Hkv
    kv_chunk = min(kv_chunk, Sk)
    q_chunk = min(q_chunk, Sq)
    if layer is not None and q_chunk * rep == 1:
        # one query row a KV head (decode without GQA): XLA would turn each
        # chunk's product into a multiply-reduce that relays the chunk to
        # put d_head minor; a second row, padded and dropped, keeps it a
        # matrix product, which reads the chunk where it lies
        q_chunk = 2

    nk = (Sk + kv_chunk - 1) // kv_chunk
    pad_k = nk * kv_chunk - Sk
    if pad_k and layer is None:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = (Sq + q_chunk - 1) // q_chunk
    pad_q = nq * q_chunk - Sq
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    qg = qp.reshape(B, nq * q_chunk, Hkv, rep, Dh)

    cfgt = (bool(causal), int(window), int(q_chunk), int(kv_chunk), int(Sk))
    q_off_f = jnp.asarray(q_offset, jnp.float32)
    kv_valid_f = jnp.asarray(Sk if kv_valid is None else kv_valid,
                             jnp.float32)
    if layer is None:
        out = _flash(cfgt, qg, k, v, q_off_f, kv_valid_f)
    else:
        out, _ = _flash_fwd_impl(cfgt, qg, k, v, q_off_f, kv_valid_f, layer)
    return out.reshape(B, nq * q_chunk, Hq, Dh)[:, :Sq].astype(q.dtype)


@jax.named_scope(QKV)
def _qkv(cfg, p, x, src):
    B, S, _ = x.shape
    dt = cfg.jdtype
    q = x @ p["wq"].astype(dt)
    k = src @ p["wk"].astype(dt)
    v = src @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    Sk = src.shape[1]
    # shard heads over 'model' when divisible; otherwise context-parallel:
    # shard the sequence dim (attention semantics are position-global, so
    # GSPMD handles the halo/all-gather)
    q = constrain_any(q.reshape(B, S, cfg.n_heads, cfg.d_head),
                      [("batch", None, "heads", None),
                       ("batch", "act_seq", None, None)])
    k = constrain_any(k.reshape(B, Sk, cfg.n_kv_heads, cfg.d_head),
                      [("batch", None, "kv", None),
                       ("batch", "act_seq", None, None)])
    v = constrain_any(v.reshape(B, Sk, cfg.n_kv_heads, cfg.d_head),
                      [("batch", None, "kv", None),
                       ("batch", "act_seq", None, None)])
    return q, k, v


def attention_block(cfg, p: Params, x, positions, *, cache=None,
                    layer=None, causal=True, window=0, kv_from=None):
    """Full attention block; returns (out, new_cache).

    cache: a group's stacked ``kv_cache``; the block is layer ``layer`` of
    it.  Only the new roped K/V rows are written into the stack, and
    attention reads its layer's chunks of slots where they lie.  Keys are
    stored *already roped* at their absolute positions, so a ring cache
    (local attention over the last ``window`` tokens) needs no re-rope.
    kv_from: cross-attention memory (B, Sm, d) — non-causal, no cache.
    """
    B, S, _ = x.shape
    dt = cfg.jdtype
    q, k, v = _qkv(cfg, p, x, x if kv_from is None else kv_from)

    if kv_from is not None:
        out = flash_attention(q, k, v, causal=False)
        return out.reshape(B, S, cfg.q_dim) @ p["wo"].astype(dt), None

    new_cache = None
    if cache is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        out = flash_attention(q, k, v, causal=causal, window=window)
    else:
        idx = kv_cache.fill(cache, layer)
        qpos = idx + jnp.arange(S)[None, :].repeat(B, 0)
        q = rope(q, qpos, cfg.rope_theta)
        k = rope(k, qpos, cfg.rope_theta)
        ring = kv_cache.is_ring(cache, window)
        prefill_ring = ring and S > 1
        if prefill_ring:
            # windowed prefill: attend without the cache, which keeps only
            # the last `window` rows
            out = flash_attention(q, k, v, causal=True, window=window,
                                  q_offset=0)
        new_cache = kv_cache.write(cache, layer, idx, k, v, window)
        if not prefill_ring:
            ck, cv = kv_cache.kv(new_cache)
            if ring:
                out = flash_attention(
                    q, ck, cv, causal=False,
                    kv_valid=kv_cache.filled(idx, S, window), layer=layer)
            else:
                out = flash_attention(q, ck, cv, causal=True, window=window,
                                      q_offset=idx,
                                      kv_valid=kv_cache.filled(idx, S),
                                      layer=layer)
    out = out.reshape(B, S, cfg.q_dim)
    with jax.named_scope(ATTN_OUT):
        out = out @ p["wo"].astype(dt)
    return out, new_cache


def cross_attention_cached(cfg, p: Params, x, ck, cv, layer):
    """Cross-attention against layer ``layer`` of the stacked memory K/V
    (``kv_cache.memory``), as ``cross_kv`` gives them per layer."""
    B, S, _ = x.shape
    dt = cfg.jdtype
    q = x @ p["wq"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
    q = q.reshape(B, S, cfg.n_heads, cfg.d_head)
    out = flash_attention(q, ck, cv, causal=False, layer=layer)
    return out.reshape(B, S, cfg.q_dim) @ p["wo"].astype(dt)


def cross_kv(cfg, p: Params, memory):
    """A layer's memory K/V, in the order ``kv_cache`` holds them."""
    dt = cfg.jdtype
    B, Sm, _ = memory.shape
    k = memory @ p["wk"].astype(dt)
    v = memory @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return tuple(kv_cache.heads_first(
        a.reshape(B, Sm, cfg.n_kv_heads, cfg.d_head)) for a in (k, v))


# ---------------------------------------------------------------------------
# MLP (SwiGLU) and MoE
# ---------------------------------------------------------------------------

def mlp_params(cfg, key) -> Tuple[Params, Specs]:
    ks = jax.random.split(key, 3)
    dt = cfg.jparam_dtype
    p = {
        "wg": _init(ks[0], (cfg.d_model, cfg.d_ff), dt),
        "wu": _init(ks[1], (cfg.d_model, cfg.d_ff), dt),
        "wd": _init(ks[2], (cfg.d_ff, cfg.d_model), dt,
                    scale=1.0 / math.sqrt(cfg.d_ff)),
    }
    s = {"wg": ("embed", "mlp"), "wu": ("embed", "mlp"),
         "wd": ("mlp", "embed")}
    return p, s


@jax.named_scope(MLP)
def mlp(cfg, p: Params, x):
    dt = cfg.jdtype
    g = jax.nn.silu(constrain(x @ p["wg"].astype(dt),
                              ("batch", None, "mlp")))
    u = constrain(x @ p["wu"].astype(dt), ("batch", None, "mlp"))
    return constrain((g * u) @ p["wd"].astype(dt), ("batch", None, None))


def moe_params(cfg, key) -> Tuple[Params, Specs]:
    ks = jax.random.split(key, 4)
    dt = cfg.jparam_dtype
    E = cfg.n_experts
    p = {
        "router": _init(ks[0], (cfg.d_model, E), dt),
        "wg": _init(ks[1], (E, cfg.d_model, cfg.d_ff), dt),
        "wu": _init(ks[2], (E, cfg.d_model, cfg.d_ff), dt),
        "wd": _init(ks[3], (E, cfg.d_ff, cfg.d_model), dt,
                    scale=1.0 / math.sqrt(cfg.d_ff)),
    }
    s = {"router": ("embed", "expert"),
         "wg": ("expert", "embed", "mlp"),
         "wu": ("expert", "embed", "mlp"),
         "wd": ("expert", "mlp", "embed")}
    return p, s


@jax.named_scope(MLP)
def moe(cfg, p: Params, x, rng: Optional[jax.Array] = None):
    """Top-k token-choice MoE with fixed expert capacity (dropping).

    Returns (out, aux_loss).  Dispatch/combine are scatter/gather based so
    shapes stay static under jit; experts shard over the 'expert' logical
    axis (expert parallelism).
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    xt = x.reshape(T, D)
    dt = cfg.jdtype
    logits = (xt @ p["router"].astype(jnp.float32).astype(dt)
              ).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = lax.top_k(probs, K)  # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch-style)
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,)).at[expert_idx.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)

    capacity = int(max(1, math.ceil(T * K * cfg.capacity_factor / E)))
    flat_expert = expert_idx.reshape(-1)  # (T*K,)
    # position of each (token, k) within its expert's queue
    onehot = jax.nn.one_hot(flat_expert, E, dtype=jnp.int32)  # (T*K, E)
    pos_in_expert = (jnp.cumsum(onehot, axis=0) - onehot)
    pos = jnp.take_along_axis(
        pos_in_expert, flat_expert[:, None], axis=1)[:, 0]  # (T*K,)
    keep = pos < capacity
    slot = jnp.where(keep, pos, capacity)  # overflow -> scratch slot

    # dispatch: (E, capacity+1, D); scratch row absorbs dropped tokens
    buf = jnp.zeros((E, capacity + 1, D), dt)
    tok_idx = jnp.repeat(jnp.arange(T), K)
    buf = buf.at[flat_expert, slot].add(xt[tok_idx].astype(dt))
    buf = constrain(buf, ("expert", None, None))

    h = jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(dt))
    h = jax.nn.silu(h)
    u = jnp.einsum("ecd,edf->ecf", buf, p["wu"].astype(dt))
    y = jnp.einsum("ecf,efd->ecd", h * u, p["wd"].astype(dt))

    # combine
    gathered = y[flat_expert, slot]  # (T*K, D)
    w = (gate_vals.reshape(-1) * keep).astype(dt)
    out = jnp.zeros((T, D), dt).at[tok_idx].add(gathered * w[:, None])
    return out.reshape(B, S, D), aux


def embedding_params(cfg, key) -> Tuple[Params, Specs]:
    dt = cfg.jparam_dtype
    p = {"tok": _init(key, (cfg.vocab, cfg.d_model), dt, scale=1.0)}
    return p, {"tok": ("vocab", "embed")}
