"""Model assembly: a uniform functional API over every assigned family.

  init(cfg, key)                 -> (params, specs)
  abstract_init(cfg, key)        -> their shapes and specs, unallocated
  loss_fn(cfg, params, batch)    -> (loss, aux)        (train shapes)
  prefill(cfg, params, batch, cache) -> (last_logits, cache)
  decode_step(cfg, params, tok, cache) -> (logits, cache)
  init_cache(cfg, batch, max_len) -> cache pytree (K/V: ``kv_cache``)

Layer stacks are ``lax.scan``'d over stacked parameters (keeps HLO small so
the 512-device dry-run compiles fast and collective parsing can scale scan
bodies by trip count).  ``cfg.remat`` wraps the scan body in jax.checkpoint.

Families:
  dense  — qwen1.5-0.5b, minitron-8b, yi-34b, phi3-mini: GQA + SwiGLU
  moe    — phi3.5-moe, llama4-scout: dense attention + top-k expert MLP
  ssm    — mamba2-130m: attention-free SSD blocks
  hybrid — recurrentgemma-2b: RG-LRU blocks + local attention (1:2 pattern)
  vlm    — llava-next-34b: dense backbone; patch-embedding frontend stub
  audio  — seamless-m4t-medium: encoder-decoder; frame-embedding frontend
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed.sharding import constrain
from repro.obs.serving import EMBED, LAYERS, LM_HEAD

from . import kv_cache
from .config import ModelConfig
from .layers import (_init, attention_block, attention_params,
                     cross_attention_cached, cross_kv, embedding_params, mlp,
                     mlp_params, moe, moe_params, rmsnorm, rmsnorm_params)
from .rglru import init_rglru_state, rglru_block, rglru_params
from .ssm import init_ssm_state, ssm_block, ssm_params

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# layer kinds: 'attn' (causal), 'enc' (non-causal), 'wattn' (local window),
# 'xattn' (causal self + cross), 'ssm', 'rglru'
# ---------------------------------------------------------------------------

def _layer_params(cfg: ModelConfig, kind: str, key):
    k1, k2, k3, _ = jax.random.split(key, 4)
    p: Params = {"ln1": rmsnorm_params(cfg.d_model, cfg.jparam_dtype)[0]}
    s: Params = {"ln1": rmsnorm_params(cfg.d_model, cfg.jparam_dtype)[1]}
    if kind in ("attn", "enc", "wattn", "xattn"):
        p["attn"], s["attn"] = attention_params(cfg, k1)
        if kind == "xattn":
            p["cross"], s["cross"] = attention_params(cfg, k3)
            p["ln_cross"], s["ln_cross"] = rmsnorm_params(
                cfg.d_model, cfg.jparam_dtype)
    elif kind == "ssm":
        p["ssm"], s["ssm"] = ssm_params(cfg, k1)
    elif kind == "rglru":
        p["rglru"], s["rglru"] = rglru_params(cfg, k1)
    else:
        raise ValueError(kind)
    if kind != "ssm":
        p["ln2"], s["ln2"] = rmsnorm_params(cfg.d_model, cfg.jparam_dtype)
        if cfg.n_experts and kind == "attn":
            p["moe"], s["moe"] = moe_params(cfg, k2)
        else:
            p["mlp"], s["mlp"] = mlp_params(cfg, k2)
    return p, s


def _at(tree, i):
    """Layer ``i`` of a stacked cache entry."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False), tree)


def _put(tree, new, i):
    """``tree`` with layer ``i`` of each stacked leaf replaced by ``new``."""
    return jax.tree.map(
        lambda a, n: lax.dynamic_update_index_in_dim(a, n, i, 0), tree, new)


def _layer_apply(cfg: ModelConfig, kind: str, p: Params, x, positions,
                 cache=None, layer=None, enc_out=None):
    """One block; returns (x, new_cache, aux).

    ``cache`` is the kind's stacked cache (leading dim: the group's
    layers) and the block is its layer ``layer``: the block writes its
    new K/V rows or its recurrent state into the stack and returns it."""
    aux = jnp.zeros((), jnp.float32)
    # sequence parallelism on the residual stream: the per-layer activation
    # checkpoint (scan carry) shards its sequence dim over 'model'
    x = constrain(x, ("batch", "act_seq", None))
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    new_cache = cache
    if kind in ("attn", "enc", "wattn", "xattn"):
        win = cfg.window if kind == "wattn" else 0
        a, nc = attention_block(
            cfg, p["attn"], h, positions,
            cache=None if cache is None else cache["attn"], layer=layer,
            causal=(kind != "enc"), window=win)
        if cache is not None:
            new_cache = dict(cache, attn=nc)
        x = x + a
        if kind == "xattn":
            hc = rmsnorm(p["ln_cross"], x, cfg.norm_eps)
            memory = None if cache is None else kv_cache.memory(cache)
            if memory is not None:
                a2 = cross_attention_cached(cfg, p["cross"], hc, *memory,
                                            layer)
            else:
                assert enc_out is not None
                a2, _ = attention_block(cfg, p["cross"], hc, positions,
                                        kv_from=enc_out)
            x = x + a2
    elif kind in ("ssm", "rglru"):
        block = ssm_block if kind == "ssm" else rglru_block
        a, st = block(cfg, p[kind],
                      h, None if cache is None else _at(cache[kind], layer))
        if cache is not None:
            new_cache = dict(cache, **{kind: _put(cache[kind], st, layer)})
        x = x + a
        if kind == "ssm":
            return x, new_cache, aux
    else:
        raise ValueError(kind)

    x = constrain(x, ("batch", "act_seq", None))
    h = rmsnorm(p["ln2"], x, cfg.norm_eps)
    if "moe" in p:
        m, a_moe = moe(cfg, p["moe"], h)
        aux = aux + a_moe.astype(jnp.float32)
    else:
        m = mlp(cfg, p["mlp"], h)
    return constrain(x + m, ("batch", "act_seq", None)), new_cache, aux


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------

def layer_pattern(cfg: ModelConfig) -> Tuple[str, ...]:
    if cfg.family == "ssm":
        return ("ssm",) * cfg.n_layers
    if cfg.family == "hybrid":
        pat = cfg.block_pattern or ("rglru", "rglru", "wattn")
        full = pat * ((cfg.n_layers + len(pat) - 1) // len(pat))
        return full[:cfg.n_layers]
    if cfg.family == "audio":
        return ("enc",) * cfg.enc_layers + ("xattn",) * cfg.dec_layers
    return ("attn",) * cfg.n_layers


def _stack_groups(cfg: ModelConfig) -> List[Tuple[Tuple[str, ...], int]]:
    pat = layer_pattern(cfg)
    if cfg.family == "hybrid":
        base = cfg.block_pattern or ("rglru", "rglru", "wattn")
        n_groups = cfg.n_layers // len(base)
        out: List[Tuple[Tuple[str, ...], int]] = []
        if n_groups:
            out.append((tuple(base), n_groups))
        for kind in pat[n_groups * len(base):]:
            out.append(((kind,), 1))
        return out
    if cfg.family == "audio":
        return [(("enc",), cfg.enc_layers), (("xattn",), cfg.dec_layers)]
    return [((pat[0],), cfg.n_layers)]


def init(cfg: ModelConfig, key) -> Tuple[Params, Params]:
    keys = jax.random.split(key, 8)
    params: Params = {}
    specs: Params = {}
    params["embed"], specs["embed"] = embedding_params(cfg, keys[0])
    params["final_norm"], specs["final_norm"] = rmsnorm_params(
        cfg.d_model, cfg.jparam_dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = _init(keys[1], (cfg.d_model, cfg.vocab),
                                  cfg.jparam_dtype)
        specs["lm_head"] = ("embed", "vocab")
    if cfg.frontend != "none":
        params["frontend_proj"] = _init(
            keys[2], (cfg.frontend_dim, cfg.d_model), cfg.jparam_dtype)
        specs["frontend_proj"] = (None, "embed")

    params["groups"] = []
    specs["groups"] = []
    gkey = keys[3]
    for kinds, count in _stack_groups(cfg):
        gkey, sub = jax.random.split(gkey)
        lkeys = jax.random.split(sub, count * len(kinds)).reshape(
            count, len(kinds), 2)
        per_kind_p = []
        per_kind_s = []
        for ki, kind in enumerate(kinds):
            ps = [_layer_params(cfg, kind, lkeys[c, ki])
                  for c in range(count)]
            per_kind_p.append(
                jax.tree.map(lambda *xs: jnp.stack(xs), *[p for p, _ in ps]))
            per_kind_s.append(jax.tree.map(
                lambda spec: ("layers",) + tuple(spec), ps[0][1],
                is_leaf=lambda x: isinstance(x, tuple)))
        # lists (not tuples): several tree transforms use is_leaf=tuple-of-
        # names or tuple-of-outputs predicates that must not match containers
        params["groups"].append(list(per_kind_p))
        specs["groups"].append(list(per_kind_s))
    return params, specs


def abstract_init(cfg: ModelConfig, key) -> Tuple[Params, Params]:
    """:func:`init`'s shapes and specs, with nothing allocated (specs are
    trace-static)."""
    specs_holder = {}

    def run(k):
        p, s = init(cfg, k)
        specs_holder["specs"] = s
        return p

    shapes = jax.eval_shape(run, key)
    return shapes, specs_holder["specs"]


def _apply_group(cfg, kinds, count, group_params, x, positions,
                 caches=None, enc_out=None):
    """Run a group's stacked layers.  The group's stacked caches ride in
    the carry, so each layer writes only its new rows into them and XLA
    updates them in place; a scan over (params, layer index) alone."""
    def body(carry, per_layer):
        x, aux, caches = carry
        layer_params, i = per_layer
        new_caches = []
        for ki, kind in enumerate(kinds):
            c = None if caches is None else caches[ki]
            x, nc, a = _layer_apply(cfg, kind, layer_params[ki], x,
                                    positions, cache=c, layer=i,
                                    enc_out=enc_out)
            new_caches.append(nc)
            aux = aux + a
        out_caches = tuple(new_caches) if caches is not None else None
        return (x, aux, out_caches), None

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)

    # the stack of layers, however it is run
    with jax.named_scope(LAYERS):
        carry = (x, jnp.zeros((), jnp.float32), caches)
        if count == 1 or cfg.unroll_layers:
            for i in range(count):
                lp = jax.tree.map(lambda a: a[i], group_params)
                carry, _ = body(carry, (lp, i))
        else:
            layers = None if caches is None else jnp.arange(count)
            carry, _ = lax.scan(body, carry, (group_params, layers))
        x, aux, caches = carry
        return x, caches, aux


@jax.named_scope(EMBED)
def _embed(cfg, params, tokens):
    e = params["embed"]["tok"].astype(cfg.jdtype)[tokens]
    return constrain(e * math.sqrt(cfg.d_model), ("batch", "act_seq", None))


def _head(cfg, params, x):
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        w = params["embed"]["tok"].astype(cfg.jdtype).T
    else:
        w = params["lm_head"].astype(cfg.jdtype)
    with jax.named_scope(LM_HEAD):
        logits = (x @ w).astype(jnp.float32)
    return constrain(logits, ("batch", "act_seq", "vocab"))


def _encoder_out(cfg, params, enc_frames, caches=None):
    B = enc_frames.shape[0]
    fe = (enc_frames.astype(cfg.jdtype)
          @ params["frontend_proj"].astype(cfg.jdtype))
    pos = jnp.arange(fe.shape[1])[None, :].repeat(B, 0)
    kinds, count = _stack_groups(cfg)[0]
    enc_x, _, _ = _apply_group(cfg, kinds, count, params["groups"][0],
                               fe, pos)
    return enc_x


def forward(cfg: ModelConfig, params: Params, tokens, *,
            embeds=None, enc_frames=None, caches=None, positions=None):
    """Returns (logits, new_caches, aux)."""
    x = _embed(cfg, params, tokens)
    B = x.shape[0]
    if cfg.family == "vlm" and embeds is not None:
        fe = (embeds.astype(cfg.jdtype)
              @ params["frontend_proj"].astype(cfg.jdtype))
        x = jnp.concatenate([fe, x], axis=1)
    S = x.shape[1]
    if positions is None:
        positions = jnp.arange(S)[None, :].repeat(B, 0)

    groups = _stack_groups(cfg)
    enc_out = None
    gidx = 0
    if cfg.family == "audio":
        gidx = 1
        if enc_frames is not None:
            enc_out = _encoder_out(cfg, params, enc_frames)
        # else: decoding — cross K/V come from the cache

    aux = jnp.zeros((), jnp.float32)
    new_caches = [None] * len(groups)
    for gi in range(gidx, len(groups)):
        kinds, count = groups[gi]
        cache_g = None if caches is None else caches["groups"][gi]
        x, nc, a = _apply_group(cfg, kinds, count, params["groups"][gi],
                                x, positions, caches=cache_g,
                                enc_out=enc_out)
        aux = aux + a
        new_caches[gi] = nc

    logits = _head(cfg, params, x)
    out_caches = None
    if caches is not None:
        out_caches = dict(caches)
        out_caches["groups"] = new_caches
        if gidx == 1:
            out_caches["groups"][0] = caches["groups"][0]
    return logits, out_caches, aux


# ---------------------------------------------------------------------------
# training loss
# ---------------------------------------------------------------------------

def loss_fn(cfg: ModelConfig, params: Params, batch) -> Tuple[jnp.ndarray, Dict]:
    """batch: dict(tokens=(B,S), labels=(B,S) [, embeds / enc_frames])."""
    logits, _, aux = forward(
        cfg, params, batch["tokens"],
        embeds=batch.get("embeds"), enc_frames=batch.get("enc_frames"))
    labels = batch["labels"]
    V = logits.shape[-1]
    if logits.shape[1] != labels.shape[1]:  # vlm: loss on text tail only
        logits = logits[:, -labels.shape[1]:]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    nll = (lse - gold) * mask
    loss = nll.sum() / jnp.maximum(mask.sum(), 1.0)
    total = loss + 0.01 * aux
    return total, {"ce": loss, "aux": aux}


# ---------------------------------------------------------------------------
# serving: caches, prefill, decode
# ---------------------------------------------------------------------------

def _group_cache(cfg: ModelConfig, kind: str, count: int, batch: int,
                 max_len: int):
    """The stacked cache of a group's ``count`` layers of one kind."""
    if kind in ("attn", "xattn", "wattn"):
        slots = (min(cfg.window or max_len, max_len) if kind == "wattn"
                 else max_len)
        c = {"attn": kv_cache.init(cfg, count, batch, slots)}
        if kind == "xattn":
            c.update(kv_cache.init_memory(cfg, count, batch, max_len))
        return c
    if kind in ("ssm", "rglru"):
        state = (init_ssm_state if kind == "ssm" else init_rglru_state)(
            cfg, batch)
        return {kind: jax.tree.map(
            lambda a: jnp.broadcast_to(a, (count,) + a.shape).copy(), state)}
    if kind == "enc":
        return None
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    groups = []
    for kinds, count in _stack_groups(cfg):
        per_kind = tuple(_group_cache(cfg, kind, count, batch, max_len)
                         for kind in kinds)
        groups.append(per_kind if any(
            c is not None for c in per_kind) else None)
    return {"groups": groups, "pos": jnp.zeros((), jnp.int32)}


def prefill(cfg: ModelConfig, params: Params, batch, cache):
    """Returns (last_token_logits, cache)."""
    tokens = batch["tokens"]
    if cfg.family == "audio":
        # encode once, cache cross-attention K/V, then prefill the decoder
        enc_out = _encoder_out(cfg, params, batch["enc_frames"])
        dec_group = 1
        kinds, count = _stack_groups(cfg)[dec_group]
        gp = params["groups"][dec_group]

        def fill(layer_params):
            return cross_kv(cfg, layer_params[0]["cross"], enc_out)

        xks, xvs = lax.map(fill, gp)
        cg = kv_cache.with_memory(cache["groups"][dec_group][0], xks, xvs)
        cache = dict(cache)
        cache["groups"] = list(cache["groups"])
        cache["groups"][dec_group] = (cg,)
        # cross K/V are now cached; skip re-encoding inside forward
        logits, cache, _ = forward(cfg, params, tokens, caches=cache)
    else:
        logits, cache, _ = forward(
            cfg, params, tokens, embeds=batch.get("embeds"), caches=cache)
    s_total = tokens.shape[1]
    if cfg.family == "vlm" and batch.get("embeds") is not None:
        s_total += batch["embeds"].shape[1]
    cache["pos"] = cache["pos"] + s_total
    return logits[:, -1], cache


def decode_step(cfg: ModelConfig, params: Params, tok, cache):
    """tok: (B, 1) int32.  Returns (logits (B, vocab), cache)."""
    pos = cache["pos"]
    B = tok.shape[0]
    positions = pos + jnp.zeros((B, 1), jnp.int32)
    logits, cache, _ = forward(cfg, params, tok, caches=cache,
                               positions=positions)
    cache["pos"] = pos + 1
    return logits[:, -1], cache
