"""The stacked cache is updated in place: each layer of the scan writes its
new K/V rows (or its recurrent state) into the group's stacked cache, which
rides in the scan's carry.

Two checks per architecture, over a prefill and three decode steps:

* against a reference that threads per-layer caches through ``lax.scan``
  as inputs and outputs (each layer reads its slice and hands back a new
  one, which the scan stacks into a second cache): every logit and every
  cache leaf agrees;
* against the no-cache ``forward`` over the grown sequence: the decode
  logits agree as closely as the per-layer-scan program's did: exactly,
  but for the recurrences of mamba2 and of a deeper recurrentgemma, whose
  step form differs from the parallel form by a bf16 ulp of the logits.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.configs import ARCHS, get_config
from repro.models import lm

B, S, STEPS = 2, 72, 3  # S exceeds the smoke window (64) and is no multiple
# (arch, config overrides, the widest gap between decode and the no-cache
# forward that the per-layer-scan program showed on this data)
CASES = [(arch, {}, 0.25 if arch == "mamba2_130m" else 0.0)
         for arch in ARCHS] + [
    ("phi3_mini_3_8b", {"unroll_layers": True}, 0.0),
    ("phi3_mini_3_8b", {"remat": True}, 0.0),
    # two scanned (rglru, rglru, wattn) groups and a trailing rglru layer
    ("recurrentgemma_2b", {"n_layers": 7}, 0.25),
]


def _per_layer_scan_group(cfg, kinds, count, group_params, x, positions,
                          caches=None, enc_out=None):
    """Each layer's cache slice is a scan input; its new slice an output
    (or, for one layer or unrolled layers, sliced out and stacked back)."""
    def body(carry, per_layer):
        x, aux = carry
        layer_params, layer_cache = per_layer
        new = []
        for ki, kind in enumerate(kinds):
            c = None if layer_cache is None else layer_cache[ki]
            one = None if c is None else jax.tree.map(lambda a: a[None], c)
            x, nc, a = lm._layer_apply(cfg, kind, layer_params[ki], x,
                                       positions, cache=one, layer=0,
                                       enc_out=enc_out)
            new.append(None if nc is None
                       else jax.tree.map(lambda a: a[0], nc))
            aux = aux + a
        return (x, aux), (tuple(new) if layer_cache is not None else None)

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    carry = (x, jnp.zeros((), jnp.float32))
    if count == 1 or cfg.unroll_layers:
        slices = []
        for i in range(count):
            lp, lc = jax.tree.map(lambda a: a[i], (group_params, caches))
            carry, nc = body(carry, (lp, lc))
            slices.append(nc)
        new_caches = (None if caches is None else
                      jax.tree.map(lambda *xs: jnp.stack(xs), *slices))
    else:
        carry, new_caches = lax.scan(body, carry, (group_params, caches))
    x, aux = carry
    return x, new_caches, aux


def _config(arch, overrides):
    cfg = dataclasses.replace(get_config(arch, smoke=True), **overrides)
    if cfg.n_experts:  # no token is dropped, in one step as in S of them
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
    return cfg


def _serve(cfg, params, batch, fed):
    """Logits of the prefill and each decode step, and the cache after
    each; jitted, so that the program is compiled as it is served."""
    extra = batch["embeds"].shape[1] if "embeds" in batch else 0
    cache = lm.init_cache(cfg, B, S + extra + STEPS)
    last, cache = jax.jit(lambda p, b, c: lm.prefill(cfg, p, b, c))(
        params, batch, cache)
    logits, caches = [last], [cache]
    decode = jax.jit(lambda p, t, c: lm.decode_step(cfg, p, t, c))
    for t in range(STEPS):
        out, cache = decode(params, fed[:, t:t + 1], cache)
        logits.append(out)
        caches.append(cache)
    return logits, caches


@pytest.mark.parametrize("arch,overrides,forward_gap", CASES,
                         ids=[a + "".join(f"-{k}={v}" for k, v in o.items())
                              for a, o, _ in CASES])
def test_stacked_cache_matches_per_layer_scan(arch, overrides, forward_gap,
                                              monkeypatch):
    cfg = _config(arch, overrides)
    rng = np.random.default_rng(2)
    params, _ = lm.init(cfg, jax.random.PRNGKey(2))
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    fed = jnp.asarray(rng.integers(0, cfg.vocab, (B, STEPS)), jnp.int32)
    batch = {"tokens": tokens}
    if cfg.family == "vlm":
        batch["embeds"] = jnp.asarray(
            rng.normal(size=(B, 8, cfg.frontend_dim)), jnp.float32)
    if cfg.family == "audio":
        batch["enc_frames"] = jnp.asarray(
            rng.normal(size=(B, S, cfg.frontend_dim)), jnp.float32)

    logits, caches = _serve(cfg, params, batch, fed)
    with monkeypatch.context() as m:
        m.setattr(lm, "_apply_group", _per_layer_scan_group)
        ref_logits, ref_caches = _serve(cfg, params, batch, fed)

    for got, want in zip(logits, ref_logits):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=1e-6)
    for got, want in zip(caches, ref_caches):
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert g.shape == w.shape and g.dtype == w.dtype
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(w, np.float32),
                                       rtol=0, atol=1e-6)

    forward = jax.jit(lambda p, t: lm.forward(
        cfg, p, t, embeds=batch.get("embeds"),
        enc_frames=batch.get("enc_frames"))[0][:, -1])
    for t, got in enumerate(logits):
        want = forward(params, jnp.concatenate([tokens, fed[:, :t]], 1))
        gap = float(jnp.abs(got - want).max())
        assert gap <= forward_gap, (t, gap)

