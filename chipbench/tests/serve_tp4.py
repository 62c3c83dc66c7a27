"""Serve ``data/smoke-gqa-tp4.json`` on four devices and on one, and hold
both to the float32 reference; prints one JSON line of readings.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 chipbench/tests/serve_tp4.py

A process of its own, because the device count is fixed before JAX starts
(``test_chipbench_tp4.py`` runs it).  Prefill and then ``G`` greedy decode
steps go through ``make_serve_steps`` on a (1, 4) mesh, the KV heads
sharded two to a device, as the four-chip cell serves Yi-34B:

- ``tp_vs_ref``, ``one_vs_ref``, ``tp_vs_one``: float32 logits of four
  devices and of one (the same weights, the same fed tokens) against the
  reference and each other, as a share of the reference's largest logit;
- ``fault_vs_ref``: four devices serving ``wk`` with the columns of KV
  heads 1 and 2 (on devices 0 and 1) swapped, against the reference of
  the sound weights;
- ``bf16_max_gap``: ``check.compare`` on a bfloat16 run, as a cell's
  ``correct`` reads it;
- ``collectives``: ``repro.obs.serving.collectives`` of the four-device
  decode step, and ``cache_spec``, the cache's sharding.
"""
from __future__ import annotations

import json
import sys
from dataclasses import replace
from functools import partial
from pathlib import Path
from typing import Any, Callable, NamedTuple

import smoke_root  # noqa: F401  (puts the checkout on the path)

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import bench, check, reference, weights
from chipbench.cell import _abstract_params

B, P, G = 2, 12, 5
SEED = 2**31 + 15
DATA = Path(__file__).resolve().parent / "data" / "smoke-gqa-tp4.json"
SWAPPED = (1, 2)  # KV heads on devices 0 and 1


class Steps(NamedTuple):
    """The program's served steps on one mesh, and what feeds them."""

    params_abs: Any
    param_sh: Any
    prefill: Callable
    decode: Callable
    init_cache: Callable  # jitted, placed with the cache's shardings
    tok_sh: Any


def _steps(cfg, devices, model) -> Steps:
    from repro.launch.mesh import make_elastic_mesh
    from repro.models import lm
    from repro.serving.engine import make_serve_steps

    mesh = make_elastic_mesh(model, devices=devices)
    init_cache = partial(lm.init_cache, cfg, B, P + G)
    batch_abs = {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32)}
    params_abs, specs = _abstract_params(cfg)
    prefill, decode, (param_sh, batch_sh, cache_sh, tok_sh) = \
        make_serve_steps(cfg, mesh, specs, jax.eval_shape(init_cache),
                         batch_abs)
    return Steps(params_abs, param_sh, prefill, decode,
                 jax.jit(init_cache, out_shardings=cache_sh), tok_sh)


def _serve(steps: Steps, params, prompts, fed=None):
    """Logits (B, G+1, vocab) of the prefill and G decode steps, fed
    ``fed`` (B, G) or, without it, their own greedy tokens."""
    last, cache = steps.prefill(params, {"tokens": prompts},
                                steps.init_cache())
    logits = [last]
    for t in range(G):
        tok = (jnp.argmax(logits[-1], -1)[:, None] if fed is None
               else fed[:, t:t + 1]).astype(jnp.int32)
        out, cache = steps.decode(params, jax.device_put(tok, steps.tok_sh),
                                  cache)
        logits.append(out)
    return jnp.stack(logits, 1)


def _rel(a, ref) -> float:
    a, ref = np.asarray(a), np.asarray(ref)
    return float(np.abs(a - ref).max() / np.abs(ref).max())


def main() -> int:
    config = json.loads(DATA.read_text())
    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"needs 4 devices (XLA_FLAGS="
                         f"--xla_force_host_platform_device_count=4), "
                         f"found {len(devices)}")
    served = bench.model_config(config)
    cfg = replace(served, dtype="float32", param_dtype="float32")
    shape = reference.Shape.from_config(config)
    prompts = jnp.asarray(np.random.default_rng(SEED % 2**32).integers(
        0, cfg.vocab, (B, P)), jnp.int32)

    tp = _steps(cfg, devices, config["mesh"]["model"])
    params = weights.make(tp.params_abs, tp.param_sh, SEED, cfg.d_model)
    logits = _serve(tp, params, prompts)
    fed = jnp.argmax(logits, -1)[:, :G]
    seqs = np.concatenate([np.asarray(prompts), np.asarray(fed)], 1)
    view = weights.ReferenceView(params, cfg.d_model, devices[0])
    ref = reference.logits(view, shape, seqs, P - 1)

    one = _steps(cfg, devices[:1], 1)
    one_logits = _serve(one, jax.device_put(params, one.param_sh), prompts,
                        fed)

    wk = params["groups"][0][0]["attn"]["wk"]
    cols = wk.reshape(*wk.shape[:-1], config["num_key_value_heads"], -1)
    a, b = SWAPPED
    cols = cols.at[..., [a, b], :].set(cols[..., [b, a], :])
    faulty = jax.tree.map(lambda x: x, params)  # new containers, same leaves
    faulty["groups"][0][0]["attn"]["wk"] = jax.device_put(
        cols.reshape(wk.shape), wk.sharding)
    fault_logits = _serve(tp, faulty, prompts, fed)

    low = _steps(served, devices, config["mesh"]["model"])
    low_params = weights.make(low.params_abs, low.param_sh, SEED,
                              cfg.d_model)
    low_logits = _serve(low, low_params, prompts)
    low_served = np.asarray(jnp.argmax(low_logits, -1))
    low_seqs = np.concatenate([np.asarray(prompts), low_served[:, :-1]], 1)
    gap = check.compare(weights.ReferenceView(low_params, cfg.d_model,
                                              devices[0]),
                        shape, low_seqs, low_served, block=2)["max_gap"]

    from repro.obs.serving import collectives

    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=tp.tok_sh)
    text = tp.decode.lower(params, tok, jax.eval_shape(tp.init_cache)
                           ).compile().as_text()
    k = tp.init_cache()["groups"][0][0]["attn"]["k"]
    print(json.dumps({
        "tp_vs_ref": _rel(logits, ref), "one_vs_ref": _rel(one_logits, ref),
        "tp_vs_one": _rel(logits, one_logits),
        "fault_vs_ref": _rel(fault_logits, ref), "bf16_max_gap": gap,
        "collectives": collectives(text),
        "cache_shape": list(k.shape), "cache_spec": list(k.sharding.spec),
        "d_model": cfg.d_model, "layers": cfg.n_layers, "batch": B}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
