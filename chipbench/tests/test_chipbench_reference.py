"""The float32 reference against the program's served path, prefill and
then decode through the cache, at smoke size on the CPU."""
from __future__ import annotations

import json
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import smoke_root
from chipbench import bench, check, weights

B, P, G = 2, 12, 5
dense = bench.family("dense")


def _served(dtype, seed=3):
    """Logits the program serves at each position, its greedy tokens, the
    prompts and the published-form view of its weights."""
    from repro.launch.mesh import make_elastic_mesh
    from repro.models import lm
    from repro.serving.engine import make_serve_steps
    from chipbench.cell import _abstract_params

    config = json.loads((smoke_root.DATA / "smoke-dense.json").read_text())
    cfg = replace(bench.model_config(config, dense), dtype=dtype,
                  param_dtype=dtype)
    mesh = make_elastic_mesh(1, devices=jax.devices()[:1])
    init_cache = partial(lm.init_cache, cfg, B, P + G)
    batch = {"tokens": jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab, (B, P)), jnp.int32)}
    params_abs, specs = _abstract_params(cfg)
    prefill, decode, (param_sh, *_) = make_serve_steps(
        cfg, mesh, specs, jax.eval_shape(init_cache), batch)
    params = weights.make(params_abs, param_sh, seed, cfg.d_model,
                          dense.WEIGHTS)
    last, cache = prefill(params, batch, init_cache())
    logits = [last]
    for _ in range(G):
        tok = jnp.argmax(logits[-1], -1)[:, None].astype(jnp.int32)
        out, cache = decode(params, tok, cache)
        logits.append(out)
    logits = jnp.stack(logits, 1)  # (B, G+1, vocab)
    served = np.asarray(jnp.argmax(logits, -1))
    view = dense.ReferenceView(params, cfg.d_model, jax.devices()[0])
    return config, logits, served, np.asarray(batch["tokens"]), view


@pytest.fixture(scope="module")
def f32():
    return _served("float32")


def test_reference_matches_float32_prefill_and_decode(f32):
    config, logits, served, prompts, view = f32
    seqs = np.concatenate([prompts, served[:, :-1]], 1)
    ref = dense.logits(view, dense.Shape.from_config(config), seqs, P - 1)
    err = float(jnp.abs(ref - logits).max() / jnp.abs(ref).max())
    assert err < 1e-4, err  # f32 both sides: only summation order differs


def test_reference_sees_a_wrong_rope_base(f32):
    config, logits, served, prompts, view = f32
    seqs = np.concatenate([prompts, served[:, :-1]], 1)
    shape = replace(dense.Shape.from_config(config), rope_theta=5e6)
    ref = dense.logits(view, shape, seqs, P - 1)
    assert float(jnp.abs(ref - logits).max() / jnp.abs(ref).max()) > 1e-2


@pytest.mark.parametrize("precision,low,high", [("int8", 1e-4, 0.05),
                                                ("float8", 1e-3, 0.2)])
def test_lower_precisions_round_within_their_reach(f32, precision, low,
                                                   high):
    config, _, served, prompts, view = f32
    seqs = np.concatenate([prompts, served[:, :-1]], 1)
    shape = dense.Shape.from_config(config)
    ref = dense.logits(view, shape, seqs, P - 1)
    got = dense.logits(view, shape, seqs, P - 1, precision=precision)
    err = float(jnp.abs(ref - got).max() / jnp.abs(ref).max())
    assert low < err < high, err


def test_bf16_program_serves_near_greedy_tokens():
    config, _, served, prompts, view = _served("bfloat16")
    seqs = np.concatenate([prompts, served[:, :-1]], 1)
    got = check.compare(
        partial(dense.logits, view, dense.Shape.from_config(config)), seqs,
        served, block=2)
    assert got["max_gap"] < smoke_root.SMOKE_LIMIT
