"""Sharded train / serve step factories.

``make_train_step`` returns a jit'd step with explicit in/out shardings and
donated params/opt-state (buffer reuse).  Microbatch gradient accumulation
is a ``lax.scan`` over microbatches (keeps HLO small; remat inside).
Optional int8 gradient compression (see distributed.compression) is applied
to the gradient all-reduce when enabled.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed.sharding import batch_pspec, shardings_for
from repro.models import lm
from repro.models.config import ModelConfig
from repro.optim.adamw import OptConfig, apply_updates, init_opt_state, opt_state_specs
from repro.serving.engine import init_params


def make_train_step(cfg: ModelConfig, oc: OptConfig, mesh: Mesh,
                    specs, mode: str = "tp", microbatches: int = 1,
                    donate: bool = True, params_abs=None):
    """Returns (train_step, in_shardings, out_shardings).

    train_step(params, opt_state, batch) -> (params, opt_state, metrics)
    """
    opt_abs = (None if params_abs is None else
               jax.eval_shape(lambda p: init_opt_state(oc, p), params_abs))
    param_sh = shardings_for(specs, mesh, mode, like=params_abs)
    opt_sh = shardings_for(opt_state_specs(oc, specs), mesh, mode,
                           like=opt_abs)
    bspec = batch_pspec(mesh, extra_dims=1)

    def batch_shardings(batch_tree):
        def one(x):
            nd = x.ndim if hasattr(x, "ndim") else len(x.shape)
            return NamedSharding(mesh, batch_pspec(mesh, extra_dims=nd - 1))
        return jax.tree.map(one, batch_tree)

    def loss_over_microbatches(params, batch):
        if microbatches == 1:
            return lm.loss_fn(cfg, params, batch)[0]

        def split(x):
            B = x.shape[0]
            return x.reshape(microbatches, B // microbatches, *x.shape[1:])

        mb = jax.tree.map(split, batch)

        def body(acc, one_batch):
            l = lm.loss_fn(cfg, params, one_batch)[0]
            return acc + l, ()

        total, _ = jax.lax.scan(body, 0.0, mb)
        return total / microbatches

    from repro.distributed.sharding import activation_sharding_ctx

    def train_step(params, opt_state, batch):
        with activation_sharding_ctx(mesh, mode):
            loss, grads = jax.value_and_grad(loss_over_microbatches)(params, batch)
        # pin grads to the param (FSDP) layout: reduce-scatter, not all-reduce
        grads = jax.tree.map(
            jax.lax.with_sharding_constraint, grads, param_sh)
        new_params, new_opt, gnorm = apply_updates(oc, params, grads,
                                                   opt_state)
        metrics = {"loss": loss.astype(jnp.float32),
                   "grad_norm": gnorm.astype(jnp.float32)}
        return new_params, new_opt, metrics

    donate_argnums = (0, 1) if donate else ()
    step = jax.jit(
        train_step,
        in_shardings=(param_sh, opt_sh, None),
        out_shardings=(param_sh, opt_sh, None),
        donate_argnums=donate_argnums,
    )
    return step, param_sh, opt_sh


def init_sharded(cfg: ModelConfig, oc: Optional[OptConfig], mesh: Mesh,
                 seed: int = 0, mode: str = "tp"):
    """Initialize params (and optionally optimizer state) sharded on-device."""
    params, specs = init_params(cfg, mesh, seed=seed, mode=mode)
    if oc is None:
        return params, specs, None
    opt_abs = jax.eval_shape(lambda p: init_opt_state(oc, p), params)
    opt_sh = shardings_for(opt_state_specs(oc, specs), mesh, mode,
                           like=opt_abs)
    opt_state = jax.jit(lambda p: init_opt_state(oc, p),
                        out_shardings=opt_sh)(params)
    return params, specs, opt_state
