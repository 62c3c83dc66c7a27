import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent: the jit'd
train/prefill/decode step lowers and compiles against the production mesh
with abstract (ShapeDtypeStruct) inputs — no allocation — and we record
``memory_analysis`` (fits-in-HBM proof), ``cost_analysis`` and the parsed
HLO roofline inputs (FLOPs / bytes / collective bytes, while-trip scaled).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]
Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ALIASES, ARCHS, SHAPES, cells_for, get_config
from repro.launch.hlo_parse import summarize
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.models.config import ModelConfig
from repro.optim.adamw import OptConfig, init_opt_state, opt_state_specs
from repro.serving.engine import batch_shardings, cache_shardings, make_serve_steps
from repro.distributed.sharding import (activation_sharding_ctx,
                                         shardings_for)

VLM_PATCHES = 576


def input_specs(cfg: ModelConfig, cell) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    B, S = cell.global_batch, cell.seq_len
    sds = jax.ShapeDtypeStruct
    if cell.kind == "train":
        toks = S - (VLM_PATCHES if cfg.family == "vlm" else 0)
        batch = {"tokens": sds((B, toks), jnp.int32),
                 "labels": sds((B, toks), jnp.int32)}
        if cfg.family == "vlm":
            batch["embeds"] = sds((B, VLM_PATCHES, cfg.frontend_dim),
                                  jnp.float32)
        if cfg.family == "audio":
            batch["enc_frames"] = sds((B, S, cfg.frontend_dim), jnp.float32)
        return batch
    if cell.kind == "prefill":
        toks = S - (VLM_PATCHES if cfg.family == "vlm" else 0)
        batch = {"tokens": sds((B, toks), jnp.int32)}
        if cfg.family == "vlm":
            batch["embeds"] = sds((B, VLM_PATCHES, cfg.frontend_dim),
                                  jnp.float32)
        if cfg.family == "audio":
            batch["enc_frames"] = sds((B, S, cfg.frontend_dim), jnp.float32)
        return batch
    # decode: one new token against a cache of length S
    return {"tokens": sds((B, 1), jnp.int32)}


def _abstract_cache(cfg, B, S):
    return jax.eval_shape(lambda: lm.init_cache(cfg, B, S))


# per-arch sharding mode from the §Perf hillclimb (EXPERIMENTS.md):
#   dp     — small models: TP all-reduces dominated; pure DP is 28-100x less
#            collective traffic (mamba2 measurement).
#   tp_ep  — large MoE: expert weights stored in compute layout
#            (expert x ff over model x data) — no gather at use.
MODE_OVERRIDES = {
    "mamba2-130m": "dp",
    "qwen1.5-0.5b": "dp",
    "seamless-m4t-medium": "dp",
    "llama4-scout-17b-a16e": "tp_ep",
    "phi3.5-moe-42b-a6.6b": "tp_ep",
}

DEFAULT_MICROBATCHES = {
    # grad-accumulation factor for the train_4k cell: chosen so the
    # per-device activation footprint fits v5e HBM (16 GB); recorded in
    # EXPERIMENTS.md §Dry-run.
    "yi-34b": 4,
    "llava-next-34b": 4,
    "llama4-scout-17b-a16e": 4,
    "phi3.5-moe-42b-a6.6b": 2,
    "minitron-8b": 2,
}


def run_cell(arch: str, shape: str, multi_pod: bool, mode: str = "",
             serve_param_dtype: str = "bfloat16",
             microbatches: int = 0) -> dict:
    cell = SHAPES[shape]
    cfg = get_config(arch)
    if not mode:
        mode = MODE_OVERRIDES.get(arch, "tp_fsdp")
        if mode == "dp" and cell.kind != "train":
            # pure DP needs global_batch % n_devices == 0; serve batches
            # (32/128/1) don't divide 256 — fall back to TP+SP serving
            mode = "tp_fsdp"
    if not microbatches:
        microbatches = (DEFAULT_MICROBATCHES.get(arch, 1)
                        if cell.kind == "train" else 1)
    if cell.kind != "train":
        cfg = cfg.scaled(param_dtype=serve_param_dtype)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size

    t0 = time.time()
    params_abs, specs = lm.abstract_init(cfg, jax.random.PRNGKey(0))
    param_sh = shardings_for(specs, mesh, mode, like=params_abs)
    batch_abs = input_specs(cfg, cell)
    result = {"arch": arch, "shape": shape,
              "mesh": "multipod_2x16x16" if multi_pod else "pod_16x16",
              "mode": mode, "kind": cell.kind, "n_devices": int(n_dev),
              "microbatches": microbatches}

    if cell.kind == "train":
        oc = OptConfig()
        opt_abs = jax.eval_shape(lambda p: init_opt_state(oc, p), params_abs)
        opt_sh = shardings_for(opt_state_specs(oc, specs), mesh, mode,
                               like=opt_abs)
        batch_sh = batch_shardings(mesh, batch_abs)

        from repro.training.step import make_train_step
        with mesh, activation_sharding_ctx(mesh, mode):
            # build the un-jitted step fn with our shardings and lower it
            from repro.models import lm as _lm
            from repro.optim.adamw import apply_updates

            def train_step(params, opt_state, batch):
                def loss(p):
                    if microbatches == 1:
                        return _lm.loss_fn(cfg, p, batch)[0]

                    def split(x):
                        return x.reshape(microbatches,
                                         x.shape[0] // microbatches,
                                         *x.shape[1:])

                    mb = jax.tree.map(split, batch)

                    def body(acc, one):
                        return acc + _lm.loss_fn(cfg, p, one)[0], ()

                    tot, _ = jax.lax.scan(body, 0.0, mb)
                    return tot / microbatches

                l, grads = jax.value_and_grad(loss)(params)
                # grads are intermediates: pin them to the param (FSDP)
                # layout so XLA reduce-scatters instead of materializing
                # model-sharded-only full gradients
                grads = jax.tree.map(
                    jax.lax.with_sharding_constraint, grads, param_sh)
                new_p, new_o, gn = apply_updates(oc, params, grads, opt_state)
                return new_p, new_o, {"loss": l, "grad_norm": gn}

            lowered = jax.jit(
                train_step,
                in_shardings=(param_sh, opt_sh, batch_sh),
                out_shardings=(param_sh, opt_sh, None),
                donate_argnums=(0, 1),
            ).lower(params_abs, opt_abs, batch_abs)
    else:
        cache_abs = _abstract_cache(cfg, cell.global_batch, cell.seq_len)
        cache_sh = cache_shardings(cfg, cache_abs, mesh)
        batch_sh = batch_shardings(mesh, batch_abs)
        with mesh, activation_sharding_ctx(mesh, mode):
            if cell.kind == "prefill":
                def prefill_fn(params, batch, cache):
                    return lm.prefill(cfg, params, batch, cache)
                lowered = jax.jit(
                    prefill_fn,
                    in_shardings=(param_sh, batch_sh, cache_sh),
                    out_shardings=(None, cache_sh),
                    donate_argnums=(2,),
                ).lower(params_abs, batch_abs, cache_abs)
            else:
                tok_abs = batch_abs["tokens"]
                tok_sh = batch_shardings(mesh, {"t": tok_abs})["t"]

                def decode_fn(params, tok, cache):
                    return lm.decode_step(cfg, params, tok, cache)
                lowered = jax.jit(
                    decode_fn,
                    in_shardings=(param_sh, tok_sh, cache_sh),
                    out_shardings=(None, cache_sh),
                    donate_argnums=(2,),
                ).lower(params_abs, tok_abs, cache_abs)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    result["lower_s"] = round(t_lower, 1)
    result["compile_s"] = round(t_compile, 1)
    result["memory_per_device"] = {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "peak_live_bytes": (mem.argument_size_in_bytes
                            + mem.temp_size_in_bytes),
    }
    result["cost_analysis"] = {
        k: v for k, v in cost.items()
        if k in ("flops", "bytes accessed", "transcendentals")
    }
    t0 = time.time()
    hlo = compiled.as_text()
    s = summarize(hlo)
    result["hlo"] = {
        "per_device_flops": s.flops,
        "per_device_bytes": s.bytes_accessed,
        "collective_bytes": s.collective_bytes,
        "total_collective_bytes": s.total_collective_bytes,
        "hlo_chars": len(hlo),
        "parse_s": round(time.time() - t0, 1),
    }
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("pod", "multipod", "both"),
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mode", default="",
                    help="sharding mode; empty = per-arch MODE_OVERRIDES")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    archs = [args.arch] if args.arch else list(ALIASES)
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]

    for arch in archs:
        cfg = get_config(arch)
        cells = [args.shape] if args.shape else cells_for(cfg)
        for shape in cells:
            for mp in meshes:
                mesh_tag = "multipod" if mp else "pod"
                fn = outdir / f"{arch}__{shape}__{mesh_tag}.json"
                if fn.exists():
                    print(f"skip {fn} (exists)", flush=True)
                    continue
                print(f"=== {arch} x {shape} x {mesh_tag} ===", flush=True)
                try:
                    res = run_cell(arch, shape, mp, mode=args.mode)
                    print(json.dumps(res["memory_per_device"]), flush=True)
                    print(json.dumps(res["hlo"]), flush=True)
                except Exception as e:  # noqa: BLE001
                    res = {"arch": arch, "shape": shape, "mesh": mesh_tag,
                           "error": str(e),
                           "traceback": traceback.format_exc()[-2000:]}
                    print(f"FAILED: {e}", flush=True)
                fn.write_text(json.dumps(res, indent=2))


if __name__ == "__main__":
    main()
