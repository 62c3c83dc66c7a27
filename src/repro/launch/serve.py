"""Batched serving driver: prefill a batch of prompts, decode greedily.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --smoke \\
      --batch 4 --prompt-len 64 --gen 32

Params and cache are built on the mesh (jit with ``out_shardings``); the
prefill and decode steps are compiled before the clock starts, so the
printed step times hold no compilation.
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_elastic_mesh
from repro.models import lm
from repro.obs.serving import (WATCH, attend_chunk_share, cache_relayouts,
                                collectives)
from repro.serving.engine import init_params, make_serve_steps


@dataclass
class ServeRun:
    """What one :func:`generate` call produced, with its timings."""

    batch: dict  # the prompt as served
    tokens: jax.Array  # (B, G) greedy tokens; [:, 0] comes from prefill
    logits: List[jax.Array]  # G x (B, vocab): prefill's last, then decode's
    compile_s: float  # lower + compile of the prefill and decode steps
    prefill_s: float
    decode_s_per_step: float  # nan when G == 1
    # on a mesh of several devices: per compiled step, {kind: (count,
    # bytes per device)} of the collectives one run of it issues
    collectives: Dict[str, Dict[str, Tuple[int, int]]] = field(
        default_factory=dict)
    # per compiled step, (count, bytes per device) of the buffers one run
    # of it makes that hold a layer's K or V or a whole stacked K/V leaf
    cache_relayouts: Dict[str, Tuple[int, int]] = field(
        default_factory=dict)
    # per step, the share of (query block, KV chunk) pairs a causal
    # attention layer visits, of every allocated chunk; decode's averaged
    # over the run's steps
    attend_chunk_share: Dict[str, float] = field(default_factory=dict)


def generate(cfg, mesh, params, specs, batch, gen: int, mode: str = "tp",
             extra_len: int = 0) -> ServeRun:
    """Prefill ``batch`` and decode ``gen - 1`` greedy steps on ``mesh``.

    ``extra_len`` reserves cache slots for prompt positions that are not
    tokens (a VLM's image embeddings).
    """
    B, P = batch["tokens"].shape
    rows, slots = P + extra_len, P + gen + extra_len
    init_cache = partial(lm.init_cache, cfg, B, slots)
    prefill_step, decode_step, (_, _, cache_sh, _) = make_serve_steps(
        cfg, mesh, specs, jax.eval_shape(init_cache), batch, mode=mode)
    cache = jax.jit(init_cache, out_shardings=cache_sh)()
    tok_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)

    t0 = time.perf_counter()
    prefill_step = prefill_step.lower(params, batch, cache).compile()
    if gen > 1:
        decode_step = decode_step.lower(params, tok_abs, cache).compile()
    compile_s = time.perf_counter() - t0
    texts = {"prefill": prefill_step.as_text()}
    if gen > 1:
        texts["decode"] = decode_step.as_text()
    counted = ({name: collectives(text) for name, text in texts.items()}
               if mesh.devices.size > 1 else {})
    relaid = {name: cache_relayouts(text) for name, text in texts.items()}
    visited = {"prefill": attend_chunk_share(0, rows, slots)}
    if gen > 1:
        visited["decode"] = sum(
            attend_chunk_share(fill, 1, slots)
            for fill in range(rows, rows + gen - 1)) / (gen - 1)

    t0 = time.perf_counter()
    last, cache = prefill_step(params, batch, cache)
    last.block_until_ready()
    prefill_s = time.perf_counter() - t0

    toks = jnp.argmax(last, -1)[:, None].astype(jnp.int32)
    out_tokens, out_logits = [toks], [last]
    t0 = time.perf_counter()
    for _ in range(gen - 1):
        logits, cache = decode_step(params, toks, cache)
        toks = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out_tokens.append(toks)
        out_logits.append(logits)
    jax.block_until_ready(toks)
    t_decode = time.perf_counter() - t0
    return ServeRun(
        batch=batch, tokens=jnp.concatenate(out_tokens, axis=1), logits=out_logits,
        compile_s=compile_s, prefill_s=prefill_s,
        decode_s_per_step=t_decode / (gen - 1) if gen > 1 else float("nan"),
        collectives=counted, cache_relayouts=relaid,
        attend_chunk_share=visited)


def main(argv=None, devices=None):
    """CLI entry point; ``devices`` (default: all visible) hold the mesh.

    Returns ``(run, params, specs, mesh)`` so callers can check the
    served logits against further steps on the same weights.
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--mode", default="tp")
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args(argv)
    use_compile_cache()
    WATCH.install()  # counted below; `gc` spans in any profile taken

    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = make_elastic_mesh(target_model=args.model_parallel,
                             devices=devices)
    B, P, G = args.batch, args.prompt_len, args.gen

    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (B, P)), jnp.int32)}
    extra_len = 0
    if cfg.family == "vlm":
        batch["embeds"] = jnp.asarray(
            rng.normal(size=(B, 8, cfg.frontend_dim)), jnp.float32)
        extra_len = 8
    if cfg.family == "audio":
        batch["enc_frames"] = jnp.asarray(
            rng.normal(size=(B, P, cfg.frontend_dim)), jnp.float32)

    params, specs = init_params(cfg, mesh, mode=args.mode)
    gc_before = WATCH.snapshot()
    run = generate(cfg, mesh, params, specs, batch, G, mode=args.mode,
                   extra_len=extra_len)
    gc_run = WATCH.snapshot() - gc_before
    print(f"compile {run.compile_s:.2f}s  prefill {B}x{P}: "
          f"{run.prefill_s*1e3:.0f}ms  decode {G-1} steps: "
          f"{run.decode_s_per_step*1e3:.2f}ms/step "
          f"({B/max(run.decode_s_per_step, 1e-9):.1f} tok/s)")
    print("sample:", np.asarray(run.tokens[0][:16]))
    for name, kinds in run.collectives.items():
        print(f"collectives per {name} step: " + (", ".join(
            f"{kind} {n} x, {b / 1e6:.3f} MB per device"
            for kind, (n, b) in kinds.items()) or "none"))
    for name, (n, b) in run.cache_relayouts.items():
        print(f"cache relayouts per {name} step: {n} x, "
              f"{b / 1e6:.3f} MB per device")
    for name, share in run.attend_chunk_share.items():
        print(f"attend chunk share per {name} step: {share:.3f}")
    print(f"gc: {'/'.join(map(str, gc_run.collections))} collections "
          f"(generations 0/1/2), {gc_run.pause_s * 1e3:.3f} ms paused, "
          "compile included")
    return run, params, specs, mesh


if __name__ == "__main__":
    main()
