"""Smoke run of the served step and the mapper-tiled Pallas kernels on TPU v5e.

    python chip_smoke.py            # one chip: device, serve, kernels
    python chip_smoke.py --chips 4  # four chips: minitron-8b tensor-parallel

Phases (one chip):
  device   the first device must be a TPU v5e; prints the compile cache.
  serve    qwen1.5-0.5b at its published config through
           ``repro.launch.serve``: prefill 8x1024, 32 greedy tokens.  All
           logits must be finite, and the first decode step's logits must
           match the last position of a prefill one token longer.
  kernels  ``matmul_pallas`` (compiled, bf16) at the served model's
           projection shapes with ``tcm_matmul_tiles`` tiles, and
           ``flash_attention_pallas`` at its heads with mapper-chosen
           blocks, each against ``repro.kernels.ref``.

With ``--chips 4`` only the sharded path runs: minitron-8b at published
widths, tensor-parallel over four chips, then the same config cut to two
layers served on one chip and on four, whose greedy tokens must be equal
and whose logits must agree.

Everything runs in this one process: a chip belongs to one process, so no
child may touch JAX, and mapper searches run serially.  A failed check
exits non-zero.  The last line of standard output is one JSON object that
names the device.
"""
from __future__ import annotations

import argparse
import json
import sys
from dataclasses import replace
from functools import partial
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# relative error bounds, as max |x - ref| / max |ref|
DECODE_TOL = 5e-2  # bf16 activations through 24 layers, two step shapes
KERNEL_TOL = 1e-2  # bf16 outputs, f32 accumulation in another order
SHARD_TOL = 1e-3  # f32 activations: only the reduction order differs


def rel_err(x, ref) -> float:
    import numpy as np

    x, ref = np.asarray(x, np.float32), np.asarray(ref, np.float32)
    return float(np.abs(x - ref).max() / np.abs(ref).max())


def check(name: str, ok: bool, detail: str) -> None:
    print(f"{name}: {'ok' if ok else 'FAILED'} ({detail})")
    if not ok:
        sys.exit(f"check failed: {name}")


def phase_device(n_chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind!r} "
          f"count={len(devs)}")
    if d.platform != "tpu":
        sys.exit(f"device phase: no TPU found (JAX platform {d.platform!r}); "
                 "this script needs a TPU v5e")
    if "v5 lite" not in d.device_kind.lower() and \
            "v5e" not in d.device_kind.lower():
        sys.exit(f"device phase: {d.device_kind!r} is not a TPU v5e, the "
                 "only chip the mapper's VMEM model describes")
    if len(devs) < n_chips:
        sys.exit(f"device phase: {n_chips} chips asked, {len(devs)} present")
    return devs[:n_chips]


def phase_serve(devices):
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch import serve

    B, P, G = 8, 1024, 32
    print(f"serve: qwen1.5-0.5b published config, batch {B}, prompt {P}, "
          f"{G} new tokens")
    run, params, specs, mesh = serve.main(
        ["--arch", "qwen1.5-0.5b", "--batch", str(B), "--prompt-len", str(P),
         "--gen", str(G)], devices=devices)
    print(f"serve compile_s: {run.compile_s:.3f}")
    print(f"serve prefill_ms: {run.prefill_s * 1e3:.3f}")
    print(f"serve decode_ms_per_step: {run.decode_s_per_step * 1e3:.3f}")
    finite = all(bool(jnp.isfinite(x).all()) for x in run.logits)
    check("serve logits finite", finite, f"{len(run.logits)} steps")

    # decode after a P-token prefill == the last position of a P+1 prefill
    cfg = get_config("qwen1.5-0.5b")
    longer = {"tokens": jnp.concatenate(
        [run.batch["tokens"], run.tokens[:, :1]], axis=1)}
    ref = serve.generate(cfg, mesh, params, specs, longer, gen=1)
    err = rel_err(run.logits[1], ref.logits[0])
    check("serve decode consistency", err <= DECODE_TOL,
          f"rel err {err:.3e} <= tol {DECODE_TOL:.0e}")


def phase_kernels():
    import jax
    import jax.numpy as jnp

    from repro.core.autotile import tcm_matmul_tiles
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.matmul import matmul_pallas
    from repro.kernels.ref import attention_ref, matmul_ref

    d, ff, vocab = 1024, 2816, 151936
    projections = (("q/k/v/o", d, d), ("gate/up", d, ff), ("down", ff, d),
                   ("lm_head", d, vocab))
    key = jax.random.PRNGKey(0)
    for phase, M in (("prefill", 8 * 1024), ("decode", 8)):
        for name, K, N in projections:
            bm, bk, bn = tcm_matmul_tiles(M, K, N, word_bytes=2)
            ka, kb, key = jax.random.split(key, 3)
            a = jax.random.normal(ka, (M, K), jnp.bfloat16)
            b = jax.random.normal(kb, (K, N), jnp.bfloat16)
            mm = jax.jit(partial(matmul_pallas, bm=bm, bk=bk, bn=bn,
                                 interpret=False))
            err = rel_err(mm(a, b), jax.jit(matmul_ref)(a, b))
            check(f"kernel matmul {phase} {name} {M}x{K}x{N} "
                  f"tile ({bm},{bk},{bn})", err <= KERNEL_TOL,
                  f"rel err {err:.3e} <= tol {KERNEL_TOL:.0e}")

    B, S, H, Dh = 8, 1024, 16, 64
    bm, _, bn = tcm_matmul_tiles(S, Dh, S, word_bytes=2)
    bq, bkv = min(bm, S), min(bn, S)
    kq, kk, kv = jax.random.split(key, 3)
    q, k, v = (jax.random.normal(kx, (B, S, H, Dh), jnp.bfloat16)
               for kx in (kq, kk, kv))
    fa = jax.jit(partial(flash_attention_pallas, causal=True, bq=bq, bk=bkv,
                         interpret=False))
    err = rel_err(fa(q, k, v), jax.jit(attention_ref)(q, k, v))
    check(f"kernel flash_attention B{B} S{S} H{H} Dh{Dh} causal "
          f"blocks ({bq},{bkv})", err <= KERNEL_TOL,
          f"rel err {err:.3e} <= tol {KERNEL_TOL:.0e}")


def phase_sharded(devices):
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch import serve
    from repro.launch.mesh import make_elastic_mesh
    from repro.training.step import init_sharded

    B, P, G = 8, 1024, 4
    print(f"sharded: minitron-8b published config, tensor-parallel over "
          f"{len(devices)} chips, batch {B}, prompt {P}, {G} new tokens")
    run, params, *_ = serve.main(
        ["--arch", "minitron-8b", "--batch", str(B), "--prompt-len", str(P),
         "--gen", str(G), "--model-parallel", str(len(devices))],
        devices=devices)
    print(f"sharded compile_s: {run.compile_s:.3f}")
    print(f"sharded prefill_ms: {run.prefill_s * 1e3:.3f}")
    print(f"sharded decode_ms_per_step: {run.decode_s_per_step * 1e3:.3f}")
    finite = all(bool(jnp.isfinite(x).all()) for x in run.logits)
    check("sharded logits finite", finite, f"{len(run.logits)} steps")
    del run, params

    # two layers at full width; f32 activations so that one chip and four
    # differ only in reduction order, not in bf16 roundings that can flip
    # a near-tied greedy token
    cfg = replace(get_config("minitron-8b"), n_layers=2, dtype="float32")
    P, G = 128, 8
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab, (B, P)), jnp.int32)
    runs = []
    for devs in (devices[:1], devices):
        mesh = make_elastic_mesh(target_model=len(devs), devices=devs)
        params, specs, _ = init_sharded(cfg, None, mesh)
        runs.append(serve.generate(cfg, mesh, params, specs,
                                   {"tokens": tokens}, G))
        del params
    one, four = runs
    err = max(rel_err(x4, x1) for x1, x4 in zip(one.logits, four.logits))
    check("sharded logits 1 chip ~ 4 chips", err <= SHARD_TOL,
          f"rel err {err:.3e} <= tol {SHARD_TOL:.0e}")
    same = bool((np.asarray(one.tokens) == np.asarray(four.tokens)).all())
    check("sharded greedy tokens 1 chip == 4 chips", same,
          f"{B}x{G} tokens")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the tensor-parallel minitron-8b phase")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    import jax

    devices = phase_device(args.chips)
    if args.chips == 4:
        phase_sharded(devices)
    else:
        phase_serve(devices)
        phase_kernels()
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
