"""Production training driver: elastic, preemption-safe, auto-resuming.

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --smoke \\
      --steps 200 --ckpt-dir /tmp/ckpt

Fault-tolerance model (designed for 1000+ nodes, exercised here on CPU):
  * auto-resume: on start, restore the latest checkpoint (params, optimizer,
    data-iterator state) if one exists; elastic — the restore device_puts
    onto whatever mesh the surviving fleet supports (data axis shrinks).
  * preemption: SIGTERM/SIGINT triggers checkpoint-and-exit at the next step
    boundary (atomic commit; a killed writer never corrupts state).
  * async checkpointing every --ckpt-every steps off the critical path.
  * straggler watchdog: EWMA of step time; steps slower than
    --straggler-factor x the EWMA are logged with their metrics for fleet
    triage (on real fleets this feeds the scheduler's replace-node hook).
"""
from __future__ import annotations

import argparse
import signal
import sys
import time

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_elastic_mesh
from repro.optim.adamw import OptConfig
from repro.training.step import init_sharded, make_train_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=("adamw", "adafactor"))
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mode", default="tp")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--straggler-factor", type=float, default=3.0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    oc = OptConfig(kind=args.optimizer, lr=args.lr,
                   decay_steps=max(args.steps, 10))
    mesh = make_elastic_mesh(target_model=args.model_parallel)
    print(f"mesh: {dict(mesh.shape)} devices={mesh.devices.size}")

    params, specs, opt_state = init_sharded(cfg, oc, mesh, mode=args.mode)
    step_fn, param_sh, opt_sh = make_train_step(
        cfg, oc, mesh, specs, mode=args.mode,
        microbatches=args.microbatches)

    data = SyntheticTokens(DataConfig(
        global_batch=args.global_batch, seq_len=args.seq_len,
        vocab=cfg.vocab, frontend=cfg.frontend,
        frontend_dim=cfg.frontend_dim))

    start_step = 0
    mgr = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir)
        latest = mgr.latest_step()
        if latest is not None:
            state = {"params": params, "opt": opt_state}
            restored, extra = mgr.restore_sharded(
                latest, state, {"params": param_sh, "opt": opt_sh})
            params, opt_state = restored["params"], restored["opt"]
            data.restore(extra["data"])
            start_step = latest
            print(f"resumed from step {latest}")

    # preemption handling: checkpoint-and-exit at the next boundary
    preempted = {"flag": False}

    def _on_term(signum, frame):
        preempted["flag"] = True

    signal.signal(signal.SIGTERM, _on_term)
    signal.signal(signal.SIGINT, _on_term)

    ewma = None
    for step in range(start_step, args.steps):
        batch = next(data)
        t0 = time.perf_counter()
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        metrics = jax.device_get(metrics)
        dt = time.perf_counter() - t0
        ewma = dt if ewma is None else 0.9 * ewma + 0.1 * dt
        if dt > args.straggler_factor * ewma and step > start_step + 3:
            print(f"[straggler] step {step}: {dt:.2f}s vs ewma {ewma:.2f}s",
                  file=sys.stderr)
        if step % args.log_every == 0:
            print(f"step {step}: loss={metrics['loss']:.4f} "
                  f"gnorm={metrics['grad_norm']:.3f} {dt*1e3:.0f}ms")
        if mgr and ((step + 1) % args.ckpt_every == 0 or preempted["flag"]):
            mgr.save_async(step + 1, {"params": params, "opt": opt_state},
                           extra={"data": data.state()})
        if preempted["flag"]:
            print("preempted: checkpointed, exiting cleanly")
            break
    if mgr:
        mgr.save_async(min(step + 1, args.steps),
                       {"params": params, "opt": opt_state},
                       extra={"data": data.state()})
        mgr.wait()
    print(f"done at step {step + 1}; final loss "
          f"{float(metrics['loss']):.4f}")
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
