"""Integration tests: train loop, checkpoint/resume determinism, data
pipeline state, serving path, preemption semantics."""
import json
import os
import signal
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.launch.mesh import make_elastic_mesh
from repro.optim.adamw import OptConfig
from repro.training.step import init_sharded, make_train_step


@pytest.fixture()  # function scope: train_step donates params/opt buffers
def tiny_setup():
    cfg = get_config("qwen1.5-0.5b", smoke=True).scaled(
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, d_head=32,
        d_ff=128, vocab=256)
    oc = OptConfig(lr=1e-3, warmup=2, decay_steps=50)
    mesh = make_elastic_mesh(target_model=1)
    params, specs, opt_state = init_sharded(cfg, oc, mesh)
    step_fn, param_sh, opt_sh = make_train_step(cfg, oc, mesh, specs)
    return cfg, oc, mesh, params, specs, opt_state, step_fn, param_sh, opt_sh


def _data(cfg, start=0):
    return SyntheticTokens(DataConfig(
        global_batch=4, seq_len=32, vocab=cfg.vocab), start_step=start)


def test_loss_decreases(tiny_setup):
    cfg, oc, mesh, params, specs, opt_state, step_fn, *_ = tiny_setup
    data = _data(cfg)
    losses = []
    for _ in range(20):
        params, opt_state, m = step_fn(params, opt_state, next(data))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], f"no learning: {losses[0]} -> {losses[-1]}"


def test_checkpoint_resume_bitwise(tiny_setup, tmp_path):
    """Training N steps == training k, checkpoint, restore, train N-k."""
    cfg, oc, mesh, params0, specs, opt0, step_fn, param_sh, opt_sh = tiny_setup

    def fresh():  # step_fn donates its inputs; copy per phase
        return (jax.tree.map(jnp.copy, params0),
                jax.tree.map(jnp.copy, opt0))

    # straight run of 6 steps
    p, o = fresh()
    data = _data(cfg)
    for _ in range(6):
        p, o, m = step_fn(p, o, next(data))
    ref_leaves = [np.asarray(x) for x in jax.tree.leaves(p)]

    # run 3 steps, checkpoint (async), restore, run 3 more
    mgr = CheckpointManager(str(tmp_path / "ckpt"), keep=2)
    p, o = fresh()
    data = _data(cfg)
    for _ in range(3):
        p, o, m = step_fn(p, o, next(data))
    mgr.save_async(3, {"params": p, "opt": o},
                   extra={"data": data.state()})
    mgr.wait()

    state, extra = mgr.restore_sharded(
        3, {"params": p, "opt": o}, {"params": param_sh, "opt": opt_sh})
    p2, o2 = state["params"], state["opt"]
    data2 = _data(cfg)
    data2.restore(extra["data"])
    assert data2.step == 3
    for _ in range(3):
        p2, o2, m = step_fn(p2, o2, next(data2))
    for a, b in zip(ref_leaves, jax.tree.leaves(p2)):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_checkpoint_atomicity_and_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"w": jnp.arange(8.0)}
    for s in (1, 2, 3, 4):
        mgr.save(s, tree, extra={"s": s})
    assert mgr.all_steps() == [3, 4]  # retention
    # a stale .tmp dir must not be listed as a checkpoint
    (tmp_path / "step_00000099.tmp").mkdir()
    assert mgr.latest_step() == 4
    restored, extra = mgr.restore(4, tree)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(8.0))
    assert extra["s"] == 4


def test_data_pipeline_determinism_and_sharding():
    cfg = DataConfig(global_batch=8, seq_len=16, vocab=100, n_hosts=2,
                     host_id=0)
    a = SyntheticTokens(cfg)
    b = SyntheticTokens(DataConfig(global_batch=8, seq_len=16, vocab=100,
                                   n_hosts=2, host_id=1))
    x0, y0 = next(a), next(b)
    assert x0["tokens"].shape == (4, 16)  # per-host shard
    assert not np.array_equal(x0["tokens"], y0["tokens"])  # different hosts
    # restore determinism
    a2 = SyntheticTokens(cfg)
    a2.restore({"step": 1, "seed": 0, "host_id": 0})
    np.testing.assert_array_equal(next(a)["tokens"], next(a2)["tokens"])


def test_train_cli_smoke(tmp_path):
    """The production launcher end to end, with resume."""
    from repro.launch import train as train_mod
    args = ["--arch", "qwen1.5-0.5b", "--smoke", "--steps", "6",
            "--global-batch", "2", "--seq-len", "32",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
            "--log-every", "2"]
    train_mod.main(args)
    mgr = CheckpointManager(str(tmp_path))
    assert mgr.latest_step() is not None
    # resume from the checkpoint and continue
    train_mod.main(["--arch", "qwen1.5-0.5b", "--smoke", "--steps", "8",
                    "--global-batch", "2", "--seq-len", "32",
                    "--ckpt-dir", str(tmp_path), "--ckpt-every", "3"])


def test_serve_cli_smoke():
    from repro.launch import serve as serve_mod
    run, *_ = serve_mod.main(["--arch", "qwen1.5-0.5b", "--smoke",
                              "--batch", "2", "--prompt-len", "16",
                              "--gen", "4"])
    assert run.tokens.shape == (2, 4)


def test_compile_cache_dir(tmp_path, monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.compile_cache import DEFAULT_DIR, use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    enabled = jax.config.jax_enable_compilation_cache
    try:
        # on the CPU the cache stays off, whatever the environment names
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() is None
        assert jax.config.jax_enable_compilation_cache is False
        assert jax.config.jax_compilation_cache_dir == before
        jax.config.update("jax_enable_compilation_cache", enabled)
        # on a chip, a directory named by the environment is JAX's to read
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        # else one fixed directory at the checkout root
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert use_compile_cache() == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
        assert (DEFAULT_DIR.parent / "src" / "repro").is_dir()
        assert jax.config.jax_enable_compilation_cache == enabled
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def test_serve_twice_with_one_compile_cache(tmp_path):
    """A second run finds the first's compilation cache directory and
    still serves: on the CPU no executable is read back from it (one read
    back loses the K/V leaves' pinned layout and fails the first decode)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    for run in range(2):
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.serve", "--arch",
             "phi3-mini-3.8b", "--smoke", "--gen", "4"],
            capture_output=True, text=True, env=env, timeout=300)
        assert r.returncode == 0, (run, r.stderr[-2000:])
