"""Compile every cell's programs for a described v5e, without the chip.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py [workload ...]

For each cell: the weight maker, the cache allocation, and the program's
prefill and decode steps at the cell's shapes, compiled for one chip of a
described ``v5e:2x2`` (or all four, for a four-chip cell).  Prints each
program's ``memory_analysis()`` per device, and what the window holds at
once: the weights, the cache and the larger step's temporaries and
outputs.  What the compiler refuses here would fail on the chip.
"""
from __future__ import annotations

import json
import os
import sys
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
GB = 1e9


def _placed(tree, shardings):
    import jax

    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, shardings)


def rehearse(workload: str, topo) -> dict:
    import jax
    import jax.numpy as jnp

    from chipbench import bench, cell, weights
    from repro.launch.mesh import make_elastic_mesh
    from repro.models import lm
    from repro.serving.engine import make_serve_steps

    c = bench.load_cell(workload)
    cfg = bench.model_config(c.config, c.family)
    mix = c.traffic
    B, P = mix["batch"], mix["prompt_tokens"]
    mesh = make_elastic_mesh(target_model=c.config["mesh"]["model"],
                             devices=topo.devices[:c.chips])
    init_cache = partial(lm.init_cache, cfg, B, mix["cache_slots"])
    cache_abs = jax.eval_shape(init_cache)
    batch_abs = {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32)}
    params_abs, specs = cell._abstract_params(cfg)
    prefill, decode, (param_sh, batch_sh, cache_sh, tok_sh) = \
        make_serve_steps(cfg, mesh, specs, cache_abs, batch_abs)
    params = _placed(params_abs, param_sh)
    cache = _placed(cache_abs, cache_sh)
    batch = _placed(batch_abs, batch_sh)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=tok_sh)
    key = jax.eval_shape(lambda: weights.key_for(0, 0))
    build = weights.builder(params_abs, cfg.d_model, c.family.WEIGHTS)

    programs = {
        "weights": jax.jit(build, out_shardings=param_sh).lower(key),
        "cache": jax.jit(init_cache, out_shardings=cache_sh).lower(),
        "prefill": prefill.lower(params, batch, cache),
        "decode": decode.lower(params, tok, cache),
    }
    out = {"workload": workload, "chips": c.chips}
    for name, lowered in programs.items():
        try:
            m = lowered.compile().memory_analysis()
        except jax.errors.JaxRuntimeError as e:
            out[name] = {"refused": str(e).splitlines()[0]}
            continue
        out[name] = {k: getattr(m, f"{k}_size_in_bytes") / GB for k in (
            "argument", "output", "temp", "alias", "generated_code")}
    if any("refused" in out[n] for n in programs):
        return out
    step = max(("prefill", "decode"), key=lambda n: out[n]["temp"])
    out["window_peak_gb"] = (out["weights"]["output"]
                             + out["cache"]["output"]
                             + out[step]["temp"]
                             + out[step]["output"] - out[step]["alias"])
    return out


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    names = (argv if argv is not None else sys.argv[1:]) or [
        w["name"] for w in json.loads(
            (ROOT / "BENCHMARK.json").read_text())["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names:
        print(json.dumps(rehearse(name, topo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
