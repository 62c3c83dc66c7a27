"""Compile the served path's kernels and decode step for a described v5e.

Nothing runs: the TPU compiler accepts or refuses each program for a chip
that is described, not attached.  It refuses what interpret mode cannot
see: blocks that exceed scoped VMEM, slices off the tiling, programs that
do not fit the device.  The topology is described inside the fixture, so
that importing this file loads no TPU library.
"""
import dataclasses
import math
import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.autotile import tcm_matmul_tiles
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.matmul import matmul_pallas
from repro.obs.serving import LAYERS, SCOPES

# qwen1.5-0.5b projections at 8x1024 prefill tokens and 8 decode tokens:
# q/k/v/o, gate/up, down, lm_head
QWEN_MATMULS = [(M, K, N) for M in (8 * 1024, 8)
                for K, N in ((1024, 1024), (1024, 2816), (2816, 1024),
                             (1024, 151936))]
HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 host: four chips, none attached."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back from the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            jax.config.update("jax_enable_compilation_cache", enabled)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return topo.devices[0]


def _compile(fn, one_chip, *shapes):
    dev = SingleDeviceSharding(one_chip)
    args = [jax.ShapeDtypeStruct(s, d, sharding=dev) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("M,K,N", QWEN_MATMULS)
def test_matmul_kernel_tcm_tiles_compile(one_chip, M, K, N):
    bm, bk, bn = tcm_matmul_tiles(M, K, N, word_bytes=2)
    compiled = _compile(partial(matmul_pallas, bm=bm, bk=bk, bn=bn),
                        one_chip, ((M, K), jnp.bfloat16),
                        ((K, N), jnp.bfloat16))
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_qwen_heads_compile(one_chip):
    B, S, H, Dh = 8, 1024, 16, 64
    bm, _, bn = tcm_matmul_tiles(S, Dh, S, word_bytes=2)
    qkv = ((B, S, H, Dh), jnp.bfloat16)
    compiled = _compile(partial(flash_attention_pallas, causal=True,
                                bq=min(bm, S), bk=min(bn, S)),
                        one_chip, qkv, qkv, qkv)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def qwen_decode(one_chip):
    """The served decode step of qwen1.5-0.5b at published widths,
    compiled once for one described v5e."""
    from repro.launch.mesh import make_elastic_mesh
    from repro.models import lm
    from repro.serving.engine import make_serve_steps
    from repro.models.lm import abstract_init

    cfg = get_config("qwen1.5-0.5b")
    B, P, G = 8, 1024, 32
    mesh = make_elastic_mesh(target_model=1, devices=[one_chip])
    params_abs, specs = abstract_init(cfg, jax.random.PRNGKey(0))
    cache_abs = jax.eval_shape(lambda: lm.init_cache(cfg, B, P + G))
    batch_abs = {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32)}
    _, decode, (param_sh, _, cache_sh, tok_sh) = make_serve_steps(
        cfg, mesh, specs, cache_abs, batch_abs)

    def placed(tree, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shardings)

    return decode.lower(
        placed(params_abs, param_sh),
        jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=tok_sh),
        placed(cache_abs, cache_sh)).compile()


def test_qwen_decode_step_compiles_on_one_chip(qwen_decode):
    """The served decode step at published widths fits one v5e."""
    mem = qwen_decode.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_qwen_decode_step_keeps_every_scope(qwen_decode):
    """Each layer kind's named scope survives into the compiled v5e
    program's op_name metadata, fused computations included, so a device
    trace of the step can be read by scope."""
    text = qwen_decode.as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    scopes = {part for path in paths for part in path.split("/")}
    assert set(SCOPES) | {LAYERS} <= scopes
    assert text.startswith("HloModule jit_serve_decode,")


# phi3-mini-3.8b as the benchmark serves it: 4 sequences of 2048 slots,
# prompts of 1536 tokens, bfloat16 weights and cache
PHI3_B, PHI3_P, PHI3_SLOTS = 4, 1536, 2048
_COPY = re.compile(r"^\s*(?:ROOT )?%?(\S+) = (.*?) (copy|copy-start)\(",
                   re.MULTILINE)


def _served_steps(cfg, devices, B, P, slots):
    """The served prefill and decode steps at bfloat16, compiled for the
    described ``devices`` (tensor-parallel over all of them), and the
    placed shape of the first stacked K, with the cache's format as
    ``make_serve_steps`` gives it."""
    import dataclasses

    from repro.launch.mesh import make_elastic_mesh
    from repro.models import lm
    from repro.serving.engine import make_serve_steps
    from repro.models.lm import abstract_init

    cfg = dataclasses.replace(cfg, param_dtype="bfloat16", dtype="bfloat16")
    mesh = make_elastic_mesh(target_model=len(devices), devices=devices)
    params_abs, specs = abstract_init(cfg, jax.random.PRNGKey(0))
    cache_abs = jax.eval_shape(lambda: lm.init_cache(cfg, B, slots))
    batch_abs = {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32)}
    prefill, decode, (param_sh, batch_sh, cache_sh, tok_sh) = \
        make_serve_steps(cfg, mesh, specs, cache_abs, batch_abs)

    def placed(tree, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, shardings)

    params, cache = placed(params_abs, param_sh), placed(cache_abs, cache_sh)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=tok_sh)
    return cache["groups"][0][0]["attn"]["k"], cache_sh, {
        "decode": decode.lower(params, tok, cache).compile(),
        "prefill": prefill.lower(params, placed(batch_abs, batch_sh),
                                 cache).compile()}


@pytest.fixture(scope="module")
def phi3_steps(one_chip):
    """The served prefill and decode steps of phi3-mini-3.8b at the
    benchmark's shapes, compiled once for one described v5e."""
    return _served_steps(get_config("phi3-mini-3.8b"), [one_chip], PHI3_B,
                         PHI3_P, PHI3_SLOTS)


def _copies(text):
    """Each copy instruction's name, shapes and op_name."""
    for m in _COPY.finditer(text):
        line = text[m.start():text.index("\n", m.end())]
        op = re.search(r'op_name="([^"]*)"', line)
        shapes = [tuple(int(d) for d in s.split(",") if d)
                  for s in re.findall(r"\[([\d,]*)\]", m.group(2))]
        yield m.group(1), shapes, op.group(1) if op else ""


def _keeps_the_cache_where_it_lies(compiled, k, cache_sh):
    """No buffer of a layer's K/V or of a whole stacked leaf (one chip's
    share of it): neither what ``cache_relayouts`` counts nor a copy of
    as many elements; and each K/V leaf goes in and comes out in the
    format ``make_serve_steps`` gives it."""
    from jax.experimental.layout import Format

    from repro.obs.serving import cache_relayouts

    text = compiled.as_text()
    assert cache_relayouts(text) == (0, 0)
    shard = k.sharding.shard_shape(k.shape)
    sizes = {math.prod(shard), math.prod(shard[1:])}
    for name, shapes, op_name in _copies(text):
        for shape in shapes:
            assert math.prod(shape) not in sizes, (name, shape, op_name)
    want = jax.tree.leaves(cache_sh)
    assert any(isinstance(f, Format) for f in want)
    for formats in (compiled.input_formats[0][2], compiled.output_formats[1]):
        got = jax.tree.leaves(formats)
        assert len(got) == len(want)
        for g, w in zip(got, want):
            if isinstance(w, Format):  # the compiler adds its tiling
                assert g.layout.major_to_minor == w.layout.major_to_minor
                assert g.sharding == w.sharding


@pytest.mark.parametrize("step,temp_gb", [("decode", 0.05),
                                          ("prefill", 0.39)])
def test_phi3_step_updates_the_stacked_cache_in_place(phi3_steps, step,
                                                      temp_gb):
    """The step writes its new K/V rows into the donated stacked cache and
    attention reads its chunks where they lie: the compiled program makes
    no buffer of the whole cache or of a layer's slice of it, its
    temporaries hold neither, and the cache keeps its pinned layout."""
    k, cache_sh, programs = phi3_steps
    compiled = programs[step]
    _keeps_the_cache_where_it_lies(compiled, k, cache_sh)
    mem = compiled.memory_analysis()
    cache_bytes = 2 * math.prod(k.shape) * k.dtype.itemsize
    assert mem.temp_size_in_bytes < temp_gb * 1e9
    assert mem.alias_size_in_bytes >= cache_bytes


# Yi-34B as the four-chip cell serves it: one pipeline stage of 30 layers,
# 16 sequences of 4096 slots, prompts of 512 tokens, tensor-parallel 4
YI_B, YI_P, YI_SLOTS, YI_CHIPS = 16, 512, 4096, 4
_COLLECTIVE = re.compile(
    r"^\s*(?:ROOT )?%?(\S+) = (.*?) (all-reduce|all-gather|reduce-scatter|"
    r"collective-permute|all-to-all)(?:-start)?\(", re.MULTILINE)


YI = dataclasses.replace(get_config("yi-34b"), n_layers=30, norm_eps=1e-5)


@pytest.fixture(scope="module")
def yi_steps(topo):
    """The served prefill and decode steps of the Yi-34B cut, compiled
    once for the four chips of a described v5e:2x2."""
    return _served_steps(YI, topo.devices[:YI_CHIPS], YI_B, YI_P, YI_SLOTS)


@pytest.mark.parametrize("step,temp_gb", [("decode", 0.05),
                                          ("prefill", 0.42)])
def test_yi_tp4_step_keeps_each_chips_cache_in_place(yi_steps, step,
                                                     temp_gb):
    """Tensor-parallel over four chips, each chip's share of the stacked
    cache (its KV heads) is updated in place and read where it lies: no
    buffer of a whole shard or of a layer of it, no temporaries that hold
    one, the donated cache aliased, and the window within a chip's 16 GB."""
    k, cache_sh, programs = yi_steps
    shard = k.sharding.shard_shape(k.shape)
    compiled = programs[step]
    _keeps_the_cache_where_it_lies(compiled, k, cache_sh)
    mem = compiled.memory_analysis()
    cache_bytes = 2 * math.prod(shard) * k.dtype.itemsize
    assert mem.temp_size_in_bytes < temp_gb * 1e9
    assert mem.alias_size_in_bytes >= cache_bytes
    window = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
              + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert window < HBM_BYTES


def test_yi_tp4_decode_all_reduces_only_the_residual(yi_steps):
    """Each layer all-reduces the (B, 1, d_model) residual twice, after
    attention's output projection and after the MLP, and the embedding
    lookup once: nothing else crosses chips, the cache least of all."""
    from repro.obs.serving import collectives

    _, _, programs = yi_steps
    text = programs["decode"].as_text()
    n = 2 * YI.n_layers + 1
    residual = (YI_B, 1, YI.d_model)
    assert collectives(text) == {
        "all-reduce": (n, n * math.prod(residual) * 2)}
    for m in _COLLECTIVE.finditer(text):
        shapes = [tuple(int(d) for d in s.split(",") if d)
                  for s in re.findall(r"\[([\d,]*)\]", m.group(2))]
        assert shapes == [residual], m.group(0)
