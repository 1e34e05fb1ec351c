"""Compile the serving path for a described TPU v5e at qwen3-1.7b's
published widths — the Pallas kernels the engine calls, with the dtypes
it passes (bf16 queries, the float32 page pool as stored), and the
full-width decode and prefill steps, which must fit one chip's 16 GiB.

Nothing runs: the TPU compiler installed beside JAX compiles for a chip
that is described, not attached, so a block shape, tiling or memory
failure shows here before any chip time is spent.  The topology is
described inside a fixture (only the worker that runs this file loads
the TPU library); where it cannot be described, the tests skip.
"""
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.engine.models import build_model

H, HKV, DH = 16, 8, 128                  # qwen3-1.7b attention widths
PAGE, POOL_PAGES = 8, 1024               # the engine's page size and pool
B, N_PAGES = 8, 16                       # a full decode batch, 128 tokens
HBM_LIMIT = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:       # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # an entry compiled for a described chip cannot be read back
        # without one; keep these compiles out of any persistent cache
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*args).compile()


def test_flash_attention_prefill_bucket(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention
    S = lambda *a: _spec(one_chip, *a)
    sq, skv = 48, 96                     # a chunk-prefill bucket
    _compile(lambda q, k, v, qp, kp: flash_attention(
        q, k, v, q_positions=qp, kv_positions=kp),
        S((1, sq, H, DH), jnp.bfloat16), S((1, skv, HKV, DH), jnp.bfloat16),
        S((1, skv, HKV, DH), jnp.bfloat16), S((1, sq), jnp.int32),
        S((1, skv), jnp.int32))


def test_decode_attention(one_chip):
    from repro.kernels.decode_attention.ops import decode_attention
    S = lambda *a: _spec(one_chip, *a)
    T = N_PAGES * PAGE
    _compile(lambda q, k, v, qp, kp: decode_attention(
        q, k, v, q_positions=qp, kv_positions=kp),
        S((B, 1, H, DH), jnp.bfloat16), S((B, T, HKV, DH), jnp.bfloat16),
        S((B, T, HKV, DH), jnp.bfloat16), S((B, 1), jnp.int32),
        S((B, T), jnp.int32))


@pytest.mark.parametrize("variant", ["single", "blocked", "fused"])
def test_paged_decode_attention(one_chip, variant):
    from repro.kernels.paged_decode_attention.ops import (
        fused_paged_decode_attention, paged_decode_attention)
    S = lambda *a: _spec(one_chip, *a)
    q = S((B, 1, H, DH), jnp.bfloat16)
    pool = S((POOL_PAGES, PAGE, HKV, DH), jnp.float32)
    pt, lens = S((B, N_PAGES), jnp.int32), S((B,), jnp.int32)
    if variant == "fused":
        new = S((B, HKV, DH), jnp.float32)
        _compile(lambda *a: fused_paged_decode_attention(*a)[0],
                 q, pool, pool, pt, lens, new, new)
    else:
        _compile(lambda *a: paged_decode_attention(*a, variant=variant),
                 q, pool, pool, pt, lens)


def _model_and_params(one_chip, impl):
    model = build_model(get_config("qwen3-1.7b").replace(attention_impl=impl))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return model, jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype), shapes)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@functools.lru_cache(maxsize=None)
def _decode_step(one_chip, impl):
    """The engine's paged decode step at full width, pool donated as on
    the chip; returns (compiled, the pool's shape)."""
    model, params = _model_and_params(one_chip, impl)
    S = lambda *a: _spec(one_chip, *a)
    layers, kv_heads, head_dim = model.paged_kv_layout()
    shape = (layers, POOL_PAGES, PAGE, kv_heads, head_dim)
    pool = S(shape, jnp.float32)
    compiled = _compile(
        lambda p, tok, kp, vp, pt, ln: model.paged_decode_step(
            p, tok, kp, vp, pt, ln),
        params, S((B,), jnp.int32), pool, pool, S((B, N_PAGES), jnp.int32),
        S((B,), jnp.int32), donate_argnums=(2, 3))
    return compiled, shape


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_full_width_decode_step(one_chip, impl):
    compiled, _ = _decode_step(one_chip, impl)
    assert _device_bytes(compiled) < HBM_LIMIT


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_full_width_decode_step_updates_pool_in_place(one_chip, impl):
    """The layer scan updates the donated pool in place: no pool-sized
    temporary (under one layer's K+V slice), both pools aliased to the
    outputs, and no copy of the whole pool in the optimized program."""
    compiled, shape = _decode_step(one_chip, impl)
    layer_kv = 2 * math.prod(shape[1:]) * 4
    pool = math.prod(shape) * 4
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < layer_kv
    assert m.alias_size_in_bytes >= 2 * pool
    hlo_shape = re.escape("f32[%s]" % ",".join(map(str, shape)))
    copies = re.findall(rf"= {hlo_shape}(?:{{[^}}]*}})? copy(?:-start)?\(",
                        compiled.as_text())
    assert not copies


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_full_width_prefill_step(one_chip, impl):
    """The engine's chunk prefill over a cache view (cold and shared
    prompts both take it)."""
    model, params = _model_and_params(one_chip, impl)
    S = lambda *a: _spec(one_chip, *a)
    layers, kv_heads, head_dim = model.paged_kv_layout()
    view = S((layers, 1, 128, kv_heads, head_dim), jnp.bfloat16)
    cache = {"k": view, "v": view, "length": S((1,), jnp.int32)}
    compiled = _compile(
        lambda p, toks, c, n: model.prefill_with_cache(p, toks, c,
                                                       valid_len=n),
        params, S((1, 48), jnp.int32), cache, S((1,), jnp.int32))
    assert _device_bytes(compiled) < HBM_LIMIT



def test_moe_decode_step_reads_its_experts_out_of_the_stack(one_chip,
                                                           monkeypatch):
    """The engine's paged decode step of deepseek-moe-16b at its
    published widths, 7 layers as one pipeline stage (the benchmark's
    deepseek-moe-16b-pp4), with the Pallas grouped matmul compiled for
    the chip: the layer scan hands each layer's grouped matmuls the
    stacked experts of all 6 MoE layers, so no op makes a layer's (64,
    d, F) copy, the temporaries stay under one layer's expert matrix,
    and the step fits the chip."""
    from repro.engine.models import moe as M
    monkeypatch.setattr(M, "env_interpret", lambda interpret: False)
    mc = get_config("deepseek-moe-16b").replace(num_layers=7)
    model = build_model(mc)
    params = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    S = lambda *a: _spec(one_chip, *a)
    layers, kv_heads, head_dim = model.paged_kv_layout()
    pool = S((layers, POOL_PAGES, PAGE, kv_heads, head_dim), jnp.float32)
    compiled = _compile(
        lambda p, tok, kp, vp, pt, ln: model.paged_decode_step(
            p, tok, kp, vp, pt, ln, count_experts=True),
        params, S((B,), jnp.int32), pool, pool, S((B, N_PAGES), jnp.int32),
        S((B,), jnp.int32), donate_argnums=(2, 3))
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') >= 3
    assert not re.search(r"= bf16\[64,(2048,1408|1408,2048)\]", hlo)
    m = mc.moe
    expert_matrix = m.num_experts * mc.d_model * m.d_ff_expert * 2
    assert compiled.memory_analysis().temp_size_in_bytes < expert_matrix
    assert _device_bytes(compiled) < HBM_LIMIT


@pytest.mark.parametrize("rows", [PAGE, POOL_PAGES * PAGE],
                         ids=["one-page", "whole-pool"])
@pytest.mark.parametrize("name, layers", [("qwen3-1.7b", None),
                                          ("deepseek-moe-16b", 7)],
                         ids=["qwen3-1.7b", "deepseek-moe-16b-pp4"])
def test_pool_write_updates_pool_in_place(one_chip, name, layers, rows):
    """The pool writer (prefilled, copy-on-write and imported pages) at
    its one-page and its largest row bucket, pools donated as on the
    chip: no pool-sized temporary (under one layer's K+V slice), both
    pools aliased to the outputs, and no copy of the whole pool."""
    from repro.engine.kvcache import pool_write
    cfg = get_config(name)
    if layers:
        cfg = cfg.replace(num_layers=layers)
    n_layers, kv_heads, head_dim = build_model(cfg).paged_kv_layout()
    S = lambda *a: _spec(one_chip, *a)
    shape = (n_layers, POOL_PAGES, PAGE, kv_heads, head_dim)
    pool = S(shape, jnp.float32)
    new = S((n_layers, rows, kv_heads, head_dim), jnp.bfloat16)
    compiled = _compile(pool_write, pool, pool, S((2, 1), jnp.int32),
                        new, new, S((2, rows), jnp.int32),
                        donate_argnums=(0, 1))
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 2 * math.prod(shape[1:]) * 4
    assert m.alias_size_in_bytes >= 2 * math.prod(shape) * 4
    hlo_shape = re.escape("f32[%s]" % ",".join(map(str, shape)))
    copies = re.findall(rf"= {hlo_shape}(?:{{[^}}]*}})? copy(?:-start)?\(",
                        compiled.as_text())
    assert not copies
