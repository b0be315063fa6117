"""The main path's Pallas kernels and decode step, compiled for a TPU v5e
that is described, not attached.

The TPU compiler is installed with JAX, so these tests compile for a
``v5e:2x2`` topology without a chip: they catch what interpret mode
cannot (block shapes the Mosaic tiling rule refuses, kernels that fall
out of the program, programs that do not fit the chip's 16 GiB).  Each
compile asserts the kernel is in the program as a ``tpu_custom_call``.
Nothing here runs, so no result or time is checked.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports every test file.  Keep these tests in this one file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import flash_decode
from repro.kernels.mla_decode import mla_decode
from repro.kernels.ops import flash_attention_trainable
from repro.models import api
from repro.serving.engine import decode_program

HEADS, HEAD_DIM, SEQ = 16, 64, 2048      # dipaco-150m: 16 x 64, T <= 2048
V5E_HBM = 16 * 2**30
V5E_OFFERED = 15.75e9                    # what the chip gives a program


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_flash_decode_compiles(one_chip, kv):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    # 8 rows at a traced offset into a 3-layer, 16-row stacked cache,
    # read at a traced layer
    b, layers, rows = 8, 3, 16
    q = s((b, HEADS, HEAD_DIM), jnp.bfloat16)
    ci = s((b,), jnp.int32)
    at = s((), jnp.int32)
    if kv == "bf16":
        cache = s((layers, rows, HEADS, HEAD_DIM, SEQ), jnp.bfloat16)
        _compile(lambda q, k, v, ci, layer, row0: flash_decode(
            q, k, v, ci, layer, row_offset=row0),
            q, cache, cache, ci, at, at)
    else:
        cache = s((layers, rows, HEADS, HEAD_DIM, SEQ), jnp.int8)
        scale = s((layers, rows, HEADS, SEQ), jnp.float32)
        _compile(lambda q, k, v, ci, layer, row0, ks, vs: flash_decode(
            q, k, v, ci, layer, row_offset=row0, k_scale=ks, v_scale=vs),
            q, cache, cache, ci, at, at, scale, scale)


def test_flash_attention_forward_compiles(one_chip):
    x = jax.ShapeDtypeStruct((2, SEQ, HEADS, HEAD_DIM), jnp.bfloat16,
                             sharding=one_chip)
    text = _compile(flash_attention_trainable, x, x, x).as_text()
    assert text.count("tpu_custom_call") == 1      # no backward kernels


def test_flash_attention_backward_compiles(one_chip):
    x = jax.ShapeDtypeStruct((2, SEQ, HEADS, HEAD_DIM), jnp.bfloat16,
                             sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(
            flash_attention_trainable(*a).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    text = _compile(grads, x, x, x).as_text()
    # forward + the dK/dV and dQ kernels
    assert text.count("tpu_custom_call") >= 3


def test_full_width_decode_step_fits_v5e(one_chip):
    """``api.decode_step`` at ``dipaco-150m`` widths, 32 rows x 1024
    cache, with ``flash_decode`` in every layer: the program fits one
    chip's HBM."""
    cfg = get_config("dipaco-150m").replace(attn_impl="pallas")
    rows, cache_len = 32, 1024

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = place(jax.eval_shape(
        lambda: api.init_model(jax.random.PRNGKey(0), cfg)[0]))
    cache = place(jax.eval_shape(
        lambda: api.init_serve_cache(cfg, rows, cache_len)))
    tok = jax.ShapeDtypeStruct((rows, 1), jnp.int32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    compiled = _compile(
        lambda p, t, c, i: api.decode_step(p, cfg, {"tokens": t}, c, i),
        params, tok, cache, idx)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM, mem


_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
          "u32": 4, "f32": 4}


def _largest_result(hlo: str, opcodes) -> tuple:
    """The largest result, in bytes, of an instruction with one of
    ``opcodes`` anywhere in the optimized HLO (fusion bodies included,
    so a fused whole-arena select counts too)."""
    found = (0, "")
    for m in re.finditer(r"%(\S+) = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(",
                         hlo):
        name, dtype, dims, op = m.groups()
        if op in opcodes:
            size = _BYTES.get(dtype, 4) * math.prod(
                int(d) for d in dims.split(",") if d)
            found = max(found, (size, name))
    return found


def test_engine_stacked_tick_is_in_place(one_chip):
    """The stacked decode tick ``ContinuousBatchingEngine`` dispatches
    (``engine.decode_program``), at ``dipaco-150m`` widths, 8 paths x 4
    slots x 1024 bf16 cache with ``flash_decode``: the KV arena is read
    in place by the kernel and written one token per row, so the program
    needs under 1 GB beside its arguments, and no select, copy or
    dynamic-slice produces as much as one layer's K for all rows."""
    cfg = get_config("dipaco-150m").replace(attn_impl="pallas")
    paths, slots, cache_len = 8, 4, 1024
    rows = paths * slots

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    one = jax.eval_shape(
        lambda: api.init_model(jax.random.PRNGKey(0), cfg)[0])
    params = place(jax.eval_shape(api.stack_paths, [one] * paths))
    cache = place(jax.eval_shape(
        lambda: api.init_serve_cache(cfg, rows, cache_len)))
    vec = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    compiled = decode_program(cfg, paths).lower(
        params, jax.ShapeDtypeStruct((rows, 1), jnp.int32, sharding=one_chip),
        cache, vec, jax.ShapeDtypeStruct((rows,), bool, sharding=one_chip),
    ).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1e9, mem
    layer_kv = rows * cfg.num_kv_heads * cfg.head_dim * cache_len * 2
    size, name = _largest_result(hlo, ("select", "copy", "dynamic-slice"))
    assert size < layer_kv, (name, size, layer_kv)


# Moonlight-16B-A3B as the benchmark serves it: 9 layers, 32 rows of an
# 8192-position latent arena, the Pallas kernels
MOONLIGHT_ROWS, MOONLIGHT_CACHE = 32, 8192


def _moonlight(one_chip):
    cfg = get_config("moonlight-16b-a3b").replace(num_layers=9,
                                                  attn_impl="pallas")

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    params = place(jax.eval_shape(
        lambda: api.init_model(jax.random.PRNGKey(0), cfg)[0]))
    cache = place(jax.eval_shape(lambda: api.init_serve_cache(
        cfg, MOONLIGHT_ROWS, MOONLIGHT_CACHE)))
    return cfg, params, cache


def _total(mem):
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def test_mla_decode_compiles(one_chip):
    """32 rows at a traced offset into a 3-layer, 64-row latent arena of
    576 x 8192, read at a traced layer."""
    q = jax.ShapeDtypeStruct((32, 16, 576), jnp.float32, sharding=one_chip)
    cache = jax.ShapeDtypeStruct((3, 64, 576, MOONLIGHT_CACHE), jnp.bfloat16,
                                 sharding=one_chip)
    ci = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip)
    at = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = _compile(lambda q, c, ci, layer, row0: mla_decode(
        q, c, ci, layer, kv_rank=512, scale=192 ** -0.5, row_offset=row0),
        q, cache, ci, at, at).as_text()
    assert "mla_decode" in text


def test_moonlight_decode_tick_fits_v5e(one_chip):
    """The engine's decode tick (one path: ``decode_program(cfg)``) at 32
    rows x 8192: 10.86 GB of weights and the 2.72 GB latent arena as
    arguments, the arena updated in place (aliased), and temporaries
    that fit beside them; the latent attention and the expert matmuls
    are the Pallas kernels."""
    cfg, params, cache = _moonlight(one_chip)
    vec = jax.ShapeDtypeStruct((MOONLIGHT_ROWS,), jnp.int32,
                               sharding=one_chip)
    compiled = decode_program(cfg).lower(
        params, jax.ShapeDtypeStruct((MOONLIGHT_ROWS, 1), jnp.int32,
                                     sharding=one_chip),
        cache, vec, jax.ShapeDtypeStruct((MOONLIGHT_ROWS,), bool,
                                         sharding=one_chip)).compile()
    hlo = compiled.as_text()
    assert re.search(r"%mla_decode[.\d]* = ", hlo)
    assert re.search(r"%gmm[.\d]* = ", hlo)
    mem = compiled.memory_analysis()
    arena = sum(a.size * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves(cache))
    assert mem.alias_size_in_bytes >= arena, mem
    assert _total(mem) < V5E_OFFERED, mem


def test_moonlight_largest_prefill_fits_v5e(one_chip):
    """The largest prefill bucket, one row of 8192 tokens (the engine's
    bucketed prefill: head at the last position, routing counts), fits
    beside the latent arena that stays resident."""
    cfg, params, cache = _moonlight(one_chip)
    compiled = jax.jit(lambda p, t, last: api.prefill(
        p, cfg, {"tokens": t}, MOONLIGHT_CACHE, last=last, counts=True)
    ).lower(params, jax.ShapeDtypeStruct((1, MOONLIGHT_CACHE), jnp.int32,
                                         sharding=one_chip),
            jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    ).compile()
    assert re.search(r"%gmm[.\d]* = ", compiled.as_text())
    arena = sum(a.size * a.dtype.itemsize
                for a in jax.tree_util.tree_leaves(cache))
    assert _total(compiled.memory_analysis()) + arena < V5E_OFFERED
