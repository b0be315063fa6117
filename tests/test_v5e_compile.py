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
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import flash_decode
from repro.kernels.ops import flash_attention_trainable
from repro.models import api

HEADS, HEAD_DIM, SEQ = 16, 64, 2048      # dipaco-150m: 16 x 64, T <= 2048
V5E_HBM = 16 * 2**30


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

    b = 8
    q = s((b, HEADS, HEAD_DIM), jnp.bfloat16)
    ci = s((b,), jnp.int32)
    if kv == "bf16":
        cache = s((b, SEQ, HEADS, HEAD_DIM), jnp.bfloat16)
        _compile(lambda q, k, v, ci: flash_decode(q, k, v, ci),
                 q, cache, cache, ci)
    else:
        cache = s((b, SEQ, HEADS, HEAD_DIM), jnp.int8)
        scale = s((b, SEQ, HEADS), jnp.float32)
        _compile(lambda q, k, v, ci, ks, vs: flash_decode(
            q, k, v, ci, k_scale=ks, v_scale=vs),
            q, cache, cache, ci, scale, scale)


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
