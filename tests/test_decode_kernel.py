"""Flash-decode kernel parity: Pallas (interpret mode) vs the dense
ref.py oracle vs the model's jnp ring-cache branch — GQA group sizes,
ring wrap-around, sliding windows, int8 KV, per-row (B,) positions, and
the in-place read of one layer's rows out of a layer-stacked cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import flash_decode


# the kernel reads layer LAYER, rows ROW0.. of a (LAYERS, b + ROW0 + 1,
# KH, D, T) stack; the oracle gets that slice alone, so a wrong index
# map reads other layers' or rows' keys and fails the comparison
LAYERS, LAYER, ROW0 = 3, 1, 1


def _setup(key, b, h, kh, d, T, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, h, d)).astype(dtype)
    kc = jax.random.normal(ks[1], (LAYERS, b + ROW0 + 1, kh, d, T)
                           ).astype(dtype)
    vc = jax.random.normal(ks[2], (LAYERS, b + ROW0 + 1, kh, d, T)
                           ).astype(dtype)
    return q, kc, vc


def _mine(c, b):
    """The kernel's slice of a stacked cache, as a one-layer stack."""
    return c[LAYER:LAYER + 1, ROW0:ROW0 + b]


@pytest.mark.parametrize("b,h,kh,d,T,ci,window,block_k", [
    (2, 4, 4, 32, 32, [5, 20], None, 8),        # MHA, mid-cache
    (3, 8, 2, 64, 64, [0, 31, 63], None, 16),   # GQA g=4, full cache
    (2, 4, 1, 32, 48, [10, 40], None, 16),      # MQA
    (2, 4, 2, 32, 32, [40, 70], None, 8),       # ring wrap (ci > T)
    (2, 4, 2, 32, 32, [12, 45], 8, 8),          # sliding window + wrap
    (1, 2, 2, 16, 24, [3], 16, 128),            # block_k > T (shrinks)
    (2, 4, 2, 32, 40, [7, 90], 12, 8),          # non-pow2 T, deep wrap
])
def test_flash_decode_vs_ref(b, h, kh, d, T, ci, window, block_k):
    q, kc, vc = _setup(jax.random.PRNGKey(0), b, h, kh, d, T)
    ci = jnp.asarray(ci, jnp.int32)
    out = flash_decode(q, kc, vc, ci, LAYER, row_offset=ROW0, window=window,
                       block_k=block_k, interpret=True)
    expect = ref.flash_decode_ref(q, _mine(kc, b), _mine(vc, b), ci,
                                  window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_flash_decode_dtypes(dtype, tol):
    q, kc, vc = _setup(jax.random.PRNGKey(1), 2, 8, 4, 64, 32, dtype)
    ci = jnp.asarray([9, 27], jnp.int32)
    out = ops.decode_attention(q, kc, vc, ci, LAYER, row_offset=ROW0,
                               block_k=16, interpret=True)
    expect = ref.flash_decode_ref(q, _mine(kc, 2), _mine(vc, 2), ci)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [None, 8])
def test_flash_decode_int8_kv(window):
    """Fused in-kernel dequantization == dequantize-then-dense oracle."""
    b, h, kh, d, T = 2, 4, 2, 32, 32
    q, kc, vc = _setup(jax.random.PRNGKey(2), b, h, kh, d, T)

    def quant(x):          # per-(token, head) scales over D (axis -2)
        scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-2) / 127.0, 1e-8)
        qx = jnp.clip(jnp.round(x / scale[..., None, :]), -127, 127)
        return qx.astype(jnp.int8), scale

    kq, ks = quant(kc)
    vq, vs = quant(vc)
    ci = jnp.asarray([6, 50], jnp.int32)
    out = ops.decode_attention(q, kq, vq, ci, LAYER, row_offset=ROW0,
                               window=window, k_scale=ks, v_scale=vs,
                               block_k=8, interpret=True)
    expect = ref.flash_decode_ref(
        q, _mine(kq, b), _mine(vq, b), ci, window=window,
        k_scale=_mine(ks, b), v_scale=_mine(vs, b))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("kv_quant,window", [
    (False, None), (False, 12), (True, None), (True, 12),
])
def test_kernel_matches_jnp_cache_branch(kv_quant, window):
    """cfg.attn_impl='pallas' decode == the jnp masked-einsum cache
    branch, through the full apply_attention entry point, at per-row
    positions including ring wrap."""
    from repro.configs import get_smoke_config
    from repro.models.layers import apply_attention, init_attention
    from repro.models.lm import init_decode_cache
    cfg = get_smoke_config("dipaco-150m").replace(kv_quant=kv_quant)
    key = jax.random.PRNGKey(3)
    p, _ = init_attention(key, cfg)
    T, b = 16, 3
    cache = init_decode_cache(cfg, b, T)["pos0"]
    cache = jax.tree_util.tree_map(lambda x: x[0], cache)  # un-stack reps
    # build distinct per-row histories, wrapping the ring for row 2
    positions = np.asarray([3, 14, 29], np.int32)
    for t in range(int(positions.max()) + 1):
        x = jax.random.normal(jax.random.fold_in(key, t),
                              (b, 1, cfg.d_model), jnp.float32)
        step = jnp.minimum(jnp.asarray(t, jnp.int32), positions)
        out_j, cache_j = apply_attention(
            p, cfg.replace(attn_impl="full"), x, positions=step[:, None],
            window=window, cache=cache, cache_index=step)
        out_k, cache_k = apply_attention(
            p, cfg.replace(attn_impl="pallas", pallas_interpret=True), x,
            positions=step[:, None], window=window, cache=cache,
            cache_index=step)
        np.testing.assert_allclose(np.asarray(out_j), np.asarray(out_k),
                                   atol=1e-5, rtol=1e-5)
        for a, bb in zip(jax.tree_util.tree_leaves(cache_j),
                         jax.tree_util.tree_leaves(cache_k)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(bb, np.float32),
                                       atol=1e-6, rtol=1e-6)
        cache = cache_j


def test_decode_under_vmap():
    """The kernel batches correctly under vmap (the stacked-worker
    decode of ``launch.steps.make_decode_step`` vmaps the whole decode
    step over a worker axis)."""
    P, b, h, kh, d, T = 2, 3, 4, 2, 32, 24
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (P, b, h, d))
    kc = jax.random.normal(ks[1], (P, 1, b, kh, d, T))
    vc = jax.random.normal(ks[2], (P, 1, b, kh, d, T))
    ci = jnp.asarray([[0, 10, 30], [5, 23, 47]], jnp.int32)
    f = jax.vmap(lambda q_, k_, v_, c_: flash_decode(
        q_, k_, v_, c_, block_k=8, interpret=True))
    out = jax.jit(f)(q, kc, vc, ci)
    expect = jax.vmap(lambda q_, k_, v_, c_: ref.flash_decode_ref(
        q_, k_, v_, c_))(q, kc, vc, ci)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)


def test_multi_token_ring_wrap_raises():
    """A prefill block that would wrap the ring is rejected loudly
    instead of silently overwriting its own oldest entries."""
    from repro.configs import get_smoke_config
    from repro.models.layers import apply_attention, init_attention
    from repro.models.lm import init_decode_cache
    cfg = get_smoke_config("dipaco-150m")
    p, _ = init_attention(jax.random.PRNGKey(5), cfg)
    T, s = 16, 6
    cache = init_decode_cache(cfg, 1, T)["pos0"]
    cache = jax.tree_util.tree_map(lambda x: x[0], cache)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, s, cfg.d_model))
    pos = jnp.arange(12, 12 + s)[None, :]
    with pytest.raises(ValueError, match="wraps the ring"):
        apply_attention(p, cfg, x, positions=pos, cache=cache,
                        cache_index=jnp.int32(12))  # 12 % 16 + 6 > 16
    with pytest.raises(ValueError, match="exceeds cache length"):
        apply_attention(
            p, cfg,
            jax.random.normal(jax.random.PRNGKey(7), (1, 20, cfg.d_model)),
            positions=jnp.arange(20)[None, :], cache=cache,
            cache_index=jnp.int32(0))
    # a non-wrapping block at the same start is fine
    out, _ = apply_attention(p, cfg, x[:, :4], positions=pos[:, :4],
                             cache=cache, cache_index=jnp.int32(12))
    assert out.shape == (1, 4, cfg.d_model)
