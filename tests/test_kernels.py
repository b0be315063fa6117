"""Per-kernel validation: shape/dtype sweeps, assert_allclose against the
pure-jnp ref.py oracles, executed in interpret mode (CPU container)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("s,h,kh,d,dtype,causal,window", [
    (128, 4, 4, 32, jnp.float32, True, None),
    (256, 8, 2, 64, jnp.float32, True, 48),
    (128, 4, 1, 64, jnp.bfloat16, True, None),
    (256, 2, 2, 128, jnp.float32, False, None),
    (128, 4, 2, 32, jnp.bfloat16, True, 32),
])
def test_flash_attention(s, h, kh, d, dtype, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, s, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (2, s, kh, d)).astype(dtype)
    v = jax.random.normal(ks[2], (2, s, kh, d)).astype(dtype)
    out = ops.flash_attention_trainable(q, k, v, causal=causal,
                                        window=window, block_q=64,
                                        block_k=64, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("n,d,k,dtype", [
    (513, 32, 8, jnp.float32),
    (1000, 64, 16, jnp.float32),
    (256, 128, 4, jnp.bfloat16),
])
def test_router_assign(n, d, k, dtype):
    z = jax.random.normal(jax.random.PRNGKey(0), (n, d)).astype(dtype)
    c = jax.random.normal(jax.random.PRNGKey(1), (k, d)).astype(dtype)
    a, d2 = ops.router_assign(z, c, block_n=128, interpret=True)
    ea, ed2 = ref.router_assign_ref(z, c)
    assert (np.asarray(a) == np.asarray(ea)).mean() > 0.999
    np.testing.assert_allclose(np.asarray(d2), np.asarray(ed2),
                               atol=1e-2, rtol=1e-2)


@pytest.mark.parametrize("s,h,p,n,chunk,dtype", [
    (128, 2, 32, 16, 32, jnp.float32),
    (256, 4, 64, 32, 64, jnp.float32),
    (128, 2, 32, 16, 64, jnp.bfloat16),
])
def test_ssd_scan(s, h, p, n, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = (jax.random.normal(ks[0], (2, s, h, p)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, s, h))).astype(dtype)
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bm = (jax.random.normal(ks[3], (2, s, h, n)) * 0.5).astype(dtype)
    cm = (jax.random.normal(ks[4], (2, s, h, n)) * 0.5).astype(dtype)
    y = ops.ssd_scan(x, dt, a, bm, cm, chunk=chunk, interpret=True)
    ey = ref.ssd_scan_ref(x, dt, a, bm, cm, chunk=chunk)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    scale = float(jnp.abs(ey.astype(jnp.float32)).max())
    np.testing.assert_allclose(np.asarray(y, np.float32) / scale,
                               np.asarray(ey, np.float32) / scale,
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("e,c,d,f,dtype", [
    (4, 128, 256, 128, jnp.float32),
    (2, 256, 512, 256, jnp.bfloat16),
    (8, 128, 128, 512, jnp.float32),
])
def test_expert_gemm(e, c, d, f, dtype):
    xe = jax.random.normal(jax.random.PRNGKey(0), (e, c, d)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (e, d, f)).astype(dtype)
    out = ops.expert_gemm(xe, w, block_m=64, block_n=64, block_k=128,
                          interpret=True)
    expect = ref.expert_gemm_ref(xe, w)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    scale = max(float(jnp.abs(expect.astype(jnp.float32)).max()), 1.0)
    np.testing.assert_allclose(np.asarray(out, np.float32) / scale,
                               np.asarray(expect, np.float32) / scale,
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("s,h,kh,d,causal,window", [
    (128, 4, 2, 32, True, None),
    (96, 2, 1, 64, True, 24),
    (64, 4, 4, 32, False, None),
    (80, 2, 2, 32, True, None),     # ragged tail: s not a block multiple
    (64, 4, 2, 32, False, 16),      # non-causal sliding window + GQA
])
def test_flash_attention_backward(s, h, kh, d, causal, window):
    """custom_vjp Pallas backward vs autodiff of the full oracle."""
    from repro.kernels.flash_attention_bwd import flash_attention_trainable
    from repro.models.layers import full_attention
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (2, s, h, d))
    k = jax.random.normal(ks[1], (2, s, kh, d))
    v = jax.random.normal(ks[2], (2, s, kh, d))
    do = jax.random.normal(ks[3], (2, s, h, d))

    def f_ref(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=causal,
                                      window=window) * do)

    def f_ker(q, k, v):
        return jnp.sum(flash_attention_trainable(
            q, k, v, causal, window, 32, 32, True) * do)

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_ker = jax.grad(f_ker, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_ker):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


def test_pallas_attn_impl_in_model():
    """cfg.attn_impl='pallas' path end-to-end equals the xla path."""
    from repro.configs import get_smoke_config
    from repro.models import api
    cfg = get_smoke_config("qwen3-8b")
    key = jax.random.PRNGKey(0)
    params, _ = api.init_model(key, cfg)
    batch = {"tokens": jax.random.randint(key, (2, 100), 0, cfg.vocab_size)}
    l1, _ = api.forward_logits(params, cfg.replace(attn_impl="full"), batch)
    l2, _ = api.forward_logits(
        params, cfg.replace(attn_impl="pallas", pallas_interpret=True), batch)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               atol=2e-4, rtol=2e-3)


def test_pallas_never_interprets_unasked():
    """Off the TPU, a compiled kernel refuses instead of silently
    running the interpreter: interpret mode is the caller's choice."""
    from repro.configs import get_smoke_config
    from repro.models import api
    cfg = get_smoke_config("dipaco-150m").replace(attn_impl="pallas")
    key = jax.random.PRNGKey(0)
    params, _ = api.init_model(key, cfg)
    batch = {"tokens": jax.random.randint(key, (2, 16), 0, cfg.vocab_size)}
    with pytest.raises(ValueError, match="interpret"):
        api.forward_logits(params, cfg, batch)
