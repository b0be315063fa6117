"""Token-MoE dispatch equivalences and SSD correctness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.models import api
from repro.models.moe_layer import (init_moe, moe_dense_dispatch,
                                    moe_scatter_dispatch)
from repro.models.ssm import ssd_chunked


def _moe_setup(key, num_experts=4, top_k=2, cap=8.0):
    from repro.models.config import MoEConfig
    cfg = get_smoke_config("qwen3-moe-235b-a22b")
    cfg = cfg.replace(moe=MoEConfig(num_experts=num_experts, top_k=top_k,
                                    d_ff_expert=64,
                                    capacity_factor=cap))
    p, _ = init_moe(key, cfg)
    return cfg, p


def test_dense_vs_scatter_dispatch_equal_at_high_capacity():
    """With capacity high enough that nothing drops, the GShard one-hot
    path and the scatter path compute the same function."""
    key = jax.random.PRNGKey(0)
    cfg, p = _moe_setup(key, cap=8.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    y1, a1, e1 = moe_dense_dispatch(p, cfg, x, group_size=64)
    y2, a2, e2 = moe_scatter_dispatch(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(float(a1), float(a2), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 20), e=st.sampled_from([2, 4]),
       k=st.sampled_from([1, 2]))
def test_moe_gate_weights_partition_of_unity(seed, e, k):
    """Top-k gates are renormalized: output is a convex combination, so
    output magnitude stays bounded by the max single-expert output."""
    key = jax.random.PRNGKey(seed)
    cfg, p = _moe_setup(key, num_experts=e, top_k=k, cap=8.0)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, 16, cfg.d_model))
    y, aux, _ = moe_scatter_dispatch(p, cfg, x)
    assert np.isfinite(np.asarray(y)).all()
    assert float(aux) >= 0.0


def test_moe_aux_loss_balanced_router_is_minimal():
    """Uniform routing gives aux ~ router_aux_weight (the E*sum(f*p)
    lower bound)."""
    key = jax.random.PRNGKey(3)
    cfg, p = _moe_setup(key)
    # random inputs -> near-uniform; aux should be within 2x of minimum
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 64, cfg.d_model))
    _, aux, _ = moe_dense_dispatch(p, cfg, x, group_size=64)
    assert float(aux) < cfg.moe.router_aux_weight * 3.0


def test_ssd_matches_naive_recurrence():
    """Chunked SSD == token-by-token linear recurrence."""
    b, s, h, p, n = 1, 32, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, 1, n)) * 0.5
    C = jax.random.normal(ks[4], (b, s, 1, n)) * 0.5
    y, final = ssd_chunked(x, dt, A, B, C, chunk=8)
    # naive recurrence
    state = np.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        dA = np.exp(np.asarray(dt[:, t]) * np.asarray(A)[None])  # (b,h)
        Bt = np.repeat(np.asarray(B[:, t]), h, axis=1)           # (b,h,n)
        Ct = np.repeat(np.asarray(C[:, t]), h, axis=1)
        xdt = np.asarray(x[:, t]) * np.asarray(dt[:, t])[..., None]
        state = state * dA[..., None, None] \
            + xdt[..., None] * Bt[:, :, None, :]
        ys.append(np.einsum("bhpn,bhn->bhp", state, Ct))
    y_naive = np.stack(ys, 1)
    np.testing.assert_allclose(np.asarray(y), y_naive, atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(final), state, atol=2e-4,
                               rtol=1e-3)


def test_ssd_chunk_invariance():
    """Different chunk sizes give the same result."""
    b, s, h, p, n = 2, 48, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, 1, n)) * 0.5
    C = jax.random.normal(ks[4], (b, s, 1, n)) * 0.5
    y1, f1 = ssd_chunked(x, dt, A, B, C, chunk=8)
    y2, f2 = ssd_chunked(x, dt, A, B, C, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), atol=2e-4,
                               rtol=1e-3)
