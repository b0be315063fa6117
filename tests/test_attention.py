"""Attention equivalences: chunked online-softmax vs full reference,
causal-skip variant, windows, GQA/MQA; decode ring-cache consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.layers import chunked_attention, full_attention


def _qkv(key, b, s, h, kh, d):
    k1, k2, k3 = jax.random.split(key, 3)
    return (jax.random.normal(k1, (b, s, h, d)),
            jax.random.normal(k2, (b, s, kh, d)),
            jax.random.normal(k3, (b, s, kh, d)))


@pytest.mark.parametrize("s,h,kh,d,window,skip", [
    (96, 4, 4, 32, None, False),
    (100, 4, 2, 32, None, True),
    (128, 8, 1, 16, 33, False),
    (64, 4, 2, 64, 16, True),
    (257, 2, 1, 32, None, True),
])
def test_chunked_matches_full(s, h, kh, d, window, skip):
    q, k, v = _qkv(jax.random.PRNGKey(0), 2, s, h, kh, d)
    ref = full_attention(q, k, v, causal=True, window=window)
    out = chunked_attention(q, k, v, causal=True, window=window,
                            chunk_q=32, chunk_k=32, causal_skip=skip)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               atol=2e-5, rtol=2e-5)


@settings(max_examples=15, deadline=None)
@given(s=st.integers(16, 130), chunk=st.sampled_from([16, 32, 64]),
       window=st.one_of(st.none(), st.integers(4, 64)),
       skip=st.booleans())
def test_chunked_property(s, chunk, window, skip):
    q, k, v = _qkv(jax.random.PRNGKey(s), 1, s, 2, 1, 16)
    ref = full_attention(q, k, v, causal=True, window=window)
    out = chunked_attention(q, k, v, causal=True, window=window,
                            chunk_q=chunk, chunk_k=chunk, causal_skip=skip)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               atol=3e-5, rtol=3e-5)


def test_decode_matches_forward():
    """Greedy decode-with-cache logits == full-forward logits."""
    from repro.configs import get_smoke_config
    from repro.models import api
    from repro.models.lm import apply_lm, decode_step, init_decode_cache
    cfg = get_smoke_config("qwen3-8b")
    key = jax.random.PRNGKey(0)
    params, _ = api.init_model(key, cfg)
    tokens = jax.random.randint(key, (2, 12), 0, cfg.vocab_size)
    full_logits, _ = apply_lm(params, cfg, tokens)
    cache = init_decode_cache(cfg, 2, 16)
    for t in range(tokens.shape[1]):
        step_logits, cache = decode_step(params, cfg, tokens[:, t:t + 1],
                                         cache, jnp.int32(t))
        np.testing.assert_allclose(np.asarray(full_logits[:, t]),
                                   np.asarray(step_logits[:, 0]),
                                   atol=2e-4, rtol=2e-3)


def test_decode_ring_window():
    """Windowed ring cache: decode beyond cache_len stays consistent
    with a windowed full forward."""
    from repro.configs import get_smoke_config
    from repro.models import api
    from repro.models.lm import apply_lm, decode_step, init_decode_cache
    W = 8
    cfg = get_smoke_config("qwen3-8b").replace(sliding_window=W)
    key = jax.random.PRNGKey(3)
    params, _ = api.init_model(key, cfg)
    T = 20
    tokens = jax.random.randint(key, (1, T), 0, cfg.vocab_size)
    full_logits, _ = apply_lm(params, cfg, tokens, window=W)
    cache = init_decode_cache(cfg, 1, W)  # ring cache = window size
    for t in range(T):
        step_logits, cache = decode_step(params, cfg, tokens[:, t:t + 1],
                                         cache, jnp.int32(t), window=W)
        np.testing.assert_allclose(np.asarray(full_logits[:, t]),
                                   np.asarray(step_logits[:, 0]),
                                   atol=3e-4, rtol=3e-3)


def test_mamba_decode_matches_forward():
    from repro.configs import get_smoke_config
    from repro.models import api
    from repro.models.lm import apply_lm, decode_step, init_decode_cache
    cfg = get_smoke_config("mamba2-1.3b")
    key = jax.random.PRNGKey(1)
    params, _ = api.init_model(key, cfg)
    tokens = jax.random.randint(key, (2, 10), 0, cfg.vocab_size)
    full_logits, _ = apply_lm(params, cfg, tokens)
    cache = init_decode_cache(cfg, 2, 16)
    for t in range(tokens.shape[1]):
        step_logits, cache = decode_step(params, cfg, tokens[:, t:t + 1],
                                         cache, jnp.int32(t))
        np.testing.assert_allclose(np.asarray(full_logits[:, t]),
                                   np.asarray(step_logits[:, 0]),
                                   atol=5e-4, rtol=5e-3)
