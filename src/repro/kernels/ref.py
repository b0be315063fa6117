"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal=True, window=None):
    """Same semantics as kernels.flash_attention (GQA via kv repeat)."""
    from repro.models.layers import full_attention
    return full_attention(q, k, v, causal=causal, window=window)


def flash_decode_ref(q, k_cache, v_cache, cache_index, layer=0, *,
                     row_offset=0, window=None, k_scale=None, v_scale=None):
    """Dense oracle for kernels.decode_attention: single-token GQA over
    one layer of a layer-stacked ring cache ``(L, N, KH, D, T)``, query
    row b on cache row ``row_offset + b``, with per-row positions and
    optional int8 KV scales ``(L, N, KH, T)``."""
    NEG_INF = -1e30
    b, h, d = q.shape
    kh, T = k_cache.shape[2], k_cache.shape[4]
    g = h // kh

    def rows(c):
        return jax.lax.dynamic_slice_in_dim(c[layer], row_offset, b, 0)

    ci = jnp.asarray(cache_index, jnp.int32).reshape(b)
    kf = rows(k_cache).astype(jnp.float32)               # (B, KH, D, T)
    vf = rows(v_cache).astype(jnp.float32)
    if k_scale is not None:
        kf = kf * rows(k_scale).astype(jnp.float32)[:, :, None, :]
        vf = vf * rows(v_scale).astype(jnp.float32)[:, :, None, :]
    qg = q.reshape(b, kh, g, d).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bkdt->bkgt", qg, kf) / math.sqrt(d)
    slot = jnp.arange(T)[None, :]
    idx_last = (ci % T)[:, None]
    abs_pos = jnp.where(slot <= idx_last, ci[:, None] - idx_last + slot,
                        ci[:, None] - idx_last - T + slot)     # (B, T)
    valid = (abs_pos >= 0) & (abs_pos <= ci[:, None])
    if window is not None:
        valid &= abs_pos > ci[:, None] - window
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,bkdt->bkgd", p, vf)
    return out.reshape(b, h, d).astype(q.dtype)


def router_assign_ref(z, centroids):
    z = z.astype(jnp.float32)
    c = centroids.astype(jnp.float32)
    d2 = (jnp.sum(z * z, -1, keepdims=True) - 2 * z @ c.T
          + jnp.sum(c * c, -1)[None, :])
    return jnp.argmin(d2, -1).astype(jnp.int32), jnp.min(d2, -1)


def ssd_scan_ref(x, dt, a, bmat, cmat, *, chunk=128):
    """Per-head-broadcast SSD; delegates to the model's chunked oracle."""
    from repro.models.ssm import ssd_chunked
    y, _ = ssd_chunked(x, dt, a, bmat, cmat, chunk)
    return y


def expert_gemm_ref(xe, w):
    return jnp.einsum("ecd,edf->ecf", xe.astype(jnp.float32),
                      w.astype(jnp.float32)).astype(xe.dtype)
