"""Core neural-net layers in pure JAX: norms, RoPE, attention, MLPs.

Every ``init_*`` returns ``(params, axes)`` where axes mirror params with
logical sharding-axis tuples (see models/params.py).  ``apply`` functions
are pure.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import NamedSharding, PartitionSpec

from . import params as P
from .config import ModelConfig

NEG_INF = -1e30


def _layout_of(x):
    """``x``'s sharding under explicit mesh axes, else None.

    With explicit axes, an op whose contraction (or gather) runs over a
    sharded dimension has no unambiguous output sharding and must be
    told one; the block's activations keep their input layout."""
    sh = jax.typeof(x).sharding
    return None if sh.mesh.empty else sh


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def init_rmsnorm(dim: int):
    return jnp.ones((dim,)), (P.EMBED,)


def rms_norm(scale, x, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float):
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)  # (head_dim//2,)


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (d//2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, d//2)
    cos = jnp.cos(angles)[..., :, None, :]             # (..., S, 1, d//2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, cross: bool = False):
    d, h, kh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d)
    p = {
        "wq": jax.random.normal(ks[0], (d, h, hd)) * s_in,
        "wk": jax.random.normal(ks[1], (d, kh, hd)) * s_in,
        "wv": jax.random.normal(ks[2], (d, kh, hd)) * s_in,
        "wo": jax.random.normal(ks[3], (h, hd, d)) * (1.0 / math.sqrt(h * hd)),
    }
    a = {
        "wq": (P.EMBED, P.HEADS, P.HEAD_DIM),
        "wk": (P.EMBED, P.KV_HEADS, P.HEAD_DIM),
        "wv": (P.EMBED, P.KV_HEADS, P.HEAD_DIM),
        "wo": (P.HEADS, P.HEAD_DIM, P.EMBED),
    }
    if cfg.qk_norm:
        p["q_norm"], a["q_norm"] = jnp.ones((hd,)), (P.HEAD_DIM,)
        p["k_norm"], a["k_norm"] = jnp.ones((hd,)), (P.HEAD_DIM,)
    return p, a


def _gqa_scores(q, k):
    """q: (B,Sq,KH,G,D), k: (B,Sk,KH,D) -> (B,KH,G,Sq,Sk)."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                      preferred_element_type=jnp.float32)


def _gqa_out(p, v):
    """p: (B,KH,G,Sq,Sk), v: (B,Sk,KH,D) -> (B,Sq,KH,G,D)."""
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(p.dtype))


def full_attention(q, k, v, *, causal: bool, window: Optional[int],
                   q_offset: int = 0):
    """Reference O(S^2)-memory attention.  q: (B,Sq,H,D), k: (B,Sk,KH,D),
    v: (B,Sk,KH,Dv); scores scaled by 1/sqrt(D)."""
    b, sq, h, d = q.shape
    sk, kh = k.shape[1], k.shape[2]
    g = h // kh
    qg = q.reshape(b, sq, kh, g, d)
    scores = _gqa_scores(qg, k) / math.sqrt(d)
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(p, v)
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def chunked_attention(q, k, v, *, causal: bool, window: Optional[int],
                      chunk_q: int = 512, chunk_k: int = 512,
                      causal_skip: bool = False):
    """Online-softmax blockwise attention; O(S*chunk) activation memory.
    q, k: (B, S, H|KH, D); v: (B, S, KH, Dv).

    With ``causal_skip`` the fully-masked (future) key chunks are
    structurally skipped (flops ~ S^2/2 instead of S^2), and with a
    window also the fully-expired past chunks are skipped.
    """
    b, s, h, d = q.shape
    kh, dv = k.shape[2], v.shape[-1]
    g = h // kh
    nq = -(-s // chunk_q)
    pad_q = nq * chunk_q - s
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    nk = -(-k.shape[1] // chunk_k)
    pad_k = nk * chunk_k - k.shape[1]
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    sk_pad = nk * chunk_k
    qc = q.reshape(b, nq, chunk_q, kh, g, d).astype(jnp.float32)
    kc = k.reshape(b, nk, chunk_k, kh, d).astype(jnp.float32)
    vc = v.reshape(b, nk, chunk_k, kh, dv).astype(jnp.float32)
    scale = 1.0 / math.sqrt(d)
    kpos_all = jnp.arange(sk_pad).reshape(nk, chunk_k)
    valid_k = kpos_all < (sk_pad - pad_k)

    def combine(carry, j, qi, i):
        m, l, acc = carry
        kj, vj = kc[:, j], vc[:, j]
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qi, kj) * scale
        qpos = i * chunk_q + jnp.arange(chunk_q)
        kpos = kpos_all[j]
        mask = valid_k[j][None, :]
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
        if window is not None:
            mask = mask & (kpos[None, :] > qpos[:, None] - window)
        scores = jnp.where(mask, scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bkgqs,bskd->bkgqd", p, vj)
        return (m_new, l, acc)

    def q_block(i_static):
        qi = qc[:, i_static]
        m0 = jnp.full((b, kh, g, chunk_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, g, chunk_q), jnp.float32)
        a0 = jnp.zeros((b, kh, g, chunk_q, dv), jnp.float32)
        if causal_skip:
            lo = 0
            if window is not None:
                lo = max(0, (i_static * chunk_q - window) // chunk_k)
            hi = min(nk, ((i_static + 1) * chunk_q - 1) // chunk_k + 1) \
                if causal else nk
            js = jnp.arange(lo, max(hi, lo + 1))
            carry = (m0, l0, a0)
            carry, _ = jax.lax.scan(
                lambda c, j: (combine(c, j, qi, i_static), None), carry, js)
        else:
            carry = (m0, l0, a0)
            carry, _ = jax.lax.scan(
                lambda c, j: (combine(c, j, qi, i_static), None),
                carry, jnp.arange(nk))
        m, l, acc = carry
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out  # (b, kh, g, chunk_q, d)

    if causal_skip:
        blocks = [q_block(i) for i in range(nq)]
        out = jnp.stack(blocks, axis=3)  # (b, kh, g, nq, cq, d)
    else:
        out = jax.lax.map(lambda i: q_block(i), jnp.arange(nq))  # (nq,b,kh,g,cq,d)
        out = jnp.moveaxis(out, 0, 3)
    out = out.reshape(b, kh, g, nq * chunk_q, dv)
    out = jnp.moveaxis(out, 3, 1).reshape(b, nq * chunk_q, kh * g, dv)
    return out[:, :s].astype(q.dtype)


def attention_qkv(p, cfg: ModelConfig, x, *, positions, kv_x=None):
    """Projections, optional qk-norm and (self-attention only) RoPE:
    q (B, S, H, D), k and v (B, Sk, KH, D)."""
    src = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps)
    if kv_x is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_out(p, out, x):
    """Output projection of (B, S, H, D) heads back to x's width."""
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype),
                      out_sharding=_layout_of(x))


def _quant_kv(x):
    """int8 KV, per-(token, head) absmax scales (§Perf iteration N7:
    halves the decode HBM traffic): x (..., KH, D) -> (int8 (..., KH, D),
    f32 scales (..., KH))."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
                        / 127.0, 1e-8)
    qx = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return qx, scale[..., 0]


def _slot_positions(last, T: int):
    """The absolute position each ring slot holds, per row: (B, T), from
    each row's position of the *last* token written, ``last`` (B,);
    negative where nothing was written yet."""
    slot = jnp.arange(T)[None, :]
    idx_last = (last % T)[:, None]
    return jnp.where(slot <= idx_last, last[:, None] - idx_last + slot,
                     last[:, None] - idx_last - T + slot)


def _put_tokens(buf, val, layer, row_offset, slot, mask):
    """Write row r's token ``val[r]`` into a layer-stacked, tokens-minor
    buffer ``(L, N, ..., T)`` at ``(layer, row_offset + r, ..., slot[r])``;
    where ``mask`` (B,) is False, the value already there.

    One dynamic_update_slice per row: XLA keeps the carried buffer in
    the kernel's layout and updates it in place (a scatter of all rows
    at once makes it re-lay-out the whole arena around the kernel, for a
    D-minor scatter)."""
    val = val.astype(buf.dtype)
    for r in range(val.shape[0]):
        at = (layer, row_offset + r) + (0,) * (buf.ndim - 3) + (slot[r],)
        tok = val[r][None, None, ..., None]
        if mask is not None:
            tok = jnp.where(mask[r], tok,
                            jax.lax.dynamic_slice(buf, at, tok.shape))
        buf = jax.lax.dynamic_update_slice(buf, tok, at)
    return buf


def _cache_attention(cfg: ModelConfig, q, cache, ci, window):
    """Dense (jnp) attention of the s tokens at positions ``ci .. ci+s-1``
    (already written) over one layer's ring cache, tokens minor: k/v
    (B, KH, D, T), int8 scales (B, KH, T).  q: (B, S, H, D) -> same."""
    b, s = q.shape[:2]
    ck, cv = cache["k"], cache["v"]
    kh, T = ck.shape[1], ck.shape[3]
    if "k_scale" in cache:
        ck = (ck.astype(jnp.float32)
              * cache["k_scale"][:, :, None, :]).astype(q.dtype)
        cv = (cv.astype(jnp.float32)
              * cache["v_scale"][:, :, None, :]).astype(q.dtype)
    g = cfg.num_heads // kh
    qg = q.reshape(b, s, kh, g, cfg.head_dim)
    scores = (jnp.einsum("bqkgd,bkdt->bkgqt", qg, ck.astype(q.dtype),
                         preferred_element_type=jnp.float32)
              / math.sqrt(cfg.head_dim))
    abs_pos = _slot_positions(ci + s - 1, T)            # (B, T)
    qpos = ci[:, None] + jnp.arange(s)[None, :]         # (B, S)
    valid = ((abs_pos[:, None, :] >= 0)
             & (abs_pos[:, None, :] <= qpos[..., None]))   # (B,S,T)
    if window is not None:
        valid &= abs_pos[:, None, :] > qpos[..., None] - window
    scores = jnp.where(valid[:, None, None, :, :], scores, NEG_INF)
    prob = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqt,bkdt->bqkgd", prob, cv.astype(prob.dtype))
    return out.reshape(b, s, cfg.num_heads, cfg.head_dim)


def decode_attention_inplace(cfg: ModelConfig, q, k, v, cache, layer, ci, *,
                             window=None, mask=None, row_offset=0):
    """One decode token per row against a layer-stacked ring cache that
    is updated in place.

    q: (B, 1, H, D); k, v: (B, 1, KH, D); cache: dict k/v
    (L, N, KH, D, T), tokens minor, plus int8 scales k_scale/v_scale
    (L, N, KH, T); query row b is cache row ``row_offset + b``.  Each
    row's token is written at ``(layer, row, :, :, ci % T)`` — where
    ``mask`` (B,) is False, the value already held there, so the row's
    cache stays bitwise as it was — and the rows then attend layer
    ``layer``: ``flash_decode`` reads it in place, the jnp branch slices
    the rows out.  Returns (out (B, 1, H, D), cache).
    """
    T = cache["k"].shape[-1]
    slot = ci % T
    new = {"k": k[:, 0], "v": v[:, 0]}                  # (B, KH, D)
    if "k_scale" in cache:
        new["k"], new["k_scale"] = _quant_kv(new["k"])
        new["v"], new["v_scale"] = _quant_kv(new["v"])
    cache = {name: _put_tokens(buf, new[name], layer, row_offset, slot,
                               mask) for name, buf in cache.items()}
    if cfg.attn_impl == "pallas":
        from repro.kernels.ops import decode_attention as _pallas_decode
        out = _pallas_decode(
            q[:, 0], cache["k"], cache["v"], ci, layer,
            row_offset=row_offset, window=window,
            k_scale=cache.get("k_scale"), v_scale=cache.get("v_scale"),
            interpret=cfg.pallas_interpret)
        return out[:, None], cache
    # hold the carried arena in the kernel's order here too: left free,
    # XLA lays it out D-minor for the einsums and copies the whole arena
    # in and out of the program (the einsums read one layer's rows)
    cache = {name: with_layout_constraint(buf, Layout(tuple(range(buf.ndim))))
             for name, buf in cache.items()}
    mine = {name: jax.lax.dynamic_slice_in_dim(
        jax.lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False),
        row_offset, q.shape[0], 0) for name, buf in cache.items()}
    return _cache_attention(cfg, q, mine, ci, window), cache


def apply_attention(p, cfg: ModelConfig, x, *, positions, causal=True,
                    window=None, cache=None, cache_index=None, kv_x=None):
    """Multi-head attention with GQA/MQA, optional qk-norm & RoPE.

    cache: optional dict of one layer's ring cache, tokens minor: k/v
    (B, KH, D, T), and with ``cfg.kv_quant`` int8 k/v plus k_scale/v_scale
    (B, KH, T).  cache_index is the write position of the *first* token
    of this call — an int32 scalar, or a (B,) vector when requests in
    the batch sit at different positions (continuous batching).
    Multi-token calls (s > 1) write the block contiguously and mask
    causally within it; the caller must ensure the block does not wrap
    the ring.  Single-token calls go through
    :func:`decode_attention_inplace` (the multi-path decode step calls it
    directly on the whole layer-stacked cache).  kv_x overrides
    key/value source (cross-attention; no RoPE, no causal mask).
    Returns (out, new_cache).
    """
    b, s, d_model = x.shape
    cross = kv_x is not None
    q, k, v = attention_qkv(p, cfg, x, positions=positions, kv_x=kv_x)
    new_cache = None
    if cache is not None and not cross:
        T = cache["k"].shape[-1]
        ci = jnp.broadcast_to(
            jnp.asarray(cache_index, jnp.int32).reshape(-1), (b,))  # (B,)
        if s == 1:
            out, new_cache = decode_attention_inplace(
                cfg, q, k, v, jax.tree_util.tree_map(lambda c: c[None],
                                                     cache),
                0, ci, window=window)
            new_cache = jax.tree_util.tree_map(lambda c: c[0], new_cache)
            return attention_out(p, out.astype(x.dtype), x), new_cache
        # multi-token (prefill) blocks are written contiguously — a
        # block that wraps the ring would silently overwrite its own
        # oldest entries, so reject it loudly while the start positions
        # are still concrete (they are for every prefill call site:
        # prefill always starts at 0 with s <= T).
        if s > T:
            raise ValueError(
                f"multi-token cache write of {s} tokens exceeds "
                f"cache length {T}")
        if not isinstance(ci, jax.core.Tracer):
            starts = np.asarray(ci) % T
            if int(starts.max()) + s > T:
                raise ValueError(
                    f"multi-token cache write wraps the ring: start "
                    f"{int(starts.max())} + {s} tokens > cache "
                    f"length {T}; split the block or grow the cache")
        idx = ci % T
        new = {"k": k, "v": v}                          # (B, S, KH, D)
        if "k_scale" in cache:
            new["k"], new["k_scale"] = _quant_kv(k)
            new["v"], new["v_scale"] = _quant_kv(v)

        def _row_update(buf, val):
            """Per-row ring write of s tokens: buf (B, ..., T), val
            (B, S, ...) moved tokens-minor."""
            val = jnp.moveaxis(val, 1, -1).astype(buf.dtype)
            return jax.vmap(
                lambda c, x_, i: jax.lax.dynamic_update_slice(
                    c, x_, (0,) * (c.ndim - 1) + (i,)))(buf, val, idx)

        new_cache = {name: _row_update(buf, new[name])
                     for name, buf in cache.items()}
        out = _cache_attention(cfg, q, new_cache, ci, window)
        out = out.astype(x.dtype)
    else:
        causal_eff = causal and not cross
        if cfg.attn_impl == "pallas" and causal_eff:
            from repro.kernels.ops import flash_attention_trainable
            out = flash_attention_trainable(q, k, v, causal=True,
                                            window=window,
                                            interpret=cfg.pallas_interpret)
        elif cfg.attn_impl == "full" or cross or s <= cfg.attn_chunk_q:
            out = full_attention(q, k, v, causal=causal_eff, window=window)
        else:
            out = chunked_attention(
                q, k, v, causal=causal_eff, window=window,
                chunk_q=cfg.attn_chunk_q, chunk_k=cfg.attn_chunk_k,
                causal_skip=cfg.causal_skip)
    return attention_out(p, out, x), new_cache


# ---------------------------------------------------------------------------
# Multi-head latent attention (MLA, DeepSeek-V2/V3)
# ---------------------------------------------------------------------------
def init_mla(key, cfg: ModelConfig):
    """q: one projection to H heads of (nope + rope) width; the keys and
    values of a token come from ``w_dkv``: a latent of ``kv_lora_rank``
    (RMS-normalised by ``kv_norm``), expanded per head by ``w_uk`` and
    ``w_uv``, and one rotary key shared by all heads."""
    m, d, h = cfg.mla, cfg.d_model, cfg.num_heads
    r = m.kv_lora_rank
    ks = jax.random.split(key, 5)
    p = {
        "wq": jax.random.normal(ks[0], (d, h, m.qk_head_dim)) / math.sqrt(d),
        "w_dkv": jax.random.normal(ks[1], (d, m.latent_dim)) / math.sqrt(d),
        "kv_norm": jnp.ones((r,)),
        "w_uk": jax.random.normal(ks[2], (r, h, m.qk_nope_head_dim))
        / math.sqrt(r),
        "w_uv": jax.random.normal(ks[3], (r, h, m.v_head_dim)) / math.sqrt(r),
        "wo": jax.random.normal(ks[4], (h, m.v_head_dim, d))
        / math.sqrt(h * m.v_head_dim),
    }
    a = {
        "wq": (P.EMBED, P.HEADS, P.HEAD_DIM),
        "w_dkv": (P.EMBED, P.LATENT),
        "kv_norm": (P.LATENT,),
        "w_uk": (P.LATENT, P.HEADS, P.HEAD_DIM),
        "w_uv": (P.LATENT, P.HEADS, P.HEAD_DIM),
        "wo": (P.HEADS, P.HEAD_DIM, P.EMBED),
    }
    return p, a


def mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale: 1/sqrt of the query-key width (nope + rope)."""
    return 1.0 / math.sqrt(cfg.mla.qk_head_dim)


def _mla_q_latent(p, cfg: ModelConfig, x, positions):
    """Queries, split: q_nope (B, S, H, dn) and roped q_pe (B, S, H, dr);
    and each token's cache entry (B, S, r + dr): the normalised latent,
    then the roped shared key."""
    m = cfg.mla
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    q_nope, q_pe = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)
    kv = jnp.einsum("bsd,dc->bsc", x, p["w_dkv"].astype(x.dtype))
    c = rms_norm(p["kv_norm"], kv[..., :m.kv_lora_rank], cfg.norm_eps)
    k_pe = apply_rope(kv[..., None, m.kv_lora_rank:], positions,
                      cfg.rope_theta)[..., 0, :]
    return q_nope, q_pe, jnp.concatenate([c, k_pe], axis=-1)


def apply_mla(p, cfg: ModelConfig, x, *, positions, window=None):
    """The published (non-absorbed) form, for training and prefill: keys
    and values expanded from the latent, attention within the block
    (causal from its first token, which is position 0 in a prefill).
    x: (B, S, d) -> (out (B, S, d), cache entries (B, S, r + dr))."""
    m, h = cfg.mla, cfg.num_heads
    q_nope, q_pe, lat = _mla_q_latent(p, cfg, x, positions)
    c, k_pe = lat[..., :m.kv_lora_rank], lat[..., m.kv_lora_rank:]
    k_nope = jnp.einsum("bsc,chk->bshk", c, p["w_uk"].astype(x.dtype))
    v = jnp.einsum("bsc,chk->bshk", c, p["w_uv"].astype(x.dtype))
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe[:, :, None, :], k_nope.shape[:3] + (m.qk_rope_head_dim,))], -1)
    s = x.shape[1]
    if s <= cfg.attn_chunk_q:
        out = full_attention(q, k, v, causal=True, window=window)
    else:
        out = chunked_attention(q, k, v, causal=True, window=window,
                                chunk_q=cfg.attn_chunk_q,
                                chunk_k=cfg.attn_chunk_k, causal_skip=True)
    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype),
                   p["wo"].astype(x.dtype), out_sharding=_layout_of(x))
    return y, lat


def mla_decode_q(p, cfg: ModelConfig, x, positions):
    """The absorbed form's queries for one token per row: q_nope folded
    through ``w_uk`` into the latent, beside the roped q_pe: (B, 1, H,
    r + dr) in float32; and the token's cache entry (B, 1, r + dr)."""
    q_nope, q_pe, lat = _mla_q_latent(p, cfg, x, positions)
    q_lat = jnp.einsum("bshk,chk->bshc", q_nope, p["w_uk"].astype(x.dtype),
                       preferred_element_type=jnp.float32)
    return jnp.concatenate([q_lat, q_pe.astype(jnp.float32)], -1), lat


def mla_decode_out(p, out_lat, x):
    """Attention output in the latent (B, 1, H, r) -> (B, 1, d): through
    ``w_uv`` to the heads' values, then ``wo``."""
    o = jnp.einsum("bshc,chk->bshk", out_lat.astype(x.dtype),
                   p["w_uv"].astype(x.dtype))
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))


def _latent_attention(cfg: ModelConfig, q, lat, ci):
    """jnp absorbed attention of one token per row over its rows' ring
    of latents.  q: (B, H, r + dr) float32; lat: (B, r + dr, T) ->
    (B, H, r) float32."""
    r, T = cfg.mla.kv_lora_rank, lat.shape[-1]
    lat = lat.astype(jnp.float32)
    scores = jnp.einsum("bhc,bct->bht", q, lat) * mla_scale(cfg)
    pos = _slot_positions(ci, T)
    valid = (pos >= 0) & (pos <= ci[:, None])
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
    prob = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bht,bct->bhc", prob, lat[:, :r])


def mla_attention_inplace(cfg: ModelConfig, q, lat, cache, layer, ci, *,
                          mask=None, row_offset=0):
    """One decode token per row, absorbed, against the layer-stacked
    latent arena, updated in place.

    q: (B, 1, H, r + dr) from :func:`mla_decode_q`; lat: (B, 1, r + dr),
    the token's cache entry; cache: ``{"lat": (L, N, r + dr, T)}``,
    tokens minor.  The entry is written at ``(layer, row_offset + b, :,
    ci % T)`` (the old value where ``mask`` is False); then scores are
    ``q_lat . c + q_pe . k_pe`` over the row's valid ring slots and the
    output is the probabilities' sum of latents: ``mla_decode`` reads the
    layer in place, the jnp branch slices the rows out.  Returns (out
    (B, 1, H, r) float32, cache)."""
    buf = cache["lat"]
    T = buf.shape[-1]
    buf = _put_tokens(buf, lat[:, 0], layer, row_offset, ci % T, mask)
    if cfg.attn_impl == "pallas":
        from repro.kernels.ops import mla_decode
        out = mla_decode(q[:, 0], buf, ci, layer, row_offset=row_offset,
                         kv_rank=cfg.mla.kv_lora_rank,
                         scale=mla_scale(cfg),
                         interpret=cfg.pallas_interpret)
    else:
        # kept in the kernel's order, as in decode_attention_inplace
        buf = with_layout_constraint(buf, Layout(tuple(range(buf.ndim))))
        mine = jax.lax.dynamic_slice_in_dim(
            jax.lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False),
            row_offset, q.shape[0], 0)
        out = _latent_attention(cfg, q[:, 0], mine, ci)
    return out[:, None], {"lat": buf}


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------
def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    s = 1.0 / math.sqrt(d)
    if cfg.mlp_type in ("swiglu", "geglu"):
        p = {"w_gate": jax.random.normal(ks[0], (d, f)) * s,
             "w_up": jax.random.normal(ks[1], (d, f)) * s,
             "w_down": jax.random.normal(ks[2], (f, d)) * (1.0 / math.sqrt(f))}
        a = {"w_gate": (P.EMBED, P.MLP), "w_up": (P.EMBED, P.MLP),
             "w_down": (P.MLP, P.EMBED)}
    else:  # relu2 | gelu: plain 2-matrix MLP
        p = {"w_up": jax.random.normal(ks[0], (d, f)) * s,
             "w_down": jax.random.normal(ks[1], (f, d)) * (1.0 / math.sqrt(f))}
        a = {"w_up": (P.EMBED, P.MLP), "w_down": (P.MLP, P.EMBED)}
    return p, a


def apply_mlp(p, cfg: ModelConfig, x):
    t = cfg.mlp_type
    if t == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"].astype(x.dtype)) * (x @ p["w_up"].astype(x.dtype))
    elif t == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"].astype(x.dtype)) * (x @ p["w_up"].astype(x.dtype))
    elif t == "relu2":
        h = jnp.square(jax.nn.relu(x @ p["w_up"].astype(x.dtype)))
    elif t == "gelu":
        h = jax.nn.gelu(x @ p["w_up"].astype(x.dtype))
    else:
        raise ValueError(f"unknown mlp_type {t}")
    return jnp.matmul(h, p["w_down"].astype(x.dtype),
                      out_sharding=_layout_of(x))


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------
def init_embedding(key, cfg: ModelConfig):
    p = {"embedding": jax.random.normal(key, (cfg.vocab_size, cfg.d_model)) * 0.02}
    a = {"embedding": (P.VOCAB, P.EMBED)}
    if not cfg.tie_embeddings:
        k2 = jax.random.fold_in(key, 1)
        p["unembed"] = jax.random.normal(
            k2, (cfg.d_model, cfg.vocab_size)) * (1.0 / math.sqrt(cfg.d_model))
        a["unembed"] = (P.EMBED, P.VOCAB)
    return p, a


def embed_tokens(p, cfg: ModelConfig, tokens):
    # the gathered rows follow the tokens' layout (see _layout_of)
    sh = _layout_of(tokens)
    if sh is None:
        x = jnp.take(p["embedding"], tokens, axis=0)
    else:
        x = p["embedding"].at[tokens].get(out_sharding=NamedSharding(
            sh.mesh, PartitionSpec(*sh.spec, None)))
    x = x.astype(jnp.dtype(cfg.dtype))
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def unembed(p, cfg: ModelConfig, x):
    # NOTE (perf iteration #2, EXPERIMENTS.md §Perf): logits stay in the
    # activation dtype; the loss upcasts to f32 at its boundary.  With
    # preferred_element_type=f32 here, the f32 cotangent propagated back
    # through EVERY layer, doubling backward collective/memory traffic.
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, p["embedding"].astype(x.dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, p["unembed"].astype(x.dtype))
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = jnp.tanh(logits / c) * c
    return logits
