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
    """Reference O(S^2)-memory attention.  q: (B,Sq,H,D), k/v: (B,Sk,KH,D)."""
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
    return out.reshape(b, sq, h, d).astype(q.dtype)


def chunked_attention(q, k, v, *, causal: bool, window: Optional[int],
                      chunk_q: int = 512, chunk_k: int = 512,
                      causal_skip: bool = False):
    """Online-softmax blockwise attention; O(S*chunk) activation memory.

    With ``causal_skip`` the fully-masked (future) key chunks are
    structurally skipped (flops ~ S^2/2 instead of S^2), and with a
    window also the fully-expired past chunks are skipped.
    """
    b, s, h, d = q.shape
    kh = k.shape[2]
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
    vc = v.reshape(b, nk, chunk_k, kh, d).astype(jnp.float32)
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
        a0 = jnp.zeros((b, kh, g, chunk_q, d), jnp.float32)
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
    out = out.reshape(b, kh, g, nq * chunk_q, d)
    out = jnp.moveaxis(out, 3, 1).reshape(b, nq * chunk_q, kh * g, d)
    return out[:, :s].astype(q.dtype)


def apply_attention(p, cfg: ModelConfig, x, *, positions, causal=True,
                    window=None, cache=None, cache_index=None, kv_x=None):
    """Multi-head attention with GQA/MQA, optional qk-norm & RoPE.

    cache: optional dict(k=(B,T,KH,D), v=...) for decode/incremental
    prefill; cache_index is the write position of the *first* token of
    this call — an int32 scalar, or a (B,) vector when requests in the
    batch sit at different positions (continuous batching).  Multi-token
    calls (s > 1) write the block contiguously and mask causally within
    it; the caller must ensure the block does not wrap the ring.
    kv_x overrides key/value source (cross-attention; no RoPE, no causal
    mask).  Returns (out, new_cache).
    """
    b, s, d_model = x.shape
    cross = kv_x is not None
    src = kv_x if cross else x
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps)
    if not cross:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    new_cache = None
    if cache is not None and not cross:
        # decode / incremental: write k,v at cache_index (ring for windows)
        T = cache["k"].shape[1]
        ci = jnp.broadcast_to(
            jnp.asarray(cache_index, jnp.int32).reshape(-1), (b,))  # (B,)
        if s > 1:
            # multi-token (prefill) blocks are written contiguously — a
            # block that wraps the ring would silently overwrite its own
            # oldest entries, so reject it loudly while the start
            # positions are still concrete (they are for every prefill
            # call site: prefill always starts at 0 with s <= T).
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

        def _row_update(buf, val, start):
            """Per-row ring write: buf (B,T,...), val (B,s,...)."""
            return jax.vmap(
                lambda c, x_, i: jax.lax.dynamic_update_slice(
                    c, x_, (i,) + (0,) * (c.ndim - 1)))(buf, val, start)

        if "k_scale" in cache:
            # int8 KV cache: per-(token, head) absmax scales — halves the
            # decode HBM traffic (§Perf iteration N7)
            def _quant(x):
                xf = x.astype(jnp.float32)
                scale = jnp.maximum(
                    jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0,
                    1e-8)
                qx = jnp.clip(jnp.round(xf / scale), -127, 127).astype(
                    jnp.int8)
                return qx, scale[..., 0]

            kq, ks = _quant(k)
            vq, vs = _quant(v)
            ck = _row_update(cache["k"], kq, idx)
            cv = _row_update(cache["v"], vq, idx)
            cks = _row_update(cache["k_scale"], ks, idx)
            cvs = _row_update(cache["v_scale"], vs, idx)
            new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
        else:
            ck = _row_update(cache["k"], k.astype(cache["k"].dtype), idx)
            cv = _row_update(cache["v"], v.astype(cache["v"].dtype), idx)
            new_cache = {"k": ck, "v": cv}
        if s == 1 and cfg.attn_impl == "pallas":
            # NOTE (perf iteration #3, fused decode path): the jnp branch
            # below materializes dense (B, H, S, T) scores over the whole
            # ring cache — and, for int8 caches, an f32 copy of the full
            # cache — every decode step.  The Pallas flash-decode kernel
            # streams the cache block-by-block with online softmax,
            # masks ring validity in-kernel from the per-row positions,
            # and dequantizes int8 KV in VMEM, so decode HBM traffic is
            # one pass over the (possibly int8) cache.
            from repro.kernels.ops import decode_attention as _pallas_decode
            out = _pallas_decode(
                q[:, 0], ck, cv, ci, window=window,
                k_scale=new_cache.get("k_scale"),
                v_scale=new_cache.get("v_scale"),
                interpret=cfg.pallas_interpret)
            out = out[:, None].astype(x.dtype)              # (B, 1, H, D)
        else:
            if "k_scale" in new_cache:
                ckf = (ck.astype(jnp.float32)
                       * cks[..., None]).astype(q.dtype)
                cvf = (cv.astype(jnp.float32)
                       * cvs[..., None]).astype(q.dtype)
            else:
                ckf, cvf = ck, cv
            # attend over valid cache entries
            kh = ck.shape[2]
            g = cfg.num_heads // kh
            qg = q.reshape(b, s, kh, g, cfg.head_dim)
            scores = (_gqa_scores(qg, ckf.astype(q.dtype))
                      / math.sqrt(cfg.head_dim))
            slot = jnp.arange(T)[None, :]                   # (1, T)
            # absolute position stored in each ring slot, per batch row;
            # reconstructed from the position of the *last* token written
            last = ci + s - 1                               # (B,)
            idx_last = (last % T)[:, None]
            abs_pos = jnp.where(slot <= idx_last,
                                last[:, None] - idx_last + slot,
                                last[:, None] - idx_last - T + slot)  # (B,T)
            qpos = ci[:, None] + jnp.arange(s)[None, :]     # (B, S)
            valid = ((abs_pos[:, None, :] >= 0)
                     & (abs_pos[:, None, :] <= qpos[..., None]))   # (B,S,T)
            if window is not None:
                valid &= abs_pos[:, None, :] > qpos[..., None] - window
            scores = jnp.where(valid[:, None, None, :, :], scores, NEG_INF)
            prob = jax.nn.softmax(scores, axis=-1)
            out = _gqa_out(prob, cvf.astype(prob.dtype))
            out = out.reshape(b, s, cfg.num_heads,
                              cfg.head_dim).astype(x.dtype)
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
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype),
                   out_sharding=_layout_of(x))
    return y, new_cache


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
