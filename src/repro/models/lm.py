"""Pattern-scanned decoder language model.

A model is ``num_layers`` blocks following a repeating ``cfg.pattern`` of
``BlockSpec(mixer, mlp)`` entries.  Parameters for each pattern position
are *stacked* across repeats and applied with ``lax.scan`` so HLO size is
independent of depth (essential for the 94-layer dry-runs).

Supports dense / token-MoE / Mamba2 / hybrid blocks, VLM patch-embedding
injection, training forward, prefill, and single-token decode with
KV/SSM caches.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import params as P
from .config import ModelConfig
from .layers import (apply_attention, apply_mlp, embed_tokens, init_attention,
                     init_embedding, init_mlp, init_rmsnorm, rms_norm, unembed)
from .moe_layer import apply_moe, init_moe
from .ssm import apply_mamba, init_mamba, init_ssm_state


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _init_block(key, cfg: ModelConfig, spec):
    ks = jax.random.split(key, 4)
    p, a = {}, {}
    p["norm1"], a["norm1"] = init_rmsnorm(cfg.d_model)
    if spec.mixer == "attn":
        p["mixer"], a["mixer"] = init_attention(ks[0], cfg)
    elif spec.mixer == "mamba":
        p["mixer"], a["mixer"] = init_mamba(ks[0], cfg)
    else:
        raise ValueError(spec.mixer)
    if spec.mlp != "none":
        p["norm2"], a["norm2"] = init_rmsnorm(cfg.d_model)
        if spec.mlp == "dense":
            p["mlp"], a["mlp"] = init_mlp(ks[1], cfg)
        elif spec.mlp == "moe":
            p["mlp"], a["mlp"] = init_moe(ks[1], cfg)
        else:
            raise ValueError(spec.mlp)
    return p, a


def init_lm(key, cfg: ModelConfig):
    reps = cfg.pattern_repeats
    keys = jax.random.split(key, len(cfg.pattern) + 3)
    params, axes = {}, {}
    params["embed"], axes["embed"] = init_embedding(keys[-1], cfg)
    blocks_p, blocks_a = {}, {}
    for i, spec in enumerate(cfg.pattern):
        def init_one(k):
            return _init_block(k, cfg, spec)
        ks = jax.random.split(keys[i], reps)
        stacked = [init_one(k) for k in ks]
        p0, a0 = stacked[0]
        blocks_p[f"pos{i}"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *[s[0] for s in stacked])
        blocks_a[f"pos{i}"] = jax.tree_util.tree_map(
            lambda ax: (P.LAYERS, *ax), a0,
            is_leaf=lambda x: isinstance(x, tuple))
    params["blocks"], axes["blocks"] = blocks_p, blocks_a
    params["final_norm"], axes["final_norm"] = init_rmsnorm(cfg.d_model)
    if cfg.vision is not None:
        import math
        k = keys[-2]
        params["patch_proj"] = jax.random.normal(
            k, (cfg.vision.d_patch, cfg.d_model)) / math.sqrt(cfg.vision.d_patch)
        axes["patch_proj"] = (None, P.EMBED)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.dtype(cfg.dtype))
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    return params, axes


# ---------------------------------------------------------------------------
# Apply
# ---------------------------------------------------------------------------
def _apply_block(bp, cfg: ModelConfig, spec, x, *, positions, window,
                 cache=None, cache_index=None, is_prefill=False):
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(bp["norm1"], x, cfg.norm_eps)
    new_cache = None
    if spec.mixer == "attn":
        attn_cache = None if cache is None else cache
        y, new_cache = apply_attention(
            bp["mixer"], cfg, h, positions=positions, causal=True,
            window=window, cache=attn_cache, cache_index=cache_index)
    elif is_prefill:
        # mamba prefill: full-sequence scan from a zero state; the
        # incoming (stale) slot state is overwritten, matching the
        # attention branch's write-from-position-0 semantics
        y, new_cache = apply_mamba(bp["mixer"], cfg, h, return_state=True)
    else:  # mamba decode
        y, new_cache = apply_mamba(bp["mixer"], cfg, h, state=cache)
    x = x + y
    if spec.mlp != "none":
        h = rms_norm(bp["norm2"], x, cfg.norm_eps)
        if spec.mlp == "moe":
            y, a = apply_moe(bp["mlp"], cfg, h)
            aux = aux + a
        else:
            y = apply_mlp(bp["mlp"], cfg, h)
        x = x + y
    return x, new_cache, aux


def _scan_blocks(params, cfg: ModelConfig, x, *, positions, window,
                 caches=None, cache_index=None, is_prefill=False):
    """Scan the repeating pattern group over ``pattern_repeats``."""
    reps = cfg.pattern_repeats

    def body(carry, xs):
        h, aux = carry
        bparams, bcaches = xs
        new_caches = {}
        for i, spec in enumerate(cfg.pattern):
            c = None if bcaches is None else bcaches[f"pos{i}"]
            h, nc, a = _apply_block(
                bparams[f"pos{i}"], cfg, spec, h, positions=positions,
                window=window, cache=c, cache_index=cache_index,
                is_prefill=is_prefill)
            aux = aux + a
            new_caches[f"pos{i}"] = nc
        if bcaches is None:
            return (h, aux), None
        return (h, aux), new_caches

    if cfg.remat and caches is None:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)
        body_fn = jax.checkpoint(body, policy=policy)
    else:
        body_fn = body
    carry0 = (x, jnp.zeros((), jnp.float32))
    (x, aux), new_caches = jax.lax.scan(
        body_fn, carry0, (params["blocks"], caches))
    return x, aux, new_caches


def _embed_inputs(params, cfg: ModelConfig, tokens, patch_embeds=None):
    x = embed_tokens(params["embed"], cfg, tokens)
    if cfg.vision is not None and patch_embeds is not None:
        proj = (patch_embeds.astype(x.dtype)
                @ params["patch_proj"].astype(x.dtype))
        # patches occupy the first num_patches positions of the sequence
        x = jax.lax.dynamic_update_slice(x, proj, (0, 0, 0))
    return x


def apply_lm(params, cfg: ModelConfig, tokens, *, patch_embeds=None,
             window=None, return_hidden=False):
    """Training / scoring forward.  tokens: (B, S) -> logits (B, S, V)."""
    b, s = tokens.shape
    x = _embed_inputs(params, cfg, tokens, patch_embeds)
    positions = jnp.arange(s)[None, :]
    window = window if window is not None else cfg.sliding_window
    x, aux, _ = _scan_blocks(params, cfg, x, positions=positions,
                             window=window)
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    if return_hidden:
        return x, aux
    return unembed(params["embed"], cfg, x), aux


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------
def init_decode_cache(cfg: ModelConfig, batch: int, cache_len: int,
                      dtype=None):
    """Stacked caches matching the scan layout."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    reps = cfg.pattern_repeats
    caches = {}
    for i, spec in enumerate(cfg.pattern):
        if spec.mixer == "attn":
            if cfg.kv_quant:
                c = {"k": jnp.zeros((batch, cache_len, cfg.num_kv_heads,
                                     cfg.head_dim), jnp.int8),
                     "v": jnp.zeros((batch, cache_len, cfg.num_kv_heads,
                                     cfg.head_dim), jnp.int8),
                     "k_scale": jnp.zeros(
                         (batch, cache_len, cfg.num_kv_heads),
                         jnp.float32),
                     "v_scale": jnp.zeros(
                         (batch, cache_len, cfg.num_kv_heads),
                         jnp.float32)}
            else:
                c = {"k": jnp.zeros((batch, cache_len, cfg.num_kv_heads,
                                     cfg.head_dim), dtype),
                     "v": jnp.zeros((batch, cache_len, cfg.num_kv_heads,
                                     cfg.head_dim), dtype)}
        else:
            c = init_ssm_state(cfg, batch, dtype)
        caches[f"pos{i}"] = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (reps, *x.shape)), c)
    return caches


def decode_step(params, cfg: ModelConfig, tokens, caches, cache_index, *,
                window=None):
    """One decode step.  tokens: (B, 1) -> (logits (B,1,V), new_caches).

    cache_index: int32 scalar, or a (B,) vector when the batch rows sit
    at different sequence positions (continuous batching over a slot
    arena).
    """
    x = _embed_inputs(params, cfg, tokens)
    ci = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32).reshape(-1),
                          (tokens.shape[0],))
    positions = ci[:, None]
    window = window if window is not None else cfg.sliding_window
    x, aux, new_caches = _scan_blocks(
        params, cfg, x, positions=positions, window=window,
        caches=caches, cache_index=ci)
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], cfg, x)
    return logits, new_caches


def prefill(params, cfg: ModelConfig, tokens, cache_len: int, *,
            window=None, patch_embeds=None):
    """Single-pass prompt ingestion: forward ``tokens`` once, writing the
    KV/SSM decode caches incrementally (positions 0..S-1).

    Returns (logits (B,S,V), caches) — ``logits[:, -1]`` predicts the
    first generated token and ``caches`` is ready for ``decode_step`` at
    ``cache_index = S``.  Replaces the O(S) replay-through-decode loop
    the one-shot serving engine uses.
    """
    b, s = tokens.shape
    if s > cache_len:
        raise ValueError(f"prompt length {s} exceeds cache_len {cache_len}")
    caches = init_decode_cache(cfg, b, cache_len)
    x = _embed_inputs(params, cfg, tokens, patch_embeds)
    positions = jnp.arange(s)[None, :]
    window = window if window is not None else cfg.sliding_window
    x, aux, new_caches = _scan_blocks(
        params, cfg, x, positions=positions, window=window,
        caches=caches, cache_index=jnp.int32(0), is_prefill=True)
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], cfg, x)
    return logits, new_caches


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------
def lm_loss(logits, tokens, prefix_len: int = 0):
    """Per-token NLL + mask, excluding the routing prefix (paper §2.4)."""
    targets = tokens[:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    logz = jax.nn.logsumexp(lg, axis=-1)
    # a masked sum, not a gather: it also lowers when the vocab axis is
    # sharded under explicit mesh axes (exact: one term is non-zero)
    vocab = jax.lax.broadcasted_iota(jnp.int32, lg.shape, lg.ndim - 1)
    ll = jnp.sum(jnp.where(vocab == targets[..., None], lg, 0.0),
                 axis=-1) - logz
    pos = jnp.arange(targets.shape[1])[None, :]
    mask = jnp.broadcast_to((pos + 1 >= prefix_len),
                            targets.shape).astype(jnp.float32)
    return -(ll * mask), mask


def lm_loss_mean(logits, tokens, prefix_len: int = 0):
    nll, mask = lm_loss(logits, tokens, prefix_len)
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)
