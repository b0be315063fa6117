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
from .layers import (apply_attention, apply_mlp, attention_out, attention_qkv,
                     decode_attention_inplace, embed_tokens, init_attention,
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
                 cache=None, cache_index=None):
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(bp["norm1"], x, cfg.norm_eps)
    new_cache = None
    if spec.mixer == "attn":
        y, new_cache = apply_attention(
            bp["mixer"], cfg, h, positions=positions, causal=True,
            window=window, cache=cache, cache_index=cache_index)
    elif cache is not None:
        # mamba prefill: full-sequence scan from a zero state; the
        # incoming (stale) slot state is overwritten, matching the
        # attention branch's write-from-position-0 semantics
        y, new_cache = apply_mamba(bp["mixer"], cfg, h, return_state=True)
    else:
        y, _ = apply_mamba(bp["mixer"], cfg, h)
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
                 caches=None, cache_index=None):
    """Scan the repeating pattern group over ``pattern_repeats`` (with
    ``caches``: the prefill, which writes each layer's cache)."""

    def body(carry, xs):
        h, aux = carry
        bparams, bcaches = xs
        new_caches = {}
        for i, spec in enumerate(cfg.pattern):
            c = None if bcaches is None else bcaches[f"pos{i}"]
            h, nc, a = _apply_block(
                bparams[f"pos{i}"], cfg, spec, h, positions=positions,
                window=window, cache=c, cache_index=cache_index)
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
    """Layer-stacked caches, one dict per pattern position: attention
    k/v ``(reps, batch, KH, D, T)``, tokens minor (the order
    ``flash_decode`` reads in place), int8 scales ``(reps, batch, KH,
    T)``; SSM state ``(reps, batch, ...)``.  The row axis is axis 1."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    reps = cfg.pattern_repeats
    kv = (reps, batch, cfg.num_kv_heads, cfg.head_dim, cache_len)
    caches = {}
    for i, spec in enumerate(cfg.pattern):
        if spec.mixer == "attn":
            if cfg.kv_quant:
                sc = kv[:3] + kv[4:]
                c = {"k": jnp.zeros(kv, jnp.int8),
                     "v": jnp.zeros(kv, jnp.int8),
                     "k_scale": jnp.zeros(sc, jnp.float32),
                     "v_scale": jnp.zeros(sc, jnp.float32)}
            else:
                c = {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}
        else:
            c = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (reps, *x.shape)),
                init_ssm_state(cfg, batch, dtype))
        caches[f"pos{i}"] = c
    return caches


def stack_paths(path_params_list):
    """Stack homogeneous paths' parameters for :func:`decode_step`'s
    ``paths``: block leaves layer-major ``(reps, P, ...)``, so one layer
    of every path is a contiguous slice the layer loop reads in place;
    every other leaf ``(P, ...)``."""
    return {key: jax.tree_util.tree_map(
        lambda *xs, axis=int(key == "blocks"): jnp.stack(xs, axis=axis),
        *[p[key] for p in path_params_list])
        for key in path_params_list[0]}


def _decode_block(bp, cfg: ModelConfig, spec, x, cache, layer, *,
                  positions, ci, mask, row_offset, window):
    """One block of the decode step.  x: (P, S, 1, D), path-major rows;
    bp: this layer's weights, leaves (P, ...); cache: this pattern
    position's layer-stacked leaves (reps, N, ...), carried in place.
    Matmuls run per path on its weights; attention runs over all P*S
    rows at once."""
    n_p, n_s = x.shape[:2]

    def rows(t):
        return t.reshape((n_p * n_s,) + t.shape[2:])

    def per_path(t):
        return t.reshape((n_p, n_s) + t.shape[1:])

    def norm(scale, h):
        return jax.vmap(lambda s_, h_: rms_norm(s_, h_, cfg.norm_eps))(
            scale, h)

    h = norm(bp["norm1"], x)
    if spec.mixer == "attn":
        q, k, v = jax.vmap(lambda p_, h_, pos_: attention_qkv(
            p_, cfg, h_, positions=pos_))(bp["mixer"], h, positions)
        out, cache = decode_attention_inplace(
            cfg, rows(q), rows(k), rows(v), cache, layer, ci,
            window=window, mask=mask, row_offset=row_offset)
        y = jax.vmap(attention_out)(bp["mixer"],
                                    per_path(out.astype(x.dtype)), h)
    else:
        # SSM state is per row and small: read this layer's rows, step,
        # keep masked-off rows' state by a select, write the layer back
        state = jax.tree_util.tree_map(
            lambda c: jax.lax.dynamic_slice_in_dim(
                c[layer], row_offset, n_p * n_s, 0), cache)
        y, new = jax.vmap(lambda p_, h_, s_: apply_mamba(
            p_, cfg, h_, state=s_))(
                bp["mixer"], h, jax.tree_util.tree_map(per_path, state))
        new = jax.tree_util.tree_map(rows, new)
        if mask is not None:
            new = jax.tree_util.tree_map(
                lambda n, o: jnp.where(
                    mask.reshape((-1,) + (1,) * (n.ndim - 1)),
                    n.astype(o.dtype), o), new, state)
        cache = jax.tree_util.tree_map(
            lambda c, n: jax.lax.dynamic_update_slice(
                c, n[None].astype(c.dtype),
                (layer, row_offset) + (0,) * (c.ndim - 2)), cache, new)
    x = x + y
    if spec.mlp != "none":
        h = norm(bp["norm2"], x)
        if spec.mlp == "moe":
            y = jax.vmap(lambda p_, h_: apply_moe(p_, cfg, h_)[0])(
                bp["mlp"], h)
        else:
            y = jax.vmap(lambda p_, h_: apply_mlp(p_, cfg, h_))(
                bp["mlp"], h)
        x = x + y
    return x, cache


def decode_step(params, cfg: ModelConfig, tokens, caches, cache_index, *,
                window=None, mask=None, paths=None, row_offset=0):
    """One decode step.  tokens: (B, 1) -> (logits (B,1,V), new_caches).

    cache_index: int32 scalar, or a (B,) vector when the batch rows sit
    at different sequence positions (continuous batching over a slot
    arena).  caches (:func:`init_decode_cache`) ride the layer loop's
    carry and are updated in place (donate them): each layer writes one
    token per row and ``flash_decode`` reads the layer where it lies.
    Token row b is cache row ``row_offset + b``, so one island's rows can
    decode against a larger arena.  ``mask`` (B,) bool: rows where it is
    False keep their cache bitwise (their logits are junk).

    ``paths``: None for one path's params; else ``params`` is
    :func:`stack_paths` of that many paths and the rows are path-major,
    row ``p * (B // paths) + s`` on path p — the stacked tick, one
    dispatch for every island.
    """
    if paths is None:
        params, paths = stack_paths([params]), 1
    b = tokens.shape[0]
    ci = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32).reshape(-1),
                          (b,))
    tok = tokens.reshape(paths, b // paths, 1)
    x = jax.vmap(lambda e, t: embed_tokens(e, cfg, t))(params["embed"], tok)
    positions = ci.reshape(paths, b // paths, 1)
    window = window if window is not None else cfg.sliding_window

    def body(carry, xs):
        h, cs = carry
        layer, bparams = xs
        cs = dict(cs)
        for i, spec in enumerate(cfg.pattern):
            h, cs[f"pos{i}"] = _decode_block(
                bparams[f"pos{i}"], cfg, spec, h, cs[f"pos{i}"], layer,
                positions=positions, ci=ci, mask=mask,
                row_offset=row_offset, window=window)
        return (h, cs), None

    (x, caches), _ = jax.lax.scan(
        body, (x, caches),
        (jnp.arange(cfg.pattern_repeats), params["blocks"]))
    logits = jax.vmap(lambda fn, e, h: unembed(
        e, cfg, rms_norm(fn, h, cfg.norm_eps)))(
            params["final_norm"], params["embed"], x)
    return logits.reshape(b, 1, -1), caches


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
        caches=caches, cache_index=jnp.int32(0))
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
