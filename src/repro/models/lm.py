"""Pattern-scanned decoder language model.

A model is ``num_layers`` blocks following a repeating ``cfg.pattern`` of
``BlockSpec(mixer, mlp)`` entries.  Parameters for each pattern position
are *stacked* across repeats and applied with ``lax.scan`` so HLO size is
independent of depth (essential for the 94-layer dry-runs).

Supports dense / token-MoE / Mamba2 / hybrid blocks, multi-head latent
attention, leading dense layers ahead of the pattern (``cfg.first_dense``:
params ``lead``, cache ``lead``, scanned before the pattern), VLM
patch-embedding injection, training forward, prefill, and single-token
decode with KV/latent/SSM caches.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import params as P
from .config import ModelConfig
from .layers import (apply_attention, apply_mla, apply_mlp, attention_out,
                     attention_qkv, decode_attention_inplace, embed_tokens,
                     init_attention, init_embedding, init_mla, init_mlp,
                     init_rmsnorm, mla_attention_inplace, mla_decode_out,
                     mla_decode_q, rms_norm, unembed)
from .moe_layer import LayerStack, apply_moe, init_moe, routing_counts
from .ssm import apply_mamba, init_mamba, init_ssm_state


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _init_block(key, cfg: ModelConfig, spec, d_ff=None):
    ks = jax.random.split(key, 4)
    p, a = {}, {}
    p["norm1"], a["norm1"] = init_rmsnorm(cfg.d_model)
    if spec.mixer == "attn":
        p["mixer"], a["mixer"] = init_attention(ks[0], cfg)
    elif spec.mixer == "mla":
        p["mixer"], a["mixer"] = init_mla(ks[0], cfg)
    elif spec.mixer == "mamba":
        p["mixer"], a["mixer"] = init_mamba(ks[0], cfg)
    else:
        raise ValueError(spec.mixer)
    if spec.mlp != "none":
        p["norm2"], a["norm2"] = init_rmsnorm(cfg.d_model)
        if spec.mlp == "dense":
            p["mlp"], a["mlp"] = init_mlp(ks[1], cfg, d_ff)
        elif spec.mlp == "moe":
            p["mlp"], a["mlp"] = init_moe(ks[1], cfg)
        else:
            raise ValueError(spec.mlp)
    return p, a


def _init_stack(key, cfg: ModelConfig, spec, n: int, d_ff=None):
    """``n`` blocks of ``spec``, leaves stacked on a leading layer axis."""
    stacked = [_init_block(k, cfg, spec, d_ff)
               for k in jax.random.split(key, n)]
    p = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                               *[s[0] for s in stacked])
    a = jax.tree_util.tree_map(lambda ax: (P.LAYERS, *ax), stacked[0][1],
                               is_leaf=lambda x: isinstance(x, tuple))
    return p, a


def init_lm(key, cfg: ModelConfig):
    reps = cfg.pattern_repeats
    keys = jax.random.split(key, len(cfg.pattern) + 3)
    params, axes = {}, {}
    params["embed"], axes["embed"] = init_embedding(keys[-1], cfg)
    if cfg.first_dense:
        params["lead"], axes["lead"] = _init_stack(
            jax.random.fold_in(key, 11), cfg, cfg.lead_spec,
            cfg.first_dense, cfg.d_ff_dense)
    blocks_p, blocks_a = {}, {}
    for i, spec in enumerate(cfg.pattern):
        blocks_p[f"pos{i}"], blocks_a[f"pos{i}"] = _init_stack(
            keys[i], cfg, spec, reps)
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
    """One block over a token block.  Returns (x, new cache, aux loss,
    the experts each token chose (B*S, k) or None).  The new cache of an
    MLA block is its tokens' latent entries (B, S, C): the prefill
    places them."""
    aux = jnp.zeros((), jnp.float32)
    experts = None
    h = rms_norm(bp["norm1"], x, cfg.norm_eps)
    new_cache = None
    if spec.mixer == "mla":
        y, new_cache = apply_mla(bp["mixer"], cfg, h, positions=positions,
                                 window=window)
    elif spec.mixer == "attn":
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
            y, a, experts = apply_moe(bp["mlp"], cfg, h)
            aux = aux + a
        else:
            y = apply_mlp(bp["mlp"], cfg, h)
        x = x + y
    return x, new_cache, aux, experts


def _groups(params, cfg: ModelConfig, caches=None):
    """The model's layer groups in order: ``(params, specs, caches,
    names)``; the leading dense layers (one spec, params and cache
    unnested) and then the pattern (``pos<i>``)."""
    out = []
    if cfg.first_dense:
        out.append((params["lead"], (cfg.lead_spec,),
                    None if caches is None else caches["lead"], None))
    names = [f"pos{i}" for i in range(len(cfg.pattern))]
    out.append((params["blocks"], cfg.pattern,
                None if caches is None else {n: caches[n] for n in names},
                names))
    return out


EXPERTS = ("w_gate", "w_up", "w_down")   # a MoE block's routed experts


def _experts_in_place(cfg: ModelConfig, specs, names, bparams, whole,
                      layer):
    """``bparams`` (one layer of a group) with each dropless MoE block's
    routed-expert weights replaced by :class:`LayerStack`s of ``whole``
    (the group's layer-stacked params, no path axis) at ``layer``, so the
    grouped matmuls read them in place.  Unchanged where ``whole`` is
    None."""
    if whole is None or cfg.moe is None or cfg.moe.impl != "dropless":
        return bparams
    out = bparams if names is None else dict(bparams)
    for i, spec in enumerate(specs):
        if spec.mlp != "moe":
            continue
        bp = bparams if names is None else bparams[names[i]]
        wp = whole if names is None else whole[names[i]]
        new = {**bp, "mlp": {**bp["mlp"], **{
            k: LayerStack(wp["mlp"][k], layer) for k in EXPERTS}}}
        if names is None:
            out = new
        else:
            out[names[i]] = new
    return out


def _layer_loop(body, carry, xs, n: int):
    """``lax.scan`` of ``body`` over ``n`` layers; one layer is a plain
    call on its slice (a reshape), where a scan would copy each of its
    weights out of the stack."""
    if n > 1:
        return jax.lax.scan(body, carry, xs)
    carry, ys = body(carry, jax.tree_util.tree_map(lambda t: t[0], xs))
    return carry, jax.tree_util.tree_map(lambda t: t[None], ys)


def _scan_blocks(params, cfg: ModelConfig, x, *, positions, window,
                 caches=None, cache_index=None, prefill=False, path=None):
    """Scan the leading dense layers, then the pattern group over
    ``pattern_repeats``.  With ``prefill`` (the prefill, which writes
    each layer's cache), ``caches`` holds each layer's cache (None for
    MLA layers) and the new caches come back.  Returns (x, aux, new
    caches, the experts each token chose (MoE layers, B*S, k) or None).
    ``path``: the layered params are :func:`stack_paths`'s, ``(L, P,
    ...)``, and each layer reads path ``path`` of its slab."""
    new_all, experts_all = {}, []
    aux = jnp.zeros((), jnp.float32)
    for gparams, specs, gcaches, names in _groups(params, cfg, caches):
        n_layers = jax.tree_util.tree_leaves(gparams)[0].shape[0]

        def body(carry, xs, specs=specs, names=names, gparams=gparams):
            h, aux = carry
            layer, bparams, bcaches = xs
            if path is not None:
                bparams = jax.tree_util.tree_map(lambda t: t[path], bparams)
            else:
                bparams = _experts_in_place(cfg, specs, names, bparams,
                                            gparams, layer)
            new_caches, experts = [], []
            for i, spec in enumerate(specs):
                pick = (lambda t: t) if names is None else \
                    (lambda t, i=i: t[names[i]])
                c = pick(bcaches) if bcaches is not None else None
                h, nc, a, chosen = _apply_block(
                    pick(bparams), cfg, spec, h, positions=positions,
                    window=window, cache=c, cache_index=cache_index)
                aux = aux + a
                new_caches.append(nc)
                if spec.mlp == "moe":
                    experts.append(chosen)
            experts = jnp.stack(experts) if experts else None
            if not prefill:
                return (h, aux), experts
            new = new_caches[0] if names is None else dict(
                zip(names, new_caches))
            return (h, aux), (new, experts)

        if cfg.remat and not prefill:
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if cfg.remat_policy == "dots" else None)
            body_fn = jax.checkpoint(body, policy=policy)
        else:
            body_fn = body
        (x, aux), ys = _layer_loop(
            body_fn, (x, aux), (jnp.arange(n_layers), gparams, gcaches),
            n_layers)
        if prefill:
            new, experts = ys
            new_all.update({"lead": new} if names is None else new)
        else:
            experts = ys
        if any(spec.mlp == "moe" for spec in specs):
            experts_all.append(experts.reshape((-1,) + experts.shape[2:]))
    experts = jnp.concatenate(experts_all) if experts_all else None
    return x, aux, (new_all if prefill else None), experts


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
    x, aux, _, _ = _scan_blocks(params, cfg, x, positions=positions,
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
    T)``; latent attention ``lat`` ``(reps, batch, r + dr, T)``, tokens
    minor (the order ``mla_decode`` reads in place; ``kv_quant`` does not
    apply to it); SSM state ``(reps, batch, ...)``.  The leading dense
    layers' cache is ``lead``, stacked over those layers.  The row axis is
    axis 1."""
    dtype = dtype or jnp.dtype(cfg.dtype)

    def stacked(spec, n):
        if spec.mixer == "mla":
            return {"lat": jnp.zeros(
                (n, batch, cfg.mla.latent_dim, cache_len), dtype)}
        if spec.mixer == "attn":
            kv = (n, batch, cfg.num_kv_heads, cfg.head_dim, cache_len)
            if not cfg.kv_quant:
                return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}
            sc = kv[:3] + kv[4:]
            return {"k": jnp.zeros(kv, jnp.int8),
                    "v": jnp.zeros(kv, jnp.int8),
                    "k_scale": jnp.zeros(sc, jnp.float32),
                    "v_scale": jnp.zeros(sc, jnp.float32)}
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n, *x.shape)),
            init_ssm_state(cfg, batch, dtype))

    caches = {f"pos{i}": stacked(spec, cfg.pattern_repeats)
              for i, spec in enumerate(cfg.pattern)}
    if cfg.first_dense:
        caches["lead"] = stacked(cfg.lead_spec, cfg.first_dense)
    return caches


LAYERED = ("blocks", "lead")      # param groups stacked over layers
# with ``counts``, the caches a prefill or decode step returns also hold
# its MoE routing under this key (the caller pops it), so the step's
# return keeps its (logits, caches) form: ``{"counts": (MoE layers, 2)
# int32, each layer's experts hit and most tokens on one expert,
# "experts": (MoE layers, B, [S,] k) int32, the experts each token ran}``
ROUTING = "routing"


def stack_paths(path_params_list):
    """Stack homogeneous paths' parameters for :func:`decode_step`'s
    ``paths``: block leaves (``blocks``, ``lead``) layer-major ``(reps, P,
    ...)``, so one layer of every path is a contiguous slice the layer
    loop reads in place; every other leaf ``(P, ...)``."""
    return {key: jax.tree_util.tree_map(
        lambda *xs, axis=int(key in LAYERED): jnp.stack(xs, axis=axis),
        *[p[key] for p in path_params_list])
        for key in path_params_list[0]}


def _each(n_p: int, fn, *args):
    """``fn`` on each path's weights and rows (leading axis ``n_p``); one
    path is called directly on its slice (a reshape), so its kernels keep
    their names in the program and no weight is laid out again for a
    batch axis.  :class:`LayerStack`s pass through whole."""
    if n_p > 1:
        return jax.vmap(fn)(*args)
    out = fn(*jax.tree_util.tree_map(
        lambda t: t if isinstance(t, LayerStack) else t[0], args,
        is_leaf=lambda t: isinstance(t, LayerStack)))
    return jax.tree_util.tree_map(lambda t: t[None], out)


def _decode_block(bp, cfg: ModelConfig, spec, x, cache, layer, *,
                  positions, ci, mask, row_offset, window):
    """One block of the decode step.  x: (P, S, 1, D), path-major rows;
    bp: this layer's weights, leaves (P, ...); cache: this layer group's
    layer-stacked leaves (L, N, ...), carried in place.  Matmuls run per
    path on its weights; attention runs over all P*S rows at once.
    Returns (x, cache, routing or None): its routing counts (2,) and
    the experts each row chose (P*S, k)."""
    n_p, n_s = x.shape[:2]

    def rows(t):
        return t.reshape((n_p * n_s,) + t.shape[2:])

    def per_path(t):
        return t.reshape((n_p, n_s) + t.shape[1:])

    def each(fn, *args):
        return _each(n_p, fn, *args)

    def norm(scale, h):
        return each(lambda s_, h_: rms_norm(s_, h_, cfg.norm_eps), scale, h)

    routing = None
    h = norm(bp["norm1"], x)
    if spec.mixer == "mla":
        q, lat = each(lambda p_, h_, pos_: mla_decode_q(p_, cfg, h_, pos_),
                      bp["mixer"], h, positions)
        out, cache = mla_attention_inplace(
            cfg, rows(q), rows(lat), cache, layer, ci, mask=mask,
            row_offset=row_offset)
        y = each(mla_decode_out, bp["mixer"], per_path(out), h)
    elif spec.mixer == "attn":
        q, k, v = each(lambda p_, h_, pos_: attention_qkv(
            p_, cfg, h_, positions=pos_), bp["mixer"], h, positions)
        out, cache = decode_attention_inplace(
            cfg, rows(q), rows(k), rows(v), cache, layer, ci,
            window=window, mask=mask, row_offset=row_offset)
        y = each(attention_out, bp["mixer"], per_path(out.astype(x.dtype)),
                 h)
    else:
        # SSM state is per row and small: read this layer's rows, step,
        # keep masked-off rows' state by a select, write the layer back
        state = jax.tree_util.tree_map(
            lambda c: jax.lax.dynamic_slice_in_dim(
                c[layer], row_offset, n_p * n_s, 0), cache)
        y, new = each(lambda p_, h_, s_: apply_mamba(
            p_, cfg, h_, state=s_),
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
            y, chosen = each(lambda p_, h_: apply_moe(p_, cfg, h_)[::2],
                             bp["mlp"], h)
            # counted per path: the paths' experts are different weights
            c = jax.vmap(lambda e: routing_counts(
                e, cfg.moe.num_experts))(chosen)
            routing = (jnp.stack([c[:, 0].sum(), c[:, 1].max()]),
                       rows(chosen))
        else:
            y = each(lambda p_, h_: apply_mlp(p_, cfg, h_), bp["mlp"], h)
        x = x + y
    return x, cache, routing


def _one_path(params, path):
    """Path ``path``'s non-layered leaves of :func:`stack_paths` params,
    keeping a one-path axis."""
    return {k: jax.tree_util.tree_map(
        lambda t: jax.lax.dynamic_slice_in_dim(t, path, 1, 0), v)
        for k, v in params.items() if k not in LAYERED}


def decode_step(params, cfg: ModelConfig, tokens, caches, cache_index, *,
                window=None, mask=None, paths=None, row_offset=0, path=None,
                counts=False):
    """One decode step.  tokens: (B, 1) -> (logits (B,1,V), new_caches).

    cache_index: int32 scalar, or a (B,) vector when the batch rows sit
    at different sequence positions (continuous batching over a slot
    arena).  caches (:func:`init_decode_cache`) ride the layer loop's
    carry and are updated in place (donate them): each layer writes one
    token per row and ``flash_decode`` / ``mla_decode`` read the layer
    where it lies.  Token row b is cache row ``row_offset + b``, so one
    island's rows can decode against a larger arena.  ``mask`` (B,)
    bool: rows where it is False keep their cache bitwise (their logits
    are junk).

    ``paths``: None for one path's params; else ``params`` is
    :func:`stack_paths` of that many paths and the rows are path-major,
    row ``p * (B // paths) + s`` on path p — the stacked tick, one
    dispatch for every island.  ``path`` (with ``paths`` None): params
    are :func:`stack_paths` of several paths, and every row runs path
    ``path`` (an int32 scalar), read layer by layer where it lies.

    With ``counts``, ``new_caches[ROUTING]`` holds the step's routing
    (see ``ROUTING``; no such key without MoE layers).
    """
    whole = None
    if paths is None:
        if path is None:
            params = stack_paths([params])
            top = params
            # one path: its layer stacks (a reshape) for in-place reads
            whole = {k: jax.tree_util.tree_map(lambda t: t[:, 0], params[k])
                     for k in LAYERED if k in params}
        else:
            top = _one_path(params, path)
        paths = 1
    else:
        top = params
    b = tokens.shape[0]
    ci = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32).reshape(-1),
                          (b,))
    tok = tokens.reshape(paths, b // paths, 1)
    x = _each(paths, lambda e, t: embed_tokens(e, cfg, t), top["embed"], tok)
    positions = ci.reshape(paths, b // paths, 1)
    window = window if window is not None else cfg.sliding_window
    found = []
    groups = _groups(params, cfg, caches)
    wholes = [None] * len(groups) if whole is None else \
        [g[0] for g in _groups(whole, cfg)]
    for (gparams, specs, gcaches, names), gwhole in zip(groups, wholes):
        n_layers = jax.tree_util.tree_leaves(gcaches)[0].shape[0]

        def body(carry, xs, specs=specs, names=names, gwhole=gwhole):
            h, cs = carry
            layer, bparams = xs
            if path is not None:
                bparams = jax.tree_util.tree_map(
                    lambda t: jax.lax.dynamic_slice_in_dim(t, path, 1, 0),
                    bparams)
            if gwhole is not None:
                # experts read whole (no path axis); the rest per path
                bparams = _experts_in_place(cfg, specs, names, bparams,
                                            gwhole, layer)
            cs = dict(cs) if names is not None else cs
            got = []
            for i, spec in enumerate(specs):
                if names is None:
                    h, cs, routing = _decode_block(
                        bparams, cfg, spec, h, cs, layer,
                        positions=positions, ci=ci, mask=mask,
                        row_offset=row_offset, window=window)
                else:
                    h, cs[names[i]], routing = _decode_block(
                        bparams[names[i]], cfg, spec, h, cs[names[i]],
                        layer, positions=positions, ci=ci, mask=mask,
                        row_offset=row_offset, window=window)
                if routing is not None:
                    got.append(routing)
            return (h, cs), (jax.tree_util.tree_map(
                lambda *t: jnp.stack(t), *got) if got else None)

        (x, gcaches), routing = _layer_loop(
            body, (x, gcaches), (jnp.arange(n_layers), gparams), n_layers)
        caches = dict(caches)
        if names is None:
            caches["lead"] = gcaches
        else:
            caches.update(gcaches)
        if routing is not None:
            found.append(jax.tree_util.tree_map(
                lambda t: t.reshape((-1,) + t.shape[2:]), routing))
    logits = _each(paths, lambda fn, e, h: unembed(
        e, cfg, rms_norm(fn, h, cfg.norm_eps)),
        top["final_norm"], top["embed"], x)
    logits = logits.reshape(b, 1, -1)
    if counts and found:
        hit, experts = (jnp.concatenate(t) for t in zip(*found))
        caches = {**caches, ROUTING: {"counts": hit, "experts": experts}}
    return logits, caches


def _place_latents(lat, cache_len: int):
    """A prefill's latent entries (L, B, S, C) -> the arena's layout (L,
    B, C, T), at slots 0..S-1."""
    lat = jnp.swapaxes(lat, -1, -2)
    return {"lat": jnp.pad(lat, [(0, 0)] * 3
                           + [(0, cache_len - lat.shape[-1])])}


def prefill(params, cfg: ModelConfig, tokens, cache_len: int, *,
            window=None, patch_embeds=None, last=None, path=None,
            counts=False):
    """Single-pass prompt ingestion: forward ``tokens`` once, writing the
    KV/latent/SSM decode caches (positions 0..S-1).

    Returns (logits (B,S,V), caches) — ``logits[:, -1]`` predicts the
    first generated token and ``caches`` is ready for ``decode_step`` at
    ``cache_index = S``.  Replaces the O(S) replay-through-decode loop
    the one-shot serving engine uses.  With ``last`` (B,) int32, only
    those positions are unembedded: logits (B, V), each row's at its
    ``last`` position (its last real token in a padded bucket).
    ``path``: params are :func:`stack_paths` of several paths and the
    prompt runs path ``path`` (int32 scalar), read layer by layer where
    it lies.  With ``counts``, ``caches[ROUTING]`` holds the routing,
    as in :func:`decode_step`.
    """
    b, s = tokens.shape
    if s > cache_len:
        raise ValueError(f"prompt length {s} exceeds cache_len {cache_len}")
    stacked = params
    if path is not None:
        params = {k: v if k in LAYERED else jax.tree_util.tree_map(
            lambda t: t[path], v) for k, v in params.items()}
        # gather the prompt's rows straight from the stacked table: a
        # slice of it would be copied whole
        table = stacked["embed"]["embedding"]
        tokens_in = tokens + path * table.shape[1]
        params["embed"] = {**params["embed"], "embedding": table.reshape(
            (-1,) + table.shape[2:])}
    else:
        tokens_in = tokens
    caches = init_decode_cache(cfg, b, cache_len)
    # MLA layers collect their blocks' entries instead of a written cache
    latent = [n for n in caches if (cfg.lead_spec if n == "lead" else
                                    cfg.pattern[int(n[3:])]).mixer == "mla"]
    caches = {n: (None if n in latent else c) for n, c in caches.items()}
    x = _embed_inputs(params, cfg, tokens_in, patch_embeds)
    positions = jnp.arange(s)[None, :]
    window = window if window is not None else cfg.sliding_window
    if path is not None:
        params["embed"] = jax.tree_util.tree_map(lambda t: t[path],
                                                 stacked["embed"])
    x, aux, new_caches, found = _scan_blocks(
        params, cfg, x, positions=positions, window=window,
        caches=caches, cache_index=jnp.int32(0), prefill=True, path=path)
    for n in latent:
        new_caches[n] = _place_latents(new_caches[n], cache_len)
    if last is not None:
        x = jnp.take_along_axis(x, last[:, None, None], axis=1)
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], cfg, x)
    if last is not None:
        logits = logits[:, 0]
    if counts and found is not None:
        new_caches[ROUTING] = {
            "counts": jax.vmap(lambda e: routing_counts(
                e, cfg.moe.num_experts))(found),
            "experts": found.reshape(found.shape[:1] + (b, s)
                                     + found.shape[2:])}
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
