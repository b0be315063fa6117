"""Unified model API over decoder-LMs and encoder-decoders.

All call sites (DiPaCo trainer, dry-run, serving, tests) go through:
  init_model(key, cfg)            -> (params, axes)
  forward_loss(params, cfg, batch)-> (loss, aux)   batch: dict of arrays
  forward_logits(params, cfg, batch) -> logits
  init_serve_cache(cfg, batch, cache_len)
  prefill(params, cfg, batch, cache_len) -> (logits, cache)
  path_params(params, p, paths)   -> one path's params
  serve_step(params, cfg, batch, cache, index) -> (logits, new_cache)
  stack_paths(path_params_list)   -> params for serve_step(paths=P)

``serve_step`` (alias ``decode_step``) accepts a scalar index or a (B,)
vector of per-row positions, so a continuous-batching engine can decode
a slot arena whose rows sit at different sequence offsets.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .config import ModelConfig
from . import encdec as ED
from . import lm as LM
from .lm import lm_loss_mean


def is_encdec(cfg: ModelConfig) -> bool:
    return cfg.encoder is not None


def init_model(key, cfg: ModelConfig):
    if is_encdec(cfg):
        return ED.init_encdec(key, cfg)
    return LM.init_lm(key, cfg)


def forward_logits(params, cfg: ModelConfig, batch, *, window=None):
    if is_encdec(cfg):
        logits, aux = ED.apply_encdec(params, cfg, batch["tokens"],
                                      batch["frames"], window=window)
    else:
        logits, aux = LM.apply_lm(params, cfg, batch["tokens"],
                                  patch_embeds=batch.get("patch_embeds"),
                                  window=window)
    return logits, aux


def forward_loss(params, cfg: ModelConfig, batch, *, window=None):
    logits, aux = forward_logits(params, cfg, batch, window=window)
    loss = lm_loss_mean(logits, batch["tokens"], cfg.route_prefix_len)
    return loss + aux, {"lm_loss": loss, "aux_loss": aux}


def init_serve_cache(cfg: ModelConfig, batch: int, cache_len: int):
    if is_encdec(cfg):
        return ED.init_encdec_cache(cfg, batch, cache_len)
    return LM.init_decode_cache(cfg, batch, cache_len)


def prefill(params, cfg: ModelConfig, batch, cache_len: int, *, window=None,
            last=None, path=None, counts=False):
    """Single-pass prompt ingestion -> (logits, decode-ready cache).

    For decoder LMs this is one forward writing the cache at positions
    0..S-1 (logits shape (B,S,V)).  Encoder-decoders fall back to a
    sequential replay (logits shape (B,1,V)); in both cases
    ``logits[:, -1]`` predicts the first generated token.  Decoder LMs
    also take ``last`` (unembed only each row's ``last[b]`` position:
    logits (B, V)), ``path`` (params from :func:`stack_paths`, run path
    ``path``) and ``counts`` (the cache also holds the MoE routing counts
    under ``lm.ROUTING``): see ``lm.prefill``.
    """
    tokens = batch["tokens"]
    if is_encdec(cfg):
        if last is not None or path is not None or counts:
            raise NotImplementedError(
                "enc-dec prefill takes no last, path or counts")
        cache = init_serve_cache(cfg, tokens.shape[0], cache_len)
        logits = None
        for t in range(tokens.shape[1]):
            logits, cache = serve_step(
                params, cfg, {**batch, "tokens": tokens[:, t:t + 1]},
                cache, jnp.int32(t), window=window)
        return logits, cache
    return LM.prefill(params, cfg, tokens, cache_len, window=window,
                      patch_embeds=batch.get("patch_embeds"), last=last,
                      path=path, counts=counts)


def serve_step(params, cfg: ModelConfig, batch, cache, index, *, window=None,
               mask=None, paths=None, row_offset=0, path=None, counts=False):
    """One-token decode.  batch: dict(tokens (B,1) [+ enc_out and/or
    precomputed cross_kv for enc-dec models]).  The cache is updated in
    place (donate it).  Decoder LMs also take ``mask`` (rows to advance),
    ``paths`` (params from :func:`stack_paths`, rows path-major),
    ``row_offset`` (token row b is cache row ``row_offset + b``),
    ``path`` (params from :func:`stack_paths`, every row on path
    ``path``) and ``counts`` (the cache also holds the MoE routing counts
    under ``lm.ROUTING``): see ``lm.decode_step``."""
    if is_encdec(cfg):
        if (mask is not None or paths is not None or row_offset != 0
                or path is not None or counts):
            raise NotImplementedError(
                "enc-dec decode takes no mask, stacked paths or row offset")
        return ED.decode_step_encdec(params, cfg, batch["tokens"],
                                     batch.get("enc_out"), cache, index,
                                     window=window,
                                     cross_kv=batch.get("cross_kv"))
    return LM.decode_step(params, cfg, batch["tokens"], cache, index,
                          window=window, mask=mask, paths=paths,
                          row_offset=row_offset, path=path, counts=counts)


decode_step = serve_step
stack_paths = LM.stack_paths


def path_params(params, p: int, paths):
    """Path ``p``'s params: ``params`` itself when ``paths`` is None (one
    path, unstacked), else a slice of :func:`stack_paths`'s tree."""
    if paths is None:
        return params
    return {k: jax.tree_util.tree_map(
        lambda t: t[:, p] if k in LM.LAYERED else t[p], v)
        for k, v in params.items()}
