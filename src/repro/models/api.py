"""Unified model API over decoder-LMs and encoder-decoders.

All call sites (DiPaCo trainer, dry-run, serving, tests) go through:
  init_model(key, cfg)            -> (params, axes)
  forward_loss(params, cfg, batch)-> (loss, aux)   batch: dict of arrays
  forward_logits(params, cfg, batch) -> logits
  init_serve_cache(cfg, batch, cache_len)
  prefill(params, cfg, batch, cache_len) -> (logits, cache)
  serve_step(params, cfg, batch, cache, index) -> (logits, new_cache)
  stack_paths(path_params_list)   -> params for serve_step(paths=P)

``serve_step`` (alias ``decode_step``) accepts a scalar index or a (B,)
vector of per-row positions, so a continuous-batching engine can decode
a slot arena whose rows sit at different sequence offsets.
"""
from __future__ import annotations

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


def prefill(params, cfg: ModelConfig, batch, cache_len: int, *, window=None):
    """Single-pass prompt ingestion -> (logits, decode-ready cache).

    For decoder LMs this is one forward writing the cache at positions
    0..S-1 (logits shape (B,S,V)).  Encoder-decoders fall back to a
    sequential replay (logits shape (B,1,V)); in both cases
    ``logits[:, -1]`` predicts the first generated token.
    """
    tokens = batch["tokens"]
    if is_encdec(cfg):
        cache = init_serve_cache(cfg, tokens.shape[0], cache_len)
        logits = None
        for t in range(tokens.shape[1]):
            logits, cache = serve_step(
                params, cfg, {**batch, "tokens": tokens[:, t:t + 1]},
                cache, jnp.int32(t), window=window)
        return logits, cache
    return LM.prefill(params, cfg, tokens, cache_len, window=window,
                      patch_embeds=batch.get("patch_embeds"))


def serve_step(params, cfg: ModelConfig, batch, cache, index, *, window=None,
               mask=None, paths=None, row_offset=0):
    """One-token decode.  batch: dict(tokens (B,1) [+ enc_out and/or
    precomputed cross_kv for enc-dec models]).  The cache is updated in
    place (donate it).  Decoder LMs also take ``mask`` (rows to advance),
    ``paths`` (params from :func:`stack_paths`, rows path-major) and
    ``row_offset`` (token row b is cache row ``row_offset + b``): see
    ``lm.decode_step``."""
    if is_encdec(cfg):
        if mask is not None or paths is not None or row_offset != 0:
            raise NotImplementedError(
                "enc-dec decode takes no mask, stacked paths or row offset")
        return ED.decode_step_encdec(params, cfg, batch["tokens"],
                                     batch.get("enc_out"), cache, index,
                                     window=window,
                                     cross_kv=batch.get("cross_kv"))
    return LM.decode_step(params, cfg, batch["tokens"], cache, index,
                          window=window, mask=mask, paths=paths,
                          row_offset=row_offset)


decode_step = serve_step
stack_paths = LM.stack_paths
