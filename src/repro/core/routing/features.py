"""Routing features g(document) (paper §7.2.1): the average of the last
transformer block's hidden state over the first 32 tokens, computed with
the base (pretrained) LM."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.lm import apply_lm


def prefix_features(params, cfg: ModelConfig, tokens, prefix_len=None,
                    batch_size: int = 64):
    """tokens: (N, S) -> (N, d_model) float32 features."""
    pl = prefix_len or cfg.route_prefix_len

    # params are an argument, not a closure: closed-over arrays would be
    # baked into the executable as constants (the whole model's weights)
    @jax.jit
    def feat(p, tk):
        hidden, _ = apply_lm(p, cfg, tk[:, :pl], return_hidden=True)
        return jnp.mean(hidden.astype(jnp.float32), axis=1)

    outs = []
    for i in range(0, tokens.shape[0], batch_size):
        outs.append(feat(params, tokens[i:i + batch_size]))
    return jnp.concatenate(outs, axis=0)
