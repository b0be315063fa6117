"""Minimal pure-JAX parameter system with logical sharding axes.

Every layer exposes ``init(key, cfg) -> (params, axes)`` where ``params``
is a nested dict of jnp arrays and ``axes`` mirrors its structure with
leaves that are tuples of logical axis names (or None), one per array
dimension.  Logical axes are translated to mesh ``PartitionSpec``s by
``repro.launch.sharding.logical_to_spec`` (MaxText-style rules).
"""
from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

ParamTree = Any  # nested dict[str, ParamTree | jnp.ndarray]
AxisTree = Any   # same structure, leaves: tuple[str | None, ...]

# ---------------------------------------------------------------------------
# Logical axis names used across the codebase.
# ---------------------------------------------------------------------------
WORKER = "worker"       # DiPaCo path-worker (island) axis
LAYERS = "layers"       # stacked (scanned) layer axis
BATCH = "batch"
SEQ = "seq"
EMBED = "embed"
HEADS = "heads"
KV_HEADS = "kv_heads"
HEAD_DIM = "head_dim"
MLP = "mlp"
VOCAB = "vocab"
EXPERT = "expert"
EXPERT_MLP = "expert_mlp"
LATENT = "latent"       # MLA key/value latent and rotary key
SSM_INNER = "ssm_inner"
SSM_STATE = "ssm_state"
CONV = "conv"


def leaf_axes(*names):
    return tuple(names)


def init_dense(key, in_dim: int, out_dim: int, in_axis, out_axis,
               dtype=jnp.float32, scale: float | None = None):
    """He/LeCun-style init for a [in, out] matrix with logical axes."""
    scale = (1.0 / math.sqrt(in_dim)) if scale is None else scale
    w = jax.random.normal(key, (in_dim, out_dim), dtype) * scale
    return w, (in_axis, out_axis)


def init_stacked(key, stack: int, shape, axes, dtype=jnp.float32,
                 scale: float = 1.0, stack_axis: str = LAYERS):
    w = jax.random.normal(key, (stack, *shape), dtype) * scale
    return w, (stack_axis, *axes)


def tree_map_with_axes(fn: Callable, params: ParamTree, axes: AxisTree):
    """Map fn(leaf, axes_leaf) over parallel trees."""
    if isinstance(params, dict):
        return {k: tree_map_with_axes(fn, params[k], axes[k]) for k in params}
    return fn(params, axes)


def tree_axes_flatten(params: ParamTree, axes: AxisTree, prefix=()):  # -> list[(path, leaf, axes)]
    out = []
    if isinstance(params, dict):
        for k in params:
            out.extend(tree_axes_flatten(params[k], axes[k], prefix + (k,)))
    else:
        out.append((prefix, params, axes))
    return out


def count_params(params: ParamTree) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


def cast_tree(params: ParamTree, dtype) -> ParamTree:
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        params)
