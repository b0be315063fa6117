"""Logical-axis -> mesh PartitionSpec rules (MaxText-style).

Each logical axis maps to a priority list of mesh-axis candidates; a
candidate is taken only if (a) its mesh axes exist, (b) none is already
used by an earlier dimension of the same tensor, and (c) the dimension is
divisible by the candidate's total size.  Otherwise the dimension is
replicated — honest fallback that the roofline then exposes.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.models import params as P

# priority lists; entries are a mesh axis name or tuple of names
DEFAULT_RULES: dict[str, tuple] = {
    P.WORKER: (("pod", "data"), "data"),
    P.BATCH: (("pod", "data"), "data"),
    P.HEADS: ("model",),
    P.KV_HEADS: ("model",),
    P.MLP: ("model",),
    P.EXPERT: ("model",),
    P.EXPERT_MLP: ("model",),
    P.VOCAB: ("model",),
    P.SSM_INNER: ("model",),
    # never sharded:
    P.LAYERS: (), P.EMBED: (), P.HEAD_DIM: (), P.SEQ: (), P.CONV: (),
    P.LATENT: (),
    P.SSM_STATE: (), None: (),
}


def _axes_size(mesh: Mesh, cand) -> int:
    axs = cand if isinstance(cand, tuple) else (cand,)
    return math.prod(mesh.shape[a] for a in axs)


def spec_for(axes: tuple, shape: tuple, mesh: Mesh,
             rules: dict | None = None) -> PartitionSpec:
    rules = rules or DEFAULT_RULES
    used: set = set()
    parts = []
    for name, dim in zip(axes, shape):
        choice = None
        for cand in rules.get(name, ()):
            axs = cand if isinstance(cand, tuple) else (cand,)
            if any(a not in mesh.shape or a in used for a in axs):
                continue
            if dim > 0 and dim % _axes_size(mesh, cand) == 0:
                choice = cand
                used.update(axs)
                break
        parts.append(choice)
    return PartitionSpec(*parts)


def shardings_for_tree(params_shape, axes, mesh: Mesh, *, prepend=(),
                       rules: dict | None = None):
    """Map a (shapes, axes) tree to NamedShardings.

    ``prepend``: logical axes prepended to every leaf (e.g. ("worker",)
    for worker-stacked trees).
    """
    def one(leaf, ax):
        full_axes = tuple(prepend) + tuple(ax)
        spec = spec_for(full_axes, leaf.shape, mesh, rules)
        return NamedSharding(mesh, spec)

    return P.tree_map_with_axes(one, params_shape, axes)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, PartitionSpec())


def worker_stacked_sharding(mesh: Mesh):
    """NamedSharding for worker-stacked (W, ...) leaves: the leading
    worker axis over the mesh's worker axes, everything else
    replicated — the layout the streaming mesh outer step's collectives
    (launch/steps.py) assume."""
    cand = ("pod", "data") if "pod" in mesh.shape else "data"
    return NamedSharding(mesh, PartitionSpec(cand))


def batch_sharding(mesh: Mesh, ndim: int, *, batch_dim: int = 0):
    parts = [None] * ndim
    cand = ("pod", "data") if "pod" in mesh.shape else ("data",)
    parts[batch_dim] = cand if len(cand) > 1 else cand[0]
    return NamedSharding(mesh, PartitionSpec(*parts))
