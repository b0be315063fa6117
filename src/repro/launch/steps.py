"""Distributed DiPaCo step builders (stacked-worker formulation).

Inner train step: every worker (island) trains its own path on its own
shard — expressed as ``vmap`` over a leading worker axis that is sharded
over the ("pod","data") mesh axes.  Per-step collectives therefore stay
on the "model" axis (tensor parallel inside an island).

Outer step: DiLoCo-per-module mixing across the worker axis — the only
cross-island communication, once per tau inner steps.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.diloco import outer_step as _outer_step
from repro.models import api
from repro.models import params as P
from repro.models.config import ModelConfig
from repro.optim import adamw_init, adamw_update


# ---------------------------------------------------------------------------
# Shapes / init helpers
# ---------------------------------------------------------------------------
def init_worker_params(key, cfg: ModelConfig, num_workers: int):
    """All workers start from the same pretrained init (Algorithm 1)."""
    params, axes = api.init_model(key, cfg)
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (num_workers, *x.shape)), params)
    return stacked, axes


def model_param_shapes(cfg: ModelConfig):
    """(shapes, axes) via eval_shape — no allocation, safe for 340B."""
    box = {}

    def init():
        p, a = api.init_model(jax.random.PRNGKey(0), cfg)
        box["axes"] = a
        return p

    shapes = jax.eval_shape(init)
    return shapes, box["axes"]


def worker_param_shapes(cfg: ModelConfig, num_workers: int):
    """Stacked eval_shape version (no allocation) for AOT lowering."""
    shapes, axes = model_param_shapes(cfg)
    stacked = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((num_workers, *s.shape), s.dtype),
        shapes)
    return stacked, axes


def adamw_state_shapes(param_shapes):
    f32 = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), param_shapes)
    return {"m": f32, "v": f32,
            "count": jax.ShapeDtypeStruct((), jnp.int32)}


# ---------------------------------------------------------------------------
# Inner train step
# ---------------------------------------------------------------------------
def make_inner_train_step(cfg: ModelConfig):
    """(worker_params, opt_state, batch, lr) -> (params, opt, metrics).

    worker_params: (W, ...) stacked; opt_state: vmapped AdamW state per
    worker; batch: dict of (W, B_local, ...) arrays.
    """
    def one_worker(params, opt_state, batch, lr):
        (loss, parts), grads = jax.value_and_grad(
            api.forward_loss, has_aux=True)(params, cfg, batch)
        new_params, new_opt = adamw_update(grads, opt_state, params, lr=lr)
        return new_params, new_opt, {"loss": loss, **parts}

    def step(worker_params, opt_state, batch, lr):
        return jax.vmap(one_worker, in_axes=(0, 0, 0, None))(
            worker_params, opt_state, batch, lr)

    return step


def make_sync_train_step(cfg: ModelConfig, mix_layers, mix_shared, axes):
    """Fully-synchronous DiPaCo baseline (paper §4.5): per-step gradient
    mixing across paths, module by module, then a single AdamW update."""
    from repro.core.diloco import mix_deltas

    def step(worker_params, opt_state, batch, lr):
        def loss_fn(params, b):
            loss, parts = api.forward_loss(params, cfg, b)
            return loss, parts

        (loss, parts), grads = jax.vmap(
            jax.value_and_grad(loss_fn, has_aux=True))(worker_params, batch)
        mixed = mix_deltas(grads, axes, mix_layers, mix_shared)
        new_params, new_opt = jax.vmap(
            lambda g, o, p: adamw_update(g, o, p, lr=lr))(
                mixed, opt_state, worker_params)
        return new_params, new_opt, {"loss": loss, **parts}

    return step


# ---------------------------------------------------------------------------
# Outer (DiLoCo) step
# ---------------------------------------------------------------------------
def make_outer_step(cfg: ModelConfig, axes, *, lr=0.7, momentum=0.9,
                    nesterov=True):
    def step(worker_params, global_params, outer_state, mix_layers,
             mix_shared):
        return _outer_step(worker_params, global_params, outer_state, axes,
                           mix_layers, mix_shared, lr=lr, momentum=momentum,
                           nesterov=nesterov)

    return step


# ---------------------------------------------------------------------------
# Streaming mesh outer step (real collectives over the worker axes)
# ---------------------------------------------------------------------------
#
# The PR-5 fragment schedule, lowered onto an actual device mesh:
# the phase is split into K scan segments (core.fragments.segment_bounds);
# at the end of segment s fragment s's delta is cut, per-row quantized,
# and its reduce DISPATCHED — seg(s+1)'s inner compute is enqueued right
# behind it with no data dependency, so the runtime overlaps the
# fragment all-reduce with the next segment's compute.  The update lands
# one segment later (applies touch only their own fragment's leaves).
#
# Bit-exactness strategy vs the single-process oracle
# (core.diloco.segmented_streaming_phase): the reduce all_gathers the
# full (W, ...) wire leaf over the worker axes and evaluates the SAME
# full mixing einsum (core.diloco.mix_leaf) on every device, then slices
# its local rows — no psum, whose reduction order would differ from the
# einsum's.  Quantization is per worker row on both sides
# (core.diloco.rowwise_quantize_with_feedback), so row scales never
# depend on how rows are sharded.

def worker_partition_spec(mesh):
    """PartitionSpec sharding a leading worker axis over the mesh's
    worker axes (everything else replicated)."""
    from jax.sharding import PartitionSpec
    from repro.launch.mesh import worker_axes
    waxes = worker_axes(mesh)
    return PartitionSpec(waxes if len(waxes) > 1 else waxes[0])


def make_fragment_reduce_step(mesh, ax_list):
    """shard_map fragment all-reduce: ``(wire_f, mix_layers, mix_shared)
    -> og_f`` with every leaf all_gathered over ``worker_axes(mesh)``,
    mixed with the full einsum each device evaluates identically, and
    sliced back to the local rows.  ``ax_list`` is the flatten-order
    logical-axes list (core.diloco.leaf_axes_list)."""
    from jax.sharding import PartitionSpec
    from repro.core.diloco import mix_leaf
    from repro.launch.mesh import worker_axes

    waxes = worker_axes(mesh)
    wspec = worker_partition_spec(mesh)
    nshards = 1
    for a in waxes:
        nshards *= mesh.shape[a]

    def _shard_index():
        idx = 0
        for a in waxes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    def _local(wire_f, mixl, mixs):
        def one(i, x):
            full = jax.lax.all_gather(x, waxes, axis=0, tiled=True)
            og = mix_leaf(full, ax_list[i], mixl, mixs)
            wl = x.shape[0]
            return jax.lax.dynamic_slice_in_dim(
                og, _shard_index() * wl, wl, axis=0)

        return {i: one(i, x) for i, x in wire_f.items()}

    fn = jax.shard_map(_local, mesh=mesh,
                       in_specs=(wspec, PartitionSpec(), PartitionSpec()),
                       out_specs=wspec, check_vma=False)
    return jax.jit(fn)


def make_segment_scan_fn(cfg: ModelConfig):
    """jitted inner-segment runner ``(worker_params, opt_state, batches,
    lrs) -> (worker_params, opt_state, losses)``; ``batches`` is a
    (S, W, B, T) token array, one scan iteration per inner step."""
    inner = make_inner_train_step(cfg)

    def seg(worker_params, opt_state, batches, lrs):
        def body(carry, inp):
            wp, opt = carry
            batch, lr = inp
            wp, opt, metrics = inner(wp, opt, {"tokens": batch}, lr)
            return (wp, opt), metrics["loss"]

        (wp, opt), losses = jax.lax.scan(
            body, (worker_params, opt_state), (batches, lrs))
        return wp, opt, losses

    donate = () if jax.default_backend() == "cpu" else (0, 1)
    return jax.jit(seg, donate_argnums=donate)


def make_streaming_mesh_phase(cfg: ModelConfig, mesh, axes, fragspec, *,
                              comm_dtype: str = "fp32", outer_lr=0.7,
                              outer_momentum=0.9, outer_nesterov=True):
    """Build the overlapped streaming phase runner.

    Returns ``phase(worker_params, opt_state, global_params,
    frag_states, residuals, mix_layers, mix_shared, seg_batches,
    seg_lrs) -> (worker_params, opt_state, global_params, frag_states,
    residuals, losses)`` where ``seg_batches[s]``/``seg_lrs[s]`` hold
    segment ``s``'s inner-step inputs.  The dispatch order per segment
    is ``seg(s) -> apply(s-1) -> delta(s) -> reduce(s)``: reduce(s) is
    in flight while seg(s+1) computes.  Bit-exact to
    ``core.diloco.segmented_streaming_phase`` driven by the same
    jitted segment fn (regression-tested in tests/test_mesh_steps.py).
    With ``fragspec.num_fragments == 1`` this is classic burst DiLoCo
    through the same code path — the benchmark's baseline lane.
    """
    from repro.core.diloco import (leaf_axes_list, make_fragment_apply_fn,
                                   make_fragment_delta_fn)

    shapes, _ = model_param_shapes(cfg)
    ax_list = leaf_axes_list(shapes, axes)
    seg_fn = make_segment_scan_fn(cfg)
    delta_fn = make_fragment_delta_fn(comm_dtype)
    reduce_fn = make_fragment_reduce_step(mesh, ax_list)
    apply_fn = make_fragment_apply_fn(
        lr=outer_lr, momentum=outer_momentum, nesterov=outer_nesterov)
    K = fragspec.num_fragments

    def _apply(pending, g_leaves, states, w_leaves):
        f, og = pending
        state_f = {i: states[f][i] for i in og}
        g_f = {i: g_leaves[i] for i in og}
        w_f = {i: w_leaves[i] for i in og}
        new_g, new_s, new_w = apply_fn(og, state_f, g_f, w_f)
        for i in og:
            g_leaves[i] = new_g[i]
            states[f][i] = new_s[i]
            w_leaves[i] = new_w[i]

    def phase(worker_params, opt_state, global_params, frag_states,
              residuals, mix_layers, mix_shared, seg_batches, seg_lrs):
        g_leaves = list(fragspec.flatten(global_params))
        states = [dict(s) for s in frag_states]
        resid = dict(residuals or {})
        losses = []
        pending = None
        wp, opt = worker_params, opt_state
        for s in range(K):
            wp, opt, seg_losses = seg_fn(wp, opt, seg_batches[s],
                                         seg_lrs[s])
            losses.append(seg_losses)
            w_leaves = list(fragspec.flatten(wp))
            if pending is not None:
                _apply(pending, g_leaves, states, w_leaves)
                wp = fragspec.unflatten(w_leaves)
            idx = fragspec.indices[s]
            w_f = {i: w_leaves[i] for i in idx}
            g_f = {i: g_leaves[i] for i in idx}
            r_f = ({i: resid[i] for i in idx}
                   if all(i in resid for i in idx) else None)
            wire, new_r = delta_fn(w_f, g_f, r_f)
            if new_r is not None:
                resid.update(new_r)
            og = reduce_fn(wire, mix_layers, mix_shared)
            pending = (s, og)
        w_leaves = list(fragspec.flatten(wp))
        _apply(pending, g_leaves, states, w_leaves)
        wp = fragspec.unflatten(w_leaves)
        return (wp, opt, fragspec.unflatten(g_leaves), states, resid,
                jnp.concatenate(losses, axis=0))

    return phase


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------
def make_prefill_step(cfg: ModelConfig):
    """Forward scoring over stacked workers: batch dict of (W, b, ...)."""
    def step(worker_params, batch):
        def one(params, b):
            logits, aux = api.forward_logits(params, cfg, b)
            return logits

        return jax.vmap(one)(worker_params, batch)

    return step


def make_decode_step(cfg: ModelConfig, *, window=None, stacked: bool = True):
    """One-token decode; stacked=False for single-path (long-context)."""
    def one(params, batch, cache, index):
        return api.serve_step(params, cfg, batch, cache, index,
                              window=window)

    if not stacked:
        return one

    def step(worker_params, batch, caches, index):
        return jax.vmap(one, in_axes=(0, 0, 0, None))(
            worker_params, batch, caches, index)

    return step
