"""ShapeDtypeStruct input specs + dry-run case builders.

``build_case(cfg, shape, mesh)`` assembles the jittable step function and
fully-sharded argument shape structs for one (architecture x input-shape
x mesh) combination — no device allocation (AOT ``.lower()``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.partition import make_partition, mixing_matrices
from repro.models import api
from repro.models import params as P
from repro.models.config import DiPaCoConfig, InputShape, ModelConfig
from . import steps as S
from .mesh import num_workers as mesh_num_workers, worker_axes
from .sharding import DEFAULT_RULES, shardings_for_tree, spec_for

CACHE_SEQ = "cache_seq"
RULES = dict(DEFAULT_RULES)
RULES[CACHE_SEQ] = ("model",)
RULES["enc_seq"] = ()


def rules_for(cfg: ModelConfig) -> dict:
    """Per-arch sharding rules.  island_parallelism == "data": within an
    island the 16 "model" chips data-parallelize the worker's batch and
    replicate the (small) path params — per-step collective becomes one
    param-sized grad all-reduce instead of 4L activation all-reduces
    (perf iteration #1, EXPERIMENTS.md §Perf)."""
    if cfg.island_parallelism != "data":
        return RULES
    r = dict(RULES)
    for name in (P.HEADS, P.KV_HEADS, P.MLP, P.EXPERT, P.EXPERT_MLP,
                 P.VOCAB, P.SSM_INNER):
        r[name] = ()
    r[P.BATCH] = ("model", ("pod", "data"))
    return r


def _auto(mesh):
    """``mesh`` with every axis Auto: the dry-run's layouts are input
    shardings the partitioner propagates, as the model code expects
    (``jax.make_mesh`` now makes Explicit axes by default)."""
    from jax.sharding import AxisType, Mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def sds(shape, dtype, mesh, axes, rules=None):
    spec = spec_for(tuple(axes), tuple(shape), mesh, rules or RULES)
    return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                sharding=NamedSharding(_auto(mesh), spec))


def tree_sds(shapes, axes, mesh, prepend=(), rules=None):
    def one(s, ax):
        return sds(s.shape, s.dtype, mesh, tuple(prepend) + tuple(ax),
                   rules)

    return P.tree_map_with_axes(one, shapes, axes)


# ---------------------------------------------------------------------------
# Cache shape/axes trees (parallel to models.api.init_serve_cache)
# ---------------------------------------------------------------------------
def decode_cache_shapes(cfg: ModelConfig, batch: int, cache_len: int):
    dtype = jnp.dtype(cfg.dtype)
    if api.is_encdec(cfg):
        kv = jax.ShapeDtypeStruct(
            (cfg.num_layers, batch, cfg.num_kv_heads, cfg.head_dim,
             cache_len), dtype)
        kv_ax = (P.LAYERS, P.BATCH, P.KV_HEADS, P.HEAD_DIM, CACHE_SEQ)
        return {"k": kv, "v": kv}, {"k": kv_ax, "v": kv_ax}

    def stacked(spec, n):
        if spec.mixer == "mla":
            return ({"lat": jax.ShapeDtypeStruct(
                (n, batch, cfg.mla.latent_dim, cache_len), dtype)},
                {"lat": (P.LAYERS, P.BATCH, P.LATENT, CACHE_SEQ)})
        if spec.mixer == "attn":
            kv_dtype = jnp.int8 if cfg.kv_quant else dtype
            kv = jax.ShapeDtypeStruct(
                (n, batch, cfg.num_kv_heads, cfg.head_dim, cache_len),
                kv_dtype)
            kv_ax = (P.LAYERS, P.BATCH, P.KV_HEADS, P.HEAD_DIM, CACHE_SEQ)
            shapes, axes = {"k": kv, "v": kv}, {"k": kv_ax, "v": kv_ax}
            if cfg.kv_quant:
                sc = jax.ShapeDtypeStruct(
                    (n, batch, cfg.num_kv_heads, cache_len), jnp.float32)
                sc_ax = (P.LAYERS, P.BATCH, P.KV_HEADS, CACHE_SEQ)
                shapes.update(k_scale=sc, v_scale=sc)
                axes.update(k_scale=sc_ax, v_scale=sc_ax)
            return shapes, axes
        from repro.models.ssm import ssm_dims
        d_inner, n_heads, conv_dim = ssm_dims(cfg)
        return ({
            "conv": jax.ShapeDtypeStruct(
                (n, batch, cfg.ssm.conv_width - 1, conv_dim), dtype),
            "ssm": jax.ShapeDtypeStruct(
                (n, batch, n_heads, cfg.ssm.head_dim, cfg.ssm.d_state),
                jnp.float32),
        }, {
            "conv": (P.LAYERS, P.BATCH, P.CONV, P.SSM_INNER),
            "ssm": (P.LAYERS, P.BATCH, P.HEADS, P.HEAD_DIM, P.SSM_STATE),
        })

    shapes, axes = {}, {}
    for i, spec in enumerate(cfg.pattern):
        shapes[f"pos{i}"], axes[f"pos{i}"] = stacked(spec,
                                                     cfg.pattern_repeats)
    if cfg.first_dense:
        shapes["lead"], axes["lead"] = stacked(cfg.lead_spec,
                                               cfg.first_dense)
    return shapes, axes


# ---------------------------------------------------------------------------
# Batch input specs
# ---------------------------------------------------------------------------
def batch_specs(cfg: ModelConfig, shape: InputShape, mesh, *,
                stacked: bool = True, rules=None):
    """Token (+frontend stub) inputs as sharded ShapeDtypeStructs."""
    W = mesh_num_workers(mesh) if stacked else 1
    gb = shape.global_batch
    assert gb % W == 0 or not stacked, (gb, W)
    b_local = gb // W if stacked else gb
    lead = (P.WORKER,) if stacked else ()
    lead_dim = (W,) if stacked else ()
    if shape.kind == "decode":
        seq = 1
    else:
        seq = shape.seq_len
    out = {"tokens": sds((*lead_dim, b_local, seq), jnp.int32, mesh,
                         (*lead, P.BATCH, P.SEQ), rules)}
    if cfg.vision is not None and shape.kind != "decode":
        out["patch_embeds"] = sds(
            (*lead_dim, b_local, cfg.vision.num_patches, cfg.vision.d_patch),
            jnp.float32, mesh, (*lead, P.BATCH, "enc_seq", None), rules)
    if cfg.encoder is not None:
        if shape.kind == "decode":
            out["enc_out"] = sds(
                (*lead_dim, b_local, cfg.encoder.source_len, cfg.d_model),
                jnp.dtype(cfg.dtype), mesh,
                (*lead, P.BATCH, "enc_seq", P.EMBED), rules)
            if cfg.cross_kv_cache:
                kv = (*lead_dim, cfg.num_layers, b_local,
                      cfg.encoder.source_len, cfg.num_kv_heads,
                      cfg.head_dim)
                kv_ax = (*lead, P.LAYERS, P.BATCH, "enc_seq", P.KV_HEADS,
                         P.HEAD_DIM)
                out["cross_kv"] = {
                    "k": sds(kv, jnp.dtype(cfg.dtype), mesh, kv_ax, rules),
                    "v": sds(kv, jnp.dtype(cfg.dtype), mesh, kv_ax, rules),
                }
        else:
            out["frames"] = sds(
                (*lead_dim, b_local, cfg.encoder.source_len,
                 cfg.encoder.d_source),
                jnp.float32, mesh, (*lead, P.BATCH, "enc_seq", None), rules)
    return out


# ---------------------------------------------------------------------------
# Dry-run cases
# ---------------------------------------------------------------------------
@dataclass
class Case:
    name: str
    fn: Callable
    args: tuple
    static: dict


def _dipaco_partition_for(cfg: ModelConfig, W: int):
    """Default 4x4 = 16-path partition used by the dry-run."""
    reps = cfg.pattern_repeats
    if reps >= 2:
        dcfg = DiPaCoConfig(levels=(4, 4))
    else:
        dcfg = DiPaCoConfig(levels=(16,))
    part = make_partition(dcfg, reps)
    worker_paths = np.arange(W) % part.num_paths
    mixl, mixs = mixing_matrices(part, worker_paths)
    return part, mixl, mixs


def build_train_case(cfg: ModelConfig, shape: InputShape, mesh) -> Case:
    W = mesh_num_workers(mesh)
    rules = rules_for(cfg)
    pshapes, axes = S.worker_param_shapes(cfg, W)
    pshard = tree_sds(pshapes, axes, mesh, prepend=(P.WORKER,), rules=rules)
    opt_shapes = S.adamw_state_shapes(pshapes)
    opt_shard = {
        "m": tree_sds(opt_shapes["m"], axes, mesh, prepend=(P.WORKER,),
                      rules=rules),
        "v": tree_sds(opt_shapes["v"], axes, mesh, prepend=(P.WORKER,),
                      rules=rules),
        "count": sds((W,), jnp.int32, mesh, (P.WORKER,), rules),
    }
    batch = batch_specs(cfg, shape, mesh, stacked=True, rules=rules)
    lr = sds((), jnp.float32, mesh, ())
    fn = S.make_inner_train_step(cfg)
    return Case(name=f"{cfg.name}:{shape.name}:train", fn=fn,
                args=(pshard, opt_shard, batch, lr),
                static={"workers": W})


def build_outer_case(cfg: ModelConfig, shape: InputShape, mesh) -> Case:
    W = mesh_num_workers(mesh)
    pshapes, axes = S.worker_param_shapes(cfg, W)
    pshard = tree_sds(pshapes, axes, mesh, prepend=(P.WORKER,))
    mom = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), pshapes)
    mom_shard = {"momentum": tree_sds(mom, axes, mesh, prepend=(P.WORKER,))}
    part, mixl, mixs = _dipaco_partition_for(cfg, W)
    mixl_s = sds(mixl.shape, jnp.float32, mesh, (None, None, None))
    mixs_s = sds(mixs.shape, jnp.float32, mesh, (None, None))
    fn = S.make_outer_step(cfg, axes)
    return Case(name=f"{cfg.name}:{shape.name}:outer", fn=fn,
                args=(pshard, pshard, mom_shard, mixl_s, mixs_s),
                static={"workers": W, "paths": part.num_paths})


def build_prefill_case(cfg: ModelConfig, shape: InputShape, mesh) -> Case:
    W = mesh_num_workers(mesh)
    rules = rules_for(cfg)
    pshapes, axes = S.worker_param_shapes(cfg, W)
    pshard = tree_sds(pshapes, axes, mesh, prepend=(P.WORKER,), rules=rules)
    batch = batch_specs(cfg, shape, mesh, stacked=True, rules=rules)
    fn = S.make_prefill_step(cfg)
    return Case(name=f"{cfg.name}:{shape.name}:prefill", fn=fn,
                args=(pshard, batch), static={"workers": W})


def build_decode_case(cfg: ModelConfig, shape: InputShape, mesh) -> Case:
    stacked = shape.global_batch > 1
    W = mesh_num_workers(mesh) if stacked else 1
    cache_len = shape.window or shape.seq_len
    b_local = shape.global_batch // W if stacked else shape.global_batch
    if stacked:
        pshapes, axes = S.worker_param_shapes(cfg, W)
        pshard = tree_sds(pshapes, axes, mesh, prepend=(P.WORKER,))
        cshapes, caxes = decode_cache_shapes(cfg, b_local, cache_len)
        cshapes = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((W, *s.shape), s.dtype), cshapes)
        cshard = tree_sds(cshapes, caxes, mesh, prepend=(P.WORKER,))
    else:
        pshapes, axes = S.model_param_shapes(cfg)
        pshard = tree_sds(pshapes, axes, mesh)
        cshapes, caxes = decode_cache_shapes(cfg, b_local, cache_len)
        cshard = tree_sds(cshapes, caxes, mesh)
    batch = batch_specs(cfg, shape, mesh, stacked=stacked)
    idx = sds((), jnp.int32, mesh, ())
    fn = S.make_decode_step(cfg, window=shape.window, stacked=stacked)
    if stacked:
        args = (pshard, batch, cshard, idx)
    else:
        args = (pshard, batch, cshard, idx)
    return Case(name=f"{cfg.name}:{shape.name}:decode", fn=fn,
                args=args, static={"workers": W, "cache_len": cache_len})


def build_case(cfg: ModelConfig, shape: InputShape, mesh) -> Case:
    if shape.kind == "train":
        return build_train_case(cfg, shape, mesh)
    if shape.kind == "prefill":
        return build_prefill_case(cfg, shape, mesh)
    return build_decode_case(cfg, shape, mesh)


# ---------------------------------------------------------------------------
# Model-FLOPs reference (6*N_active*D) for the roofline table
# ---------------------------------------------------------------------------
def active_param_count(cfg: ModelConfig) -> tuple:
    """(total, active) parameter counts from eval_shape (no alloc)."""
    shapes, axes = S.model_param_shapes(cfg)
    flat = P.tree_axes_flatten(shapes, axes)
    total = 0
    active = 0.0
    for path, leaf, ax in flat:
        n = math.prod(leaf.shape)
        total += n
        if cfg.moe is not None and P.EXPERT in ax and "router" not in path[-1]:
            frac = cfg.moe.top_k / cfg.moe.num_experts
            active += n * frac
        else:
            active += n
    return total, int(active)


def model_flops(cfg: ModelConfig, shape: InputShape) -> float:
    total, active = active_param_count(cfg)
    # exclude embedding table from the 6ND rule-of-thumb
    embed = cfg.vocab_size * cfg.d_model
    n = max(active - embed, 1)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch  # one token per request
    return 2.0 * n * tokens
