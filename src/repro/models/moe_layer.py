"""Token-level Mixture-of-Experts layer (GShard-style and scatter-based).

Three dispatch implementations (selectable via ``MoEConfig.impl``):

- ``dense``   : GShard capacity dispatch via one-hot einsums, grouped to
                bound memory.  The classic TPU formulation; pays extra
                dispatch/combine matmul FLOPs.
- ``scatter`` : sort-free capacity-bucket scatter + batched expert GEMM +
                gather.  Dispatch becomes memory traffic instead of
                MXU FLOPs (MegaBlocks-style; see kernels/moe_gmm for the
                Pallas ragged version).
- ``dropless``: every (token, choice) pair sorted by expert, grouped
                matmuls over the experts' row groups (Megablox's Pallas
                ``gmm`` with the Pallas kernels, ``jax.lax.ragged_dot``
                otherwise), then unsorted: no capacity, no token dropped.

Two routers (``MoEConfig.router``): ``softmax`` top-k, renormalised,
with a Switch load-balance loss; and DeepSeek-V3's ``sigmoid`` router
(``noaux_tc``): a per-expert bias chooses the experts but is not part of
the gates, the chosen sigmoid scores are normalised and scaled.

Every dispatch also returns its routing, int32 ``(N, k)``: the experts
each token chose (:func:`routing_counts` counts them).

This is the *token-level* MoE used inside assigned MoE architectures —
orthogonal to DiPaCo's document-level path routing (see DESIGN.md §4).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import params as P
from .config import ModelConfig, MoEConfig


def init_moe(key, cfg: ModelConfig):
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.num_experts
    ks = jax.random.split(key, 5)
    s = 1.0 / math.sqrt(d)
    p = {
        "router": jax.random.normal(ks[0], (d, e)) * s,
        "w_gate": jax.random.normal(ks[1], (e, d, f)) * s,
        "w_up": jax.random.normal(ks[2], (e, d, f)) * s,
        "w_down": jax.random.normal(ks[3], (e, f, d)) * (1.0 / math.sqrt(f)),
    }
    a = {
        "router": (P.EMBED, P.EXPERT),
        "w_gate": (P.EXPERT, P.EMBED, P.EXPERT_MLP),
        "w_up": (P.EXPERT, P.EMBED, P.EXPERT_MLP),
        "w_down": (P.EXPERT, P.EXPERT_MLP, P.EMBED),
    }
    if m.router == "sigmoid":
        # e_score_correction_bias: chooses, never weights; trained by the
        # load-balancing update, not by the loss
        p["router_bias"] = jnp.zeros((e,))
        a["router_bias"] = (P.EXPERT,)
    if m.num_shared > 0:
        from .layers import init_mlp
        p["shared"], a["shared"] = init_mlp(
            jax.random.fold_in(key, 7), cfg,
            d_ff=m.d_ff_shared or m.num_shared * m.d_ff_expert)
    return p, a


def _router_topk(p, m: MoEConfig, x):
    """x: (N, d) -> gates (N, k), idx (N, k), aux_loss scalar."""
    logits = jnp.einsum("nd,de->ne", x, p["router"].astype(x.dtype),
                        preferred_element_type=jnp.float32)
    if m.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores + jax.lax.stop_gradient(
            p["router_bias"].astype(jnp.float32))
        _, idx = jax.lax.top_k(choice, m.top_k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
        # float32 gates, as the published code weights the experts
        return gates * m.routed_scaling, idx, jnp.zeros((), jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss
    e = m.num_experts
    frac = jnp.mean(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=(0, 1))
    prob_mean = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * prob_mean) * m.router_aux_weight
    return gates.astype(x.dtype), idx, aux


def _expert_ffn(p, cfg: ModelConfig, xe):
    """xe: (..., E, C, d) batched per-expert FFN."""
    dt = xe.dtype
    if cfg.mlp_type in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else jax.nn.gelu
        h = act(jnp.einsum("...ecd,edf->...ecf", xe, p["w_gate"].astype(dt))) \
            * jnp.einsum("...ecd,edf->...ecf", xe, p["w_up"].astype(dt))
    elif cfg.mlp_type == "relu2":
        h = jnp.square(jax.nn.relu(
            jnp.einsum("...ecd,edf->...ecf", xe, p["w_up"].astype(dt))))
    else:
        h = jax.nn.gelu(jnp.einsum("...ecd,edf->...ecf", xe, p["w_up"].astype(dt)))
    return jnp.einsum("...ecf,efd->...ecd", h, p["w_down"].astype(dt))


def moe_dense_dispatch(p, cfg: ModelConfig, x, group_size: int = 1024):
    """GShard capacity dispatch.  x: (B, S, d) -> (y, aux, experts)."""
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    xf = x.reshape(n, d)
    gates, idx, aux = _router_topk(p, m, xf)
    experts = idx
    g = min(group_size, n)
    ng = -(-n // g)
    pad = ng * g - n
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
        gates = jnp.pad(gates, ((0, pad), (0, 0)))
        idx = jnp.pad(idx, ((0, pad), (0, 0)), constant_values=0)
        # padded tokens get zero gate so they contribute nothing
        gates = gates * (jnp.arange(ng * g)[:, None] < n)
    k = m.top_k
    e = m.num_experts
    cap = max(1, int(g * k * m.capacity_factor / e))
    if g <= 64:
        cap = g  # tiny batches (decode): dropless capacity
    xg = xf.reshape(ng, g, d)
    # flatten (token, choice) -> t for capacity counting within each group
    idx_t = idx.reshape(ng, g * k)
    gates_t = gates.reshape(ng, g * k).astype(jnp.float32)
    onehot_t = jax.nn.one_hot(idx_t, e, dtype=jnp.float32)     # (G,t,E)
    pos_t = jnp.cumsum(onehot_t, axis=1) - onehot_t
    pos_c = jnp.sum(pos_t * onehot_t, axis=-1).astype(jnp.int32)  # (G,t)
    keep = (pos_c < cap).astype(jnp.float32)
    pos_oh = jax.nn.one_hot(pos_c, cap, dtype=jnp.float32) * keep[..., None]
    oh_k = (onehot_t * keep[..., None]).reshape(ng, g, k, e)
    pos_k = pos_oh.reshape(ng, g, k, cap)
    gat_k = gates_t.reshape(ng, g, k)
    # (G,g,E,C) tensors; contract k pairwise to avoid (G,g,k,E,C) transient
    dispatch = jnp.einsum("Ggke,Ggkc->Ggec", oh_k, pos_k).astype(x.dtype)
    combine = jnp.einsum("Ggke,Ggkc->Ggec", oh_k * gat_k[..., None], pos_k)
    xe = jnp.einsum("Ggec,Ggd->Gecd", dispatch, xg)            # (G,E,C,d)
    ye = _expert_ffn(p, cfg, xe)                               # (G,E,C,d)
    y = jnp.einsum("Ggec,Gecd->Ggd", combine.astype(x.dtype), ye)
    y = y.reshape(ng * g, d)[:n].reshape(b, s, d)
    if m.num_shared > 0:
        from .layers import apply_mlp
        y = y + apply_mlp(p["shared"], cfg, x)
    return y, aux, experts


def moe_scatter_dispatch(p, cfg: ModelConfig, x):
    """Capacity-bucket scatter dispatch: memory-traffic dispatch, GEMM-only
    expert compute.  x: (B, S, d) -> (y, aux, experts)."""
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    xf = x.reshape(n, d)
    gates, idx, aux = _router_topk(p, m, xf)
    e = m.num_experts
    cap = max(1, int(n * m.top_k * m.capacity_factor / e))
    flat_e = idx.reshape(-1)                                   # (n*k,)
    token_of = jnp.repeat(jnp.arange(n), m.top_k)
    gate_of = gates.reshape(-1)
    # position of each (token, choice) within its expert bucket
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)        # (n*k, E)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)
    pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = pos < cap
    slot = jnp.where(keep, flat_e * cap + pos, e * cap)        # overflow -> dump row
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].set(xf[token_of])
    xe = buf[:-1].reshape(1, e, cap, d)
    ye = _expert_ffn(p, cfg, xe).reshape(e * cap, d)
    ye = jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)], axis=0)
    contrib = ye[slot] * (gate_of * keep).astype(ye.dtype)[:, None]
    y = jnp.zeros((n, d), x.dtype).at[token_of].add(contrib)
    y = y.reshape(b, s, d)
    if m.num_shared > 0:
        from .layers import apply_mlp
        y = y + apply_mlp(p["shared"], cfg, x)
    return y, aux, idx


def routing_counts(idx, num_experts: int):
    """int32 (2,): experts that at least one (token, choice) pair went
    to, and the most pairs any one expert got."""
    sizes = jnp.bincount(idx.reshape(-1), length=num_experts)
    return jnp.stack([jnp.sum(sizes > 0), jnp.max(sizes)]).astype(jnp.int32)


class LayerStack(NamedTuple):
    """One layer's routed-expert weight given as the whole layer stack
    ``(L, E, ...)`` and the layer index: the grouped matmul reads that
    layer's experts where they lie.  A layer loop that sliced them out
    instead would copy every expert of the layer for the kernel, each
    step."""
    stack: jax.Array
    layer: jax.Array


def _grouped(cfg: ModelConfig, x, w, sizes):
    """Rows of x sorted by expert, group e times w[e] (``w`` an array
    (E, ...) or a :class:`LayerStack`)."""
    if cfg.attn_impl == "pallas":
        from repro.kernels.ops import grouped_matmul
        if isinstance(w, LayerStack):
            # the stack as (L*E) groups, rows only in this layer's
            # groups: the kernel reads no other layer's weights
            n_l, e = w.stack.shape[:2]
            sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((n_l * e,), jnp.int32), sizes.astype(jnp.int32),
                (w.layer * e,))
            w = w.stack.reshape((n_l * e,) + w.stack.shape[2:])
        return grouped_matmul(x, w.astype(x.dtype), sizes,
                              interpret=cfg.pallas_interpret)
    if isinstance(w, LayerStack):
        w = w.stack[w.layer]
    return jax.lax.ragged_dot(x, w.astype(x.dtype), sizes)


def moe_dropless_dispatch(p, cfg: ModelConfig, x):
    """Dropless dispatch.  x: (B, S, d) -> (y, aux, experts).

    The (token, choice) pairs are sorted by expert (a stable sort, so
    each expert's rows keep token order), each expert's rows go through
    its FFN as one group of three grouped matmuls, and the outputs are
    put back in pair order by the inverse permutation (a gather) and
    summed over the choices with their gates, in float32."""
    m = cfg.moe
    b, s, d = x.shape
    n, k, e = b * s, m.top_k, m.num_experts
    xf = x.reshape(n, d)
    gates, idx, aux = _router_topk(p, m, xf)
    flat = idx.reshape(-1)                                     # (n*k,)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=e).astype(jnp.int32)
    xs = xf[order // k]                                        # sorted rows
    dt = x.dtype
    if cfg.mlp_type in ("swiglu", "geglu"):
        act = jax.nn.silu if cfg.mlp_type == "swiglu" else jax.nn.gelu
        h = act(_grouped(cfg, xs, p["w_gate"], sizes)) \
            * _grouped(cfg, xs, p["w_up"], sizes)
    elif cfg.mlp_type == "relu2":
        h = jnp.square(jax.nn.relu(_grouped(cfg, xs, p["w_up"], sizes)))
    else:
        h = jax.nn.gelu(_grouped(cfg, xs, p["w_up"], sizes))
    ys = _grouped(cfg, h.astype(dt), p["w_down"], sizes)
    inv = jnp.argsort(order)
    ys = ys[inv].reshape(n, k, d).astype(jnp.float32)
    y = jnp.einsum("nkd,nk->nd", ys, gates.astype(jnp.float32))
    y = y.astype(dt).reshape(b, s, d)
    if m.num_shared > 0:
        from .layers import apply_mlp
        y = y + apply_mlp(p["shared"], cfg, x)
    return y, aux, idx


def apply_moe(p, cfg: ModelConfig, x):
    """x: (B, S, d) -> (y, aux loss, experts int32 (B*S, k): each
    token's chosen experts)."""
    if cfg.moe.impl == "dropless":
        return moe_dropless_dispatch(p, cfg, x)
    if cfg.moe.impl == "scatter":
        return moe_scatter_dispatch(p, cfg, x)
    return moe_dense_dispatch(p, cfg, x)
