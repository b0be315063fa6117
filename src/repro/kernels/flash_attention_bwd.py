"""Flash attention backward (TPU Pallas) + custom_vjp wiring.

Two kernels (the canonical split):
  dkv kernel — grid (B, KH, nk, nq): for each key block, accumulate
               dK/dV over the query blocks that attend to it.
  dq  kernel — grid (B, H, nq, nk): for each query block, accumulate dQ
               over its key blocks.

Both recompute p = softmax(qk) blockwise from the saved (q, k, v, o,
delta=rowsum(do*o), lse) — O(S) memory like the forward.  GQA: dK/dV
accumulate over the g = H/KH query heads of each KV head inside the
kernel body (heads of one KV group are contiguous, so one block of g
heads carries the whole group).  Like the forward, the kernels work
head-major on ``(B, H, S, D)`` arrays with ``(B, H, S, 1)`` row
statistics; the residuals are saved in that layout, so only the
custom_vjp boundary transposes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (NEG_INF, attention_blocks, block_mask,
                              block_runs, flash_attention, flash_attention_bhsd,
                              pad_seq)


def _recompute_p(q, k, lse, q_start, k_start, *, scale, geom):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = block_mask(q_start, k_start, **geom)
    if geom["seq_len"] is not None:  # ragged tail: padded queries are
        qpos = q_start + jax.lax.broadcasted_iota(   # not differentiated
            jnp.int32, mask.shape, 0)
        mask = jnp.logical_and(mask, qpos < geom["seq_len"])
    return jnp.where(mask, jnp.exp(jnp.where(mask, s, NEG_INF) - lse), 0.0)


def _runs(q_start, k_start, geom):
    run = block_runs(q_start, k_start, **geom)
    if geom["seq_len"] is not None:
        run = jnp.logical_and(run, q_start < geom["seq_len"])
    return run


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, delta_ref, lse_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, geom, nq, g, scale):
    j = pl.program_id(2)
    i = pl.program_id(3)

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_start = i * geom["block_q"]
    k_start = j * geom["block_k"]

    @pl.when(_runs(q_start, k_start, geom))
    def _compute():
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        for gi in range(g):   # query heads of this KV head
            q = q_ref[0, gi].astype(jnp.float32)      # (bq, D)
            do = do_ref[0, gi].astype(jnp.float32)
            p = _recompute_p(q, k, lse_ref[0, gi], q_start, k_start,
                             scale=scale, geom=geom)
            dv_scr[...] += jax.lax.dot_general(
                p, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[0, gi]) * scale
            dk_scr[...] += jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _write():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, delta_ref, lse_ref, dq_ref,
               dq_scr, *, geom, nk, scale):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q_start = i * geom["block_q"]
    k_start = j * geom["block_k"]

    @pl.when(_runs(q_start, k_start, geom))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        p = _recompute_p(q, k, lse_ref[0, 0], q_start, k_start,
                         scale=scale, geom=geom)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0]) * scale
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _write():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal, window, block_q,
                        block_k, interpret):
    """Head-major backward: q, o, do (B, H, S, D); k, v (B, KH, S, D);
    lse (B, H, S, 1).  Returns (dq, dk, dv) head-major."""
    b, h, s, d = q.shape
    kh = k.shape[1]
    g = h // kh
    bq, bk, sp = attention_blocks(s, block_q, block_k)
    # ragged tail: pad, mask, slice back.  Padded lse rows are 0 and
    # padded q/do rows are 0, so padded queries contribute exactly
    # nothing to dK/dV; padded keys are masked out of every p.
    seq_len = s if sp != s else None
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)            # (b, h, s, 1)
    q, k, v, do, delta, lse = (pad_seq(x, sp)
                               for x in (q, k, v, do, delta, lse))
    nq, nk = sp // bq, sp // bk
    scale = 1.0 / math.sqrt(d)
    geom = dict(causal=causal, window=window, block_q=bq, block_k=bk,
                seq_len=seq_len)

    # dK/dV: one KV head per step, its g query heads as one block
    qg_spec = pl.BlockSpec((1, g, bq, d), lambda bi, hi, j, i: (bi, hi, i, 0))
    rowg_spec = pl.BlockSpec((1, g, bq, 1),
                             lambda bi, hi, j, i: (bi, hi, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, d), lambda bi, hi, j, i: (bi, hi, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, geom=geom, nq=nq, g=g, scale=scale),
        grid=(b, kh, nk, nq),
        in_specs=[qg_spec, kv_spec, kv_spec, qg_spec, rowg_spec, rowg_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((b, kh, sp, d), k.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32)] * 2,
        interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, do, delta, lse)

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, hi, i, j: (bi, hi, i, 0))
    row_spec = pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, i, j: (bi, hi, i, 0))
    kvq_spec = pl.BlockSpec((1, 1, bk, d),
                            lambda bi, hi, i, j: (bi, hi // g, j, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, geom=geom, nk=nk, scale=scale),
        grid=(b, h, nq, nk),
        in_specs=[q_spec, kvq_spec, kvq_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, do, delta, lse)
    return dq[:, :, :s], dk[:, :, :s], dv[:, :, :s]


# ---------------------------------------------------------------------------
# custom_vjp wrapper — (B, S, H, D) at the boundary, head-major inside
# ---------------------------------------------------------------------------
def _bhsd(x):
    return jnp.swapaxes(x, 1, 2)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_trainable(q, k, v, causal=True, window=None,
                              block_q=128, block_k=128, interpret=False):
    # undifferentiated (inference) calls run the forward kernel alone,
    # without the log-sum-exp output only the backward reads
    return flash_attention(q, k, v, causal=causal, window=window,
                           block_q=block_q, block_k=block_k,
                           interpret=interpret)


def _fa_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    qt, kt, vt = _bhsd(q), _bhsd(k), _bhsd(v)
    o, lse = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret, with_lse=True)
    return _bhsd(o), (qt, kt, vt, o, lse)


def _fa_bwd(causal, window, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    grads = flash_attention_bwd(q, k, v, o, lse, _bhsd(do), causal=causal,
                                window=window, block_q=block_q,
                                block_k=block_k, interpret=interpret)
    return tuple(_bhsd(x) for x in grads)


flash_attention_trainable.defvjp(_fa_fwd, _fa_bwd)
