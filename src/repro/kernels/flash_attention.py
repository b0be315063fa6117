"""Fused flash attention (TPU Pallas): causal + sliding-window + GQA.

Online-softmax accumulation across key blocks; the innermost grid
dimension walks key blocks sequentially so VMEM scratch (m, l, acc)
carries across iterations (canonical TPU flash pattern).  Fully-masked
key blocks (future causal blocks / expired window blocks) are skipped
with pl.when — the kernel analogue of the XLA-level ``causal_skip``
optimization in models/layers.py.

The kernel works head-major, on ``(B, H, S, D)`` arrays, so every block
has ``(block, D)`` as its two minor dimensions: the TPU tiling rule
wants those divisible by (8, 128) or equal to the array's, and a block
that picked one head out of a ``(B, S, H, D)`` array would break it.
Per-row statistics (the log-sum-exp the backward needs) are
``(B, H, S, 1)`` columns for the same reason.

Target: TPU v5e MXU — block_q x block_k tiles default 128x128 (MXU
aligned); VMEM working set per step ~ (2*block_q + 2*block_k) * head_dim
* 4B, well under the 16 MiB budget for head_dim <= 256.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def block_runs(q_start, k_start, *, causal, window, block_q, block_k,
               seq_len=None):
    """Scalar test: does the (q block, k block) pair hold any admissible
    (query, key) pair?  Shared by the forward and both backward kernels."""
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + block_k - 1 > q_start - window)
    if seq_len is not None:          # ragged tail: skip all-padding blocks
        run = jnp.logical_and(run, k_start < seq_len)
    return run


def block_mask(q_start, k_start, *, causal, window, block_q, block_k,
               seq_len=None):
    """(block_q, block_k) admissibility of each (query, key) pair."""
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                              (block_q, block_k), 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                              (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask = jnp.logical_and(mask, kpos <= qpos)
    if window is not None:
        mask = jnp.logical_and(mask, kpos > qpos - window)
    if seq_len is not None:          # padded keys never receive weight
        mask = jnp.logical_and(mask, kpos < seq_len)
    return mask


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, causal, window,
                block_q, block_k, nk, scale, seq_len):
    if len(rest) == 4:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref = None
        m_scr, l_scr, acc_scr = rest
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    geom = dict(causal=causal, window=window, block_q=block_q,
                block_k=block_k, seq_len=seq_len)
    q_start = i * block_q
    k_start = j * block_k

    @pl.when(block_runs(q_start, k_start, **geom))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = block_mask(q_start, k_start, **geom)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                            # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _write():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, 0] = m_scr[...] + jnp.log(l)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def attention_blocks(s: int, block_q: int, block_k: int):
    """Clamp the blocks to the (8-aligned) sequence and return
    ``(block_q, block_k, padded_len)``: the padded length is a common
    multiple of both blocks."""
    bq = min(block_q, _round_up(s, 8))
    bk = min(block_k, _round_up(s, 8))
    return bq, bk, _round_up(s, math.lcm(bq, bk))


def pad_seq(x, sp, axis=2):
    """Right-pad ``axis`` (the sequence axis) of ``x`` to length ``sp``."""
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, sp - x.shape[axis])
    return x if sp == x.shape[axis] else jnp.pad(x, pad)


def flash_attention_bhsd(q, k, v, *, causal=True, window=None,
                         block_q=128, block_k=128, interpret=False,
                         with_lse=False):
    """Head-major flash attention.

    q: (B, H, S, D); k, v: (B, KH, S, D) -> o (B, H, S, D), and with
    ``with_lse`` also the per-row log-sum-exp (B, H, S, 1) in f32.  A
    ragged tail (S not a block multiple) is padded, masked and sliced
    back off.
    """
    b, h, s, d = q.shape
    kh = k.shape[1]
    assert h % kh == 0, (h, kh)
    g = h // kh
    bq, bk, sp = attention_blocks(s, block_q, block_k)
    seq_len = s if sp != s else None
    q, k, v = (pad_seq(x, sp) for x in (q, k, v))
    nq, nk = sp // bq, sp // bk
    kernel = functools.partial(
        _fwd_kernel, causal=causal, window=window, block_q=bq, block_k=bk,
        nk=nk, scale=1.0 / math.sqrt(d), seq_len=seq_len)
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, hi, i, j: (bi, hi, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, d),
                           lambda bi, hi, i, j: (bi, hi // g, j, 0))
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct((b, h, sp, d), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, 1, bq, 1),
                                      lambda bi, hi, i, j: (bi, hi, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((b, h, sp, 1), jnp.float32))
    outs = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    outs = [x[:, :, :s] for x in outs]
    return tuple(outs) if with_lse else outs[0]


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, S, H, D); k, v: (B, S, KH, D) -> (B, S, H, D)."""
    o = flash_attention_bhsd(
        *(jnp.swapaxes(x, 1, 2) for x in (q, k, v)), causal=causal,
        window=window, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return jnp.swapaxes(o, 1, 2)
