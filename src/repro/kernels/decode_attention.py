"""Fused flash-decode attention (TPU Pallas): single-token GQA over the
ring KV cache.

DiPaCo serves each input on one cheap path (§2.2/§2.6), so per-token
decode on a path *is* the serving cost model.  This kernel replaces the
dense ``(B, H, S, T)`` masked-einsum cache branch of
``models/layers.py::apply_attention`` for the ``s == 1`` decode case:

* **split-K online softmax** — the innermost grid axis walks key blocks
  of the cache-length axis sequentially, carrying (m, l, acc) in VMEM
  scratch, so no ``(B, H, T)`` score tensor is ever materialized;
* **in-kernel ring/window masking** — per-row ``cache_index`` arrives
  via scalar prefetch (SMEM) and the absolute position of every ring
  slot is reconstructed inside the kernel, masking unwritten slots,
  causally-future slots and window-expired slots; fully-invalid key
  blocks skip their matmuls entirely (``pl.when``);
* **in-place, layer-indexed read** — the cache is the decode step's
  whole layer-stacked buffer ``(L, N, KH, D, T)``; the layer and a row
  offset arrive by scalar prefetch and the block index map reads the
  right layer's rows, so nothing is sliced or transposed for the kernel;
* **fused int8 dequantization** — with a quantized cache
  (``cfg.kv_quant``) the int8 K/V blocks and their per-(token, head)
  scales are dequantized in VMEM right before the dot, so the quantized
  cache never round-trips through an f32 HBM materialization.

Target: TPU v5e.  VMEM working set per grid step is the row's queries
``(H, D)`` plus one K and one V block ``(KH, D, block_k)`` (int8 or
bf16) plus scratch — 0.5 MiB for 16x64 heads at ``block_k=128``.  Off the
TPU the kernel runs only in interpret mode, which the caller asks for
(see ``ops.decode_attention``).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _block_has_valid(j, ci, *, T: int, block_k: int,
                     window: Optional[int]):
    """Scalar test: does key block ``j`` hold any admissible ring slot?

    The admissible entries form a ring interval of ``n`` slots ending at
    slot ``ci % T`` (the newest write), ``n = min(ci + 1, window, T)``.
    Pure scalar arithmetic, so the skip needs no vector reduction."""
    n = ci + 1
    if window is not None:
        n = jnp.minimum(n, window)
    last = ci % T
    first = last - n + 1                      # may be negative: wraps
    lo = j * block_k
    hi = lo + block_k - 1
    plain = jnp.logical_and(lo <= last, hi >= first)
    wrapped = jnp.logical_or(lo <= last, hi >= first + T)
    return jnp.logical_or(n >= T, jnp.where(first >= 0, plain, wrapped))


def _decode_kernel(ci_ref, _at_ref, q_ref, k_ref, v_ref, *rest,
                   quantized: bool, T: int, block_k: int, nk: int,
                   kh: int, g: int, d: int, window: Optional[int],
                   scale: float):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    bi = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ci = ci_ref[bi]

    @pl.when(_block_has_valid(j, ci, T=T, block_k=block_k, window=window))
    def _compute():
        # ring-slot validity, reconstructed from this row's decode
        # position: the token at position ci sits in slot ci % T; slots
        # "after" it in ring order hold entries T positions older (or
        # nothing yet).  Slots run along lanes, as a (1, block_k) row.
        slot = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        idx_last = ci % T
        abs_pos = jnp.where(slot <= idx_last, ci - idx_last + slot,
                            ci - idx_last - T + slot)
        valid = jnp.logical_and(abs_pos >= 0, abs_pos <= ci)
        if window is not None:
            valid = jnp.logical_and(valid, abs_pos > ci - window)
        # one KV head at a time: its (D, bk) slab of keys and values,
        # tokens along lanes, against its g query rows (the heads of one
        # KV group are contiguous)
        for h in range(kh):
            rows = slice(h * g, (h + 1) * g)
            q = q_ref[0, rows, :].astype(jnp.float32)        # (g, D)
            k = k_ref[0, h].astype(jnp.float32)              # (D, bk)
            v = v_ref[0, h].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if quantized:
                # per-(slot, head) scales factor out of the dots: one
                # (1, bk) row each, on the scores and the probabilities
                s = s * ks_ref[0, h:h + 1, :]
            s = jnp.where(valid, s * scale, NEG_INF)
            m_prev = m_scr[rows, :]                          # (g, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[rows, :] = l_scr[rows, :] * alpha + p.sum(axis=-1,
                                                             keepdims=True)
            if quantized:
                p = p * vs_ref[0, h:h + 1, :]
            acc_scr[rows, :] = acc_scr[rows, :] * alpha + jax.lax.dot_general(
                p, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[rows, :] = m_new

    @pl.when(j == nk - 1)
    def _write():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _pick_block_k(T: int, block_k: int) -> int:
    """``block_k`` when it tiles the cache, else the whole cache length
    (a block equal to the array dimension is always a legal TPU tile)."""
    return block_k if T % block_k == 0 else T


def flash_decode(q, k_cache, v_cache, cache_index, layer=0, *,
                 row_offset=0, window: Optional[int] = None, k_scale=None,
                 v_scale=None, block_k: int = 128, interpret: bool = False):
    """Single-token decode attention over one layer of a layer-stacked
    ring KV cache, read in place.

    q: (B, H, D) — the current token's queries (RoPE already applied).
    k_cache, v_cache: (L, N, KH, D, T) ring caches of L layers and N
    rows, tokens minor, f32/bf16 — or int8 with ``k_scale``/``v_scale``
    (L, N, KH, T) per-(token, head) scales.
    cache_index: (B,) int32 — each row's decode position (the position
    the current token was just written at; masking admits ring entries
    with absolute position in ``[max(0, ci-window+1), ci]``).
    layer, row_offset: int32 scalars, static or traced: query row ``b``
    attends cache row ``row_offset + b`` of layer ``layer``.

    Both scalars reach the kernel by scalar prefetch and pick each grid
    step's block in the ``index_map``, ``(layer, row_offset + b, 0, 0,
    j)``: the kernel DMAs its ``(KH, D, block_k)`` blocks straight out of
    the whole stacked buffer, so a decode step that carries the cache
    through its layer loop slices, transposes and copies nothing.  The
    tokens-minor order is the one a TPU picks for a ``(..., D, T)``
    buffer with D < 128; blocks have minor dimensions the tiling rule
    accepts for D a multiple of 8, one DMA for all heads.

    Returns (B, H, D) in q's dtype.
    """
    b, h, d = q.shape
    kh, T = k_cache.shape[2], k_cache.shape[4]
    assert h % kh == 0, (h, kh)
    quantized = k_scale is not None
    assert quantized == (v_scale is not None)
    bk = _pick_block_k(T, block_k)
    nk = T // bk
    ci = jnp.asarray(cache_index, jnp.int32).reshape(b)
    at = jnp.stack([jnp.asarray(layer, jnp.int32),
                    jnp.asarray(row_offset, jnp.int32)])
    kernel = functools.partial(
        _decode_kernel, quantized=quantized, T=T, block_k=bk, nk=nk,
        kh=kh, g=h // kh, d=d, window=window, scale=1.0 / math.sqrt(d))
    q_spec = pl.BlockSpec((1, h, d), lambda bi, j, ci, at: (bi, 0, 0))
    kv_spec = pl.BlockSpec((pl.squeezed, 1, kh, d, bk),
                           lambda bi, j, ci, at: (at[0], at[1] + bi, 0, 0, j))
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [q, k_cache, v_cache]
    if quantized:
        sc_spec = pl.BlockSpec((pl.squeezed, 1, kh, bk),
                               lambda bi, j, ci, at: (at[0], at[1] + bi, 0, j))
        in_specs += [sc_spec, sc_spec]
        args += [x.astype(jnp.float32) for x in (k_scale, v_scale)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nk),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
        name="flash_decode",
    )(ci, at, *args)
