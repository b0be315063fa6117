"""Latent decode attention (TPU Pallas): one token per row, absorbed
multi-head latent attention (DeepSeek-V2/V3) over the ring of latents.

A row's cache holds, per token, one ``C = r + dr`` wide entry: the
normalised key/value latent (``r``) and the rotary key shared by all
heads (``dr``).  In the absorbed form the queries arrive already folded
into that space, ``q = [q_nope . W_uk, q_pe]`` (H, C), so a score is one
dot with the whole entry and the output is the probabilities' sum of
the latents' first ``r`` rows; ``W_uv`` maps it back out afterwards.

* **in place, layer-indexed** — the cache is the decode step's whole
  layer-stacked arena ``(L, N, C, T)``, tokens minor; the layer and a
  row offset arrive by scalar prefetch and pick each block in the index
  map, so nothing is sliced or copied for the kernel;
* **one read per token** — keys and values are the same entries, so
  each ``(C, block_k)`` block is read once for both dots;
* **split-K online softmax** over the cache-length axis, with the ring
  masked in the kernel from each row's position; blocks past a row's
  newest entry (before the ring wraps) are neither computed nor read:
  their index map repeats the last valid block, which the pipeline does
  not fetch again.

Off the TPU the kernel runs only in interpret mode, which the caller
asks for (see ``ops.mla_decode``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import NEG_INF, _block_has_valid, _pick_block_k


def _mla_kernel(ci_ref, _at_ref, q_ref, lat_ref, o_ref, m_scr, l_scr,
                acc_scr, *, T: int, block_k: int, nk: int, r: int,
                scale: float):
    bi = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ci = ci_ref[bi]

    @pl.when(_block_has_valid(j, ci, T=T, block_k=block_k, window=None))
    def _compute():
        slot = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        idx_last = ci % T
        abs_pos = jnp.where(slot <= idx_last, ci - idx_last + slot,
                            ci - idx_last - T + slot)
        valid = jnp.logical_and(abs_pos >= 0, abs_pos <= ci)
        q = q_ref[0].astype(jnp.float32)                     # (H, C)
        lat = lat_ref[0].astype(jnp.float32)                 # (C, bk)
        s = jax.lax.dot_general(q, lat, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(valid, s * scale, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, lat[:r], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _write():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
            o_ref.dtype)


def mla_decode(q, lat_cache, cache_index, layer=0, *, kv_rank: int,
               scale: float, row_offset=0, block_k: int = 512,
               interpret: bool = False):
    """Absorbed latent attention of one token per row over one layer of
    a layer-stacked ring of latents, read in place.

    q: (B, H, C) float32 — ``[q_nope . W_uk, q_pe]`` (RoPE applied).
    lat_cache: (L, N, C, T), tokens minor; entry ``[:kv_rank]`` is the
    latent, the rest the shared rotary key.
    cache_index: (B,) int32 — each row's position, whose entry was just
    written; the ring admits positions ``[0, ci]``.
    layer, row_offset: int32 scalars, static or traced: query row ``b``
    reads cache row ``row_offset + b`` of layer ``layer``.

    Returns (B, H, kv_rank) float32: the softmax-weighted latents.
    """
    b, h, c = q.shape
    T = lat_cache.shape[-1]
    assert lat_cache.shape[2] == c, (lat_cache.shape, q.shape)
    bk = _pick_block_k(T, block_k)
    nk = T // bk
    ci = jnp.asarray(cache_index, jnp.int32).reshape(b)
    at = jnp.stack([jnp.asarray(layer, jnp.int32),
                    jnp.asarray(row_offset, jnp.int32)])

    def lat_block(bi, j, ci, at):
        # before the ring wraps, blocks past the newest entry repeat the
        # last valid one: the pipeline skips a fetch of the same block
        last = jnp.where(ci[bi] >= T, nk - 1, (ci[bi] % T) // bk)
        return (at[0], at[1] + bi, 0, jnp.minimum(j, last))

    kernel = functools.partial(_mla_kernel, T=T, block_k=bk, nk=nk,
                               r=kv_rank, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, h, c), lambda bi, j, ci, at: (bi, 0, 0)),
            pl.BlockSpec((pl.squeezed, 1, c, bk), lat_block),
        ],
        out_specs=pl.BlockSpec((1, h, kv_rank),
                               lambda bi, j, ci, at: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, kv_rank), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, kv_rank), jnp.float32),
        interpret=interpret,
        name="mla_decode",
    )(ci, at, q, lat_cache)
