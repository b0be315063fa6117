"""Jit'd public wrappers around the Pallas kernels.

The kernels compile for the TPU.  Interpret mode (the correctness path
on a CPU) runs only when the caller passes ``interpret=True``; it is
never inferred from the backend, so a TPU run that lost its chip fails
(Pallas refuses a compiled kernel on the CPU) instead of silently
interpreting.  The model layer picks these up when ``cfg.attn_impl ==
'pallas'``, with ``interpret=cfg.pallas_interpret``.
"""
from __future__ import annotations

import functools

import jax

from .decode_attention import flash_decode as _flash_decode
from .mla_decode import mla_decode as _mla_decode
from .moe_gmm import expert_gemm as _gemm
from .moe_gmm import grouped_matmul as _grouped
from .router_assign import router_assign as _assign
from .ssd_scan import ssd_scan as _ssd


@functools.partial(jax.jit, static_argnames=("window", "block_k",
                                             "interpret"))
def decode_attention(q, k_cache, v_cache, cache_index, layer=0, *,
                     row_offset=0, window=None, k_scale=None, v_scale=None,
                     block_k=128, interpret=False):
    """Flash-decode: single-token GQA attention over one layer of the
    layer-stacked ring KV cache, read in place (split-K online softmax,
    in-kernel ring/window masking, fused int8 dequant).  q: (B, H, D);
    caches (L, N, KH, D, T); cache_index (B,); query row b reads cache
    row ``row_offset + b`` of layer ``layer``."""
    return _flash_decode(q, k_cache, v_cache, cache_index, layer,
                         row_offset=row_offset, window=window,
                         k_scale=k_scale, v_scale=v_scale, block_k=block_k,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("kv_rank", "scale", "block_k",
                                             "interpret"))
def mla_decode(q, lat_cache, cache_index, layer=0, *, kv_rank, scale,
               row_offset=0, block_k=512, interpret=False):
    """Latent decode attention: one token per row, absorbed MLA over one
    layer of the layer-stacked ring of latents ``(L, N, C, T)``, read in
    place.  q: (B, H, C) float32; returns (B, H, kv_rank) float32."""
    return _mla_decode(q, lat_cache, cache_index, layer, kv_rank=kv_rank,
                       scale=scale, row_offset=row_offset, block_k=block_k,
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul(x, w, group_sizes, *, interpret=False):
    """Dropless grouped matmul over rows sorted by group: x (M, K), w
    (G, K, N), group_sizes (G,) -> (M, N)."""
    return _grouped(x, w, group_sizes, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention_trainable(q, k, v, *, causal=True, window=None,
                              block_q=128, block_k=128, interpret=False):
    """Flash attention, q: (B, S, H, D); k, v: (B, S, KH, D).  The one
    entry for inference and training: undifferentiated it runs the
    forward kernel alone; under ``jax.grad`` its custom_vjp saves the
    log-sum-exp and runs the Pallas backward kernels (dq/dkv with
    blockwise p recomputation)."""
    from .flash_attention_bwd import flash_attention_trainable as _fat
    return _fat(q, k, v, causal, window, block_q, block_k, interpret)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def router_assign(z, centroids, *, block_n=256, interpret=False):
    return _assign(z, centroids, block_n=block_n, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a, bmat, cmat, *, chunk=128, interpret=False):
    return _ssd(x, dt, a, bmat, cmat, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def expert_gemm(xe, w, *, block_m=128, block_n=128, block_k=512,
                interpret=False):
    return _gemm(xe, w, block_m=block_m, block_n=block_n, block_k=block_k,
                 interpret=interpret)
