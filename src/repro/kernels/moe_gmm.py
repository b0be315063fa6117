"""Per-expert batched GEMM kernel (TPU Pallas) — the compute core of the
scatter-dispatch MoE path: xe (E, C, d) @ w (E, d, f) -> (E, C, f).

Grid (experts, C tiles, f tiles, d tiles) with an f32 VMEM accumulator;
the d dimension is innermost/sequential.  MXU-aligned default tiles
128x128x512.

:func:`grouped_matmul` is the dropless (ragged) form the ``dropless``
dispatch uses: rows sorted by expert, group offsets instead of a
capacity dimension, on Megablox's kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc_scr, *, nk: int):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0].astype(jnp.float32)      # (bm, bk)
    w = w_ref[0].astype(jnp.float32)      # (bk, bn)
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _write():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def expert_gemm(xe, w, *, block_m: int = 128, block_n: int = 128,
                block_k: int = 512, interpret: bool = False):
    """xe: (E, C, d), w: (E, d, f) -> (E, C, f)."""
    e, c, d = xe.shape
    f = w.shape[-1]
    bm = min(block_m, c)
    bn = min(block_n, f)
    bk = min(block_k, d)
    assert c % bm == 0 and f % bn == 0 and d % bk == 0, (c, f, d, bm, bn, bk)
    grid = (e, c // bm, f // bn, d // bk)
    kernel = functools.partial(_gmm_kernel, nk=d // bk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda ei, i, j, k: (ei, i, k)),
            pl.BlockSpec((1, bk, bn), lambda ei, i, j, k: (ei, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda ei, i, j, k: (ei, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), xe.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xe, w)


def _tile(dim: int) -> int:
    """A block of ``dim``: the whole of it up to 1536, else the largest
    multiple of 128 up to 1024 that divides it."""
    if dim <= 1536:
        return dim
    fits = [t for t in range(128, 1025, 128) if dim % t == 0]
    return fits[-1] if fits else dim


def grouped_matmul(x, w, group_sizes, *, interpret: bool = False):
    """Dropless grouped matmul: x (M, K), rows sorted by group; group g's
    ``group_sizes[g]`` rows times ``w[g]`` (G, K, N) -> (M, N) in x's
    dtype, float32 accumulation.

    The ragged kernel is Megablox's (``jax.experimental.pallas.ops.tpu.
    megablox.gmm``; ``gmm`` in the device trace): it visits each row tile
    once per group that has rows in it, and reads a group's weights only
    where it has rows, so an expert no token chose costs no weight read.
    Row tiles are 32 for a decode's few rows (fewer, smaller visits) and
    256 for a prefill's; weight blocks as :func:`_tile`."""
    from jax.experimental.pallas.ops.tpu.megablox.ops import gmm
    m, k = x.shape
    n = w.shape[-1]
    tm = 32 if m <= 2048 else 256
    pad = -m % tm
    out = gmm(jnp.pad(x, ((0, pad), (0, 0))), w.astype(x.dtype),
              group_sizes.astype(jnp.int32), preferred_element_type=x.dtype,
              tiling=(tm, _tile(k), _tile(n)), interpret=interpret)
    return out[:m]
