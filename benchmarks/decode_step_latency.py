"""Per-tick decode latency / decode tokens-per-second microbenchmark.

Fills every slot of a multi-island continuous-batching engine, then
times steady-state decode ticks across the four decode configurations:

* attention impl: dense jnp cache branch vs the Pallas flash-decode
  kernel (interpret mode on this CPU container — kernel-dispatch
  structure is exercised; real-TPU timing is the deploy target);
* island dispatch: per-island Python loop (one jit call per path) vs
  the stacked-island tick (params stacked along a path axis, one
  vmapped dispatch advances every island).

Writes results into ``BENCH_decode.json`` so future PRs have a decode
perf trajectory to regress against.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.models import api
from repro.serving import ContinuousBatchingEngine, EngineOptions, Request

from .common import record_bench


def _fill_and_time(cfg, paths, *, stacked, slots, cache_len, prompt_len,
                   warm_ticks, ticks):
    eng = ContinuousBatchingEngine(cfg, paths, options=EngineOptions(
        cache_len=cache_len, slots_per_path=slots, stacked=stacked))
    num_paths = len(paths)
    counter = iter(range(10_000))
    eng._route_prompt = lambda prompt: next(counter) % num_paths
    rng = np.random.default_rng(0)
    total = num_paths * slots
    max_new = warm_ticks + ticks + 8   # keep every row in flight
    for rid in range(total):
        eng.submit(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab_size, prompt_len).astype(
                np.int32),
            max_new=max_new))
    for _ in range(warm_ticks):        # admission tick + decode compile
        eng.step()
    assert len(eng.in_flight) == total
    jax.block_until_ready(eng.device_state())
    t0 = time.perf_counter()
    for _ in range(ticks):
        eng.step()
    jax.block_until_ready(eng.device_state())
    dt = time.perf_counter() - t0
    assert len(eng.in_flight) == total, "rows retired mid-measurement"
    return dt / ticks, total


def run(quick: bool = True):
    cfg = get_smoke_config("dipaco-150m").replace(route_prefix_len=8)
    # many small islands, few slots each (§2.2/§2.6 serving regime)
    num_paths, slots = (8, 4) if quick else (8, 8)
    ticks = 8 if quick else 20
    cache_len, prompt_len = 64, 16
    key = jax.random.PRNGKey(0)
    paths = [api.init_model(jax.random.fold_in(key, p), cfg)[0]
             for p in range(num_paths)]

    rows = []
    tick_s = {}
    for impl in ("chunked", "pallas"):
        for stacked in (False, True):
            per_tick, nrows = _fill_and_time(
                # the smoke preset on the CPU: kernels interpreted
                cfg.replace(attn_impl=impl, pallas_interpret=True), paths,
                stacked=stacked,
                slots=slots, cache_len=cache_len, prompt_len=prompt_len,
                warm_ticks=3, ticks=ticks)
            label = ("jnp" if impl == "chunked" else "pallas",
                     "stacked" if stacked else "looped")
            tick_s[label] = per_tick
            rows.append({
                "name": f"decode_{label[0]}_{label[1]}",
                "us_per_call": per_tick * 1e6,
                "tick_ms": per_tick * 1e3,
                "decode_tok_per_s": nrows / per_tick,
                "rows": nrows, "islands": num_paths,
            })
    rows.append({
        "name": "decode_stacked_speedup",
        "us_per_call": tick_s[("jnp", "stacked")] * 1e6,
        "jnp_loop_over_stacked":
            tick_s[("jnp", "looped")] / tick_s[("jnp", "stacked")],
        "pallas_loop_over_stacked":
            tick_s[("pallas", "looped")] / tick_s[("pallas", "stacked")],
    })
    record_bench("decode_step_latency", rows)
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
