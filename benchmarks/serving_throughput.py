"""Serving throughput: continuous batching vs the one-shot batch loop.

A mixed-length Poisson arrival trace is served three times on the wall
clock:

* one-shot baseline: whenever requests have arrived, take them as one
  batch (grouped by prompt length — the old engine needs rectangular
  batches), run ``generate`` to completion, only then admit the next
  batch; prefill is the old token-by-token replay.
* continuous batching, PR-1 configuration: per-island decode loop (one
  jit dispatch per path per tick) and batch-1 exact-length prefill.
* continuous batching, current configuration: stacked-island decode
  (one vmapped dispatch advances every island) + length-bucketed
  batched prefill.

Reports requests/s, p50/p95/p99 request latency and (for the
continuous engines) p50/p95 time-to-first-token for each, the speedups,
verifies greedy outputs are token-identical across all engines, and
records the rows into ``BENCH_decode.json``.  Offered load exceeds the
one-shot capacity so req/s measures service capacity, not the Poisson
arrival rate.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.models import api
from repro.serving import (ContinuousBatchingEngine, EngineOptions,
                           PathServingEngine, Request, ServingFleet,
                           poisson_trace, prefix_hash_router)

from .common import record_bench


def _percentiles(lat):
    lat = np.asarray(lat)
    return (float(np.percentile(lat, 50)), float(np.percentile(lat, 95)),
            float(np.percentile(lat, 99)))


def _serve_oneshot(engine, trace, max_new):
    """Blocking batch loop: admit everything that has arrived, generate,
    repeat.  Returns (tokens_by_rid, latency_by_rid, makespan)."""
    trace = sorted(trace, key=lambda r: r.arrival)
    i, n = 0, len(trace)
    tokens, latency = {}, {}
    t0 = time.perf_counter()
    while i < n:
        now = time.perf_counter() - t0
        batch = []
        while i < n and trace[i].arrival <= now:
            batch.append(trace[i])
            i += 1
        if not batch:
            time.sleep(min(1e-3, trace[i].arrival - now))
            continue
        by_len = {}
        for r in batch:
            by_len.setdefault(len(r.prompt), []).append(r)
        for _, group in by_len.items():
            res = engine.generate(np.stack([r.prompt for r in group]),
                                  max_new=max_new)
            # drain async device work before reading the clock, so
            # req/s and latencies aren't skewed by pending dispatches
            jax.block_until_ready(engine.device_state())
            done = time.perf_counter() - t0
            for j, r in enumerate(group):
                tokens[r.rid] = res.tokens[j]
                latency[r.rid] = done - r.arrival
    jax.block_until_ready(engine.device_state())
    return tokens, latency, time.perf_counter() - t0


def run(quick: bool = True):
    # offered load must exceed every engine's service capacity (the
    # continuous engines sustain ~100 req/s at this scale, the one-shot
    # ~10) so requests/s measures capacity, not the Poisson arrival rate
    n, rate = (48, 300.0) if quick else (128, 300.0)
    max_new = 12 if quick else 24
    prompt_lens = (16, 24, 32)
    cache_len = max(prompt_lens) + max_new
    # float32 smoke config: greedy argmax must be numerically stable so
    # the token-identity check is meaningful
    cfg = get_smoke_config("dipaco-150m").replace(route_prefix_len=8)
    key = jax.random.PRNGKey(0)
    # many small islands with few slots each — the regime the paper
    # serves (§2.2/§2.6) and where per-island dispatch overhead bites
    num_paths, slots = (8, 4) if quick else (8, 8)
    paths = [api.init_model(jax.random.fold_in(key, p), cfg)[0]
             for p in range(num_paths)]

    # deterministic prompt-hash routing spreads the trace over all
    # islands identically for every engine (keeps the token-identity
    # check meaningful without training a router)
    hash_route = prefix_hash_router(num_paths)

    def make_trace():
        return poisson_trace(n, rate=rate, prompt_lens=prompt_lens,
                             max_new=max_new, vocab_size=cfg.vocab_size,
                             seed=7)

    oneshot = PathServingEngine(cfg, paths, options=EngineOptions(
        cache_len=cache_len, route_fn=hash_route))
    cont_pr1 = ContinuousBatchingEngine(cfg, paths, options=EngineOptions(
        cache_len=cache_len, slots_per_path=slots, stacked=False,
        bucketed_prefill=False, route_fn=hash_route))
    # buckets matched to the trace's length distribution (how a
    # deployment would choose them); compile cache stays bounded by
    # the bucket set either way
    cont = ContinuousBatchingEngine(cfg, paths, options=EngineOptions(
        cache_len=cache_len, slots_per_path=slots,
        prefill_buckets=prompt_lens, route_fn=hash_route))

    # warmup: compile every (batch, length) prefill/decode variant off
    # the clock
    warm = [Request(rid=10_000 + i, prompt=np.full(ln, 1, np.int32),
                    max_new=2, arrival=0.0)
            for i, ln in enumerate(prompt_lens)]
    for eng in (cont_pr1, cont):
        eng.warmup()   # bounded (bucket, batch) prefill + decode set
        eng.serve_trace([Request(r.rid, r.prompt, r.max_new, 0.0)
                         for r in warm])
        eng.scheduler.stats = type(eng.scheduler.stats)()  # drop warmup
    for ln in prompt_lens:
        oneshot.generate(np.full((1, ln), 1, np.int32), max_new=2)

    def _serve_cont_once(eng):
        # per-trial stats so the recorded backpressure_ticks describe
        # one trace, not the sum over trials
        eng.scheduler.stats = type(eng.scheduler.stats)()
        t0 = time.perf_counter()
        fins = eng.serve_trace(make_trace(), realtime=True)
        jax.block_until_ready(eng.device_state())
        span_wall = time.perf_counter() - t0
        span = max(max(f.finished_at for f in fins), span_wall)
        return ({f.rid: f.tokens for f in fins},
                {f.rid: f.latency for f in fins}, span,
                {f.rid: f.ttft for f in fins})

    # interleaved trials + median span: wall-clock noise on a shared
    # CPU swings whole seconds, so pair the engines in time and take a
    # robust summary rather than a single (or best-of) measurement
    span_1s = []
    for _ in range(3):
        tok_1, lat_1, s1 = _serve_oneshot(oneshot, make_trace(), max_new)
        span_1s.append(s1)
    span_1 = float(np.median(span_1s))
    trials = 5
    res_p, res_c = [], []
    for _ in range(trials):
        res_p.append(_serve_cont_once(cont_pr1))
        res_c.append(_serve_cont_once(cont))
    tok_p, lat_p, _, ttft_p = res_p[-1]
    tok_c, lat_c, _, ttft_c = res_c[-1]
    span_p = float(np.median([r[2] for r in res_p]))
    span_c = float(np.median([r[2] for r in res_c]))

    match = all((tok_c[r] == tok_1[r]).all()
                and (tok_p[r] == tok_1[r]).all() for r in tok_1)
    if not match:
        raise RuntimeError(
            "continuous-batching greedy outputs diverged from the "
            "one-shot engine")
    rps_1, rps_p, rps_c = n / span_1, n / span_p, n / span_c
    p50_1, p95_1, p99_1 = _percentiles(list(lat_1.values()))
    p50_p, p95_p, p99_p = _percentiles(list(lat_p.values()))
    p50_c, p95_c, p99_c = _percentiles(list(lat_c.values()))
    # time-to-first-token (prefill + queueing): the latency users feel
    # on streaming responses; the one-shot engine has no per-request
    # first-token timestamp (the whole batch blocks to completion)
    t50_p, t95_p, _ = _percentiles(list(ttft_p.values()))
    t50_c, t95_c, _ = _percentiles(list(ttft_c.values()))
    rows = [
        {"name": "serving_oneshot", "us_per_call": span_1 / n * 1e6,
         "req_per_s": rps_1, "p50_s": p50_1, "p95_s": p95_1,
         "p99_s": p99_1, "n": n},
        {"name": "serving_continuous_pr1", "us_per_call": span_p / n * 1e6,
         "req_per_s": rps_p, "p50_s": p50_p, "p95_s": p95_p,
         "p99_s": p99_p, "ttft_p50_s": t50_p, "ttft_p95_s": t95_p,
         "n": n, "stacked": 0, "bucketed_prefill": 0,
         "backpressure_ticks":
             cont_pr1.scheduler.stats.backpressure_ticks},
        {"name": "serving_continuous", "us_per_call": span_c / n * 1e6,
         "req_per_s": rps_c, "p50_s": p50_c, "p95_s": p95_c,
         "p99_s": p99_c, "ttft_p50_s": t50_c, "ttft_p95_s": t95_c,
         "n": n, "stacked": int(cont.stacked),
         "bucketed_prefill": int(cont.bucketed),
         "backpressure_ticks":
             cont.scheduler.stats.backpressure_ticks},
        {"name": "serving_speedup", "us_per_call": 0.0,
         "req_per_s_ratio": rps_c / rps_1,
         "stacked_bucketed_over_pr1": rps_c / rps_p,
         "tokens_identical": int(match)},
    ]
    record_bench("serving_throughput", rows)
    return rows


# ---------------------------------------------------------------------------
# Serving fleet (multi-process path-affinity front door)
# ---------------------------------------------------------------------------

def _register_v2(reg, cfg, dcfg, base, db):
    """Mint a second registry version from slightly perturbed modules
    (what one outer phase would publish), so the hot-swap check has a
    real version transition to move the fleet across."""
    from repro.core.module_store import ModuleStore
    from repro.core.partition import make_partition
    _, axes = api.init_model(jax.random.PRNGKey(0), cfg)
    bumped = jax.tree_util.tree_map(lambda x: x * (1.0 + 1e-2), base)
    store = ModuleStore(bumped, axes,
                        make_partition(dcfg, cfg.pattern_repeats))
    rows = {}
    for mid in reg.module_ids:
        tree = store.shared if mid == (-1, -1) \
            else store.module_params(*mid)
        rows[mid] = db.write({"params": tree}, path_id=0, phase=1,
                             step=1, kind="module", level=mid[0],
                             expert=mid[1])
    return reg.register(rows, note="fleet bench v2")


def run_fleet(quick: bool = True):
    """Serving-fleet scenario: N engine *processes* behind the
    path-affinity front door vs one engine with the same per-path slot
    budget, serving the same priority-mixed Poisson trace.

    Reports req/s for both, p99 latency and p50/p95 TTFT per priority
    class, verifies the fleet's greedy tokens are identical to the
    single engine's (fp32 smoke config — preemption and prefix caching
    are identity-preserving by construction), and hot-swaps the whole
    fleet with one ``registry.promote``.  Speedup gate is adaptive: on
    a multi-core host the fleet must beat the single engine by >= 1.05x
    req/s; this CI container pins everything to one core, where N
    processes time-slice a single CPU and the honest bound is a noise
    floor (>= 0.3x, the PR-6 mesh-speedup precedent).  The raw ratio is
    recorded either way so multi-core runs regress on the real number.
    """
    import os
    import tempfile

    from repro.deploy import DeploymentRegistry
    from repro.infra import CheckpointDB
    from repro.models.config import DiPaCoConfig
    from repro.serving import (PRIO_HIGH, PRIO_PREEMPTIBLE, PRIO_STANDARD,
                               EngineOptions)

    n, rate = (32, 120.0) if quick else (96, 200.0)
    max_new = 8 if quick else 16
    prompt_lens = (16, 24)
    cache_len = max(prompt_lens) + max_new
    size = 2 if quick else 4
    cfg = get_smoke_config("dipaco-150m").replace(route_prefix_len=8)
    dcfg = DiPaCoConfig(levels=(2, 2))          # 4 path islands
    base, _ = api.init_model(jax.random.PRNGKey(0), cfg)
    hash_route = prefix_hash_router(4)

    def make_trace():
        t = poisson_trace(n, rate=rate, prompt_lens=prompt_lens,
                          max_new=max_new, vocab_size=cfg.vocab_size,
                          seed=13,
                          priorities=((PRIO_HIGH, PRIO_STANDARD,
                                       PRIO_PREEMPTIBLE),
                                      (0.25, 0.5, 0.25)))
        for r in t:   # pre-route: identical assignment for both engines
            r.path = hash_route(r.prompt)
        return t

    with tempfile.TemporaryDirectory() as root:
        # children rebuild this registry from (cfg, dcfg, root, seed=0),
        # so base_params must be the seed-0 init for payload identity
        reg = DeploymentRegistry(cfg, dcfg, os.path.join(root, "deploy"),
                                 key=jax.random.PRNGKey(0),
                                 base_params=base)
        m1 = reg.register(note="fleet bench v1")
        reg.promote(m1.version)
        opts = EngineOptions(registry=reg, cache_len=cache_len,
                             slots_per_path=2,
                             prefill_buckets=prompt_lens, prefix_cache=64)

        single = ContinuousBatchingEngine(cfg, options=opts)
        single.warmup()
        single.serve_trace([Request(rid=10_000 + i,
                                    prompt=np.full(ln, 1, np.int32),
                                    max_new=2, arrival=0.0)
                            for i, ln in enumerate(prompt_lens)])
        single.scheduler.stats = type(single.scheduler.stats)()
        # best-of-2 spans: both serves are post-warmup, min is the
        # standard scheduler-noise reducer on a shared CI host
        span_1s = []
        for _ in range(2):
            t0 = time.perf_counter()
            fins_1 = single.serve_trace(make_trace(), realtime=True)
            jax.block_until_ready(single.device_state())
            span_1s.append(max(time.perf_counter() - t0,
                               max(f.finished_at for f in fins_1)))
        span_1 = min(span_1s)

        from repro.serving import ServingFleet
        with ServingFleet(cfg, size=size, options=opts,
                          backend="process", seed=0,
                          warmup=True) as fleet:
            span_fs = []
            for _ in range(2):
                t0 = time.perf_counter()
                fins_f = fleet.serve_trace(make_trace())
                span_fs.append(max(time.perf_counter() - t0,
                                   max(f.finished_at for f in fins_f)))
            span_f = min(span_fs)
            # one promote hot-swaps every member (the cross-process
            # SERVING pointer poll inside each child's engine tick)
            db = CheckpointDB(os.path.join(root, "db"))
            m2 = _register_v2(reg, cfg, dcfg, base, db)
            t_swap = time.perf_counter()
            reg.promote(m2.version)
            fleet.wait_version(m2.version, timeout=300.0)
            swap_s = time.perf_counter() - t_swap
            routed = fleet.stats["routed"]
            rebalances = fleet.stats["rebalances"]

    if len(fins_f) != n or len(fins_1) != n:
        raise RuntimeError(f"fleet returned {len(fins_f)}/{n}, "
                           f"single {len(fins_1)}/{n} requests")
    tok_1 = {f.rid: f.tokens for f in fins_1}
    match = all(np.array_equal(f.tokens, tok_1[f.rid]) for f in fins_f)
    if not match:
        raise RuntimeError("fleet greedy outputs diverged from the "
                           "single-engine baseline")
    rps_1, rps_f = n / span_1, n / span_f
    ratio = rps_f / rps_1
    cores = os.cpu_count() or 1
    floor = 1.05 if cores > size else 0.3
    if ratio < floor:
        raise RuntimeError(
            f"fleet speedup {ratio:.2f}x below the {floor}x floor "
            f"({cores} cores, {size} members)")

    rows = [
        {"name": "fleet_single_baseline", "us_per_call": span_1 / n * 1e6,
         "req_per_s": rps_1, "n": n},
        {"name": "fleet_process", "us_per_call": span_f / n * 1e6,
         "req_per_s": rps_f, "members": size, "routed": routed,
         "rebalances": rebalances, "n": n},
        {"name": "fleet_speedup", "us_per_call": 0.0,
         "req_per_s_ratio": ratio, "gate_floor": floor,
         "tokens_identical": int(match), "hot_swap_s": swap_s,
         "swap_version": m2.version},
    ]
    prio_names = {PRIO_HIGH: "high", PRIO_STANDARD: "standard",
                  PRIO_PREEMPTIBLE: "preemptible"}
    by_prio = {}
    for f in fins_f:
        by_prio.setdefault(f.priority, []).append(f)
    for c in sorted(by_prio):
        fl = by_prio[c]
        lat = [f.latency for f in fl]
        tt = [f.ttft for f in fl]
        rows.append({
            "name": f"fleet_prio_{prio_names[c]}",
            "us_per_call": float(np.mean(lat)) * 1e6,
            "p99_s": _percentiles(lat)[2],
            "ttft_p50_s": _percentiles(tt)[0],
            "ttft_p95_s": _percentiles(tt)[1],
            "n": len(fl)})
    record_bench("serving_fleet", rows)
    return rows


if __name__ == "__main__":
    import sys
    scenario = run_fleet if "--fleet" in sys.argv else run
    for r in scenario():
        print(r)
