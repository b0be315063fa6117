"""Serving drivers' shared loop: builds ``ContinuousBatchingEngine``
through its public API (``EngineOptions``, ``warmup``, ``submit``,
``step``), offers the cell's traffic, and keeps per-request records.

Clock: every tick is stepped with ``now`` = its tick number, and the
host clock is read before and after ``step`` returns.  ``step`` ends in
``np.asarray`` of the tick's logits, so the clock after it is in step
with the device.  A request's admission, first token and finish are
recorded as tick numbers by the engine and turned into times through
that map: the first token and the finish at the end of their tick, the
admission at its start.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

import reference
import traffic as traffic_mod
from harness import annotate


@dataclasses.dataclass
class Record:
    rid: int
    path: int
    plen: int
    max_new: int
    due: float
    submitted: float = float("nan")
    admit_tick: int = -1
    first_tick: int = -1
    finish_tick: int = -1
    emitted: int = 0
    tokens: np.ndarray | None = None


@dataclasses.dataclass
class ServeRun:
    """What the serving metric readers read."""
    model: dict                 # the configuration's model mapping
    records: list               # Record per request offered
    tick_start: dict            # tick -> host seconds from window open
    tick_end: dict
    decoded: dict               # tick -> rows that decoded in it
    serve: dict                 # the configuration's serve knobs
    close: float                # host seconds from window open: last tick end
    seconds: float              # --seconds (arrival window, open loop)
    open_loop: bool

    def due_in_window(self) -> list:
        return [r for r in self.records if r.due < self.seconds
                and not np.isnan(r.submitted)]


def program_config(cfgfile: dict, over: dict):
    """The program's ``ModelConfig`` for a configuration file, with the
    file's sizes and serve knobs; every size is checked to be the one
    that runs."""
    from repro.configs import get_config
    model = {**cfgfile["model"], **over.get("model", {})}
    serve = {**cfgfile["serve"], **over.get("serve", {})}
    cfg = get_config(cfgfile["program_config"])
    fields = {f.name for f in dataclasses.fields(cfg)}
    knobs = {k: serve[k] for k in ("attn_impl", "kv_quant") if k in serve}
    cfg = cfg.replace(**{k: v for k, v in model.items() if k in fields},
                      **knobs, **over.get("program", {}))
    for k, v in model.items():
        if k in fields and getattr(cfg, k) != v:
            raise SystemExit(f"program runs {k}={getattr(cfg, k)}, the "
                             f"configuration states {v}")
    return cfg, {**model, **knobs}, serve


def check_layout(weights, cfg) -> None:
    """The seeded weights have the program's tree, shapes and types."""
    import jax
    from repro.models import api
    want = jax.eval_shape(lambda k: api.init_model(k, cfg)[0],
                          jax.random.key(0))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), weights[0])
    exp = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got != exp:
        raise SystemExit(f"weight layout differs from the program's:\n"
                         f"{got}\nvs\n{exp}")


def buckets_for(mix: dict, cache_len: int) -> tuple:
    """Prefill lengths for the mix: the powers of two from the one that
    holds its shortest prompt up to its longest, and the longest itself
    (the engine adds ``cache_len``)."""
    lo, hi = mix["prompt"]["lo"], min(mix["prompt"]["hi"], cache_len)
    out, b = [hi], 16
    while b < hi:
        if 2 * b > lo:
            out.append(b)
        b *= 2
    return tuple(sorted(out))


def warm_admissions(engine, router, vocab: int, slots: int, bucket: int,
                    path: int = 0) -> None:
    """Admission groups of every size 1..slots on one path, each request
    done at its first token: compiles the slot writes of every group
    size before the window (``warmup`` compiles the prefills)."""
    from repro.serving import Request
    rng = np.random.default_rng(12345)
    rid = -1
    for k in range(1, slots + 1):
        for _ in range(k):
            prompt = rng.integers(0, vocab, size=bucket, dtype=np.int32)
            router.add(prompt, path)
            engine.submit(Request(rid=rid, prompt=prompt, max_new=1))
            rid -= 1
        engine.step(now=float(engine.ticks + 1))
    assert engine.idle


@dataclasses.dataclass
class Served:
    engine: object
    weights: list
    model: dict
    knobs: dict
    mix: dict
    router: traffic_mod.Router


def build(ctx) -> Served:
    """Seeded weights, the engine and its warm-up: the set-up."""
    import jax
    from repro.serving import ContinuousBatchingEngine, EngineOptions
    cfg, model, knobs = program_config(ctx.config, ctx.over)
    mix = {**ctx.traffic, **ctx.over.get("traffic", {})}
    ctx.mark("imports")
    with annotate("bench.setup.weights"):
        weights = reference.make_weights(model, ctx.seed, knobs["paths"])
        jax.block_until_ready(weights)
        check_layout(weights, cfg)
    ctx.mark("weights")
    router = traffic_mod.Router([])
    buckets = buckets_for(mix, knobs["cache_len"])
    opts = EngineOptions(route_fn=router, cache_len=knobs["cache_len"],
                         slots_per_path=knobs["slots_per_path"],
                         reroute_every=0, prefill_buckets=buckets,
                         prefix_cache=0, preemption=False)
    with annotate("bench.setup.engine"):
        engine = ContinuousBatchingEngine(cfg, weights, options=opts)
        ctx.mark("engine")
        engine.warmup()
        ctx.mark("warmup")
        warm_admissions(engine, router, model["vocab_size"],
                        knobs["slots_per_path"], buckets[0])
        ctx.mark("warm_admissions")
    return Served(engine, weights, model, knobs, mix, router)


def requests(ctx, sv: Served, open_loop: bool, rate=None) -> list:
    """The run's traffic: ``rate`` x ``--seconds`` Poisson arrivals, or
    the cell's backlog."""
    if open_loop:
        rate = ctx.cell["rate_per_s"] if rate is None else rate
        n = max(1, int(round(rate * ctx.seconds)))
    else:
        n = int(ctx.cell["backlog"])
    reqs = traffic_mod.generate(sv.mix, n=n, seconds=ctx.seconds,
                                seed=ctx.seed, vocab=sv.model["vocab_size"],
                                num_paths=sv.knobs["paths"],
                                open_loop=open_loop)
    for r in reqs:
        sv.router.add(r.prompt, r.path)
    return reqs


def offer(ctx, sv: Served, reqs: list, open_loop: bool) -> ServeRun:
    """The measured window: submit each request when it is due, step the
    engine, and record every tick and request."""
    from repro.serving import Request
    engine, seconds, n = sv.engine, ctx.seconds, len(reqs)
    recs = {r.rid: Record(rid=r.rid, path=r.path, plen=len(r.prompt),
                          max_new=r.max_new, due=r.due) for r in reqs}
    run = ServeRun(model=sv.model, records=list(recs.values()),
                   tick_start={}, tick_end={}, decoded={}, serve=sv.knobs,
                   close=0.0, seconds=seconds, open_loop=open_loop)

    def absorb(fins, tick):
        for f in fins:
            rec = recs[f.rid]
            rec.admit_tick = int(f.admitted_at)
            rec.first_tick = int(f.first_token_at)
            rec.finish_tick = tick
            rec.tokens = f.tokens
            rec.emitted = len(f.tokens) - rec.plen

    drain_s = ctx.cell.get("drain_s", 0.0)
    ctx.window_opens()
    t0 = time.perf_counter()
    i = 0
    with ctx.traced_window():
        while True:
            now = time.perf_counter() - t0
            if open_loop and now > seconds + drain_s:
                break
            if not open_loop and now >= seconds:
                break
            if i < n and reqs[i].due <= now:
                with annotate("bench.submit"):
                    while i < n and reqs[i].due <= now:
                        r = reqs[i]
                        engine.submit(Request(rid=r.rid, prompt=r.prompt,
                                              max_new=r.max_new))
                        recs[r.rid].submitted = time.perf_counter() - t0
                        i += 1
            if engine.idle:
                if i >= n:
                    break
                with annotate("bench.wait_arrival"):
                    time.sleep(max(0.0, reqs[i].due - now))
                continue
            tick = engine.ticks + 1
            decoding = len(engine.in_flight)
            ts = time.perf_counter() - t0
            with annotate("bench.step"):
                fins = engine.step(now=float(tick))
            te = time.perf_counter() - t0
            run.tick_start[tick], run.tick_end[tick] = ts, te
            run.decoded[tick] = decoding
            absorb(fins, tick)
        run.close = time.perf_counter() - t0
    # requests still in flight (offline: the window closes on them)
    for st in engine.in_flight.values():
        rec = recs[st.req.rid]
        rec.admit_tick = int(st.admitted_at)
        rec.first_tick = int(st.first_token_at)
        rec.emitted = st.emitted
    ctx.window_closed()
    return run


def serve(ctx, open_loop: bool):
    sv = build(ctx)
    reqs = requests(ctx, sv, open_loop)
    run = offer(ctx, sv, reqs, open_loop)
    weights, model = sv.weights, sv.model
    sv = None                     # frees the engine before the reference
    gc.collect()
    return run, weights, model


def check_sample(ctx, run: ServeRun, weights, model: dict) -> dict:
    """Compare a sample of the finished requests, drawn from the seed and
    holding the longest, with the float32 reference."""
    done = [r for r in run.records if r.finish_tick >= 0]
    short = sum(1 for r in done if r.emitted != r.max_new)
    k = min(len(done), int(ctx.cell["check_requests"]))
    rng = traffic_mod.rng_for(ctx.seed, 2)
    longest = max(done, key=lambda r: (r.emitted, r.rid)) if done else None
    rest = [r for r in done if r is not longest]
    pick = ([longest] if longest else []) + [
        rest[j] for j in rng.permutation(len(rest))[:max(0, k - 1)]]
    seqs = [(r.path, r.tokens, r.plen) for r in pick]
    out = reference.served_gaps(weights, model, seqs,
                                rows=int(ctx.cell["check_rows"]),
                                length=run.serve["cache_len"],
                                control=ctx.control) if seqs else \
        {"tokens": 0, "served_gap_max": float("inf")}
    out["requests_checked"] = len(pick)
    out["short_outputs"] = short
    return out


@dataclasses.dataclass
class Outcome:
    view: ServeRun
    check: dict          # name -> (value, limit)
    attempted: int
    failed: int
    info: dict


def outcome(ctx, open_loop: bool) -> Outcome:
    run, weights, model = serve(ctx, open_loop)
    got = check_sample(ctx, run, weights, model)
    limits = ctx.cell["limits"]
    check = {"served_gap_max": (got["served_gap_max"],
                                limits["served_gap_max"]),
             "short_outputs": (got["short_outputs"], 0)}
    if open_loop:
        offered = run.due_in_window()
        failed = sum(1 for r in offered if r.finish_tick < 0)
    else:
        offered = [r for r in run.records if r.admit_tick >= 0]
        failed = 0
    info = {k: v for k, v in got.items() if k not in check}
    info.update(ticks=len(run.tick_end), window_close_s=run.close,
                finished=sum(1 for r in run.records if r.finish_tick >= 0))
    return Outcome(view=run, check=check, attempted=len(offered),
                   failed=failed, info=info)
