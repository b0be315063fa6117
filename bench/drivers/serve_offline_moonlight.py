"""Offline batch serving of Moonlight-16B-A3B: a backlog larger than the
window can drain is due at the window's start; the window closes after
``--seconds`` on the requests still queued or in flight.

It runs ``bench/serving.py``'s loop (``program_config``,
``check_layout``, ``buckets_for``, ``warm_admissions``, ``requests``,
``offer``) with its own set-up and check: the weights and the float32
reference are ``bench/reference_moonlight.py``'s.  A rehearsal (``--rehearse``)
runs the program's CPU-size preset with the rehearsal's traffic and
cache, on one path, in float32, and is held to the cell's
``rehearsal_limits``; the chip run to its ``limits``.  Both judge the
widest gap over the served tokens, the reference running at each
position the experts the program ran there, and how far those experts
fall below the reference's own choice (``reference_moonlight.served_gaps``).
"""
import dataclasses
import gc

import reference_moonlight as reference
import serving
import traffic as traffic_mod
from harness import annotate

NESTED = {"mla": ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                  "v_head_dim"),
          "moe": ("num_experts", "top_k", "d_ff_expert", "num_shared",
                  "d_ff_shared", "routed_scaling")}


def program_config(ctx):
    """(cfg, model, serve) as ``serving.program_config``, the nested MLA
    and MoE sizes checked too; a rehearsal takes the program's smoke
    sizes."""
    if not ctx.over:
        cfg, model, serve = serving.program_config(ctx.config, {})
    else:
        from repro.configs import get_smoke_config
        cfg = get_smoke_config(ctx.config["program_config"]).replace(
            attn_impl=ctx.config["serve"]["attn_impl"],
            pallas_interpret=True)
        model = {f.name: getattr(cfg, f.name)
                 for f in dataclasses.fields(cfg)
                 if isinstance(getattr(cfg, f.name), (int, float, str))}
        model.update({k: getattr(cfg.mla, k) for k in NESTED["mla"]})
        model.update({k: getattr(cfg.moe, k) for k in NESTED["moe"]})
        serve = {**ctx.config["serve"], **ctx.over.get("serve", {}),
                 "paths": 1}
        return cfg, model, serve
    for group, keys in NESTED.items():
        for k in keys:
            if getattr(getattr(cfg, group), k) != model[k]:
                raise SystemExit(f"program runs {group}.{k}="
                                 f"{getattr(getattr(cfg, group), k)}, the "
                                 f"configuration states {model[k]}")
    return cfg, model, serve


def build(ctx) -> serving.Served:
    """Seeded weights, the engine and its warm-up: the set-up."""
    import jax
    from repro.serving import ContinuousBatchingEngine, EngineOptions
    cfg, model, knobs = program_config(ctx)
    mix = {**ctx.traffic, **ctx.over.get("traffic", {})}
    ctx.mark("imports")
    with annotate("bench.setup.weights"):
        weights = reference.make_weights(model, ctx.seed, knobs["paths"])
        jax.block_until_ready(weights)
        serving.check_layout(weights, cfg)
    ctx.mark("weights")
    router = traffic_mod.Router([])
    buckets = serving.buckets_for(mix, knobs["cache_len"])
    opts = EngineOptions(route_fn=router, cache_len=knobs["cache_len"],
                         slots_per_path=knobs["slots_per_path"],
                         reroute_every=0, prefill_buckets=buckets,
                         prefix_cache=0, preemption=False)
    with annotate("bench.setup.engine"):
        engine = ContinuousBatchingEngine(cfg, weights, options=opts)
        ctx.mark("engine")
        engine.warmup()
        ctx.mark("warmup")
        serving.warm_admissions(engine, router, model["vocab_size"],
                                knobs["slots_per_path"], buckets[0])
        ctx.mark("warm_admissions")
    return serving.Served(engine, weights, model, knobs, mix, router)


def check_sample(ctx, run, weights, model: dict, experts: dict) -> dict:
    """Compare a sample of the finished requests, drawn from the seed and
    holding the longest, with the float32 reference; ``experts``: rid ->
    the experts each of its positions ran."""
    done = [r for r in run.records if r.finish_tick >= 0]
    short = sum(1 for r in done if r.emitted != r.max_new)
    k = min(len(done), int(ctx.cell["check_requests"]))
    rng = traffic_mod.rng_for(ctx.seed, 2)
    longest = max(done, key=lambda r: (r.emitted, r.rid)) if done else None
    rest = [r for r in done if r is not longest]
    pick = ([longest] if longest else []) + [
        rest[j] for j in rng.permutation(len(rest))[:max(0, k - 1)]]
    seqs = [(r.path, r.tokens, r.plen, experts.get(r.rid)) for r in pick]
    mix = {**ctx.traffic, **ctx.over.get("traffic", {})}
    out = reference.served_gaps(weights, model, seqs,
                                rows=int(ctx.cell["check_rows"]),
                                length=run.serve["cache_len"],
                                most_served=mix["output"]["hi"],
                                control=ctx.control) if seqs else \
        {"tokens": 0, "served_gap_max": float("inf"),
         "served_gap_median": float("inf")}
    out["requests_checked"] = len(pick)
    out["short_outputs"] = short
    return out


def run(ctx):
    sv = build(ctx)
    reqs = serving.requests(ctx, sv, open_loop=False)
    experts = {}
    step = sv.engine.step

    def stepped(now):
        """The engine's step, keeping the experts of what finished."""
        fins = step(now=now)
        experts.update((f.rid, f.experts) for f in fins)
        return fins

    sv.engine.step = stepped
    view = serving.offer(ctx, sv, reqs, open_loop=False)
    weights, model = sv.weights, sv.model
    sv = None                     # frees the engine before the reference
    gc.collect()
    got = check_sample(ctx, view, weights, model, experts)
    limits = ctx.cell["rehearsal_limits" if ctx.over else "limits"]
    check = {name: (got[name], limits[name]) for name in limits}
    check["short_outputs"] = (got["short_outputs"], 0)
    info = {k: v for k, v in got.items() if k not in check}
    info.update(ticks=len(view.tick_end), window_close_s=view.close,
                finished=sum(1 for r in view.records if r.finish_tick >= 0))
    return serving.Outcome(
        view=view, check=check,
        attempted=sum(1 for r in view.records if r.admit_tick >= 0),
        failed=0, info=info)
