"""Serving launcher: routes batched requests to path replicas.

    # one-shot baseline over randomly initialized paths
    PYTHONPATH=src python -m repro.launch.serve --arch dipaco-150m \
        --paths 4 --requests 8 --max-new 16 [--reroute-every 8]

    # continuous-batching engine fed by a Poisson trace
    PYTHONPATH=src python -m repro.launch.serve --engine continuous \
        --rate 40

    # the same on a CPU: the reduced preset, Pallas kernels interpreted
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \
        --smoke --engine continuous

    # serve the promoted version of a deployment registry (written by
    # examples/train_and_serve.py or a Publisher), hot-swapping when
    # the serving pointer moves
    PYTHONPATH=src python -m repro.launch.serve --engine continuous \
        --deploy-root /tmp/dipaco_deploy --levels 2x2 \
        --swap-policy drain

    # multi-process serving fleet behind the path-affinity front door
    # (requires --deploy-root: members rendezvous on the registry's
    # SERVING pointer, so one promote hot-swaps the whole fleet)
    PYTHONPATH=src python -m repro.launch.serve --fleet 2 \
        --deploy-root /tmp/dipaco_deploy --levels 2x2
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Any, List, Optional

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, get_smoke_config
from repro.data import SyntheticCorpus
from repro.models import api
from repro.models.config import DiPaCoConfig
from repro.serving import (ContinuousBatchingEngine, EngineOptions,
                           PathServingEngine, poisson_trace,
                           prefix_hash_router)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dipaco-150m")
    ap.add_argument("--smoke", action="store_true",
                    help="the arch's reduced preset (CPU rehearsals); "
                         "Pallas kernels then run in interpret mode")
    ap.add_argument("--attn-impl", choices=["chunked", "pallas"],
                    default="chunked",
                    help="attention: XLA online-softmax, or the Pallas "
                         "flash kernels (flash_decode on every tick)")
    ap.add_argument("--cache-len", type=int, default=0,
                    help="KV cache length per slot (default: prompt-len "
                         "+ max-new)")
    ap.add_argument("--engine", choices=["oneshot", "continuous"],
                    default="oneshot")
    ap.add_argument("--continuous", action="store_true",
                    help="deprecated alias for --engine continuous")
    ap.add_argument("--paths", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--reroute-every", type=int, default=0)
    ap.add_argument("--rate", type=float, default=40.0,
                    help="Poisson arrival rate (req/s), continuous engine")
    ap.add_argument("--slots", type=int, default=8,
                    help="cache slots per path island, continuous engine")
    ap.add_argument("--deploy-root", default=None,
                    help="serve from the DeploymentRegistry at this root "
                         "(the promoted serving version) instead of "
                         "randomly initialized paths")
    ap.add_argument("--levels", default="2x2",
                    help="partition levels of the deployment (--deploy-"
                         "root), e.g. 2x2; must match the training run")
    ap.add_argument("--seed", type=int, default=0,
                    help="base-init seed of the deployment (--deploy-root);"
                         " must match the training run")
    ap.add_argument("--swap-policy", choices=["drain", "live"],
                    default="drain",
                    help="hot-swap pinning policy when the registry's "
                         "serving version moves mid-trace")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="serve through a fleet of N engines behind the "
                         "path-affinity front door (requires "
                         "--deploy-root)")
    ap.add_argument("--fleet-backend", choices=["process", "inproc"],
                    default="process",
                    help="fleet members as OS processes (default) or "
                         "in this process (debugging)")
    return ap


@dataclass
class Setup:
    """What every engine kind serves from."""
    cfg: Any
    corpus: SyntheticCorpus
    registry: Any                  # DeploymentRegistry, or None
    paths: Optional[list]          # random paths; None with a registry
    opts: EngineOptions


@dataclass
class ContinuousRun:
    """What one ``--engine continuous`` run served."""
    engine: ContinuousBatchingEngine
    trace: list
    finished: List[Any]
    compile_s: float               # engine.warmup(): every jit entry
    serve_s: float                 # the trace, on the wall clock


def build_config(args):
    """The served model: the arch's ``config()``, or with ``--smoke``
    its reduced preset with the Pallas kernels interpreted."""
    if args.smoke:
        cfg = get_smoke_config(args.arch).replace(pallas_interpret=True)
    else:
        cfg = get_config(args.arch)
    return cfg.replace(route_prefix_len=8, attn_impl=args.attn_impl)


def setup(args) -> Setup:
    cfg = build_config(args)
    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size, num_domains=4,
                             seq_len=args.prompt_len, seed=0)
    registry = paths = None
    if args.deploy_root:
        from repro.deploy import DeploymentRegistry
        levels = tuple(int(x) for x in args.levels.split("x"))
        registry = DeploymentRegistry(
            cfg, DiPaCoConfig(levels=levels), args.deploy_root,
            key=jax.random.PRNGKey(args.seed))
        num_paths = registry.num_paths
        print(f"[serve] registry {args.deploy_root}: versions "
              f"{registry.versions}, serving v{registry.serving_version}")
    else:
        key = jax.random.PRNGKey(args.seed)
        num_paths = args.paths
        paths = [api.init_model(jax.random.fold_in(key, p), cfg)[0]
                 for p in range(num_paths)]
    # one validated options bag configures either engine; every prompt
    # has --prompt-len tokens, so that is the one prefill bucket besides
    # cache_len (which the engine always adds)
    opts = EngineOptions(
        registry=registry, swap_policy=args.swap_policy,
        cache_len=args.cache_len or args.prompt_len + args.max_new,
        slots_per_path=args.slots, reroute_every=args.reroute_every,
        route_fn=prefix_hash_router(num_paths),
        prefill_buckets=(args.prompt_len,))
    return Setup(cfg, corpus, registry, paths, opts)


def trace_of(args, st: Setup):
    return poisson_trace(args.requests, rate=args.rate,
                         prompt_lens=[args.prompt_len],
                         max_new=args.max_new,
                         vocab_size=st.cfg.vocab_size, seed=0,
                         corpus=st.corpus)


def run_continuous(args, st: Setup) -> ContinuousRun:
    """Warm every jit entry off the clock, then serve the Poisson trace
    on the wall clock."""
    engine = ContinuousBatchingEngine(st.cfg, st.paths, options=st.opts)
    t0 = time.perf_counter()
    engine.warmup()
    compile_s = time.perf_counter() - t0
    trace = trace_of(args, st)
    t0 = time.perf_counter()
    fins = engine.serve_trace(trace, realtime=True)
    return ContinuousRun(engine=engine, trace=trace, finished=fins,
                         compile_s=compile_s,
                         serve_s=time.perf_counter() - t0)


def main(argv=None) -> None:
    enable_compile_cache()
    ap = build_parser()
    args = ap.parse_args(argv)
    engine_kind = "continuous" if args.continuous else args.engine
    if args.fleet and not args.deploy_root:
        ap.error("--fleet requires --deploy-root (fleet members "
                 "rendezvous on the registry's SERVING pointer)")
    st = setup(args)
    cfg, registry, opts = st.cfg, st.registry, st.opts
    if args.fleet:
        from repro.serving import ServingFleet
        trace = trace_of(args, st)
        t0 = time.time()
        with ServingFleet(cfg, size=args.fleet, options=opts,
                          backend=args.fleet_backend,
                          seed=args.seed) as fleet:
            fins = fleet.serve_trace(trace)
            versions = fleet.versions()
            stats = dict(fleet.stats)
        dt = time.time() - t0
        toks = args.requests * args.max_new
        lat = sorted(f.latency for f in fins)
        print(f"[serve] fleet of {args.fleet} ({args.fleet_backend}): "
              f"{toks} tokens in {dt:.2f}s ({toks / dt:.1f} tok/s), "
              f"p50 latency {lat[len(lat) // 2] * 1e3:.0f}ms, "
              f"routed={stats['routed']} "
              f"rebalances={stats['rebalances']}")
        print(f"[serve] member versions {versions}")
        print(f"[serve] request->path: "
              f"{[f.path for f in fins]}")
        return
    if engine_kind == "continuous":
        run = run_continuous(args, st)
        engine, fins, dt = run.engine, run.finished, run.serve_s
        toks = args.requests * args.max_new
        print(f"[serve] compiled every jit entry in {run.compile_s:.2f}s")
        lat = sorted(f.latency for f in fins)
        ttft = sorted(f.ttft for f in fins)
        print(f"[serve] {toks} tokens in {dt:.2f}s ({toks / dt:.1f} tok/s) "
              f"over {engine.ticks} ticks, "
              f"p50 latency {lat[len(lat) // 2] * 1e3:.0f}ms, "
              f"p50 ttft {ttft[len(ttft) // 2] * 1e3:.0f}ms, "
              f"switches={sum(f.switches for f in fins)}")
        if registry is not None:
            print(f"[serve] served version(s) "
                  f"{sorted(set(f.version for f in fins))}, "
                  f"hot swaps={engine.swaps}")
        print(f"[serve] request->path: "
              f"{[f.path for f in sorted(fins, key=lambda f: f.rid)]}")
        return
    engine = PathServingEngine(cfg, st.paths, options=EngineOptions(
        registry=registry, cache_len=opts.cache_len))
    t0 = time.time()
    res = engine.generate(st.corpus.sample_documents(args.requests),
                          max_new=args.max_new,
                          reroute_every=args.reroute_every)
    dt = time.time() - t0
    toks = args.requests * args.max_new
    print(f"[serve] {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s), switches={res.switches}")
    if registry is not None:
        print(f"[serve] serving version v{engine.version}")
    print(f"[serve] request->path: {res.paths.tolist()}")


if __name__ == "__main__":
    main()
