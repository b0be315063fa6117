#!/usr/bin/env python3
"""Find an open-loop serving cell's knee: one engine, one window per
offered rate, each run to its end.

    python bench/sweep.py --workload serve-150m-chat --seed 7 \\
        --seconds 30 --rates 4,6,8,10,12

Prints one JSON line per rate: the tails of TTFT, TPOT and queue wait,
and how long the window's requests took to drain.  The knee is the
highest rate whose queue wait stays bounded (no backlog growing through
the window); a cell's ``rate_per_s`` is set once from it, by hand.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402
import serving  # noqa: E402
from stats import percentile  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == args.workload)
    over = harness.load_json(BENCH / "rehearsal.json") \
        if args.rehearse else {}
    harness.device_info(entry["chips"], args.rehearse)
    harness.enable_compile_cache()
    ctx = harness.Context(
        name=args.workload,
        cell=harness.load_json(BENCH / "workloads" / f"{args.workload}.json"),
        config=harness.load_json(BENCH / "configs" / f"{entry['config']}.json"),
        traffic=harness.load_json(BENCH / "traffic" / f"{entry['traffic']}.json"),
        seed=args.seed, seconds=args.seconds, trace=False, control=False,
        over=over, started=STARTED, compiles=harness.CompileCounter())
    sv = serving.build(ctx)
    print(json.dumps({"setup_s": time.perf_counter() - STARTED}), flush=True)
    for rate in [float(r) for r in args.rates.split(",")]:
        reqs = serving.requests(ctx, sv, True, rate)
        run = serving.offer(ctx, sv, reqs, True)
        due = run.due_in_window()
        ttft = [run.tick_end[r.first_tick] - r.due if r.first_tick >= 0
                else float("inf") for r in due]
        tpot = [1e3 * (run.tick_end[r.finish_tick] - run.tick_end[r.first_tick])
                / (r.emitted - 1) for r in due
                if r.finish_tick >= 0 and r.emitted > 1]
        wait = [run.tick_start[r.admit_tick] - r.due if r.admit_tick >= 0
                else float("inf") for r in due]
        ticks = sorted(run.tick_end)
        tick_ms = [1e3 * (run.tick_end[t] - run.tick_start[t]) for t in ticks]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(due),
            "finished": sum(r.finish_tick >= 0 for r in due),
            "ttft_p50_s": percentile(ttft, 50), "ttft_p95_s": percentile(ttft, 95),
            "tpot_p50_ms": percentile(tpot, 50), "tpot_p95_ms": percentile(tpot, 95),
            "queue_wait_p95_s": percentile(wait, 95),
            "queue_wait_max_s": max(wait) if wait else None,
            "tick_p50_ms": percentile(tick_ms, 50), "ticks": len(ticks),
            "close_s": run.close,
            "compiles_in_window": ctx.compiles.count}), flush=True)
        ctx.compiles.count = 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
