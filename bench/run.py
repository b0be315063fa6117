#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its driver,
rate and limits are in ``bench/workloads/<cell>.json``, its model in
``bench/configs/<config>.json`` and its traffic mix in
``bench/traffic/<traffic>.json``.  Every metric is a reader in
``bench/metrics/<metric>.py``.  With ``--trace 0`` the run reports the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read from a profiler trace of the window.  The last line of standard
output is one JSON object; the numbers that decide ``correct`` are its
last key, ``check``, and the last lines of standard error.

Without a TPU the run exits with code 3 and prints no result.
``--rehearse`` runs the cell on the CPU at the tiny sizes of
``bench/rehearsal.json`` instead (tests only: its numbers are not
device numbers).  ``--control`` also reads the float8 control's gap.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness  # noqa: E402


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes (tests only)")
    ap.add_argument("--control", action="store_true",
                    help="also read the float8 control's gap")
    return ap.parse_args(argv)


def run_cell(args, over=None, started=STARTED) -> dict:
    """One run of one cell: the result object (also printed by main)."""
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    entry = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cell = harness.load_json(BENCH / "workloads" / f"{args.workload}.json")
    config = harness.load_json(BENCH / "configs" / f"{entry['config']}.json")
    mix = harness.load_json(BENCH / "traffic" / f"{entry['traffic']}.json")
    if over is None:
        over = harness.load_json(BENCH / "rehearsal.json") \
            if args.rehearse else {}
    platform, kind, count = harness.device_info(entry["chips"],
                                                args.rehearse)
    jax_ready = time.perf_counter() - started
    peaks = harness.load_json(BENCH / "peaks.json")
    if kind not in peaks and not args.rehearse:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    harness.enable_compile_cache()
    ctx = harness.Context(
        name=args.workload, cell=cell, config=config, traffic=mix,
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        control=args.control, over=over, started=started,
        compiles=harness.CompileCounter())
    ctx.peaks = peaks.get(kind, next(iter(peaks.values())))
    ctx.setup_marks["devices"] = jax_ready
    out = harness.load_driver(cell["driver"]).run(ctx)

    check = dict(out.check)
    check["compiles_in_window"] = (ctx.compiles.count, 0)
    correct = all(v <= lim for v, lim in check.values())

    metrics, trace, breakdown = {}, None, None
    if args.trace:
        import trace_reduce
        trace = trace_reduce.load(ctx.trace_file)
        chosen = [m for m in bench["per_layer"] if applies(m, args.workload)]
        breakdown = {"device_ops": trace.top_ops(10),
                     "idle_gaps": trace.idle_gaps(10)}
    else:
        chosen = [m for m in bench["end_to_end"]
                  if applies(m, args.workload)]
    for m in chosen:
        if m["name"] == "setup_s":
            value = ctx.setup_s
        else:
            value = harness.load_reader(m["name"])(out.view, trace, ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": platform, "kind": kind, "count": count,
              "memory_peak_bytes": ctx.memory_peak}
    if trace is not None:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s()
        shutil.rmtree(harness.OUT / args.workload, ignore_errors=True)
    result = {"correct": bool(correct), "attempted": int(out.attempted),
              "failed": int(out.failed), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["info"] = {**out.info, "setup_marks": ctx.setup_marks}
    result["check"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in check.items()}
    return result


def main(argv=None) -> int:
    args = parse(argv)
    result = run_cell(args)
    for k, c in result["check"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
