"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (scaffold contract).  ``derived``
packs the benchmark-specific result (PPL, ratios, notes) as
``k=v|k=v``.  ``--full`` runs the longer (non-quick) configurations.
"""
from __future__ import annotations

import argparse
import math
import sys
import time

from repro.compile_cache import enable_compile_cache


def _derived(row: dict) -> str:
    skip = {"name", "us_per_call"}
    parts = []
    for k, v in row.items():
        if k in skip:
            continue
        if isinstance(v, float):
            v = f"{v:.6g}"
        parts.append(f"{k}={v}")
    return "|".join(parts)


# fast, CI-friendly subset exercising the kernel layer, the shared
# training harness (common.setup), the serving subsystem, the decode
# hot path, the async training service (async-vs-barrier), the
# deployment plane (publish/canary/hot-swap), the elastic-fleet
# chaos gate (30% mid-phase worker loss must stay within 2% of the
# stable fleet's loss — asserted inside the suite), the multi-process
# serving-fleet gate (token identity vs a single engine + adaptive
# speedup floor + one-promote hot swap — asserted inside the suite)
# and the telemetry overhead gate (tracing-on phase wall <= 1.03x
# tracing-off)
SMOKE_SUITES = ("kernels", "table2", "serving", "decode", "outer_exec",
                "deploy", "fleet", "fleet_serve", "obs")

# suites whose metrics must additionally be non-zero under --smoke (a
# zero decode latency / wall-clock / observed-lag / staleness means the
# measurement broke)
POSITIVE_SUITES = ("decode", "outer_exec", "deploy", "obs")


def _finite(row: dict) -> bool:
    return all(math.isfinite(v) for v in row.values()
               if isinstance(v, (int, float)))


# fields that are legitimately zero (e.g. observed staleness on a run
# where no shard happened to overtake a straggler) — not gated
ZERO_OK_FIELDS = {"max_observed_lag"}


def _positive(row: dict) -> bool:
    return all(v > 0 for k, v in row.items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)
               and k not in ZERO_OK_FIELDS)


# per-suite headline field for the --smoke summary table: the first of
# these present in a suite's rows is reported next to its verdict
_KEY_FIELDS = ("overhead_ratio", "loss_delta_pct", "mean_loss", "ppl",
               "val_ppl", "p99_us", "p50_us", "tokens_per_s",
               "us_per_call")


class _Suite:
    """Adapter for a scenario function living inside another suite
    module (e.g. serving_throughput.run_fleet) so the harness can treat
    it like a module with a ``run``."""

    def __init__(self, fn):
        self.run = fn


def _key_metric(rows) -> str:
    for field in _KEY_FIELDS:
        for r in rows:
            v = r.get(field)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                return f"{r['name']}.{field}={v:.6g}"
    return "-"


def _smoke_summary(results: dict, failures: list) -> None:
    """One table: suite, headline metric, gate verdict, plus the trace
    files the suites produced (what CI uploads for Perfetto)."""
    print("\nsuite        key metric                               gate")
    traces = set()
    for name, rows in results.items():
        if rows is None:
            print(f"{name:<12} {'(suite raised)':<40} FAIL")
            continue
        bad = any(f.startswith(f"{name}/") or f.startswith(f"{name}:")
                  for f in failures)
        print(f"{name:<12} {_key_metric(rows):<40} "
              f"{'FAIL' if bad else 'PASS'}")
        traces.update(r["trace"] for r in rows if r.get("trace"))
    for t in sorted(traces):
        print(f"trace: {t}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark module names")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: fast suite subset; exit non-zero on "
                         "any failure or non-finite metric")
    args = ap.parse_args()
    quick = not args.full
    enable_compile_cache()

    from . import (decode_step_latency, deploy_latency, elastic_fleet,
                   fig8_convergence, fig9_path_scaling, fig11_alternating,
                   kernels_micro, obs_overhead, outer_exec_scaling,
                   roofline, serving_throughput, sync_vs_diloco,
                   table1_variants, table2_flatmoe_overfit,
                   table3_eval_routing, table5_sharding)
    suites = {
        "table1": table1_variants,
        "table2": table2_flatmoe_overfit,
        "table3": table3_eval_routing,
        "table5": table5_sharding,
        "fig8": fig8_convergence,
        "fig9": fig9_path_scaling,
        "fig11": fig11_alternating,
        "sync_vs_diloco": sync_vs_diloco,
        "outer_exec": outer_exec_scaling,
        "fleet": elastic_fleet,
        "kernels": kernels_micro,
        "roofline": roofline,
        "serving": serving_throughput,
        "fleet_serve": _Suite(serving_throughput.run_fleet),
        "decode": decode_step_latency,
        "deploy": deploy_latency,
        "obs": obs_overhead,
    }
    if args.smoke:
        suites = {k: suites[k] for k in SMOKE_SUITES}
    if args.only:
        names = args.only.split(",")
        unknown = [n for n in names if n not in suites]
        if unknown:
            ap.error(f"unknown suite(s) {unknown}; "
                     f"known: {sorted(suites)}")
        suites = {k: v for k, v in suites.items() if k in names}

    failures = []
    results = {}
    print("name,us_per_call,derived")
    for name, mod in suites.items():
        t0 = time.time()
        try:
            rows = mod.run(quick=quick)
        except Exception as e:  # noqa: BLE001
            print(f"{name}_FAILED,0,error={type(e).__name__}: {e}")
            failures.append(f"{name}: {type(e).__name__}: {e}")
            results[name] = None
            continue
        results[name] = rows
        for r in rows:
            if args.smoke and not _finite(r):
                failures.append(f"{name}/{r['name']}: non-finite metric")
            if (args.smoke and name in POSITIVE_SUITES
                    and not _positive(r)):
                failures.append(f"{name}/{r['name']}: zero metric")
            print(f"{r['name']},{r.get('us_per_call', 0.0):.1f},"
                  f"{_derived(r)}")
        print(f"# {name} finished in {time.time() - t0:.1f}s",
              file=sys.stderr)
    if args.smoke:
        _smoke_summary(results, failures)
    if args.smoke and failures:
        for f in failures:
            print(f"SMOKE FAILURE: {f}", file=sys.stderr)
        sys.exit(1)
    raised = [n for n, rows in results.items() if rows is None]
    if raised:
        print(f"suite(s) raised: {', '.join(raised)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
