"""Useful work of a serving run, rebuilt from the requests' records.

A request admitted at tick ``a`` is prefilled in tick ``a`` (its first
token comes from the prefill) and decodes one token in each later tick
until its finish tick, or until the window closed on it.  At tick ``t``
its decode attends to ``plen + t - a`` positions.  Only those rows and
positions count: never empty slots, pad rows or pad positions.
"""
from __future__ import annotations

import flops


def decode_rows(run):
    """``(tick, context)`` of every row decode in the run."""
    last = max(run.tick_end) if run.tick_end else 0
    for r in run.records:
        if r.admit_tick < 0:
            continue
        end = r.finish_tick if r.finish_tick >= 0 else \
            r.admit_tick + max(r.emitted, 1) - 1
        for t in range(r.admit_tick + 1, min(end, last) + 1):
            yield t, r.plen + t - r.admit_tick


def prefills(run):
    """``(tick, prompt length)`` of every admitted request."""
    for r in run.records:
        if r.admit_tick >= 0:
            yield r.admit_tick, r.plen


def model_flops(run) -> float:
    m = run.model
    return (sum(flops.prefill_flops(m, n) for _, n in prefills(run))
            + sum(flops.token_flops(m, c) for _, c in decode_rows(run)))


def tick_seconds(run) -> float:
    return sum(run.tick_end[t] - run.tick_start[t] for t in run.tick_end)


def mfu(run, peaks) -> float | None:
    """Useful model FLOPs over the summed wall time of the ticks, as a
    share of the chip's bf16 peak (%)."""
    wall = tick_seconds(run)
    if wall <= 0:
        return None
    return 100.0 * model_flops(run) / (wall * peaks["bf16_flops_per_s"])
