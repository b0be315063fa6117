"""Sampling: device idle time inside the engine's copies of logits to
the host (``serve.fetch``), per tick (ms)."""
import program_spans


def read(run, trace, ctx):
    w = program_spans.window(trace, ctx)
    return w.idle_ms_per_tick({"serve.fetch"}) if w else None
