"""Scheduler: device idle time inside the self time of the engine's
scheduling (``serve.schedule``), admission (``serve.admit``) and prefill
dispatch (``serve.prefill``) spans, per tick (ms)."""
import program_spans

SPANS = {"serve.schedule", "serve.admit", "serve.prefill"}


def read(run, trace, ctx):
    w = program_spans.window(trace, ctx)
    return w.idle_ms_per_tick(SPANS) if w else None
