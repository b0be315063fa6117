"""Sampling: device idle time inside the self time of the engine's
emission (``serve.emit``: host argmax, retirement), per tick (ms)."""
import program_spans


def read(run, trace, ctx):
    w = program_spans.window(trace, ctx)
    return w.idle_ms_per_tick({"serve.emit"}) if w else None
