"""Engine host: device idle time inside the engine's ticks
(``serve.tick``, the spans nested in it included), per tick (ms)."""
import program_spans


def read(run, trace, ctx):
    w = program_spans.window(trace, ctx)
    return w.idle_ms_per_tick({"serve.tick"}, own=False) if w else None
