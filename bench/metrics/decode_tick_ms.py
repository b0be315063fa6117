"""Model step: device time of the decode programs (the stacked decode of
every island, and the single-island decode of sparse ticks) per tick
that decoded, in the traced window (ms)."""
DECODE_PROGRAMS = ("jit__decode_one", "jit__decode_island")


def read(run, trace, ctx):
    runs = trace.module_runs(DECODE_PROGRAMS)
    ticks = sum(1 for t, n in run.decoded.items() if n > 0)
    if not runs or not ticks:
        return None
    return sum(e - s for s, e in runs) / 1e6 / ticks
