"""Model step (bucketed prefill): device time of the prefill programs per
1000 real prompt tokens admitted in the traced window (ms)."""
PREFILL_PROGRAMS = ("jit__prefill_bucketed",)


def read(run, trace, ctx):
    runs = trace.module_runs(PREFILL_PROGRAMS)
    toks = sum(r.plen for r in run.records if r.admit_tick >= 0)
    if not runs or not toks:
        return None
    return sum(e - s for s, e in runs) / 1e6 / (toks / 1e3)
