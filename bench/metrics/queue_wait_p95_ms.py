"""Scheduler: 95th percentile of the time from a request's due arrival to
the start of the tick that admitted it (ms); never admitted counts as
infinite."""
from stats import percentile


def read(run, trace, ctx):
    due = run.due_in_window()
    return percentile([1e3 * (run.tick_start[r.admit_tick] - r.due)
                       if r.admit_tick >= 0 else float("inf")
                       for r in due], 95) if due else None
