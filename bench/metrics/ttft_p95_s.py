"""95th percentile over the requests due in the window of the time from
a request's due arrival to the end of the tick that emitted its first
token; a request that never got one counts as infinite."""
from stats import percentile


def read(run, trace, ctx):
    return percentile([run.tick_end[r.first_tick] - r.due
                       if r.first_tick >= 0 else float("inf")
                       for r in run.due_in_window()], 95)
