"""95th percentile over the requests due in the window of (finish -
first token) / (outputs - 1), in ms; a request that did not finish
counts as infinite."""
from stats import percentile


def read(run, trace, ctx):
    vals = []
    for r in run.due_in_window():
        if r.finish_tick < 0:
            vals.append(float("inf"))
        elif r.emitted > 1:
            vals.append(1e3 * (run.tick_end[r.finish_tick]
                               - run.tick_end[r.first_tick]) / (r.emitted - 1))
    return percentile(vals, 95) if vals else None
