"""Load generator: 95th percentile of how late the harness submitted a
request after it was due (ms).  Requests arrive between ticks, so a
tick's length bounds it."""
from stats import percentile


def read(run, trace, ctx):
    due = run.due_in_window()
    return percentile([1e3 * (r.submitted - r.due) for r in due], 95) \
        if due else None
