"""Open-loop serving: Poisson arrivals at the cell's fixed rate over the
window, then the window's requests served to their end (``drain_s`` at
most).  Every request due in the window counts; one that does not
finish counts as failed."""
import serving


def run(ctx):
    return serving.outcome(ctx, open_loop=True)
