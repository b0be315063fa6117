"""Offline batch serving: a backlog larger than the window can drain is
due at the window's start; the window closes after ``--seconds`` on the
requests still queued or in flight."""
import serving


def run(ctx):
    return serving.outcome(ctx, open_loop=False)
