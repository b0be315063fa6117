"""Every prompt token prefilled and every token generated inside the
window, over the window (tokens/s)."""


def read(run, trace, ctx):
    toks = sum(r.plen + r.emitted for r in run.records if r.admit_tick >= 0)
    return toks / run.close if run.close > 0 else None
