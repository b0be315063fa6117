"""Model step: useful model FLOPs (prefill of every admitted prompt and
one decode per active row per tick) over the summed wall time of the
ticks, as a share of the chip's bf16 peak (%)."""
import serve_work


def read(run, trace, ctx):
    return serve_work.mfu(run, ctx.peaks)
