"""Model step: useful model FLOPs of Moonlight (each admitted prompt's
prefill with one head position, one absorbed decode per active row per
tick; ``flops_moonlight``) over the summed wall time of the ticks, as a
share of the chip's bf16 peak (%)."""
import flops_moonlight as fm
import serve_work


def read(run, trace, ctx):
    wall = serve_work.tick_seconds(run)
    if wall <= 0:
        return None
    m = run.model
    work = (sum(fm.prefill_flops(m, n) for _, n in serve_work.prefills(run))
            + sum(fm.token_flops(m, c)
                  for _, c in serve_work.decode_rows(run)))
    return 100.0 * work / (wall * ctx.peaks["bf16_flops_per_s"])
