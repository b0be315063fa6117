"""Kernels: the least time the chip needs for the latent decode
attention of the active rows (their valid positions' latents read once,
scores and the weighted sum computed once; ``flops_moonlight``) over the
summed device time of the ``mla_decode`` kernel in the decode programs
of the traced window (%)."""
import flops_moonlight as fm
import op_time
import serve_work


def read(run, trace, ctx):
    if trace is None:
        return None
    kernel_s = op_time.named_ns(trace, op_time.DECODE_PROGRAMS,
                                "mla_decode") / 1e9
    if kernel_s <= 0:
        return None
    f = b = 0.0
    for _, c in serve_work.decode_rows(run):
        df, db = fm.mla_decode_work(run.model, c)
        f += df
        b += db
    least = max(f / ctx.peaks["bf16_flops_per_s"],
                b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
