"""Kernels: the least time the chip needs for the decode attention of
the active rows (their valid positions' keys and values read once, QK^T
and PV computed once) over the summed device time of ``flash_decode``
in the traced window (%).

``flash_decode`` is the only Pallas kernel in the decode programs; the
stacked tick runs it under ``vmap``, where it carries the name of the
enclosing call, so its time is that of the Pallas kernels in those
programs."""
import flops
import serve_work

DECODE_PROGRAMS = ("jit__decode_one", "jit__decode_island")


def read(run, trace, ctx):
    kernel_s = trace.pallas_ns(DECODE_PROGRAMS) / 1e9
    if kernel_s <= 0:
        return None
    f = b = 0.0
    for _, c in serve_work.decode_rows(run):
        df, db = flops.decode_attention_work(run.model, c)
        f += df
        b += db
    least = max(f / ctx.peaks["bf16_flops_per_s"],
                b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
