"""Kernels: the least time the chip needs to read the routed experts
that the decode ticks hit (the engine's ``serve.decode`` ``experts_hit``,
summed over MoE layers and ticks, times one expert's three bf16
matrices) at the HBM peak, over the summed device time of the expert
matmuls (Megablox ``gmm``) in the decode programs of the traced window
(%)."""
import flops_moonlight as fm
import op_time
import program_spans


def read(run, trace, ctx):
    w = program_spans.window(trace, ctx)
    if w is None:
        return None
    kernel_s = op_time.named_ns(trace, op_time.DECODE_PROGRAMS,
                                "gmm") / 1e9
    hit = sum(a.get("experts_hit", 0) for n, _, _, a, _ in w.spans
              if n == "serve.decode")
    if kernel_s <= 0 or hit <= 0:
        return None
    least = hit * fm.expert_bytes(run.model) / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / kernel_s
