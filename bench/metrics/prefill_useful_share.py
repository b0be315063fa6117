"""Model step: real prompt tokens over the tokens the prefill programs
computed, padding to the length bucket and to a power of two in rows
included (``serve.prefill`` ``tokens`` over ``padded_tokens``), in the
traced window (%)."""
import program_spans


def read(run, trace, ctx):
    w = program_spans.window(trace, ctx)
    padded = w.args_sum("serve.prefill", "padded_tokens") if w else 0
    if not padded:
        return None
    return 100.0 * w.args_sum("serve.prefill", "tokens") / padded
