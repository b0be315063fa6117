"""Sampling: bytes of logits the engine copied to the host
(``serve.fetch`` ``bytes``), per tick (MB, 1e6 bytes)."""
import program_spans


def read(run, trace, ctx):
    w = program_spans.window(trace, ctx)
    return w.args_sum("serve.fetch", "bytes") / 1e6 / w.ticks if w else None
