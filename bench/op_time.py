"""Device time of named ops inside chosen programs, from a reduced trace
(``trace_reduce.Trace``).  A Pallas kernel's op is named after it in the
program (``mla_decode.3``, Megablox's ``gmm.7``)."""
from __future__ import annotations

import bisect

from trace_reduce import op_name

DECODE_PROGRAMS = ("jit__decode_one", "jit__decode_island")


def named_ns(trace, programs, name: str, chip: int = 0) -> float:
    """Summed device time (ns) of the ops called ``name`` (``name`` or
    ``name.<n>``) that ran inside runs of ``programs`` in the window."""
    runs = trace.module_runs(programs, chip)
    starts = [s for s, _ in runs]
    out = 0.0
    for t, s, e in trace.dev(chip).ops:
        if op_name(t).split(".")[0] != name:
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and e <= runs[i][1]:
            out += e - s
    return out
