"""The engine's own spans in the profiler trace of the window, and the
device idle time under them.

The engine's ``repro.obs`` spans (``serve.tick`` and the phases nested
in it) enter ``jax.profiler`` annotations, so they lie on the
``/host:CPU`` plane, their arguments as event stats.  Device idle time
is that of chip 0: the window minus the union of its ``XLA Ops``.  The
device's events lie on a clock that reads about 1.5 ms behind the
host's on a v5e, as much as a prefill takes, so they are moved onto the
host's clock first (``clock_offset``).  A span's self time is its
interval minus those of the spans nested in it.  "Per tick" divides by
the number of ``serve.tick`` spans inside the window.

The arithmetic works on plain ``(name, start_ns, end_ns, ...)`` tuples,
with ``trace_reduce``'s interval functions.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

from trace_reduce import clip, subtract, total, union

PREFIX = "serve."
TICK = "serve.tick"
# the host's launch of a device program, and its notice that it ended
LAUNCH = "tpu::System::Execute"
DONE = "tpu::System::Execute=>Done"


def self_times(spans) -> list:
    """For spans ``(name, s, e, ...)`` of one host thread, where spans
    nest: each span's interval minus those of the spans nested in it."""
    order = sorted(range(len(spans)), key=lambda i: (spans[i][1],
                                                     -spans[i][2]))
    kids = [[] for _ in spans]
    stack = []
    for i in order:
        s, e = spans[i][1], spans[i][2]
        while stack and spans[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            kids[stack[-1]].append((s, e))
        stack.append(i)
    return [subtract([(sp[1], sp[2])], union(k))
            for sp, k in zip(spans, kids)]


def intersect(a, b) -> list:
    """The parts of the merged intervals ``a`` that the merged ``b``
    covers."""
    return subtract(a, subtract(a, b))


def clock_offset(launches, dones, runs) -> float:
    """What to add to the device's clock to put it on the host's.

    A chip runs its programs in the order the host launched them, so
    the i-th program run ``runs[i]`` (device clock) belongs to the i-th
    launch and the i-th end notice (host clock).  A program starts
    after its launch and ends before its notice, which bounds the
    offset from below and above; the middle of the bounds is returned.
    0 where the counts differ or the bounds cross (the events do not
    pair)."""
    if not runs or not len(launches) == len(dones) == len(runs):
        return 0.0
    lo = max(t - s for t, (s, _) in zip(launches, runs))
    hi = min(t - e for t, (_, e) in zip(dones, runs))
    return (lo + hi) / 2 if lo <= hi else 0.0


def device_gaps(trace, offset: float = 0.0, chip: int = 0) -> list:
    """The intervals of the window in which nothing runs on ``chip``, on
    the host's clock (the device's moved by ``offset``)."""
    busy = union([(s + offset, e + offset) for _, s, e in
                  trace.dev(chip).ops])
    return subtract([(trace.lo, trace.hi)], clip(busy, trace.lo, trace.hi))


@functools.lru_cache(maxsize=1)
def load(path: str) -> tuple:
    """The ``serve.*`` events ``(name, s, e, args)`` of each host line,
    and the starts of the host's program launches and end notices."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    lines, launches, dones = [], [], []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = []
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    evs.append((ev.name, ev.start_ns, ev.end_ns,
                                dict(ev.stats)))
                elif ev.name == LAUNCH:
                    launches.append(ev.start_ns)
                elif ev.name == DONE:
                    dones.append(ev.start_ns)
            if evs:
                lines.append(evs)
    return tuple(lines), sorted(launches), sorted(dones)


@dataclass
class Window:
    gaps: list      # device idle intervals of the window, merged
    spans: list     # (name, s, e, args, self time) inside the window
    ticks: int

    def idle_ms_per_tick(self, names, own: bool = True) -> float:
        """Device idle time under the spans named ``names``, their self
        time (``own``) or their whole interval, per tick."""
        ivs = union([iv for n, s, e, _, o in self.spans if n in names
                     for iv in (o if own else [(s, e)])])
        return total(intersect(self.gaps, ivs)) / 1e6 / self.ticks

    def args_sum(self, name: str, key: str) -> float:
        return float(sum(a[key] for n, _, _, a, _ in self.spans
                         if n == name))


def build(lines, gaps, lo: float, hi: float) -> Window | None:
    """The spans of ``lines`` that lie inside ``[lo, hi]``; ``None``
    where no tick does."""
    spans = []
    for line in lines:
        for sp, own in zip(line, self_times(line)):
            if sp[1] >= lo and sp[2] <= hi:
                spans.append((*sp[:4], own))
    ticks = sum(1 for sp in spans if sp[0] == TICK)
    return Window(gaps, spans, ticks) if ticks else None


def window(trace, ctx) -> Window | None:
    """The engine spans of the traced window; ``None`` where the trace
    holds no TPU plane (a CPU rehearsal) or no engine tick (a program
    without these spans)."""
    if trace is None or not trace.devices or not ctx.trace_file:
        return None
    lines, launches, dones = load(ctx.trace_file)
    runs = sorted((s, e) for _, s, e in trace.dev(0).modules)
    offset = clock_offset(launches, dones, runs)
    return build(lines, device_gaps(trace, offset), trace.lo, trace.hi)
