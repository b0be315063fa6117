"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
metric readers use.

On a TPU the trace holds a plane ``/device:TPU:<n>`` per chip with the
lines ``XLA Modules`` (one event per program run, named
``jit_<function>(<id>)``) and ``XLA Ops`` (one event per HLO
instruction, named by its HLO text, ``%<name>.<n> = ...``; a ``while``
loop's event spans the ops of its body), and a plane ``/host:CPU`` whose
``python`` line holds the harness's ``jax.profiler.TraceAnnotation``
spans.  All of them share one clock, in nanoseconds.

The arithmetic works on plain ``(name, start_ns, end_ns)`` tuples so
that it can be checked without a trace.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all")
HARNESS_PREFIX = "bench."
# a Pallas kernel on the TPU is a custom call with this target; under
# ``vmap`` it is named after the enclosing call (``%closed_call.14``),
# not after the kernel, so it is found by its target and its program
PALLAS = 'custom_call_target="tpu_custom_call"'


def union(intervals):
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b):
    """Parts of the merged intervals ``a`` that no interval of the merged
    ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def leaves(events):
    """The events that contain no other event (``while`` loops and other
    containers drop out, their body ops stay)."""
    evs = sorted(events, key=lambda x: (x[1], -x[2]))
    parent = [False] * len(evs)
    stack = []
    for i, (_, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][2]:
            parent[stack[-1]] = True
        stack.append(i)
    return [ev for ev, p in zip(evs, parent) if not p]


def op_name(hlo_text: str) -> str:
    """``%flash_decode.7 = bf16[...] custom-call(...)`` -> ``flash_decode.7``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def module_of(name: str) -> str:
    """``jit__decode_one(123)`` -> ``jit__decode_one``."""
    return name.split("(", 1)[0]


@dataclass
class Device:
    ops: list = field(default_factory=list)       # (hlo text, s, e)
    modules: list = field(default_factory=list)   # (module, s, e)


@dataclass
class Trace:
    devices: dict          # chip id -> Device
    host: list             # harness annotations (name, s, e)
    lo: float              # window, trace clock (ns)
    hi: float

    # -- per chip ---------------------------------------------------------
    def dev(self, chip: int) -> Device:
        """The chip's events (none where the trace holds no TPU plane, as
        in a CPU rehearsal)."""
        return self.devices.get(chip, Device())

    def busy_ns(self, chip: int) -> float:
        return total(clip(union([(s, e) for _, s, e in self.dev(chip).ops]),
                          self.lo, self.hi))

    def busy_s(self) -> float:
        """Busy seconds averaged over the chips."""
        n = max(len(self.devices), 1)
        return sum(self.busy_ns(c) for c in self.devices) / n / 1e9

    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def pallas_ns(self, prefixes, chip: int = 0) -> float:
        """Summed device time of the Pallas kernels that ran inside the
        program runs whose module name starts with one of ``prefixes``."""
        runs = self.module_runs(prefixes, chip)
        starts = [s for s, _ in runs]
        out = 0.0
        for t, s, e in self.dev(chip).ops:
            if PALLAS not in t:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and e <= runs[i][1]:
                out += e - s
        return out

    def module_runs(self, prefixes, chip: int = 0) -> list:
        """``(s, e)`` of the program runs whose module name starts with
        one of ``prefixes``, inside the window."""
        return sorted((s, e) for n, s, e in self.dev(chip).modules
                      if n.startswith(tuple(prefixes)) and s >= self.lo
                      and e <= self.hi)

    def collective_exposed_ns(self, chip: int = 0) -> float:
        """Collective op time on ``chip`` during which no other op runs."""
        ops = leaves(self.dev(chip).ops)
        coll = union([(s, e) for t, s, e in ops if COLLECTIVE.search(
            t.split("(", 1)[0])])
        comp = union([(s, e) for t, s, e in ops if not COLLECTIVE.search(
            t.split("(", 1)[0])])
        return total(clip(subtract(coll, comp), self.lo, self.hi))

    # -- breakdown ----------------------------------------------------------
    def top_ops(self, n: int = 10, chip: int = 0) -> list:
        """The ``n`` device ops (leaf ops, by module and name) that took
        the most time in the window."""
        mods = sorted(self.dev(chip).modules, key=lambda x: x[1])
        starts = [s for _, s, _ in mods]
        acc = defaultdict(float)
        for t, s, e in leaves(self.dev(chip).ops):
            s, e = max(s, self.lo), min(e, self.hi)
            if e <= s:
                continue
            i = bisect.bisect_right(starts, s) - 1
            mod = module_of(mods[i][0]) if i >= 0 and mods[i][2] >= e \
                else "?"
            acc[f"{mod}/{op_name(t)}"] += (e - s) / 1e9
        return sorted(([k, v] for k, v in acc.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10, chip: int = 0) -> list:
        """Device idle time in the window, summed by the innermost harness
        annotation open on the host at each gap's midpoint; the ``n``
        largest."""
        busy = clip(union([(s, e) for _, s, e in self.dev(chip).ops]),
                    self.lo, self.hi)
        gaps = subtract([(self.lo, self.hi)], busy)
        host = sorted(self.host, key=lambda x: x[1])
        acc = defaultdict(float)
        active, i = [], 0
        for s, e in gaps:              # sorted: sweep the host spans once
            mid = (s + e) / 2
            while i < len(host) and host[i][1] <= mid:
                active.append(host[i])
                i += 1
            active = [h for h in active if h[2] > mid]
            inner = max(active, key=lambda h: h[1]) if active else None
            acc[inner[0] if inner else "no harness span"] += (e - s) / 1e9
        return sorted(([k, v] for k, v in acc.items()),
                      key=lambda kv: -kv[1])[:n]


def load(path: str, window_span: str = "bench.window") -> Trace:
    """Read ``path``; the window is the first ``window_span`` annotation
    on the host (the whole trace where there is none)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), Device())
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops.extend((ev.name, ev.start_ns, ev.end_ns)
                                   for ev in line.events)
                elif line.name == "XLA Modules":
                    dev.modules.extend((ev.name, ev.start_ns, ev.end_ns)
                                       for ev in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns, ev.end_ns)
                            for ev in line.events
                            if ev.name.startswith(HARNESS_PREFIX))
    win = [(s, e) for n, s, e in host if n == window_span]
    if win:
        lo, hi = win[0]
    else:
        spans = [(s, e) for d in devices.values() for _, s, e in d.ops]
        lo = min((s for s, _ in spans), default=0.0)
        hi = max((e for _, e in spans), default=0.0)
    return Trace(devices=devices, host=host, lo=lo, hi=hi)
