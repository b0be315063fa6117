"""The engine spans' reduction: self time and device idle time under
spans on synthetic events, and the readers that fall silent without
engine spans or without a TPU plane."""
from pathlib import Path
from types import SimpleNamespace

import pytest

import harness
import program_spans as ps
import trace_reduce as tr

DATA = Path(__file__).parent / "data"
READERS = ["idle_in_tick_ms", "idle_in_admit_ms", "idle_in_fetch_ms",
           "idle_in_emit_ms", "logits_to_host_mb_per_tick",
           "prefill_useful_share"]


def _line():
    """One tick of one host thread, nested as the engine nests it."""
    return [("serve.tick", 0, 100, {"step_num": 1}),
            ("serve.schedule", 0, 10, {}),
            ("serve.admit", 10, 40, {"path": 0, "requests": 3}),
            ("serve.prefill", 12, 35, {"rows": 3, "padded_rows": 4,
                                       "tokens": 700, "padded_tokens": 1024,
                                       "bucket": 256}),
            ("serve.fetch", 30, 35, {"bytes": 4 * 32000 * 4}),
            ("serve.decode", 40, 80, {"rows": 5, "slots": 32, "dense": 1}),
            ("serve.fetch", 70, 80, {"bytes": 32 * 32000 * 4}),
            ("serve.emit", 80, 95, {"rows": 8, "finished": 1}),
            # a tick that ends after the window closes does not count
            ("serve.tick", 110, 130, {"step_num": 2})]


def _trace():
    ops = [("%fusion.1 = t fusion(x)", 5, 30),
           ("%fusion.2 = t fusion(x)", 45, 70)]
    return tr.Trace(devices={0: tr.Device(ops=ops)}, host=[], lo=0, hi=120)


def test_self_time_leaves_out_nested_spans():
    own = dict(zip([n + str(i) for i, (n, *_) in enumerate(_line())],
                   ps.self_times(_line())))
    assert own["serve.tick0"] == [(95, 100)]
    assert own["serve.admit2"] == [(10, 12), (35, 40)]
    assert own["serve.prefill3"] == [(12, 30)]
    assert own["serve.decode5"] == [(40, 70)]
    assert own["serve.fetch6"] == [(70, 80)]
    assert ps.intersect([(0, 10), (20, 30)], [(5, 25)]) == \
        [(5, 10), (20, 25)]


def test_clock_offset_from_launches_and_end_notices():
    # device clock 100 behind: runs at 0-10 and 20-40 launched at 95 and
    # 110, their ends noticed at 112 and 145
    runs = [(0, 10), (20, 40)]
    assert ps.clock_offset([95, 110], [112, 145], runs) == (95 + 102) / 2
    # moved by the offset, each run lies between its launch and notice
    off = ps.clock_offset([95, 110], [112, 145], runs)
    assert all(t <= s + off and e + off <= d for t, d, (s, e) in
               zip([95, 110], [112, 145], runs))
    assert ps.clock_offset([95], [112, 145], runs) == 0.0     # unpaired
    assert ps.clock_offset([95, 110], [100, 145], runs) == 0.0  # crossed
    t = _trace()
    assert ps.device_gaps(t, offset=10) == [(0, 15), (40, 55), (80, 120)]


def test_idle_under_spans_and_counts():
    t = _trace()
    gaps = ps.device_gaps(t)
    assert gaps == [(0, 5), (30, 45), (70, 120)]
    w = ps.build([_line()], gaps, t.lo, t.hi)
    assert w.ticks == 1
    ms = 1e6                         # the synthetic clock is in ns
    tick = w.idle_ms_per_tick({"serve.tick"}, own=False) * ms
    admit = w.idle_ms_per_tick({"serve.schedule", "serve.admit",
                                "serve.prefill"}) * ms
    fetch = w.idle_ms_per_tick({"serve.fetch"}) * ms
    emit = w.idle_ms_per_tick({"serve.emit"}) * ms
    # tick: 0-5, 30-45, 70-100; schedule 0-5, admit's own 35-40;
    # fetches 30-35 and 70-80; emit 80-95
    assert (tick, admit, fetch, emit) == pytest.approx((50, 10, 15, 15))
    assert admit + fetch + emit <= tick
    assert w.args_sum("serve.fetch", "bytes") == 36 * 32000 * 4
    assert w.args_sum("serve.prefill", "tokens") == 700


def _ctx(trace_file):
    return SimpleNamespace(trace_file=trace_file)


@pytest.mark.parametrize("name", READERS)
def test_readers_silent_without_tpu_plane_or_engine_spans(name):
    read = harness.load_reader(name)
    cpu = tr.Trace(devices={}, host=[], lo=0, hi=120)
    assert read(None, cpu, _ctx("unused.xplane.pb")) is None
    assert read(None, _trace(), _ctx(None)) is None
    # a v5e trace of the model alone: a TPU plane, no engine span
    decode = str(DATA / "v5e_decode.xplane.pb")
    assert read(None, tr.load(decode), _ctx(decode)) is None


def _covered(ops, lo, hi):
    """Time in [lo, hi] during which at least one op is open, by
    counting starts and ends."""
    ops = [(max(s, lo), min(e, hi)) for s, e in ops if min(e, hi) > max(s, lo)]
    marks = sorted([(s, 1) for s, _ in ops] + [(e, -1) for _, e in ops])
    covered, depth, last = 0.0, 0, None
    for x, d in marks:
        if depth > 0:
            covered += x - last
        depth, last = depth + d, x
    return covered


def test_recorded_v5e_engine_trace():
    """Six engine ticks on a v5e (``bench/tools/record_engine_trace.py``):
    five prompts of 40, 90, 130, 70 and 200 tokens on one island, in
    buckets 64, 128, 256, 128 and 256; a dense decode of 2 x 4 slots."""
    from jax.profiler import ProfileData
    path = str(DATA / "v5e_engine.xplane.pb")
    t, ctx = tr.load(path), _ctx(path)
    got = {n: harness.load_reader(n)(None, t, ctx) for n in READERS}
    assert None not in got.values(), got
    parts = (got["idle_in_admit_ms"] + got["idle_in_fetch_ms"]
             + got["idle_in_emit_ms"])
    assert 0 < parts <= got["idle_in_tick_ms"]
    assert got["prefill_useful_share"] == pytest.approx(
        100 * (40 + 90 + 130 + 70 + 200) / (64 + 128 + 256 + 128 + 256))
    # five one-row prefills of (1, 32000) bf16 logits, five decodes of
    # (2, 4, 32000), over six ticks
    assert got["logits_to_host_mb_per_tick"] == pytest.approx(
        (5 + 5 * 8) * 32000 * 2 / 1e6 / 6)
    # an independent reading of the ticks' idle time from the file
    pd = ProfileData.from_file(path)
    host = [ev for p in pd.planes if p.name == "/host:CPU"
            for ln in p.lines for ev in ln.events]
    ticks = [(ev.start_ns, ev.end_ns) for ev in host
             if ev.name == "serve.tick" and t.lo <= ev.start_ns
             and ev.end_ns <= t.hi]
    assert len(ticks) == 6
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    runs = sorted((ev.start_ns, ev.end_ns) for ln in dev.lines
                  if ln.name == "XLA Modules" for ev in ln.events)
    launch = sorted(ev.start_ns for ev in host
                    if ev.name == "tpu::System::Execute")
    done = sorted(ev.start_ns for ev in host
                  if ev.name == "tpu::System::Execute=>Done")
    # 20 programs; the device's clock reads 1.39-1.72 ms behind
    lead = max(a - s for a, (s, _) in zip(launch, runs))
    lag = min(d - e for d, (_, e) in zip(done, runs))
    assert len(runs) == len(launch) == len(done) == 20
    assert 1.3e6 < lead <= lag < 1.8e6
    off = (lead + lag) / 2
    ops = [(ev.start_ns + off, ev.end_ns + off) for ln in dev.lines
           if ln.name == "XLA Ops" for ev in ln.events]
    idle = sum((e - s) - _covered(ops, s, e) for s, e in ticks)
    assert got["idle_in_tick_ms"] == pytest.approx(idle / 1e6 / 6)
