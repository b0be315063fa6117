"""The trace reduction: interval arithmetic on synthetic events, and the
parser on a small trace recorded on a v5e (``data/v5e_decode.xplane.pb``:
a few prefills and decode steps of the 150M path with ``flash_decode``,
under ``bench.*`` annotations)."""
from pathlib import Path

import pytest

import trace_reduce as tr

DATA = Path(__file__).parent / "data" / "v5e_decode.xplane.pb"


def test_union_and_subtract():
    assert tr.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert tr.total(tr.clip([(0, 10), (12, 20)], 5, 15)) == 8


def test_leaves_drop_containers():
    evs = [("%while.1 = x while(y)", 0, 100), ("%fusion.2 = a", 10, 20),
           ("%flash_decode.3 = b custom-call(c)", 30, 50),
           ("%copy.4 = d", 120, 130)]
    assert [e[0] for e in tr.leaves(evs)] == [e[0] for e in evs[1:]]


def _trace():
    ops = [("%while.1 = t while(x)", 0, 100),
           ("%fusion.2 = t fusion(x)", 0, 40),
           ("%flash_decode.3 = t custom-call(x)", 40, 60),
           ("%all-gather.4 = t all-gather(x)", 55, 90),
           ("%flash_decode.5 = t custom-call(x)", 150, 170),
           ("%all-reduce.6 = t all-reduce(x)", 200, 260),
           ('%closed_call.8 = t custom-call(x), '
            'custom_call_target="tpu_custom_call"', 152, 158),
           ('%custom-call.9 = t custom-call(), '
            'custom_call_target="AllocateBuffer"', 160, 161),
           ("%fusion.7 = t fusion(x)", 245, 270)]
    mods = [("jit__decode_one(1)", 0, 100), ("jit__decode_one(1)", 150, 170),
            ("jit_step(2)", 200, 260)]
    host = [("bench.window", 0, 300), ("bench.step", 0, 180),
            ("bench.submit", 100, 130)]
    return tr.Trace(devices={0: tr.Device(ops=ops, modules=mods)},
                    host=host, lo=0, hi=300)


def test_busy_kernels_modules_collectives():
    t = _trace()
    # busy: [0,100] + [150,170] + [200,270]
    assert t.busy_ns(0) == 190
    assert t.busy_s() == pytest.approx(190e-9)
    assert t.window_s() == pytest.approx(300e-9)
    # the Pallas kernel under vmap, inside a decode program run
    assert t.pallas_ns(["jit__decode_one"]) == 6
    assert t.pallas_ns(["jit_step"]) == 0
    assert t.module_runs(["jit__decode_one"]) == [(0, 100), (150, 170)]
    # all-gather 55-90: 60-90 has nothing else beside it (the while loop
    # is a container, not compute); all-reduce 200-260 minus 245-270
    assert t.collective_exposed_ns() == 30 + 45


def test_breakdown_attributes_idle_to_innermost_span():
    t = _trace()
    gaps = dict(t.idle_gaps())
    # idle: 100-150 (mid 125: bench.submit), 170-200 (mid 185: window),
    # 270-300 (window)
    assert gaps == {"bench.submit": pytest.approx(50e-9),
                    "bench.window": pytest.approx(60e-9)}
    top = dict(t.top_ops())
    assert top["jit__decode_one/fusion.2"] == pytest.approx(40e-9)
    assert "jit__decode_one/while.1" not in top


def test_recorded_v5e_trace():
    from jax.profiler import ProfileData
    t = tr.load(str(DATA))
    assert 0 in t.devices and t.lo < t.hi
    # an independent reading of the same file: the TPU plane's ops,
    # clipped to the window
    pd = ProfileData.from_file(str(DATA))
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops = [(ev.name, max(ev.start_ns, t.lo), min(ev.end_ns, t.hi))
           for ln in plane.lines if ln.name == "XLA Ops" for ev in ln.events]
    ops = [(n, s, e) for n, s, e in ops if e > s]
    # busy: the time during which at least one op is open, by counting
    # starts and ends
    marks = sorted([(s, 1) for _, s, _ in ops] + [(e, -1) for _, _, e in ops])
    covered, depth, last = 0.0, 0, None
    for x, d in marks:
        if depth > 0:
            covered += x - last
        depth, last = depth + d, x
    assert t.busy_ns(0) == pytest.approx(covered)
    # flash_decode is the only Pallas kernel of the traced decode steps
    first = min(s for s, _ in t.module_runs(["jit__lambda"]))
    fd = sum(e - s for n, s, e in ops
             if n.startswith("%flash_decode.") and s >= first)
    assert fd > 0 and t.pallas_ns(["jit__lambda"]) == pytest.approx(fd)
    # 2 prefills and 3 decodes ran; the device clock reads about 1.5 ms
    # behind the host's here, so the first prefill falls before the window
    assert len(t.module_runs(["jit__lambda"])) == 4
    names = [n for n, _ in t.idle_gaps()]
    assert set(names) <= {"bench.window", "bench.prefill", "bench.decode",
                          "bench.host"}
