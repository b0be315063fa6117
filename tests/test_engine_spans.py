"""The continuous-batching engine's host phases in a ``jax.profiler``
capture: ``repro.obs`` spans reach the profiler's trace whether the
engine has no telemetry or a JSONL ``Telemetry``, nested inside each
``serve.tick``, with the prefill padding and logits-fetch counts the
benchmark reads."""
import glob

import jax
import numpy as np
import pytest

from repro.models import api
from repro.obs import NULL, Telemetry, read_trace, validate_trace
from repro.serving import ContinuousBatchingEngine, EngineOptions, Request

PATHS, SLOTS, CACHE_LEN = 2, 4, 64
ENGINE_SPANS = {"serve.schedule", "serve.admit", "serve.prefill",
                "serve.fetch", "serve.decode", "serve.emit"}


@pytest.fixture(scope="module")
def cfg():
    from repro.configs import get_smoke_config
    return get_smoke_config("dipaco-150m").replace(route_prefix_len=8)


@pytest.fixture(scope="module")
def paths(cfg):
    key = jax.random.PRNGKey(0)
    return [api.init_model(jax.random.fold_in(key, p), cfg)[0]
            for p in range(PATHS)]


def _requests(cfg, rids, lens):
    rng = np.random.default_rng(7)
    return [Request(rid=r, prompt=rng.integers(0, cfg.vocab_size, n,
                                               dtype=np.int32), max_new=3)
            for r, n in zip(rids, lens)]


def _serve_captured(engine, cfg, out_dir):
    """Two waves of requests under a profiler capture: the second wave
    is admitted in the tick that decodes the first, so that tick holds
    every engine phase.  Returns the host's ``serve.*`` events."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        for r in _requests(cfg, [0, 1, 2], [9, 20, 33]):
            engine.submit(r)
        engine.step()
        for r in _requests(cfg, [3, 4], [12, 17]):
            engine.submit(r)
        while not engine.idle:
            engine.step()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(out_dir / "plugins/profile/*/*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    return [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events
            if ev.name.startswith("serve.")]


def _inside(ev, outer):
    return outer[1] <= ev[1] and ev[2] <= outer[2]


@pytest.mark.parametrize("sink", ["none", "jsonl"])
def test_engine_phases_in_profiler_trace(cfg, paths, tmp_path, sink):
    tel = Telemetry(tmp_path / "serve.jsonl", fresh=True) \
        if sink == "jsonl" else None
    engine = ContinuousBatchingEngine(cfg, paths, options=EngineOptions(
        cache_len=CACHE_LEN, slots_per_path=SLOTS, telemetry=tel,
        route_fn=lambda prompt: len(prompt) % PATHS))
    evs = _serve_captured(engine, cfg, tmp_path / "profile")

    ticks = [e for e in evs if e[0] == "serve.tick"]
    assert [t[3]["step_num"] for t in ticks] == \
        list(range(1, engine.ticks + 1))
    inner = [e for e in evs if e[0] != "serve.tick"]
    assert {e[0] for e in inner} == ENGINE_SPANS
    assert all(any(_inside(e, t) for t in ticks) for e in inner)
    # the tick that decodes the first wave admits the second
    assert {e[0] for e in inner if _inside(e, ticks[1])} == ENGINE_SPANS
    decodes = [e for e in inner if e[0] == "serve.decode"]
    assert all(any(f[0] == "serve.fetch" and _inside(f, d) for f in inner)
               for d in decodes)

    # prefill: padded to its bucket in length and a power of two in rows
    prefills = [e[3] for e in inner if e[0] == "serve.prefill"]
    assert sum(p["rows"] for p in prefills) == 5
    assert sum(p["tokens"] for p in prefills) == 9 + 20 + 33 + 12 + 17
    for p in prefills:
        assert p["tokens"] <= p["padded_tokens"] == \
            p["padded_rows"] * p["bucket"]
        assert p["rows"] <= p["padded_rows"]
    # a fetch copies the f32 logits of every row its program computed
    row_bytes = cfg.vocab_size * np.dtype(np.float32).itemsize
    for e in inner:
        if e[0] != "serve.fetch":
            continue
        outer, = [o for o in inner if o[0] in ("serve.prefill",
                                               "serve.decode")
                  and _inside(e, o)]
        rows = outer[3]["padded_rows" if outer[0] == "serve.prefill"
                        else "slots"]
        assert e[3]["bytes"] == rows * row_bytes
    # of two islands, one with work already makes a tick dense: one
    # stacked dispatch decodes every slot
    assert all(d[3]["dense"] == 1 and d[3]["slots"] == PATHS * SLOTS
               for d in decodes)

    if tel is not None:
        tel.close()
        records, skipped = read_trace(tmp_path / "serve.jsonl")
        assert skipped == 0 and validate_trace(records) == []
        spans = [r for r in records if r["k"] == "span"]
        assert sorted(r["name"] for r in spans) == sorted(e[0] for e in evs)
        for r in spans:
            if r["name"] == "serve.prefill":
                assert r["args"]["tokens"] <= r["args"]["padded_tokens"]


def test_null_span_allocates_nothing_without_a_capture():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert NULL.span("serve.tick", step_num=1) is NULL.span("serve.emit")
    assert Telemetry().span("serve.fetch", bytes=4) is NULL.span("x")
