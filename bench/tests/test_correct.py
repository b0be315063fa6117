"""``correct`` on CPU rehearsals of every cell (the tiny sizes of
``bench/rehearsal.json``; the harness's look for a chip is skipped):
sound runs pass, the float8 control put in the program's place fails,
and so does every fault a serving cell can have, planted under the
timed path."""
import json
import time

import numpy as np
import pytest

import harness
import run as bench_run
import serving
from harness import BENCH, ROOT

CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]


def rehearse(cell, seed, *extra):
    args = bench_run.parse(["--workload", cell, "--seed", str(seed),
                            "--seconds", "4", "--trace", "0", "--rehearse",
                            *extra])
    return bench_run.run_cell(args)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_rehearsal_is_correct_and_control_is_not(cell):
    r = rehearse(cell, 2 ** 31 + 11, "--control")
    assert r["correct"], r["check"]
    assert r["attempted"] > 0 and r["failed"] == 0
    limit = r["check"]["served_gap_max"]["limit"]
    # the control, in the program's place, is judged by the same limit
    assert r["info"]["control_gap_max"] > limit, r["info"]


def _state_unchanged(monkeypatch):
    """Decode returns the cache it was given: no key or value is kept."""
    from repro.models import api
    orig = api.serve_step
    monkeypatch.setattr(api, "serve_step", lambda p, cfg, batch, cache, i,
                        **kw: (orig(p, cfg, batch, cache, i, **kw)[0], cache))


def _half_batch(monkeypatch):
    """Decode computes the first half of the rows and hands their logits
    to the second half too."""
    import jax.numpy as jnp
    from repro.models import api
    orig = api.serve_step

    def step(p, cfg, batch, cache, i, **kw):
        logits, new = orig(p, cfg, batch, cache, i, **kw)
        h = logits.shape[0] - logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:logits.shape[0] - h]]), new
    monkeypatch.setattr(api, "serve_step", step)


def _token_altered(monkeypatch):
    """Each finished request's last token is changed where it is emitted."""
    from repro.serving import engine as eng
    orig = eng.ContinuousBatchingEngine._emit_tick

    def emit(self, now):
        fins = orig(self, now)
        for f in fins:
            f.tokens = np.asarray(f.tokens).copy()
            f.tokens[-1] = (f.tokens[-1] + 1) % self.cfg.vocab_size
        return fins
    monkeypatch.setattr(eng.ContinuousBatchingEngine, "_emit_tick", emit)


@pytest.mark.parametrize("plant", [_state_unchanged, _half_batch,
                                   _token_altered])
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(cell, plant, monkeypatch):
    plant(monkeypatch)
    r = rehearse(cell, 5)
    assert not r["correct"], r["check"]


def test_open_loop_driver_rehearsal():
    """The open-loop driver and the chat readers on the chat cells' files
    (kept in ``bench/`` for the benchmark PR that lists those cells),
    in a CPU rehearsal: sound, every request finished, the control over
    the limit."""
    def load(*parts):
        return harness.load_json(BENCH.joinpath(*parts))

    ctx = harness.Context(
        name="serve-150m-chat", cell=load("workloads", "serve-150m-chat.json"),
        config=load("configs", "dipaco-150m.json"),
        traffic=load("traffic", "chat.json"), seed=2 ** 31 + 3, seconds=4,
        trace=False, control=True, over=load("rehearsal.json"),
        started=time.perf_counter(), compiles=harness.CompileCounter())
    out = serving.outcome(ctx, open_loop=True)
    assert all(v <= lim for v, lim in out.check.values()), out.check
    assert out.attempted > 0 and out.failed == 0
    assert out.info["control_gap_max"] > out.check["served_gap_max"][1]
    for name in ("ttft_p95_s", "tpot_p95_ms"):
        value = harness.load_reader(name)(out.view, None, ctx)
        assert 0 < value < float("inf")
