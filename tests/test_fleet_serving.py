"""Serving fleet + the engine features it transports: priority-class
admission, preemptible slots (§2.4.3 re-prefill re-admission),
cross-request prefix caching, TTFT accounting, and the path-affinity
front door (rendezvous routing, autoscaled replicas, fleet-wide hot
swap off one registry promote)."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import api
from repro.models.config import DiPaCoConfig
from repro.serving import (PRIO_HIGH, PRIO_PREEMPTIBLE, PRIO_STANDARD,
                           ContinuousBatchingEngine, EngineOptions,
                           FinishedRequest, Request, ServingFleet,
                           poisson_trace)


@pytest.fixture(scope="module")
def cfg():
    from repro.configs import get_smoke_config
    return get_smoke_config("dipaco-150m").replace(route_prefix_len=8)


@pytest.fixture(scope="module")
def two_paths(cfg):
    key = jax.random.PRNGKey(0)
    p0, _ = api.init_model(key, cfg)
    p1, _ = api.init_model(jax.random.fold_in(key, 1), cfg)
    return [p0, p1]


def _prompts(cfg, lens, seed=10):
    return [np.asarray(jax.random.randint(jax.random.PRNGKey(seed + i),
                                          (l,), 0, cfg.vocab_size),
                       np.int32)
            for i, l in enumerate(lens)]


def _eng(cfg, paths, **opt):
    opt.setdefault("cache_len", 48)
    return ContinuousBatchingEngine(cfg, paths,
                                    options=EngineOptions(**opt))


# ---------------------------------------------------------------------
# priority classes
# ---------------------------------------------------------------------

def test_priority_class_admission_order(cfg, two_paths):
    """One slot, three same-path arrivals at t=0 in worst submission
    order: admission drains strictly by class — high, standard,
    preemptible — never FIFO across classes."""
    prompts = _prompts(cfg, [8, 8, 8], seed=60)
    eng = _eng(cfg, two_paths, cache_len=32, slots_per_path=1)
    trace = [
        Request(rid=0, prompt=prompts[0], max_new=3, path=0,
                priority=PRIO_PREEMPTIBLE),
        Request(rid=1, prompt=prompts[1], max_new=3, path=0,
                priority=PRIO_STANDARD),
        Request(rid=2, prompt=prompts[2], max_new=3, path=0,
                priority=PRIO_HIGH),
    ]
    fins = eng.serve_trace(trace)
    assert len(fins) == 3
    admitted = {f.rid: f.admitted_at for f in fins}
    assert admitted[2] < admitted[1] < admitted[0]
    assert all(f.priority == r.priority
               for f, r in zip(sorted(fins, key=lambda f: f.rid), trace))


def test_preemption_evicts_preemptible_and_stays_greedy_identical(
        cfg, two_paths):
    """A high-priority arrival on a full island evicts the preemptible
    occupant; the evictee re-admits via §2.4.3 re-prefill and its final
    tokens equal an uninterrupted solo run."""
    prompts = _prompts(cfg, [8, 8], seed=70)
    solo = _eng(cfg, two_paths, cache_len=32, slots_per_path=1)
    ref = solo.serve_trace([Request(rid=0, prompt=prompts[0], max_new=8,
                                    path=0,
                                    priority=PRIO_PREEMPTIBLE)])[0]

    eng = _eng(cfg, two_paths, cache_len=32, slots_per_path=1)
    trace = [
        Request(rid=0, prompt=prompts[0], max_new=8, path=0,
                priority=PRIO_PREEMPTIBLE, arrival=0.0),
        # arrives mid-decode of rid 0 (simulated clock, 1ms per tick)
        Request(rid=1, prompt=prompts[1], max_new=3, path=0,
                priority=PRIO_HIGH, arrival=0.003),
    ]
    fins = {f.rid: f for f in eng.serve_trace(trace)}
    assert len(fins) == 2
    assert fins[0].preemptions >= 1
    assert eng.scheduler.stats.preemptions >= 1
    # the high request did not wait for the preemptible to finish
    assert fins[1].finished_at < fins[0].finished_at
    np.testing.assert_array_equal(fins[0].tokens, ref.tokens)


def test_preemption_disabled_high_waits(cfg, two_paths):
    prompts = _prompts(cfg, [8, 8], seed=71)
    eng = _eng(cfg, two_paths, cache_len=32, slots_per_path=1,
               preemption=False)
    trace = [
        Request(rid=0, prompt=prompts[0], max_new=8, path=0,
                priority=PRIO_PREEMPTIBLE, arrival=0.0),
        Request(rid=1, prompt=prompts[1], max_new=3, path=0,
                priority=PRIO_HIGH, arrival=0.003),
    ]
    fins = {f.rid: f for f in eng.serve_trace(trace)}
    assert fins[0].preemptions == 0
    assert eng.scheduler.stats.preemptions == 0
    assert fins[1].admitted_at >= fins[0].finished_at


# ---------------------------------------------------------------------
# cross-request prefix cache
# ---------------------------------------------------------------------

def test_prefix_cache_exact_and_extension_identity(cfg, two_paths):
    """Exact repeats and shared-prefix extensions served from the cache
    produce bit-identical greedy tokens to a cold engine, and the
    hit/extension counters record the reuse."""
    p16 = _prompts(cfg, [16], seed=80)[0]
    longer = np.concatenate([p16, _prompts(cfg, [4], seed=81)[0]])
    cold = _eng(cfg, two_paths, cache_len=48, slots_per_path=2)
    ref = {f.rid: f for f in cold.serve_trace([
        Request(rid=0, prompt=p16, max_new=6, path=0),
        Request(rid=1, prompt=longer, max_new=6, path=0)])}

    warm = _eng(cfg, two_paths, cache_len=48, slots_per_path=2,
                prefix_cache=8)
    first = warm.serve_trace([Request(rid=0, prompt=p16, max_new=6,
                                      path=0)])
    np.testing.assert_array_equal(first[0].tokens, ref[0].tokens)
    assert warm.prefix_cache.misses == 1
    # exact repeat: stored row + logits, no new prefill
    again = warm.serve_trace([Request(rid=2, prompt=p16, max_new=6,
                                      path=0)])
    np.testing.assert_array_equal(again[0].tokens, ref[0].tokens)
    assert warm.prefix_cache.hits == 1
    # shared prefix, longer prompt: replay only the 4-token tail
    ext = warm.serve_trace([Request(rid=3, prompt=longer, max_new=6,
                                    path=0)])
    np.testing.assert_array_equal(ext[0].tokens, ref[1].tokens)
    assert warm.prefix_cache.extensions == 1


def test_prefix_cache_invalidated_on_install(cfg, two_paths):
    eng = _eng(cfg, two_paths, cache_len=48, slots_per_path=2,
               prefix_cache=8)
    p = _prompts(cfg, [16], seed=82)[0]
    eng.serve_trace([Request(rid=0, prompt=p, max_new=4, path=0)])
    assert len(eng.prefix_cache) == 1
    eng._install(eng._version + 1, list(eng.paths))
    assert len(eng.prefix_cache) == 0


# ---------------------------------------------------------------------
# TTFT + backpressure accounting
# ---------------------------------------------------------------------

def test_ttft_measured_from_arrival():
    """Regression: ttft anchors at trace arrival (queue wait included),
    falling back to admission only when no arrival was stamped."""
    f = FinishedRequest(rid=0, tokens=np.zeros(1, np.int32), path=0,
                        switches=0, arrival=1.0, admitted_at=5.0,
                        finished_at=7.0, first_token_at=6.0)
    assert f.ttft == pytest.approx(5.0)
    g = FinishedRequest(rid=1, tokens=np.zeros(1, np.int32), path=0,
                        switches=0, arrival=0.0, admitted_at=5.0,
                        finished_at=7.0, first_token_at=6.0)
    assert g.ttft == pytest.approx(1.0)


def test_ttft_includes_queue_wait_in_backlog(cfg, two_paths):
    """With one slot and simultaneous arrivals, later-served requests
    must report strictly larger TTFT (p95 > p50 over the backlog) —
    the bug was measuring from admission, which hid the queue."""
    prompts = _prompts(cfg, [8] * 4, seed=90)
    eng = _eng(cfg, two_paths, cache_len=32, slots_per_path=1)
    # near-simultaneous *traced* arrivals (arrival > 0 anchors TTFT at
    # the trace clock; 0.0 would fall back to the admission anchor)
    fins = eng.serve_trace([Request(rid=i, prompt=prompts[i], max_new=4,
                                    path=0, arrival=1e-6)
                            for i in range(4)])
    tt = sorted(f.ttft for f in fins)
    assert all(t >= 0 for t in tt)
    assert np.percentile(tt, 95) > np.percentile(tt, 50)
    for f in fins:   # first token can never precede admission work
        assert f.ttft >= (f.admitted_at - f.arrival)
    # per-path starvation was recorded for the contended island
    assert eng.scheduler.stats.backpressure_ticks > 0
    assert eng.scheduler.stats.starved_by_path.get(0, 0) > 0


def test_poisson_trace_tiles_short_corpus_docs():
    """A corpus doc shorter than its drawn bucket is tiled, not
    truncated: every emitted prompt hits exactly its bucket length."""
    from repro.data import SyntheticCorpus
    corpus = SyntheticCorpus(vocab_size=64, num_domains=2, seq_len=8,
                             seed=0)
    trace = poisson_trace(16, rate=50.0, prompt_lens=(16, 24),
                          max_new=4, vocab_size=64, seed=3,
                          corpus=corpus,
                          priorities=((PRIO_HIGH, PRIO_PREEMPTIBLE),
                                      (0.5, 0.5)))
    assert {len(r.prompt) for r in trace} <= {16, 24}
    for r in trace:
        np.testing.assert_array_equal(r.prompt[:8], r.prompt[8:16])
    assert {r.priority for r in trace} <= {PRIO_HIGH, PRIO_PREEMPTIBLE}


# ---------------------------------------------------------------------
# fleet front door
# ---------------------------------------------------------------------

@pytest.fixture()
def fleet_plane(tiny_cfg, tiny_base, tmp_path):
    """A promoted 4-path deployment registry (levels (2,2), seed-0
    base) — what fleet members rendezvous on."""
    from repro.deploy import DeploymentRegistry
    base, _ = tiny_base
    dcfg = DiPaCoConfig(levels=(2, 2))
    reg = DeploymentRegistry(tiny_cfg, dcfg, str(tmp_path / "deploy"),
                             key=jax.random.PRNGKey(0), base_params=base)
    m1 = reg.register(note="v1")
    reg.promote(m1.version)
    return dict(cfg=tiny_cfg, dcfg=dcfg, base=base, reg=reg,
                tmp=tmp_path, m1=m1)


def _mint_v2(plane):
    """Register a second version from perturbed module payloads."""
    from repro.core.module_store import ModuleStore
    from repro.core.partition import make_partition
    from repro.infra import CheckpointDB
    cfg, dcfg, reg = plane["cfg"], plane["dcfg"], plane["reg"]
    _, axes = api.init_model(jax.random.PRNGKey(0), cfg)
    bumped = jax.tree_util.tree_map(lambda x: x * 1.01, plane["base"])
    store = ModuleStore(bumped, axes,
                        make_partition(dcfg, cfg.pattern_repeats))
    db = CheckpointDB(str(plane["tmp"] / "db"))
    rows = {}
    for mid in reg.module_ids:
        tree = store.shared if mid == (-1, -1) \
            else store.module_params(*mid)
        rows[mid] = db.write({"params": tree}, path_id=0, phase=1,
                             step=1, kind="module", level=mid[0],
                             expert=mid[1])
    return reg.register(rows, note="v2")


def _fleet_trace(cfg, n=8, seed=4, max_new=4):
    return poisson_trace(n, rate=200.0, prompt_lens=(12, 16),
                         max_new=max_new, vocab_size=cfg.vocab_size,
                         seed=seed,
                         priorities=((PRIO_HIGH, PRIO_STANDARD,
                                      PRIO_PREEMPTIBLE),
                                     (0.25, 0.5, 0.25)))


def test_fleet_requires_registry(tiny_cfg):
    with pytest.raises(ValueError, match="registry"):
        ServingFleet(tiny_cfg, size=2, options=EngineOptions())


def test_process_fleet_refuses_off_cpu(tiny_cfg, monkeypatch):
    """A parent on an accelerator holds its devices: spawning members
    that each import JAX would make them contend for the same chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="contend"):
        ServingFleet(tiny_cfg, size=2, options=EngineOptions(),
                     backend="process")


def test_rendezvous_affinity_is_consistent(fleet_plane):
    """Scaling a path's replicas up appends the next-ranked member and
    scaling down drops the tail — existing assignments never move."""
    opts = EngineOptions(registry=fleet_plane["reg"], cache_len=24,
                         slots_per_path=2)
    fleet = ServingFleet(fleet_plane["cfg"], size=3, options=opts,
                         backend="inproc")
    for p in range(fleet.num_paths):
        fleet.replicas[p] = 1
        one = fleet.members(p)
        fleet.replicas[p] = 2
        two = fleet.members(p)
        fleet.replicas[p] = 3
        three = fleet.members(p)
        assert two[0] == one[0] and three[:2] == two
        assert len(set(three)) == 3
        fleet.replicas[p] = 1


def test_fleet_autoscale_fans_out_and_decays(fleet_plane):
    opts = EngineOptions(registry=fleet_plane["reg"], cache_len=24,
                         slots_per_path=2)
    fleet = ServingFleet(fleet_plane["cfg"], size=3, options=opts,
                         backend="inproc")
    # queue depth: 5 outstanding on path 0 at 2 slots/replica -> 3
    fleet._outstanding_by_path[0] = 5
    fleet.rebalance()
    assert fleet.replicas[0] == 3
    # burst passes -> decays back to one replica
    fleet._outstanding_by_path[0] = 0
    fleet.rebalance()
    assert fleet.replicas[0] == 1
    # backpressure signal alone also fans out; the cumulative counter
    # is delta-merged, so an unchanged count adds no new demand
    fleet.engines[0].scheduler.stats.starved_by_path[1] = 4
    fleet.rebalance()
    assert fleet.replicas[1] == 2
    fleet.rebalance()
    assert fleet.replicas[1] == 1


def test_fleet_inproc_token_identity_and_spread(fleet_plane):
    """The fleet's greedy tokens equal a single engine's on the same
    pre-routed trace, and with 4 paths over 2 members the rendezvous
    assignment gives both members traffic."""
    cfg, reg = fleet_plane["cfg"], fleet_plane["reg"]
    opts = EngineOptions(registry=reg, cache_len=24, slots_per_path=2)
    single = ContinuousBatchingEngine(cfg, options=opts)
    fleet = ServingFleet(cfg, size=2, options=opts, backend="inproc")
    ref_trace = _fleet_trace(cfg)
    for r in ref_trace:   # same assignment the front door will compute
        r.path = fleet.route_fn(r.prompt)
    ref = {f.rid: f for f in single.serve_trace(ref_trace)}
    fins = fleet.serve_trace(_fleet_trace(cfg))
    assert len(fins) == len(ref)
    for f in fins:
        np.testing.assert_array_equal(f.tokens, ref[f.rid].tokens)
    assert fleet.stats["routed"] == len(fins)
    assert all(e.ticks > 0 for e in fleet.engines)
    by_engine = [s["ticks"] for s in fleet.member_stats()]
    assert all(t > 0 for t in by_engine)


def test_fleet_promote_hot_swaps_every_member_inproc(fleet_plane):
    cfg, reg = fleet_plane["cfg"], fleet_plane["reg"]
    opts = EngineOptions(registry=reg, cache_len=24, slots_per_path=2)
    fleet = ServingFleet(cfg, size=2, options=opts, backend="inproc")
    fleet.serve_trace(_fleet_trace(cfg, n=4, seed=5))
    v1 = fleet_plane["m1"].version
    assert fleet.versions() == [v1, v1]
    m2 = _mint_v2(fleet_plane)
    reg.promote(m2.version)
    fleet.wait_version(m2.version, timeout=60.0)
    assert fleet.versions() == [m2.version, m2.version]
    # post-swap requests are served on (and stamped with) the new version
    fins = fleet.serve_trace(_fleet_trace(cfg, n=4, seed=6))
    assert {f.version for f in fins} == {m2.version}


@pytest.mark.slow
def test_fleet_process_backend_end_to_end(fleet_plane):
    """Two real engine processes: spawn, serve a priority-mixed trace
    with token identity against an inproc member, hot-swap the whole
    fleet off one promote, close cleanly."""
    cfg, reg = fleet_plane["cfg"], fleet_plane["reg"]
    opts = EngineOptions(registry=reg, cache_len=24, slots_per_path=2,
                         prefix_cache=8)
    single = ContinuousBatchingEngine(cfg, options=opts)
    ref_trace = _fleet_trace(cfg, n=6, max_new=3)
    with ServingFleet(cfg, size=2, options=opts, backend="process",
                      seed=0) as fleet:
        for r in ref_trace:
            r.path = fleet.route_fn(r.prompt)
        ref = {f.rid: f for f in single.serve_trace(ref_trace)}
        fins = fleet.serve_trace(_fleet_trace(cfg, n=6, max_new=3))
        assert len(fins) == 6
        for f in fins:
            np.testing.assert_array_equal(f.tokens, ref[f.rid].tokens)
        # latency stamps were rebased into the front door's timebase
        assert all(f.finished_at >= f.arrival >= 0.0 for f in fins)
        m2 = _mint_v2(fleet_plane)
        reg.promote(m2.version)
        fleet.wait_version(m2.version, timeout=300.0)
        assert fleet.versions() == [m2.version, m2.version]
