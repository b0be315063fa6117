"""Continuous-batching serving subsystem: slot arena bookkeeping,
admission backpressure, one-shot-vs-continuous greedy equivalence, and
§2.4.3 re-route cache migration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import api
from repro.serving import (ContinuousBatchingEngine, EngineOptions,
                           PathServingEngine, Request, SlotArena,
                           SlotExhausted, poisson_trace)


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
                                          (l,), 0, cfg.vocab_size), np.int32)
            for i, l in enumerate(lens)]


# ---------------------------------------------------------------------------
# Slot arena
# ---------------------------------------------------------------------------
def test_slot_arena_alloc_free_exhaustion(cfg):
    arena = SlotArena(cfg, num_slots=3, cache_len=32)
    slots = [arena.alloc() for _ in range(3)]
    assert sorted(slots) == [0, 1, 2]
    assert arena.num_free == 0
    assert arena.try_alloc() is None
    with pytest.raises(SlotExhausted):
        arena.alloc()
    arena.free(slots[1])
    assert arena.num_free == 1
    assert arena.alloc() == slots[1]
    arena.free(slots[0])
    with pytest.raises(ValueError):  # double-free
        arena.free(slots[0])


def test_slot_arena_write_roundtrip(cfg):
    arena = SlotArena(cfg, num_slots=4, cache_len=16)
    sub = api.init_serve_cache(cfg, 2, 16)
    sub = jax.tree_util.tree_map(
        lambda x: (jnp.arange(x.size, dtype=jnp.float32)
                   .reshape(x.shape).astype(x.dtype))
        if jnp.issubdtype(x.dtype, jnp.floating) else x + 1, sub)
    arena.write_slots(sub, [3, 1], [5, 7])
    assert arena.positions[3] == 5 and arena.positions[1] == 7
    flat_a = jax.tree_util.tree_leaves(arena.cache)
    flat_s = jax.tree_util.tree_leaves(sub)
    for a, s in zip(flat_a, flat_s):
        np.testing.assert_array_equal(np.asarray(a[:, 3]), np.asarray(s[:, 0]))
        np.testing.assert_array_equal(np.asarray(a[:, 1]), np.asarray(s[:, 1]))
        # untouched rows stay zero
        assert not np.asarray(a[:, 0]).any()


# ---------------------------------------------------------------------------
# Continuous batching vs one-shot engine
# ---------------------------------------------------------------------------
def test_admission_backpressure_order(cfg, two_paths):
    """With a single slot, requests are served FIFO, one at a time."""
    prompts = _prompts(cfg, [8, 8, 8], seed=40)
    eng = ContinuousBatchingEngine(cfg, two_paths, options=EngineOptions(
        cache_len=32, slots_per_path=1))
    trace = [Request(rid=i, prompt=prompts[i], max_new=4) for i in range(3)]
    fins = eng.serve_trace(trace)
    assert [f.rid for f in fins] == [0, 1, 2]
    assert eng.scheduler.stats.backpressure_ticks > 0


def test_submit_validates_capacity(cfg, two_paths):
    eng = ContinuousBatchingEngine(cfg, two_paths, options=EngineOptions(
        cache_len=16, slots_per_path=1))
    with pytest.raises(ValueError):
        eng.submit(Request(rid=0, prompt=np.zeros(10, np.int32), max_new=8))


# ---------------------------------------------------------------------------
# Cross-engine greedy token-identity matrix
# ---------------------------------------------------------------------------
_EQ_LENS = [16, 12, 8, 16, 12]

# every decode configuration the serving plane can run, as one matrix:
# (attn_impl, stacked islands, bucketed prefill, int8 KV cache).  Each
# row is checked against its *reference group*: fp32 rows against the
# one-shot engine's greedy tokens, int8-KV rows against the first
# int8-KV engine (quantized cache numerics differ from fp32, so the
# groups are only comparable within themselves).
_ENGINE_MATRIX = [
    ("jnp-looped", "chunked", False, True, False),
    ("jnp-stacked", "chunked", True, True, False),
    ("pallas-looped", "pallas", False, True, False),
    ("pallas-stacked", "pallas", True, True, False),
    ("batch1-prefill", "chunked", False, False, False),
    ("jnp-looped-int8kv", "chunked", False, True, True),
    ("jnp-stacked-int8kv", "chunked", True, True, True),
    ("pallas-looped-int8kv", "pallas", False, True, True),
    ("pallas-stacked-int8kv", "pallas", True, True, True),
]


def _serve_matrix_engine(cfg, two_paths, prompts, *, attn_impl, stacked,
                         bucketed, kv_quant, slots=2):
    ecfg = cfg.replace(attn_impl=attn_impl, kv_quant=kv_quant,
                       pallas_interpret=True)
    eng = ContinuousBatchingEngine(ecfg, two_paths, options=EngineOptions(
        cache_len=48, slots_per_path=slots, stacked=stacked,
        bucketed_prefill=bucketed))
    trace = [Request(rid=i, prompt=prompts[i], max_new=6)
             for i in range(len(_EQ_LENS))]
    fins = {f.rid: f for f in eng.serve_trace(trace)}
    return eng, fins


@pytest.fixture(scope="module")
def matrix_refs(cfg, two_paths):
    """Per-group reference greedy tokens for the engine matrix.

    fp32 group: the one-shot engine (exact-length batched prefill +
    full-arena jnp decode).  int8-KV group: the plain jnp looped
    continuous engine with a quantized cache.  NOTE the dtype-
    equivalence gotcha: greedy token identity across engines only holds
    because the smoke configs run fp32 end to end — under bf16 the
    logit perturbations from reordered reductions are large enough to
    flip argmax ties, so these checks would have to become top-k
    agreement instead."""
    prompts = _prompts(cfg, _EQ_LENS, seed=33)
    old = PathServingEngine(cfg, two_paths,
                            options=EngineOptions(cache_len=48))
    fp32 = {}
    for ln in sorted(set(_EQ_LENS)):
        idx = [i for i, l in enumerate(_EQ_LENS) if l == ln]
        r = old.generate(np.stack([prompts[i] for i in idx]), max_new=6)
        for j, i in enumerate(idx):
            fp32[i] = r.tokens[j]
    _, fins = _serve_matrix_engine(cfg, two_paths, prompts,
                                   attn_impl="chunked", stacked=False,
                                   bucketed=True, kv_quant=True)
    int8 = {i: fins[i].tokens for i in fins}
    return prompts, {"fp32": fp32, "int8": int8}


@pytest.mark.parametrize(
    "name,attn_impl,stacked,bucketed,kv_quant", _ENGINE_MATRIX,
    ids=[row[0] for row in _ENGINE_MATRIX])
def test_engine_matrix_greedy_token_identity(cfg, two_paths, matrix_refs,
                                             name, attn_impl, stacked,
                                             bucketed, kv_quant):
    """One parametrized cross-engine matrix replacing the former
    per-engine greedy checks (continuous-vs-oneshot, four decode
    configs, bucketed-vs-batch1 prefill, int8-KV configs): every
    serving configuration must emit identical greedy tokens to its
    reference group, under slot contention, and hand every slot back.
    fp32-only — see ``matrix_refs`` for the dtype-equivalence gotcha."""
    prompts, refs = matrix_refs
    ref = refs["int8" if kv_quant else "fp32"]
    eng, fins = _serve_matrix_engine(
        cfg, two_paths, prompts, attn_impl=attn_impl, stacked=stacked,
        bucketed=bucketed, kv_quant=kv_quant)
    assert eng.stacked is stacked and eng.bucketed is bucketed
    assert len(fins) == len(_EQ_LENS)
    for i in range(len(_EQ_LENS)):
        np.testing.assert_array_equal(fins[i].tokens, ref[i])
    # 5 requests through 2x2 slots: contention must have exerted
    # backpressure, and every slot returned to the pool
    assert eng.scheduler.stats.backpressure_ticks > 0
    assert eng.scheduler.stats.completed == len(_EQ_LENS)
    assert all(a.num_free == 2 for a in eng.arenas)


def test_stacked_reroute_migration(cfg, two_paths):
    """§2.4.3 migration lands in the stacked arena of the target island
    and keeps decoding there (stacked + bucketed engine)."""
    prompt = _prompts(cfg, [16], seed=5)[0]
    old = PathServingEngine(cfg, two_paths, options=EngineOptions(
        router=ScriptedRouter(), feat_params=two_paths[0], cache_len=64))
    ref = old.generate(prompt[None], max_new=12, reroute_every=4)
    eng = ContinuousBatchingEngine(cfg, two_paths, options=EngineOptions(
        router=ScriptedRouter(), feat_params=two_paths[0],
        cache_len=64, slots_per_path=2, reroute_every=4, stacked=True))
    fins = eng.serve_trace([Request(rid=0, prompt=prompt, max_new=12)])
    np.testing.assert_array_equal(fins[0].tokens, ref.tokens[0])
    assert fins[0].switches == ref.switches
    assert all(a.num_free == 2 for a in eng.arenas)


def test_heterogeneous_paths_fall_back_to_loop(cfg, two_paths):
    """Paths with different architectures cannot stack: auto-detect
    falls back to the per-island loop; forcing stacked raises."""
    cfg_small = cfg.replace(d_ff=256)
    p_other, _ = api.init_model(jax.random.PRNGKey(9), cfg_small)
    mixed = [two_paths[0], p_other]
    eng = ContinuousBatchingEngine(cfg, mixed, options=EngineOptions(
        cache_len=32, slots_per_path=2))
    assert not eng.stacked
    with pytest.raises(ValueError, match="homogeneous"):
        ContinuousBatchingEngine(cfg, mixed, options=EngineOptions(
            cache_len=32, slots_per_path=2, stacked=True))
    with pytest.raises(ValueError, match="attention-only"):
        from repro.configs import get_smoke_config
        mcfg = get_smoke_config("mamba2-1.3b")
        mp, _ = api.init_model(jax.random.PRNGKey(10), mcfg)
        ContinuousBatchingEngine(mcfg, [mp], options=EngineOptions(
            cache_len=32, slots_per_path=2, bucketed_prefill=True))


def test_mamba_paths_disable_bucketing_automatically():
    """SSM paths auto-disable bucketed prefill (pad tokens would pollute
    the recurrent state) but still serve correctly."""
    from repro.configs import get_smoke_config
    mcfg = get_smoke_config("mamba2-1.3b").replace(route_prefix_len=8)
    mp, _ = api.init_model(jax.random.PRNGKey(11), mcfg)
    eng = ContinuousBatchingEngine(mcfg, [mp], options=EngineOptions(
        cache_len=32, slots_per_path=2))
    assert not eng.bucketed and eng.stacked
    prompts = _prompts(mcfg, [8, 10], seed=50)
    fins = eng.serve_trace([Request(rid=i, prompt=prompts[i], max_new=4)
                            for i in range(2)])
    assert len(fins) == 2
    assert all(len(f.tokens) == len(prompts[f.rid]) + 4 for f in fins)


# ---------------------------------------------------------------------------
# §2.4.3 re-route cache migration
# ---------------------------------------------------------------------------
class ScriptedRouter:
    """Admission -> path 0; re-route checks alternate between paths."""

    def __init__(self):
        self.calls = 0

    def assign(self, z):
        self.calls += 1
        if self.calls == 1:
            return np.zeros(z.shape[0], np.int32)
        return np.full(z.shape[0], self.calls % 2, np.int32)


def test_reroute_migration_matches_oneshot(cfg, two_paths):
    """Forced path switches: the migrated slot must reproduce the old
    engine's full re-prefill token-for-token."""
    prompt = _prompts(cfg, [16], seed=5)[0]
    old = PathServingEngine(cfg, two_paths, options=EngineOptions(
        router=ScriptedRouter(), feat_params=two_paths[0], cache_len=64))
    ref = old.generate(prompt[None], max_new=12, reroute_every=4)
    assert ref.switches > 0

    eng = ContinuousBatchingEngine(cfg, two_paths, options=EngineOptions(
        router=ScriptedRouter(), feat_params=two_paths[0],
        cache_len=64, slots_per_path=2, reroute_every=4))
    fins = eng.serve_trace([Request(rid=0, prompt=prompt, max_new=12)])
    assert len(fins) == 1
    np.testing.assert_array_equal(fins[0].tokens, ref.tokens[0])
    assert fins[0].switches == ref.switches
    assert fins[0].path == ref.paths[0]
    # source slots were evicted on every migration: all slots free again
    assert eng.arenas[0].num_free == 2 and eng.arenas[1].num_free == 2


def test_migration_deferred_when_target_full(cfg, two_paths):
    """A re-route to a full island is deferred, not dropped: the request
    keeps decoding on its current path."""
    class AlwaysOther:
        def assign(self, z):
            return np.ones(z.shape[0], np.int32) * 1

    class Admit0ThenOther(AlwaysOther):
        def __init__(self):
            self.calls = 0

        def assign(self, z):
            self.calls += 1
            if self.calls == 1:
                return np.zeros(z.shape[0], np.int32)
            return super().assign(z)

    eng = ContinuousBatchingEngine(cfg, two_paths, options=EngineOptions(
        router=Admit0ThenOther(), feat_params=two_paths[0], cache_len=64,
        slots_per_path=1, reroute_every=4))
    # occupy path 1's only slot so migration has nowhere to go
    eng.arenas[1].alloc()
    prompt = _prompts(cfg, [16], seed=6)[0]
    fins = eng.serve_trace([Request(rid=0, prompt=prompt, max_new=8)])
    assert len(fins) == 1
    assert fins[0].path == 0 and fins[0].switches == 0


# ---------------------------------------------------------------------------
# Incremental prefill API (the cache surface the engine is built on)
# ---------------------------------------------------------------------------
def test_prefill_matches_decode_replay(cfg):
    params, _ = api.init_model(jax.random.PRNGKey(2), cfg)
    toks = jnp.asarray(_prompts(cfg, [12], seed=20)[0][None])
    cache_r = api.init_serve_cache(cfg, 1, 24)
    lg_r = None
    for t in range(toks.shape[1]):
        lg_r, cache_r = api.serve_step(params, cfg,
                                       {"tokens": toks[:, t:t + 1]},
                                       cache_r, jnp.int32(t))
    lg_p, cache_p = api.prefill(params, cfg, {"tokens": toks}, 24)
    np.testing.assert_allclose(np.asarray(lg_p[:, -1]),
                               np.asarray(lg_r[:, 0]), atol=1e-4, rtol=1e-4)
    # decode continuation from both caches agrees (vector index on the
    # prefilled cache, scalar on the replayed one)
    nxt = jnp.argmax(lg_p[:, -1], -1)[:, None].astype(toks.dtype)
    s = toks.shape[1]
    lg1, _ = api.serve_step(params, cfg, {"tokens": nxt}, cache_r,
                            jnp.int32(s))
    lg2, _ = api.serve_step(params, cfg, {"tokens": nxt}, cache_p,
                            jnp.full((1,), s, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg1), np.asarray(lg2),
                               atol=1e-4, rtol=1e-4)


def test_poisson_trace_shape():
    trace = poisson_trace(32, rate=50.0, prompt_lens=[8, 12, 16],
                          max_new=4, vocab_size=64, seed=3)
    assert len(trace) == 32
    assert all(len(r.prompt) in (8, 12, 16) for r in trace)
    arr = [r.arrival for r in trace]
    assert arr == sorted(arr) and arr[0] > 0
