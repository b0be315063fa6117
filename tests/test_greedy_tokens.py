"""The engine's served tokens against a plain host loop of ``np.argmax``.

The engine's programs pick each row's greedy next token on the device
and fetch only the ids; here a host loop that never touches the engine
(exact-length ``api.prefill``, then one ``api.serve_step`` per token,
``np.argmax`` over the float32 logits on the host) must produce the same
tokens, for a tiny attention model and a tiny MLA + MoE model.  Each
scenario drives one of the engine's writers of ``next_token``: bucketed
admission with the dense and the sparse stacked tick, exact admission
with the per-island tick, a prefix-cache exact hit and extension, a
preemption re-admit, and a §2.4.3 re-route migration.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import api
from repro.serving import (PRIO_HIGH, PRIO_PREEMPTIBLE,
                           ContinuousBatchingEngine, EngineOptions, Request)

CACHE = 64
ARCHS = ("dipaco-150m", "moonlight-16b-a3b")


class _Model:
    """One architecture's config, three paths' weights and the host
    loop's jitted model steps."""

    def __init__(self, arch):
        self.cfg = get_smoke_config(arch).replace(route_prefix_len=8)
        key = jax.random.PRNGKey(21)
        self.paths = [api.init_model(jax.random.fold_in(key, p), self.cfg)[0]
                      for p in range(3)]
        cfg = self.cfg
        self._prefill = jax.jit(lambda w, t: api.prefill(
            w, cfg, {"tokens": t}, CACHE))
        self._step = jax.jit(lambda w, t, c, i: api.serve_step(
            w, cfg, {"tokens": t}, c, i))

    def greedy(self, prompt, max_new, path=0, moves=None):
        """Greedy tokens of ``prompt`` on ``path``.  ``moves`` maps a
        count of emitted tokens to the path the request moves to then:
        the running text is prefilled there again, as a migration
        does."""
        moves = moves or {}
        toks = [int(t) for t in prompt]
        logits, cache = self._prefill(self.paths[path],
                                      jnp.asarray([toks], jnp.int32))
        logits = np.asarray(logits[0, -1])
        for emitted in range(1, max_new + 1):
            toks.append(int(np.argmax(logits)))
            if emitted == max_new:
                break
            if moves.get(emitted, path) != path:
                path = moves[emitted]
                logits, cache = self._prefill(
                    self.paths[path], jnp.asarray([toks], jnp.int32))
                logits = np.asarray(logits[0, -1])
                continue
            logits, cache = self._step(
                self.paths[path], jnp.asarray([[toks[-1]]], jnp.int32),
                cache, jnp.int32(len(toks) - 1))
            logits = np.asarray(logits[0, 0])
        return np.asarray(toks, np.int32)


@pytest.fixture(scope="module", params=ARCHS)
def model(request):
    return _Model(request.param)


def _prompt(cfg, n, seed):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, n,
                                                dtype=np.int32)


def _run(eng, waves):
    """Serve ``waves`` (lists of requests, one wave submitted per tick
    until none is left) to completion: rid -> FinishedRequest."""
    fins = {}
    for wave in waves:
        for r in wave:
            eng.submit(r)
        fins.update((f.rid, f) for f in eng.step())
    while not eng.idle:
        fins.update((f.rid, f) for f in eng.step())
    return fins


def _check(model, fins, reqs, **kw):
    assert sorted(fins) == sorted(r.rid for r in reqs)
    for r in reqs:
        np.testing.assert_array_equal(
            fins[r.rid].tokens,
            model.greedy(r.prompt, r.max_new, path=r.path or 0, **kw))


def _engine(model, paths=3, **opts):
    return ContinuousBatchingEngine(
        model.cfg, model.paths[:paths],
        options=EngineOptions(cache_len=CACHE, **opts))


def test_bucketed_admission_dense_and_sparse_ticks(model):
    """Bucketed admission groups of one and two rows; ticks where two of
    three islands decode (dense) and where one does (sparse)."""
    cfg = model.cfg
    reqs = [Request(rid=i, prompt=_prompt(cfg, n, 40 + i), max_new=m,
                    path=p)
            for i, (n, m, p) in enumerate([(9, 7, 0), (14, 3, 0),
                                           (20, 6, 1), (30, 2, 0),
                                           (12, 5, 1)])]
    eng = _engine(model, slots_per_path=2, prefill_buckets=(16, 32))
    assert eng.stacked and eng.bucketed
    calls = {"_decode_stacked": 0, "_decode_island": 0}
    for name in calls:
        def counted(*a, _program=getattr(eng, name), _name=name):
            calls[_name] += 1
            return _program(*a)
        setattr(eng, name, counted)
    _check(model, _run(eng, [reqs[:3], reqs[3:]]), reqs)
    assert calls["_decode_stacked"] > 0 and calls["_decode_island"] > 0


def test_exact_admission_per_island_tick(model):
    """``stacked=False``, ``bucketed_prefill=False``: each prompt
    prefills at its own length, each island decodes on its own."""
    cfg = model.cfg
    reqs = [Request(rid=i, prompt=_prompt(cfg, n, 50 + i), max_new=4,
                    path=i % 2) for i, n in enumerate([9, 13, 11])]
    eng = _engine(model, paths=2, slots_per_path=2, stacked=False,
                  bucketed_prefill=False)
    assert not eng.stacked and not eng.bucketed
    _check(model, _run(eng, [reqs]), reqs)


def test_prefix_cache_exact_hit_and_extension(model):
    """A repeated prompt takes the stored row and its next token; a
    prompt that extends a cached one replays only its tail."""
    cfg = model.cfg
    base = _prompt(cfg, 12, 60)
    reqs = [Request(rid=0, prompt=base, max_new=5),
            Request(rid=1, prompt=base.copy(), max_new=5),
            Request(rid=2, prompt=np.concatenate([base, _prompt(cfg, 5, 61)]),
                    max_new=4)]
    eng = _engine(model, paths=1, slots_per_path=3, prefix_cache=8,
                  prefill_buckets=(16, 32))
    fins = _run(eng, [reqs[:1], reqs[1:]])
    assert eng.prefix_cache.hits == 1 and eng.prefix_cache.extensions == 1
    _check(model, fins, reqs)


def test_preemption_readmit(model):
    """A high-priority request evicts a preemptible one from the only
    slot; the evictee re-prefills its running text on re-admission and
    continues as if it had never stopped."""
    cfg = model.cfg
    low = Request(rid=0, prompt=_prompt(cfg, 10, 70), max_new=8,
                  priority=PRIO_PREEMPTIBLE)
    high = Request(rid=1, prompt=_prompt(cfg, 15, 71), max_new=3,
                   priority=PRIO_HIGH)
    eng = _engine(model, paths=1, slots_per_path=1, prefill_buckets=(16,))
    fins = _run(eng, [[low], [], [], [high]])
    assert fins[0].preemptions == 1 and fins[1].preemptions == 0
    _check(model, fins, [low, high])


class _Alternate:
    """Admission -> path 0; the k-th re-route check sends the request
    to path k % 2."""

    def __init__(self):
        self.calls = 0

    def assign(self, z):
        self.calls += 1
        return np.full(z.shape[0], (self.calls - 1) % 2, np.int32)


def test_reroute_migration(model):
    """Re-route checks every 3 tokens move the request to the other path
    and back; each move re-prefills the running text there."""
    cfg = model.cfg
    req = Request(rid=0, prompt=_prompt(cfg, 12, 80), max_new=10)
    eng = _engine(model, paths=2, slots_per_path=2, router=_Alternate(),
                  feat_params=model.paths[0], reroute_every=3,
                  prefill_buckets=(16, 32))
    fins = _run(eng, [[req]])
    assert fins[0].switches == 3
    _check(model, fins, [req], moves={3: 1, 6: 0, 9: 1})
