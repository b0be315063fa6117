"""Property-based SlotArena / StackedSlotArenas invariants.

Random admit / free / migrate / multi-token-write sequences (hypothesis)
against a host-side model: slots are never aliased, the free
list and the active flags stay consistent, ``cache_index`` (the
per-slot ``positions`` vector the decode masks are built from) is never
corrupted, and every active slot's cache rows hold exactly the bytes
written for *its* request — no write ever bleeds into another slot or
island.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from hypothesis import given, settings, strategies as st

from repro.models import api
from repro.serving import SlotArena
from repro.serving.cache import StackedSlotArenas

CACHE_LEN = 16


@functools.lru_cache(maxsize=1)
def _cfg():
    from repro.configs import get_smoke_config
    return get_smoke_config("dipaco-150m").replace(route_prefix_len=8)


def _payload(value: float, rows: int = 1):
    """A batch-``rows`` sub-cache pytree filled with a request-unique
    constant (float leaves; int leaves offset by the value)."""
    sub = api.init_serve_cache(_cfg(), rows, CACHE_LEN)
    return jax.tree_util.tree_map(
        lambda x: (jnp.full(x.shape, value, x.dtype)
                   if jnp.issubdtype(x.dtype, jnp.floating)
                   else x + jnp.asarray(value, x.dtype)), sub)


def _check_row(arena_cache, slot: int, value: float):
    """Every leaf of slot ``slot``'s row equals the request's fill."""
    for leaf in jax.tree_util.tree_leaves(arena_cache):
        row = np.asarray(leaf[:, slot])
        want = np.full(row.shape, value, row.dtype)
        np.testing.assert_array_equal(row, want)


def _model_invariants(arena, model: dict):
    active = {s for s, _ in enumerate(arena.active) if arena.active[s]}
    assert active == set(model)                       # no aliasing/leaks
    assert arena.num_free == arena.num_slots - len(model)
    for s in range(arena.num_slots):
        want = model[s][1] if s in model else 0       # parked at 0 if free
        assert arena.positions[s] == want
    idx = arena.decode_indices()
    assert idx.shape == (arena.num_slots,)
    np.testing.assert_array_equal(
        idx, [model[s][1] if s in model else 0
              for s in range(arena.num_slots)])


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000), num_slots=st.integers(1, 3))
def test_slot_arena_random_op_sequences(seed, num_slots):
    """admit / free / multi-token-write sequences keep the arena's
    bookkeeping and cache contents consistent with a host-side model."""
    rng = np.random.default_rng(seed)
    arena = SlotArena(_cfg(), num_slots=num_slots, cache_len=CACHE_LEN)
    model: dict = {}                                  # slot -> (value, pos)
    next_value = 1.0
    for _ in range(12):
        op = rng.choice(["admit", "free", "write"])
        if op == "admit":
            slot = arena.try_alloc()
            if slot is None:
                assert len(model) == num_slots        # only when truly full
            else:
                assert slot not in model              # never alias a live slot
                pos = int(rng.integers(1, CACHE_LEN))
                arena.write_slots(_payload(next_value), [slot], [pos])
                model[slot] = (next_value, pos)
                next_value += 1.0
        elif op == "free" and model:
            slot = int(rng.choice(sorted(model)))
            arena.free(slot)
            del model[slot]
            assert arena.positions[slot] == 0         # parked, maskable
        elif op == "write" and model:
            # multi-token write: advance the slot by k tokens
            slot = int(rng.choice(sorted(model)))
            value, pos = model[slot]
            pos = min(pos + int(rng.integers(1, 4)), CACHE_LEN)
            arena.write_slots(_payload(value), [slot], [pos])
            model[slot] = (value, pos)
        _model_invariants(arena, model)
    for slot, (value, _) in model.items():
        _check_row(arena.cache, slot, value)


@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_stacked_arenas_random_ops_and_migration(seed):
    """The same invariants across stacked islands, plus §2.4.3-style
    migrations (free on the source island, admit + rewrite on the
    target): no operation may corrupt another island's slots or
    ``cache_index`` rows."""
    rng = np.random.default_rng(seed)
    P, num_slots = 3, 2
    stacked = StackedSlotArenas(_cfg(), num_paths=P, num_slots=num_slots,
                                cache_len=CACHE_LEN)
    model: dict = {}                                  # (p, slot) -> (v, pos)
    next_value = 1.0
    for _ in range(14):
        op = rng.choice(["admit", "free", "write", "migrate"])
        p = int(rng.integers(0, P))
        view = stacked.views[p]
        if op == "admit":
            slot = view.try_alloc()
            if slot is None:
                assert sum(1 for (q, _s) in model if q == p) == num_slots
            else:
                assert (p, slot) not in model
                pos = int(rng.integers(1, CACHE_LEN))
                view.write_slots(_payload(next_value), [slot], [pos])
                model[(p, slot)] = (next_value, pos)
                next_value += 1.0
        elif op == "free":
            mine = sorted(s for (q, s) in model if q == p)
            if mine:
                slot = int(rng.choice(mine))
                view.free(slot)
                del model[(p, slot)]
        elif op == "write":
            mine = sorted(s for (q, s) in model if q == p)
            if mine:
                slot = int(rng.choice(mine))
                value, pos = model[(p, slot)]
                pos = min(pos + int(rng.integers(1, 4)), CACHE_LEN)
                view.write_slots(_payload(value), [slot], [pos])
                model[(p, slot)] = (value, pos)
        elif op == "migrate" and model:
            # move one live request to another island (re-prefill there)
            src = sorted(model)[int(rng.integers(0, len(model)))]
            tgt_p = int(rng.integers(0, P))
            tgt_slot = stacked.views[tgt_p].try_alloc()
            if tgt_slot is None:
                continue                              # deferred migration
            value, pos = model.pop(src)
            stacked.views[src[0]].free(src[1])
            stacked.views[tgt_p].write_slots(_payload(value), [tgt_slot],
                                             [pos])
            model[(tgt_p, tgt_slot)] = (value, pos)
        # per-island invariants through the per-path facade views
        for q in range(P):
            sub = {s: vp for (qq, s), vp in model.items() if qq == q}
            _model_invariants(stacked.views[q], sub)
    # cache contents: every live slot holds its own request's bytes
    for (p, slot), (value, _) in model.items():
        _check_row(stacked.views[p].cache, slot, value)


def test_stacked_views_share_bookkeeping_arrays():
    """The facade's positions/active are *views*: mutations through the
    stacked arena and through the view observe each other (a copy here
    would desynchronize decode masks from admissions)."""
    stacked = StackedSlotArenas(_cfg(), num_paths=2, num_slots=2,
                                cache_len=CACHE_LEN)
    view = stacked.views[1]
    slot = stacked.alloc(1)
    assert view.active[slot]
    stacked.write_slots(1, _payload(3.0), [slot], [7])
    assert view.positions[slot] == 7
    view.free(slot)
    assert not stacked.active[1, slot]
    assert stacked.positions[1, slot] == 0
