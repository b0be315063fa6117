"""Slot-pooled KV/SSM cache arena for continuous batching.

One :class:`SlotArena` per path island (paper §2.2/§2.6: paths are
instantiated and served independently).  The arena holds a single
layer-stacked decode-cache pytree (``api.init_serve_cache``: attention
k/v ``(reps, num_slots, KH, D, T)``, or for latent attention one
``(reps, num_slots, r + dr, T)`` buffer of latents and rotary keys,
tokens minor) whose row axis, axis 1, is the slot; a request occupies
one slot row from admission to completion.  Allocation and
free are O(1) host-side bookkeeping — cache buffers are written in
place (row scatter), never rebuilt per request.

Stale rows need no zeroing: the attention mask only admits ring entries
whose reconstructed absolute position is in ``[0, current position]``,
and a prefill overwrites positions ``0..S-1`` of its row, so a freshly
allocated slot can never attend a previous occupant's keys.

:class:`PrefixCache` adds cross-request reuse on top of the arenas:
prefill KV rows are remembered content-keyed by
``(path, version, prompt tokens)`` so a repeated prompt — or one whose
prefix another request already prefills — skips (part of) its prefill
forward.  Reuse is exact-by-construction for full-prompt hits (the
stored row and greedy next token came from an identical forward) and
greedy-token-identical for prefix extensions (single-token replay is
the same §2.4.3 re-prefill primitive the token-identity matrix pins
against one-forward prefill).
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import api
from repro.models.config import ModelConfig


class SlotExhausted(Exception):
    """Raised by :meth:`SlotArena.alloc` when no slot is free."""


# the arena buffers are donated: row writes update in place instead of
# copying the whole pool every admission
@functools.partial(jax.jit, donate_argnums=0)
def _write_rows(arena, rows, slots):
    """Write row i of the batch-R cache ``rows`` into arena row
    ``slots[i]``; leaves are layer-stacked ``(reps, rows, ...)``, the
    row axis is axis 1 (rows beyond ``len(slots)`` are ignored)."""
    def one(a, r):
        def body(i, acc):
            row = jax.lax.dynamic_index_in_dim(r, i, axis=1, keepdims=True)
            return jax.lax.dynamic_update_slice(
                acc, row.astype(acc.dtype),
                (0, slots[i]) + (0,) * (acc.ndim - 2))
        return jax.lax.fori_loop(0, slots.shape[0], body, a)
    return jax.tree_util.tree_map(one, arena, rows)



class SlotArena:
    """Fixed-size pool of per-request cache slots for one path island."""

    def __init__(self, cfg: ModelConfig, num_slots: int, cache_len: int):
        self.cfg = cfg
        self.num_slots = num_slots
        self.cache_len = cache_len
        self.cache = api.init_serve_cache(cfg, num_slots, cache_len)
        self._free = list(range(num_slots - 1, -1, -1))
        # per-slot next write position; parked at 0 while free (decode
        # ticks leave free rows' caches untouched: their mask is False)
        self.positions = np.zeros(num_slots, np.int32)
        self.active = np.zeros(num_slots, bool)

    # -- bookkeeping ---------------------------------------------------
    @property
    def num_free(self) -> int:
        return len(self._free)

    def alloc(self) -> int:
        if not self._free:
            raise SlotExhausted(f"all {self.num_slots} slots in use")
        slot = self._free.pop()
        self.active[slot] = True
        self.positions[slot] = 0
        return slot

    def try_alloc(self):
        """Like :meth:`alloc` but returns None instead of raising."""
        try:
            return self.alloc()
        except SlotExhausted:
            return None

    def free(self, slot: int) -> None:
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        self.active[slot] = False
        self.positions[slot] = 0
        self._free.append(slot)

    # -- cache movement ------------------------------------------------
    def write_slots(self, sub_cache, slots, positions) -> None:
        """Scatter a batch-R cache pytree into arena rows ``slots``.

        ``positions[i]`` is the number of valid tokens row ``i`` holds
        (the next decode index for that request).
        """
        slots = np.asarray(slots, np.int32)
        self.cache = _write_rows(self.cache, sub_cache, jnp.asarray(slots))
        for s, p in zip(slots, np.asarray(positions, np.int32)):
            self.positions[s] = p

    def decode_indices(self) -> np.ndarray:
        """(num_slots,) per-row cache_index vector for a decode tick."""
        return self.positions.copy()


class PrefixCache:
    """Content-keyed cross-request reuse of prefill KV rows.

    Entries map ``(path, version, tokens)`` to a single-slot cache
    pytree (leaves ``(reps, 1, ...)`` — one arena row) plus the
    greedy next token that forward produced.  ``lookup`` returns the
    longest usable entry: the exact prompt when present, else the
    longest *strict* prefix (the engine replays the remaining tokens
    through single-row decode steps — a fixed (1, 1) shape, so the
    whole extension machinery costs one jit entry).

    LRU-bounded by entry count; versioned keys plus an explicit
    :meth:`invalidate` on hot swap keep a superseded deployment's rows
    from ever being served (and from pinning its buffers).
    """

    def __init__(self, max_entries: int):
        if max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, "
                             f"got {max_entries}")
        self.max_entries = max_entries
        self._entries: OrderedDict = OrderedDict()
        self.hits = 0          # exact full-prompt reuse
        self.extensions = 0    # strict-prefix reuse + replay
        self.misses = 0

    def __len__(self) -> int:
        return len(self._entries)

    @staticmethod
    def _key(path: int, version: int, tokens) -> tuple:
        return (int(path), int(version), tuple(int(t) for t in tokens))

    def put(self, path: int, version: int, tokens, row_cache,
            next_token: int) -> None:
        key = self._key(path, version, tokens)
        self._entries.pop(key, None)
        self._entries[key] = (row_cache, int(next_token))
        while len(self._entries) > self.max_entries:
            self._entries.popitem(last=False)

    def lookup(self, path: int, version: int,
               tokens) -> Optional[Tuple[int, object, int]]:
        """Longest usable entry for ``tokens``: ``(n_cached, row_cache,
        next_token)`` with ``n_cached == len(tokens)`` for an exact hit, a
        shorter strict prefix otherwise; None on miss.  Prefix probing
        walks backwards from the full prompt so the first find is the
        longest (prompts are short relative to cache_len; the probe is
        host-side tuple hashing)."""
        toks = tuple(int(t) for t in tokens)
        for n in range(len(toks), 0, -1):
            key = (int(path), int(version), toks[:n])
            hit = self._entries.get(key)
            if hit is None:
                continue
            self._entries.move_to_end(key)
            if n == len(toks):
                self.hits += 1
            else:
                self.extensions += 1
            return n, hit[0], hit[1]
        self.misses += 1
        return None

    def invalidate(self) -> None:
        """Drop every entry (hot swap: a new version's keys never match
        old entries, but keeping them would pin superseded buffers)."""
        self._entries.clear()


class StackedSlotArenas:
    """Joint slot arenas for ``num_paths`` homogeneous path islands.

    All paths of a DiPaCo deployment share one architecture, so their
    decode caches live in one pytree with the islands folded into the
    row axis: attention k/v ``(reps, P * S, KH, D, T)`` — layer outermost,
    tokens minor — where row ``p * S + s`` is slot ``s`` of path ``p``
    (int8 scales ``(reps, P * S, KH, T)``, latents ``(reps, P * S,
    r + dr, T)``, SSM state ``(reps, P * S, ...)``; leading dense layers
    under ``lead``).  One decode dispatch then advances *every* island per tick
    (the stacked-island tick) instead of one jit call per island from a
    Python loop — per Pathways, dispatch overhead rather than FLOPs
    dominates the many-small-islands regime — and it reads each layer in
    place and writes one token per row, since the layer loop carries
    the arena in exactly this order.

    Host-side bookkeeping (free lists, positions, active flags) stays
    per path, ``(P, S)``; :meth:`view` exposes a :class:`SlotArena`-shaped
    facade per island so engine/test code is agnostic to the backing
    layout.
    """

    def __init__(self, cfg: ModelConfig, num_paths: int, num_slots: int,
                 cache_len: int):
        self.cfg = cfg
        self.num_paths = num_paths
        self.num_slots = num_slots
        self.cache_len = cache_len
        self.cache = api.init_serve_cache(cfg, num_paths * num_slots,
                                          cache_len)
        self._free = [list(range(num_slots - 1, -1, -1))
                      for _ in range(num_paths)]
        self.positions = np.zeros((num_paths, num_slots), np.int32)
        self.active = np.zeros((num_paths, num_slots), bool)
        self.views = [_StackedArenaView(self, p) for p in range(num_paths)]

    # -- per-path bookkeeping (mirrors SlotArena) ----------------------
    def num_free(self, path: int) -> int:
        return len(self._free[path])

    def alloc(self, path: int) -> int:
        if not self._free[path]:
            raise SlotExhausted(
                f"all {self.num_slots} slots of path {path} in use")
        slot = self._free[path].pop()
        self.active[path, slot] = True
        self.positions[path, slot] = 0
        return slot

    def free(self, path: int, slot: int) -> None:
        if not self.active[path, slot]:
            raise ValueError(f"slot {slot} of path {path} is not active")
        self.active[path, slot] = False
        self.positions[path, slot] = 0
        self._free[path].append(slot)

    def write_slots(self, path: int, sub_cache, slots, positions) -> None:
        """Scatter a batch-R cache pytree into rows ``slots`` of island
        ``path`` (R may be smaller than the sub-cache batch: padded
        bucket rows beyond R are ignored)."""
        slots = np.asarray(slots, np.int32)
        self.cache = _write_rows(self.cache, sub_cache,
                                 jnp.asarray(path * self.num_slots + slots))
        for s, p in zip(slots, np.asarray(positions, np.int32)):
            self.positions[path, s] = p


class _StackedArenaView:
    """SlotArena-shaped facade over one path of a StackedSlotArenas."""

    def __init__(self, stacked: StackedSlotArenas, path: int):
        self._stacked = stacked
        self.path = path
        self.num_slots = stacked.num_slots
        self.cache_len = stacked.cache_len
        # numpy row views: in-place writes hit the shared arrays
        self.positions = stacked.positions[path]
        self.active = stacked.active[path]

    @property
    def num_free(self) -> int:
        return self._stacked.num_free(self.path)

    @property
    def cache(self):
        """This island's cache rows (gathered; for tests/inspection)."""
        lo = self.path * self.num_slots
        return jax.tree_util.tree_map(lambda x: x[:, lo:lo + self.num_slots],
                                      self._stacked.cache)

    def alloc(self) -> int:
        return self._stacked.alloc(self.path)

    def try_alloc(self):
        try:
            return self.alloc()
        except SlotExhausted:
            return None

    def free(self, slot: int) -> None:
        self._stacked.free(self.path, slot)

    def write_slots(self, sub_cache, slots, positions) -> None:
        self._stacked.write_slots(self.path, sub_cache, slots, positions)

    def decode_indices(self) -> np.ndarray:
        return self.positions.copy()
