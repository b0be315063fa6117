"""Path-serving engines (paper §2.2/§2.6: "at test time, the paths are
instantiated and served independently, with text routed to each path via
a router").

Two engines share the routing/feature machinery:

* :class:`PathServingEngine` — the original one-shot batch engine: a
  synchronous ``generate`` over a fixed request batch, with
  full-sequence re-prefill (token-by-token replay) on §2.4.3 re-route.
  Kept as the benchmark baseline.
* :class:`ContinuousBatchingEngine` — tick-based continuous batching:
  an admission scheduler feeds per-path slot arenas; every tick prefills
  new admissions (single multi-token forward per prompt-length group)
  while decoding all in-flight requests of an island in one masked
  full-arena decode step.  §2.4.3 re-routing migrates a request by
  re-prefilling only into a freshly allocated slot on the target path
  and evicting the source slot — the §6 KV-recompute limitation,
  implemented honestly but incrementally.

Both engines optionally serve from a deployment registry
(repro/deploy): instead of a fixed ``path_params_list`` they take a
``registry`` handle and hot-swap the whole path set *between decode
ticks* whenever the registry's tagged serving version moves (promote or
rollback).  Swaps never recompile — shapes and dtypes are unchanged, so
every warmed jit entry stays valid; the stacked param tree is
double-buffered with the old buffers donated to the new stack.  The
per-request pinning policy is chosen at construction:

* ``swap_policy="drain"`` — in-flight requests finish on the version
  they were admitted under: admissions pause (scheduler backpressure)
  until the arenas drain, then the new version installs.  Requests
  admitted after the swap are token-identical to a freshly constructed
  engine on the new parameters.
* ``swap_policy="live"`` — the new version installs immediately and
  every in-flight request is migrated onto it mid-stream by
  re-prefilling its running text into its slot (the §2.4.3 migration
  machinery, minus the island move).  Token divergence is accepted and
  the affected requests are flagged ``swapped_midstream``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import api
from repro.models.config import ModelConfig
from repro.models.lm import apply_lm

from repro.obs import as_telemetry

from .cache import PrefixCache, SlotArena, StackedSlotArenas
from .scheduler import (PRIO_HIGH, PRIO_PREEMPTIBLE, Request,
                        RequestState, Scheduler)


def _paths_homogeneous(path_params_list) -> bool:
    """True when every path shares one pytree structure + leaf shapes
    (same architecture), i.e. params can stack along a path axis."""
    t0 = jax.tree_util.tree_structure(path_params_list[0])
    s0 = [(leaf.shape, leaf.dtype)
          for leaf in jax.tree_util.tree_leaves(path_params_list[0])]
    for p in path_params_list[1:]:
        if jax.tree_util.tree_structure(p) != t0:
            return False
        if [(leaf.shape, leaf.dtype)
                for leaf in jax.tree_util.tree_leaves(p)] != s0:
            return False
    return True


def _default_buckets(cache_len: int):
    """Power-of-two prompt-length buckets, capped at cache_len."""
    buckets, b = [], 16
    while b < cache_len:
        buckets.append(b)
        b *= 2
    buckets.append(cache_len)
    return tuple(buckets)


@dataclass
class EngineOptions:
    """Construction options shared by both serving engines.

    One validated bag replaces the loose ``registry=`` / ``swap_policy=``
    / bucket kwargs that were duplicated across
    :class:`PathServingEngine`, :class:`ContinuousBatchingEngine` and
    ``launch/serve.py``::

        opts = EngineOptions(registry=reg, swap_policy="live",
                             cache_len=256, slots_per_path=4)
        eng = ContinuousBatchingEngine(cfg, options=opts)

    The continuous-batching-only fields (``slots_per_path`` onward) are
    accepted and ignored by the one-shot engine, so one options object
    can configure either engine.  (The PR-6-era loose-kwarg
    construction form is gone: engines reject unknown keyword
    arguments with a TypeError pointing here.)
    """

    router: Any = None
    route_fn: Any = None
    feat_params: Any = None
    registry: Any = None
    cache_len: int = 512
    swap_policy: str = "drain"
    # telemetry handle (repro.obs.Telemetry) — None = no-op tracing
    telemetry: Any = None
    # --- ContinuousBatchingEngine only ---------------------------------
    slots_per_path: int = 8
    reroute_every: int = 0
    stacked: Optional[bool] = None
    bucketed_prefill: Optional[bool] = None
    prefill_buckets: Optional[tuple] = None
    # cross-request prefix cache capacity (entries); 0 = disabled
    prefix_cache: int = 0
    # allow a queued PRIO_HIGH admit to evict a PRIO_PREEMPTIBLE slot
    # (the evictee re-queues and re-admits via §2.4.3 re-prefill)
    preemption: bool = True

    def __post_init__(self):
        if self.router is not None and self.route_fn is not None:
            raise ValueError("pass either router (feature-based) or "
                             "route_fn (prompt -> path id), not both")
        if self.swap_policy not in ("drain", "live"):
            raise ValueError(f"swap_policy must be 'drain' or 'live', "
                             f"got {self.swap_policy!r}")
        if self.cache_len < 1:
            raise ValueError(f"cache_len must be >= 1, "
                             f"got {self.cache_len}")
        if self.slots_per_path < 1:
            raise ValueError(f"slots_per_path must be >= 1, "
                             f"got {self.slots_per_path}")
        if self.reroute_every < 0:
            raise ValueError(f"reroute_every must be >= 0, "
                             f"got {self.reroute_every}")
        if self.prefill_buckets is not None:
            self.prefill_buckets = tuple(self.prefill_buckets)
            if any(b > self.cache_len or b < 1
                   for b in self.prefill_buckets):
                raise ValueError(
                    f"prefill_buckets {self.prefill_buckets} must lie "
                    f"in [1, cache_len={self.cache_len}]")
        if self.prefix_cache < 0:
            raise ValueError(f"prefix_cache must be >= 0, "
                             f"got {self.prefix_cache}")


def _resolve_options(options, legacy):
    """The PR-6 loose-kwarg deprecation shim expired: engines take
    ``options=EngineOptions(...)`` only, and any stray keyword argument
    fails loudly with the replacement spelled out."""
    if legacy:
        raise TypeError(
            f"serving engines no longer accept loose keyword arguments "
            f"{sorted(legacy)} (the per-kwarg construction form was "
            f"deprecated in PR 6 and has been removed); pass "
            f"options=EngineOptions({', '.join(sorted(legacy))}, ...) "
            f"instead")
    return options if options is not None else EngineOptions()


@dataclass
class GenerationResult:
    tokens: np.ndarray          # (B, prompt + new)
    paths: np.ndarray           # (B,) final path per request
    switches: int


@dataclass
class FinishedRequest:
    rid: int
    tokens: np.ndarray          # (prompt + new,)
    path: int                   # final path
    switches: int
    arrival: float
    admitted_at: float
    finished_at: float
    first_token_at: float = 0.0
    version: int = -1           # registry version the request finished on
    swapped_midstream: bool = False   # a live hot-swap hit this request
    priority: int = 1
    preemptions: int = 0        # times a high-priority admit evicted it

    @property
    def latency(self) -> float:
        return self.finished_at - self.arrival

    @property
    def ttft(self) -> float:
        """Time to first generated token, measured from the request's
        trace arrival (queue wait included); non-trace runs submit with
        ``arrival == 0.0`` and anchor at admission instead."""
        return self.first_token_at - (self.arrival or self.admitted_at)


class _EngineBase:
    """Shared routing / feature / registry plumbing."""

    def __init__(self, cfg: ModelConfig, path_params_list=None, *,
                 options: Optional[EngineOptions] = None, **legacy):
        self.cfg = cfg
        opts = _resolve_options(options, legacy)
        self.options = opts
        if opts.registry is not None:
            if path_params_list is not None:
                raise ValueError(
                    "pass either path_params_list or registry, not both")
            self._version, path_params_list = opts.registry.serving()
        elif path_params_list is None:
            raise ValueError("either path_params_list or a registry "
                             "handle is required")
        else:
            self._version = -1
        self.registry = opts.registry
        self.swap_policy = opts.swap_policy
        self.tel = as_telemetry(opts.telemetry)
        self.paths = path_params_list
        self.router = opts.router
        self.route_fn = opts.route_fn
        self.feat_params = opts.feat_params
        self.cache_len = opts.cache_len

        cfg_ = cfg
        # bind only the feature params, not the whole path list: the
        # closure must not pin a superseded version's full parameter
        # set in memory after a hot swap
        feat_src = opts.feat_params if opts.feat_params is not None \
            else path_params_list[0]

        @jax.jit
        def _feats(tokens):
            h, _ = apply_lm(feat_src, cfg_, tokens, return_hidden=True)
            return jnp.mean(h.astype(jnp.float32), axis=1)

        self._feats = _feats

    def route(self, tokens) -> np.ndarray:
        if self.route_fn is not None:
            return np.asarray([self.route_fn(t) for t in tokens], np.int32)
        if self.router is None:
            return np.zeros(tokens.shape[0], np.int32)
        z = self._feats(jnp.asarray(tokens[:, :self.cfg.route_prefix_len]))
        return np.asarray(self.router.assign(z))

    @property
    def version(self) -> int:
        """Registry version currently installed (-1: no registry).
        NOTE: routing features (``_feats``) stay pinned to the
        construction-time parameters — the router is versioned with the
        deployment, not with every weight swap."""
        return self._version


class PathServingEngine(_EngineBase):
    """One-shot batch engine (baseline): synchronous generate per batch."""

    def __init__(self, cfg: ModelConfig, path_params_list=None, *,
                 options: Optional[EngineOptions] = None, **legacy):
        super().__init__(cfg, path_params_list, options=options,
                         **legacy)
        cfg_ = cfg

        def _decode(params, tok, cache, idx):
            logits, cache = api.serve_step(
                params, cfg_, {"tokens": tok}, cache, idx)
            return logits[:, 0], cache

        # donate the cache: decode updates it in place (the caller
        # always rebinds its reference to the returned cache)
        self._decode = jax.jit(_decode, donate_argnums=2)
        self._last_cache = None

    def poll_registry(self) -> bool:
        """Install the registry's serving version if it moved.  Called
        between ``generate`` batches — trivially drain semantics, since
        the one-shot engine holds no in-flight state across calls."""
        if self.registry is None:
            return False
        if self.registry.serving_version == self._version:
            return False
        t0 = time.monotonic_ns()
        self._version, self.paths = self.registry.serving()
        self.tel.complete_span("serve.swap", t0)
        return True

    def device_state(self):
        """Device buffers still possibly in flight (for benchmark
        ``block_until_ready`` before reading the wall clock)."""
        return jax.tree_util.tree_leaves(self._last_cache)

    def _build_cache(self, params, tokens):
        """Prefill by replaying tokens through decode steps (the old
        one-compiled-fn path; the continuous engine prefills in one
        forward instead)."""
        b, s = tokens.shape
        cache = api.init_serve_cache(self.cfg, b, self.cache_len)
        logits = None
        for t in range(s):
            logits, cache = self._decode(params, tokens[:, t:t + 1], cache,
                                         jnp.int32(t))
        return logits, cache

    # ------------------------------------------------------------------
    def generate(self, prompts: np.ndarray, max_new: int, *,
                 reroute_every: int = 0, greedy: bool = True,
                 seed: int = 0) -> GenerationResult:
        """NOTE: with ``reroute_every`` a whole co-routed group follows
        the first request's re-route vote (the original demo-scale
        behavior, kept for baseline stability); the continuous engine
        re-routes per request, so the engines only match token-for-token
        under re-routing for single-request groups."""
        self.poll_registry()
        prompts = np.asarray(prompts)
        b, s0 = prompts.shape
        assign = self.route(prompts)
        switches = 0
        results = np.zeros((b, s0 + max_new), np.int32)
        results[:, :s0] = prompts
        final_paths = np.asarray(assign).copy()
        for p in np.unique(assign):
            sel = np.nonzero(assign == p)[0]
            params = self.paths[int(p)]
            # logits predicts the token at position `pos`
            logits, cache = self._build_cache(
                params, jnp.asarray(results[sel, :s0]))
            cur_path = int(p)
            pos = s0
            for t in range(max_new):
                nxt = jnp.argmax(logits, -1)   # greedy
                results[sel, pos] = np.asarray(nxt, np.int32)
                if (reroute_every and (t + 1) % reroute_every == 0
                        and self.router is not None and t + 1 < max_new):
                    z = self._feats(jnp.asarray(
                        results[sel, max(0, pos - reroute_every + 1):pos + 1]))
                    new_p = int(np.asarray(self.router.assign(z))[0])
                    if new_p != cur_path:
                        switches += 1
                        cur_path = new_p
                        params = self.paths[new_p]
                        # §6 limitation: rebuild the cache on the new path
                        logits, cache = self._build_cache(
                            params, jnp.asarray(results[sel, :pos + 1]))
                        pos += 1
                        continue
                logits, cache = self._decode(
                    params, jnp.asarray(results[sel, pos:pos + 1]), cache,
                    jnp.int32(pos))
                pos += 1
            final_paths[sel] = cur_path
            self._last_cache = cache
        return GenerationResult(tokens=results, paths=final_paths,
                                switches=switches)


class ContinuousBatchingEngine(_EngineBase):
    """Continuous-batching, multi-path serving engine.

    Per tick: (1) route + admit arrivals into islands with free slots,
    prefilling admissions in length-bucketed batched forwards (prompts
    padded up to a small fixed bucket set, so the compile cache is
    bounded by the buckets, not the admission pattern); (2) decode every
    in-flight request of *all* islands in one stacked vmapped dispatch —
    path params are stacked along a leading axis and the masked decode
    step is vmapped over it (rows that were prefilled this tick, or are
    free, keep their cache untouched); (3) emit one greedy token per
    request, retiring finished requests and migrating re-routed ones.

    ``stacked=False`` falls back to one jit call per island (required
    for heterogeneous path architectures, where params cannot stack);
    ``bucketed_prefill=False`` falls back to batch-1 exact-length
    prefill (automatic for SSM/enc-dec paths, whose recurrent state
    would absorb pad tokens).
    """

    def __init__(self, cfg: ModelConfig, path_params_list=None, *,
                 options: Optional[EngineOptions] = None, **legacy):
        super().__init__(cfg, path_params_list, options=options,
                         **legacy)
        opts = self.options               # resolved by the base
        path_params_list = self.paths     # resolved by the base (registry)
        cache_len = self.cache_len
        slots_per_path = opts.slots_per_path
        self.reroute_every = opts.reroute_every
        self.swaps = 0
        self.last_swap_tick = -1
        # monotonic start of a pending drain-policy swap window (the
        # serve.swap span runs from first drain tick to install)
        self._swap_wait_ns = None
        num_paths = len(path_params_list)
        homog = _paths_homogeneous(path_params_list)
        self.stacked = homog if opts.stacked is None else opts.stacked
        if self.stacked and not homog:
            raise ValueError("stacked decode requires homogeneous path "
                             "architectures; pass stacked=False")
        # pad tokens are causally invisible to attention rows, but a
        # recurrent SSM state (or enc-dec replay) would absorb them
        can_bucket = (not api.is_encdec(cfg)
                      and all(spec.mixer == "attn" for spec in cfg.pattern))
        self.bucketed = can_bucket if opts.bucketed_prefill is None \
            else opts.bucketed_prefill
        if self.bucketed and not can_bucket:
            raise ValueError("bucketed prefill requires attention-only "
                             "patterns; pass bucketed_prefill=False")
        buckets = (opts.prefill_buckets
                   if opts.prefill_buckets is not None
                   else _default_buckets(cache_len))
        # cache_len is always a bucket so every admissible sequence
        # (submit enforces prompt+max_new <= cache_len) — including
        # §2.4.3 migration re-prefills of the running text — hits the
        # warmed, bounded compile set instead of an exact-length compile
        self.prefill_buckets = tuple(sorted(set(buckets) | {cache_len}))
        if self.stacked:
            self._stacked_params = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *path_params_list)
            self._stacked_arenas = StackedSlotArenas(
                cfg, num_paths, slots_per_path, cache_len)
            self.arenas = self._stacked_arenas.views
        else:
            self._stacked_params = None
            self._stacked_arenas = None
            self.arenas = [SlotArena(cfg, slots_per_path, cache_len)
                           for _ in path_params_list]
        self.scheduler = Scheduler(num_paths)
        self.in_flight: Dict[int, RequestState] = {}
        self.ticks = 0
        self.preemption = opts.preemption
        # rid -> RequestState evicted by a high-priority admit; restored
        # (new slot + §2.4.3 re-prefill of the running text) when the
        # scheduler re-admits the request
        self._preempted: Dict[int, RequestState] = {}
        self.prefix_cache = (PrefixCache(opts.prefix_cache)
                             if opts.prefix_cache else None)
        # states whose first token was emitted this tick — realtime
        # serve_trace re-stamps their first_token_at after the step's
        # device work completes, so TTFT includes that tick's compute
        self._new_first: list = []
        cfg_ = cfg

        @jax.jit
        def _prefill(params, tokens):
            logits, cache = api.prefill(params, cfg_, {"tokens": tokens},
                                        cache_len)
            return logits[:, -1], cache

        self._prefill = _prefill

        @jax.jit
        def _prefill_bucketed(params, tokens, last):
            """Padded-bucket prefill: per-row gather of the logits at
            each prompt's true last token (pad rows/tails ignored)."""
            logits, cache = api.prefill(params, cfg_, {"tokens": tokens},
                                        cache_len)
            lg = jnp.take_along_axis(
                logits, last[:, None, None], axis=1)[:, 0]
            return lg, cache

        self._prefill_bucketed = _prefill_bucketed

        def _extend_one(params, tok, cache, idx):
            logits, cache = api.serve_step(params, cfg_, {"tokens": tok},
                                           cache, idx)
            return logits[:, 0], cache

        # prefix-cache extension: replay an uncached prompt tail into a
        # stored single-slot row — fixed (1, 1) token shape, so the
        # whole extension machinery costs one jit entry
        self._extend = jax.jit(_extend_one, donate_argnums=2)

        def _decode_one(params, tok, cache, idx, mask):
            logits, new_cache = api.serve_step(
                params, cfg_, {"tokens": tok}, cache, idx)

            def sel(new, old):
                m = mask.reshape((1, -1) + (1,) * (new.ndim - 2))
                return jnp.where(m, new.astype(old.dtype), old)

            new_cache = jax.tree_util.tree_map(sel, new_cache, cache)
            return logits[:, 0], new_cache

        # caches are donated (in-place decode); every caller rebinds
        # its cache reference to the returned pytree
        self._decode_masked = jax.jit(_decode_one, donate_argnums=2)
        # stacked-island tick: one dispatch advances every island
        self._decode_stacked = jax.jit(jax.vmap(_decode_one),
                                       donate_argnums=2)

        def _decode_island(params, path, tok, stacked_cache, idx, mask):
            """Single-island decode against the stacked arena: slice the
            island's cache rows out, decode, scatter them back in place
            (donation).  Used by the hybrid tick when few islands have
            work — a full stacked dispatch would burn (P-k)/P of its
            compute on empty islands."""
            cache_p = jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_index_in_dim(
                    x, path, axis=0, keepdims=False), stacked_cache)
            logits, new_cache = _decode_one(params, tok, cache_p, idx,
                                            mask)
            new_stacked = jax.tree_util.tree_map(
                lambda full, new: jax.lax.dynamic_update_index_in_dim(
                    full, new.astype(full.dtype), path, axis=0),
                stacked_cache, new_cache)
            return logits, new_stacked

        self._decode_island = jax.jit(_decode_island, donate_argnums=3)

        def _restack(old, *new):
            return jax.tree_util.tree_map(
                lambda o, *ns: jnp.stack(ns).astype(o.dtype), old, *new)

        # hot-swap double-buffering: the outgoing stacked tree is
        # donated, so XLA reuses its buffers for the incoming stack
        # instead of holding both full param sets alive
        self._restack = jax.jit(_restack, donate_argnums=0)

    # -- hot swap (deployment registry) --------------------------------
    def _install(self, version: int, paths) -> None:
        """Swap the serving parameters between ticks.  Never recompiles:
        the new version has identical shapes/dtypes (same partition), so
        every warmed prefill/decode jit entry stays valid."""
        self.paths = list(paths)
        if self.stacked:
            self._stacked_params = self._restack(self._stacked_params,
                                                 *self.paths)
        self._version = version
        self.swaps += 1
        self.last_swap_tick = self.ticks
        if self.prefix_cache is not None:
            self.prefix_cache.invalidate()

    def _poll_swap(self) -> bool:
        """Install a new serving version if the registry moved; returns
        True while a drain-policy swap is pending (admissions pause)."""
        if self.registry is None:
            return False
        if self.registry.serving_version == self._version:
            return False
        version, paths = self.registry.serving()
        if version == self._version:
            return False
        if self.swap_policy == "live":
            t0 = time.monotonic_ns()
            self._install(version, paths)
            self._reprefill_inflight()
            self.tel.complete_span("serve.swap", t0)
            return False
        if self.in_flight:
            # drain: in-flight requests finish on their admitted
            # version; new admissions wait (scheduler backpressure)
            if self._swap_wait_ns is None:
                self._swap_wait_ns = time.monotonic_ns()
            return True
        t0 = self._swap_wait_ns or time.monotonic_ns()
        self._swap_wait_ns = None
        self._install(version, paths)
        self.tel.complete_span("serve.swap", t0)
        return False

    def _prefill_running(self, path: int, tokens):
        """Re-prefill a request's full running text on island ``path``
        (the §2.4.3 migration primitive shared by re-route moves and
        live hot-swaps): returns (next-token logits row, cache)."""
        n = len(tokens)
        length = self._bucket(n) if self.bucketed else n
        with self.tel.span("serve.prefill", rows=1, padded_rows=1, tokens=n,
                           padded_tokens=length, bucket=length):
            if self.bucketed:
                tok = np.zeros((1, length), np.int32)
                tok[0, :n] = tokens
                logits, cache = self._prefill_bucketed(
                    self.paths[path], jnp.asarray(tok),
                    jnp.asarray([n - 1], np.int32))
            else:
                logits, cache = self._prefill(
                    self.paths[path],
                    jnp.asarray(np.asarray(tokens, np.int32)[None]))
            return self._fetch(logits)[0], cache

    def _fetch(self, logits) -> np.ndarray:
        """Copy logits to the host: where a tick waits for the device."""
        with self.tel.span("serve.fetch", bytes=logits.nbytes):
            return np.asarray(logits)

    def _reprefill_inflight(self) -> None:
        """Live-swap migration: rebuild every in-flight request's cache
        on the just-installed version by re-prefilling its running text
        into its slot (the §2.4.3 migration machinery, minus the island
        move).  The continuation diverges from both the old-version
        stream and a fresh new-version generation — accepted, and the
        request is flagged."""
        for st in self.in_flight.values():
            logits, cache = self._prefill_running(st.path, st.tokens)
            self.arenas[st.path].write_slots(cache, [st.slot],
                                             [len(st.tokens)])
            st.next_logits = logits
            st.prefilled_this_tick = True
            st.swapped_midstream = True
            st.version = self._version

    def device_state(self):
        """Device buffers still possibly in flight (for benchmark
        ``block_until_ready`` before reading the wall clock)."""
        if self.stacked:
            return jax.tree_util.tree_leaves(self._stacked_arenas.cache)
        return [leaf for a in self.arenas
                for leaf in jax.tree_util.tree_leaves(a.cache)]

    def _bucket(self, n: int) -> int:
        """Smallest configured bucket >= n (always exists: the bucket
        set contains cache_len and submit caps sequences at it)."""
        for b in self.prefill_buckets:
            if b >= n:
                return b
        raise AssertionError(
            f"length {n} exceeds every bucket {self.prefill_buckets}")

    def warmup(self) -> None:
        """Pre-compile the engine's bounded jit cache off the serving
        clock: every (length-bucket, batch-bucket) prefill variant plus
        the decode dispatch — the compile set a bucketed engine pays at
        startup instead of per admission pattern.  (Non-bucketed
        prefill compiles per exact prompt length and cannot be warmed
        ahead of the trace.)"""
        slots = self.arenas[0].num_slots
        sizes, r = [], 1
        while r < slots:
            sizes.append(r)
            r <<= 1
        sizes.append(r)
        seen = set()
        warm_paths = []
        for p in self.paths:
            sig = tuple((leaf.shape, str(leaf.dtype))
                        for leaf in jax.tree_util.tree_leaves(p))
            if sig not in seen:
                seen.add(sig)
                warm_paths.append(p)
        if self.bucketed:
            for params in warm_paths:
                for length in self.prefill_buckets:
                    for rows in sizes:
                        self._prefill_bucketed(
                            params, jnp.zeros((rows, length), jnp.int32),
                            jnp.full((rows,), length - 1, jnp.int32))
        if self.stacked:
            sa = self._stacked_arenas
            tok = jnp.zeros((sa.num_paths, sa.num_slots, 1), jnp.int32)
            mask = jnp.zeros((sa.num_paths, sa.num_slots), bool)
            _, sa.cache = self._decode_stacked(
                self._stacked_params, tok, sa.cache,
                jnp.asarray(sa.positions), mask)   # mask=False: no-op
            _, sa.cache = self._decode_island(
                self.paths[0], jnp.int32(0), tok[0], sa.cache,
                jnp.asarray(sa.positions[0]), mask[0])
            # warm the hot-swap install too: the swap contract is "no
            # compile inside a serving tick", which must include the
            # first swap's restack dispatch
            self._stacked_params = self._restack(self._stacked_params,
                                                 *self.paths)
        else:
            for p, params in enumerate(self.paths):
                arena = self.arenas[p]
                tok = jnp.zeros((arena.num_slots, 1), jnp.int32)
                mask = jnp.zeros(arena.num_slots, bool)
                _, arena.cache = self._decode_masked(
                    params, tok, arena.cache,
                    jnp.asarray(arena.decode_indices()), mask)
        jax.block_until_ready(self.device_state())

    # -- submission ----------------------------------------------------
    def submit(self, req: Request) -> None:
        if len(req.prompt) + req.max_new > self.cache_len:
            raise ValueError(
                f"request {req.rid}: prompt {len(req.prompt)} + max_new "
                f"{req.max_new} exceeds cache_len {self.cache_len}")
        if len(req.prompt) < self.cfg.route_prefix_len and self.router:
            raise ValueError(
                f"request {req.rid}: prompt shorter than routing prefix "
                f"({self.cfg.route_prefix_len})")
        self.scheduler.submit(req)

    def _route_prompt(self, prompt: np.ndarray) -> int:
        if self.route_fn is not None:
            return int(self.route_fn(prompt))
        if self.router is None:
            return 0
        z = self._feats(
            jnp.asarray(prompt[None, :self.cfg.route_prefix_len]))
        return int(np.asarray(self.router.assign(z))[0])

    # -- one engine tick ----------------------------------------------
    def step(self, now: float = 0.0) -> List[FinishedRequest]:
        """Advance the engine one tick; returns requests finished now."""
        self.ticks += 1
        with self.tel.span("serve.tick", step_num=self.ticks):
            admissions = {}
            with self.tel.span("serve.schedule"):
                draining = self._poll_swap()
                self.scheduler.route_arrivals(self._route_prompt)
                if not draining:
                    if self.preemption:
                        self._preempt_tick()
                    admissions = self.scheduler.admissions(
                        {p: a.num_free for p, a in enumerate(self.arenas)})
                elif self.scheduler.pending:
                    # the drain pause is backpressure too: requests are
                    # waiting on the swap, not on slots — count every
                    # queued request starved by the stall
                    self.scheduler.drain_backpressure()
            for p, reqs in admissions.items():
                with self.tel.span("serve.admit", path=p,
                                   requests=len(reqs)):
                    self._admit(p, reqs, now)
            self._decode_tick()
            with self.tel.span("serve.emit",
                               rows=len(self.in_flight)) as sp:
                fins = self._emit_tick(now)
                sp.set(finished=len(fins))
        return fins

    def _preempt_tick(self) -> None:
        """Evict PRIO_PREEMPTIBLE slots for queued PRIO_HIGH admits.

        Per island: when more high-priority requests wait than slots are
        free, the least-progressed preemptible occupants (least decode
        work lost) release their slots.  An evictee re-queues at the
        head of its class and re-admits through the §2.4.3 re-prefill
        migration path as soon as its island frees a slot again, so its
        greedy continuation is token-identical to an uninterrupted run.
        """
        for p, arena in enumerate(self.arenas):
            need = self.scheduler.queued(p, PRIO_HIGH) - arena.num_free
            if need <= 0:
                continue
            victims = sorted(
                (st for st in self.in_flight.values()
                 if st.path == p
                 and st.req.priority == PRIO_PREEMPTIBLE),
                key=lambda st: st.emitted)
            for st in victims[:need]:
                arena.free(st.slot)
                del self.in_flight[st.req.rid]
                st.preemptions += 1
                st.next_logits = None
                st.prefilled_this_tick = False
                self._preempted[st.req.rid] = st
                self.scheduler.requeue(st.req, p)
                self.scheduler.stats.preemptions += 1

    def _prefix_admit(self, path: int, r: Request, arena,
                      now: float) -> bool:
        """Admit ``r`` from the cross-request prefix cache when (a
        prefix of) its prompt is cached under the current version.

        Exact hits write the stored row + logits — bit-exact, both came
        from an identical prefill forward.  Prefix hits replay only the
        uncached tail through single-row decode steps (the same replay
        primitive the token-identity matrix pins against one-forward
        prefill) and promote the extended row to a full-prompt entry.
        """
        if self.prefix_cache is None:
            return False
        hit = self.prefix_cache.lookup(path, self._version, r.prompt)
        if hit is None:
            return False
        n, row, logits = hit
        s0 = len(r.prompt)
        if n < s0:
            # copy the stored row: the replay loop donates its cache
            # argument, which must not consume the cached entry
            row = jax.tree_util.tree_map(jnp.array, row)
            with self.tel.span("serve.prefill", rows=1, padded_rows=1,
                               tokens=s0 - n, padded_tokens=s0 - n,
                               bucket=s0 - n):
                for t in range(n, s0):
                    lg, row = self._extend(
                        self.paths[path],
                        jnp.asarray([[r.prompt[t]]], jnp.int32),
                        row, jnp.int32(t))
                logits = self._fetch(lg)[0]
            self.prefix_cache.put(path, self._version, r.prompt, row,
                                  logits)
        slot = arena.alloc()
        arena.write_slots(row, [slot], [s0])
        self.in_flight[r.rid] = RequestState(
            req=r, path=path, slot=slot,
            tokens=list(map(int, r.prompt)),
            next_logits=np.asarray(logits).copy(),
            prefilled_this_tick=True, admitted_at=now,
            version=self._version)
        return True

    def _admit(self, path: int, reqs: List[Request], now: float) -> None:
        """Prefill admissions.

        Bucketed mode (default for attention paths): prompts are
        right-padded up to a small fixed set of bucket lengths and the
        batch is padded to a power of two, so the whole admission group
        of a bucket prefills in ONE forward and the jit compile cache is
        bounded by ``len(buckets) * log2(slots)`` entries.  Pad tokens
        are harmless: each junk cache slot is overwritten by decode
        before the ring-validity mask would ever admit it, and the
        per-row logits gather reads each prompt's true last position.

        Fallback: batch-1 exact-length prefill per request (compile
        cache bounded by distinct prompt lengths).
        """
        arena = self.arenas[path]
        fresh: List[Request] = []
        for r in reqs:
            st = self._preempted.pop(r.rid, None)
            if st is not None:
                # preemption re-admission: restore the running text
                # (prompt + tokens generated before eviction) through
                # the §2.4.3 re-prefill primitive — greedy-identical
                # to the uninterrupted continuation
                slot = arena.alloc()
                logits, cache = self._prefill_running(path, st.tokens)
                arena.write_slots(cache, [slot], [len(st.tokens)])
                st.path, st.slot = path, slot
                st.next_logits = logits
                st.prefilled_this_tick = True
                self.in_flight[r.rid] = st
            elif not self._prefix_admit(path, r, arena, now):
                fresh.append(r)
        reqs = fresh
        if not reqs:
            return
        if not self.bucketed:
            for r in reqs:
                s0 = len(r.prompt)
                with self.tel.span("serve.prefill", rows=1, padded_rows=1,
                                   tokens=s0, padded_tokens=s0, bucket=s0):
                    logits, cache = self._prefill(
                        self.paths[path], jnp.asarray(r.prompt[None]))
                    slot = arena.alloc()
                    arena.write_slots(cache, [slot], [s0])
                    logits = self._fetch(logits)[0]
                self.in_flight[r.rid] = RequestState(
                    req=r, path=path, slot=slot,
                    tokens=list(map(int, r.prompt)),
                    next_logits=logits,
                    prefilled_this_tick=True, admitted_at=now,
                    version=self._version)
                if self.prefix_cache is not None:
                    self.prefix_cache.put(path, self._version, r.prompt,
                                          cache, logits)
            return
        groups: Dict[int, List[Request]] = {}
        for r in reqs:
            groups.setdefault(self._bucket(len(r.prompt)), []).append(r)
        for length, group in sorted(groups.items()):
            rows = 1 << (len(group) - 1).bit_length()   # batch bucket
            tok = np.zeros((rows, length), np.int32)
            last = np.zeros(rows, np.int32)
            for i, r in enumerate(group):
                tok[i, :len(r.prompt)] = r.prompt
                last[i] = len(r.prompt) - 1
            with self.tel.span("serve.prefill", rows=len(group),
                               padded_rows=rows,
                               tokens=sum(len(r.prompt) for r in group),
                               padded_tokens=rows * length, bucket=length):
                logits, cache = self._prefill_bucketed(
                    self.paths[path], jnp.asarray(tok), jnp.asarray(last))
                slots = [arena.alloc() for _ in group]
                arena.write_slots(cache, slots,
                                  [len(r.prompt) for r in group])
                logits = self._fetch(logits)
            for i, r in enumerate(group):
                self.in_flight[r.rid] = RequestState(
                    req=r, path=path, slot=slots[i],
                    tokens=list(map(int, r.prompt)),
                    next_logits=logits[i],
                    prefilled_this_tick=True, admitted_at=now,
                    version=self._version)
                if self.prefix_cache is not None:
                    self.prefix_cache.put(
                        path, self._version, r.prompt,
                        jax.tree_util.tree_map(
                            lambda x, i=i: x[:, i:i + 1], cache),
                        logits[i])

    def _decode_tick(self) -> None:
        """Advance every in-flight request one token.

        Stacked mode: ONE vmapped dispatch decodes the full
        (paths, slots) arena — per-island dispatch overhead is paid
        once per tick, not once per island.  Fallback: one masked
        full-arena decode step per island with work.
        """
        rows = [st for st in self.in_flight.values()
                if not st.prefilled_this_tick]
        if not rows:
            return
        active = sorted({st.path for st in rows})
        # the stacked tick decodes every island when half have work
        dense = self.stacked and 2 * len(active) >= len(self.arenas)
        islands = len(self.arenas) if dense else len(active)
        with self.tel.span("serve.decode", rows=len(rows),
                           slots=islands * self.arenas[0].num_slots,
                           dense=dense):
            if self.stacked:
                self._decode_tick_stacked(rows, active, dense)
            else:
                self._decode_tick_islands(rows, active)

    def _decode_tick_islands(self, rows, active) -> None:
        for p in active:
            arena = self.arenas[p]
            mine = [st for st in rows if st.path == p]
            tok = np.zeros((arena.num_slots, 1), np.int32)
            mask = np.zeros(arena.num_slots, bool)
            for st in mine:
                arena.positions[st.slot] = len(st.tokens) - 1
                tok[st.slot, 0] = st.tokens[-1]
                mask[st.slot] = True
            logits, arena.cache = self._decode_masked(
                self.paths[p], jnp.asarray(tok), arena.cache,
                jnp.asarray(arena.decode_indices()), jnp.asarray(mask))
            logits = self._fetch(logits)
            for st in mine:
                st.next_logits = logits[st.slot]

    def _decode_tick_stacked(self, rows, active, dense) -> None:
        sa = self._stacked_arenas
        tok = np.zeros((sa.num_paths, sa.num_slots, 1), np.int32)
        mask = np.zeros((sa.num_paths, sa.num_slots), bool)
        for st in rows:
            sa.positions[st.path, st.slot] = len(st.tokens) - 1
            tok[st.path, st.slot, 0] = st.tokens[-1]
            mask[st.path, st.slot] = True
        if dense:
            # dense tick: one vmapped dispatch advances every island
            logits, sa.cache = self._decode_stacked(
                self._stacked_params, jnp.asarray(tok), sa.cache,
                jnp.asarray(sa.positions), jnp.asarray(mask))
            logits = self._fetch(logits)
            for st in rows:
                st.next_logits = logits[st.path, st.slot]
            return
        # sparse tick (e.g. trace drain): decode only the active
        # islands, slicing their rows in/out of the stacked arena
        out = {}
        for p in active:
            lg, sa.cache = self._decode_island(
                self.paths[p], jnp.int32(p), jnp.asarray(tok[p]),
                sa.cache, jnp.asarray(sa.positions[p]),
                jnp.asarray(mask[p]))
            out[p] = self._fetch(lg)
        for st in rows:
            st.next_logits = out[st.path][st.slot]

    def _emit_tick(self, now: float) -> List[FinishedRequest]:
        """Append one greedy token per request; retire / migrate."""
        done: List[FinishedRequest] = []
        self._new_first = []
        for st in list(self.in_flight.values()):
            st.prefilled_this_tick = False
            st.tokens.append(int(np.argmax(st.next_logits)))
            if st.first_token_at is None:
                st.first_token_at = now
                self._new_first.append(st)
            if st.done:
                self.arenas[st.path].free(st.slot)
                fin = FinishedRequest(
                    rid=st.req.rid, tokens=np.asarray(st.tokens, np.int32),
                    path=st.path, switches=st.switches,
                    arrival=st.req.arrival, admitted_at=st.admitted_at,
                    finished_at=now, first_token_at=st.first_token_at,
                    version=st.version,
                    swapped_midstream=st.swapped_midstream,
                    priority=st.req.priority,
                    preemptions=st.preemptions)
                done.append(fin)
                del self.in_flight[st.req.rid]
                self.scheduler.record_completion()
                continue
            if (self.reroute_every and self.router is not None
                    and st.emitted % self.reroute_every == 0):
                self._maybe_migrate(st)
        return done

    def _maybe_migrate(self, st: RequestState) -> None:
        """§2.4.3 re-route: incremental cache migration to a new path.

        Re-prefills the running text only into a freshly allocated slot
        on the target island and evicts the source slot; deferred when
        the target island has no free slot (backpressure beats dropping
        the in-flight cache).
        """
        window = self.reroute_every
        z = self._feats(jnp.asarray(
            np.asarray(st.tokens[-window:], np.int32)[None]))
        new_p = int(np.asarray(self.router.assign(z))[0])
        if new_p == st.path:
            return
        slot = self.arenas[new_p].try_alloc()
        if slot is None:
            return
        logits, cache = self._prefill_running(new_p, st.tokens)
        self.arenas[new_p].write_slots(cache, [slot], [len(st.tokens)])
        self.arenas[st.path].free(st.slot)
        st.path, st.slot = new_p, slot
        st.next_logits = logits
        st.switches += 1
        st.prefilled_this_tick = True

    # -- drivers -------------------------------------------------------
    @property
    def idle(self) -> bool:
        return not self.in_flight and self.scheduler.pending == 0

    def serve_trace(self, trace: List[Request], *, realtime: bool = False,
                    tick_dt: float = 1e-3) -> List[FinishedRequest]:
        """Drive a full arrival trace to completion.

        realtime=False replays arrivals on a simulated clock advancing
        ``tick_dt`` seconds per engine tick (deterministic, for tests
        and CI); realtime=True paces arrivals on the wall clock for
        throughput measurement.
        """
        trace = sorted(trace, key=lambda r: r.arrival)
        i = 0
        now = 0.0
        t0 = time.perf_counter()
        out: List[FinishedRequest] = []
        while i < len(trace) or not self.idle:
            if realtime:
                now = time.perf_counter() - t0
            elif self.idle and i < len(trace):
                now = max(now, trace[i].arrival)   # jump over idle gaps
            while i < len(trace) and trace[i].arrival <= now:
                self.submit(trace[i])
                i += 1
            if self.idle and i < len(trace) and realtime:
                time.sleep(min(1e-3, trace[i].arrival - now))
                continue
            fins = self.step(now=now)
            if realtime:
                # re-stamp completions AND first tokens at the
                # post-step clock: the tick's device compute belongs in
                # TTFT, not just the pre-step submission instant
                now = time.perf_counter() - t0
                new_rids = {st.req.rid for st in self._new_first}
                for st in self._new_first:
                    st.first_token_at = now
                for f in fins:
                    f.finished_at = now
                    if f.rid in new_rids:
                        f.first_token_at = now
            else:
                now += tick_dt
            out.extend(fins)
        self.tel.flush()   # trace safe point: trace ends with the run
        return out
