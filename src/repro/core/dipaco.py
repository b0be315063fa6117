"""DiPaCo trainer (Algorithm 1) — vectorized stacked-worker simulation.

Every path is a row of a worker-stacked parameter pytree; the inner
phase is ``tau`` vmapped AdamW steps (zero cross-path communication by
construction), the outer phase applies the per-module DiLoCo mixing
(core/diloco.py).  With W == P this is exactly Algorithm 1; the
round-based many-islands deployment of the same math lives in
``repro.infra`` (task queue + sharded outer executors) and is tested to
produce identical updates.

Special cases (paper §2.6.3 / §4.3):
  flat MoE : DiPaCoConfig(levels=(P,), shared_embeddings=False)
  DiLoCo   : DiPaCoConfig(levels=(1,))  — all paths share one module
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.loader import ShardLoader
from repro.data.sharder import PreShardedDataset
from repro.models import api
from repro.models.config import DiPaCoConfig, ModelConfig
from repro.models.lm import apply_lm, lm_loss
from repro.models.params import cast_tree
from repro.optim import adamw_init, cosine_schedule
from repro.core.diloco import outer_state_init, outer_step
from repro.core.partition import make_partition, mixing_matrices
from repro.launch.steps import make_inner_train_step


def stack_tree(tree, n):
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n, *x.shape)).copy(), tree)


def master_rows(tree, n):
    """``n`` stacked float32 copies of ``tree``: the workers' master
    weights, and the global copy they sync against.  The model computes
    in ``cfg.dtype`` (every layer casts its weights to the activations'
    dtype); AdamW updates the f32 masters, where a bf16 copy would round
    away every update below half a bf16 step (2^-9 at the norm scales'
    1.0, more than a step of the default schedule)."""
    return stack_tree(cast_tree(tree, jnp.float32), n)


def row(tree, i):
    return jax.tree_util.tree_map(lambda x: x[i], tree)


@dataclass
class PhaseMetrics:
    """Per-phase result every ``Trainer`` backend returns.

    A hybrid: attribute access for the vectorized-trainer consumers
    (``m.mean_loss``), dict-style access (``m["outer_updates"]``) for
    the service consumers — backend-specific counters ride in
    ``extra`` and are reachable by key alongside the dataclass
    fields."""
    mean_loss: float
    final_loss: float = math.nan
    per_path_loss: Optional[np.ndarray] = None
    extra: dict = field(default_factory=dict)

    def __getitem__(self, key):
        if key in self.extra:
            return self.extra[key]
        if key != "extra" and hasattr(self, key):
            return getattr(self, key)
        raise KeyError(key)

    def get(self, key, default=None):
        try:
            return self[key]
        except KeyError:
            return default

    def keys(self):
        return (["mean_loss", "final_loss", "per_path_loss"]
                + list(self.extra))


class DiPaCoTrainer:
    def __init__(self, cfg: ModelConfig, dcfg: DiPaCoConfig,
                 dataset: PreShardedDataset, *, key,
                 base_params=None, batch_size: int = 8,
                 peak_lr: float = 4e-4, warmup: int = 100,
                 total_steps: int = 10_000, seed: int = 0):
        self.cfg, self.dcfg = cfg, dcfg
        self.dataset = dataset
        self.batch_size = batch_size
        self.partition = make_partition(dcfg, cfg.pattern_repeats)
        P = self.partition.num_paths
        # workers >= paths: e.g. classic DiLoCo is P=1 path, W workers
        W = dataset.num_shards
        assert W % P == 0 or P == 1, (W, P)
        self.num_workers = W
        self.worker_paths = np.arange(W) % P
        if base_params is None:
            base_params, axes = api.init_model(key, cfg)
        else:
            _, axes = api.init_model(key, cfg)
        self.axes = axes
        self.worker_params = master_rows(base_params, W)
        self.global_params = master_rows(base_params, W)
        self.opt_state = jax.vmap(adamw_init)(self.worker_params)
        self.outer_state = outer_state_init(self.global_params)
        alphas = dataset.alphas() if dcfg.loss_reweigh else None
        mixl, mixs = mixing_matrices(
            self.partition, self.worker_paths, alphas,
            grad_norm_rescale=dcfg.grad_norm_rescale)
        self.mix_layers = jnp.asarray(mixl)
        self.mix_shared = jnp.asarray(mixs)
        self.loaders = [ShardLoader(s, batch_size, seed=seed + i)
                        for i, s in enumerate(dataset.shards)]
        self.step = 0
        self.phase = 0
        self.lr = lambda t: cosine_schedule(
            t, peak_lr=peak_lr, warmup=warmup, total_steps=total_steps)
        self._inner = make_inner_train_step(cfg)
        # phase and outer step update the stacked state in place: four
        # full-width workers' params, AdamW moments, global copies and
        # outer momenta only fit one chip without a second copy
        # (run_phase rebinds every donated reference)
        self._phase_fn = jax.jit(self._make_phase(), donate_argnums=(0, 1))
        self._outer_fn = jax.jit(self._make_outer(), donate_argnums=(0, 1, 2))

        @jax.jit
        def _nll_eval(p, tk):
            logits, _ = apply_lm(p, cfg, tk)
            nll, mask = lm_loss(logits, tk, cfg.route_prefix_len)
            return nll.sum(), mask.sum()

        self._nll_eval = _nll_eval
        # early stopping (paper §2.7)
        self.best_holdout = np.full(W, np.inf)
        self.best_params = None

    # ------------------------------------------------------------------
    @classmethod
    def resume(cls, cfg, dcfg, dataset, *, key, ckpt_root, **kw):
        """Part of the ``Trainer`` protocol.  The in-memory vectorized
        trainer keeps no durable state to resume from — use the
        ``"barrier"``/``"service"`` (CheckpointDB) or ``"mesh"``
        (phase-state file) backends of ``repro.make_trainer`` for
        kill-and-resume runs."""
        raise NotImplementedError(
            "DiPaCoTrainer is in-memory only and cannot resume; use "
            "make_trainer(..., backend='barrier'|'service'|'mesh')")

    # ------------------------------------------------------------------
    def _make_phase(self):
        inner = self._inner

        def phase(worker_params, opt_state, batches, lrs):
            def body(carry, inp):
                wp, opt = carry
                batch, lr = inp
                wp, opt, metrics = inner(wp, opt, {"tokens": batch}, lr)
                return (wp, opt), metrics["loss"]

            (wp, opt), losses = jax.lax.scan(
                body, (worker_params, opt_state), (batches, lrs))
            return wp, opt, losses  # losses: (tau, P)

        return phase

    def _make_outer(self):
        dcfg = self.dcfg

        def outer(worker_params, global_params, outer_state, mixl, mixs):
            return outer_step(worker_params, global_params, outer_state,
                              self.axes, mixl, mixs, lr=dcfg.outer_lr,
                              momentum=dcfg.outer_momentum,
                              nesterov=dcfg.outer_nesterov)

        return outer

    # ------------------------------------------------------------------
    def phase_inputs(self, tau: Optional[int] = None):
        """The next phase's ``(batches (tau, W, B, S), lrs (tau,))``."""
        from repro.data.loader import phase_batches
        tau = tau or self.dcfg.inner_steps
        batches = np.stack(
            [phase_batches(ld.tokens, ld.batch_size, tau, i, self.phase)
             for i, ld in enumerate(self.loaders)], axis=1)
        lrs = jnp.asarray([self.lr(self.step + t) for t in range(tau)])
        return jnp.asarray(batches), lrs

    def run_phase(self, tau: Optional[int] = None) -> PhaseMetrics:
        tau = tau or self.dcfg.inner_steps
        self.worker_params, self.opt_state, losses = self._phase_fn(
            self.worker_params, self.opt_state, *self.phase_inputs(tau))
        self.step += tau
        self.phase += 1
        self.worker_params, self.global_params, self.outer_state = \
            self._outer_fn(self.worker_params, self.global_params,
                           self.outer_state, self.mix_layers,
                           self.mix_shared)
        losses = np.asarray(losses)
        if self.dcfg.early_stopping:
            self._early_stop_update()
        return PhaseMetrics(mean_loss=float(losses.mean()),
                            final_loss=float(losses[-1].mean()),
                            per_path_loss=losses[-1])

    # ------------------------------------------------------------------
    def _early_stop_update(self):
        hold = self.holdout_losses()
        improved = hold < self.best_holdout
        if self.best_params is None:
            self.best_params = jax.tree_util.tree_map(
                lambda x: x.copy(), self.worker_params)
            self.best_holdout = hold
            return
        mask = jnp.asarray(improved)

        def sel(cur, best):
            m = mask.reshape((-1,) + (1,) * (cur.ndim - 1))
            return jnp.where(m, cur, best)

        self.best_params = jax.tree_util.tree_map(
            sel, self.worker_params, self.best_params)
        self.best_holdout = np.minimum(hold, self.best_holdout)

    def holdout_losses(self) -> np.ndarray:
        W = self.num_workers
        out = np.zeros(W)
        for i in range(W):
            h = self.dataset.holdouts[i] if self.dataset.holdouts else None
            if h is None or len(h) == 0:
                out[i] = np.inf
                continue
            out[i] = self._eval_worker(i, h[:64])
        return out

    # ------------------------------------------------------------------
    def worker_of_path(self, p: int) -> int:
        return int(np.nonzero(self.worker_paths == p)[0][0])

    def path_params(self, i: int, *, best: bool = False):
        """Params of the first worker hosting path i."""
        src = self.best_params if (best and self.best_params is not None) \
            else self.worker_params
        return row(src, self.worker_of_path(i))

    def eval_path(self, i: int, tokens, *, best: bool = False,
                  batch_size: int = 32) -> float:
        return self._eval_worker(self.worker_of_path(i), tokens, best=best,
                                 batch_size=batch_size)

    def _eval_worker(self, w: int, tokens, *, best: bool = False,
                     batch_size: int = 32) -> float:
        src = self.best_params if (best and self.best_params is not None) \
            else self.worker_params
        params = row(src, w)
        nll_of = self._nll_eval
        tot, cnt = 0.0, 0.0
        for j in range(0, len(tokens), batch_size):
            a, b = nll_of(params, jnp.asarray(tokens[j:j + batch_size]))
            tot += float(a)
            cnt += float(b)
        return tot / max(cnt, 1.0)

    def evaluate_routed(self, docs, assignments, *, best: bool = False):
        """PPL with docs routed to shards (route-once evaluation)."""
        assignments = np.asarray(assignments)
        tot, cnt = 0.0, 0
        nlls = []
        for p in np.unique(assignments):
            idx = np.nonzero(assignments == p)[0]
            nll = self.eval_path(int(p), docs[idx], best=best)
            tot += nll * len(idx)
            cnt += len(idx)
        nll = tot / max(cnt, 1)
        return {"nll": nll, "ppl": float(np.exp(nll))}


class SyncDiPaCoTrainer(DiPaCoTrainer):
    """Fully-synchronous ablation (paper §4.5): per-STEP gradient mixing
    module-by-module (communicating tau x more often), then one AdamW
    step per worker.  Same mixing matrices, no outer optimizer."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        from repro.launch.steps import make_sync_train_step
        # gradient mixing must be an unbiased average: no sqrt rescale
        mixl, mixs = mixing_matrices(
            self.partition, self.worker_paths,
            self.dataset.alphas() if self.dcfg.loss_reweigh else None,
            grad_norm_rescale=False)
        self._sync_mixl = jnp.asarray(mixl)
        self._sync_mixs = jnp.asarray(mixs)
        sync_step = make_sync_train_step(self.cfg, self._sync_mixl,
                                         self._sync_mixs, self.axes)

        def phase(worker_params, opt_state, batches, lrs):
            def body(carry, inp):
                wp, opt = carry
                batch, lr = inp
                wp, opt, metrics = sync_step(wp, opt, {"tokens": batch}, lr)
                return (wp, opt), metrics["loss"]

            (wp, opt), losses = jax.lax.scan(
                body, (worker_params, opt_state), (batches, lrs))
            return wp, opt, losses

        self._phase_fn = jax.jit(phase)

        def no_outer(worker_params, global_params, outer_state, *_):
            return worker_params, global_params, outer_state

        self._outer_fn = no_outer


def flat_moe_config(num_paths: int, **kw) -> DiPaCoConfig:
    """Flat MoE baseline (§2.6.3): one level, no sharing at all."""
    return DiPaCoConfig(levels=(num_paths,), shared_embeddings=False, **kw)


def diloco_config(num_workers: int, **kw) -> DiPaCoConfig:
    """Classic DiLoCo (§2.5): every worker trains the whole (single)
    module; paths collapse at every outer step."""
    return DiPaCoConfig(levels=(1,), shared_embeddings=True, **kw)
