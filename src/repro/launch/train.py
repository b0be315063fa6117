"""Production training launcher + the streaming mesh trainer.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b \
        --levels 4x4 --phases 2 --tau 20 [--smoke] [--backend mesh]

The model is the arch's ``config()``; ``--smoke`` picks its reduced
preset instead, for CPU rehearsals (``JAX_PLATFORMS=cpu``) of the same
code path.  The preset is never chosen from the backend.

``MeshStreamingTrainer`` is the ``backend="mesh"`` implementation of
the ``repro.make_trainer`` protocol: DiPaCoTrainer semantics with the
phase split into K scan segments and each fragment's outer all-reduce
running through real collectives (launch/steps.py), overlapped with
the next segment's inner compute.
"""
from __future__ import annotations

import argparse
import glob
import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, get_smoke_config
from repro.core.dipaco import PhaseMetrics, master_rows, row
from repro.core.diloco import fragment_state_init
from repro.core.fragments import FragmentSpec, segment_bounds
from repro.core.partition import make_partition, mixing_matrices
from repro.core.routing import kmeans_fit, prefix_features
from repro.data import SyntheticCorpus, shard_documents
from repro.data.loader import ShardLoader
from repro.infra.ckpt_db import load_tree, save_tree
from repro.models import api
from repro.models.config import DiPaCoConfig, ModelConfig
from repro.optim import adamw_init, cosine_schedule
from .mesh import make_worker_mesh
from .sharding import batch_sharding, worker_stacked_sharding
from .steps import make_streaming_mesh_phase


class MeshStreamingTrainer:
    """Streaming fragment-wise DiPaCo on a real device mesh.

    Same math as ``core.diloco.segmented_streaming_phase`` (bit-exact,
    tests/test_mesh_steps.py), with worker-stacked trees sharded over
    the mesh's worker axes and fragment reduces running as shard_map
    all_gathers that overlap the next segment's inner compute.  With
    ``dcfg.outer_fragments == 1`` the schedule degenerates to classic
    burst DiLoCo through the identical code path.

    ``ckpt_root`` (optional) enables phase-granular checkpointing: the
    full trainer state is written after every phase and ``resume``
    continues bit-exactly (batch schedules are pure functions of the
    phase counter).
    """

    def __init__(self, cfg: ModelConfig, dcfg: DiPaCoConfig,
                 dataset, *, key, ckpt_root: Optional[str] = None,
                 base_params=None, batch_size: int = 8,
                 peak_lr: float = 4e-4, warmup: int = 100,
                 total_steps: int = 10_000, seed: int = 0, mesh=None):
        self.cfg, self.dcfg = cfg, dcfg
        self.dataset = dataset
        self.batch_size = batch_size
        self.ckpt_root = ckpt_root
        self.partition = make_partition(dcfg, cfg.pattern_repeats)
        P = self.partition.num_paths
        W = dataset.num_shards
        if not (W % P == 0 or P == 1):
            raise ValueError(f"num_shards {W} not a multiple of paths {P}")
        self.num_workers = W
        self.worker_paths = np.arange(W) % P
        if base_params is None:
            base_params, axes = api.init_model(key, cfg)
        else:
            _, axes = api.init_model(key, cfg)
        self.axes = axes
        self.mesh = mesh if mesh is not None else make_worker_mesh(W)
        self._wshard = worker_stacked_sharding(self.mesh)
        self._bshard = batch_sharding(self.mesh, 4, batch_dim=1)

        def put(tree):
            return jax.device_put(tree, self._wshard)

        self.worker_params = put(master_rows(base_params, W))
        self.global_params = put(master_rows(base_params, W))
        self.opt_state = jax.vmap(adamw_init)(self.worker_params)
        self.fragspec = FragmentSpec(self.global_params,
                                     dcfg.outer_fragments)
        self.frag_states = fragment_state_init(self.global_params,
                                               self.fragspec)
        self.residuals: dict = {}
        # per-worker byte accounting on the unstacked leaf layout (the
        # stacked spec's fragments cover the same leaves, x W rows)
        self._row_spec = FragmentSpec(base_params, dcfg.outer_fragments)
        self.comm_stats = {"peak_sync_bytes": 0, "total_comm_bytes": 0,
                           "sends": 0}
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
        self._phase_fn = make_streaming_mesh_phase(
            cfg, self.mesh, axes, self.fragspec,
            comm_dtype=dcfg.comm_dtype, outer_lr=dcfg.outer_lr,
            outer_momentum=dcfg.outer_momentum,
            outer_nesterov=dcfg.outer_nesterov)

    # ------------------------------------------------------------------
    @classmethod
    def resume(cls, cfg, dcfg, dataset, *, key, ckpt_root, **kw):
        """Rebuild from the newest phase-state file under ``ckpt_root``
        (no-op construction if none exists yet).  Same constructor
        arguments as the original run."""
        self = cls(cfg, dcfg, dataset, key=key, ckpt_root=ckpt_root, **kw)
        files = sorted(glob.glob(
            os.path.join(ckpt_root, "mesh_phase_*.npz")))
        if not files:
            return self
        like = self._state_tree()
        if dcfg.comm_dtype != "fp32":
            # after one full phase every leaf carries a residual
            like["residuals"] = {
                i: jnp.zeros(jnp.shape(l), jnp.float32)
                for i, l in enumerate(
                    self.fragspec.flatten(self.global_params))}
        state = load_tree(files[-1], like)
        put = lambda t: jax.device_put(t, self._wshard)  # noqa: E731
        self.worker_params = put(state["worker"])
        self.global_params = put(state["global"])
        self.opt_state = put(state["opt"])
        self.frag_states = put(state["frag_states"])
        self.residuals = put(state["residuals"])
        self.step = int(state["meta"]["step"])
        self.phase = int(state["meta"]["phase"])
        self.comm_stats = {k: int(v)
                           for k, v in state["meta"]["comm"].items()}
        return self

    def _state_tree(self):
        return {"worker": self.worker_params,
                "global": self.global_params,
                "opt": self.opt_state,
                "frag_states": self.frag_states,
                "residuals": self.residuals,
                "meta": {"step": np.int64(self.step),
                         "phase": np.int64(self.phase),
                         "comm": {k: np.int64(v)
                                  for k, v in self.comm_stats.items()}}}

    def _save_phase(self):
        save_tree(os.path.join(self.ckpt_root,
                               f"mesh_phase_{self.phase:06d}.npz"),
                  self._state_tree())

    # ------------------------------------------------------------------
    def run_phase(self, tau: Optional[int] = None) -> PhaseMetrics:
        from repro.data.loader import phase_batches
        tau = tau or self.dcfg.inner_steps
        K = self.fragspec.num_fragments
        bounds = segment_bounds(tau, K)
        batches = np.stack(
            [phase_batches(ld.tokens, ld.batch_size, tau, i, self.phase)
             for i, ld in enumerate(self.loaders)], axis=1)
        lrs = np.asarray([self.lr(self.step + t) for t in range(tau)],
                         np.float32)
        seg_batches = [jax.device_put(
            jnp.asarray(batches[bounds[s]:bounds[s + 1]]), self._bshard)
            for s in range(K)]
        seg_lrs = [jnp.asarray(lrs[bounds[s]:bounds[s + 1]])
                   for s in range(K)]
        (self.worker_params, self.opt_state, self.global_params,
         self.frag_states, self.residuals, losses) = self._phase_fn(
            self.worker_params, self.opt_state, self.global_params,
            self.frag_states, self.residuals, self.mix_layers,
            self.mix_shared, seg_batches, seg_lrs)
        self.step += tau
        self.phase += 1
        # one send instant per fragment per worker; peak = the largest
        # single instant (burst K=1: the whole tree at once)
        frag_bytes = [self._row_spec.wire_bytes(f, self.dcfg.comm_dtype)
                      for f in range(K)]
        self.comm_stats["sends"] += K * self.num_workers
        self.comm_stats["total_comm_bytes"] += \
            sum(frag_bytes) * self.num_workers
        self.comm_stats["peak_sync_bytes"] = max(
            self.comm_stats["peak_sync_bytes"], max(frag_bytes))
        if self.ckpt_root:
            self._save_phase()
        losses = np.asarray(losses)
        return PhaseMetrics(
            mean_loss=float(losses.mean()),
            final_loss=float(losses[-1].mean()),
            per_path_loss=losses[-1],
            extra={"outer_updates": K,
                   "comm": dict(self.comm_stats)})

    # ------------------------------------------------------------------
    def worker_of_path(self, p: int) -> int:
        return int(np.nonzero(self.worker_paths == p)[0][0])

    def path_params(self, i: int):
        return row(self.worker_params, self.worker_of_path(i))


def _parse_profiles(specs):
    """``SHARD:BANDWIDTH[:COMPUTE[:PREEMPT]]`` → {shard: WorkerProfile}."""
    from repro.infra.fleet import WorkerProfile
    profiles = {}
    for spec in specs:
        parts = spec.split(":")
        if not 2 <= len(parts) <= 4:
            raise SystemExit(f"bad --profile {spec!r}: expected "
                             "SHARD:BANDWIDTH[:COMPUTE[:PREEMPT]]")
        shard = int(parts[0])
        nums = [float(x) for x in parts[1:]]
        profiles[shard] = WorkerProfile(
            bandwidth=nums[0],
            compute=nums[1] if len(nums) > 1 else 1.0,
            preempt_rate=nums[2] if len(nums) > 2 else 0.0)
    return profiles


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dipaco-150m")
    ap.add_argument("--levels", default="2x2")
    ap.add_argument("--phases", type=int, default=2)
    ap.add_argument("--tau", type=int, default=10)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--docs", type=int, default=512)
    ap.add_argument("--smoke", action="store_true",
                    help="the arch's reduced preset (CPU rehearsals)")
    ap.add_argument("--backend", default="vector",
                    choices=("vector", "mesh", "service", "barrier"),
                    help="trainer backend (repro.make_trainer); 'mesh' "
                         "runs the streaming fragment schedule through "
                         "real collectives; 'service'/'barrier' run the "
                         "checkpointed worker-pool infrastructure")
    ap.add_argument("--ckpt-root", default=None,
                    help="CheckpointDB root for service/barrier (and "
                         "optional mesh phase snapshots); a tempdir is "
                         "created when omitted")
    ap.add_argument("--num-workers", type=int, default=4,
                    help="pool threads for --backend service/barrier")
    ap.add_argument("--max-phase-lag", type=int, default=1,
                    help="staleness window for --backend service")
    ap.add_argument("--fragments", type=int, default=1,
                    help="outer fragments K for --backend mesh")
    ap.add_argument("--comm-dtype", default="fp32",
                    choices=("fp32", "int8", "int4"))
    ap.add_argument("--comm-dtype-policy", default="uniform",
                    choices=("uniform", "leafwise"),
                    help="'leafwise' quantizes large matmul leaves hard "
                         "(int4) but keeps norms/embeddings high "
                         "precision")
    ap.add_argument("--transport-retries", type=int, default=0,
                    help="per-send retry budget (exponential backoff) "
                         "for the service transport")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="deterministic fault-injection seed")
    ap.add_argument("--fault-drop", type=float, default=0.0)
    ap.add_argument("--fault-dup", type=float, default=0.0)
    ap.add_argument("--fault-corrupt", type=float, default=0.0)
    ap.add_argument("--fault-delay", type=float, default=0.0)
    ap.add_argument("--fault-delay-s", type=float, default=0.01,
                    help="injected delay duration per delayed send")
    ap.add_argument("--profile", action="append", default=[],
                    metavar="SHARD:BW[:COMPUTE[:PREEMPT]]",
                    help="per-worker fleet profile (repeatable); "
                         "bandwidth < 1 re-ranks that worker's fragment "
                         "sends smallest-first")
    ap.add_argument("--chaos-kill-frac", type=float, default=0.0,
                    help="service backend: evict this fraction of the "
                         "fleet mid-phase, then rejoin it for the last "
                         "phase (ChaosController)")
    ap.add_argument("--chaos-phase", type=int, default=1,
                    help="phase at which --chaos-kill-frac fires")
    return ap


def build_trainer(args, *, cfg_overrides=None, **trainer_kw):
    """Routed shards and a trainer for ``args`` (the launcher's code
    path up to the first phase).  ``cfg_overrides`` replace ModelConfig
    fields (e.g. a depth cut); ``trainer_kw`` go to the backend (e.g.
    ``mesh=``)."""
    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch)).replace(route_prefix_len=8,
                                               **(cfg_overrides or {}))
    levels = tuple(int(x) for x in args.levels.split("x"))
    P = int(np.prod(levels))
    print(f"[launch] arch={cfg.name} smoke={args.smoke} levels={levels} "
          f"paths={P} devices={len(jax.devices())}")

    corpus = SyntheticCorpus(vocab_size=cfg.vocab_size,
                             num_domains=max(8, P), seq_len=args.seq,
                             seed=0)
    docs = corpus.sample_documents(args.docs)
    key = jax.random.PRNGKey(0)
    base, _ = api.init_model(key, cfg)
    feats = prefix_features(base, cfg, jnp.asarray(docs))
    _, assign, _ = kmeans_fit(jax.random.PRNGKey(1), feats, P)
    ds = shard_documents(docs, np.asarray(assign), P)

    from repro.training import make_trainer
    faults = None
    rates = {"drop": args.fault_drop, "dup": args.fault_dup,
             "corrupt": args.fault_corrupt, "delay": args.fault_delay}
    if any(v > 0 for v in rates.values()):
        faults = {"seed": args.fault_seed, "delay_s": args.fault_delay_s,
                  **rates}
    dcfg = DiPaCoConfig(levels=levels, inner_steps=args.tau,
                        outer_fragments=args.fragments,
                        comm_dtype=args.comm_dtype,
                        comm_dtype_policy=args.comm_dtype_policy,
                        transport_retries=args.transport_retries,
                        transport_faults=faults)
    kw: dict = {}
    ckpt_root = args.ckpt_root
    if args.backend in ("service", "barrier"):
        if ckpt_root is None:
            import tempfile
            ckpt_root = tempfile.mkdtemp(prefix="dipaco-ckpt-")
            print(f"[launch] ckpt_root={ckpt_root}")
        kw["num_workers"] = args.num_workers
        if args.profile:
            kw["profiles"] = _parse_profiles(args.profile)
        if args.backend == "service":
            kw["max_phase_lag"] = args.max_phase_lag
    if args.backend == "vector":
        ckpt_root = None
    kw.update(trainer_kw)
    return make_trainer(cfg, dcfg, ds, backend=args.backend, key=key,
                        ckpt_root=ckpt_root, base_params=base,
                        batch_size=args.batch_size, peak_lr=2e-3,
                        warmup=args.tau,
                        total_steps=args.phases * args.tau, **kw)


def main(argv=None) -> None:
    enable_compile_cache()
    args = build_parser().parse_args(argv)
    tr = build_trainer(args)
    t0 = time.time()
    if args.backend == "service" and args.chaos_kill_frac > 0:
        # scripted elasticity demo: kill a fleet fraction mid-phase,
        # let the survivors train with resized quorums, rejoin the
        # victims before the final phase
        from repro.infra import ChaosController
        events = [{"phase": args.chaos_phase, "action": "kill_frac",
                   "frac": args.chaos_kill_frac, "when": "mid"}]
        chaos = ChaosController(tr, events, seed=args.fault_seed)
        m = chaos.run(max(args.phases - 1, 1), tau=args.tau)
        print(f"[chaos] events={m['chaos_events']} "
              f"epoch={m['fleet_epoch']} members={m['members']}")
        evicted = sorted(set(range(tr.num_shards)) - tr.members)
        if evicted:
            tr.fleet.join(evicted)
            print(f"[chaos] rejoined {evicted}")
        m = tr.run(1, tau=args.tau)
        print(f"[final] mean_loss {m['mean_loss']:.4f} "
              f"members={len(m['members'])} "
              f"epoch={m['fleet_epoch']} transport={m['transport']} "
              f"({time.time() - t0:.1f}s)")
    else:
        for ph in range(args.phases):
            m = tr.run_phase()
            print(f"[phase {ph}] loss {m.mean_loss:.4f} "
                  f"({time.time() - t0:.1f}s)")
    if args.backend == "mesh":
        print(f"[comm] {tr.comm_stats}")
    if args.backend in ("service", "barrier"):
        tr.shutdown()
    print("[done]")


if __name__ == "__main__":
    main()
