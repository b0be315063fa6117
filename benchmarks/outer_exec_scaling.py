"""Paper §3.3: sharded outer-optimization executors with online
accumulation vs a naive monolithic averager — wall-clock per outer step
and peak working-set proxy — plus the §3 async-vs-barrier comparison:
the same miniature training run through the global-barrier round
trainer and through the phase-pipelined ``TrainingService``
(``max_phase_lag=1``) with one deliberately slow shard.  The barrier
pays the straggler every phase; the pipelined service overlaps it.

Streaming fragment-wise outer sync (Streaming DiLoCo): the same run
with the classic one-burst fp32 outer sync vs 4 staggered fragments +
int8 outer gradients — simulated peak bytes per sync instant must drop
>= 4x with < 1% phase-loss regression (both gated under ``--smoke``).

Mesh lane (real collectives): burst (K=1) vs overlapped streaming
(K=4, int8) through ``launch.steps.make_streaming_mesh_phase`` in a
subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
— one worker row per XLA device, every fragment reduce an actual
cross-device all_gather.  Streaming dispatches fragment f's reduce
before segment f+1's inner compute, so its wall-clock per phase must
not exceed burst's (gated under ``--smoke``).  Results are recorded to
``BENCH_train.json``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.module_store import ModuleStore
from repro.core.partition import make_partition
from repro.infra.outer_executor import ShardedOuterExecutors
from repro.models import api
from repro.models.config import DiPaCoConfig
from . import common


def _executor_rows(s):
    cfg, base, key = s["cfg"], s["base"], s["key"]
    P = 8
    dcfg = DiPaCoConfig(levels=(2, 4))
    part = make_partition(dcfg, cfg.pattern_repeats)
    _, axes = api.init_model(key, cfg)
    deltas = [jax.tree_util.tree_map(
        lambda x: jnp.full(x.shape, 0.01 * (w + 1), jnp.float32), base)
        for w in range(P)]
    rows = []

    # sharded online: accumulate as checkpoints "arrive"
    store = ModuleStore(base, axes, part)
    execs = ShardedOuterExecutors(store, part, np.arange(P))
    t0 = time.time()
    for w in range(P):
        execs.accumulate(w, deltas[w])
    dt_sharded = time.time() - t0

    # naive: wait for all, average full trees in one place
    t0 = time.time()
    acc = jax.tree_util.tree_map(jnp.zeros_like, deltas[0])
    for w in range(P):
        acc = jax.tree_util.tree_map(lambda a, d: a + d / P, acc,
                                     deltas[w])
    jax.block_until_ready(jax.tree_util.tree_leaves(acc)[0])
    dt_naive = time.time() - t0

    module_bytes = max(
        sum(x.size * 4 for x in jax.tree_util.tree_leaves(
            store.module_params(l, 0)) if x is not None)
        for l in range(part.num_levels))
    full_bytes = sum(x.size * 4 for x in jax.tree_util.tree_leaves(base))
    rows.append({"name": "outer_exec_sharded_online",
                 "us_per_call": dt_sharded / P * 1e6,
                 "peak_module_bytes": module_bytes,
                 "outer_updates": execs.total_updates})
    rows.append({"name": "outer_exec_naive_monolithic",
                 "us_per_call": dt_naive / P * 1e6,
                 "peak_module_bytes": full_bytes,
                 "outer_updates": 1})
    return rows


def _async_vs_barrier_rows(s, quick: bool):
    """Same run through both regimes under *stochastic* stalls — the
    paper's preemption/jitter scenario.  Each (shard, phase) task stalls
    with probability ``stall_prob`` on a schedule deterministic in
    (shard, phase), so both modes see the identical stall set.  The
    barrier pays (almost) every phase's worst stall; the pipelined
    service overlaps a stalled shard with the other shards' next
    phase."""
    from repro.data import shard_documents
    from repro.infra.service import TrainingService

    cfg, key = s["cfg"], s["key"]
    W = 4
    docs, doms = s["docs"][:256], np.asarray(s["doms"][:256])
    ds = shard_documents(docs, doms % W, W)
    dcfg = DiPaCoConfig(levels=(2, 2), inner_steps=2)
    phases, stall, stall_prob = (4, 0.4, 0.5) if quick else (8, 0.5, 0.5)

    def stall_s(shard: int, phase: int) -> float:
        rng = np.random.default_rng(97 + shard * 131 + phase * 7919)
        return stall if rng.random() < stall_prob else 0.0

    results = {}
    for mode, lag in (("barrier", 0), ("async_lag1", 1)):
        with tempfile.TemporaryDirectory() as root:
            svc = TrainingService(
                cfg, dcfg, ds, key=key, ckpt_root=root,
                base_params=s["base"], batch_size=4, peak_lr=1e-3,
                warmup=10, total_steps=200, num_workers=W,
                max_phase_lag=lag)
            inner = svc._handle

            def jittered(task, _inner=inner):
                time.sleep(stall_s(task.payload["shard_id"],
                                   task.payload["phase"]))
                return _inner(task)

            svc.pool.handler = jittered
            svc.run(1)                    # warm the jit out of the timing
            t0 = time.time()
            m = svc.run(phases)
            dt = time.time() - t0
            results[mode] = (dt, m)
            svc.shutdown()
    dt_b, _ = results["barrier"]
    dt_a, m_a = results["async_lag1"]
    return [
        {"name": "train_service_barrier",
         "us_per_call": dt_b / phases * 1e6,
         "wall_s_per_phase": dt_b / phases, "phases": phases,
         "stall_s": stall, "stall_prob": stall_prob},
        {"name": "train_service_async_lag1",
         "us_per_call": dt_a / phases * 1e6,
         "wall_s_per_phase": dt_a / phases, "phases": phases,
         "stall_s": stall, "stall_prob": stall_prob,
         "max_observed_lag": m_a["max_observed_lag"],
         "outer_updates": m_a["outer_updates"],
         "speedup_vs_barrier": dt_b / dt_a},
    ]


def _streaming_rows(s, quick: bool):
    """Classic one-burst fp32 outer sync vs streaming fragment-wise
    sync with quantized outer gradients, same run otherwise.  Single
    pool worker keeps the accumulation order (and hence the loss)
    deterministic; the comparison is bandwidth shape + quality, the
    wall-clock overlap is covered by the async-vs-barrier rows."""
    from repro.data import shard_documents
    from repro.infra.service import TrainingService

    cfg, key = s["cfg"], s["key"]
    W = 4
    docs, doms = s["docs"][:256], np.asarray(s["doms"][:256])
    ds = shard_documents(docs, doms % W, W)
    phases = 3 if quick else 6
    variants = {
        "burst_fp32": {},
        "stream_frag4_int8": dict(outer_fragments=4, fragment_stagger=1,
                                  comm_dtype="int8"),
    }
    runs = {}
    tel = common.make_telemetry("outer_exec")
    for name, over in variants.items():
        dcfg = DiPaCoConfig(levels=(2, 2), inner_steps=2, **over)
        tel.instant("bench.section", section=f"outer_sync_{name}")
        with tempfile.TemporaryDirectory() as root:
            svc = TrainingService(
                cfg, dcfg, ds, key=key, ckpt_root=root,
                base_params=s["base"], batch_size=4, peak_lr=1e-3,
                warmup=10, total_steps=200, num_workers=1,
                telemetry=tel)
            svc.run(1, tau=2)             # warm the jit out of the timing
            # the warmup phase must not pollute the recorded comms
            # (peak is schedule-determined, but sends/totals are counts)
            svc.reset_comm_stats()
            t0 = time.time()
            m = svc.run(phases, tau=2)
            dt = time.time() - t0
            runs[name] = (m, m["comm"], dt)
            svc.shutdown()
    tel.close()
    mb, cb, dtb = runs["burst_fp32"]
    ms, cs, dts = runs["stream_frag4_int8"]
    peak_reduction = cb["peak_sync_bytes"] / max(cs["peak_sync_bytes"], 1)
    loss_ratio = ms["mean_loss"] / mb["mean_loss"]
    # the headline claims, gated in --smoke (run.py turns an exception
    # into a non-zero exit): streaming must cut the sync-instant
    # bandwidth burst >= 4x without hurting the phase loss > 1%
    assert peak_reduction >= 4.0, (
        f"peak comms reduction {peak_reduction:.2f}x < 4x "
        f"({cb['peak_sync_bytes']} -> {cs['peak_sync_bytes']} bytes)")
    assert loss_ratio <= 1.01, (
        f"streaming phase-loss regression {100 * (loss_ratio - 1):.2f}% "
        f"> 1% ({mb['mean_loss']:.4f} -> {ms['mean_loss']:.4f})")
    return [
        {"name": "outer_sync_burst_fp32",
         "us_per_call": dtb / phases * 1e6,
         "wall_s_per_phase": dtb / phases, "phases": phases,
         "peak_sync_bytes": cb["peak_sync_bytes"],
         "total_comm_bytes": cb["total_comm_bytes"],
         "sends": cb["sends"], "mean_loss": mb["mean_loss"]},
        {"name": "outer_sync_stream_frag4_int8",
         "us_per_call": dts / phases * 1e6,
         "wall_s_per_phase": dts / phases, "phases": phases,
         "peak_sync_bytes": cs["peak_sync_bytes"],
         "total_comm_bytes": cs["total_comm_bytes"],
         "sends": cs["sends"], "mean_loss": ms["mean_loss"],
         "peak_comms_reduction": peak_reduction,
         "total_comms_reduction":
             cb["total_comm_bytes"] / max(cs["total_comm_bytes"], 1),
         "loss_ratio_vs_burst": loss_ratio},
    ]


_MESH_MARK = "MESH_LANE_ROWS:"


def _mesh_lane_child(quick: bool):
    """Child entry point (8 forced host devices): burst K=1 vs
    overlapped streaming K=4 int8 through the identical
    ``make_streaming_mesh_phase`` code path, min-of-N phase wall."""
    from repro.configs import get_smoke_config
    from repro.core.diloco import fragment_state_init
    from repro.core.dipaco import stack_tree
    from repro.core.fragments import FragmentSpec, segment_bounds
    from repro.core.partition import make_partition, mixing_matrices
    from repro.launch.mesh import make_worker_mesh
    from repro.launch.steps import make_streaming_mesh_phase
    from repro.models.config import DiPaCoConfig
    from repro.optim import adamw_init

    ndev = len(jax.devices())
    assert ndev == 8, f"mesh lane expected 8 forced devices, got {ndev}"
    cfg = get_smoke_config("dipaco-150m").replace(
        route_prefix_len=common.PREFIX)
    W, B, T = 8, 2, common.SEQ
    tau, reps = (8, 5) if quick else (16, 7)
    key = jax.random.PRNGKey(0)
    base, axes = api.init_model(key, cfg)
    worker0 = stack_tree(base, W)
    glob0 = stack_tree(jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), base), W)
    opt0 = jax.vmap(adamw_init)(worker0)
    part = make_partition(DiPaCoConfig(levels=(2, 4)),
                          cfg.pattern_repeats)
    mixl, mixs = mixing_matrices(part, np.arange(W) % part.num_paths)
    mixl, mixs = jnp.asarray(mixl), jnp.asarray(mixs)
    mesh = make_worker_mesh(W)
    rng = np.random.default_rng(0)
    batches = jnp.asarray(rng.integers(
        0, cfg.vocab_size, (tau, W, B, T)).astype(np.int32))
    lrs = jnp.linspace(1e-3, 5e-4, tau).astype(jnp.float32)

    def build(K, comm):
        spec = FragmentSpec(glob0, K)
        states = fragment_state_init(glob0, spec)
        bounds = segment_bounds(tau, K)
        seg_b = [batches[bounds[s]:bounds[s + 1]] for s in range(K)]
        seg_l = [lrs[bounds[s]:bounds[s + 1]] for s in range(K)]
        phase = make_streaming_mesh_phase(cfg, mesh, axes, spec,
                                          comm_dtype=comm)

        def once():
            out = phase(worker0, opt0, glob0, states, {}, mixl, mixs,
                        seg_b, seg_l)
            jax.block_until_ready(out)
            return out

        return once

    lanes = [("mesh_burst_k1_fp32", 1, "fp32"),
             ("mesh_stream_frag4_int8", 4, "int8")]
    fns = [build(K, comm) for _, K, comm in lanes]
    outs = [fn() for fn in fns]             # compile out of the timing
    walls = [[] for _ in lanes]
    for _ in range(reps):                   # interleave: shared noise
        for i, fn in enumerate(fns):
            t0 = time.time()
            fn()
            walls[i].append(time.time() - t0)
    rows = []
    for (name, K, comm), w, out in zip(lanes, walls, outs):
        wall = min(w)                       # min-of-N: noise-floor cost
        rows.append({"name": name, "us_per_call": wall * 1e6,
                     "wall_s_per_phase": wall, "devices": ndev,
                     "workers": W, "fragments": K, "comm_dtype": comm,
                     "tau": tau,
                     "mean_loss": float(np.asarray(out[-1]).mean())})
    burst, stream = rows
    ratio = stream["wall_s_per_phase"] / burst["wall_s_per_phase"]
    stream["wall_ratio_vs_burst"] = ratio
    stream["speedup_vs_burst"] = 1.0 / ratio
    # the overlap claim, gated in --smoke: splitting the phase into K
    # segments and dispatching fragment f's reduce before segment f+1's
    # compute must not cost wall-clock vs the one-burst baseline.  On a
    # single-core host the reduce cannot run concurrently with compute
    # (no idle parallelism), so "no penalty" is asserted within the
    # measured dispatch-noise floor; on real multi-device hardware the
    # overlap is the win.
    assert ratio <= 1.05, (
        f"streaming phase wall {stream['wall_s_per_phase']:.3f}s "
        f"exceeds burst {burst['wall_s_per_phase']:.3f}s by "
        f"{100 * (ratio - 1):.1f}% (> 5% noise floor)")
    print(_MESH_MARK + json.dumps(rows))


def _mesh_lane_rows(quick: bool):
    """Run the mesh lane in a subprocess where XLA can still be told to
    present 8 host devices (the parent's device count is locked at its
    first jax use).  It times forced host devices, so a parent that
    holds an accelerator refuses instead of recording CPU rows."""
    import jax
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"the mesh lane times 8 forced CPU devices in a child "
            f"process; this parent holds the {jax.default_backend()!r} "
            f"backend, whose rows it would not measure")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    cmd = [sys.executable, "-m", "benchmarks.outer_exec_scaling",
           "--mesh-lane"] + ([] if quick else ["--full"])
    out = subprocess.run(cmd, env=env, cwd=root, capture_output=True,
                         text=True, timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f"mesh lane failed:\n{out.stdout[-2000:]}\n"
                           f"{out.stderr[-2000:]}")
    for line in out.stdout.splitlines():
        if line.startswith(_MESH_MARK):
            return json.loads(line[len(_MESH_MARK):])
    raise RuntimeError(f"mesh lane produced no rows:\n{out.stdout[-2000:]}")


def run(quick: bool = True):
    s = common.setup(quick)
    rows = _executor_rows(s)
    rows += _async_vs_barrier_rows(s, quick)
    rows += _streaming_rows(s, quick)
    rows += _mesh_lane_rows(quick)
    common.record_bench("outer_exec_async", rows,
                        path=common.BENCH_TRAIN_PATH,
                        trace=common.trace_path("outer_exec"))
    return rows


if __name__ == "__main__":
    if "--mesh-lane" in sys.argv:
        _mesh_lane_child(quick="--full" not in sys.argv)
    else:
        for r in run():
            print(r)
