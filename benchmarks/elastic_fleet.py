"""Elastic fleet robustness (§3.4): stable vs lossy vs flapping fleets.

Three identical miniature training runs through ``TrainingService``:

``fleet_stable``
    The reference: all four shards, calm transport.

``fleet_loss30_recovered``
    30% of the fleet is killed *mid-phase* (``ChaosController``
    ``kill_frac``), the survivors train on with resized quorums, the
    victims rejoin at the end and catch up.  Gated under ``--smoke``:
    the final-phase mean loss must land within 2% of the stable
    fleet's (the ISSUE acceptance bar) — elasticity must cost noise,
    not convergence.  ``recovery_wall_s`` is the recovered-phase
    latency: the wall-clock of the catch-up phase after the rejoin.

``fleet_flapping_faulty``
    One shard flaps (leave/join every phase boundary) while the
    transport drops/duplicates/corrupts sends on a seeded schedule —
    the full chaos layer at once.  Records the retry overhead (retries
    per goodput send, burned retry bytes) separately from goodput;
    gated on the chaos actually firing (retries > 0, epochs > 0) and
    the run still converging to a finite loss.

Results are recorded to ``BENCH_train.json`` under ``elastic_fleet``.
"""
from __future__ import annotations

import dataclasses
import tempfile
import time

import numpy as np

from repro.data import shard_documents
from repro.infra import ChaosController, TrainingService
from repro.models.config import DiPaCoConfig
from . import common

_W = 4


def _dataset(s):
    docs, doms = s["docs"][:256], np.asarray(s["doms"][:256])
    return shard_documents(docs, doms % _W, _W)


def _service(s, ds, root, dcfg, **over):
    kw = dict(key=s["key"], base_params=s["base"], batch_size=4,
              peak_lr=1e-3, warmup=10, total_steps=200, num_workers=1)
    kw.update(over)
    return TrainingService(s["cfg"], dcfg, ds, ckpt_root=root, **kw)


def _stable_row(s, ds, dcfg, phases, tel):
    with tempfile.TemporaryDirectory() as root:
        with _service(s, ds, root, dcfg, telemetry=tel) as svc:
            svc.run(1, tau=2)              # warm the jit out of the timing
            t0 = time.time()
            m = svc.run(phases, tau=2)
            dt = time.time() - t0
    return {"name": "fleet_stable", "us_per_call": dt / phases * 1e6,
            "wall_s_per_phase": dt / phases, "phases": phases,
            "mean_loss": m["mean_loss"],
            "outer_updates": m["outer_updates"],
            "members": len(m["members"])}


def _loss30_row(s, ds, dcfg, phases, stable_loss, tel):
    with tempfile.TemporaryDirectory() as root:
        with _service(s, ds, root, dcfg, telemetry=tel) as svc:
            svc.run(1, tau=2)
            chaos = ChaosController(svc, [
                {"phase": 1, "action": "kill_frac", "frac": 0.3,
                 "when": "mid"}], seed=11)
            t0 = time.time()
            chaos.run(phases - 1, tau=2)   # degraded fleet trains on
            dt_degraded = time.time() - t0
            evicted = sorted(set(range(_W)) - svc.members)
            assert evicted, "kill_frac(0.3) evicted nobody"
            svc.fleet.join(evicted)
            t0 = time.time()
            m = svc.run(1, tau=2)          # victims catch up + final phase
            recovery = time.time() - t0
    delta_pct = 100.0 * abs(m["mean_loss"] - stable_loss) / stable_loss
    # the ISSUE acceptance gate: losing 30% of the workers mid-phase
    # must not cost more than 2% final loss vs the stable fleet
    assert delta_pct <= 2.0, (
        f"30%-loss fleet diverged from stable: mean_loss "
        f"{m['mean_loss']:.4f} vs {stable_loss:.4f} "
        f"({delta_pct:.2f}% > 2%)")
    assert len(m["members"]) == _W         # the fleet healed
    return {"name": "fleet_loss30_recovered",
            "us_per_call": dt_degraded / max(phases - 1, 1) * 1e6,
            "wall_s_per_phase": dt_degraded / max(phases - 1, 1),
            "phases": phases, "mean_loss": m["mean_loss"],
            "loss_delta_pct": delta_pct, "recovery_wall_s": recovery,
            "evicted": len(evicted), "fleet_epoch": m["fleet_epoch"],
            "outer_updates": m["outer_updates"]}


def _flapping_row(s, ds, dcfg, phases, stable_loss, tel):
    noisy = dataclasses.replace(
        dcfg, transport_retries=12,
        transport_faults={"seed": 5, "drop": 0.15, "dup": 0.1,
                          "corrupt": 0.05, "delay": 0.05,
                          "delay_s": 0.0})
    events = []
    for p in range(1, phases, 2):          # flap shard 3 every 2 phases
        events.append({"phase": p, "action": "leave", "shards": [3]})
        events.append({"phase": p + 1, "action": "join", "shards": [3]})
    with tempfile.TemporaryDirectory() as root:
        with _service(s, ds, root, noisy, telemetry=tel) as svc:
            svc.run(1, tau=2)
            chaos = ChaosController(svc, events)
            t0 = time.time()
            m = chaos.run(phases, tau=2)
            dt = time.time() - t0
            st = m["transport"]
    flaps = m["fleet_epoch"]
    retries = st["retries"]
    goodput = st["sends"]
    # the chaos layer must actually have fired — a zero here means the
    # benchmark silently stopped exercising the retry/flap machinery
    assert flaps >= 2, f"fleet never flapped (epoch={flaps})"
    assert retries > 0, f"faulty transport never retried: {st}"
    assert np.isfinite(m["mean_loss"])
    delta_pct = 100.0 * abs(m["mean_loss"] - stable_loss) / stable_loss
    return {"name": "fleet_flapping_faulty",
            "us_per_call": dt / phases * 1e6,
            "wall_s_per_phase": dt / phases, "phases": phases,
            "mean_loss": m["mean_loss"], "loss_delta_pct": delta_pct,
            "fleet_epoch": flaps, "goodput_sends": goodput,
            "retries": retries,
            "retry_overhead": retries / max(goodput, 1),
            "drops": st["drops"], "dups": st["dups"],
            "corruptions": st["corruptions"],
            "checksum_rejects": st["checksum_rejects"]}


def run(quick: bool = True):
    s = common.setup(quick)
    ds = _dataset(s)
    phases = 4 if quick else 8
    dcfg = DiPaCoConfig(levels=(2, 2), inner_steps=2, comm_dtype="int8")
    # one telemetry plane across all three fleets: the whole chaos run
    # (phases, fragment sends, retries, membership epochs) lands in a
    # single Perfetto-exportable timeline (CI uploads it)
    with common.make_telemetry("fleet") as tel:
        stable = _stable_row(s, ds, dcfg, phases, tel)
        rows = [stable,
                _loss30_row(s, ds, dcfg, phases, stable["mean_loss"],
                            tel),
                _flapping_row(s, ds, dcfg, phases, stable["mean_loss"],
                              tel)]
    common.record_bench("elastic_fleet", rows,
                        path=common.BENCH_TRAIN_PATH, trace=tel.path)
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
