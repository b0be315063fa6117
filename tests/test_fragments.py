"""Streaming fragment-wise outer sync (Streaming DiLoCo): fragment
partition + wire quantization core, per-fragment executor windows,
fragment-complete publisher gating, and the service-level regression
that the defaults stay bit-identical to unfragmented DiLoCo."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.core.diloco import (fragment_state_init,
                               fragment_window_outer_gradient,
                               outer_state_init, outer_step,
                               streaming_outer_step,
                               window_outer_gradient)
from repro.core.fragments import (FragmentSpec, fake_quantize,
                                  fragment_send_slot,
                                  quantize_with_feedback,
                                  tree_wire_bytes)
from repro.core.module_store import ModuleStore
from repro.core.partition import make_partition, mixing_matrices
from repro.infra import CheckpointDB, ShardedOuterExecutors
from repro.models.config import DiPaCoConfig
from repro.optim.nesterov import nesterov_update


def _tree(seed=0, shapes=((4, 8), (16,), (2, 3, 5), (7,))):
    rng = np.random.default_rng(seed)
    return {f"leaf{i}": jnp.asarray(rng.normal(size=s), jnp.float32)
            for i, s in enumerate(shapes)}


# ---------------------------------------------------------------------
# FragmentSpec
# ---------------------------------------------------------------------

# deadline=None: the first example compiles JAX, well past 200 ms
@settings(max_examples=20, deadline=None)
@given(k=st.integers(1, 8), seed=st.integers(0, 100))
def test_fragment_spec_partition_properties(k, seed):
    """Every leaf lands in exactly one fragment, no fragment is empty,
    and the assignment is a deterministic function of the template."""
    tree = _tree(seed)
    spec = FragmentSpec(tree, k)
    assert 1 <= spec.num_fragments <= min(k, spec.num_leaves)
    covered = sorted(i for idx in spec.indices for i in idx)
    assert covered == list(range(spec.num_leaves))
    assert all(len(idx) > 0 for idx in spec.indices)
    spec2 = FragmentSpec(_tree(seed), k)
    assert np.array_equal(spec.assign, spec2.assign)
    # slicing + re-merging leaves reproduces the tree
    leaves = spec.flatten(tree)
    for f in range(spec.num_fragments):
        for i, leaf in spec.slice_leaves(tree, f).items():
            np.testing.assert_array_equal(np.asarray(leaf),
                                          np.asarray(leaves[i]))


def test_fragment_spec_balances_bytes():
    tree = {f"x{i}": jnp.zeros((64,)) for i in range(8)}
    spec = FragmentSpec(tree, 4)
    assert spec.num_fragments == 4
    assert spec.elems == [128, 128, 128, 128]


def test_fragment_spec_rejects_wrong_tree():
    spec = FragmentSpec(_tree(), 2)
    with pytest.raises(ValueError, match="leaves"):
        spec.flatten({"a": jnp.zeros(3)})
    with pytest.raises(ValueError):
        FragmentSpec({}, 2)


def test_wire_bytes_accounting():
    tree = {"a": jnp.zeros((8, 8))}
    assert tree_wire_bytes(tree) == 256
    assert tree_wire_bytes(tree, "int8") == 64 + 4
    assert tree_wire_bytes(tree, "int4") == 32 + 4
    spec = FragmentSpec(tree, 1)
    assert spec.wire_bytes(0) == 256
    assert spec.wire_bytes(0, "int4") == 36
    assert spec.total_bytes("int8") == 68
    with pytest.raises(ValueError, match="comm_dtype"):
        spec.wire_bytes(0, "bf16")


def test_fragment_send_slots():
    assert [fragment_send_slot(f, 0, 4) for f in range(4)] == [0, 0, 0, 0]
    assert [fragment_send_slot(f, 1, 4) for f in range(4)] == [0, 1, 2, 3]
    assert [fragment_send_slot(f, 3, 4) for f in range(4)] == [0, 3, 2, 1]


# ---------------------------------------------------------------------
# wire quantization + error feedback
# ---------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(dtype=st.sampled_from(["int8", "int4"]), seed=st.integers(0, 50))
def test_fake_quantize_bounded_error(dtype, seed):
    tree = _tree(seed)
    q = fake_quantize(tree, dtype)
    qmax = 127 if dtype == "int8" else 7
    for k in tree:
        x, y = np.asarray(tree[k]), np.asarray(q[k])
        step = np.abs(x).max() / qmax
        assert np.abs(x - y).max() <= 0.5 * step + 1e-7


def test_fake_quantize_zero_tree_roundtrips():
    z = {"a": jnp.zeros((5,))}
    out = fake_quantize(z, "int8")
    np.testing.assert_array_equal(np.asarray(out["a"]), 0.0)
    assert np.isfinite(np.asarray(out["a"])).all()


def test_fp32_wire_is_identity():
    tree = _tree()
    assert fake_quantize(tree, "fp32") is tree
    wire, resid = quantize_with_feedback(tree, None, "fp32")
    assert wire is tree and resid is None


def test_error_feedback_telescopes():
    """Sum of T wire payloads == sum of T true deltas up to one final
    quantization error — the residual carries, it does not accumulate."""
    rng = np.random.default_rng(3)
    resid = None
    true_sum = np.zeros((32,))
    wire_sum = np.zeros((32,))
    for t in range(20):
        d = {"x": jnp.asarray(rng.normal(size=(32,)), jnp.float32)}
        wire, resid = quantize_with_feedback(d, resid, "int4")
        true_sum += np.asarray(d["x"])
        wire_sum += np.asarray(wire["x"])
    # wire_sum + final residual == true_sum exactly (fp32 rounding)
    np.testing.assert_allclose(wire_sum + np.asarray(resid["x"]),
                               true_sum, atol=1e-4)
    # and without feedback the 20-step error would be ~sqrt(20) bigger:
    # with it, the gap stays a single-step quantization error
    step = np.abs(np.asarray(resid["x"])).max()
    assert np.abs(wire_sum - true_sum).max() <= step + 1e-6


# ---------------------------------------------------------------------
# streaming_outer_step (functional core)
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def mixer(tiny_cfg, tiny_base):
    base, axes = tiny_base
    part = make_partition(DiPaCoConfig(levels=(2, 2)),
                          tiny_cfg.pattern_repeats)
    W = 4
    mixL, mixS = mixing_matrices(part, np.arange(W))

    def stack(t):
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (W, *x.shape)), t)

    gp = stack(base)
    wp = jax.tree_util.tree_map(
        lambda x: x + 0.01 * jnp.arange(W, dtype=jnp.float32).reshape(
            (W,) + (1,) * (x.ndim - 1)), gp)
    return dict(axes=axes, mixL=mixL, mixS=mixS, gp=gp, wp=wp)


def test_streaming_outer_step_k1_bitwise_equals_outer_step(mixer):
    """fragments=1, comm_dtype=fp32, full sync == the classic
    outer_step, bit for bit (the acceptance regression)."""
    nw, ng, _ = outer_step(mixer["wp"], mixer["gp"],
                           outer_state_init(mixer["gp"]), mixer["axes"],
                           mixer["mixL"], mixer["mixS"])
    spec = FragmentSpec(mixer["gp"], 1)
    nw2, ng2, _ = streaming_outer_step(
        mixer["wp"], mixer["gp"], fragment_state_init(mixer["gp"], spec),
        mixer["axes"], mixer["mixL"], mixer["mixS"], spec)
    for a, b in zip(jax.tree_util.tree_leaves(ng),
                    jax.tree_util.tree_leaves(ng2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(nw),
                    jax.tree_util.tree_leaves(nw2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_streaming_outer_step_fragments_compose(mixer):
    """Syncing all K fragments == the unfragmented update (grouping
    leaves cannot change per-leaf math), and syncing a subset leaves
    exactly the other fragments' leaves untouched."""
    _, ng1, _ = outer_step(mixer["wp"], mixer["gp"],
                           outer_state_init(mixer["gp"]), mixer["axes"],
                           mixer["mixL"], mixer["mixS"])
    spec = FragmentSpec(mixer["gp"], 4)
    _, ng4, _ = streaming_outer_step(
        mixer["wp"], mixer["gp"], fragment_state_init(mixer["gp"], spec),
        mixer["axes"], mixer["mixL"], mixer["mixS"], spec)
    for a, b in zip(jax.tree_util.tree_leaves(ng1),
                    jax.tree_util.tree_leaves(ng4)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # partial sync: only fragment 0
    nw0, ng0, states = streaming_outer_step(
        mixer["wp"], mixer["gp"], fragment_state_init(mixer["gp"], spec),
        mixer["axes"], mixer["mixL"], mixer["mixS"], spec,
        sync_fragments=[0])
    g_leaves = spec.flatten(mixer["gp"])
    w_leaves = spec.flatten(mixer["wp"])
    out_leaves = spec.flatten(ng0)
    outw_leaves = spec.flatten(nw0)
    full_leaves = spec.flatten(ng4)
    synced = set(spec.indices[0])
    for i in range(spec.num_leaves):
        if i in synced:
            np.testing.assert_array_equal(np.asarray(out_leaves[i]),
                                          np.asarray(full_leaves[i]))
            np.testing.assert_array_equal(np.asarray(outw_leaves[i]),
                                          np.asarray(full_leaves[i]))
        else:
            # global untouched AND worker copies keep their own
            # inner-trained values (not reset to the stale global)
            np.testing.assert_array_equal(np.asarray(out_leaves[i]),
                                          np.asarray(g_leaves[i]))
            np.testing.assert_array_equal(np.asarray(outw_leaves[i]),
                                          np.asarray(w_leaves[i]))
    # unsynced fragments kept zero momentum
    assert all(not np.asarray(states[3][i]).any()
               for i in spec.indices[3])


def test_streaming_outer_step_quantized_close(mixer):
    _, ng, _ = outer_step(mixer["wp"], mixer["gp"],
                          outer_state_init(mixer["gp"]), mixer["axes"],
                          mixer["mixL"], mixer["mixS"])
    spec = FragmentSpec(mixer["gp"], 2)
    _, ngq, _ = streaming_outer_step(
        mixer["wp"], mixer["gp"], fragment_state_init(mixer["gp"], spec),
        mixer["axes"], mixer["mixL"], mixer["mixS"], spec,
        comm_dtype="int8")
    for a, b in zip(jax.tree_util.tree_leaves(ng),
                    jax.tree_util.tree_leaves(ngq)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(b).all()
        # int8 wire: small relative error, not bit-equality
        assert np.abs(a - b).max() <= 0.02 * max(np.abs(a).max(), 1e-6)


# ---------------------------------------------------------------------
# per-fragment executor windows
# ---------------------------------------------------------------------

def _store(tiny_cfg, tiny_base, levels=(2, 2)):
    base, axes = tiny_base
    part = make_partition(DiPaCoConfig(levels=levels),
                          tiny_cfg.pattern_repeats)
    return ModuleStore(base, axes, part), part, base


def _delta(base, v):
    return jax.tree_util.tree_map(
        lambda x: jnp.full(x.shape, v, jnp.float32), base)


def test_executor_fragment_feed_matches_whole_feed(tiny_cfg, tiny_base):
    """Feeding fragments one at a time (the staggered schedule) ends
    bit-identical to feeding whole deltas, and to fragments=1."""
    s1, part, base = _store(tiny_cfg, tiny_base)
    e1 = ShardedOuterExecutors(s1, part, np.arange(4))
    s3, _, _ = _store(tiny_cfg, tiny_base)
    e3 = ShardedOuterExecutors(s3, part, np.arange(4), fragments=3)
    s3f, _, _ = _store(tiny_cfg, tiny_base)
    e3f = ShardedOuterExecutors(s3f, part, np.arange(4), fragments=3)
    for w in range(4):
        e1.accumulate(w, _delta(base, 0.01 * (w + 1)), phase=0)
        e3.accumulate(w, _delta(base, 0.01 * (w + 1)), phase=0)
    for f in range(3):                       # staggered: fragment-major
        for w in range(4):
            e3f.accumulate(w, _delta(base, 0.01 * (w + 1)), phase=0,
                           fragment=f)
    for p in range(4):
        for a, b, c in zip(jax.tree_util.tree_leaves(s1.assemble(p)),
                           jax.tree_util.tree_leaves(s3.assemble(p)),
                           jax.tree_util.tree_leaves(s3f.assemble(p))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_executor_fragments_apply_independently(tiny_cfg, tiny_base):
    """A fragment window fires on its own quorum: fragment 0 applies
    (and only its leaves move) while fragment 1 still accumulates."""
    store, part, base = _store(tiny_cfg, tiny_base)
    execs = ShardedOuterExecutors(store, part, np.arange(4), fragments=2)
    ex = execs.execs[(0, 0)]                 # contributors: workers 0, 1
    before = ex.spec.flatten(ex._params())
    before = [np.asarray(x) for x in before]
    execs.accumulate(0, _delta(base, 0.01), phase=0, fragment=0)
    execs.accumulate(1, _delta(base, 0.02), phase=0, fragment=0)
    assert [w.updates for w in ex.windows] == [1, 0]
    assert [w.phase for w in ex.windows] == [1, 0]
    after = ex.spec.flatten(ex._params())
    for i in range(ex.spec.num_leaves):
        same = np.array_equal(before[i], np.asarray(after[i]))
        assert same == (i in ex.spec.indices[1])
    # the applied fragment matches the per-fragment window oracle
    segs = [store.slice_for_level(_delta(base, v), 0)
            for v in (0.01, 0.02)]
    og = fragment_window_outer_gradient(segs, [0.25, 0.25], ex.spec, 0)
    full = window_outer_gradient(segs, [0.25, 0.25])
    full_leaves = ex.spec.flatten(full)
    for i, g in og.items():
        np.testing.assert_allclose(np.asarray(g),
                                   np.asarray(full_leaves[i]), atol=1e-7)
        p32 = before[i].astype(np.float32)
        want, _ = nesterov_update(
            {"x": g}, {"momentum": {"x": jnp.zeros_like(g)}},
            {"x": jnp.asarray(p32)}, lr=0.7, momentum=0.9, nesterov=True)
        np.testing.assert_allclose(np.asarray(after[i]),
                                   np.asarray(want["x"]), atol=1e-6)


def test_executor_fragment_rows_and_restore(tiny_cfg, tiny_base, tmp_path):
    """Each fragment apply writes its own tagged module row; a fresh
    executor set restores per-fragment phases/momenta bit-exactly."""
    db = CheckpointDB(str(tmp_path))
    store, part, base = _store(tiny_cfg, tiny_base)
    execs = ShardedOuterExecutors(store, part, np.arange(4), fragments=2,
                                  ckpt_db=db)
    for w in range(4):
        execs.accumulate(w, _delta(base, 0.01 * (w + 1)), phase=0)
    rows = db.rows(kind="module")
    ex = execs.execs[(0, 0)]
    mine = [r for r in rows if (r.level, r.expert) == (0, 0)]
    slices = [r for r in mine if not r.extra.get("full")]
    assert sorted(r.fragment for r in slices) == \
        list(range(ex.spec.num_fragments))
    # exactly one params-only full row for the completed phase
    assert [r.fragment for r in mine if r.extra.get("full")] == [-1]
    assert all(r.extra["num_fragments"] == ex.spec.num_fragments
               for r in mine)
    # partial second phase: only worker 0's fragment 0 so far
    execs.accumulate(0, _delta(base, 0.05), phase=1, fragment=0)
    store2, _, _ = _store(tiny_cfg, tiny_base)
    execs2 = ShardedOuterExecutors(store2, part, np.arange(4),
                                   fragments=2, ckpt_db=None)
    execs2.restore_from_db(db)
    for k, ex in execs._all().items():
        ex2 = execs2._all()[k]
        assert [w.phase for w in ex2.windows] == \
            [w.phase for w in ex.windows]
        for w, w2 in zip(ex.windows, ex2.windows):
            for i in w.indices:
                np.testing.assert_array_equal(np.asarray(w.mom[i]),
                                              np.asarray(w2.mom[i]))
    for p in range(4):
        for a, b in zip(jax.tree_util.tree_leaves(store.assemble(p)),
                        jax.tree_util.tree_leaves(store2.assemble(p))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_slice_rows_cut_write_amplification(tiny_cfg, tiny_base, tmp_path):
    """With K fragments every apply used to persist the classic full
    row (params + momentum): K·(P+M) bytes per module phase.  Slice
    rows bring that to the K disjoint slices (P+M total) plus one
    params-only full row — (P+M) + P.  For K=4 and M ≈ P the analytic
    saving is 4·2P / 3P ≈ 2.7×; gate conservatively at 2× (container
    metadata and the momentum/param byte split add noise)."""
    dbs = {}
    for k in (1, 4):
        db = CheckpointDB(str(tmp_path / f"k{k}"))
        store, part, base = _store(tiny_cfg, tiny_base)
        execs = ShardedOuterExecutors(store, part, np.arange(4),
                                      fragments=k, ckpt_db=db)
        for p in range(2):
            for w in range(4):
                execs.accumulate(w, _delta(base, 0.01 * (w + p + 1)),
                                 phase=p)
        dbs[k] = db

    def phase_bytes(db, p):
        return sum(os.path.getsize(r.file)
                   for r in db.rows(kind="module") if r.phase == p)

    for p in range(2):
        full = phase_bytes(dbs[1], p)        # one (P+M) row per module
        legacy_k4 = 4 * full                 # pre-fix K=4 write cost
        actual_k4 = phase_bytes(dbs[4], p)
        assert actual_k4 < 2.0 * full        # ≈ (P+M) + P, not 4·(P+M)
        assert legacy_k4 / actual_k4 >= 2.0


@pytest.mark.slow
@pytest.mark.parametrize("comm_dtype", ["fp32", "int8", "int4"])
def test_kill_resume_across_fragment_boundary(tiny_cfg, tiny_base,
                                              tmp_path, comm_dtype):
    """Kill between fragment applies of a phase — fragment 0 of phase 1
    applied and persisted, fragment 1 still pending — then restore a
    fresh executor set from the rows.  Window phases, momentum and
    assembled params must come back bit-exact, and finishing the
    interrupted phase plus one more phase on both the survivor and the
    resumed set must stay bit-identical, with deltas that passed
    through the int8/int4 wire included."""
    db = CheckpointDB(str(tmp_path))
    store, part, base = _store(tiny_cfg, tiny_base)
    live = ShardedOuterExecutors(store, part, np.arange(4), fragments=2,
                                 ckpt_db=db)

    def wire(v):
        return fake_quantize(_delta(base, v), comm_dtype)

    for w in range(4):                       # phase 0: fragment-complete
        live.accumulate(w, wire(0.01 * (w + 1)), phase=0)
    for w in range(4):                       # phase 1: fragment 0 only
        live.accumulate(w, wire(0.02 * (w + 1)), phase=1, fragment=0)
    # "kill": the process dies here; a fresh set resumes from the rows
    store2, _, _ = _store(tiny_cfg, tiny_base)
    resumed = ShardedOuterExecutors(store2, part, np.arange(4),
                                    fragments=2, ckpt_db=None)
    resumed.restore_from_db(db)
    for k, ex in live._all().items():
        ex2 = resumed._all()[k]
        assert [w.phase for w in ex2.windows] == \
            [w.phase for w in ex.windows]
        for w, w2 in zip(ex.windows, ex2.windows):
            for i in w.indices:
                np.testing.assert_array_equal(np.asarray(w.mom[i]),
                                              np.asarray(w2.mom[i]))
    # finish phase 1 and run phase 2 on both sets: bit-identical
    for execs in (live, resumed):
        for w in range(4):
            execs.accumulate(w, wire(0.02 * (w + 1)), phase=1, fragment=1)
        for w in range(4):
            execs.accumulate(w, wire(0.03 * (w + 1)), phase=2)
    for p in range(4):
        for a, b in zip(jax.tree_util.tree_leaves(store.assemble(p)),
                        jax.tree_util.tree_leaves(store2.assemble(p))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------
# publisher: fragment-complete candidate gating
# ---------------------------------------------------------------------

def test_publisher_waits_for_fragment_complete_phase(tiny_cfg, tiny_base,
                                                     tmp_path):
    from repro.deploy import DeploymentRegistry, Publisher
    base, axes = tiny_base
    dcfg = DiPaCoConfig(levels=(2, 2), outer_fragments=2)
    part = make_partition(dcfg, tiny_cfg.pattern_repeats)
    db = CheckpointDB(str(tmp_path / "db"))
    store = ModuleStore(base, axes, part)
    execs = ShardedOuterExecutors(store, part, np.arange(4), ckpt_db=db,
                                  fragments=2)
    reg = DeploymentRegistry(tiny_cfg, dcfg, str(tmp_path / "deploy"),
                             key=jax.random.PRNGKey(0), base_params=base)
    pub = Publisher(db, reg)
    pub.bootstrap()
    # fragment 0 of every module applies phase 0 — NOT fragment-complete
    for w in range(4):
        execs.accumulate(w, _delta(base, 0.01 * (w + 1)), phase=0,
                         fragment=0)
    assert all(ex.windows[0].updates == 1
               for ex in execs._all().values())
    assert pub.completed_phase() == -1
    assert pub.poll() is None
    # late fragments land -> phase 0 fragment-complete -> candidate cut
    for f in range(1, 2):
        for w in range(4):
            execs.accumulate(w, _delta(base, 0.01 * (w + 1)), phase=0,
                             fragment=f)
    assert pub.completed_phase() == 0
    m = pub.poll()
    assert m is not None and m.version == 2
    pub.close()


def test_publisher_resume_uses_cut_phase_not_ref_phases(tiny_cfg,
                                                       tiny_base,
                                                       tmp_path):
    """A restarted publisher must resume from the manifest's recorded
    ``cut_phase``.  (Since the slice-row fix, K>1 manifest payloads are
    the params-only full rows written exactly at phase completion, so
    refs can no longer run ahead of the cut — asserted below — but the
    recorded cut_phase remains the restart-resume source of truth.)"""
    from repro.deploy import DeploymentRegistry, Publisher
    base, axes = tiny_base
    dcfg = DiPaCoConfig(levels=(2, 2), outer_fragments=2)
    part = make_partition(dcfg, tiny_cfg.pattern_repeats)
    db = CheckpointDB(str(tmp_path / "db"))
    store = ModuleStore(base, axes, part)
    execs = ShardedOuterExecutors(store, part, np.arange(4), ckpt_db=db,
                                  fragments=2)
    reg = DeploymentRegistry(tiny_cfg, dcfg, str(tmp_path / "deploy"),
                             key=jax.random.PRNGKey(0), base_params=base)
    pub = Publisher(db, reg)
    # phase 0 fully applies, then fragment 0 races ahead to phase 1:
    # the newest row per module is now a phase-1 row
    for f in (0, 1):
        for w in range(4):
            execs.accumulate(w, _delta(base, 0.01 * (w + 1)), phase=0,
                             fragment=f)
    for w in range(4):
        execs.accumulate(w, _delta(base, 0.02 * (w + 1)), phase=1,
                         fragment=0)
    assert pub.completed_phase() == 0
    m = pub.poll()
    assert m is not None and m.cut_phase == 0
    # refs are the phase-complete full rows: exactly the cut phase
    assert {r.phase for r in m.refs} == {0}
    reg.promote(m.version)                       # published before the kill
    pub.close()
    # publisher restart: must pick up at the cut phase (min-over-refs
    # would give 1 and skip phase 1), so the next fragment-complete
    # phase still gets published
    pub2 = Publisher(db, reg)
    assert pub2._last_cut_phase == 0
    for w in range(4):
        execs.accumulate(w, _delta(base, 0.02 * (w + 1)), phase=1,
                         fragment=1)
    assert pub2.completed_phase() == 1
    assert pub2.poll() is not None
    pub2.close()


# ---------------------------------------------------------------------
# service-level regression: defaults bit-identical, streaming works
# ---------------------------------------------------------------------

def _tiny_ds(tiny_docs, k=4):
    from repro.data import shard_documents
    docs, doms = tiny_docs
    return shard_documents(docs, doms % k, k)


def _svc_kwargs(key, base, **over):
    kw = dict(key=key, base_params=base, batch_size=4, peak_lr=1e-3,
              warmup=10, total_steps=100, num_workers=1)
    kw.update(over)
    return kw


@pytest.mark.slow
def test_service_fragments_default_config_bit_identical(tiny_cfg,
                                                        tiny_docs,
                                                        tiny_base):
    """fragments=4/stagger=0/fp32 through the full service == the
    unfragmented run, bit for bit — fragmentation alone changes only
    row granularity, never the math."""
    from repro.infra import TrainingService
    ds = _tiny_ds(tiny_docs)
    base, _ = tiny_base
    key = jax.random.PRNGKey(0)
    outs = {}
    for name, over in (("k1", {}), ("k4", dict(outer_fragments=4))):
        dcfg = DiPaCoConfig(levels=(2, 2), inner_steps=2, **over)
        with tempfile.TemporaryDirectory() as root:
            svc = TrainingService(tiny_cfg, dcfg, ds, ckpt_root=root,
                                  **_svc_kwargs(key, base))
            m = svc.run(2, tau=2)
            outs[name] = ({p: svc.path_params(p) for p in range(4)},
                          m["mean_loss"])
            svc.shutdown()
    assert outs["k1"][1] == outs["k4"][1]
    for p in range(4):
        for a, b in zip(jax.tree_util.tree_leaves(outs["k1"][0][p]),
                        jax.tree_util.tree_leaves(outs["k4"][0][p])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_service_streaming_staggered_overlap_and_quantization(
        tiny_cfg, tiny_docs, tiny_base):
    """Staggered int8 streaming: late fragments stay in flight while
    the shard starts its next phase, peak sync bytes drop well below
    the fp32 burst, and the run stays finite and close to baseline."""
    from repro.infra import TrainingService
    ds = _tiny_ds(tiny_docs)
    base, _ = tiny_base
    key = jax.random.PRNGKey(0)
    stats = {}
    for name, over in (
            ("burst", {}),
            ("stream", dict(outer_fragments=4, fragment_stagger=1,
                            comm_dtype="int8"))):
        dcfg = DiPaCoConfig(levels=(2, 2), inner_steps=2, **over)
        with tempfile.TemporaryDirectory() as root:
            svc = TrainingService(tiny_cfg, dcfg, ds, ckpt_root=root,
                                  **_svc_kwargs(key, base))
            m = svc.run(3, tau=2)
            assert svc.pending_fragments == []   # run() is a sync point
            qres = {r.path_id for r in svc.db.rows(kind="qres")}
            stats[name] = (m, dict(m["comm"]), qres)
            svc.shutdown()
    mb, cb, qb = stats["burst"]
    ms, cs, qs = stats["stream"]
    assert cb["peak_sync_bytes"] / cs["peak_sync_bytes"] >= 4.0
    assert np.isfinite(ms["mean_loss"])
    assert abs(ms["mean_loss"] - mb["mean_loss"]) / mb["mean_loss"] < 0.05
    # quantizer residual rows (the resume substrate) per shard — only
    # on the quantized run
    assert qb == set() and qs == {0, 1, 2, 3}


@pytest.mark.slow
def test_resume_ignores_orphan_qres_row(tiny_cfg, tiny_docs, tiny_base):
    """The qres (quantizer residual) row is committed just before its
    train row; a kill in that window leaves an orphan residual whose
    wire payload was never folded.  Resume must fall back to the last
    *committed* phase's residual — adopting the orphan would double-
    subtract the lost payload when the phase re-runs."""
    from repro.infra import TrainingService
    ds = _tiny_ds(tiny_docs)
    base, _ = tiny_base
    key = jax.random.PRNGKey(0)
    dcfg = DiPaCoConfig(levels=(2, 2), inner_steps=2, comm_dtype="int8")
    with tempfile.TemporaryDirectory() as root:
        svc = TrainingService(tiny_cfg, dcfg, ds, ckpt_root=root,
                              **_svc_kwargs(key, base))
        svc.run(1, tau=2)
        committed = {s: jax.tree_util.tree_leaves(svc._qresid[s])
                     for s in range(4)}
        # simulate the kill window: phase-1 residual written, train row
        # never committed
        orphan = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32) + 99.0, svc.path_params(0))
        svc.db.write(orphan, path_id=0, phase=1, step=4, kind="qres")
        svc.shutdown()
        res = TrainingService.resume(tiny_cfg, dcfg, ds, ckpt_root=root,
                                     **_svc_kwargs(key, base))
        assert res.clock[0] == 1          # phase 1 will re-run
        for a, b in zip(committed[0],
                        jax.tree_util.tree_leaves(res._qresid[0])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        res.shutdown()


def test_service_rejects_bad_comm_dtype(tiny_cfg, tiny_docs, tiny_base):
    from repro.infra import TrainingService
    ds = _tiny_ds(tiny_docs)
    base, _ = tiny_base
    dcfg = DiPaCoConfig(levels=(2, 2), comm_dtype="bf16")
    with tempfile.TemporaryDirectory() as root:
        with pytest.raises(ValueError, match="comm_dtype"):
            TrainingService(tiny_cfg, dcfg, ds, ckpt_root=root,
                            **_svc_kwargs(jax.random.PRNGKey(0), base))
