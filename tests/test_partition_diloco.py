"""Hypothesis property tests on the paper's core invariants:
partition coverage, mixing-matrix structure, DiLoCo outer-step algebra,
module-store assembly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.diloco import mix_deltas, outer_step
from repro.core.partition import (make_partition, mixing_matrices,
                                  paths_through_module)
from repro.models.config import DiPaCoConfig


@settings(max_examples=30, deadline=None)
@given(k1=st.integers(1, 4), k2=st.integers(1, 4), reps=st.integers(2, 12))
def test_partition_coverage(k1, k2, reps):
    part = make_partition(DiPaCoConfig(levels=(k1, k2)), reps)
    assert part.num_paths == k1 * k2
    # every repeat belongs to exactly one level
    for r in range(reps):
        lvl = part.level_of_repeat(r)
        assert part.boundaries[lvl] <= r < part.boundaries[lvl + 1]
    # paths through modules of a level partition the path set
    for l, K in enumerate((k1, k2)):
        all_paths = np.concatenate(
            [paths_through_module(part, l, e) for e in range(K)])
        assert sorted(all_paths.tolist()) == list(range(part.num_paths))


@settings(max_examples=25, deadline=None)
@given(k1=st.integers(1, 3), k2=st.integers(1, 3), reps=st.integers(2, 8),
       rescale=st.booleans(), seed=st.integers(0, 100))
def test_mixing_matrix_properties(k1, k2, reps, rescale, seed):
    part = make_partition(DiPaCoConfig(levels=(k1, k2)), reps)
    P = part.num_paths
    rng = np.random.default_rng(seed)
    alphas = rng.uniform(0.1, 1.0, P)
    mix, mix_s = mixing_matrices(part, np.arange(P), alphas,
                                 grad_norm_rescale=rescale)
    assert mix.shape == (reps, P, P)
    for r in range(reps):
        l = part.level_of_repeat(r)
        a = part.paths[:, l]
        m = mix[r]
        # row support = paths through the same module
        for w in range(P):
            support = np.nonzero(m[w] > 0)[0]
            assert set(support) <= set(np.nonzero(a == a[w])[0])
        if not rescale:
            np.testing.assert_allclose(m.sum(1), 1.0, atol=1e-6)
        else:
            counts = (a[:, None] == a[None, :]).sum(1)
            np.testing.assert_allclose(m.sum(1), np.sqrt(counts), atol=1e-5)
        # workers through the same module have identical rows (sync)
        for w, v in [(i, j) for i in range(P) for j in range(P)
                     if a[i] == a[j]]:
            np.testing.assert_allclose(m[w], m[v], atol=1e-12)


def _toy_tree(W, R, key):
    k1, k2 = jax.random.split(key)
    params = {"blocks": {"pos0": {"w": jax.random.normal(k1, (W, R, 4))}},
              "embed": {"e": jax.random.normal(k2, (W, 8))}}
    axes = {"blocks": {"pos0": {"w": ("layers", None)}},
            "embed": {"e": (None,)}}
    return params, axes


def test_identical_workers_identity():
    """If every worker holds identical deltas, mixing is a no-op
    (up to rescale)."""
    part = make_partition(DiPaCoConfig(levels=(2, 2)), 4)
    mix, mix_s = mixing_matrices(part, np.arange(4), None,
                                 grad_norm_rescale=False)
    params, axes = _toy_tree(1, 4, jax.random.PRNGKey(0))
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[0], (4, *x.shape[1:])), params)
    mixed = mix_deltas(stacked, axes, jnp.asarray(mix), jnp.asarray(mix_s))
    for a, b in zip(jax.tree_util.tree_leaves(mixed),
                    jax.tree_util.tree_leaves(stacked)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_outer_step_plain_average():
    """lr=1, momentum=0 outer step == module-wise weighted average of
    worker params (DiLoCo fixed point)."""
    part = make_partition(DiPaCoConfig(levels=(2,)), 2)
    W = part.num_paths
    mix, mix_s = mixing_matrices(part, np.arange(W), None,
                                 grad_norm_rescale=False)
    key = jax.random.PRNGKey(1)
    worker, axes = _toy_tree(W, 2, key)
    global_p = jax.tree_util.tree_map(
        lambda x: jnp.zeros_like(x) + 1.0, worker)
    state = {"momentum": jax.tree_util.tree_map(
        lambda x: jnp.zeros_like(x), global_p)}
    new_w, new_g, _ = outer_step(worker, global_p, state, axes,
                                 jnp.asarray(mix), jnp.asarray(mix_s),
                                 lr=1.0, momentum=0.0, nesterov=False)
    # theta' = theta - (theta - avg(w)) = avg over module group
    lvl0 = np.asarray(worker["blocks"]["pos0"]["w"])
    a = part.paths[:, 0]
    for w in range(W):
        grp = np.nonzero(a == a[w])[0]
        np.testing.assert_allclose(
            np.asarray(new_g["blocks"]["pos0"]["w"][w]),
            lvl0[grp].mean(0), atol=1e-5)


def test_path_specific_no_mixing():
    """Path-specific level (K_l = P): mixing is identity (footnote 1 —
    outer optimizer still applies, but no averaging)."""
    dcfg = DiPaCoConfig(levels=(2, 2), path_specific_levels=(1,))
    part = make_partition(dcfg, 4)
    mix, _ = mixing_matrices(part, np.arange(4), None,
                             grad_norm_rescale=False)
    for r in range(part.boundaries[1], 4):   # level-1 repeats
        np.testing.assert_allclose(mix[r], np.eye(4), atol=1e-12)


def test_module_store_roundtrip(tiny_cfg, tiny_base):
    from repro.core.module_store import ModuleStore
    params, axes = tiny_base
    part = make_partition(DiPaCoConfig(levels=(2, 2)),
                          tiny_cfg.pattern_repeats)
    store = ModuleStore(params, axes, part)
    for p in range(part.num_paths):
        asm = store.assemble(p)
        for a, b in zip(jax.tree_util.tree_leaves(asm),
                        jax.tree_util.tree_leaves(params)):
            assert a.shape == b.shape
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32))
    # mutate module (0,1); only paths through it change
    mod = store.module_params(0, 1)
    bumped = jax.tree_util.tree_map(
        lambda x: None if x is None else x + 1.0, mod)
    store.set_module(0, 1, bumped)
    for p in range(part.num_paths):
        asm = store.assemble(p)
        changed = not np.allclose(
            np.asarray(asm["blocks"]["pos0"]["norm1"], np.float32),
            np.asarray(params["blocks"]["pos0"]["norm1"], np.float32))
        assert changed == (part.module_of(p, 0) == 1)
