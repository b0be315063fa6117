"""Routing subsystem: k-means invariants, product k-means composition,
discriminative router training + bias calibration, overlap top-n."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.routing import (kmeans_assign, kmeans_fit,
                                product_kmeans_assign, product_kmeans_fit,
                                train_discriminative_router)
from repro.core.routing.kmeans import topn_assign


def _clustered(key, n, d, k, spread=0.1):
    kc, kn, ka = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (k, d)) * 3
    assign = jax.random.randint(ka, (n,), 0, k)
    return centers[assign] + spread * jax.random.normal(kn, (n, d)), assign


def test_kmeans_assignment_is_argmin():
    z, _ = _clustered(jax.random.PRNGKey(0), 200, 8, 4)
    c, a, _ = kmeans_fit(jax.random.PRNGKey(1), z, 4, iters=10)
    a2, d2 = kmeans_assign(z, c)
    brute = jnp.argmin(
        jnp.sum((z[:, None, :] - c[None]) ** 2, -1), -1)
    assert (np.asarray(a2) == np.asarray(brute)).all()


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 50), k=st.sampled_from([2, 4, 8]))
def test_kmeans_inertia_nonincreasing(seed, k):
    z, _ = _clustered(jax.random.PRNGKey(seed), 150, 6, k)
    c5, _, i5 = kmeans_fit(jax.random.PRNGKey(seed + 1), z, k, iters=5)
    c20, _, i20 = kmeans_fit(jax.random.PRNGKey(seed + 1), z, k, iters=20)
    assert float(i20) <= float(i5) + 1e-3


def test_kmeans_recovers_clusters():
    z, true = _clustered(jax.random.PRNGKey(2), 400, 8, 4, spread=0.05)
    c, a, _ = kmeans_fit(jax.random.PRNGKey(3), z, 4, iters=25)
    # purity close to 1 for well-separated clusters
    a, true = np.asarray(a), np.asarray(true)
    purity = sum(np.bincount(true[a == i]).max()
                 for i in range(4) if (a == i).any()) / len(a)
    assert purity > 0.95


def test_product_kmeans_composition():
    z, _ = _clustered(jax.random.PRNGKey(4), 300, 16, 4)
    cents, a = product_kmeans_fit(jax.random.PRNGKey(5), z, 3, iters=10)
    a2 = product_kmeans_assign(z, cents)
    assert (np.asarray(a) == np.asarray(a2)).all()
    assert np.asarray(a).max() < 9  # k^2 composite shards


def test_topn_overlap_superset():
    z, _ = _clustered(jax.random.PRNGKey(6), 100, 8, 4)
    c, a, _ = kmeans_fit(jax.random.PRNGKey(7), z, 4, iters=10)
    top2 = np.asarray(topn_assign(z, c, 2))
    a = np.asarray(kmeans_assign(z, c)[0])
    assert (top2[:, 0] == a).all()          # first choice = argmin


def test_discriminative_router_learns_and_calibrates():
    key = jax.random.PRNGKey(8)
    z, true = _clustered(key, 400, 16, 4, spread=0.2)
    router = train_discriminative_router(
        jax.random.PRNGKey(9), z, true, 4, steps=300, calibrate=True)
    pred = np.asarray(router.assign(z))
    acc = (pred == np.asarray(true)).mean()
    assert acc > 0.9
    # calibration: matches target distribution within a few percent
    frac = np.bincount(pred, minlength=4) / len(pred)
    target = np.bincount(np.asarray(true), minlength=4) / len(true)
    assert np.abs(frac - target).max() < 0.08


def test_rerouted_eval_runs(tiny_cfg, tiny_base, tiny_docs):
    from repro.core.routing.frequent import evaluate_rerouted
    from repro.core.routing import prefix_features
    docs, _ = tiny_docs
    docs = docs[:32]
    params, _ = tiny_base
    feats = prefix_features(params, tiny_cfg, jnp.asarray(docs))
    router = train_discriminative_router(
        jax.random.PRNGKey(0), feats,
        np.zeros(len(docs), np.int64), 2, steps=20, calibrate=False)
    res = evaluate_rerouted([params, params], tiny_cfg, router, params,
                            jnp.asarray(docs), every=16)
    assert np.isfinite(res["nll"]) and res["ppl"] > 0
