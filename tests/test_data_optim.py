"""Data pipeline + optimizers: determinism, sharding, schedules."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data import SyntheticCorpus, shard_documents
from repro.data.loader import ShardLoader, phase_batches
from repro.optim import (adamw_init, adamw_update, cosine_schedule,
                         nesterov_init, nesterov_update)


def test_corpus_deterministic():
    c1 = SyntheticCorpus(seed=3)
    c2 = SyntheticCorpus(seed=3)
    d1 = c1.sample_documents(16, seed=5)
    d2 = c2.sample_documents(16, seed=5)
    np.testing.assert_array_equal(d1, d2)


def test_corpus_domain_signal():
    """Domain bigram structure must be learnable: within-domain bigram
    agreement >> cross-domain."""
    c = SyntheticCorpus(vocab_size=256, num_domains=4, seq_len=128,
                        bigram_q=0.8, seed=0)
    docs, doms = c.sample_documents(64, return_domains=True)
    hit = []
    for d in range(4):
        sel = docs[doms == d]
        if len(sel) == 0:
            continue
        pi = c.perms[d]
        hit.append((pi[sel[:, :-1]] == sel[:, 1:]).mean())
    assert min(hit) > 0.6   # ~bigram_q


@settings(max_examples=10, deadline=None)
@given(n=st.integers(10, 200), k=st.integers(2, 8),
       topn=st.integers(1, 3))
def test_sharder_overlap_and_coverage(n, k, topn):
    docs = np.arange(n * 4, dtype=np.int32).reshape(n, 4)
    rng = np.random.default_rng(0)
    assign = rng.integers(0, k, size=(n, topn))
    ds = shard_documents(docs, assign, k)
    # every doc appears in every shard it was assigned to
    total = sum(len(s) for s in ds.shards)
    uniq_assign = sum(len(np.unique(assign[i])) for i in range(n))
    assert total == uniq_assign
    assert abs(ds.alphas().sum() - 1.0) < 1e-9


def test_phase_batches_deterministic():
    toks = np.arange(400, dtype=np.int32).reshape(100, 4)
    b1 = phase_batches(toks, 8, 5, shard_id=2, phase=3)
    b2 = phase_batches(toks, 8, 5, shard_id=2, phase=3)
    b3 = phase_batches(toks, 8, 5, shard_id=2, phase=4)
    np.testing.assert_array_equal(b1, b2)
    assert not np.array_equal(b1, b3)
    assert b1.shape == (5, 8, 4)


def test_cosine_schedule_shape():
    assert float(cosine_schedule(0, peak_lr=1e-3, warmup=100,
                                 total_steps=1000)) == 0.0
    assert abs(float(cosine_schedule(100, peak_lr=1e-3, warmup=100,
                                     total_steps=1000)) - 1e-3) < 1e-9
    end = float(cosine_schedule(1000, peak_lr=1e-3, warmup=100,
                                total_steps=1000))
    assert end < 2e-4  # decays to final_frac * peak


def test_adamw_first_step_direction():
    params = {"w": jnp.ones((3,))}
    grads = {"w": jnp.asarray([1.0, -1.0, 0.0])}
    st_ = adamw_init(params)
    new, st_ = adamw_update(grads, st_, params, lr=0.1, weight_decay=0.0,
                            grad_clip=None)
    # adam first step = -lr * sign(g)
    np.testing.assert_allclose(np.asarray(new["w"]),
                               [1 - 0.1, 1 + 0.1, 1.0], atol=1e-3)


def test_nesterov_accumulates():
    params = {"w": jnp.zeros((2,))}
    g = {"w": jnp.ones((2,))}
    st_ = nesterov_init(params)
    p1, st_ = nesterov_update(g, st_, params, lr=1.0, momentum=0.9)
    # buf = 1; step = g + mu*buf = 1.9
    np.testing.assert_allclose(np.asarray(p1["w"]), [-1.9, -1.9], atol=1e-6)
    p2, st_ = nesterov_update(g, st_, p1, lr=1.0, momentum=0.9)
    # buf = 0.9 + 1 = 1.9; step = 1 + 0.9*1.9 = 2.71
    np.testing.assert_allclose(np.asarray(p2["w"]),
                               [-1.9 - 2.71] * 2, atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100))
def test_adamw_decreases_quadratic(seed):
    key = jax.random.PRNGKey(seed)
    target = jax.random.normal(key, (8,))
    params = {"w": jnp.zeros((8,))}
    st_ = adamw_init(params)

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2)

    l0 = float(loss(params))
    for _ in range(50):
        g = jax.grad(loss)(params)
        params, st_ = adamw_update(g, st_, params, lr=0.05,
                                   weight_decay=0.0)
    assert float(loss(params)) < l0 * 0.5
