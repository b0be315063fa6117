"""The engine's decode programs against a plain copy of the select-based
stacked tick they replace.

The oracle below is the tick as it was before the KV arena went
layer-major and tokens-minor: ``vmap`` over paths of a per-path decode
whose layer ``scan`` takes each layer's cache ``(S, T, KH, D)`` in
``xs`` and returns a new one in ``ys``, then a ``jnp.where`` over the
whole arena keeps the rows the mask leaves out.  Its attention is the
kernel oracle ``ref.flash_decode_ref``.  Over several ticks with mixed
masks (free rows, rows prefilled this tick, active rows) and positions
past the ring's end, the engine's programs must give the oracle's
greedy tokens on the active rows, write the oracle's keys and values
there, and leave every masked-off row's cache bitwise as it was; the
model step each program wraps (``api.serve_step``, on the same inputs)
must give the oracle's logits there.  Pallas runs in interpret mode;
the config is the f32 smoke path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.models import api
from repro.models.layers import (apply_mlp, attention_out, attention_qkv,
                                 embed_tokens, rms_norm, unembed)
from repro.serving import ContinuousBatchingEngine, EngineOptions

PATHS, SLOTS, T, TICKS = 3, 2, 16, 4


def _quant(x):
    """int8 KV over D with per-(token, head) scales, as the model's."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
                        / 127.0, 1e-8)
    return (jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8),
            scale[..., 0])


def _old_decode(params, cfg, tok, cache, idx):
    """One path: cache leaves (reps, S, T, KH, D) / scales (reps, S, T,
    KH), a layer scan with the cache in xs and ys."""
    rows = jnp.arange(tok.shape[0])
    slot = idx % T

    def body(h, xs):
        bp, c = xs
        bp, c = bp["pos0"], dict(c["pos0"])
        hn = rms_norm(bp["norm1"], h, cfg.norm_eps)
        q, k, v = attention_qkv(bp["mixer"], cfg, hn, positions=idx[:, None])
        new = {"k": k[:, 0], "v": v[:, 0]}
        if "k_scale" in c:
            new["k"], new["k_scale"] = _quant(new["k"])
            new["v"], new["v_scale"] = _quant(new["v"])
        c = {n: c[n].at[rows, slot].set(new[n].astype(c[n].dtype))
             for n in c}
        # the kernel oracle takes a (layers, rows, KH, D, T) stack
        kv = {n: jnp.moveaxis(c[n], 1, -1)[None] for n in c}
        out = ref.flash_decode_ref(
            q[:, 0], kv["k"], kv["v"], idx, window=cfg.sliding_window,
            k_scale=kv.get("k_scale"), v_scale=kv.get("v_scale"))
        h = h + attention_out(bp["mixer"], out[:, None].astype(h.dtype), hn)
        h = h + apply_mlp(bp["mlp"], cfg,
                          rms_norm(bp["norm2"], h, cfg.norm_eps))
        return h, {"pos0": c}

    h, new = jax.lax.scan(body, embed_tokens(params["embed"], cfg, tok),
                          (params["blocks"], cache))
    h = rms_norm(params["final_norm"], h, cfg.norm_eps)
    return unembed(params["embed"], cfg, h)[:, 0], new


def _old_tick(paths, cfg, tok, cache, idx, mask):
    """The select-based stacked tick: (P, ...) params, tokens, positions
    and mask; cache leaves (P, reps, S, ...)."""
    def one(params, tok, cache, idx, mask):
        logits, new = _old_decode(params, cfg, tok, cache, idx)
        return logits, jax.tree_util.tree_map(
            lambda n, o: jnp.where(
                mask.reshape((1, -1) + (1,) * (n.ndim - 2)), n, o),
            new, cache)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *paths)
    return jax.jit(jax.vmap(one))(stacked, tok, cache, idx, mask)


def _to_old(cache, paths):
    """Row-folded (reps, P*S, KH, D, T) -> the oracle's (P, reps, S, T,
    KH, D) (scales (P, reps, S, T, KH))."""
    def one(x):
        x = jnp.moveaxis(x, -1, 2)                  # tokens after rows
        x = x.reshape((x.shape[0], paths, -1) + x.shape[2:])
        return jnp.moveaxis(x, 1, 0)
    return jax.tree_util.tree_map(one, cache)


def _random_cache(cfg, rows, key):
    """An arena full of distinct values: int8 keys and values, positive
    scales, normal floats."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(
        api.init_serve_cache(cfg, rows, T))
    out = []
    for i, (path, x) in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        if x.dtype == jnp.int8:
            out.append(jax.random.randint(k, x.shape, -127, 128, jnp.int8))
        elif jax.tree_util.keystr(path).endswith("scale']"):
            out.append(jax.random.uniform(k, x.shape, x.dtype, 0.001, 0.02))
        else:
            out.append(jax.random.normal(k, x.shape, x.dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def _tick_inputs(cfg, t, rows):
    """Tick t's tokens, positions and mask: row r is active on most
    ticks, free (mask False, parked at 0) or prefilled this tick (mask
    False) on others; positions run past T so the ring wraps."""
    rng = np.random.default_rng(100 + t)
    tok = rng.integers(0, cfg.vocab_size, (rows, 1)).astype(np.int32)
    pos = (np.arange(rows) * 7 + 3 * t + 5).astype(np.int32)
    kind = (np.arange(rows) + t) % 4           # 0 free, 1 prefilled
    pos[kind == 0] = 0
    return tok, pos, kind >= 2


CASES = {
    "pallas": dict(attn_impl="pallas"),
    "xla": dict(attn_impl="chunked"),
    "pallas-int8kv": dict(attn_impl="pallas", kv_quant=True),
    "xla-int8kv": dict(attn_impl="chunked", kv_quant=True),
    "pallas-window": dict(attn_impl="pallas", sliding_window=6),
}


@pytest.fixture(scope="module")
def paths():
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("dipaco-150m")
    return [api.init_model(jax.random.PRNGKey(p), cfg)[0]
            for p in range(PATHS)]


def _engine(over, paths, stacked=True, num_paths=PATHS):
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("dipaco-150m").replace(pallas_interpret=True,
                                                  **over)
    return cfg, ContinuousBatchingEngine(cfg, paths[:num_paths],
                                         options=EngineOptions(
        cache_len=T, slots_per_path=SLOTS, stacked=stacked))


def _check(ids, logits, cache, want_logits, want_cache, before, mask,
           n_paths):
    """Active rows match the oracle: the model step's logits within
    1e-4, the program's greedy ids wherever the oracle's best logit
    leads the second by more than twice that; masked-off rows keep
    their bytes."""
    ids, logits = np.asarray(ids), np.asarray(logits)
    want_logits = np.asarray(want_logits).reshape(logits.shape)
    assert ids.dtype == np.int32 and ids.shape == logits.shape[:1]
    np.testing.assert_allclose(logits[mask], want_logits[mask],
                               atol=1e-4, rtol=1e-4)
    top2 = np.sort(want_logits, -1)[:, -2:]
    tol = 1e-4 + 1e-4 * np.abs(top2[:, 1])
    decided = mask & (top2[:, 1] - top2[:, 0] > 2 * tol)
    assert decided.sum() >= mask.sum() - 1
    np.testing.assert_array_equal(ids[decided],
                                  want_logits[decided].argmax(-1))
    for new, old in zip(jax.tree_util.tree_leaves(cache),
                        jax.tree_util.tree_leaves(before)):
        new, old = np.asarray(new), np.asarray(old)
        assert new.dtype == old.dtype and new.shape == old.shape
        np.testing.assert_array_equal(new[:, ~mask], old[:, ~mask])
    for new, want in zip(jax.tree_util.tree_leaves(_to_old(cache, n_paths)),
                         jax.tree_util.tree_leaves(want_cache)):
        new, want = np.asarray(new), np.asarray(want)
        assert new.dtype == want.dtype
        # an int8 entry may round one unit apart on a last-bit difference
        tol = 1 if new.dtype == np.int8 else 1e-5
        np.testing.assert_allclose(new.astype(np.float32),
                                   want.astype(np.float32), atol=tol,
                                   rtol=1e-5)


def _copy(tree):
    return jax.tree_util.tree_map(jnp.copy, tree)


def _model_step(cfg, paths=None, island=False):
    """The model step a decode program wraps, on the program's own
    arguments: its ``(rows, vocab)`` logits, the cache dropped."""
    if island:
        def step(params, p, row0, tok, cache, idx, mask):
            return api.serve_step(params, cfg, {"tokens": tok}, cache, idx,
                                  mask=mask, row_offset=row0, path=p)[0]
    else:
        def step(params, tok, cache, idx, mask):
            return api.serve_step(params, cfg, {"tokens": tok}, cache, idx,
                                  mask=mask, paths=paths)[0]
    return jax.jit(lambda *a: step(*a)[:, 0])


@pytest.mark.parametrize("case", list(CASES))
def test_stacked_tick_matches_select_oracle(paths, case):
    """The dense stacked tick: one dispatch over all P*S rows."""
    cfg, eng = _engine(CASES[case], paths)
    step = _model_step(cfg, paths=PATHS)
    rows = PATHS * SLOTS
    cache = _random_cache(cfg, rows, jax.random.PRNGKey(7))
    for t in range(TICKS):
        tok, pos, mask = _tick_inputs(cfg, t, rows)
        inputs = (jnp.asarray(tok), _copy(cache), jnp.asarray(pos),
                  jnp.asarray(mask))
        logits = step(eng._stacked_params, *inputs)
        ids, new = eng._decode_stacked(eng._stacked_params, *inputs)
        want_logits, want = _old_tick(
            paths, cfg, tok.reshape(PATHS, SLOTS, 1), _to_old(cache, PATHS),
            pos.reshape(PATHS, SLOTS), mask.reshape(PATHS, SLOTS))
        _check(ids, logits, new, want_logits, want, cache, mask, PATHS)
        cache = new


def test_island_tick_matches_select_oracle(paths):
    """The sparse tick: one island's rows, at their offset into the
    stacked arena, decoded in place; every other island's rows stay."""
    cfg, eng = _engine(CASES["pallas"], paths)
    step = _model_step(cfg, island=True)
    rows = PATHS * SLOTS
    cache = _random_cache(cfg, rows, jax.random.PRNGKey(8))
    for t in range(TICKS):
        tok, pos, mask = _tick_inputs(cfg, t, rows)
        p = t % PATHS
        mine = slice(p * SLOTS, (p + 1) * SLOTS)
        inputs = (eng._stacked_params, jnp.int32(p), jnp.int32(p * SLOTS),
                  jnp.asarray(tok[mine]), _copy(cache),
                  jnp.asarray(pos[mine]), jnp.asarray(mask[mine]))
        logits = step(*inputs)
        ids, new = eng._decode_island(*inputs)
        island = np.zeros(rows, bool)
        island[mine] = mask[mine]
        want_logits, want = _old_tick(
            paths, cfg, tok.reshape(PATHS, SLOTS, 1), _to_old(cache, PATHS),
            pos.reshape(PATHS, SLOTS), island.reshape(PATHS, SLOTS))
        full_ids = np.zeros(rows, np.int32)
        full_ids[mine] = np.asarray(ids)
        full = np.zeros((rows,) + logits.shape[1:], np.float32)
        full[mine] = np.asarray(logits)
        _check(full_ids, full, new, want_logits, want, cache, island, PATHS)
        cache = new


def test_unstacked_tick_matches_select_oracle(paths):
    """``stacked=False``: one island's own arena, its own params."""
    cfg, eng = _engine(CASES["pallas-int8kv"], paths, stacked=False,
                       num_paths=1)
    step = _model_step(cfg)
    cache = _random_cache(cfg, SLOTS, jax.random.PRNGKey(9))
    for t in range(TICKS):
        tok, pos, mask = _tick_inputs(cfg, t, SLOTS)
        inputs = (eng.paths[0], jnp.asarray(tok), _copy(cache),
                  jnp.asarray(pos), jnp.asarray(mask))
        logits = step(*inputs)
        ids, new = eng._decode_masked(*inputs)
        want_logits, want = _old_tick(
            paths[:1], cfg, tok[None], _to_old(cache, 1), pos[None],
            mask[None])
        _check(ids, logits, new, want_logits, want, cache, mask, 1)
        cache = new
