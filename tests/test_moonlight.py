"""Moonlight-16B-A3B (DeepSeek-V3 blocks) at CPU size against the plain
float32 reference in ``bench/reference_moonlight.py``, on its seeded
weights:

- prefill, then every absorbed decode step through the latent arena,
  against the reference's full forward (float32 program: a tight bound;
  bfloat16 program: a bound the float8 control exceeds);
- the dropless dispatch against the reference's MoE under a router bias
  that sends every token to two experts (a capacity dispatch drops);
- the reference following the experts a program ran, and judging how
  far they fall below its own choice;
- the ``mla_decode`` kernel (interpret mode) against the jnp branch over
  ring wrap, masked rows, a row offset and a layer index;
- ``ContinuousBatchingEngine`` serving the preset with bucketed prefill,
  each emitted token against the reference's greedy choice, and the
  model step's logits on the served text against the reference's;
- ``bench/flops_moonlight.py`` against ``launch/flopmodel.py``;
- the engine's routing counts on its prefill and decode spans, for a
  model with MoE layers only.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import api
from repro.models.config import MoEConfig

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "bench"))
import flops_moonlight  # noqa: E402
import reference  # noqa: E402
import reference_moonlight as ref  # noqa: E402

SEED = 3000001599
CACHE = 64


def model_mapping(cfg):
    """The reference's ``model`` mapping of a program config."""
    return {"num_layers": cfg.num_layers, "d_model": cfg.d_model,
            "num_heads": cfg.num_heads, "vocab_size": cfg.vocab_size,
            "dtype": cfg.dtype, "norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "first_dense": cfg.first_dense,
            "d_ff_dense": cfg.d_ff_dense,
            "kv_lora_rank": cfg.mla.kv_lora_rank,
            "qk_nope_head_dim": cfg.mla.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.mla.qk_rope_head_dim,
            "v_head_dim": cfg.mla.v_head_dim,
            "num_experts": cfg.moe.num_experts, "top_k": cfg.moe.top_k,
            "d_ff_expert": cfg.moe.d_ff_expert,
            "d_ff_shared": cfg.moe.d_ff_shared,
            "routed_scaling": cfg.moe.routed_scaling}


def _cfg(dtype="float32", impl="chunked"):
    return get_smoke_config("moonlight-16b-a3b").replace(
        dtype=dtype, attn_impl=impl, pallas_interpret=impl == "pallas")


def _weights(cfg):
    w = ref.make_weights(model_mapping(cfg), SEED)[0]
    want = jax.eval_shape(lambda: api.init_model(jax.random.PRNGKey(0),
                                                 cfg)[0])
    assert (jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), w)
            == jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), want))
    return w


def _tokens(cfg, b=2, s=24):
    return jax.random.randint(jax.random.PRNGKey(5), (b, s), 0,
                              cfg.vocab_size)


def _served_logits(cfg, w, tokens, split):
    """The program's logits at every position: prefill of the first
    ``split`` tokens, then one absorbed decode step per later token."""
    logits, cache = api.prefill(w, cfg, {"tokens": tokens[:, :split]},
                                CACHE)
    out = [logits]
    for t in range(split, tokens.shape[1]):
        lg, cache = api.serve_step(w, cfg, {"tokens": tokens[:, t:t + 1]},
                                   cache, jnp.int32(t))
        out.append(lg)
    return np.asarray(jnp.concatenate(out, 1), np.float32)


@pytest.mark.parametrize("impl", ["chunked", "pallas"])
def test_prefill_and_absorbed_decode_match_reference(impl):
    """float32 program, float32 reference at HIGHEST: the two differ
    only in summation order and in the form of the attention (published
    in the reference and the prefill, absorbed in each decode step), so
    logits agree to 1e-3 of logits of order one; a routing flip at a
    near-tie, a wrong rotary position or a stale latent would show as an
    error of order 0.1 or more.  The float8 control breaks the bound."""
    cfg = _cfg("float32", impl)
    w = _weights(cfg)
    tokens = _tokens(cfg)
    want = np.asarray(ref.forward(w, model_mapping(cfg), tokens))
    got = _served_logits(cfg, w, tokens, split=10)
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=0)
    fp8 = np.asarray(ref.forward(w, model_mapping(cfg), tokens,
                                 reference.matmul_fp8))
    assert np.max(np.abs(fp8 - want)) > 1e-3


def test_bf16_program_meets_a_bound_the_float8_control_exceeds():
    """bfloat16 program (bf16 weights and activations, f32 accumulation)
    against the float32 reference on the same weights.  Its logits carry
    bf16 rounding, about 2^-8 of the activations: the median error stays
    under 0.03; the median, because a bf16 routing flip at a near-tie
    moves the few positions after it by much more.  The reference with
    float8 operands (2^-4) lies well above the bound."""
    cfg = _cfg("bfloat16")
    w = _weights(cfg)
    tokens = _tokens(cfg)
    m = model_mapping(cfg)
    want = np.asarray(ref.forward(w, m, tokens))
    got = _served_logits(cfg, w, tokens, split=10)
    fp8 = np.asarray(ref.forward(w, m, tokens, reference.matmul_fp8))
    program_err = np.median(np.abs(got - want))
    control_err = np.median(np.abs(fp8 - want))
    assert program_err < 0.03, program_err
    assert control_err > 0.03, control_err


def _moe_cfg(impl, dispatch="dropless"):
    base = _cfg("float32", impl)
    return base.replace(moe=MoEConfig(
        num_experts=8, top_k=2, d_ff_expert=64, num_shared=1,
        d_ff_shared=64, impl=dispatch, router="sigmoid",
        routed_scaling=2.446))


@pytest.mark.parametrize("impl", ["chunked", "pallas"])
def test_dropless_dispatch_matches_reference_under_skew(impl):
    """A router bias of +10 on experts 0 and 1 sends every token there
    (the bias chooses; the gates stay the sigmoid scores): each of the
    two experts gets all 96 tokens, four times a 1.25 capacity.  The
    dropless dispatch computes every pair, as the reference does; the
    capacity dispatch, run on the same weights, drops tokens and
    differs."""
    from repro.models.moe_layer import apply_moe, routing_counts
    cfg = _moe_cfg(impl)
    w = _weights(cfg)["blocks"]["pos0"]["mlp"]
    w = jax.tree_util.tree_map(lambda t: t[0], w)
    w["router_bias"] = w["router_bias"].at[:2].add(10.0)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 48, cfg.d_model))
    y, _, experts = apply_moe(w, cfg, x)
    assert routing_counts(experts, 8).tolist() == [2, 96]
    want = ref._moe(w, model_mapping(cfg), x.reshape(96, -1),
                    reference.matmul_f32)[0].reshape(x.shape)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4,
                               rtol=1e-4)
    capped, _, _ = apply_moe(w, _moe_cfg(impl, "dense"), x)
    assert np.max(np.abs(np.asarray(capped) - np.asarray(want))) > 1e-2


@pytest.mark.parametrize("bias, regret", [((0.3, 0.1, 0.1, 0.0), 0.0),
                                          ((0.3, 0.2, 0.1, 0.0), 0.1)])
def test_reference_runs_the_programs_experts_and_judges_them(bias, regret):
    """With the router's weights zero every sigmoid score is 0.5, so the
    bias alone orders the 4 experts: the reference's own top 2 are
    experts 0 and 1.  A program that ran experts 0 and 2 instead is
    followed (the reference runs 0 and 2) and judged by its regret, the
    2nd choice value less the 3rd's: 0 where they tie (a near-tie may go
    either way), 0.1 where they part."""
    cfg = _cfg("float32")
    w = _weights(cfg)
    m = model_mapping(cfg)
    mlp = w["blocks"]["pos0"]["mlp"]
    w["blocks"]["pos0"]["mlp"] = {
        **mlp, "router": jnp.zeros_like(mlp["router"]),
        "router_bias": jnp.broadcast_to(jnp.asarray(bias, jnp.float32),
                                        mlp["router_bias"].shape)}
    tokens = _tokens(cfg, b=1)
    _, own, none = ref.hidden(w, m, tokens)
    assert np.asarray(own).tolist() == [[[[0, 1]]] * tokens.shape[1]]
    np.testing.assert_array_equal(np.asarray(none), 0.0)
    route = jnp.broadcast_to(jnp.asarray([0, 2], jnp.int32), own.shape)
    _, ran, got = ref.hidden(w, m, tokens, route=route)
    np.testing.assert_array_equal(np.asarray(ran), np.asarray(route))
    np.testing.assert_allclose(np.asarray(got), regret, atol=1e-6)
    # random "served" tokens: most are not the reference's first choice
    experts = np.asarray(route[0]).transpose(1, 0, 2)[:, :-1]
    out = ref.served_gaps([w], m, [(0, np.asarray(tokens[0]), 10, experts)],
                          rows=1, length=CACHE, most_served=14)
    assert out["tokens"] == 14 and out["served_miss_share"] > 0.5
    assert 0 < out["served_gap_max"] < np.inf
    assert out["routing_regret_max"] == pytest.approx(regret, abs=1e-6)


@pytest.mark.parametrize("block_k", [32, 128])
def test_mla_decode_kernel_matches_jnp_branch(block_k):
    """Three rows at offset 2 into a 6-row, 3-layer latent arena, read at
    layer 1: one row before the ring wraps (blocks past its newest entry
    are skipped), one exactly at the end of the ring, one wrapped twice;
    the middle row is masked off (its arena row keeps its bytes, its
    output is not compared)."""
    from repro.kernels.ops import mla_decode
    from repro.models.layers import _latent_attention, mla_scale
    cfg = _cfg("float32")
    c, r, T = cfg.mla.latent_dim, cfg.mla.kv_lora_rank, 128
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    arena = jax.random.normal(ks[0], (3, 6, c, T))
    q = jax.random.normal(ks[1], (3, cfg.num_heads, c))
    ci = jnp.array([40, 127, 300], jnp.int32)
    got = mla_decode(q, arena, ci, 1, kv_rank=r, scale=mla_scale(cfg),
                     row_offset=2, block_k=block_k, interpret=True)
    want = _latent_attention(cfg, q, arena[1, 2:5], ci)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)

    from repro.models.layers import mla_attention_inplace
    lat = jax.random.normal(ks[2], (3, 1, c))
    mask = jnp.array([True, False, True])
    outs = {}
    for impl in ("chunked", "pallas"):
        out, new = mla_attention_inplace(
            _cfg("float32", impl), q[:, None], lat, {"lat": arena}, 1, ci,
            mask=mask, row_offset=2)
        outs[impl] = (np.asarray(out), np.asarray(new["lat"]))
    np.testing.assert_allclose(outs["pallas"][0][[0, 2]],
                               outs["chunked"][0][[0, 2]], atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_array_equal(outs["pallas"][1], outs["chunked"][1])
    new = outs["pallas"][1]
    np.testing.assert_array_equal(new[1, 3], np.asarray(arena[1, 3]))
    for row, (b, t) in ((2, (0, 40)), (4, (2, 300 % T))):
        np.testing.assert_array_equal(new[1, row, :, t], np.asarray(lat[b, 0]))
    untouched = np.ones(new.shape[:2], bool)
    untouched[1, [2, 4]] = False
    np.testing.assert_array_equal(new[untouched],
                                  np.asarray(arena)[untouched])


@pytest.mark.parametrize("impl", ["chunked", "pallas"])
def test_engine_serves_moonlight_with_bucketed_prefill(impl, monkeypatch):
    """The continuous engine on one path, 3 slots, bucketed prefill with
    a prefill budget of 32 positions (two rows of 16, one of 32): every
    token it emits (from prefill and decode ticks) is the reference's
    greedy choice at that position wherever the reference's best logit
    leads the second by more than twice the float32 bound of the first
    test, and the model step (prefill of the padded prompt, then one
    decode step per served token) gives the reference's logits on the
    served text to that bound.  Each finished request carries the
    experts every position but its last ran, the reference's own choice
    (float32 on both sides)."""
    from repro.serving import ContinuousBatchingEngine, EngineOptions
    from repro.serving import Request
    from repro.serving import engine as engine_mod
    monkeypatch.setattr(engine_mod, "PREFILL_TOKENS", 32)
    cfg = _cfg("float32", impl)
    w = _weights(cfg)
    eng = ContinuousBatchingEngine(cfg, [w], options=EngineOptions(
        cache_len=CACHE, slots_per_path=3, prefill_buckets=(16, 32)))
    assert eng._row_buckets(16) == [1, 2] and eng._row_buckets(64) == [1]
    assert eng.bucketed and eng._stacked_params is w   # held once
    seen = {}
    emit = eng._emit_tick

    def record(now):
        for st in eng.in_flight.values():
            seen.setdefault(st.req.rid, []).append(
                (len(st.tokens), st.next_token))
        return emit(now)

    eng._emit_tick = record
    rng = np.random.default_rng(4)
    for rid, n in enumerate([9, 14, 20, 30]):
        eng.submit(Request(rid=rid, prompt=rng.integers(
            0, cfg.vocab_size, n, dtype=np.int32), max_new=6))
    fins = []
    while not eng.idle:
        fins += eng.step()
    assert len(fins) == 4
    m = model_mapping(cfg)
    prefill = jax.jit(lambda t, last: api.prefill(
        w, cfg, {"tokens": t}, CACHE, last=last))
    step = jax.jit(lambda t, c, i: api.serve_step(w, cfg, {"tokens": t},
                                                  c, i))
    decided = 0
    for f in fins:
        want = np.asarray(ref.forward(w, m, jnp.asarray(f.tokens[None])))[0]
        assert [n for n, _ in seen[f.rid]] == list(range(
            len(f.tokens) - 6, len(f.tokens)))
        for n, tok in seen[f.rid]:
            assert tok == f.tokens[n]
            second, best = np.sort(want[n - 1])[-2:]
            if best - second > 2e-3:
                decided += 1
                assert tok == np.argmax(want[n - 1])
        # the model step on the served text: the prompt padded to 32 as
        # the engine's bucket, then one decode step per served token
        plen = len(f.tokens) - 6
        tok = np.zeros((1, 32), np.int32)
        tok[0, :plen] = f.tokens[:plen]
        lg, cache = prefill(jnp.asarray(tok), jnp.asarray([plen - 1]))
        got = [lg[0]]
        for t in range(plen, len(f.tokens) - 1):
            lg, cache = step(jnp.asarray(f.tokens[None, t:t + 1]), cache,
                             jnp.int32(t))
            got.append(lg[0, 0])
        np.testing.assert_allclose(np.stack(got), want[plen - 1:-1],
                                   atol=1e-3, rtol=0)
        own = np.asarray(ref.hidden(w, m, jnp.asarray(f.tokens[None]))[1])
        assert f.experts.shape == (cfg.num_layers - cfg.first_dense,
                                   len(f.tokens) - 1, cfg.moe.top_k)
        np.testing.assert_array_equal(
            np.sort(f.experts, -1),
            np.sort(own[0, :-1].transpose(1, 0, 2), -1))
    assert decided >= 20


@pytest.mark.parametrize("part", ["mla", "moe", "dense", "token", "layers"])
@pytest.mark.parametrize("absorbed", [False, True])
def test_flops_moonlight_equals_flopmodel(part, absorbed):
    """The benchmark's copy of the FLOP counts is the program's
    ``launch/flopmodel.py`` on the benchmark's 9-layer configuration."""
    import json

    from repro.launch import flopmodel
    from repro.models.config import InputShape
    path = os.path.join(os.path.dirname(__file__), "..", "bench",
                        "configs", "moonlight-16b-a3b.json")
    with open(path) as f:
        m = json.load(f)["model"]
    cfg = get_config("moonlight-16b-a3b").replace(
        num_layers=m["num_layers"])
    assert model_mapping(cfg).items() <= m.items()
    s = 3000.0
    if part == "mla":
        got = flops_moonlight.mla_flops(m, s, absorbed)
        want = flopmodel.mla_flops_per_token(cfg, s, absorbed)
    elif part == "moe":
        got = flops_moonlight.moe_flops(m)
        want = flopmodel.moe_flops_per_token(cfg, 1.0)
    elif part == "dense":
        got = flops_moonlight.dense_mlp_flops(m)
        want = flopmodel.mlp_flops_per_token(cfg, cfg.d_ff_dense)
    elif part == "layers":
        got = flops_moonlight.layers_flops(m, s, absorbed)
        want = sum(
            n * flopmodel.block_flops_per_token(cfg, spec, s, 1.0, d_ff,
                                                absorbed)
            for spec, n, d_ff in ((cfg.lead_spec, cfg.first_dense,
                                   cfg.d_ff_dense),
                                  (cfg.pattern[0], cfg.pattern_repeats,
                                   cfg.d_ff)))
    else:
        kind = "decode" if absorbed else "prefill"
        shape = InputShape("one", int(s), 1, kind)
        want = flopmodel.analyze(cfg, shape).fwd_flops
        got = (flops_moonlight.token_flops(m, s) if absorbed else
               s * (flops_moonlight.layers_flops(m, s, False)
                    + flops_moonlight.head_flops(m)))
    assert got == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("arch", ["moonlight-16b-a3b", "dipaco-150m"])
def test_engine_spans_carry_routing_counts_only_with_moe(arch, tmp_path):
    """Each prefill and decode of a model with MoE layers records, on its
    ``serve.prefill`` / ``serve.decode`` span, the experts its rows chose
    (summed over the MoE layers) and the most tokens one expert got, as
    the program counted them beside its logits; a model without MoE
    records neither."""
    from repro.obs import Telemetry, read_trace
    from repro.serving import ContinuousBatchingEngine, EngineOptions
    from repro.serving import Request
    if arch == "dipaco-150m":
        cfg = get_smoke_config(arch)
        w = api.init_model(jax.random.PRNGKey(0), cfg)[0]
    else:
        cfg = _cfg()
        w = _weights(cfg)
    tel = Telemetry(tmp_path / "serve.jsonl", fresh=True)
    eng = ContinuousBatchingEngine(cfg, [w], options=EngineOptions(
        cache_len=CACHE, slots_per_path=2, prefill_buckets=(16,),
        telemetry=tel))
    rng = np.random.default_rng(6)
    for rid, n in enumerate([9, 12, 15]):
        eng.submit(Request(rid=rid, prompt=rng.integers(
            0, cfg.vocab_size, n, dtype=np.int32), max_new=3))
    while not eng.idle:
        eng.step()
    tel.close()
    records, _ = read_trace(tmp_path / "serve.jsonl")
    spans = [r for r in records if r["k"] == "span"
             and r["name"] in ("serve.prefill", "serve.decode")]
    assert {r["name"] for r in spans} == {"serve.prefill", "serve.decode"}
    for r in spans:
        a = r["args"]
        if cfg.moe is None:
            assert "experts_hit" not in a and "max_expert_tokens" not in a
            continue
        layers = cfg.num_layers - cfg.first_dense
        assert 1 <= a["experts_hit"] <= layers * cfg.moe.num_experts
        rows = a.get("padded_tokens", a.get("slots"))
        assert 1 <= a["max_expert_tokens"] <= rows
