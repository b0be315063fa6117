"""Seeded weights and the plain float32 reference of Moonlight-16B-A3B
(DeepSeek-V3 blocks), cut to the configuration's depth.

Nothing here imports the program.  ``make_weights`` draws one path's
weights on the device in one jitted call, in the tree and types the
engine serves (checked against the program's own tree by
``bench/drivers/serve_offline_moonlight.py``):
projections normal / sqrt(fan-in), the embedding 0.02, RMSNorm scales
ones, the router's per-expert bias normal x 0.02 (it chooses experts, so
a bias of zeros would leave that part of the router untested).

``forward`` is the architecture as published, in ``jax.numpy``:

- layer 0 dense, then MoE layers; pre-norm RMSNorm (eps from the
  configuration) before the mixer and before the MLP;
- latent attention in its published (non-absorbed) form: q one
  projection to 16 heads of 128 + 64; ``w_dkv`` to a 512 latent,
  RMS-normalised, and a 64 rotary key shared by all heads; per-head keys
  ``[c . W_uk, k_pe]`` and values ``c . W_uv``; RoPE (theta from the
  configuration) rotates halves, which is the published interleaved
  pairing up to a fixed permutation of the rotary columns of random
  weights; causal softmax scaled by 192^-0.5; output projection;
- MoE: sigmoid scores of the router; the bias is added to choose the
  top 6 and left out of the gates; the 6 chosen scores normalised to sum
  1 and scaled by 2.446; each token goes through exactly its 6 experts
  (SwiGLU of 1408; no capacity, nothing dropped), plus the shared
  SwiGLU of 2816; given a program's routing, the 6 experts the program
  ran there instead (``served_gaps`` says why);
- final RMSNorm and the untied head.

Every matmul goes through ``mm``: float32 at ``HIGHEST`` precision, or
float8 e4m3 operands with per-tensor scales (the control, one precision
step below the configuration's bfloat16).  The experts run on the
(token, choice) pairs sorted by expert and padded to chunks of
``CHUNK`` rows, one expert per chunk; attention runs in blocks of
``QBLOCK`` queries; logits are computed only at the served positions.
So a sequence of the whole context fits beside the weights once the
engine is freed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference import F32, matmul_f32, matmul_fp8

CHUNK = 256       # expert rows per chunk of the sorted pairs
QBLOCK = 512      # queries per attention block
LBLOCK = 256      # served positions per block of logits


def make_weights(m: dict, seed: int, num_paths: int = 1):
    """``[tree]``: one path of configuration ``m`` (its ``model``
    mapping), drawn from ``seed`` on the device in one call."""
    assert num_paths == 1, num_paths
    d, h, V = m["d_model"], m["num_heads"], m["vocab_size"]
    r, dn, dr, dv = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                     m["qk_rope_head_dim"], m["v_head_dim"])
    E, f, fs, F = (m["num_experts"], m["d_ff_expert"], m["d_ff_shared"],
                   m["d_ff_dense"])
    lead, L = m["first_dense"], m["num_layers"] - m["first_dense"]
    dtype = jnp.dtype(m["dtype"])

    def tree(key):
        keys = iter(jax.random.split(key, 32))

        def normal(shape, scale):
            return (jax.random.normal(next(keys), shape, F32)
                    * scale).astype(dtype)

        def ones(shape):
            return jnp.ones(shape, dtype)

        def mla(n):
            return {"wq": normal((n, d, h, dn + dr), d ** -0.5),
                    "w_dkv": normal((n, d, r + dr), d ** -0.5),
                    "kv_norm": ones((n, r)),
                    "w_uk": normal((n, r, h, dn), r ** -0.5),
                    "w_uv": normal((n, r, h, dv), r ** -0.5),
                    "wo": normal((n, h, dv, d), (h * dv) ** -0.5)}

        def swiglu(shape_in, shape_out, fan_in, fan_mid):
            return {"w_gate": normal(shape_in, fan_in ** -0.5),
                    "w_up": normal(shape_in, fan_in ** -0.5),
                    "w_down": normal(shape_out, fan_mid ** -0.5)}

        return {
            "embed": {"embedding": normal((V, d), 0.02),
                      "unembed": normal((d, V), d ** -0.5)},
            "lead": {"norm1": ones((lead, d)), "mixer": mla(lead),
                     "norm2": ones((lead, d)),
                     "mlp": swiglu((lead, d, F), (lead, F, d), d, F)},
            "blocks": {"pos0": {
                "norm1": ones((L, d)), "mixer": mla(L),
                "norm2": ones((L, d)),
                "mlp": {"router": normal((L, d, E), d ** -0.5),
                        "router_bias": normal((L, E), 0.02),
                        **swiglu((L, E, d, f), (L, E, f, d), d, f),
                        "shared": swiglu((L, d, fs), (L, fs, d), d, fs)},
            }},
            "final_norm": ones((d,)),
        }

    # a 31-bit key from the seed, whatever its size
    k = int(np.random.default_rng([abs(int(seed)), 1]).integers(2 ** 31))
    return [jax.jit(tree)(jax.random.key(k))]


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, theta):
    """x: (B, S, H, D) at positions 0..S-1; halves rotated."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(a, m, y, mm):
    """Published MLA over the whole sequence, causal.  y: (B, S, d)."""
    r, dn = m["kv_lora_rank"], m["qk_nope_head_dim"]
    q = mm("bsd,dhk->bshk", y, a["wq"])
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], m["rope_theta"])],
                        -1)
    kv = mm("bsd,dc->bsc", y, a["w_dkv"])
    c = _rms(kv[..., :r], a["kv_norm"], m["norm_eps"])
    k_pe = _rope(kv[..., None, r:], m["rope_theta"])          # (B,S,1,dr)
    k_nope = mm("bsc,chk->bshk", c, a["w_uk"])
    v = mm("bsc,chk->bshk", c, a["w_uv"])
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_pe, k_nope.shape[:3] + k_pe.shape[-1:])], -1)
    b, s, h, dq = q.shape
    scale = dq ** -0.5
    nb = s // QBLOCK if s % QBLOCK == 0 else 1
    qb = s // nb

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 1)
        sc = mm("bqhd,bkhd->bhqk", qi, k) * scale
        qpos = i * qb + jnp.arange(qb)
        causal = jnp.arange(s)[None, :] <= qpos[:, None]
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return mm("bhqk,bkhd->bqhd", p, v)

    o = jax.lax.map(block, jnp.arange(nb))            # (nb, B, qb, H, dv)
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, h, -1)
    return mm("bqhk,hkd->bqd", o, a["wo"])


def _swiglu(w, y, mm):
    g = mm("nd,df->nf", y, w["w_gate"])
    u = mm("nd,df->nf", y, w["w_up"])
    return mm("nf,fd->nd", jax.nn.silu(g) * u, w["w_down"])


def _moe(w, m, y, mm, route=None):
    """y: (N, d) -> ((N, d), experts (N, k), regret (N,)).  The experts
    run are ``route``'s (N, k) where it holds them (>= 0), else the
    reference's own top 6.  ``regret``: how far the least choice value
    (score + bias) of the experts run lies below the reference's own 6th
    best: 0 where they are the reference's own choice, the 6th less the
    7th where a near-tie went the other way."""
    E, k = m["num_experts"], m["top_k"]
    n, d = y.shape
    scores = jax.nn.sigmoid(mm("nd,de->ne", y, w["router"]))
    choice = scores + w["router_bias"].astype(F32)
    top, idx = jax.lax.top_k(choice, k)
    if route is not None:
        idx = jnp.where(route >= 0, route, idx)
    regret = jnp.maximum(top[:, k - 1] - jnp.take_along_axis(
        choice, idx, -1).min(-1), 0.0)
    gates = jnp.take_along_axis(scores, idx, -1)
    gates = gates / jnp.sum(gates, -1, keepdims=True) * m["routed_scaling"]
    # pairs sorted by expert; each expert's run padded to whole chunks,
    # so that a chunk holds one expert's rows
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    e_of = flat[order]
    count = jnp.bincount(flat, length=E)
    padded = (count + CHUNK - 1) // CHUNK * CHUNK
    pstart = jnp.cumsum(padded) - padded
    rank = jnp.arange(n * k) - (jnp.cumsum(count) - count)[e_of]
    dest = pstart[e_of] + rank
    rows = (n * k + E * CHUNK + CHUNK - 1) // CHUNK * CHUNK
    buf = jnp.zeros((rows, d), F32).at[dest].set(y[order // k])
    chunk_e = jnp.minimum(jnp.searchsorted(
        jnp.cumsum(padded), jnp.arange(rows // CHUNK) * CHUNK, side="right"),
        E - 1)

    def expert(args):
        xc, e = args
        return _swiglu({name: w[name][e] for name in
                        ("w_gate", "w_up", "w_down")}, xc, mm)

    out = jax.lax.map(expert, (buf.reshape(-1, CHUNK, d), chunk_e))
    out = out.reshape(rows, d)[dest]                     # sorted pairs
    pairs = jnp.zeros((n * k, d), F32).at[order].set(out).reshape(n, k, d)
    return jnp.einsum("nkd,nk->nd", pairs, gates) + _swiglu(
        w["shared"], y, mm), idx, regret


def hidden(w, m: dict, tokens, mm=matmul_f32, route=None):
    """Final-normed hidden states (B, S, d) in float32 for ``tokens``;
    the experts each position ran in each MoE layer (B, S, L, k); and
    each position's routing regret (see :func:`_moe`), the most over the
    MoE layers (B, S).  ``route`` (B, S, L, k): the experts to run where
    it holds them (>= 0), as a program chose them; elsewhere the
    reference chooses its own."""
    eps = m["norm_eps"]
    x = w["embed"]["embedding"][tokens].astype(F32)
    b, s = tokens.shape
    k = m["top_k"]
    n_moe = m["num_layers"] - m["first_dense"]
    if route is None:
        route = jnp.full((b, s, n_moe, k), -1, jnp.int32)

    def layer(x, lw):
        x = x + _attention(lw["mixer"], m, _rms(x, lw["norm1"], eps), mm)
        return x, _rms(x, lw["norm2"], eps).reshape(b * s, -1)

    def dense(x, lw):
        x, y = layer(x, lw)
        return x + _swiglu(lw["mlp"], y, mm).reshape(b, s, -1), None

    def moe(x, xs):
        lw, rl = xs
        x, y = layer(x, lw)
        y, idx, regret = _moe(lw["mlp"], m, y, mm, rl.reshape(b * s, k))
        return x + y.reshape(b, s, -1), (idx.reshape(b, s, k),
                                         regret.reshape(b, s))

    x, _ = jax.lax.scan(dense, x, w["lead"])
    x, (idx, regret) = jax.lax.scan(moe, x, (w["blocks"]["pos0"],
                                             jnp.moveaxis(route, 2, 0)))
    return (_rms(x, w["final_norm"], eps), jnp.moveaxis(idx, 0, 2),
            regret.max(0))


def forward(w, m: dict, tokens, mm=matmul_f32, route=None):
    """Logits (B, S, V) in float32 of ``tokens`` (B, S)."""
    return mm("bsd,dv->bsv", hidden(w, m, tokens, mm, route)[0],
              w["embed"]["unembed"])


@functools.partial(jax.jit, static_argnames=("m_items", "control", "span"))
def _gaps(w, tokens, route, first, count, m_items, control, span):
    """Per served token, the gap by which its reference logit lies below
    the reference's best, (B, span), NaN where nothing was served, the
    reference running the experts ``route`` gives where it gives them;
    and each row's routing regret, the most over the positions ``route``
    gives (see :func:`_moe`).  With ``control``, the same for the float8
    forward in the program's place: its first choices at the same
    positions and the experts it chose itself.  Served tokens of row r
    sit at ``first[r] .. first[r] + count[r] - 1`` (at most ``span``)."""
    m = dict(m_items)
    head = w["embed"]["unembed"]
    # position j predicts token j + 1: the served ones
    pos = first[:, None] - 1 + jnp.arange(span)[None, :]      # (B, span)
    served = jnp.arange(span)[None, :] < count[:, None]
    pos = jnp.clip(pos, 0, tokens.shape[1] - 2)
    target = jnp.take_along_axis(tokens, pos + 1, axis=1)
    routed = route[..., 0, 0] >= 0                            # (B, S)

    def blocks_at(x):
        """x at ``pos``, in blocks of positions (nb, B, LBLOCK, d)."""
        xs = jnp.take_along_axis(x, pos[..., None], axis=1)
        return jnp.moveaxis(xs.reshape(xs.shape[0], -1, LBLOCK,
                                       xs.shape[-1]), 1, 0)

    def judged(rt, tok):
        """Gaps of ``tok`` (B, span) and regret under routing ``rt``."""
        x, _, regret = hidden(w, m, tokens, route=rt)
        ref = jax.lax.map(lambda xb: matmul_f32("bsd,dv->bsv", xb, head),
                          blocks_at(x))              # (nb, B, LBLOCK, V)
        best = ref.max(-1)
        tb = jnp.moveaxis(tok.reshape(tok.shape[0], -1, LBLOCK), 1, 0)
        ok = jnp.moveaxis(served.reshape(served.shape[0], -1, LBLOCK), 1, 0)
        lg = jnp.take_along_axis(ref, tb[..., None], -1)[..., 0]
        gap = jnp.where(ok, best - lg, jnp.nan)         # (nb, B, LBLOCK)
        return (jnp.moveaxis(gap, 0, 1).reshape(gap.shape[1], -1),
                jnp.where(routed, regret, 0.0).max(-1))

    out = judged(route, target)
    if not control:
        return out + out
    x8, own8, _ = hidden(w, m, tokens, matmul_fp8)
    alt = jax.lax.map(lambda xb: jnp.argmax(
        matmul_fp8("bsd,dv->bsv", xb, head), -1), blocks_at(x8))
    alt = jnp.moveaxis(alt, 0, 1).reshape(alt.shape[1], -1)
    return out + judged(jnp.where(routed[..., None, None], own8, -1), alt)


def served_gaps(weights, m: dict, seqs, *, rows: int, length: int,
                most_served: int, control: bool = False) -> dict:
    """Gaps over served sequences, the reference running at every
    position the experts the program ran there.  A bfloat16 program
    routes a token otherwise than the float32 reference where two
    choice values lie within its rounding; through 8 MoE layers of
    random weights such a flip moves later logits as far as the float8
    control does.  So the reference follows the program's routing, and
    ``routing_regret_max`` judges that routing apart: how far the
    experts the program chose fall below the reference's own choice, the
    most over every routed position of every layer.
    ``served_gap_max`` is the widest gap over every served token; the
    median, the 99th percentile and the share of tokens that are not the
    reference's first choice (``served_miss_share``) beside it.  With
    ``control``, the same of the float8 forward's first choices and own
    routing at the same positions.  ``seqs``: list of ``(path, tokens,
    plen, experts)`` (path 0), tokens = prompt + served tokens, at most
    ``most_served`` of them served; ``experts`` (MoE layers, n, k) the
    experts the program ran at positions 0..n-1, or None (the reference
    then routes every position itself).  Runs ``rows`` sequences padded
    to ``length`` at a time (one compile)."""
    m_items = tuple(sorted((k, v) for k, v in m.items()
                           if isinstance(v, (int, float, str))))
    span = -(-most_served // LBLOCK) * LBLOCK
    n_moe = m["num_layers"] - m["first_dense"]
    got, n_tokens = [], 0
    for i in range(0, len(seqs), rows):
        chunk = seqs[i:i + rows]
        tok = np.zeros((rows, length), np.int32)
        route = np.full((rows, length, n_moe, m["top_k"]), -1, np.int32)
        first = np.full(rows, 1, np.int32)
        count = np.zeros(rows, np.int32)                # pad rows: empty
        for r, (_, t, plen, experts) in enumerate(chunk):
            tok[r, :len(t)] = t
            if experts is not None:
                route[r, :experts.shape[1]] = np.moveaxis(experts, 0, 1)
            first[r], count[r] = plen, len(t) - plen
            n_tokens += len(t) - plen
        out = _gaps(weights[0], jnp.asarray(tok), jnp.asarray(route),
                    jnp.asarray(first), jnp.asarray(count), m_items,
                    control, span)
        got.append([np.asarray(a)[:len(chunk)] for a in out])
    served, regret, ctrl, ctrl_regret = (
        np.concatenate([g[j] for g in got], axis=None) for j in range(4))
    keep = ~np.isnan(served)
    out = {"tokens": n_tokens}
    for name, g, rg in (("served", served, regret),
                        ("control", ctrl, ctrl_regret))[:1 + control]:
        g = g[keep]
        out[f"{name}_gap_max"] = float(np.max(g, initial=0.0))
        out[f"{name}_gap_median"] = float(np.median(g))
        out[f"{name}_gap_p99"] = float(np.quantile(g, 0.99))
        # tokens that are not the reference's first choice
        out[f"{name}_miss_share"] = float(np.mean(g > 0))
    out["routing_regret_max"] = float(np.max(regret))
    if control:
        out["control_regret_max"] = float(np.max(ctrl_regret))
    return out
