"""Seeded weights and the plain float32 reference of a DiPaCo path.

Nothing here imports the program.  ``make_weights`` draws every path's
weights on the device in one jitted call, in the layout and type the
engine serves (checked against the program's own tree by the driver),
with the program's init scales: normal / sqrt(fan-in) for projections,
0.02 for the tied embedding, ones for the RMSNorm scales.

``forward`` is the architecture written out in ``jax.numpy``: pre-norm
blocks of RMSNorm (eps from the configuration), multi-head attention
with rotary embeddings (halves rotated, theta from the configuration),
causal softmax scaled by 1/sqrt(head_dim), a GELU MLP (tanh
approximation, as ``jax.nn.gelu``), a final RMSNorm and logits through
the tied embedding.  With ``mm=matmul_f32`` every matmul runs in float32
at ``HIGHEST`` precision; ``matmul_fp8`` rounds both operands to
float8 e4m3 with per-tensor scales first: the control, one precision
step below the bfloat16 that the configurations state.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0   # largest finite float8_e4m3fn


def matmul_f32(spec, a, b):
    return jnp.einsum(spec, a.astype(F32), b.astype(F32), precision=HIGHEST)


def _fp8(x):
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def matmul_fp8(spec, a, b):
    return jnp.einsum(spec, _fp8(a), _fp8(b), precision=HIGHEST)


def make_weights(m: dict, seed: int, num_paths: int):
    """``num_paths`` weight trees of configuration ``m`` (its ``model``
    mapping), drawn from ``seed`` on the device in one call."""
    L, d, h, kh, hd, f, V = (m["num_layers"], m["d_model"], m["num_heads"],
                             m["num_kv_heads"], m["head_dim"], m["d_ff"],
                             m["vocab_size"])
    dtype = jnp.dtype(m["dtype"])

    def one(key):
        ks = jax.random.split(key, 7)

        def normal(k, shape, scale):
            return (jax.random.normal(k, shape, F32) * scale).astype(dtype)

        ones = functools.partial(jnp.ones, dtype=dtype)
        return {
            "embed": {"embedding": normal(ks[0], (V, d), 0.02)},
            "blocks": {"pos0": {
                "norm1": ones((L, d)),
                "mixer": {
                    "wq": normal(ks[1], (L, d, h, hd), d ** -0.5),
                    "wk": normal(ks[2], (L, d, kh, hd), d ** -0.5),
                    "wv": normal(ks[3], (L, d, kh, hd), d ** -0.5),
                    "wo": normal(ks[4], (L, h, hd, d), (h * hd) ** -0.5),
                },
                "norm2": ones((L, d)),
                "mlp": {"w_up": normal(ks[5], (L, d, f), d ** -0.5),
                        "w_down": normal(ks[6], (L, f, d), f ** -0.5)},
            }},
            "final_norm": ones((d,)),
        }

    @jax.jit
    def gen(key):
        return [one(jax.random.fold_in(key, p)) for p in range(num_paths)]

    # a 31-bit key from the seed, whatever its size
    k = int(np.random.default_rng([abs(int(seed)), 1]).integers(2 ** 31))
    return gen(jax.random.key(k))


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, theta):
    """x: (B, S, H, D) at positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs          # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(w, m: dict, tokens, mm=matmul_f32):
    """Logits (B, S, V) in float32 of one path for ``tokens`` (B, S)."""
    eps, theta = m["norm_eps"], m["rope_theta"]
    h, kh, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    emb = w["embed"]["embedding"]
    x = emb[tokens].astype(F32)
    s = tokens.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lw):
        a = lw["mixer"]
        y = _rms(x, lw["norm1"], eps)
        q = _rope(mm("bsd,dhk->bshk", y, a["wq"]), theta)
        k = _rope(mm("bsd,dhk->bshk", y, a["wk"]), theta)
        v = mm("bsd,dhk->bshk", y, a["wv"])
        k = jnp.repeat(k, h // kh, axis=2)
        v = jnp.repeat(v, h // kh, axis=2)
        sc = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = mm("bhqk,bkhd->bqhd", p, v)
        x = x + mm("bqhk,hkd->bqd", o, a["wo"])
        y = _rms(x, lw["norm2"], eps)
        u = jax.nn.gelu(mm("bsd,df->bsf", y, lw["mlp"]["w_up"]))
        return x + mm("bsf,fd->bsd", u, lw["mlp"]["w_down"]), None

    x, _ = jax.lax.scan(layer, x, w["blocks"]["pos0"])
    x = _rms(x, w["final_norm"], eps)
    return mm("bsd,vd->bsv", x, emb)


@functools.partial(jax.jit, static_argnames=("m_items", "control"))
def _gaps(w, tokens, first, count, m_items, control):
    """Per row, the widest gap by which a served token's reference logit
    lies below the reference's best; with ``control``, the same for the
    token that the float8 forward puts first.  Served tokens of row r
    sit at ``first[r] .. first[r] + count[r] - 1``."""
    m = dict(m_items)
    ref = forward(w, m, tokens)[:, :-1]                   # predicts t+1
    best = ref.max(-1)
    pos = jnp.arange(tokens.shape[1] - 1)[None, :] + 1    # predicted index
    served = (pos >= first[:, None]) & (pos < (first + count)[:, None])

    def gap_of(tok):
        lg = jnp.take_along_axis(ref, tok[..., None], -1)[..., 0]
        return jnp.max(jnp.where(served, best - lg, -jnp.inf), axis=-1)

    out = gap_of(tokens[:, 1:])
    if control:
        alt = jnp.argmax(forward(w, m, tokens, matmul_fp8)[:, :-1], -1)
        return out, gap_of(alt)
    return out, out


def served_gaps(weights, m: dict, seqs, *, rows: int, length: int,
                control: bool = False) -> dict:
    """Widest gaps over served sequences.  ``seqs``: list of
    ``(path, tokens, plen)``, tokens = prompt + served tokens.  Runs path
    by path, ``rows`` sequences padded to ``length`` at a time (one
    compile)."""
    m_items = tuple(sorted((k, v) for k, v in m.items()
                           if isinstance(v, (int, float, str))))
    served, ctrl, n_tokens = [], [], 0
    by_path = {}
    for p, toks, plen in seqs:
        by_path.setdefault(p, []).append((toks, plen))
    for p in sorted(by_path):
        group = by_path[p]
        for i in range(0, len(group), rows):
            chunk = group[i:i + rows]
            tok = np.zeros((rows, length), np.int32)
            first = np.full(rows, length, np.int32)     # pad rows: empty
            count = np.zeros(rows, np.int32)
            for r, (t, plen) in enumerate(chunk):
                tok[r, :len(t)] = t
                first[r], count[r] = plen, len(t) - plen
                n_tokens += len(t) - plen
            a, b = _gaps(weights[p], jnp.asarray(tok), jnp.asarray(first),
                         jnp.asarray(count), m_items, control)
            served.append(np.asarray(a)[:len(chunk)])
            ctrl.append(np.asarray(b)[:len(chunk)])
    out = {"tokens": n_tokens,
           "served_gap_max": float(np.max(np.concatenate(served)))}
    if control:
        out["control_gap_max"] = float(np.max(np.concatenate(ctrl)))
    return out
