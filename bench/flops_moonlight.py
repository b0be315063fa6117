"""Operation and byte counts of the work a Moonlight request needs.

The per-token forward FLOPs are copied from ``launch/flopmodel.py``
(``mla_flops_per_token``, ``moe_flops_per_token`` for the dropless
dispatch, ``mlp_flops_per_token`` and the unembedding term of
``analyze``) so that the yardstick stays here, where a change to the
program cannot move it.  ``m`` is the configuration's ``model`` mapping
(``bench/configs/moonlight-16b-a3b.json``).

Only useful work is counted: a token computes its 6 routed experts and
the shared ones, latent attention over its valid positions (the
published form in a prefill, where token ``i`` attends ``i + 1``
positions, the absorbed form in a decode), and the head once per
prefill row (its last position) and once per decoded token.
"""
from __future__ import annotations

BF16 = 2


def mla_flops(m, s_kv: float, absorbed: bool) -> float:
    d, h = m["d_model"], m["num_heads"]
    r, dn, dr, dv = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                     m["qk_rope_head_dim"], m["v_head_dim"])
    proj = 2 * d * h * (dn + dr) + 2 * d * (r + dr) + 2 * h * dv * d
    if absorbed:
        fold = 2 * h * dn * r + 2 * h * r * dv
        return proj + fold + 2 * s_kv * h * ((r + dr) + r)
    expand = 2 * r * h * (dn + dv)
    return proj + expand + 2 * s_kv * h * ((dn + dr) + dv)


def moe_flops(m) -> float:
    """Router, the top-k routed experts and the shared SwiGLU."""
    d = m["d_model"]
    return (2 * d * m["num_experts"]
            + 2 * 3 * d * m["d_ff_expert"] * m["top_k"]
            + 2 * 3 * d * m["d_ff_shared"])


def dense_mlp_flops(m) -> float:
    return 2 * 3 * m["d_model"] * m["d_ff_dense"]


def head_flops(m) -> float:
    return 2 * m["d_model"] * m["vocab_size"]


def layers_flops(m, s_kv: float, absorbed: bool) -> float:
    """One token through every layer, without the head."""
    lead, moe = m["first_dense"], m["num_layers"] - m["first_dense"]
    att = mla_flops(m, s_kv, absorbed)
    return lead * (att + dense_mlp_flops(m)) + moe * (att + moe_flops(m))


def token_flops(m, s_kv: float) -> float:
    """Forward FLOPs of one decoded token that attends to ``s_kv``
    positions (absorbed latent attention), head included."""
    return layers_flops(m, s_kv, absorbed=True) + head_flops(m)


def prefill_flops(m, n: int) -> float:
    """A causal prefill of an ``n``-token prompt: token ``i`` attends to
    ``i + 1`` positions, so the mean is ``(n + 1) / 2``; one head
    position."""
    return n * layers_flops(m, (n + 1) / 2.0, absorbed=False) + head_flops(m)


def latent_width(m) -> int:
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def mla_decode_work(m, ctx: int) -> tuple:
    """(FLOPs, HBM bytes) that the latent decode attention of one row
    needs, over all layers, at a context of ``ctx`` positions: the
    scores over the whole entry and the weighted sum of the latent, over
    the valid positions, reading each entry once, plus the row's queries
    and output (float32)."""
    c, r, h = latent_width(m), m["kv_lora_rank"], m["num_heads"]
    flops = 2 * ctx * h * (c + r)
    nbytes = ctx * c * BF16 + 4 * h * (c + r)
    return m["num_layers"] * flops, m["num_layers"] * nbytes


def expert_bytes(m) -> float:
    """HBM bytes of one routed expert's three matrices in one layer."""
    return 3 * m["d_model"] * m["d_ff_expert"] * BF16
