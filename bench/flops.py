"""Operation and byte counts of the work a request needs.

The per-token forward FLOPs are copied from ``launch/flopmodel.py``
(``attn_flops_per_token``, ``mlp_flops_per_token`` and the unembedding
term of ``analyze``) so that the yardstick stays here, where a change
to the program cannot move it.  ``m`` is a configuration's ``model``
mapping (``bench/configs/<config>.json``).

Only useful work is counted: the rows that hold a request and their
valid positions, never empty slots or padding.  A token at position
``i`` (0-based) attends to ``i + 1`` positions.
"""
from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def attn_flops_per_token(m, s_kv: float) -> float:
    d, h, kh, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], \
        m["head_dim"]
    proj = 2 * d * (h + 2 * kh) * hd + 2 * h * hd * d
    scores = 2 * s_kv * h * hd * 2          # QK^T and PV
    return proj + scores


def mlp_flops_per_token(m) -> float:
    nmat = 3 if m["mlp_type"] in ("swiglu", "geglu") else 2
    return 2 * nmat * m["d_model"] * m["d_ff"]


def token_flops(m, s_kv: float) -> float:
    """Forward FLOPs of one token that attends to ``s_kv`` positions."""
    per_layer = attn_flops_per_token(m, s_kv) + mlp_flops_per_token(m)
    return m["num_layers"] * per_layer + 2 * m["d_model"] * m["vocab_size"]


def prefill_flops(m, n: int) -> float:
    """Forward FLOPs of a causal prefill of an ``n``-token prompt: token
    ``i`` attends to ``i + 1`` positions, so the mean is ``(n + 1) / 2``."""
    return n * token_flops(m, (n + 1) / 2.0)


def decode_attention_work(m, ctx: int) -> tuple:
    """(FLOPs, HBM bytes) that the decode attention of one row needs,
    over all layers, at a context of ``ctx`` positions: QK^T and PV over
    the valid positions, reading their keys and values once, plus the
    query and the output."""
    h, kh, hd, layers = m["num_heads"], m["num_kv_heads"], m["head_dim"], \
        m["num_layers"]
    kv_bytes = 1 if m.get("kv_quant") else DTYPE_BYTES[m["dtype"]]
    act = DTYPE_BYTES[m["dtype"]]
    flops = 4 * ctx * h * hd
    nbytes = 2 * ctx * kh * hd * kv_bytes + 2 * h * hd * act
    if m.get("kv_quant"):
        nbytes += 2 * ctx * kh * 4            # f32 scales per (token, head)
    return layers * flops, layers * nbytes
