"""Operations and bytes of what the ``sambay`` builder's models add: differential
attention over paged keys and values (in a window, or over one pool that several
layers read) and the state-space layers, as the mathematics needs them whatever
kernel does the work; and the decode token's operations for the share of the
chip's peak the whole step reaches.
"""
from __future__ import annotations

from .costs import Cost


def diff_attention_decode(context_tokens: float, n_seqs: float, heads: int, kv_heads: int,
                          head_dim: int, *, itemsize: int = 2) -> Cost:
    """One layer's decode-step differential attention, ``context_tokens`` the
    sum over the sequences of the key positions each query may see. Every head
    takes ``QK^T`` at ``head_dim`` and ``PV`` over the pair's two value heads
    side by side (``2 * head_dim``). The keys and values a position holds are
    ``kv_heads * head_dim`` elements each, read once; a query row of
    ``head_dim`` and an output row of ``2 * head_dim`` a sequence and head."""
    flops = (2.0 + 4.0) * heads * head_dim * context_tokens
    kv = 2.0 * kv_heads * head_dim * context_tokens * itemsize
    qo = 3.0 * n_seqs * heads * head_dim * itemsize
    return Cost(flops, kv + qo)


def matmul_params(d: dict) -> dict:
    """Weights a token is multiplied by, for one layer of each kind, the MLP
    every layer has, and the tied head."""
    dm, hd = d["d_model"], d["head_dim"]
    q, kv = d["heads"] * hd, d["kv_heads"] * hd
    di, n, r = d["d_inner"], d["d_state"], d["dt_rank"]
    attn = dm * (q + 2 * kv) + q * dm
    return {"mamba": dm * 2 * di + di * (r + 2 * n) + r * di + di * dm,
            "window_attn": attn, "full_attn": attn, "cross_attn": 2 * dm * q,
            "gmu": 2 * dm * di, "mlp": 3 * dm * d["d_ff"], "head": dm * d["vocab"]}


def decode_flops_per_token(d: dict, context: float) -> float:
    """Operations one generated token needs at ``context`` key positions: two a
    matrix-multiplied weight, differential attention by what each layer sees
    (the window layers the window at most, the full layer and the layers that
    read its pool the whole context), and the scan's state update."""
    p, n = matmul_params(d), d["layers"]
    weights = sum(n[k] * p[k] for k in n) + d["n_layer"] * p["mlp"] + p["head"]
    per_key = 6.0 * d["heads"] * d["head_dim"]
    attention = per_key * (n["window_attn"] * min(context, d["window"])
                           + (n["full_attn"] + n["cross_attn"]) * context)
    scan = n["mamba"] * 7.0 * d["d_inner"] * d["d_state"]
    return 2.0 * weights + attention + scan
