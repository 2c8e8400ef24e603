"""Operations and bytes of what the ``block_moe`` builder's models run in a pass over blocks, as
the mathematics needs them whatever kernel does the work: the routed experts' SwiGLU over the rows
of a pass (128 held experts of width 768 at the published widths), attention of a block's K rows
against the pages its sequence holds, and a row's operations for the share of the chip's peak the
passes reach.
"""
from __future__ import annotations

from .costs import Cost


def ragged_experts(rows: float, experts_with_rows: float, d_model: int, width: int,
                   *, itemsize: int = 2) -> Cost:
    """One layer's routed experts over ``rows`` rows (a token counts once for each expert it
    chose; all are held here): three ``d_model x width`` products a row, six operations a weight.
    The three panels of each expert that has a row are read once, whatever the number of its
    rows; a row is read once and its result written once."""
    flops = 6.0 * d_model * width * rows
    panels = 3.0 * d_model * width * itemsize * experts_with_rows
    return Cost(flops, panels + 2.0 * rows * d_model * itemsize)


def block_attention(key_rows: float, n_seqs: float, block: int, heads: int, kv_heads: int,
                    head_dim: int, *, itemsize: int = 2) -> Cost:
    """One layer's attention of a pass: ``n_seqs`` sequences of ``block`` query rows each, every
    row over all of its sequence's ``key_rows / n_seqs`` cached positions (its own block whole:
    no causal half inside it). ``key_rows`` is the sum over the sequences of the positions each
    holds. ``QK^T`` and ``PV``: ``4 * heads * head_dim`` operations a query and key. A key and a
    value row cross HBM ONCE a key head, for all the block's queries and the ``heads / kv_heads``
    query heads that read it; a query row and an output row a query and head."""
    flops = 4.0 * heads * head_dim * block * key_rows
    kv = 2.0 * kv_heads * key_rows * head_dim * itemsize
    qo = 2.0 * n_seqs * block * heads * head_dim * itemsize
    return Cost(flops, kv + qo)


def matmul_params(d: dict) -> dict:
    """Weights a row of a pass is multiplied by, by part of a layer, and the head."""
    dm = d["d_model"]
    attn = dm * (d["heads"] + 2 * d["kv_heads"]) * d["head_dim"] + d["heads"] * d["head_dim"] * dm
    return {"attn": attn, "router": dm * d["n_routed"], "expert": 3 * dm * d["expert_width"],
            "head": dm * d["vocab"]}


def flops_per_row(d: dict, context: float) -> float:
    """Operations one ROW of a pass needs (a block's every position is a row, in a denoise pass
    and in the commit pass alike) at ``context`` cached positions: two a matrix-multiplied weight
    (attention's projections, the router, the experts it chose, the head) and attention over
    the context."""
    p = matmul_params(d)
    layer = p["attn"] + p["router"] + d["experts_per_token"] * p["expert"]
    attention = 4.0 * d["heads"] * d["head_dim"] * context
    return 2.0 * (d["n_layer"] * layer + p["head"]) + d["n_layer"] * attention
