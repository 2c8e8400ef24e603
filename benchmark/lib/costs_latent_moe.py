"""Operations and bytes of what the ``latent_moe`` builder's models add: the routed experts'
SwiGLU over the rows of the experts held here, and latent attention against one cached row a
token, as the mathematics needs them whatever kernel does the work; and the decode token's
operations for the share of the chip's peak the whole step reaches.
"""
from __future__ import annotations

from .costs import Cost


def ragged_experts(rows: float, experts_with_rows: float, d_model: int, width: int,
                   *, itemsize: int = 2) -> Cost:
    """One layer's routed experts over ``rows`` rows (a token counts once for each of its
    chosen experts that is held here): three ``d_model x width`` products a row, six
    operations a weight. The three panels of each expert that has a row are read once,
    whatever the number of its rows; a row is read once and its result written once."""
    flops = 6.0 * d_model * width * rows
    panels = 3.0 * d_model * width * itemsize * experts_with_rows
    return Cost(flops, panels + 2.0 * rows * d_model * itemsize)


def latent_decode(context_rows: float, n_seqs: float, heads: int, width: int, v_width: int,
                  row: int, *, itemsize: int = 2) -> Cost:
    """One layer's decode-step latent attention in its absorbed form, ``context_rows`` the
    sum over the sequences of the cached rows each query sees. Every head scores a row over
    its ``width`` columns (the latent and the roped key) and takes its first ``v_width`` as the
    value: ``2 * heads * (width + v_width)`` operations a row and query. A row crosses HBM
    once for all heads at its stored width ``row`` (``width`` padded to whole 128-lane groups:
    the padding is read because it is stored); a query row and an output row a sequence and
    head."""
    flops = 2.0 * heads * (width + v_width) * context_rows
    qo = n_seqs * heads * (row + v_width) * itemsize
    return Cost(flops, context_rows * row * itemsize + qo)


def matmul_params(d: dict) -> dict:
    """Weights a decode token is multiplied by, by part of a layer, and the head. Attention
    in the absorbed form: the query pair, the latent projection, a head's query carried into
    the latent and its output carried out of it, and the output projection."""
    dm, h = d["d_model"], d["heads"]
    attn = (dm * d["q_rank"] + d["q_rank"] * h * (d["nope"] + d["rope"]) + dm * d["latent_width"]
            + h * d["nope"] * d["kv_rank"] + h * d["kv_rank"] * d["v_dim"] + h * d["v_dim"] * dm)
    expert = 3 * dm * d["expert_width"]
    return {"attn": attn, "router": dm * d["n_routed"], "shared": d["n_shared"] * expert,
            "expert": expert, "head": dm * d["vocab"]}


def decode_flops_per_token(d: dict, context: float, held_per_token: float) -> float:
    """Operations one generated token needs on THIS chip at ``context`` cached rows, with
    ``held_per_token`` of its chosen experts held here: two a matrix-multiplied weight (the
    held experts it chose, the shared expert, the router, attention's projections, the head's
    slice) and latent attention over the context."""
    p = matmul_params(d)
    layer = p["attn"] + p["router"] + p["shared"] + held_per_token * p["expert"]
    attention = 2.0 * d["heads"] * (d["latent_width"] + d["kv_rank"]) * context
    return 2.0 * (d["n_layer"] * layer + p["head"]) + d["n_layer"] * attention
