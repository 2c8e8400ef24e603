"""Operations of what the ``shortcut_moe`` builder's models add to ``costs_latent_moe.py``: the
decode token's operations through a DOUBLE layer (two latent-attention blocks, two dense FFNs,
one router over routed and identity outputs, the held experts a token chose), for the share of
the chip's peak the whole step reaches. The two kernels' own costs are ``costs_latent_moe``'s
(``ragged_experts``, ``latent_decode``): the mathematics is the same at other widths.
"""
from __future__ import annotations

from . import costs_latent_moe


def matmul_params(d: dict) -> dict:
    """Weights a decode token is multiplied by, by part: one attention block (absorbed form),
    one dense FFN, the router over every output it has, one expert, the head's slice."""
    p = costs_latent_moe.matmul_params(d)
    return {"attn": p["attn"], "ffn": 3 * d["d_model"] * d["ffn_width"],
            "router": d["d_model"] * (d["n_routed"] + d["n_zero"]), "expert": p["expert"], "head": p["head"]}


def decode_flops_per_token(d: dict, context: float, held_per_token: float) -> float:
    """Operations one generated token needs on THIS chip at ``context`` cached rows, with
    ``held_per_token`` of its chosen experts held here: two a matrix-multiplied weight (a double
    layer's two attention blocks and two dense FFNs, its router, the held real experts the token
    chose; the head's slice) and latent attention over the context in every attention block. An
    identity expert counts NOTHING: a token that chose one is multiplied by no weight for it."""
    p = matmul_params(d)
    double_layer = 2 * p["attn"] + 2 * p["ffn"] + p["router"] + held_per_token * p["expert"]
    attention = 2.0 * d["heads"] * (d["latent_width"] + d["kv_rank"]) * context
    return 2.0 * (d["n_expert_layers"] * double_layer + p["head"]) + d["n_layer"] * attention
