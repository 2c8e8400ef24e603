"""Operations and bytes of what the ``parallel_moe`` builder's models run that ``lib/costs.py`` and
``lib/costs_latent_moe.py`` do not count already, as the mathematics needs them whatever kernel does
the work: a prompt chunk's attention in a window layer (the keys inside each query's window, read
from the window's first page), and a decode token's operations for the share of the chip's peak the
whole step reaches. A decode step's paged attention is ``costs.paged_decode`` over the keys each
query sees (at most the window in a window layer); the routed experts are
``costs_latent_moe.ragged_experts``.
"""
from __future__ import annotations

from .costs import Cost, causal_pairs


def window_pairs(chunk: int, start: int, window: int) -> float:
    """(query, key) pairs a chunk of ``chunk`` queries at positions ``start ..`` keeps under a
    causal mask and a window: query ``t`` sees ``min(t + 1, window)`` keys."""
    full = min(chunk, max(window - start, 0))           # queries that see every key so far
    pairs = causal_pairs(full, start + full)
    return pairs + float(chunk - full) * window


def paged_chunk(chunk: int, start: int, heads: int, kv_heads: int, head_dim: int,
                window=None, *, itemsize: int = 2) -> Cost:
    """One layer's chunk of ``chunk`` queries at positions ``start ..``: causal inside the chunk,
    over every key written so far, or in a window layer over the keys inside each query's
    window, read from the first page of the first query's window (what the kernel copies: the
    pages the chunk's queries can see, each once a key head)."""
    if window is None:
        pairs, first = causal_pairs(chunk, start + chunk), 0
    else:
        pairs, first = window_pairs(chunk, start, window), max(start - window + 1, 0)
    flops = 4.0 * heads * head_dim * pairs
    qo = 2.0 * heads * chunk * head_dim * itemsize
    kv = 2.0 * kv_heads * (start + chunk - first) * head_dim * itemsize
    return Cost(flops, qo + kv)


def matmul_params(d: dict) -> dict:
    """Weights a decode token is multiplied by, by part of a layer, and the tied head's slice."""
    dm = d["d_model"]
    attn = dm * (d["heads"] + 2 * d["kv_heads"]) * d["head_dim"] + d["heads"] * d["head_dim"] * dm
    expert = 3 * dm * d["expert_width"]
    return {"attn": attn, "router": dm * d["n_routed"], "shared": d["n_shared"] * expert,
            "expert": expert, "head": dm * d["vocab"]}


def decode_flops_per_token(d: dict, full_keys: float, window_keys: float,
                           held_per_token: float) -> float:
    """Operations one generated token needs on THIS chip, with ``held_per_token`` of its chosen
    experts held here: two a matrix-multiplied weight (attention's projections, the router, the
    four shared experts, the held experts it chose, the head's slice) and attention over the
    keys a full layer sees (``full_keys``, its whole context) and a window layer sees
    (``window_keys``, at most the window)."""
    p = matmul_params(d)
    layer = p["attn"] + p["router"] + p["shared"] + held_per_token * p["expert"]
    keys = d["layers"]["full"] * full_keys + d["layers"]["window"] * window_keys
    return 2.0 * (d["n_layer"] * layer + p["head"]) + 4.0 * d["heads"] * d["head_dim"] * keys
