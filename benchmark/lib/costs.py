"""Operations and bytes a call needs, from its shapes alone.

These are the benchmark's yardstick for roofline shares and for model FLOP/s
utilization: what the *algorithm* needs for the call, not what an
implementation happens to execute. A multiply-accumulate is two operations.
Bytes are what has to cross HBM at least once: every operand read once, every
result written once.
"""
from __future__ import annotations

from dataclasses import dataclass

from .peaks import Peaks


@dataclass(frozen=True)
class Cost:
    flops: float
    bytes: float

    def __add__(self, other: "Cost") -> "Cost":
        return Cost(self.flops + other.flops, self.bytes + other.bytes)

    def scaled(self, k: float) -> "Cost":
        return Cost(self.flops * k, self.bytes * k)


def roofline_seconds(cost: Cost, peaks: Peaks) -> tuple:
    """``(seconds, bound)``: the least time the chip could take for ``cost``,
    and which of the two limits sets it (``"compute"`` or ``"bandwidth"``)."""
    t_flops = cost.flops / peaks.bf16_flops
    t_bytes = cost.bytes / peaks.hbm_bytes_per_s
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")


def matmul(m: int, n: int, k: int, *, in_bytes: int = 2, out_bytes: int = 2) -> Cost:
    """``(m, k) @ (k, n)``."""
    return Cost(2.0 * m * n * k, float((m * k + k * n) * in_bytes + m * n * out_bytes))


def causal_pairs(t_q: int, t_k: int) -> float:
    """(query, key) pairs a causal mask keeps when the ``t_q`` queries are the
    last ``t_q`` positions of a ``t_k``-long context."""
    start = t_k - t_q
    return float(t_q * start + t_q * (t_q + 1) // 2)


def flash_fwd(batch: int, heads: int, kv_heads: int, t_q: int, t_k: int, head_dim: int,
              *, causal: bool = True, itemsize: int = 2) -> Cost:
    """Attention forward: ``QK^T`` and ``PV`` over the kept pairs. Reads q, k,
    v; writes o and the f32 log-sum-exp row the backward needs."""
    pairs = causal_pairs(t_q, t_k) if causal else float(t_q * t_k)
    flops = 4.0 * batch * heads * head_dim * pairs
    qo = 2 * batch * heads * t_q * head_dim * itemsize
    kv = 2 * batch * kv_heads * t_k * head_dim * itemsize
    lse = batch * heads * t_q * 4
    return Cost(flops, float(qo + kv + lse))


def flash_bwd(batch: int, heads: int, kv_heads: int, t_q: int, t_k: int, head_dim: int,
              *, causal: bool = True, itemsize: int = 2) -> Cost:
    """Attention backward without a stored probability matrix: recompute
    ``QK^T``, then ``dV = P^T dO``, ``dP = dO V^T``, ``dQ = dS K``,
    ``dK = dS^T Q`` — five matrix products over the kept pairs; the recompute
    is part of the algorithm (no ``T x T`` matrix may be kept), so it counts.
    Reads q, k, v, o, dO and the log-sum-exp; writes dq, dk, dv."""
    pairs = causal_pairs(t_q, t_k) if causal else float(t_q * t_k)
    flops = 10.0 * batch * heads * head_dim * pairs
    q_like = 4 * batch * heads * t_q * head_dim * itemsize       # q, o, dO, dq
    kv_like = 4 * batch * kv_heads * t_k * head_dim * itemsize   # k, v, dk, dv
    lse = batch * heads * t_q * 4
    return Cost(flops, float(q_like + kv_like + lse))


def paged_decode(context_tokens: float, n_seqs: float, heads: int, kv_heads: int,
                 head_dim: int, *, itemsize: int = 2) -> Cost:
    """One decode step's attention over paged keys and values:
    ``context_tokens`` is the sum of the active sequences' context lengths.
    Every cached key and value is read once; a query row and an output row per
    sequence and head."""
    flops = 4.0 * heads * head_dim * context_tokens
    kv = 2.0 * kv_heads * head_dim * context_tokens * itemsize
    qo = 2.0 * n_seqs * heads * head_dim * itemsize
    return Cost(flops, kv + qo)


def paged_chunk(chunk: int, start: int, heads: int, kv_heads: int, head_dim: int,
                *, itemsize: int = 2) -> Cost:
    """One prefill chunk of ``chunk`` queries at positions ``start ..`` over
    the ``start + chunk`` keys written so far (causal inside the chunk)."""
    flops = 4.0 * heads * head_dim * causal_pairs(chunk, start + chunk)
    qo = 2.0 * heads * chunk * head_dim * itemsize
    kv = 2.0 * kv_heads * (start + chunk) * head_dim * itemsize
    return Cost(flops, qo + kv)


def matmul_params_per_layer(d_model: int, heads: int, kv_heads: int, head_dim: int,
                            d_ff: int, mlp_matrices: int) -> int:
    """Weights of one transformer block that a token is multiplied by."""
    attn = d_model * (heads + 2 * kv_heads) * head_dim + heads * head_dim * d_model
    return attn + mlp_matrices * d_model * d_ff


def train_flops_per_token(*, n_layer: int, d_model: int, heads: int, kv_heads: int,
                          head_dim: int, d_ff: int, mlp_matrices: int, vocab: int,
                          seq_len: int) -> float:
    """Operations the forward and backward passes need per trained token:
    six per matrix-multiplied weight (two forward, four backward) plus causal
    attention, ``6 * heads * head_dim * seq_len`` per layer (twelve for full
    attention, halved by the mask). The embedding lookup multiplies nothing
    and is not counted; the output head is. Recomputed operations (activation
    checkpointing) do not count."""
    weights = n_layer * matmul_params_per_layer(d_model, heads, kv_heads, head_dim,
                                                d_ff, mlp_matrices) + vocab * d_model
    return 6.0 * weights + 6.0 * n_layer * heads * head_dim * seq_len
