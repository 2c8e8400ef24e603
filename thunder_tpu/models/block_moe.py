"""A grouped-query mixture-of-experts decoder that generates by DIFFUSION OVER BLOCKS
(SDAR, JetLM 2025; ``model_type`` ``sdar_moe``, whose layer is Qwen3-MoE's), in thunder_tpu's
op language.

A sequence is its prompt followed by blocks of ``K`` positions; position ``t`` lies in block
``floor(t / K)``. A layer, ``X`` the residual stream and ``N`` RMSNorm, no biases:

    h = N1(X);  Q = h Wq,  Kk = h Wk,  V = h Wv
    every head of Q and of Kk through an RMSNorm over its head_size columns with a learned
    weight (``Config.norm_qk``: Qwen3's q_norm / k_norm), then rope (rotate-half) at the TRUE
    position; A = softmax(Q Kk^T / sqrt(head_size) + M);  X' = X + (A V) Wo
    u = N2(X');  r = softmax(u Wr) in float32;  S = the n_expert_per_token largest;
    g_e = r_e / sum_S r;  Y = X' + sum_{e in S} g_e W_down^e (silu(W_gate^e u) * W_up^e u)

``M`` is BLOCK-causal when the forward is given a block length (``M[t, s] = 0`` where
``floor(s / K) <= floor(t / K)``, else ``-inf``: every position sees all earlier blocks and the
whole of its own) and plain causal without one. After the last layer an RMSNorm and an untied
head; the logits at position ``t`` are for the token AT ``t`` (no shift).

The experts are ``moe.HeldExperts`` with ``score="softmax"``, no shared expert and ALL of them
held: drop-free through ``moe.ragged_experts``. The attention is ``litgpt.CausalSelfAttention``,
so the model is served as a dense rope GPT is (``serving/runner.py: DenseGPT``; its blocks route,
so each is a ``RoutedBlock`` there): paged keys and values of every position, the block program
``verify`` with the block's last position as every row's coverage. How a block is generated
(its denoise passes, and the run over its final tokens that settles its keys and values, inside
the next block's first pass) is the engine's (``serving/scheduler.py``, ``block_diffusion=``).

Scopes a device profile is split by: ``attn`` (``rope`` within), ``moe_router``,
``moe_experts``, ``head``.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from .. import nn
from ..core.trace import named_scope
from . import litgpt
from .moe import HeldExperts


@dataclass
class Config(litgpt.Config):
    name: str = "tiny-block-moe"
    norm_qk: bool = True
    norm_eps: float = 1e-6
    n_expert: int = 8
    n_expert_per_token: int = 2
    moe_intermediate_size: int = 64
    norm_topk_prob: bool = True


class Block(nn.Module):
    def __init__(self, cfg: Config, dtype=jnp.float32):
        super().__init__()
        self.norm_1 = litgpt._norm(cfg, dtype)
        self.attn = litgpt.CausalSelfAttention(cfg, dtype)
        self.norm_2 = litgpt._norm(cfg, dtype)
        self.experts = HeldExperts(cfg.n_embd, cfg.moe_intermediate_size, cfg.n_expert,
                                   (0, cfg.n_expert), cfg.n_expert_per_token, n_shared=0,
                                   norm_topk_prob=cfg.norm_topk_prob, score="softmax", dtype=dtype)

    def forward(self, x, cos, sin, block_length=None):
        with named_scope("attn"):
            h = self.attn(self.norm_1(x), cos, sin, block_length)
        return self.tail(x, h)

    def tail(self, x, h, *routing):
        """As ``litgpt.Block.tail``; ``routing`` is what ``HeldExperts`` takes beside the rows
        (live, counted) where a served program has idle rows or counts its routing."""
        with named_scope("attn"):
            x = x + h
        return x + self.experts(self.norm_2(x), *routing)


class BlockMoE(nn.Module):
    """Embedding, ``n_layer`` blocks, a final RMSNorm and an untied head."""

    def __init__(self, cfg: Config, dtype=jnp.float32):
        super().__init__()
        self.cfg = cfg
        self.wte = nn.Embedding(cfg.padded_vocab_size, cfg.n_embd, dtype=dtype)
        self.h = nn.ModuleList([Block(cfg, dtype) for _ in range(cfg.n_layer)])
        self.ln_f = litgpt._norm(cfg, dtype)
        self.lm_head = nn.Linear(cfg.n_embd, cfg.padded_vocab_size, bias=False, dtype=dtype)
        cos, sin = litgpt.build_rope_cache(cfg.block_size, cfg.rope_n_elem, cfg.rope_base, dtype)
        self.register_buffer("cos", cos)
        self.register_buffer("sin", sin)

    def forward(self, idx, block_length=None):
        """Logits (B, T, V) of whole sequences with no cache, block-causal under a block length."""
        T = idx.shape[1]
        with named_scope("attn/rope"):
            cos, sin = self.cos[:T], self.sin[:T]
        with named_scope("embed"):
            x = self.wte(idx)
        for block in self.h:
            x = block(x, cos, sin, block_length)
        with named_scope("head"):
            return self.lm_head(self.ln_f(x))


def tiny_block_moe(**overrides) -> BlockMoE:
    keys = dict(block_size=128, vocab_size=320, n_layer=2, n_head=4, n_query_groups=2, n_embd=64,
                head_size=32)
    keys.update(overrides)
    return BlockMoE(Config(**keys))
