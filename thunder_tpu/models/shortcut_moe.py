"""A shortcut-connected mixture-of-experts decoder over latent attention
(LongCat-Flash's layer, arXiv:2509.01322; the public
``config.json`` of LongCat-Flash-Omni's text decoder carries these keys), in
thunder_tpu's op language, as ONE CHIP of an expert-parallel group holds it.

A layer is a DOUBLE layer: two latent-attention blocks, two dense SwiGLU FFNs
and ONE expert layer on a shortcut (ScMoE). ``x`` the residual stream, ``N``
RMSNorm, no biases, every sublayer ``i`` = 0, 1 with its own weights:

    a0 = x  + MLA_0(N1_0(x));   u0 = N2_0(a0);   m = Experts(u0);   h0 = a0 + FFN_0(u0)
    a1 = h0 + MLA_1(N1_1(h0));  u1 = N2_1(a1);   y = a1 + FFN_1(u1) + m

The experts read the first block's post-attention norm and their result is
added only at the layer's end, beside the second block: on a pod their
exchange runs while the dense path of both halves computes. On one chip the
order is XLA's to choose.

* ``FFN(u) = (silu(u W_g) * (u W_u)) W_d``, width ``intermediate_size``.
* ``MLA``: ``latent_moe.LatentAttention`` with plain rope (``rope_factor`` 1)
  and the two low-rank streams rescaled: ``c_q = s_q RMSNorm(u W_qa)``,
  ``c_kv = s_kv RMSNorm(c)`` with ``s = (n_embd / rank) ** 0.5``
  (``Config.q_lora_scale``, ``kv_lora_scale``; the published file has the two
  as booleans ``mla_scale_q_lora``, ``mla_scale_kv_lora``, and the factors are
  the family's modelling code's). QK heads are ``qk_nope_head_dim +
  qk_rope_head_dim`` wide, V heads ``v_head_dim``; the cached row is ``[c_kv |
  k_rope]``. Whole prompts expand keys and values; ``decode``, ``chunk``,
  ``mixed`` and ``verify`` run the absorbed form against the cached rows.
* ``Experts``: ``moe.HeldExperts`` with ``score="softmax"`` over
  ``n_routed_experts + n_zero_experts`` outputs, the chosen scores NOT
  normalised and scaled by ``routed_scaling_factor``; the routed experts are
  the first columns of the router and the zero-compute (identity) experts the
  last; ``m = sum_{chosen, routed} g_i SwiGLU_i(u) + (sum_{chosen, identity}
  g_i) u``. No shared expert. This chip computes the experts it holds
  (``experts_held``) and the identity part of every token.

What the published file has no key for, as ``benchmark/configs/longcat-flash-omni-ep32-l4.json``
states under ``assumed`` with its reasons: ``s_q = (6144 / 1536) ** 0.5 = 2`` and ``s_kv = (6144 /
512) ** 0.5 = 3.4641``; rope on interleaved pairs; ``norm_topk_prob`` false; router columns 0-511
the routed experts and 512-767 the identity ones; an untied head; cos and sin tabulated for
4,096 positions (``block_size``); router logits, softmax, choice and weights in float32.

**How it is served** (serving/runner.py hands a layer ONE cache and carries ONE
stream): each HALF of a double layer is a served layer with its own
``PagedLatent`` pool, and the experts' result rides from the first half to the
second in ``step.shared["shortcut"]``, which is what ``Step.shared`` is for
("what a layer leaves for later layers"). So ``model.h`` holds ``2 * n_layer``
halves, ``h[2l]`` with the experts and ``h[2l + 1]`` without, and the engine,
its allocator and its page tables see ``2 * n_layer`` latent layers. Scopes a
device profile is split by: ``mla_attn``, ``dense_ffn``, ``moe_router``,
``moe_experts``, ``zero_experts``.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from .. import nn
from ..core.trace import named_scope
from ..observability import events as _obs
from ..ops import ltorch
from . import latent_moe
from .moe import HeldExperts


@dataclass
class Config(latent_moe.Config):
    name: str = "tiny-shortcut-moe"
    n_layer: int = 1                 # DOUBLE layers: twice as many attention blocks and pools
    intermediate_size: int = 128     # of each of a layer's two dense FFNs
    n_zero_experts: int = 4          # identity experts, the router's last columns
    n_shared_experts: int = 0
    norm_topk_prob: bool = False
    norm_eps: float = 1e-5

    def __post_init__(self):
        # the family rescales both normed low-rank streams (its published switches are both on)
        self.q_lora_scale = (self.n_embd / self.q_lora_rank) ** 0.5
        self.kv_lora_scale = (self.n_embd / self.kv_lora_rank) ** 0.5


class SwiGLU(nn.Module):
    def __init__(self, n_embd: int, width: int, dtype):
        super().__init__()
        self.gate = nn.Linear(n_embd, width, bias=False, dtype=dtype)
        self.up = nn.Linear(n_embd, width, bias=False, dtype=dtype)
        self.down = nn.Linear(width, n_embd, bias=False, dtype=dtype)

    def forward(self, x):
        return self.down(ltorch.silu(self.gate(x)) * self.up(x))


class Half(nn.Module):
    """One half of a double layer, a served layer: attention over its own latent
    pool and a dense FFN. The FIRST half also holds the layer's experts, which
    read the rows its FFN reads and whose result it leaves in
    ``shared["shortcut"]``; the second takes it from there and adds it."""

    def __init__(self, cfg: Config, dtype, first: bool):
        super().__init__()
        self.norm_1 = nn.RMSNorm(cfg.n_embd, eps=cfg.norm_eps, dtype=dtype)
        self.attn = latent_moe.LatentAttention(cfg, dtype)
        self.norm_2 = nn.RMSNorm(cfg.n_embd, eps=cfg.norm_eps, dtype=dtype)
        self.mlp = SwiGLU(cfg.n_embd, cfg.intermediate_size, dtype)
        self.first = first
        if first:
            self.experts = HeldExperts(cfg.n_embd, cfg.moe_intermediate_size, cfg.n_routed_experts,
                                       tuple(cfg.experts_held), cfg.n_expert_per_token,
                                       n_shared=cfg.n_shared_experts, norm_topk_prob=cfg.norm_topk_prob,
                                       routed_scaling_factor=cfg.routed_scaling_factor,
                                       score="softmax", n_zero=cfg.n_zero_experts, dtype=dtype)

    @property
    def cache(self):
        return self.attn.cache

    def tail(self, x, shared: dict, *routing):
        """What follows a half's attention, x the stream with the attention added;
        ``routing`` is what ``HeldExperts`` takes beside the rows (live, counted,
        counted_rows)."""
        with named_scope("dense_ffn"):
            u = self.norm_2(x)
        if self.first:
            shared["shortcut"] = self.experts(u, *routing)
        with named_scope("dense_ffn"):
            x = x + self.mlp(u)
            return x if self.first else x + shared.pop("shortcut")

    def forward(self, x, where, shared: dict):
        """The half over whole sequences with no cache."""
        with named_scope("mla_attn"):
            u = self.norm_1(x)
            x = x + self.attn.expanded(*self.attn.queries(u, where), *self.attn.latent(u, where))
        return self.tail(x, shared)

    def _served(program: str):
        def run(self, step, x, state):
            with named_scope("mla_attn"):
                h, state = getattr(self.attn, program)(step, self.norm_1(x), state)
                x = x + h
            counted = None
            if program in ("decode", "mixed") and _obs.enabled():  # a trace-time gate, as in latent_moe.Block
                counted = step.shared.setdefault("counted", [])
            return self.tail(x, step.shared, step.shared["live"], counted,
                             step.shared.get("counted_rows")), state

        run.__name__ = program
        return run

    prefill, chunk = _served("prefill"), _served("chunk")
    decode, verify, mixed = _served("decode"), _served("verify"), _served("mixed")
    del _served


class ShortcutMoE(latent_moe.LatentMoE):
    """Embedding, ``n_layer`` double layers as ``2 * n_layer`` halves, a final
    RMSNorm and an untied head; positions, rope rows and ``serving()`` are
    ``LatentMoE``'s."""

    @staticmethod
    def blocks(cfg: Config, dtype) -> list:
        return [Half(cfg, dtype, first=i % 2 == 0) for i in range(2 * cfg.n_layer)]

    def through(self, x, where):
        shared: dict = {}
        for half in self.h:
            x = half(x, where, shared)
        return x
