"""LitGPT-style configurable transformer in thunder_tpu's op language.

Capability counterpart of the reference's in-repo model zoo
(thunder/tests/litgpt_model.py — LitGPT config + GPT reimplementation used by
its benchmarks and network tests). Covers the same architectural axes: RoPE,
RMSNorm/LayerNorm, GQA (n_query_groups), GptNeox vs LLaMA (SwiGLU) MLPs,
parallel residuals, tied/untied heads. Configs include Llama-2/Llama-3 class
models plus tiny test configs.

TPU notes: weights default to bfloat16-friendly fp32 masters; attention runs
through ltorch.sdpa which the Pallas flash-attention executor claims whole."""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..core.trace import named_scope
from ..ops import ltorch


@dataclass
class Config:
    name: str = "tiny"
    block_size: int = 128
    vocab_size: int = 512
    padded_vocab_size: Optional[int] = None
    n_layer: int = 2
    n_head: int = 4
    n_embd: int = 64
    head_size: Optional[int] = None
    n_query_groups: Optional[int] = None
    rotary_percentage: float = 1.0
    parallel_residual: bool = False
    bias: bool = False
    norm_class_name: str = "RMSNorm"
    mlp_class_name: str = "LLaMAMLP"
    intermediate_size: Optional[int] = None
    norm_eps: float = 1e-5
    rope_base: int = 10000
    lm_head_bias: bool = False
    shared_embedding: bool = False
    # an RMSNorm over head_size, with a learned weight, on every head of q and of k before
    # rope (Qwen3's q_norm / k_norm; litgpt's key of the same name)
    norm_qk: bool = False
    # recompute each transformer block in the backward instead of saving its
    # activations (remat.checkpoint -> RECOMPUTE_IN_BACKWARD machinery)
    activation_checkpoint: bool = False

    def __post_init__(self):
        if self.padded_vocab_size is None:
            self.padded_vocab_size = _next_multiple(self.vocab_size, 128)
        if self.head_size is None:
            self.head_size = self.n_embd // self.n_head
        if self.n_query_groups is None:
            self.n_query_groups = self.n_head
        if self.intermediate_size is None:
            self.intermediate_size = 4 * self.n_embd

    @property
    def rope_n_elem(self) -> int:
        return int(self.rotary_percentage * self.head_size)

    @classmethod
    def from_name(cls, name: str, **overrides) -> "Config":
        cfg = dict(configs[name])
        cfg.update(overrides)
        return cls(**cfg)


def _next_multiple(n: int, k: int) -> int:
    return ((n + k - 1) // k) * k


configs: dict[str, dict] = {
    "tiny": dict(name="tiny", block_size=128, vocab_size=512, n_layer=2, n_head=4, n_embd=64),
    "tiny-llama2": dict(
        name="tiny-llama2", block_size=256, vocab_size=320, n_layer=3, n_head=4, n_query_groups=2,
        n_embd=128, intermediate_size=352, norm_class_name="RMSNorm", mlp_class_name="LLaMAMLP",
    ),
    "tiny-gptneox": dict(
        name="tiny-gptneox", block_size=128, vocab_size=320, n_layer=2, n_head=4, n_embd=64,
        norm_class_name="LayerNorm", mlp_class_name="GptNeoxMLP", parallel_residual=True, bias=True,
    ),
    # benchmark-class configs (matching LitGPT hyperparameters)
    "nanogpt-124m": dict(
        name="nanogpt-124m", block_size=1024, vocab_size=50257, n_layer=12, n_head=12, n_embd=768,
        norm_class_name="LayerNorm", mlp_class_name="GptNeoxMLP", bias=True,
    ),
    # largest Llama-2-class config that trains on ONE v5e chip (16 GB) with
    # AdamW fp32 state — the single-chip north-star shape (BASELINE.json)
    "llama-350m": dict(
        name="llama-350m", block_size=2048, vocab_size=32000, padded_vocab_size=32000,
        n_layer=24, n_head=16, n_embd=1024, intermediate_size=2816,
        norm_class_name="RMSNorm", mlp_class_name="LLaMAMLP", rope_base=10000,
    ),
    # 1.02B-param Llama-class config (width 2048, head_dim 128, GQA 4 groups,
    # vocab 32k): the largest round shape whose AdamW-f32 state (~12.2 GB)
    # plus remat'd activations trains on one 16 GB chip at B=1, T=2048
    "llama-1b": dict(
        name="llama-1b", block_size=2048, vocab_size=32000, padded_vocab_size=32000,
        n_layer=20, n_head=16, n_query_groups=4, n_embd=2048, intermediate_size=5504,
        norm_class_name="RMSNorm", mlp_class_name="LLaMAMLP", rope_base=10000,
    ),
    "Llama-2-7b-hf": dict(
        name="Llama-2-7b-hf", block_size=4096, vocab_size=32000, padded_vocab_size=32000,
        n_layer=32, n_head=32, n_embd=4096, intermediate_size=11008,
        norm_class_name="RMSNorm", mlp_class_name="LLaMAMLP", rope_base=10000,
    ),
    # Llama-2-7B at full width (4096 / head_dim 128 / MLP 11008 / vocab 32k)
    # truncated to 4 blocks: the deepest 7B-dims stack whose AdamW f32 state
    # fits one 16 GB chip — per-layer compute is EXACTLY the 7B model's, so
    # its MFU is the honest single-chip 7B-shape number (BENCH_7B.json)
    "llama-7b-block4": dict(
        name="llama-7b-block4", block_size=4096, vocab_size=32000, padded_vocab_size=32000,
        n_layer=4, n_head=32, n_embd=4096, intermediate_size=11008,
        norm_class_name="RMSNorm", mlp_class_name="LLaMAMLP", rope_base=10000,
    ),
    "Llama-2-13b-hf": dict(
        name="Llama-2-13b-hf", block_size=4096, vocab_size=32000, padded_vocab_size=32000,
        n_layer=40, n_head=40, n_embd=5120, intermediate_size=13824,
        norm_class_name="RMSNorm", mlp_class_name="LLaMAMLP", rope_base=10000,
    ),
    "Llama-3-8B": dict(
        name="Llama-3-8B", block_size=8192, vocab_size=128000, padded_vocab_size=128256,
        n_layer=32, n_head=32, n_query_groups=8, n_embd=4096, intermediate_size=14336,
        norm_class_name="RMSNorm", mlp_class_name="LLaMAMLP", rope_base=500000,
    ),
    "Llama-3-1B": dict(
        name="Llama-3-1B", block_size=8192, vocab_size=128000, padded_vocab_size=128256,
        n_layer=16, n_head=32, n_query_groups=8, n_embd=2048, intermediate_size=8192,
        norm_class_name="RMSNorm", mlp_class_name="LLaMAMLP", rope_base=500000,
    ),
}


def _norm(cfg: Config, dtype):
    if cfg.norm_class_name == "RMSNorm":
        return nn.RMSNorm(cfg.n_embd, eps=cfg.norm_eps, dtype=dtype)
    return nn.LayerNorm(cfg.n_embd, eps=cfg.norm_eps, dtype=dtype)


class GptNeoxMLP(nn.Module):
    def __init__(self, cfg: Config, dtype=jnp.float32):
        super().__init__()
        self.fc = nn.Linear(cfg.n_embd, cfg.intermediate_size, bias=cfg.bias, dtype=dtype)
        self.proj = nn.Linear(cfg.intermediate_size, cfg.n_embd, bias=cfg.bias, dtype=dtype)

    def forward(self, x):
        return self.proj(ltorch.gelu(self.fc(x), approximate="tanh"))


class LLaMAMLP(nn.Module):
    def __init__(self, cfg: Config, dtype=jnp.float32):
        super().__init__()
        self.fc_1 = nn.Linear(cfg.n_embd, cfg.intermediate_size, bias=cfg.bias, dtype=dtype)
        self.fc_2 = nn.Linear(cfg.n_embd, cfg.intermediate_size, bias=cfg.bias, dtype=dtype)
        self.proj = nn.Linear(cfg.intermediate_size, cfg.n_embd, bias=cfg.bias, dtype=dtype)

    def forward(self, x):
        return self.proj(ltorch.silu(self.fc_1(x)) * self.fc_2(x))


class CausalSelfAttention(nn.Module):
    def __init__(self, cfg: Config, dtype=jnp.float32):
        super().__init__()
        self.cfg = cfg
        shape = (cfg.n_head + 2 * cfg.n_query_groups) * cfg.head_size
        self.attn = nn.Linear(cfg.n_embd, shape, bias=cfg.bias, dtype=dtype)
        self.proj = nn.Linear(cfg.n_head * cfg.head_size, cfg.n_embd, bias=cfg.bias, dtype=dtype)
        if cfg.norm_qk:
            self.norm_q = nn.RMSNorm(cfg.head_size, eps=cfg.norm_eps, dtype=dtype)
            self.norm_k = nn.RMSNorm(cfg.head_size, eps=cfg.norm_eps, dtype=dtype)

    def forward(self, x, cos, sin, block_length=None):
        """``block_length`` K: attention is BLOCK-causal, a position sees every earlier block
        of K positions and the whole of its own (generation by diffusion over blocks); None
        is plain causal."""
        cfg = self.cfg
        B, T, _ = x.shape
        nh, ng, hs = cfg.n_head, cfg.n_query_groups, cfg.head_size
        qkv = self.attn(x)
        # split grouped qkv: (B, T, (nh + 2*ng) * hs)
        q_per_kv = nh // ng
        qkv = ltorch.reshape(qkv, (B, T, ng, q_per_kv + 2, hs))
        q = qkv[:, :, :, : q_per_kv, :]
        k = qkv[:, :, :, q_per_kv: q_per_kv + 1, :]
        v = qkv[:, :, :, q_per_kv + 1:, :]
        q = ltorch.reshape(q, (B, T, nh, hs))
        k = ltorch.reshape(k, (B, T, ng, hs))
        v = ltorch.reshape(v, (B, T, ng, hs))
        q, k = norm_qk(self, q, k)
        q = ltorch.permute(q, (0, 2, 1, 3))  # (B, nh, T, hs)
        k = ltorch.permute(k, (0, 2, 1, 3))
        v = ltorch.permute(v, (0, 2, 1, 3))

        n_elem = cfg.rope_n_elem
        from ..parallel.context_parallel import current_seq_parallel_ctx

        if (block_length is None and 0 < n_elem <= hs and n_elem % 2 == 0
                and current_seq_parallel_ctx() is None):
            # fused rope+attention symbol (GQA included: the kernel indexes
            # kv blocks by q_head // group; a rotary width narrower than the
            # head included: the tables are (T, n_elem) and say it): the pallas
            # executor applies rope in-kernel and rotates the rope VJP in-kernel
            # in backward; ring-attention CP rewrites plain sdpa bsyms, so it
            # keeps the decomposed path
            y = ltorch.rope_sdpa(q, k, v, cos, sin, is_causal=True,
                                 scale=1.0 / math.sqrt(hs))
        else:
            q = _apply_rope(q, cos, sin, n_elem)
            k = _apply_rope(k, cos, sin, n_elem)
            if ng != nh:
                k = _repeat_kv(k, q_per_kv)
                v = _repeat_kv(v, q_per_kv)
            # the flash kernels compare positions by index: a block-causal mask goes explicitly
            mask = None if block_length is None else block_causal_mask(T, block_length, x.device)
            y = ltorch.sdpa(q, k, v, attn_mask=mask, is_causal=mask is None, scale=1.0 / math.sqrt(hs))
        y = ltorch.reshape(ltorch.permute(y, (0, 2, 1, 3)), (B, T, nh * hs))
        return self.proj(y)


def norm_qk(attn, q, k):
    """The per-head RMSNorm of q and of k (any layout whose last dimension is the head's),
    before rope, where ``cfg.norm_qk`` says so; else q and k as they are. The ONE place it is
    applied from: ``CausalSelfAttention.forward`` and ``inference.split_qkv_rope`` both call
    it, so the compiled forward and the served layers cannot drift."""
    if not attn.cfg.norm_qk:
        return q, k
    return attn.norm_q(q), attn.norm_k(k)


def block_causal_mask(T: int, block_length: int, device):
    """(T, T) bool: position t sees position s where s's block is t's or an earlier one."""
    from ..core import dtypes, prims

    blk = ltorch.floor_divide(prims.iota(T, dtype=dtypes.int32, device=device), block_length)
    return ltorch.ge(ltorch.unsqueeze(blk, 1), ltorch.unsqueeze(blk, 0))


def _repeat_kv(x, n: int):
    # (B, ng, T, hs) -> (B, ng*n, T, hs)
    B, ng, T, hs = x.shape
    x = ltorch.unsqueeze(x, 2)
    x = ltorch.expand(x, (B, ng, n, T, hs))
    return ltorch.reshape(x, (B, ng * n, T, hs))


def _apply_rope(x, cos, sin, n_elem: int):
    """Half-split RoPE over the first `n_elem` columns, for the roads that do
    not go through `ltorch.rope_sdpa`: a context-parallel trace (ring attention
    rewrites plain sdpa), an odd rotary width, and serving, which rotates a
    prompt's or a step's rows itself (`inference.split_qkv_rope`). Structured
    as half-width muls with ONE final concat:
    the cat([-x2, x1])-then-multiply form pays an extra full-width
    materialize + awkward slice/negate fusions in XLA (profiled ~16 ms/step
    on llama-350m); with duplicated-half caches cos[:d/2] == cos[d/2:], so
    out1 = x1·c − x2·s and out2 = x2·c + x1·s need no concat until the end."""
    if n_elem <= 0:
        return x
    hs = x.shape[-1]
    h = n_elem // 2
    with named_scope("rope"):
        x1 = x[..., :h]
        x2 = x[..., h:n_elem]
        c = cos[..., :h]
        s = sin[..., :h]
        out1 = x1 * c - x2 * s
        out2 = x2 * c + x1 * s
        if n_elem < hs:
            return ltorch.cat([out1, out2, x[..., n_elem:]], -1)
        return ltorch.cat([out1, out2], -1)


class Block(nn.Module):
    def __init__(self, cfg: Config, dtype=jnp.float32):
        super().__init__()
        self.cfg = cfg
        self.norm_1 = _norm(cfg, dtype)
        self.attn = CausalSelfAttention(cfg, dtype)
        self.norm_2 = _norm(cfg, dtype)
        self.mlp = {"LLaMAMLP": LLaMAMLP, "GptNeoxMLP": GptNeoxMLP}[cfg.mlp_class_name](cfg, dtype)

    def forward(self, x, cos, sin):
        with named_scope("attn"):
            h = self.attn(self.norm_1(x), cos, sin)
        return self.tail(x, h)

    def tail(self, x, h):
        """The block's output from its input ``x`` and its attention's output
        ``h``: the residuals and the MLP. What a caller that runs the attention
        itself (the cached and the paged engines) asks of a block. The attention's
        residual is the last of ``attn``; the norm, the MLP and its residual are ``mlp``."""
        with named_scope("attn"):
            x_h = x + h
        with named_scope("mlp"):
            return x_h + self.mlp(self.norm_2(x if self.cfg.parallel_residual else x_h))


class GPT(nn.Module):
    def __init__(self, cfg: Config, dtype=jnp.float32):
        super().__init__()
        self.cfg = cfg
        self.wte = nn.Embedding(cfg.padded_vocab_size, cfg.n_embd, dtype=dtype)
        self.h = nn.ModuleList([Block(cfg, dtype) for _ in range(cfg.n_layer)])
        self.ln_f = _norm(cfg, dtype)
        self.lm_head = nn.Linear(cfg.n_embd, cfg.padded_vocab_size, bias=cfg.lm_head_bias, dtype=dtype)
        cos, sin = build_rope_cache(cfg.block_size, cfg.rope_n_elem, cfg.rope_base, dtype)
        self.register_buffer("cos", cos)
        self.register_buffer("sin", sin)

    def forward(self, idx):
        from ..transforms import remat

        B, T = idx.shape
        with named_scope("attn/rope"):
            cos, sin = rope_slice(self.cos, self.sin, T)
        with named_scope("embed"):
            x = self.wte(idx)
        for block in self.h:
            if self.cfg.activation_checkpoint:
                x = remat.checkpoint(block)(x, cos, sin)
            else:
                x = block(x, cos, sin)
        with named_scope("head"):
            return self.lm_head(self.ln_f(x))


class GPTForCausalLM(nn.Module):
    """GPT + shifted cross-entropy loss — the pretraining step target."""

    def __init__(self, cfg: Config, dtype=jnp.float32):
        super().__init__()
        self.gpt = GPT(cfg, dtype)
        self.cfg = cfg

    def forward(self, idx, targets):
        logits = self.gpt(idx)
        B, T, V = logits.shape
        with named_scope("head"):
            return ltorch.cross_entropy(
                ltorch.reshape(logits, (B * T, V)), ltorch.reshape(targets, (B * T,))
            )


def rope_slice(cos_full, sin_full, T: int):
    """Positions [0, T) normally; under context-parallel tracing the device's
    sequence block [idx*T, (idx+1)*T) — local tokens carry global positions."""
    from ..parallel.context_parallel import current_seq_parallel_ctx

    ctx = current_seq_parallel_ctx()
    if ctx is None:
        return cos_full[:T], sin_full[:T]
    from ..core import prims
    from ..ops import clang
    from ..parallel import prims as dist_prims

    axis, _ = ctx
    n_elem = cos_full.shape[-1]
    offset = dist_prims.axis_index(axis) * T
    cos = prims.dynamic_slice(clang.ensure_proxy(cos_full), (offset, 0), (T, n_elem))
    sin = prims.dynamic_slice(clang.ensure_proxy(sin_full), (offset, 0), (T, n_elem))
    return cos, sin


def build_rope_cache(seq_len: int, n_elem: int, base: int = 10000, dtype=jnp.float32):
    if n_elem <= 0:
        z = jnp.zeros((seq_len, 0), dtype)
        return z, z
    theta = 1.0 / (base ** (jnp.arange(0, n_elem, 2, dtype=jnp.float32) / n_elem))
    seq = jnp.arange(seq_len, dtype=jnp.float32)
    idx_theta = jnp.outer(seq, theta)  # (T, n_elem/2)
    idx_theta = jnp.concatenate([idx_theta, idx_theta], axis=-1)  # (T, n_elem)
    return jnp.cos(idx_theta).astype(dtype), jnp.sin(idx_theta).astype(dtype)


def name_to_config(name: str) -> dict:
    return configs[name]
