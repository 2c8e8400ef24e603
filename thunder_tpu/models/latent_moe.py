"""A latent-attention mixture-of-experts decoder (DeepSeek-V2/V3's layer,
arXiv:2405.04434 and arXiv:2412.19437; the ``mistral4`` family carries the same
keys) in thunder_tpu's op language, as ONE CHIP of an expert-parallel group
holds it.

One layer, ``x`` the residual stream, RMSNorm, no biases:

    h = x + MLA(norm_1(x));   y = h + Experts(norm_2(h))

* **MLA** (multi-head latent attention). Queries through a low-rank pair:
  ``c_q = s_q RMSNorm(x W_qa)``, ``q = c_q W_qb``, a head ``[q_nope | q_rope]``.
  Keys and values through ONE latent a token: ``[c | k_r] = x W_kva``,
  ``c_kv = s_kv RMSNorm(c)``, ``k_rope = rope(k_r)`` (one head, shared by all;
  ``s_q``, ``s_kv``: ``Config.q_lora_scale``, ``kv_lora_scale``, 1 in DeepSeek's layer);
  a head's ``[k_nope | v] = c_kv W_kvb``. Scores are ``(q_nope . k_nope +
  q_rope . k_rope) * scale`` under a causal float32 softmax. Rope is on
  interleaved pairs with YaRN frequencies (arXiv:2309.00071); queries are
  multiplied by ``1 + beta ln(1 + floor(pos / original))``.
* **The cache holds ``c_kv`` and ``k_rope`` only** (``kv_pages.PagedLatent``):
  one row a token a layer, no per-head keys or values.
* Whole prompts (``prefill``, ``forward``) EXPAND keys and values from the
  latent and run plain causal attention. ``decode``, ``chunk`` and ``verify``
  run the ABSORBED form against the cached rows: ``q_lat = q_nope W_kvb^K``
  carries a head's query into the latent, ``score = q_lat . c_kv + q_rope .
  k_rope``, ``o_lat = softmax . c_kv`` and ``o = o_lat W_kvb^V`` — one
  ``ltorch.paged_latent_attention`` over a pool every head reads once. Both
  forms use one set of weights.
* **Experts**: ``moe.HeldExperts`` — the router over all experts, the rows
  of the experts held here through sorted ragged groups, the shared expert.

``Block`` is a served layer (serving/runner.py): it declares its latent rows
and gives ``prefill``, ``chunk``, ``decode`` and ``verify``, each part under the
named scope a device profile is split by (``mla_attn``, ``moe_router``,
``moe_experts``, ``shared_expert``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core import dtypes, prims
from ..core.trace import named_scope
from ..observability import events as _obs
from ..ops import clang, ltorch
from .moe import HeldExperts


@dataclass
class Config:
    name: str = "tiny-latent-moe"
    block_size: int = 256            # the most positions a sequence may have (rope rows)
    vocab_size: int = 512
    n_layer: int = 2
    n_embd: int = 64
    n_head: int = 4
    q_lora_rank: int = 32
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 16
    v_head_dim: int = 32
    moe_intermediate_size: int = 64
    n_routed_experts: int = 8        # the router's width
    experts_held: tuple = (0, 8)     # [lo, hi) of them live here
    n_expert_per_token: int = 2
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 1.0         # YaRN: 1 is plain rope
    rope_original: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0      # 0: the softmax scale is plain qk_head_dim ** -0.5
    query_scaling_beta: float = 0.0
    # what the two normed low-rank streams are multiplied by (LongCat-Flash's
    # ``mla_scale_q_lora`` / ``mla_scale_kv_lora``: ``(n_embd / rank) ** 0.5``); 1 traces nothing
    q_lora_scale: float = 1.0
    kv_lora_scale: float = 1.0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim ** -0.5 * m ** 2`` with ``m = 0.1 mscale_all_dim ln(factor) + 1``
        (DeepSeek-V3's YaRN convention: the attention's temperature rides on the scale)."""
        scale = self.qk_head_dim ** -0.5
        if self.rope_factor > 1.0 and self.mscale_all_dim:
            scale *= yarn_mscale(self.rope_factor, self.mscale_all_dim) ** 2
        return scale


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int, beta_fast: float,
                  beta_slow: float) -> np.ndarray:
    """The ``dim / 2`` rope frequencies under YaRN (arXiv:2309.00071, section 3.2):
    pairs that turn more than ``beta_fast`` times over the original context keep
    their frequency, those that turn less than ``beta_slow`` times have it divided
    by ``factor``, and a linear ramp lies between."""
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1.0:
        return (1.0 / pos_freqs).astype(np.float32)

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp
    return ((1.0 / (factor * pos_freqs)) * (1.0 - keep) + (1.0 / pos_freqs) * keep).astype(np.float32)


def rope_tables(cfg: Config):
    """(cos, sin), each (block_size, qk_rope_head_dim / 2) float32, times YaRN's
    attention factor ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
    inv = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_factor, cfg.rope_original,
                        cfg.beta_fast, cfg.beta_slow)
    factor = 1.0
    if cfg.rope_factor > 1.0 and cfg.mscale_all_dim:
        factor = yarn_mscale(cfg.rope_factor, cfg.mscale) / yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim)
    angle = np.outer(np.arange(cfg.block_size, dtype=np.float64), inv.astype(np.float64))
    return (jnp.asarray(np.cos(angle) * factor, jnp.float32),
            jnp.asarray(np.sin(angle) * factor, jnp.float32))


def _scaled(x, factor: float):
    """``x * factor`` in float32, handed back in x's type; ``x`` itself at 1."""
    if factor == 1.0:
        return x
    return ltorch.to(ltorch.to(x, dtype=dtypes.float32) * factor, dtype=x.dtype)


def rope_interleaved(x, cos, sin):
    """Rope on the interleaved pairs ``(x[2i], x[2i + 1])`` of the last axis, in
    float32, handed back in x's type; cos and sin broadcast against
    ``x[..., ::2]``. The columns stay where they are."""
    shape = tuple(x.shape)
    pairs = ltorch.to(ltorch.reshape(x, shape[:-1] + (shape[-1] // 2, 2)), dtype=dtypes.float32)
    a, b = pairs[..., 0], pairs[..., 1]
    out = ltorch.stack([a * cos - b * sin, b * cos + a * sin], -1)
    return ltorch.to(ltorch.reshape(out, shape), dtype=x.dtype)


class LatentAttention(nn.Module):
    def __init__(self, cfg: Config, dtype):
        super().__init__()
        from ..serving.kv_pages import PagedLatent

        self.cfg = cfg
        H, nope, rope, v = cfg.n_head, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        self.q_a = nn.Linear(cfg.n_embd, cfg.q_lora_rank, bias=False, dtype=dtype)
        self.q_norm = nn.RMSNorm(cfg.q_lora_rank, eps=cfg.norm_eps, dtype=dtype)
        self.q_b = nn.Linear(cfg.q_lora_rank, H * (nope + rope), bias=False, dtype=dtype)
        self.kv_a = nn.Linear(cfg.n_embd, cfg.kv_lora_rank + rope, bias=False, dtype=dtype)
        self.kv_norm = nn.RMSNorm(cfg.kv_lora_rank, eps=cfg.norm_eps, dtype=dtype)
        # a head's rows of kv_b are its [k_nope | v] columns of W_kvb
        self.kv_b = nn.Linear(cfg.kv_lora_rank, H * (nope + v), bias=False, dtype=dtype)
        self.o = nn.Linear(H * v, cfg.n_embd, bias=False, dtype=dtype)
        self.cache = PagedLatent(cfg.kv_lora_rank + rope)
        self.scale = cfg.softmax_scale

    # -- the two low-rank paths ------------------------------------------------
    def queries(self, x, where):
        """(q_nope (B, T, H, nope), q_rope (B, T, H, rope)) of the normed tokens
        x, roped and scaled for their positions (``where``: cos, sin (B, T, 1,
        rope / 2) and the positions' query scale (B, T, 1, 1))."""
        cfg = self.cfg
        B, T, _ = x.shape
        cos, sin, qscale = where
        c_q = _scaled(self.q_norm(self.q_a(x)), cfg.q_lora_scale)
        q = ltorch.reshape(self.q_b(c_q), (B, T, cfg.n_head, cfg.qk_head_dim))
        q = ltorch.to(ltorch.to(q, dtype=dtypes.float32) * qscale, dtype=q.dtype)
        return (q[..., :cfg.qk_nope_head_dim],
                rope_interleaved(q[..., cfg.qk_nope_head_dim:], cos, sin))

    def latent(self, x, where):
        """What the cache holds of the normed tokens x: (c_kv (B, T, kv_lora_rank),
        k_rope (B, T, rope))."""
        r = self.cfg.kv_lora_rank
        cos, sin, _ = where
        ckr = self.kv_a(x)
        return (_scaled(self.kv_norm(ckr[..., :r]), self.cfg.kv_lora_scale),
                rope_interleaved(ckr[..., r:], cos[:, :, 0], sin[:, :, 0]))

    def rows(self, c, k_rope):
        """(B, T, row): the pool's rows, zeros over the padding."""
        B, T, _ = c.shape
        pad = self.cache.row - self.cache.width
        parts = [c, k_rope] + ([ltorch.zeros(B, T, pad, device=c.device, dtype=c.dtype)] if pad else [])
        return ltorch.cat(parts, -1)

    def _kvb(self):
        """W_kvb by head: (keys (H, nope, r), values (H, v, r))."""
        cfg = self.cfg
        w = ltorch.reshape(self.kv_b.weight, (cfg.n_head, cfg.qk_nope_head_dim + cfg.v_head_dim,
                                              cfg.kv_lora_rank))
        return w[:, :cfg.qk_nope_head_dim], w[:, cfg.qk_nope_head_dim:]

    # -- expanded: whole sequences, no cache ------------------------------------
    def expanded(self, q_nope, q_rope, c, k_rope):
        cfg = self.cfg
        B, T, H, _ = q_nope.shape
        kv = ltorch.reshape(self.kv_b(c), (B, T, H, cfg.qk_nope_head_dim + cfg.v_head_dim))
        k = ltorch.cat([kv[..., :cfg.qk_nope_head_dim],
                        ltorch.expand(ltorch.unsqueeze(k_rope, 2), (B, T, H, cfg.qk_rope_head_dim))], -1)
        q = ltorch.cat([q_nope, q_rope], -1)
        heads_first = (0, 2, 1, 3)
        y = ltorch.sdpa(ltorch.permute(q, heads_first), ltorch.permute(k, heads_first),
                        ltorch.permute(kv[..., cfg.qk_nope_head_dim:], heads_first),
                        is_causal=True, scale=self.scale)
        return self.o(ltorch.reshape(ltorch.permute(y, heads_first), (B, T, H * cfg.v_head_dim)))

    # -- absorbed: against the cached rows ---------------------------------------
    def absorbed(self, q_nope, q_rope, pool, table, q_pos):
        """The heads' outputs (B, T, H * v), before the output projection."""
        cfg = self.cfg
        B, T, H, nope = q_nope.shape
        r, v = cfg.kv_lora_rank, cfg.v_head_dim
        w_k, w_v = self._kvb()
        by_head = ltorch.reshape(ltorch.permute(q_nope, (2, 0, 1, 3)), (H, B * T, nope))
        q_lat = ltorch.permute(ltorch.reshape(ltorch.matmul(by_head, w_k), (H, B, T, r)), (1, 0, 2, 3))
        parts = [q_lat, ltorch.permute(q_rope, (0, 2, 1, 3))]
        pad = self.cache.row - self.cache.width
        if pad:
            parts.append(ltorch.zeros(B, H, T, pad, device=q_lat.device, dtype=q_lat.dtype))
        o_lat = ltorch.paged_latent_attention(ltorch.cat(parts, -1), pool, table, q_pos, self.scale, r)
        o_lat = ltorch.reshape(ltorch.permute(o_lat, (1, 0, 2, 3)), (H, B * T, r))
        y = ltorch.matmul(o_lat, ltorch.transpose(w_v, 1, 2))                 # (H, B * T, v)
        return ltorch.reshape(ltorch.permute(ltorch.reshape(y, (H, B, T, v)), (1, 2, 0, 3)),
                              (B, T, H * v))

    # -- served ------------------------------------------------------------------
    def prefill(self, step, x, state):
        where = step.shared["rope"]
        c, k_rope = self.latent(x, where)
        ps = step.page_size
        blocks = ltorch.reshape(self.rows(c, k_rope), (x.shape[1] // ps, ps, self.cache.row))
        pool = ltorch.index_put(state[0], (step.page_ids["full"],), blocks)
        return self.expanded(*self.queries(x, where), c, k_rope), (pool,)

    def _projected(self, x, where):
        """What the absorbed form needs of the normed tokens x: the queries
        (q_nope, q_rope) and the pool's rows (B, T, row)."""
        return self.queries(x, where), self.rows(*self.latent(x, where))

    def _chunk_rows(self, step, q, rows, state):
        """Writes a chunk's rows (1, T, row), then attends the whole table: the
        pages written before (shared prefix pages among them) and its own."""
        ps = step.page_size
        blocks = ltorch.reshape(rows, (rows.shape[1] // ps, ps, self.cache.row))
        pool = ltorch.index_put(state[0], (step.chunk_pages["full"],), blocks)
        return self.absorbed(*q, pool, step.tables["full"], step.q_pos), (pool,)

    def _token_rows(self, step, q, rows, state, q_pos):
        """decode and verify: every token's row to its page and slot, then the
        absorbed form at the tokens' positions."""
        tok = ltorch.reshape(rows, (-1, self.cache.row))
        pool = ltorch.index_put(state[0], (step.page_of["full"], step.slot_in_page), tok)
        return self.absorbed(*q, pool, step.tables["full"], q_pos), (pool,)

    def chunk(self, step, x, state):
        y, state = self._chunk_rows(step, *self._projected(x, step.shared["rope"]), state)
        return self.o(y), state

    def decode(self, step, x, state):
        y, state = self._token_rows(step, *self._projected(x, step.shared["rope"]), state,
                                    ltorch.reshape(step.pos, (-1, 1)))
        return self.o(y), state

    def verify(self, step, x, state):
        y, state = self._token_rows(step, *self._projected(x, step.shared["rope"]), state,
                                    step.pos_mat)
        return self.o(y), state

    def mixed(self, step, x, state):
        """A chunk's T rows and after them one row a decode slot, x (1, T + B, D):
        one operand of the two low-rank paths and of the output projection; the
        writes and the absorbed attention split, each kind of row through the
        body its own program runs (``step.chunk``, ``step.decode``)."""
        T = step.chunk.T
        B = x.shape[1] - T
        (q_nope, q_rope), rows = self._projected(x, step.shared["rope"])

        def seqs(a):  # the decode rows, a sequence each: (1, B, ..) -> (B, 1, ..)
            return ltorch.reshape(a[:, T:], (B, 1) + tuple(a.shape[2:]))

        y_c, state = self._chunk_rows(step.chunk, (q_nope[:, :T], q_rope[:, :T]), rows[:, :T], state)
        y_d, state = self._token_rows(step.decode, (seqs(q_nope), seqs(q_rope)), rows[:, T:], state,
                                      ltorch.reshape(step.decode.pos, (-1, 1)))
        return self.o(ltorch.cat([y_c, ltorch.reshape(y_d, (1, B, y_d.shape[-1]))], 1)), state


class Block(nn.Module):
    def __init__(self, cfg: Config, dtype):
        super().__init__()
        self.norm_1 = nn.RMSNorm(cfg.n_embd, eps=cfg.norm_eps, dtype=dtype)
        self.attn = LatentAttention(cfg, dtype)
        self.norm_2 = nn.RMSNorm(cfg.n_embd, eps=cfg.norm_eps, dtype=dtype)
        self.experts = HeldExperts(cfg.n_embd, cfg.moe_intermediate_size, cfg.n_routed_experts,
                                   tuple(cfg.experts_held), cfg.n_expert_per_token,
                                   n_shared=cfg.n_shared_experts, norm_topk_prob=cfg.norm_topk_prob,
                                   routed_scaling_factor=cfg.routed_scaling_factor, dtype=dtype)

    @property
    def cache(self):
        return self.attn.cache

    def forward(self, x, where):
        """The layer over whole sequences with no cache."""
        with named_scope("mla_attn"):
            u = self.norm_1(x)
            x = x + self.attn.expanded(*self.attn.queries(u, where), *self.attn.latent(u, where))
        return x + self.experts(self.norm_2(x))

    def _served(program: str):
        def run(self, step, x, state):
            with named_scope("mla_attn"):
                h, state = getattr(self.attn, program)(step, self.norm_1(x), state)
            x = x + h
            counted = None
            if program in ("decode", "mixed") and _obs.enabled():  # a trace-time gate, as in moe.MoEMLP
                counted = step.shared.setdefault("counted", [])
            # a mixed program counts its decode rows only, as the decode program does
            return x + self.experts(self.norm_2(x), step.shared["live"], counted,
                                    step.shared.get("counted_rows")), state

        run.__name__ = program
        return run

    prefill, chunk = _served("prefill"), _served("chunk")
    decode, verify, mixed = _served("decode"), _served("verify"), _served("mixed")
    del _served


class LatentMoE(nn.Module):
    def __init__(self, cfg: Config, dtype=jnp.float32):
        super().__init__()
        self.cfg = cfg
        self.wte = nn.Embedding(cfg.vocab_size, cfg.n_embd, dtype=dtype)
        self.h = nn.ModuleList(self.blocks(cfg, dtype))
        self.ln_f = nn.RMSNorm(cfg.n_embd, eps=cfg.norm_eps, dtype=dtype)
        self.lm_head = nn.Linear(cfg.n_embd, cfg.vocab_size, bias=False, dtype=dtype)
        cos, sin = rope_tables(cfg)
        self.register_buffer("cos", cos)
        self.register_buffer("sin", sin)

    @staticmethod
    def blocks(cfg: Config, dtype) -> list:
        """The served layers, in order (a model with another layer brings its own:
        models/shortcut_moe.py)."""
        return [Block(cfg, dtype) for _ in range(cfg.n_layer)]

    def where(self, pos):
        """What a layer needs of the positions pos (B, T) int32: the rope rows
        (B, T, 1, rope / 2) and the query scale (B, T, 1, 1)."""
        cfg = self.cfg
        B, T = pos.shape
        half = cfg.qk_rope_head_dim // 2
        flat = ltorch.reshape(ltorch.clamp(pos, max=cfg.block_size - 1), (B * T,))
        cos = ltorch.reshape(clang.take(clang.ensure_proxy(self.cos), flat, 0), (B, T, 1, half))
        sin = ltorch.reshape(clang.take(clang.ensure_proxy(self.sin), flat, 0), (B, T, 1, half))
        wraps = ltorch.to(ltorch.floor_divide(pos, cfg.rope_original), dtype=dtypes.float32)
        qscale = 1.0 + cfg.query_scaling_beta * ltorch.log(1.0 + wraps)
        return cos, sin, ltorch.reshape(qscale, (B, T, 1, 1))

    def forward(self, idx):
        """Logits (B, T, V) of whole sequences, with no cache."""
        B, T = idx.shape
        pos = ltorch.expand(ltorch.reshape(prims.iota(T, dtype=dtypes.int32, device=idx.device), (1, T)),
                            (B, T))
        where = self.where(pos)
        return self.lm_head(self.ln_f(self.through(self.wte(idx), where)))

    def through(self, x, where):
        """The layers over whole sequences x (B, T, D) with no cache."""
        for block in self.h:
            x = block(x, where)
        return x

    def serving(self):
        """This model as the paged engine serves it (serving/runner.py)."""
        return _Served(self)


class _Served:
    def __init__(self, model: LatentMoE):
        self.model = model
        self.layers = list(model.h)
        self.max_positions = model.cfg.block_size

    def begin(self, step) -> None:
        """The program's positions: their rope rows and query scale, and which
        of its tokens are no padding (an idle decode slot, a bucket's tail),
        for the expert layers; in a mixed program also which rows are the
        decode step's, whose routing alone is counted."""
        i32 = dtypes.int32
        if step.program in ("prefill", "chunk"):
            t = ltorch.reshape(prims.iota(step.T, dtype=i32, device=step.last.device), (1, step.T))
            pos = t if step.program == "prefill" else t + step.start_pos
            live = ltorch.reshape(ltorch.le(t, step.last), (step.T,))
        elif step.program == "decode":
            pos = ltorch.reshape(step.pos, (-1, 1))
            live = ltorch.gt(step.pos, 0)
        elif step.program == "mixed":  # the chunk's T rows, then one a decode slot
            chunk, pos_d = step.chunk, step.decode.pos
            t = ltorch.reshape(prims.iota(chunk.T, dtype=i32, device=pos_d.device), (1, chunk.T))
            pos = ltorch.cat([chunk.q_pos, ltorch.reshape(pos_d, (1, -1))], 1)
            live = ltorch.cat([ltorch.reshape(ltorch.le(t, chunk.last), (chunk.T,)),
                               ltorch.gt(pos_d, 0)], 0)
            step.shared["counted_rows"] = ltorch.ge(
                prims.iota(pos.shape[1], dtype=i32, device=pos_d.device), chunk.T)
        else:
            pos = step.pos_mat
            live = ltorch.reshape(ltorch.expand(ltorch.gt(pos[:, :1], 0), tuple(pos.shape)), (-1,))
        step.shared["rope"] = self.model.where(pos)
        step.shared["live"] = live

    def embed(self, toks):
        return self.model.wte(toks)

    def head(self, x):
        return self.model.lm_head(self.model.ln_f(x))
