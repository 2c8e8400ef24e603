"""A PARALLEL block of mixed window and position-free full attention beside held experts
(Cohere's Command A+, ``model_type`` ``cohere2_moe``) in thunder_tpu's op language, as ONE
CHIP of an expert-parallel group holds it.

One layer, ``X`` the residual stream, ``LN`` a LayerNorm with a weight and no bias, no biases
anywhere:

    h = LN(X)                                  one norm feeds both branches
    Q = h Wq,  K = h Wk,  V = h Wv             n_head query heads, n_query_groups key/value heads
    window layers: rope on the interleaved pairs (x[2i], x[2i + 1]) of every head of Q and K;
                   key s visible from query t iff t - sliding_window < s <= t
    full layers:   NO positions, causal over everything
    A = softmax(Q K^T / sqrt(head_size) + M) V;  attn = A Wo
    routed + shared = HeldExperts(h)           a sigmoid router over all routed experts in
                                               float32, the chosen scores normalised, the rows of
                                               the experts held here; the shared experts AVERAGED
    X' = X + attn + routed + shared

After the last layer a LayerNorm and a head tied to the embedding. ``Config.layer_types`` says
which layers are which (``"window"`` or ``"full"``; the published pattern is three window
layers, then one full).

Interleaved rope by a fixed LAYOUT: each head's columns of Wq and Wk are stored with the
published even columns first, then the odd ones (``published_columns``). A pair ``(x[2i],
x[2i + 1])`` of the published layout is then ``(x[i], x[i + head_size / 2])`` of the stored one,
which the rotate-half rope of ``litgpt._apply_rope`` turns by the same angle: the scores are
the published ones exactly, and the cached keys are the published keys in the stored column
order. The full layers store the same layout and take no rope.

Every ``Block`` is a served layer (serving/runner.py): it declares its paged keys and values
(``kv_pages.PagedKV`` with the window for a window layer, without for a full one) and gives
``prefill``, ``chunk``, ``decode`` and ``mixed``, each under the named scopes a device profile is
split by: ``attn/window`` or ``attn/full``, ``moe_router``, ``moe_experts``, ``shared_expert``
(``moe.HeldExperts``'), and ``head``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core import dtypes, prims
from ..core.trace import named_scope
from ..observability import events as _obs
from ..ops import clang, ltorch
from .litgpt import _apply_rope, build_rope_cache
from .moe import HeldExperts


@dataclass
class Config:
    name: str = "tiny-parallel-moe"
    block_size: int = 256            # the most positions a sequence may have (rope rows)
    vocab_size: int = 512
    n_embd: int = 64
    n_head: int = 8
    n_query_groups: int = 2
    head_size: int = 32
    sliding_window: int = 16
    layer_types: tuple = field(default=("window", "window", "window", "full"))
    rope_base: float = 50000.0
    norm_eps: float = 1e-5
    expert_width: int = 64
    n_routed_experts: int = 8        # the router's width
    experts_held: tuple = (0, 8)     # [lo, hi) of them live here
    n_expert_per_token: int = 2
    n_shared_experts: int = 4
    norm_topk_prob: bool = True

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)


def published_columns(head_size: int) -> np.ndarray:
    """For each stored column of a head of Wq or Wk, the published column it holds: the even
    ones, then the odd ones."""
    return np.concatenate([np.arange(0, head_size, 2), np.arange(1, head_size, 2)])


def _masked_attention(q, k, v, scale: float, window):
    """Causal attention of q (B, H, T, D) over the same T tokens' k and v (B, Hkv, T, D), the
    mask written out (and the window where there is one): the whole-prompt path."""
    H, T = q.shape[1], q.shape[2]
    k = ltorch.repeat_interleave(k, H // k.shape[1], 1)
    v = ltorch.repeat_interleave(v, H // v.shape[1], 1)
    scores = ltorch.matmul(q, ltorch.transpose(k, -2, -1)) * scale
    t = ltorch.reshape(prims.iota(T, dtype=dtypes.int32, device=q.device), (T, 1))
    s = ltorch.reshape(prims.iota(T, dtype=dtypes.int32, device=q.device), (1, T))
    mask = ltorch.le(s, t)
    if window is not None:
        mask = ltorch.logical_and(mask, ltorch.gt(s, t - window))
    probs = ltorch.softmax(ltorch.where(mask, scores, float("-inf")), -1)
    return ltorch.matmul(ltorch.to(probs, dtype=v.dtype), v)


class Attention(nn.Module):
    """GQA attention of one layer kind: ``"window"`` (rope, a window) or ``"full"`` (no
    positions). Its paged programs follow ``sambay.DiffAttention``'s: the pools of its own
    kind (``step.page_of[kind]``, ``step.tables[kind]``), a window passed to the kernels."""

    def __init__(self, cfg: Config, kind: str, dtype):
        super().__init__()
        from ..serving.kv_pages import PagedKV

        if kind not in ("window", "full"):
            raise ValueError(f"a layer is 'window' or 'full', not {kind!r}")
        self.cfg, self.kind = cfg, kind
        nh, ng, hs = cfg.n_head, cfg.n_query_groups, cfg.head_size
        self.window = cfg.sliding_window if kind == "window" else None
        self.qkv = nn.Linear(cfg.n_embd, (nh + 2 * ng) * hs, bias=False, dtype=dtype)
        self.o = nn.Linear(nh * hs, cfg.n_embd, bias=False, dtype=dtype)
        self.cache = PagedKV(ng, hs, hs, self.window)
        self.scale = 1.0 / math.sqrt(hs)

    def split(self, h, rope):
        """q (B, nh, T, hs), k and v (B, ng, T, hs) of the normed tokens h (B, T, d); ``rope``
        the (cos, sin) rows of their positions, broadcasting against (B, heads, T, hs), which a
        full layer does not read."""
        cfg = self.cfg
        B, T, _ = h.shape
        nh, ng, hs = cfg.n_head, cfg.n_query_groups, cfg.head_size
        qkv = self.qkv(h)

        def heads(a, n):
            return ltorch.permute(ltorch.reshape(a, (B, T, n, hs)), (0, 2, 1, 3))

        q = heads(qkv[..., :nh * hs], nh)
        k = heads(qkv[..., nh * hs:(nh + ng) * hs], ng)
        v = heads(qkv[..., (nh + ng) * hs:], ng)
        if self.window is not None:
            q, k = _apply_rope(q, *rope, hs), _apply_rope(k, *rope, hs)
        return q, k, v

    def _out(self, y):
        """y (B, nh, T, hs) -> what the attention adds (B, T, d)."""
        B, nh, T, hs = y.shape
        return self.o(ltorch.reshape(ltorch.permute(y, (0, 2, 1, 3)), (B, T, nh * hs)))

    def dense(self, h, rope):
        """What the attention adds over whole sequences with no cache."""
        q, k, v = self.split(h, rope)
        return self._out(_masked_attention(q, k, v, self.scale, self.window))

    # -- served ------------------------------------------------------------------
    def prefill(self, step, h, state):
        from ..serving.runner import _write_pages

        q, k, v = self.split(h, step.shared["rope"])
        pages, ps = step.page_ids[self.cache.kind], step.page_size
        state = (_write_pages(state[0], pages, k, ps), _write_pages(state[1], pages, v, ps))
        return self._out(_masked_attention(q, k, v, self.scale, self.window)), state

    def _chunk_rows(self, step, q, k, v, state):
        """A chunk's rows, q (1, nh, T, hs): its pages written, then the whole table attended
        with per-query coverage (and the window)."""
        from ..serving.runner import _write_pages

        kind, ps = self.cache.kind, step.page_size
        state = (_write_pages(state[0], step.chunk_pages[kind], k, ps),
                 _write_pages(state[1], step.chunk_pages[kind], v, ps))
        y = ltorch.paged_chunk_attention(q, *state, step.tables[kind], step.q_pos, scale=self.scale,
                                         window=self.window)
        return y, state

    def _decode_rows(self, step, q, k, v, state):
        """One token a sequence, q (B, nh, 1, hs): its k/v to its page and slot, then attention
        over the sequence's pages of this kind."""
        from ..serving.runner import _write_tokens

        kind = self.cache.kind
        B = q.shape[0]
        state = _write_tokens(state, step.page_of[kind], step.slot_in_page,
                              ltorch.permute(k, (0, 2, 1, 3)), ltorch.permute(v, (0, 2, 1, 3)))
        y = ltorch.paged_attention(q[:, :, 0], *state, step.tables[kind], step.seq_lens,
                                   scale=self.scale, window=self.window)
        return ltorch.reshape(y, (B, y.shape[1], 1, y.shape[2])), state

    def chunk(self, step, h, state):
        y, state = self._chunk_rows(step, *self.split(h, step.shared["rope"]), state)
        return self._out(y), state

    def decode(self, step, h, state):
        y, state = self._decode_rows(step, *self.split(h, step.shared["rope"]), state)
        return self._out(y), state

    def mixed(self, step, h, state):
        """A chunk's T rows and after them one row a decode slot, h (1, T + B, d): one operand
        of the projections; the writes and the attention split, each kind of row through the
        body its own program runs (``step.chunk``, ``step.decode``)."""
        T = step.chunk.T
        q, k, v = self.split(h, step.shared["rope"])

        def seqs(a):  # the decode rows, a sequence each: (1, H, B, hs) <-> (B, H, 1, hs)
            return ltorch.permute(a, (2, 1, 0, 3))

        y_c, state = self._chunk_rows(step.chunk, q[:, :, :T], k[:, :, :T], v[:, :, :T], state)
        y_d, state = self._decode_rows(step.decode, seqs(q[:, :, T:]), seqs(k[:, :, T:]),
                                       seqs(v[:, :, T:]), state)
        return self._out(ltorch.cat([y_c, seqs(y_d)], 2)), state


class Block(nn.Module):
    def __init__(self, cfg: Config, layer: int, dtype):
        super().__init__()
        self.kind = cfg.layer_types[layer]
        self.norm = nn.LayerNorm(cfg.n_embd, eps=cfg.norm_eps, bias=False, dtype=dtype)
        self.attn = Attention(cfg, self.kind, dtype)
        self.experts = HeldExperts(cfg.n_embd, cfg.expert_width, cfg.n_routed_experts,
                                   tuple(cfg.experts_held), cfg.n_expert_per_token,
                                   n_shared=cfg.n_shared_experts, shared="average",
                                   norm_topk_prob=cfg.norm_topk_prob, score="sigmoid", dtype=dtype)

    @property
    def cache(self):
        return self.attn.cache

    def forward(self, x, rope):
        """The layer over whole sequences with no cache."""
        h = self.norm(x)
        with named_scope(f"attn/{self.kind}"):
            a = self.attn.dense(h, rope)
        return x + a + self.experts(h)

    def _served(program: str):
        def run(self, step, x, state):
            h = self.norm(x)
            with named_scope(f"attn/{self.kind}"):
                a, state = getattr(self.attn, program)(step, h, state)
            counted = None
            if program in ("decode", "mixed") and _obs.enabled():  # a trace-time gate, as in latent_moe
                counted = step.shared.setdefault("counted", [])
            # the experts route the attention's own normed rows; a mixed program counts its
            # decode rows only, as the decode program does
            return x + a + self.experts(h, step.shared["live"], counted,
                                        step.shared.get("counted_rows")), state

        run.__name__ = program
        return run

    prefill, chunk = _served("prefill"), _served("chunk")
    decode, mixed = _served("decode"), _served("mixed")
    del _served


class ParallelMoE(nn.Module):
    """Embedding, the blocks, a last LayerNorm and the head tied to the embedding."""

    def __init__(self, cfg: Config, dtype=jnp.float32):
        super().__init__()
        self.cfg = cfg
        self.wte = nn.Embedding(cfg.vocab_size, cfg.n_embd, dtype=dtype)
        self.h = nn.ModuleList([Block(cfg, i, dtype) for i in range(cfg.n_layer)])
        self.ln_f = nn.LayerNorm(cfg.n_embd, eps=cfg.norm_eps, bias=False, dtype=dtype)
        cos, sin = build_rope_cache(cfg.block_size, cfg.head_size, cfg.rope_base, dtype)
        self.register_buffer("cos", cos)
        self.register_buffer("sin", sin)

    def rope(self, pos):
        """(cos, sin) rows (B, 1, T, head_size) of the positions pos (B, T) int32."""
        B, T = pos.shape
        hs = self.cfg.head_size
        flat = ltorch.reshape(ltorch.clamp(pos, max=self.cfg.block_size - 1), (B * T,))
        return tuple(ltorch.reshape(clang.take(clang.ensure_proxy(t), flat, 0), (B, 1, T, hs))
                     for t in (self.cos, self.sin))

    def head(self, x):
        return ltorch.linear(self.ln_f(x), self.wte.weight)

    def forward(self, idx):
        """Logits (B, T, V) of whole sequences, with no cache."""
        B, T = idx.shape
        with named_scope("attn/window"):
            rope = self.rope(ltorch.expand(ltorch.reshape(
                prims.iota(T, dtype=dtypes.int32, device=idx.device), (1, T)), (B, T)))
        with named_scope("embed"):
            x = self.wte(idx)
        for block in self.h:
            x = block(x, rope)
        with named_scope("head"):
            return self.head(x)

    def serving(self):
        """This model as the paged engine serves it (serving/runner.py)."""
        return _Served(self)


class _Served:
    def __init__(self, model: ParallelMoE):
        self.model = model
        self.layers = list(model.h)
        self.max_positions = model.cfg.block_size

    def begin(self, step) -> None:
        """The program's positions' rope rows, which of its tokens are no padding (an idle
        decode slot, a bucket's tail) for the expert layers, and in a mixed program which rows
        are the decode step's, whose routing alone is counted."""
        i32 = dtypes.int32
        if step.program in ("prefill", "chunk"):
            t = ltorch.reshape(prims.iota(step.T, dtype=i32, device=step.last.device), (1, step.T))
            pos = t if step.program == "prefill" else t + step.start_pos
            live = ltorch.reshape(ltorch.le(t, step.last), (step.T,))
        elif step.program == "decode":
            pos = ltorch.reshape(step.pos, (-1, 1))
            live = ltorch.gt(step.pos, 0)
        else:  # mixed: the chunk's T rows, then one a decode slot
            chunk, pos_d = step.chunk, step.decode.pos
            t = ltorch.reshape(prims.iota(chunk.T, dtype=i32, device=pos_d.device), (1, chunk.T))
            pos = ltorch.cat([chunk.q_pos, ltorch.reshape(pos_d, (1, -1))], 1)
            live = ltorch.cat([ltorch.reshape(ltorch.le(t, chunk.last), (chunk.T,)),
                               ltorch.gt(pos_d, 0)], 0)
            step.shared["counted_rows"] = ltorch.ge(
                prims.iota(pos.shape[1], dtype=i32, device=pos_d.device), chunk.T)
        with named_scope("attn/window"):
            step.shared["rope"] = self.model.rope(pos)
        step.shared["live"] = live

    def embed(self, toks):
        return self.model.wte(toks)

    def head(self, x):
        return self.model.head(x)

