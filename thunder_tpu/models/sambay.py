"""SambaY-style hybrid decoder (arXiv:2507.06607; Phi-4-mini-flash-reasoning) in
thunder_tpu's op language: five kinds of layer in one stack and no positional
encoding anywhere.

With ``half = n_layer // 2``, layer ``i`` mixes with

* ``mamba``        (``i <= half``, a multiple of ``mamba_every``): the Mamba-1
                   selective state-space mixer; layer ``half``'s scan output
                   (before its gate) is the model's MEMORY;
* ``window_attn``  (the other ``i < half``): differential attention over the
                   last ``sliding_window`` positions;
* ``full_attn``    (``i = half + 1``): differential attention over every
                   position — its keys and values are the model's ONE cache;
* ``gmu``          (``i > half + 1``, a multiple of ``mamba_every``): a gated
                   memory unit, ``(memory * silu(x W_1)) W_2`` at the same token;
* ``cross_attn``   (the other ``i > half + 1``): differential attention that
                   has queries only and reads layer ``half + 1``'s keys and
                   values.

Every block is ``x += mixer(LN(x)); x += mlp(LN'(x))`` with a fused gated-SiLU
MLP; a last LayerNorm and the tied head close the stack.

Differential attention pairs adjacent heads: a pair's two query heads each
take a softmax with the KV pair's key head of the same place, both over the
pair's value heads side by side (V twice as wide as QK); the pair's output is
``(1 - l0) * RMSNorm(A_1 - l * A_2)``. Here a KV pair is ONE cached head: its two
key heads side by side (2 x head_size wide, as its values are), and a query
head padded with zeros over the lanes of the key head it does not read, so
that ONE plain paged-attention call a layer computes both softmaxes and the
pools have 128-wide rows (ops/ltorch.py paged_attention).

Each mixer is also a SERVED layer (serving/runner.py): it declares what it
caches (``cache``) and gives its work for a whole prompt (``prefill``), a chunk
that starts from stored state (``chunk``) and one decode token (``decode``).
``Block`` wraps those with the norms, the MLP and the named scopes a device
profile is split by. The scan state and the conv tail are float32.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax.numpy as jnp

from .. import nn
from ..core import dtypes, prims
from ..core.trace import named_scope
from ..nn.module import Parameter
from ..ops import ltorch

# the type recurrent state is kept in between programs (the published one)
STATE_DTYPE = jnp.float32
SUBLN_EPS = 1e-5


@dataclass
class Config:
    name: str = "tiny-sambay"
    block_size: int = 4096          # the most positions a sequence may have
    vocab_size: int = 512
    n_layer: int = 8
    n_head: int = 8
    n_query_groups: int = 4
    n_embd: int = 64
    intermediate_size: int = 128
    sliding_window: int = 16
    mamba_every: int = 2
    norm_eps: float = 1e-5
    d_inner: int = 128
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 4

    @property
    def head_size(self) -> int:
        return self.n_embd // self.n_head

    @property
    def half(self) -> int:
        return self.n_layer // 2

    def layer_kind(self, i: int) -> str:
        if i <= self.half:
            return "mamba" if i % self.mamba_every == 0 else "window_attn"
        if i == self.half + 1:
            return "full_attn"
        return "gmu" if i % self.mamba_every == 0 else "cross_attn"


class MLP(nn.Module):
    def __init__(self, cfg: Config, dtype):
        super().__init__()
        self.width = cfg.intermediate_size
        self.fc_1 = nn.Linear(cfg.n_embd, 2 * cfg.intermediate_size, bias=False, dtype=dtype)
        self.fc_2 = nn.Linear(cfg.intermediate_size, cfg.n_embd, bias=False, dtype=dtype)

    def forward(self, x):
        gu = self.fc_1(x)
        return self.fc_2(gu[..., self.width:] * ltorch.silu(gu[..., :self.width]))


class _Conv(nn.Module):
    """The parameters of a depthwise causal convolution (ltorch.causal_conv1d)."""

    def __init__(self, channels: int, width: int, dtype):
        super().__init__()
        self.weight = Parameter(jnp.zeros((channels, width), dtype))
        self.bias = Parameter(jnp.zeros((channels,), dtype))


def _rows_at(state, slot, fresh):
    """Row ``slot`` of a per-slot array as a batch of one; zeros where the
    sequence starts here (``fresh``: True, or a traced bool)."""
    row = prims.dynamic_slice(state, (slot,) + (0,) * (state.ndim - 1), (1,) + tuple(state.shape[1:]))
    if fresh is True:
        return ltorch.zeros_like(row)
    return ltorch.where(fresh, ltorch.zeros_like(row), row)


def _put_row(state, slot, row):
    return prims.dynamic_update_slice(state, ltorch.to(row, dtype=state.dtype),
                                      (slot,) + (0,) * (state.ndim - 1))


class MambaMixer(nn.Module):
    """Mamba-1 (arXiv:2312.00752). Caches per slot the scan state (d_inner,
    d_state) and the conv's last d_conv - 1 inputs, both float32."""

    def __init__(self, cfg: Config, layer: int, dtype):
        super().__init__()
        from ..serving.kv_pages import Recurrent

        self.cfg = cfg
        self.is_memory = layer == cfg.half
        di, n, r = cfg.d_inner, cfg.d_state, cfg.dt_rank
        self.in_proj = nn.Linear(cfg.n_embd, 2 * di, bias=False, dtype=dtype)
        self.conv = _Conv(di, cfg.d_conv, dtype)
        self.x_proj = nn.Linear(di, r + 2 * n, bias=False, dtype=dtype)
        self.dt_proj = nn.Linear(r, di, bias=True, dtype=dtype)
        self.A_log = Parameter(jnp.zeros((di, n), dtype))
        self.D = Parameter(jnp.ones((di,), dtype))
        self.out_proj = nn.Linear(di, cfg.n_embd, bias=False, dtype=dtype)
        self.cache = Recurrent(((di, n), (cfg.d_conv - 1, di)), STATE_DTYPE)

    def mix(self, u, h0, tail, last=None):
        """u (B, T, d) from the state (h0 (B, d_inner, d_state), tail (B,
        d_conv - 1, d_inner)). With ``last`` (an index into T) the tokens
        after it are padding: they leave the state as token ``last`` left it.
        Returns (output (B, T, d), y (B, T, d_inner), h_T, new tail)."""
        cfg = self.cfg
        B, T, _ = u.shape
        di, n, r, K = cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.d_conv
        xz = self.in_proj(u)
        x, z = xz[..., :di], xz[..., di:]
        x, xp = ltorch.causal_conv1d(x, self.conv.weight, self.conv.bias, tail)
        x = ltorch.silu(x)
        dbc = self.x_proj(x)
        dt = ltorch.softplus(self.dt_proj(dbc[..., :r]))
        if last is None:
            new_tail = xp[:, T:]
        else:
            # padding neither moves the state (dt = 0: decay 1, drive 0) nor the tail
            t = ltorch.reshape(prims.iota(T, dtype=dtypes.int32, device=u.device), (1, T, 1))
            dt = ltorch.where(ltorch.le(t, last), dt, ltorch.zeros_like(dt))
            new_tail = prims.dynamic_slice(xp, (0, last + 1, 0), (B, K - 1, di))
        A = -ltorch.exp(ltorch.to(self.A_log, dtype=dtypes.float32))
        y, hT = ltorch.selective_scan(x, dt, A, dbc[..., r:r + n], dbc[..., r + n:], h0)
        y = y + self.D * x
        return self.out_proj(y * ltorch.silu(z)), y, hT, new_tail

    def _leave_memory(self, step, y):
        if self.is_memory:
            step.shared["memory"] = y

    def _one_sequence(self, step, x, state, fresh):
        h, tail = state
        out, y, hT, new_tail = self.mix(x, _rows_at(h, step.slot, fresh),
                                        _rows_at(tail, step.slot, fresh), step.last)
        self._leave_memory(step, y)
        return out, (_put_row(h, step.slot, hT), _put_row(tail, step.slot, new_tail))

    def prefill(self, step, x, state):
        return self._one_sequence(step, x, state, True)

    def chunk(self, step, x, state):
        return self._one_sequence(step, x, state, ltorch.eq(step.start_pos, 0))

    def decode(self, step, x, state):
        h, tail = state
        out, y, hT, new_tail = self.mix(x, h, tail)
        self._leave_memory(step, y)
        # an idle slot (or one whose prompt is still being chunked) keeps its rows
        keep = ltorch.reshape(step.live, (-1, 1, 1))
        return out, (ltorch.where(keep, ltorch.to(hT, dtype=h.dtype), h),
                     ltorch.where(keep, ltorch.to(new_tail, dtype=tail.dtype), tail))


class GMU(nn.Module):
    """Gated memory unit: the memory gated by this layer's input. Caches nothing."""

    cache = None

    def __init__(self, cfg: Config, dtype):
        super().__init__()
        self.in_proj = nn.Linear(cfg.n_embd, cfg.d_inner, bias=False, dtype=dtype)
        self.out_proj = nn.Linear(cfg.d_inner, cfg.n_embd, bias=False, dtype=dtype)

    def mix(self, x, memory):
        return self.out_proj(memory * ltorch.silu(self.in_proj(x)))

    def _served(self, step, x, state):
        return self.mix(x, step.shared["memory"]), state

    prefill = chunk = decode = _served


class DiffAttention(nn.Module):
    """Differential attention (arXiv:2410.05258) over adjacent pairs of heads,
    in a window, over everything, or (``kind == "cross_attn"``) with queries
    only over another layer's keys and values."""

    def __init__(self, cfg: Config, layer: int, kind: str, dtype):
        super().__init__()
        from ..serving.kv_pages import PagedKV, ReadsKV

        self.cfg = cfg
        self.kind = kind
        self.lambda_init = 0.8 - 0.6 * math.exp(-0.3 * layer)
        nh, ng, hs = cfg.n_head, cfg.n_query_groups, cfg.head_size
        if kind == "cross_attn":
            self.q = nn.Linear(cfg.n_embd, nh * hs, bias=True, dtype=dtype)
            self.cache = ReadsKV(cfg.half + 1)
        else:
            self.qkv = nn.Linear(cfg.n_embd, (nh + 2 * ng) * hs, bias=True, dtype=dtype)
            self.cache = PagedKV(ng // 2, 2 * hs, 2 * hs,
                                 cfg.sliding_window if kind == "window_attn" else None)
        self.window = cfg.sliding_window if kind == "window_attn" else None
        self.scale = 1.0 / math.sqrt(hs)  # of a key head, not of the padded pair
        self.proj = nn.Linear(nh * hs, cfg.n_embd, bias=True, dtype=dtype)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            setattr(self, name, Parameter(jnp.zeros((hs,), dtype)))
        self.subln = nn.RMSNorm(2 * hs, eps=SUBLN_EPS, dtype=dtype)

    # -- layouts ------------------------------------------------------------
    # kv pair m is one cached head: key heads 2m (read by every pair's first
    # query head) and 2m + 1 (by the second) side by side, and both value
    # heads side by side; it serves the `rep` query pairs m * rep .. m * rep +
    # rep - 1. Its query heads come in the order (first or second, pair).
    def _split(self, x):
        """(q (B, nh, T, 2 hs): each query head over the lanes of its key head,
        zeros over the other's; k and v (B, ng / 2, T, 2 hs), or None for a
        layer that has queries only) of the tokens x (B, T, d)."""
        cfg = self.cfg
        B, T, _ = x.shape
        nh, ng, hs = cfg.n_head, cfg.n_query_groups, cfg.head_size

        def paired(t):  # (B, T, ng * hs) -> (B, ng / 2, T, 2 hs)
            return ltorch.permute(ltorch.reshape(t, (B, T, ng // 2, 2 * hs)), (0, 2, 1, 3))

        if self.kind == "cross_attn":
            q, k, v = self.q(x), None, None
        else:
            qkv = self.qkv(x)
            q = qkv[..., :nh * hs]
            k, v = paired(qkv[..., nh * hs:(nh + ng) * hs]), paired(qkv[..., (nh + ng) * hs:])
        q = ltorch.permute(ltorch.reshape(q, (B, T, ng // 2, nh // ng, 2, hs)), (0, 2, 4, 3, 1, 5))
        first, second = q[:, :, 0], q[:, :, 1]                   # (B, kv pair, pair in it, T, hs)
        zeros = ltorch.zeros_like(first)
        q = ltorch.stack([ltorch.cat([first, zeros], -1), ltorch.cat([zeros, second], -1)], 2)
        return ltorch.reshape(q, (B, nh, T, 2 * hs)), k, v

    def _combine(self, a):
        """a (B, nh, T, 2 hs), heads ordered as ``_split`` orders the queries ->
        the layer's output (B, T, d)."""
        cfg = self.cfg
        B, nh, T, w = a.shape
        ng = cfg.n_query_groups
        f32 = dtypes.float32
        a = ltorch.reshape(ltorch.to(a, dtype=f32), (B, ng // 2, 2, nh // ng, T, w))
        lam = (ltorch.exp(ltorch.sum(ltorch.to(self.lambda_q1, dtype=f32) * ltorch.to(self.lambda_k1, dtype=f32)))
               - ltorch.exp(ltorch.sum(ltorch.to(self.lambda_q2, dtype=f32) * ltorch.to(self.lambda_k2, dtype=f32)))
               + self.lambda_init)
        y = a[:, :, 0] - lam * a[:, :, 1]                                   # (B, kv pair, rep, T, w)
        y = ltorch.rms_norm(y, (w,), ltorch.to(self.subln.weight, dtype=f32), SUBLN_EPS)
        y = ltorch.to(y * (1.0 - self.lambda_init), dtype=self.proj.weight.dtype)
        return self.proj(ltorch.reshape(ltorch.permute(y, (0, 3, 1, 2, 4)), (B, T, nh * cfg.head_size)))

    def dense(self, q, k, v):
        """Attention of q (B, nh, T, 2 hs) over the same T tokens' k and v,
        with this layer's mask written out: the whole-prompt path."""
        nh = q.shape[1]
        T = q.shape[2]
        k = ltorch.repeat_interleave(k, nh // k.shape[1], 1)
        v = ltorch.repeat_interleave(v, nh // v.shape[1], 1)
        scores = ltorch.matmul(q, ltorch.transpose(k, -2, -1)) * self.scale
        t = ltorch.reshape(prims.iota(T, dtype=dtypes.int32, device=q.device), (T, 1))
        s = ltorch.reshape(prims.iota(T, dtype=dtypes.int32, device=q.device), (1, T))
        mask = ltorch.le(s, t)
        if self.window is not None:
            mask = ltorch.logical_and(mask, ltorch.gt(s, t - self.window))
        probs = ltorch.softmax(ltorch.where(mask, scores, float("-inf")), -1)
        return ltorch.matmul(ltorch.to(probs, dtype=v.dtype), v)

    def mix(self, x, kv=None):
        """The layer over a whole sequence with no cache. Returns (output, (k,
        v)): a cross layer is given layer ``half + 1``'s and hands them on."""
        q, k, v = self._split(x)
        if self.kind == "cross_attn":
            k, v = kv
        return self._combine(self.dense(q, k, v)), (k, v)

    # -- served -------------------------------------------------------------
    def _pools(self, step, state):
        return step.states[self.cache.of] if self.kind == "cross_attn" else state

    def prefill(self, step, x, state):
        from ..serving.runner import _page_blocks

        q, k, v = self._split(x)
        if self.kind == "cross_attn":
            k, v = step.shared["kv"]
        else:
            page_ids = step.page_ids[self.cache.kind]
            state = (ltorch.index_put(state[0], (page_ids,), _page_blocks(k, step.page_size)),
                     ltorch.index_put(state[1], (page_ids,), _page_blocks(v, step.page_size)))
            if self.kind == "full_attn":
                step.shared["kv"] = (k, v)
        return self._combine(self.dense(q, k, v)), state

    def chunk(self, step, x, state):
        from ..serving.runner import _page_blocks

        q, k, v = self._split(x)
        if self.kind == "cross_attn":
            kind = "full"
        else:
            kind = self.cache.kind
            pages = step.chunk_pages[kind]
            state = (ltorch.index_put(state[0], (pages,), _page_blocks(k, step.page_size)),
                     ltorch.index_put(state[1], (pages,), _page_blocks(v, step.page_size)))
        kp, vp = self._pools(step, state)
        y = ltorch.paged_chunk_attention(q, kp, vp, step.tables[kind], step.q_pos,
                                         scale=self.scale, window=self.window)
        return self._combine(y), state

    def decode(self, step, x, state):
        from ..serving.runner import _write_tokens

        B = x.shape[0]
        q, k, v = self._split(x)
        if self.kind == "cross_attn":
            kind = "full"
        else:
            kind = self.cache.kind
            state = _write_tokens(state, step.page_of[kind], step.slot_in_page,
                                  ltorch.permute(k, (0, 2, 1, 3)), ltorch.permute(v, (0, 2, 1, 3)))
        kp, vp = self._pools(step, state)
        y = ltorch.paged_attention(q[:, :, 0], kp, vp, step.tables[kind], step.seq_lens,
                                   scale=self.scale, window=self.window)
        return self._combine(ltorch.reshape(y, (B, y.shape[1], 1, y.shape[2]))), state


class Block(nn.Module):
    def __init__(self, cfg: Config, layer: int, dtype):
        super().__init__()
        self.kind = kind = cfg.layer_kind(layer)
        self.norm_1 = nn.LayerNorm(cfg.n_embd, eps=cfg.norm_eps, dtype=dtype)
        if kind == "mamba":
            self.mixer = MambaMixer(cfg, layer, dtype)
        elif kind == "gmu":
            self.mixer = GMU(cfg, dtype)
        else:
            self.mixer = DiffAttention(cfg, layer, kind, dtype)
        self.norm_2 = nn.LayerNorm(cfg.n_embd, eps=cfg.norm_eps, dtype=dtype)
        self.mlp = MLP(cfg, dtype)

    @property
    def cache(self):
        return self.mixer.cache

    def _tail(self, x, h):
        x = x + h
        with named_scope("mlp"):
            return x + self.mlp(self.norm_2(x))

    def _served(program: str):
        def run(self, step, x, state):
            with named_scope(self.kind):
                h, state = getattr(self.mixer, program)(step, self.norm_1(x), state)
            return self._tail(x, h), state

        run.__name__ = program
        return run

    prefill, chunk, decode = _served("prefill"), _served("chunk"), _served("decode")
    del _served


class SambaY(nn.Module):
    def __init__(self, cfg: Config, dtype=jnp.float32):
        super().__init__()
        self.cfg = cfg
        self.wte = nn.Embedding(cfg.vocab_size, cfg.n_embd, dtype=dtype)
        self.h = nn.ModuleList([Block(cfg, i, dtype) for i in range(cfg.n_layer)])
        self.ln_f = nn.LayerNorm(cfg.n_embd, eps=cfg.norm_eps, dtype=dtype)

    def head(self, x):
        return ltorch.linear(self.ln_f(x), self.wte.weight)

    def forward(self, idx):
        """Logits (B, T, V) of whole sequences, with no cache."""
        cfg = self.cfg
        B, T = idx.shape
        x = self.wte(idx)
        memory = kv = None
        for block in self.h:
            u = block.norm_1(x)
            if block.kind == "mamba":
                h0 = ltorch.zeros(B, cfg.d_inner, cfg.d_state, device=x.device, dtype=dtypes.float32)
                tail = ltorch.zeros(B, cfg.d_conv - 1, cfg.d_inner, device=x.device, dtype=dtypes.float32)
                h, y, _, _ = block.mixer.mix(u, h0, tail)
                if block.mixer.is_memory:
                    memory = y
            elif block.kind == "gmu":
                h = block.mixer.mix(u, memory)
            else:
                h, got = block.mixer.mix(u, kv)
                if block.kind == "full_attn":
                    kv = got
            x = block._tail(x, h)
        return self.head(x)

    def serving(self):
        """This model as the paged engine serves it (serving/runner.py)."""
        return _Served(self)


class _Served:
    def __init__(self, model: SambaY):
        self.model = model
        self.layers = list(model.h)

    def begin(self, step) -> None:  # no positional encoding to prepare
        pass

    def embed(self, toks):
        return self.model.wte(toks)

    def head(self, x):
        return self.model.head(x)
