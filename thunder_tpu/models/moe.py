"""Mixture-of-Experts transformer (Mixtral-class) with grouped matmuls and
all-to-all expert parallelism.

Capability counterpart of the reference's MoE support: the `_GROUPED_MM` prim
(reference thunder/core/prims.py:272) + DTensor-based expert parallelism in
thunder/tests/distributed/test_moe.py:29-144 and
thunder/benchmarks/benchmark_inference.py:30-52. TPU-native, routing keeps
static shapes (capacity-based dispatch — XLA needs static shapes to tile the
MXU) and expert dispatch across the `ep` mesh axis rides `all_to_all`."""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..ops import clang, ltorch
from .litgpt import Config as GPTConfig, CausalSelfAttention, _norm


@dataclass
class MoEConfig:
    n_embd: int = 128
    intermediate_size: int = 256
    n_expert: int = 8
    n_expert_per_token: int = 2
    # None = drop-free: the grouped road sorts the rows by expert into ragged
    # groups (``ragged_experts``: no bins, no capacity) and the dense road's
    # capacity is N, which no expert can exceed; a float opts into Switch-style
    # drops with cap = ceil(cf * N * K / E) rounded up to the sublane tile
    capacity_factor: float | None = None
    # "grouped" packs tokens into per-expert capacity bins and runs
    # ltorch.grouped_mlp (the pallas grouped kernel claims it on TPU);
    # "dense" is the one-hot einsum reference road — every expert multiplies
    # every token, routing handled by combine weights. Both roads share the
    # router and the capacity/drop decision and are token-exact equals.
    dispatch: str = "grouped"


class MoEMLP(nn.Module):
    """Top-k routed SwiGLU experts with capacity-based static-shape dispatch.

    Tokens are routed to top-k experts; slots are granted FIFO by token index
    (Switch convention) and tokens over an expert's capacity are dropped —
    their combine weight is zeroed on the dense road and they never enter a
    bin on the grouped road, so both roads produce bit-identical outputs.
    """

    def __init__(self, cfg: MoEConfig, dtype=jnp.float32):
        super().__init__()
        self.cfg = cfg
        d, h, e = cfg.n_embd, cfg.intermediate_size, cfg.n_expert
        self.gate = nn.Linear(d, e, bias=False, dtype=dtype)
        k = jax.random.PRNGKey(21)
        s = 1.0 / math.sqrt(d)
        self.w_gate = nn.Parameter(jax.random.uniform(k, (e, d, h), dtype, -s, s))
        self.w_up = nn.Parameter(jax.random.uniform(jax.random.fold_in(k, 1), (e, d, h), dtype, -s, s))
        self.w_down = nn.Parameter(jax.random.uniform(jax.random.fold_in(k, 2), (e, h, d), dtype, -s / 2, s / 2))
        # routing health stats, refreshed per step only while observability
        # is enabled (events.enabled() is a trace-time gate: disabled runs
        # trace zero extra ops) — read back via moe.* telemetry publishers
        self.register_buffer("moe_expert_load", jnp.zeros((e,), dtype))
        self.register_buffer("moe_dropped_tokens", jnp.zeros((), dtype))
        self.register_buffer("moe_router_entropy", jnp.zeros((), dtype))

    def capacity(self, n_tokens: int) -> int:
        cfg = self.cfg
        if cfg.capacity_factor is None:
            # an expert appears at most once per token. The dense road's bound only: the
            # grouped road's drop-free dispatch is ragged and has no bins (forward)
            return n_tokens
        cap = math.ceil(cfg.capacity_factor * n_tokens * cfg.n_expert_per_token / cfg.n_expert)
        return min(n_tokens, (cap + 7) // 8 * 8)  # sublane-tile rounding

    def forward(self, x):
        from ..observability import events

        cfg = self.cfg
        B, T, D = x.shape
        N = B * T
        E, K = cfg.n_expert, cfg.n_expert_per_token
        xf = ltorch.reshape(x, (N, D))

        router_logits = self.gate(xf)  # (N, E)
        probs = ltorch.softmax(router_logits, -1)
        topk_probs, topk_idx = ltorch.topk(probs, K, -1)  # (N, K)
        # normalize selected probabilities (Mixtral convention)
        topk_probs = topk_probs / ltorch.sum(topk_probs, -1, keepdim=True)

        if cfg.capacity_factor is None and cfg.dispatch == "grouped":
            # drop-free: rows sorted by expert, ragged groups, every expert held
            out, counts, _ = ragged_experts(xf, topk_idx, topk_probs,
                                            (self.w_gate, self.w_up, self.w_down), (0, E))
            if events.enabled():
                lsm = ltorch.log_softmax(router_logits, -1)
                entropy = -ltorch.sum(ltorch.sum(probs * lsm, -1), 0) / N
                self.update_buffer("moe_expert_load", counts.to(probs.dtype) / (N * K))
                self.update_buffer("moe_dropped_tokens", ltorch.zeros_like(entropy))
                self.update_buffer("moe_router_entropy", entropy)
            return ltorch.reshape(ltorch.to(out, dtype=x.dtype), (B, T, D))

        # capacity/drop decision shared by BOTH roads: slot rank within each
        # expert is FIFO by flattened (token, k) index via cumsum of one-hot
        cap = self.capacity(N)
        flat_e = ltorch.reshape(topk_idx, (N * K,))
        oh = ltorch.one_hot(flat_e, E)  # (N*K, E) int
        ranks = ltorch.cumsum(oh, 0)
        rank = ltorch.squeeze(ltorch.take_along_dim(ranks, ltorch.unsqueeze(flat_e, 1), 1), 1) - 1
        keep = rank < cap  # (N*K,) bool
        counts = ltorch.sum(oh, 0)  # (E,) assignments per expert
        w = ltorch.reshape(topk_probs, (N * K,)) * keep.to(probs.dtype)

        if events.enabled():
            lsm = ltorch.log_softmax(router_logits, -1)
            entropy = -ltorch.sum(ltorch.sum(probs * lsm, -1), 0) / N
            self.update_buffer("moe_expert_load", counts.to(probs.dtype) / (N * K))
            self.update_buffer("moe_dropped_tokens",
                               (N * K) - ltorch.sum(keep.to(probs.dtype), 0))
            self.update_buffer("moe_router_entropy", entropy)

        if cfg.dispatch == "dense":
            # one-hot einsum reference: every expert multiplies every token,
            # dropped (token, k) pairs contribute an exact 0 via their weight
            comb = oh.to(probs.dtype) * ltorch.unsqueeze(w, 1)  # (N*K, E)
            combine = ltorch.sum(ltorch.reshape(comb, (N, K, E)), 1)  # (N, E)
            xe = ltorch.expand(ltorch.unsqueeze(xf, 0), (E, N, D))
            g = ltorch.matmul(xe, self.w_gate)
            u = ltorch.matmul(xe, self.w_up)
            h = ltorch.silu(g) * u
            out_e = ltorch.matmul(h, self.w_down)  # (E, N, D)
            combine_t = ltorch.permute(combine, (1, 0))  # (E, N)
            out = ltorch.sum(out_e * ltorch.unsqueeze(combine_t, -1), 0)  # (N, D)
            return ltorch.reshape(out, (B, T, D))

        # grouped road: scatter kept tokens into per-expert capacity bins
        # (dropped tokens land on a trash row sliced off before the matmuls),
        # run the grouped MLP over (E, cap, D), gather back by slot
        trash = E * cap
        slot = ltorch.where(keep, flat_e * cap + rank, trash)  # (N*K,)
        xk = ltorch.reshape(ltorch.expand(ltorch.unsqueeze(xf, 1), (N, K, D)), (N * K, D))
        idx = ltorch.expand(ltorch.unsqueeze(slot, 1), (N * K, D))
        zero_bins = ltorch.full((trash + 1, D), 0.0, dtype=x.dtype, device=x.device)
        bins_flat = ltorch.scatter_add(zero_bins, 0, idx, xk)
        bins = ltorch.reshape(bins_flat[:trash], (E, cap, D))
        group_sizes = ltorch.clamp(counts, max=cap)
        y = ltorch.grouped_mlp(bins, self.w_gate, self.w_up, self.w_down, group_sizes)
        zero_row = ltorch.full((1, D), 0.0, dtype=x.dtype, device=x.device)
        y_flat = ltorch.cat([ltorch.reshape(y, (trash, D)), zero_row], 0)
        picked = ltorch.take_along_dim(y_flat, idx, 0)  # (N*K, D)
        out = ltorch.sum(ltorch.reshape(picked * ltorch.unsqueeze(w, 1), (N, K, D)), 1)
        return ltorch.reshape(out, (B, T, D))


def ragged_tile(n_rows: int, n_routed: int) -> int:
    """Rows a tile of the ragged dispatch holds (one expert's, ``ltorch.ragged_mlp``):
    about twice what an expert sees when ``n_rows`` (tokens x experts a token)
    spread evenly over ``n_routed`` experts, so that a group mostly fills one
    tile and its panels are read once; between the bf16 sublane tile (16) and
    the MXU's 128 rows."""
    want = -(-2 * n_rows // n_routed)
    return min(128, max(16, -(-want // 16) * 16))


def ragged_experts(xf, idx, w, panels, held: tuple, *, live=None, n_routed: int | None = None):
    """The routed part of an expert layer for the experts HELD here, dropping
    nothing: the rows of the held experts sorted by expert into ragged groups,
    ``ltorch.ragged_mlp`` over them, and each token's chosen rows summed with
    their weights. What experts outside ``held`` would add is left out.

    xf (N, D) tokens; idx (N, K) int the experts each chose, numbered over the
    whole layer; w (N, K) float32 their weights; panels (w_gate (E, D, H), w_up,
    w_down (E, H, D)) of the ``E = hi - lo`` experts ``held = (lo, hi)``; live
    (N,) bool or None: tokens that are padding (an idle slot, a bucket's tail)
    enter no group and cost no panel. Shapes are static: the rows buffer has
    room for every choice of every token plus a tile of slack a held expert.
    Returns (out (N, D) float32, group_sizes (E,) int32, held_rows (N * K,) bool)."""
    from ..core import dtypes, prims

    N, D = xf.shape
    K = idx.shape[1]
    lo, hi = held
    E = hi - lo
    tile = ragged_tile(N * K, n_routed or E)
    R = -(-N * K // tile) * tile + E * tile
    i32 = dtypes.int32
    flat = ltorch.to(ltorch.reshape(idx, (N * K,)), dtype=i32)
    here = ltorch.logical_and(ltorch.ge(flat, lo), ltorch.lt(flat, hi))
    if live is not None:
        here = ltorch.logical_and(here, ltorch.reshape(
            ltorch.expand(ltorch.unsqueeze(live, 1), (N, K)), (N * K,)))
    oh = ltorch.to(ltorch.one_hot(ltorch.where(here, flat - lo, E), E + 1)[:, :E], dtype=i32)
    counts = ltorch.sum(oh, 0)                                            # (E,)
    rank = ltorch.sum(ltorch.cumsum(oh, 0) * oh, 1) - 1                   # place in its group
    padded = ltorch.floor_divide(counts + (tile - 1), tile) * tile
    starts = ltorch.cumsum(padded, 0) - padded                            # tile-aligned
    dest = ltorch.where(here, ltorch.sum(oh * ltorch.unsqueeze(starts, 0), 1) + rank, R)
    # the one scatter is of token numbers; activations move by gathers only
    token = ltorch.floor_divide(prims.iota(N * K, dtype=i32, device=xf.device), K)
    src = ltorch.index_put(ltorch.full((R + 1,), N, dtype=i32, device=xf.device), (dest,), token)
    zero = ltorch.zeros(1, D, device=xf.device, dtype=xf.dtype)
    rows = clang.take(ltorch.cat([xf, zero], 0), src[:R], 0)              # (R, D), padding zero
    y = ltorch.ragged_mlp(rows, *panels, counts, tile)
    picked = clang.take(ltorch.cat([y, zero], 0), dest, 0)                # (N * K, D)
    f32 = dtypes.float32
    out = ltorch.sum(ltorch.reshape(
        ltorch.to(picked, dtype=f32) * ltorch.reshape(ltorch.to(w, dtype=f32), (N * K, 1)),
        (N, K, D)), 1)
    return out, counts, here


class HeldExperts(nn.Module):
    """An expert layer that is TOLD WHICH EXPERTS IT HOLDS, as one chip of an
    expert-parallel group holds them (DeepSeek-V3's layer, arXiv:2412.19437):
    the router scores all ``n_routed`` experts in float32 (``score``: a sigmoid
    an expert, or one softmax over them all; a per-expert bias is added for the
    choice only), the ``n_expert_per_token``
    largest are chosen, their scores
    normalised (``norm_topk_prob``) and scaled; the layer computes the rows of
    its own experts ``held = (lo, hi)`` through ``ragged_experts``, drops no
    token, leaves out what the absent experts would add, and adds the shared
    expert, which every chip of the group computes alike. On one chip it runs
    without the exchange; nothing stands in for the other chips. ``n_shared``
    shared experts of ``width`` each are one fused SwiGLU ``n_shared * width``
    wide, whose output is their SUM; ``shared="average"`` divides it by
    ``n_shared`` (Command A+'s ``shared_expert_combination_strategy``).

    ``n_zero`` further router outputs, numbered after the routed experts, are
    ZERO-COMPUTE (identity) experts (LongCat-Flash,
    arXiv:2509.01322): one of them gives the token back as it is, so what they add is
    ``(sum of the chosen ones' weights) * x``. A token is at home where its
    attention runs, so this chip computes that part for every token: no row of
    it enters a ragged group and no panel is read for it."""

    def __init__(self, n_embd: int, width: int, n_routed: int, held: tuple, n_expert_per_token: int,
                 *, n_shared: int = 1, shared: str = "sum", norm_topk_prob: bool = True,
                 routed_scaling_factor: float = 1.0, score: str = "sigmoid", n_zero: int = 0,
                 dtype=jnp.float32):
        super().__init__()
        lo, hi = held
        if not 0 <= lo < hi <= n_routed:
            raise ValueError(f"experts held [{lo}, {hi}) are no range of the {n_routed} routed")
        if score not in ("sigmoid", "softmax"):
            raise ValueError(f"a router scores by 'sigmoid' or 'softmax', not {score!r}")
        if shared not in ("sum", "average"):
            raise ValueError(f"shared experts combine by 'sum' or 'average', not {shared!r}")
        self.n_routed, self.held, self.k = n_routed, (lo, hi), n_expert_per_token
        self.norm_topk_prob, self.scaling = norm_topk_prob, routed_scaling_factor
        self.score, self.n_zero = score, n_zero
        e = hi - lo
        self.gate = nn.Linear(n_embd, n_routed + n_zero, bias=False, dtype=dtype)
        self.e_score_correction_bias = nn.Parameter(jnp.zeros((n_routed + n_zero,), jnp.float32))
        self.w_gate = nn.Parameter(jnp.zeros((e, n_embd, width), dtype))
        self.w_up = nn.Parameter(jnp.zeros((e, n_embd, width), dtype))
        self.w_down = nn.Parameter(jnp.zeros((e, width, n_embd), dtype))
        self.shared_width = n_shared * width
        # what the shared output is multiplied by; at 1 nothing is traced
        self.shared_scale = 1.0 / n_shared if shared == "average" and n_shared > 1 else 1.0
        if n_shared:
            self.shared_gate = nn.Linear(n_embd, self.shared_width, bias=False, dtype=dtype)
            self.shared_up = nn.Linear(n_embd, self.shared_width, bias=False, dtype=dtype)
            self.shared_down = nn.Linear(self.shared_width, n_embd, bias=False, dtype=dtype)

    def route(self, xf):
        """(idx (N, K), w (N, K) float32) of the tokens xf (N, D)."""
        from ..core import dtypes

        f32 = dtypes.float32
        logits = ltorch.linear(ltorch.to(xf, dtype=f32), ltorch.to(self.gate.weight, dtype=f32))
        s = ltorch.sigmoid(logits) if self.score == "sigmoid" else ltorch.softmax(logits, -1)
        _, idx = ltorch.topk(s + self.e_score_correction_bias, self.k, -1)
        w = ltorch.take_along_dim(s, idx, 1)
        if self.norm_topk_prob:
            w = w / (ltorch.sum(w, -1, keepdim=True) + 1e-20)
        return idx, w * self.scaling

    def forward(self, x, live=None, counted=None, counted_rows=None):
        """x (B, T, D); live (B * T,) bool or None marks the tokens that are no
        padding. With ``counted`` (a list) the layer appends its
        ``serving.runner.ROUTING_COUNTERS`` of this call as one int32 vector
        (the first four; with identity experts the fifth too), of
        the tokens ``counted_rows`` (B * T,) bool marks where it is given (the
        decode rows of a program that runs a prompt chunk beside them)."""
        from ..core import dtypes
        from ..core.trace import named_scope

        B, T, D = x.shape
        N = B * T
        xf = ltorch.reshape(x, (N, D))
        with named_scope("moe_router"):
            idx, w = self.route(xf)
        # which of a token's choices are identity experts: they enter no group below (idx >= hi)
        zero = ltorch.ge(idx, self.n_routed) if self.n_zero else None
        with named_scope("moe_experts"):
            out, counts, here = ragged_experts(xf, idx, w, (self.w_gate, self.w_up, self.w_down),
                                               self.held, live=live, n_routed=self.n_routed + self.n_zero)
            if counted is not None:
                i32 = dtypes.int32
                if counted_rows is not None:
                    lo, hi = self.held
                    live = counted_rows if live is None else ltorch.logical_and(live, counted_rows)
                    here = ltorch.logical_and(here, ltorch.reshape(ltorch.expand(
                        ltorch.unsqueeze(counted_rows, 1), (N, self.k)), (N * self.k,)))
                    flat = ltorch.to(ltorch.reshape(idx, (N * self.k,)), dtype=i32)
                    counts = ltorch.sum(ltorch.to(ltorch.one_hot(
                        ltorch.where(here, flat - lo, hi - lo), hi - lo + 1)[:, :hi - lo], dtype=i32), 0)
                routed = (ltorch.full((), N * self.k, dtype=i32, device=xf.device) if live is None
                          else ltorch.sum(ltorch.to(live, dtype=i32)) * self.k)
                tally = [ltorch.to(routed, dtype=i32),
                         ltorch.sum(ltorch.to(here, dtype=i32)),
                         ltorch.sum(ltorch.to(ltorch.gt(counts, 0), dtype=i32)),
                         ltorch.amax(counts)]
                if self.n_zero:  # rows of the counted tokens that chose an identity expert
                    chose = zero if live is None else ltorch.logical_and(zero, ltorch.unsqueeze(live, 1))
                    tally.append(ltorch.sum(ltorch.to(chose, dtype=i32)))
                counted.append(ltorch.stack(tally, 0))
            if not self.n_zero:
                out = ltorch.to(out, dtype=x.dtype)
        if self.n_zero:
            with named_scope("zero_experts"):  # added in float32, rounded once with the held part
                g0 = ltorch.sum(ltorch.where(zero, w, 0.0), -1, keepdim=True)
                out = ltorch.to(out + ltorch.to(xf, dtype=dtypes.float32) * g0, dtype=x.dtype)
        if self.shared_width:
            with named_scope("shared_expert"):
                shared = self.shared_down(ltorch.silu(self.shared_gate(xf)) * self.shared_up(xf))
                out = out + (shared if self.shared_scale == 1.0 else shared * self.shared_scale)
        return ltorch.reshape(out, (B, T, D))


def publish_moe_stats(model: nn.Module, **attrs) -> int:
    """Publish every MoEMLP's routing-health buffers (refreshed by the last
    traced step while observability was enabled) to the ``moe.*`` telemetry
    registry via ``metrics.record_moe``. Returns the number of MoE layers
    published. Call once per logged step (bench / quickstart loop)."""
    from ..observability import events, metrics

    if not events.enabled():
        return 0
    n = 0
    for _, mod in model.named_modules():
        if isinstance(mod, MoEMLP):
            bufs = dict(mod.named_buffers())
            metrics.record_moe(
                [float(v) for v in bufs["moe_expert_load"]],
                float(bufs["moe_dropped_tokens"]),
                float(bufs["moe_router_entropy"]), **attrs)
            n += 1
    return n


class MoEBlock(nn.Module):
    def __init__(self, gpt_cfg: GPTConfig, moe_cfg: MoEConfig, dtype=jnp.float32):
        super().__init__()
        self.norm_1 = _norm(gpt_cfg, dtype)
        self.attn = CausalSelfAttention(gpt_cfg, dtype)
        self.norm_2 = _norm(gpt_cfg, dtype)
        self.moe = MoEMLP(moe_cfg, dtype)

    def forward(self, x, cos, sin):
        return self.tail(x, self.attn(self.norm_1(x), cos, sin))

    def tail(self, x, h):
        """As ``litgpt.Block.tail``; an expert block is always sequential."""
        x = x + h
        return x + self.moe(self.norm_2(x))


class MoEGPT(nn.Module):
    """Mixtral-style decoder: GQA attention + MoE MLPs."""

    def __init__(self, gpt_cfg: GPTConfig, moe_cfg: MoEConfig, dtype=jnp.float32):
        super().__init__()
        from .litgpt import build_rope_cache

        self.cfg = gpt_cfg
        self.wte = nn.Embedding(gpt_cfg.padded_vocab_size, gpt_cfg.n_embd, dtype=dtype)
        self.h = nn.ModuleList([MoEBlock(gpt_cfg, moe_cfg, dtype) for _ in range(gpt_cfg.n_layer)])
        self.ln_f = _norm(gpt_cfg, dtype)
        self.lm_head = nn.Linear(gpt_cfg.n_embd, gpt_cfg.padded_vocab_size, bias=False, dtype=dtype)
        cos, sin = build_rope_cache(gpt_cfg.block_size, gpt_cfg.rope_n_elem, gpt_cfg.rope_base, dtype)
        self.register_buffer("cos", cos)
        self.register_buffer("sin", sin)

    def forward(self, idx, targets=None):
        B, T = idx.shape
        cos, sin = self.cos[:T], self.sin[:T]
        x = self.wte(idx)
        for blk in self.h:
            x = blk(x, cos, sin)
        x = self.ln_f(x)
        logits = self.lm_head(x)
        if targets is not None:
            V = logits.shape[-1]
            return ltorch.cross_entropy(
                ltorch.reshape(logits, (B * T, V)), ltorch.reshape(targets, (B * T,))
            )
        return logits


def tiny_moe() -> MoEGPT:
    gpt_cfg = GPTConfig.from_name("tiny-llama2")
    moe_cfg = MoEConfig(n_embd=gpt_cfg.n_embd, intermediate_size=160, n_expert=4, n_expert_per_token=2)
    return MoEGPT(gpt_cfg, moe_cfg)
