"""Expert parallelism: Mixtral-style MoE dispatch over a mesh axis.

Capability slot of reference thunder/tests/distributed/test_moe.py:29-144
(token-dispatch EP over NCCL all_to_all), designed TPU-first:

- tokens are sharded over the ``ep`` axis (data parallel along the same
  axis that owns the experts — the standard EP mesh layout);
- expert-stacked weights (E, ...) are sharded over ``ep`` on dim 0;
- dispatch packs each device's tokens into per-expert capacity bins and
  exchanges them with ONE ``lax.all_to_all`` over ICI (the NCCL a2a role);
- each device runs its local experts as ONE batched SwiGLU grouped-matmul
  over (E_local, n_dev * cap, D) — MXU-shaped, no scalar loops;
- a second all_to_all returns expert outputs; the weighted combine runs
  where the tokens live.

Everything is static-shaped (capacity bins), so the whole step jits under
``shard_map`` and differentiates (all_to_all/psum have exact transpose
rules) — the dryrun runs value_and_grad through it and checks the loss and
grads match the same algorithm on one device.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P



def _dispatch_bins(x, topk_idx, topk_probs, n_expert: int, cap: int):
    """Pack tokens into per-expert capacity bins.

    x: (N, D); topk_idx/topk_probs: (N, K).
    Returns bins (E, cap, D), and (expert, slot, prob) per (token, k) for the
    combine; slot == cap means dropped (guarded by a large-enough cap)."""
    N, D = x.shape
    K = topk_idx.shape[1]
    flat_e = topk_idx.reshape(-1)                      # (N*K,) expert ids
    # position of each (token, k) within its expert's bin: rank among all
    # earlier (token-major) assignments to the same expert
    onehot = jax.nn.one_hot(flat_e, n_expert, dtype=jnp.int32)   # (N*K, E)
    slot_flat = (jnp.cumsum(onehot, axis=0) - 1)                  # running count
    slot = jnp.take_along_axis(slot_flat, flat_e[:, None], 1)[:, 0]  # (N*K,)
    keep = slot < cap
    # scatter tokens into bins; over-capacity slots pass the UNCLAMPED index
    # so mode="drop" discards them instead of clobbering slot cap-1's token
    bins = jnp.zeros((n_expert, cap, D), x.dtype)
    tok = jnp.repeat(jnp.arange(N), K)
    bins = bins.at[flat_e, slot].set(x[tok], mode="drop")
    slot_c = jnp.where(keep, slot, cap - 1)  # clamped for the gather-combine
    return bins, (flat_e, slot_c, keep, tok)


def _swiglu_experts(bins, w_gate, w_up, w_down):
    """bins (E, C, D) through per-expert SwiGLU: one batched MXU matmul per
    projection (the grouped-MM role; E is the batch dim of the dot)."""
    g = jnp.einsum("ecd,edh->ech", bins, w_gate)
    u = jnp.einsum("ecd,edh->ech", bins, w_up)
    h = jax.nn.silu(g) * u
    return jnp.einsum("ech,ehd->ecd", h, w_down)


def moe_ep_forward(params: dict, x, *, mesh, axis: str = "ep",
                   dp_axis: str | None = None, n_expert_per_token: int = 2,
                   capacity_factor: float | None = None,
                   return_stats: bool = False):
    """Run a Mixtral-style MoE layer with experts AND tokens sharded over
    ``axis``. params: gate_w (D, E) replicated; w_gate/w_up/w_down stacked
    (E, D, H) / (E, D, H) / (E, H, D), sharded on dim 0. x: (N, D) sharded
    on dim 0. Returns (N, D) sharded on dim 0.

    EP×DP on one mesh: pass ``dp_axis`` to also batch-shard tokens over a
    data-parallel axis. Tokens live on (dp, ep) jointly; expert weights stay
    sharded over ``axis`` only (replicated across ``dp_axis``), so each DP
    slice runs its own all_to_all expert exchange over ICI while gradients
    for the replicated weights reduce over ``dp_axis`` as usual.

    With ``return_stats`` the routing-health gauges ride along: a dict of
    ``expert_load`` (E,), ``dropped_tokens`` and ``router_entropy`` — psum'd
    over the token axes so every host sees fleet totals (feeds the ``moe.*``
    telemetry registry)."""
    n_dev = mesh.shape[axis]
    dp_dev = mesh.shape[dp_axis] if dp_axis is not None else 1
    E = params["w_gate"].shape[0]
    assert E % n_dev == 0, f"experts {E} must divide over {axis}={n_dev}"
    K = n_expert_per_token
    N = x.shape[0]
    n_loc = N // (n_dev * dp_dev)
    # capacity: every local (token, k) assignment fits even if all pick the
    # same expert -> the distributed result is drop-free and matches the
    # single-device run exactly (capacity_factor overrides for drop tests)
    cap = n_loc * K if capacity_factor is None else int(
        math.ceil(n_loc * K / E * capacity_factor))

    def body(gate_w, w_gate, w_up, w_down, x_loc):
        # x_loc (n_loc, D); w_* (E_loc, ...): this device's experts
        logits = x_loc @ gate_w                              # (n_loc, E)
        probs = jax.nn.softmax(logits, -1)
        topk_probs, topk_idx = lax.top_k(probs, K)
        topk_probs = topk_probs / jnp.sum(topk_probs, -1, keepdims=True)
        bins, (flat_e, slot, keep, tok) = _dispatch_bins(
            x_loc, topk_idx, topk_probs, E, cap)
        # exchange: (E, cap, D) -> split E over devices -> every device ends
        # with (n_dev, E_loc, cap, D): all senders' tokens for ITS experts
        e_loc = E // n_dev
        send = bins.reshape(n_dev, e_loc, cap, -1)
        recv = lax.all_to_all(send, axis, 0, 0, tiled=False)  # (n_dev, e_loc, cap, D)
        flat = recv.swapaxes(0, 1).reshape(e_loc, n_dev * cap, -1)
        out_loc = _swiglu_experts(flat, w_gate, w_up, w_down)  # (e_loc, n_dev*cap, D)
        # return trip: back to (n_dev, e_loc, cap, D) -> all_to_all home
        back = lax.all_to_all(out_loc.reshape(e_loc, n_dev, cap, -1).swapaxes(0, 1),
                              axis, 0, 0, tiled=False)        # (n_dev, e_loc, cap, D)
        expert_out = back.reshape(E, cap, -1)
        # weighted combine at the token's home
        picked = expert_out[flat_e, slot]                     # (n_loc*K, D)
        w = (topk_probs.reshape(-1) * keep.astype(x_loc.dtype))[:, None]
        out = jnp.zeros_like(x_loc).at[tok].add(picked * w)
        if not return_stats:
            return out
        # routing health, reduced to fleet totals over every token axis
        load = jnp.sum(jax.nn.one_hot(flat_e, E, dtype=jnp.float32), 0)
        dropped = jnp.sum(1.0 - keep.astype(jnp.float32))
        ent = -jnp.sum(probs * jnp.log(jnp.clip(probs, 1e-30)))
        for ax in token_axes:
            load = lax.psum(load, ax)
            dropped = lax.psum(dropped, ax)
            ent = lax.psum(ent, ax)
        stats = {
            "expert_load": load / jnp.sum(load),
            "dropped_tokens": dropped,
            "router_entropy": ent / N,
        }
        return out, stats

    token_axes = (axis,) if dp_axis is None else (dp_axis, axis)
    tok_spec = P(token_axes)
    specs_in = (P(), P(axis), P(axis), P(axis), tok_spec)
    out_specs = (tok_spec, P()) if return_stats else tok_spec
    return jax.shard_map(body, mesh=mesh, in_specs=specs_in, out_specs=out_specs,
                         check_vma=False)(
        params["gate_w"], params["w_gate"], params["w_up"], params["w_down"], x)
