"""Plain reference for the configurations the ``sambay`` builder runs
(``model_type`` ``phi4flash``: Phi-4-mini-flash-reasoning).

The forward pass in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: a sequential scan over time, an
explicit T x T mask, no cache, no chunks, no kernel, and nothing imported from
``thunder_tpu``. It reads the published ``config.json`` keys itself, and the
sizes the published file has no key for from the configuration's ``assumed``.

Written from the published description (SambaY, arXiv:2507.06607; Mamba,
arXiv:2312.00752; Differential Transformer, arXiv:2410.05258):

* every layer ``i``: ``x += mixer_i(LN(x)); x += mlp(LN'(x))``, LayerNorm with
  gain and bias, ``mlp = ((u * silu(g)) W_fc2)`` with ``[g, u] = x W_fc1``; a last
  LayerNorm and the tied head ``logits = h W_emb^T``. No positional encoding.
* mixer by index, with ``half = num_hidden_layers // 2``: ``i`` a multiple of
  ``mb_per_layer`` and ``<= half``: Mamba-1; the other ``i < half``: differential
  attention in a window of ``sliding_window``; ``i = half + 1``: the same with the
  full causal mask, whose keys and values are the model's one cache; of the
  layers after it those at a multiple of ``mb_per_layer`` are gated memory units
  that read layer ``half``'s scan output, the others cross-attention that has
  queries only and reads layer ``half + 1``'s keys and values.
* Mamba-1: ``[x, z] = u W_in``; ``x = silu(conv(x) + b)`` (causal, depthwise);
  ``[dt, B, C] = x W_x``; ``Dt = softplus(dt W_dt + b_dt)``; ``h_t = exp(Dt_t A) h_(t-1)
  + (Dt_t x_t) (x) B_t`` with ``A = -exp(A_log)``; ``y_t = h_t C_t + D x_t``; the mixer
  gives ``(y * silu(z)) W_out``. Layer ``half``'s ``y`` is the memory.
* differential attention over adjacent pairs of heads: the pair's two query
  heads each take a softmax with the KV pair's key head of the same place, both
  over the pair's two value heads side by side; ``lambda = exp(lq1 . lk1) - exp(lq2 .
  lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 i)``; the pair gives
  ``(1 - lambda_init) RMSNorm(A_1 - lambda A_2)``.

``forward`` is ``embed``, then ``layer`` for every index, then ``head``; the three are
exported so that a caller short of memory can run them one at a time, each on
its own parameters (``layer_params``), instead of casting 3.85 B weights to
float32 at once. ``layer`` also hands back what a Mamba layer's scan state was
after a given position and how slowly each channel forgets, for the comparison
with the rows a served model keeps.

Departures: parameter names and the fused layouts (``qkv`` as ``[q, k, v]``,
``fc_1`` as ``[g, u]``, ``in_proj`` as ``[x, z]``) are the program's. With seeded
random weights a layout is a convention, not a property of the model.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
SUBLN_EPS = 1e-5


def _f32(x):
    return jnp.asarray(x).astype(F32)


def layer_kinds(config: dict) -> list:
    """The kind of every layer's mixer, by the published rule."""
    n, every = config["num_hidden_layers"], config["mb_per_layer"]
    half = n // 2
    kinds = []
    for i in range(n):
        if i <= half:
            kinds.append("mamba" if i % every == 0 else "window_attn")
        elif i == half + 1:
            kinds.append("full_attn")
        else:
            kinds.append("gmu" if i % every == 0 else "cross_attn")
    return kinds


def _layer_norm(x, params, name, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(params[name + ".weight"]) + _f32(params[name + ".bias"])


def _linear(x, params, name):
    y = x @ _f32(params[name + ".weight"]).T
    bias = params.get(name + ".bias")
    return y if bias is None else y + _f32(bias)


def _mlp(config, params, pre, h):
    g, u = jnp.split(_linear(h, params, pre + "fc_1"), 2, axis=-1)
    return _linear(u * jax.nn.silu(g), params, pre + "fc_2")


def _mamba(config, params, pre, u, state_at=None):
    """``(mixer output, y, h, step)`` for inputs ``u (T, d)``; ``y`` is the scan's
    output before the gate, ``h (d_inner, d_state)`` the scan's state after
    position ``state_at`` (the last one if ``None``) and ``step (d_inner,)`` each
    channel's mean step size ``Dt`` up to there: how slowly it forgets."""
    a = config["assumed"]
    n_state, width = a["mamba_d_state"], a["mamba_d_conv"]
    T = u.shape[0]
    x, z = jnp.split(_linear(u, params, pre + "in_proj"), 2, axis=-1)           # (T, di) each
    w = _f32(params[pre + "conv.weight"])                                        # (di, width)
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), F32), x])
    x = sum(padded[j:j + T] * w[:, j] for j in range(width)) + _f32(params[pre + "conv.bias"])
    x = jax.nn.silu(x)
    dbc = _linear(x, params, pre + "x_proj")
    rank = dbc.shape[1] - 2 * n_state
    dt, Bm, Cm = dbc[:, :rank], dbc[:, rank:rank + n_state], dbc[:, rank + n_state:]
    dt = jax.nn.softplus(_linear(dt, params, pre + "dt_proj"))                   # (T, di)
    A = -jnp.exp(_f32(params[pre + "A_log"]))                                    # (di, n)

    state_at = T - 1 if state_at is None else state_at

    def step(carry, inp):
        h, kept = carry
        t, dt_t, x_t, B_t, C_t = inp
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * x_t)[:, None] * B_t[None, :]
        return (h, jnp.where(t == state_at, h, kept)), h @ C_t

    zero = jnp.zeros(A.shape, F32)
    (_, kept), y = jax.lax.scan(step, (zero, zero), (jnp.arange(T), dt, x, Bm, Cm))
    y = y + _f32(params[pre + "D"]) * x
    step_size = jnp.sum(jnp.where(jnp.arange(T)[:, None] <= state_at, dt, 0.0), axis=0) / (state_at + 1)
    return _linear(y * jax.nn.silu(z), params, pre + "out_proj"), y, kept, step_size


def _diff_attention(config, params, pre, layer, q, k, v, mask):
    """``q (T, heads * hs)``, ``k`` and ``v (S, kv_heads * hs)``, ``mask (T, S)`` of
    the key positions each query may see; ``layer`` is the layer's index."""
    nh, ng = config["num_attention_heads"], config["num_key_value_heads"]
    hs = config["hidden_size"] // nh
    T, S = q.shape[0], k.shape[0]
    pairs, kv_pairs = nh // 2, ng // 2
    q = q.reshape(T, pairs, 2, hs)
    k = jnp.repeat(k.reshape(S, kv_pairs, 2, hs), pairs // kv_pairs, axis=1)
    v = jnp.repeat(v.reshape(S, kv_pairs, 2 * hs), pairs // kv_pairs, axis=1)
    scores = jnp.einsum("tpsd,upsd->pstu", q, k) / math.sqrt(hs)
    probs = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf), axis=-1)
    a = jnp.einsum("pstu,upe->tpse", probs, v)                                   # (T, pairs, 2, 2 hs)
    lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * layer)
    lam = (jnp.exp(jnp.sum(_f32(params[pre + "lambda_q1"]) * _f32(params[pre + "lambda_k1"])))
           - jnp.exp(jnp.sum(_f32(params[pre + "lambda_q2"]) * _f32(params[pre + "lambda_k2"])))
           + lam_init)
    y = a[:, :, 0] - lam * a[:, :, 1]
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + SUBLN_EPS)
    y = y * _f32(params[pre + "subln.weight"]) * (1.0 - lam_init)
    return _linear(y.reshape(T, nh * hs), params, pre + "proj")


def control(config: dict) -> tuple:
    """``(wrong_config, what_is_wrong)``: a configuration the same weights must
    *not* agree with. The model has no rope to spoil: the window is halved, so
    every query past it loses the older half of what its window layers see."""
    return dict(config, sliding_window=config["sliding_window"] // 2), "sliding_window / 2"


def layer_params(params: dict, index: int, prefix: str = "") -> dict:
    """Layer ``index``'s parameters under the names ``layer`` reads: those below
    ``h.<index>.``."""
    pre = f"{prefix}h.{index}."
    return {name[len(pre):]: p for name, p in params.items() if name.startswith(pre)}


def embed(config: dict, params: dict, tokens, *, prefix: str = ""):
    """``(T, d)`` float32 rows of the embedding table for token ids ``(T,)``."""
    return _f32(jnp.asarray(params[prefix + "wte.weight"])[tokens])


def layer(config: dict, kind: str, index, params: dict, x, *, memory=None, kv=None, state_at=None):
    """Block ``index`` of kind ``kind`` on ``x (T, d)`` with its own parameters
    (``layer_params``); ``memory`` is layer ``half``'s scan output for a gated
    memory unit, ``kv`` the full-attention layer's keys and values for a
    cross-attention layer. Returns ``(x, made)``: a Mamba layer makes ``memory``,
    ``state`` (its scan state after position ``state_at``) and ``step`` (each
    channel's mean step size up to there), the full-attention layer ``kv``.
    Only ``lambda_init`` reads ``index``."""
    nh, ng = config["num_attention_heads"], config["num_key_value_heads"]
    hs = config["hidden_size"] // nh
    eps = config["layer_norm_eps"]
    made = {}
    with jax.default_matmul_precision("highest"):
        T = x.shape[0]
        t, s = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
        causal = s <= t
        h = _layer_norm(x, params, "norm_1", eps)
        if kind == "mamba":
            out, made["memory"], made["state"], made["step"] = _mamba(config, params, "mixer.", h, state_at)
        elif kind == "gmu":
            out = _linear(memory * jax.nn.silu(_linear(h, params, "mixer.in_proj")), params,
                          "mixer.out_proj")
        elif kind == "cross_attn":
            out = _diff_attention(config, params, "mixer.", index, _linear(h, params, "mixer.q"), *kv, causal)
        else:
            q, k, v = jnp.split(_linear(h, params, "mixer.qkv"), [nh * hs, (nh + ng) * hs], axis=-1)
            if kind == "full_attn":
                made["kv"] = (k, v)
                mask = causal
            else:
                mask = causal & (t - config["sliding_window"] < s)
            out = _diff_attention(config, params, "mixer.", index, q, k, v, mask)
        x = x + out
        return x + _mlp(config, params, "mlp.", _layer_norm(x, params, "norm_2", eps)), made


def head(config: dict, params: dict, x, *, prefix: str = ""):
    """Logits of ``x (T, d)`` after the last block: LayerNorm, then the tied
    table, or as many of its rows as ``params`` holds."""
    with jax.default_matmul_precision("highest"):
        x = _layer_norm(x, params, prefix + "ln_f", config["layer_norm_eps"])
        return x @ _f32(params[prefix + "wte.weight"]).T


def forward(config: dict, params: dict, tokens, *, prefix: str = "", rows=None):
    """Logits ``(T, vocab)`` of one sequence of token ids ``(T,)``; with
    ``rows`` (an index array) only those positions' logits."""
    x = embed(config, params, tokens, prefix=prefix)
    memory = kv = None
    for i, kind in enumerate(layer_kinds(config)):
        x, made = layer(config, kind, i, layer_params(params, i, prefix), x, memory=memory, kv=kv)
        if i == config["num_hidden_layers"] // 2:
            memory = made["memory"]
        kv = made.get("kv", kv)
    return head(config, params, x if rows is None else x[rows], prefix=prefix)


def loss(config: dict, params: dict, tokens, targets, *, prefix: str = ""):
    """Mean cross-entropy of one sequence against ``targets`` ``(T,)``. No cell
    trains this model; the harness asks every reference for it."""
    logits = forward(config, params, tokens, prefix=prefix)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))
