"""Plain reference for the configurations the ``parallel_moe`` builder runs: Cohere's Command A+
(``model_type`` ``cohere2_moe``), a PARALLEL block of window and position-free full attention
beside routed and shared experts, as ONE CHIP of an expert-parallel group holds it.

The forward pass in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: one sequence at a time, the mask written out, rope
on the LITERAL interleaved pairs, the experts one at a time over every token with the others'
rows weighted zero, no cache, no pages, no kernel, no batching, and nothing imported from
``thunder_tpu``. It reads the published ``config.json`` keys itself, and from the file's
``assumed`` group what that file has no key for.

Written from these equations. ``LN`` is a LayerNorm with a weight and no bias, eps
``layer_norm_eps``; there are no biases:

* layer ``i``, input ``X (T, hidden_size)``, of kind ``layer_types[i]``: ``h = LN(X)`` (ONE norm
  feeds both branches: ``use_parallel_block``); ``Q = h Wq`` (``num_attention_heads`` heads of
  ``head_dim``), ``K = h Wk``, ``V = h Wv`` (``num_key_value_heads`` heads);
  ``sliding_attention`` layers rotate every head of ``Q`` and ``K`` (``rotary_pct`` 1) on the
  pairs ``(x[2i], x[2i + 1])`` (``position_embedding_type`` ``rope_gptj``) by ``t *
  rope_theta ** (-2i / head_dim)`` and let query ``t`` see keys ``t - sliding_window < s <= t``;
  ``full_attention`` layers take NO positions and see ``s <= t``;
  ``A = softmax(Q K^T / sqrt(head_dim) + M) V`` with ``num_attention_heads /
  num_key_value_heads`` query heads a key head; ``attn = A Wo``;
  ``r = sigmoid(h Wr)`` in float32 over the router's ``reduced_from.num_experts`` outputs
  (``expert_selection_fn``), ``S`` the ``num_experts_per_tok`` largest, ``g_e = r_e / sum_S r``
  (``norm_topk_prob``); ``routed = sum_{e in S, e held} g_e W2_e (silu(W1_e h) * W3_e h)`` over
  the experts ``experts_held = [lo, hi)`` only: what the others add is left out;
  ``shared = (1 / n) sum_{j < n} W2_j (silu(W1_j h) * W3_j h)`` over the ``n =
  num_shared_experts`` shared experts (``shared_expert_combination_strategy`` ``average``);
  ``X' = X + attn + routed + shared``.
* after the last layer ``LN`` and the head tied to the embedding, times ``logit_scale``.

Departures, each because the program does the same and ``correct`` is to judge the compiler and
the engine, not these choices: parameter names and layouts are the program's. The fused QKV
weight's rows are ``[Wq | Wk | Wv]``, each head's rows of Wq and of Wk with the published even
columns first and then the odd ones (``published``, below, puts them back before anything else
reads them); the shared experts are one fused SwiGLU of ``n`` blocks, read here one block an
expert; expert panels are ``(E, d, w)`` and ``(E, w, d)``. The router's selection bias that the
program's expert layer carries is zero for this family and is not read.

How it is cut into blocks of work: ``forward`` is ``embed``, then ``layer`` for every layer, then
``head``; the three are exported with ``layer_params`` so that a caller short of memory runs them
one at a time on bfloat16 weights. Inside a layer the query heads go one at a time through
``lax.scan`` and each head's queries in blocks of ``QUERY_BLOCK`` rows through ``lax.map``, so
that no score matrix is larger than a block's rows by the sequence; the experts and the shared
experts go one at a time, each cast to float32 for its own turn. ``layer`` also hands back the
keys it computed (after the rope where there is one), in the published column order, for the
comparison with the rows a served model keeps.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 512


def _f32(x):
    return jnp.asarray(x).astype(F32)


def kinds(config: dict) -> list:
    """``"window"`` or ``"full"`` for each of the configuration's layers."""
    names = {"sliding_attention": "window", "full_attention": "full"}
    return [names[t] for t in config["layer_types"][:config["num_hidden_layers"]]]


def n_routed(config: dict) -> int:
    return int(config.get("reduced_from", {}).get("num_experts", config["num_experts"]))


def _layer_norm(x, w, eps):
    m = jnp.mean(x, axis=-1, keepdims=True)
    c = x - m
    return c * jax.lax.rsqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps) * _f32(w)


def _stored_order(head_dim: int) -> np.ndarray:
    """The published column each stored column of a head of Wq or Wk holds."""
    return np.concatenate([np.arange(0, head_dim, 2), np.arange(1, head_dim, 2)])


def published(w, heads: int, head_dim: int):
    """Rows of Wq or Wk (heads * head_dim, d) from the program's order into the published one."""
    back = np.argsort(_stored_order(head_dim))
    return jnp.asarray(w).reshape(heads, head_dim, -1)[:, back, :].reshape(heads * head_dim, -1)


def _rope(x, pos, config: dict):
    """x (T, heads, head_dim) in the published column order, rotated at positions pos (T,):
    the pairs (x[2i], x[2i + 1]) for ``rope_gptj``, (x[i], x[i + head_dim / 2]) for the
    control's ``rope_rotate_half``. Returns the rotated columns as [first of every pair | second
    of every pair] (scores are sums over pairs, so the order of the pairs does not matter)."""
    hd = x.shape[-1]
    kind = config["position_embedding_type"]
    if kind == "rope_gptj":
        a, b = x[..., 0::2], x[..., 1::2]
    elif kind == "rope_rotate_half":
        a, b = x[..., :hd // 2], x[..., hd // 2:]
    else:
        raise ValueError(f"unknown position_embedding_type {kind!r}")
    inv = 1.0 / (float(config["rope_theta"]) ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos.astype(F32)[:, None, None] * inv[None, None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def published_keys(rotated, config: dict, kind: str) -> np.ndarray:
    """Keys (T, heads, head_dim) as ``layer`` hands them back, in the published column order
    (a full layer's are that already), on the host."""
    rotated = np.asarray(rotated)
    if kind == "full":
        return rotated
    hd = rotated.shape[-1]
    out = np.empty_like(rotated)
    if config["position_embedding_type"] == "rope_gptj":
        out[..., 0::2], out[..., 1::2] = rotated[..., :hd // 2], rotated[..., hd // 2:]
    else:
        out[...] = rotated
    return out


def _positions(config: dict, kind: str) -> bool:
    return kind == "window" or config["assumed"]["full_layer_positions"] == "rope"


def _attention(config, p, h, kind: str):
    """``(what attention adds (T, d), the keys (T, kv heads, head_dim))`` for the normed tokens h."""
    T, d = h.shape
    nh, ng, hd = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    per = nh // ng
    w = p["attn.qkv.weight"]
    wq = published(w[:nh * hd], nh, hd)
    wk = published(w[nh * hd:(nh + ng) * hd], ng, hd)
    wv = w[(nh + ng) * hd:]
    pos = jnp.arange(T)
    roped = _positions(config, kind)
    k = (h @ _f32(wk).T).reshape(T, ng, hd)
    v = (h @ _f32(wv).T).reshape(T, ng, hd)
    if roped:
        k = _rope(k, pos, config)
    window = config["sliding_window"] if kind == "window" else None
    qb = min(QUERY_BLOCK, T)
    n = -(-T // qb)
    wo = _f32(p["attn.o.weight"]).reshape(d, nh, hd)

    def one_head(acc, i):
        q = h @ _f32(jax.lax.dynamic_slice_in_dim(wq, i * hd, hd, 0)).T          # (T, hd)
        if roped:
            q = _rope(q[:, None], pos, config)[:, 0]
        q = jnp.pad(q, ((0, n * qb - T), (0, 0))).reshape(n, qb, hd)
        kk, vv = k[:, i // per], v[:, i // per]

        def block(args):
            qs, b = args
            t = b * qb + jnp.arange(qb)
            seen = pos[None, :] <= t[:, None]
            if window is not None:
                seen = seen & (pos[None, :] > t[:, None] - window)
            scores = jnp.where(seen, qs @ kk.T / math.sqrt(hd), -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ vv

        y = jax.lax.map(block, (q, jnp.arange(n))).reshape(n * qb, hd)[:T]
        return acc + y @ jax.lax.dynamic_index_in_dim(wo, i, 1, keepdims=False).T, None

    out, _ = jax.lax.scan(one_head, jnp.zeros((T, d), F32), jnp.arange(nh))
    return out, k


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate) * (u @ w_up)) @ w_down


def _experts(config, p, u):
    """``(routed, shared)``: what the held routed experts and the shared experts add for the
    tokens ``u (T, d)``."""
    if config["expert_selection_fn"] != "sigmoid":
        raise ValueError(f"unknown expert_selection_fn {config['expert_selection_fn']!r}")
    k = config["num_experts_per_tok"]
    r = jax.nn.sigmoid(u @ _f32(p["experts.gate.weight"]).T)                     # (T, n_routed)
    top, idx = jax.lax.top_k(r, k)
    g = top / jnp.sum(top, axis=-1, keepdims=True) if config["norm_topk_prob"] else top
    weight = jnp.zeros_like(r).at[jnp.arange(u.shape[0])[:, None], idx].set(g)   # (T, n_routed)
    lo, hi = config["experts_held"]

    def one(acc, e):
        w_gate, w_up, w_down, g_e = e
        return acc + g_e[:, None] * _swiglu(u, _f32(w_gate), _f32(w_up), _f32(w_down)), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u), (p["experts.w_gate"], p["experts.w_up"],
                                                      p["experts.w_down"], weight[:, lo:hi].T))
    n = config["num_shared_experts"]
    width = p["experts.shared_gate.weight"].shape[0] // n
    blocks = (jnp.asarray(p["experts.shared_gate.weight"]).reshape(n, width, -1),
              jnp.asarray(p["experts.shared_up.weight"]).reshape(n, width, -1),
              jnp.asarray(p["experts.shared_down.weight"]).reshape(-1, n, width).transpose(1, 0, 2))

    def one_shared(acc, e):
        w_gate, w_up, w_down = e
        return acc + _swiglu(u, _f32(w_gate).T, _f32(w_up).T, _f32(w_down).T), None

    shared, _ = jax.lax.scan(one_shared, jnp.zeros_like(u), blocks)
    return routed, shared


def layer_params(params: dict, i: int, prefix: str = "") -> dict:
    pre = f"{prefix}h.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def embed(config: dict, p: dict, tokens):
    return _f32(p["wte.weight"])[tokens]


def layer(config: dict, p: dict, x, i: int):
    """Layer ``i`` over ``x (T, d)``: ``(y, {"k": its keys (T, kv heads, head_dim), rotated
    where the layer has positions, in the order ``published_keys`` puts back})``."""
    kind = kinds(config)[i]
    with jax.default_matmul_precision("highest"):
        h = _layer_norm(x, p["norm.weight"], config["layer_norm_eps"])
        a, keys = _attention(config, p, h, kind)
        routed, shared = _experts(config, p, h)
        how, n = config["shared_expert_combination_strategy"], config["num_shared_experts"]
        if how == "average":
            moe = routed + shared / n
        elif how == "sum":
            moe = routed + shared
        elif how == "average_with_routed":
            moe = (routed + shared) / (n + 1)
        else:
            raise ValueError(f"unknown shared_expert_combination_strategy {how!r}")
        return x + a + moe, {"k": keys}


def head(config: dict, p: dict, x):
    """Logits of the rows x (R, d) over the embedding's rows in ``p["wte.weight"]``."""
    with jax.default_matmul_precision("highest"):
        return (_layer_norm(x, p["ln_f.weight"], config["layer_norm_eps"]) @ _f32(p["wte.weight"]).T
                * float(config["logit_scale"]))


def forward(config: dict, params: dict, tokens, *, prefix: str = "", rows=None):
    """Logits ``(T, vocab)`` of one sequence of token ids ``(T,)``; with ``rows`` (an index
    array) only those positions' logits."""
    x = embed(config, {"wte.weight": params[prefix + "wte.weight"]}, jnp.asarray(tokens))
    for i in range(config["num_hidden_layers"]):
        x, _ = layer(config, layer_params(params, i, prefix), x, i)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    return head(config, {k: params[prefix + k] for k in ("ln_f.weight", "wte.weight")}, x)


def loss(config: dict, params: dict, tokens, targets, *, prefix: str = ""):
    """Mean cross-entropy of one sequence's next-token logits against ``targets``. The model is
    served only; kept because the harness asks every reference for one."""
    logits = forward(config, params, tokens, prefix=prefix)
    logz = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(logz - jnp.take_along_axis(logits, jnp.asarray(targets)[:, None], axis=-1)[:, 0])


def control(config: dict) -> tuple:
    """``(wrong_config, what_is_wrong)``: the same weights with rope on the rotate-half pairs
    ``(x[i], x[i + head_dim / 2])`` in the window layers, which the same weights must NOT agree
    with."""
    return dict(config, position_embedding_type="rope_rotate_half"), "rope on rotate-half pairs"
