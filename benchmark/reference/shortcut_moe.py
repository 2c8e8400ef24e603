"""Plain reference for the configurations the ``shortcut_moe`` builder runs (LongCat-Flash's
text decoder: LongCat-Flash-Omni's public ``config.json`` keys), as ONE CHIP of an
expert-parallel group holds them.

The forward pass in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: one sequence at a time, an explicit T x T causal
mask, per-head keys and values EXPANDED from the latent at every position (never the absorbed
form the served model decodes with), the held experts one at a time over every token with the
others' rows masked, no cache, no chunks, no kernel, and nothing imported from ``thunder_tpu``.
It reads the published keys itself and what that file has no key for from ``assumed``.

Written from the technical report (LongCat-Flash, arXiv:2509.01322: the shortcut-connected
layer, zero-computation experts, the scale correction of MLA) and the keys. ``N`` is RMSNorm with eps ``rms_norm_eps``, no biases, ``d = hidden_size``; a layer
is a DOUBLE layer whose sublayers ``i`` = 0, 1 have their own weights:

    a0 = x  + MLA_0(N1_0(x));   u0 = N2_0(a0);   m = Experts(u0);   h0 = a0 + FFN_0(u0)
    a1 = h0 + MLA_1(N1_1(h0));  u1 = N2_1(a1);   y = a1 + FFN_1(u1) + m

* ``FFN(u) = (silu(u W_g) * (u W_u)) W_d``, width ``ffn_hidden_size``.
* ``MLA(u)``: ``c_q = s_q * RMSNorm(u W_qa)``, a head's ``[q_nope | q_rope] = c_q W_qb``;
  ``[c | k_r] = u W_kva``, ``c_kv = s_kv * RMSNorm(c)``, ``k_rope = rope(k_r)`` (one head for
  all, not scaled); a head's ``[k_nope | v] = c_kv W_kvb``; ``score = (q_nope . k_nope +
  rope(q_rope) . k_rope) * (qk_nope_head_dim + qk_rope_head_dim) ** -0.5`` under a causal
  softmax; heads side by side through ``W_o``. ``s_q = (d / q_lora_rank) ** 0.5`` where
  ``mla_scale_q_lora``, ``s_kv = (d / kv_lora_rank) ** 0.5`` where ``mla_scale_kv_lora``
  (``assumed.mla_scale``). Plain rope at base ``rope_theta`` on the interleaved pairs
  ``(2i, 2i + 1)`` (``assumed.rope_interleave``).
* ``Experts(u)``: ``p = softmax(u W_r)`` over ``reduced_from.n_routed_experts +
  zero_expert_num`` outputs in float32; the ``moe_topk`` largest of ``p +
  e_score_correction_bias`` are chosen (the bias for the choice only); ``g_i =
  routed_scaling_factor * p_i``, not normalised (``assumed.norm_topk_prob``); columns below
  ``reduced_from.n_routed_experts`` are routed experts of width ``expert_ffn_hidden_size``,
  the rest identity experts (``assumed.router_columns``): ``m = sum_{chosen routed} g_i
  SwiGLU_i(u) + (sum_{chosen identity} g_i) u``. The layer adds the routed part for the
  chosen experts inside ``experts_held = [lo, hi)`` only (what the absent ones would add is
  left out) and the identity part of every token (a token is at home on this chip).
* an embedding, a last RMSNorm and an untied head over the slice ``vocab_size`` of the
  published vocabulary.

How the reference is cut into blocks. The program serves each HALF of a double layer as one
layer with its own cache, so ``params`` names the halves ``h.<2l>`` (with the experts) and
``h.<2l + 1>``, and ``layer`` here is one half: it takes and hands back the CARRIED rows ``x (T,
2, d)``: ``x[:, 0]`` the residual stream and ``x[:, 1]`` the experts' result on its shortcut
(zero between double layers). ``forward`` is ``embed``, then ``layer`` for every half
(``num_hidden_layers`` of them: two a layer), then ``head``; the three are exported so that a
caller short of memory runs them one at a time (``layer_params``). Inside a half the experts
go one at a time through ``lax.scan``, the dense FFN in blocks of ``FFN_BLOCK`` hidden columns
and the heads through ``lax.map``, each cast to float32 for its own turn only: a half's FFN is
453 MB in bfloat16. ``layer`` also hands back the latent rows (``c_kv``, ``k_rope``) it
computed, for the comparison with the rows a served model keeps.

Departures: parameter names and layouts (``kv_b`` rows by head as ``[k_nope | v]``; expert
panels ``(E, d, w)``, ``(E, w, d)``) are the program's. With seeded random weights a layout is
a convention, not a property of the model. The text decoder only: the audio and vision
encoders and the codec decoder are not in the published ``config.json`` keys this reads.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# hidden columns of a dense FFN that are cast to float32 at a time
FFN_BLOCK = 2048


def _f32(x):
    return jnp.asarray(x).astype(F32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def _linear(x, w):
    return x @ _f32(w).T


def experts_held(config: dict) -> tuple:
    lo, hi = config["experts_held"]
    return int(lo), int(hi)


def n_routed(config: dict) -> int:
    """The routed experts the router has columns for: the published count, of which
    ``n_routed_experts`` are held here."""
    return int(config.get("reduced_from", {}).get("n_routed_experts", config["n_routed_experts"]))


def _rope(config: dict, x, pos):
    """Plain interleaved rope of ``x (T, ..., rope)`` at positions ``pos (T,)``."""
    dim = config["qk_rope_head_dim"]
    inv = 1.0 / float(config["rope_theta"]) ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    angle = jnp.asarray(inv, F32)[None, :] * jnp.asarray(pos, F32)[:, None]      # (T, rope / 2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    assert config["assumed"]["rope_interleave"]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def lora_scales(config: dict) -> tuple:
    """``(s_q, s_kv)``: what the two normed low-rank streams are multiplied by."""
    assert config["assumed"]["mla_scale"] == "sqrt_hidden_over_rank"
    d = config["hidden_size"]
    return ((d / config["q_lora_rank"]) ** 0.5 if config["mla_scale_q_lora"] else 1.0,
            (d / config["kv_lora_rank"]) ** 0.5 if config["mla_scale_kv_lora"] else 1.0)


def _attention(config: dict, params: dict, u):
    """MLA over the normed rows ``u (T, d)`` -> ``(output (T, d), c_kv (T, r), k_rope (T, rope))``."""
    T = u.shape[0]
    H = config["num_attention_heads"]
    nope, rope, v, r = (config[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                                             "kv_lora_rank"))
    eps = config["rms_norm_eps"]
    s_q, s_kv = lora_scales(config)
    pos = jnp.arange(T)
    c_q = s_q * _rms_norm(_linear(u, params["attn.q_a.weight"]), params["attn.q_norm.weight"], eps)
    q = _linear(c_q, params["attn.q_b.weight"]).reshape(T, H, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(config, q[..., nope:], pos)
    ckr = _linear(u, params["attn.kv_a.weight"])
    c_kv = s_kv * _rms_norm(ckr[:, :r], params["attn.kv_norm.weight"], eps)
    k_rope = _rope(config, ckr[:, r:], pos)
    w_kvb = jnp.asarray(params["attn.kv_b.weight"]).reshape(H, nope + v, r)
    mask = pos[None, :] <= pos[:, None]
    scale = (nope + rope) ** -0.5

    def one_head(args):
        qn, qr, w = args                                  # (T, nope), (T, rope), (nope + v, r)
        kv = c_kv @ _f32(w).T                             # this head's keys and values, expanded
        scores = (qn @ kv[:, :nope].T + qr @ k_rope.T) * scale
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return probs @ kv[:, nope:]

    heads = jax.lax.map(one_head, (q_nope.transpose(1, 0, 2), q_rope.transpose(1, 0, 2), w_kvb))
    out = _linear(heads.transpose(1, 0, 2).reshape(T, H * v), params["attn.o.weight"])
    return out, c_kv, k_rope


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def dense_ffn(config: dict, params: dict, u):
    """``(silu(u W_g) * (u W_u)) W_d`` in blocks of the hidden columns."""
    w_gate, w_up, w_down = (jnp.asarray(params[f"mlp.{n}.weight"]) for n in ("gate", "up", "down"))
    width, d = w_gate.shape
    block = FFN_BLOCK if width % FFN_BLOCK == 0 else width

    def one_block(total, args):
        g, up, down = args                                # (block, d), (block, d), (d, block)
        return total + _swiglu(u, _f32(g).T, _f32(up).T, _f32(down).T), None

    blocks = (w_gate.reshape(-1, block, d), w_up.reshape(-1, block, d),
              w_down.reshape(d, -1, block).transpose(1, 0, 2))
    total, _ = jax.lax.scan(one_block, jnp.zeros_like(u), blocks)
    return total


def route(config: dict, params: dict, u):
    """``(chosen (T, k), g (T, k))`` over all the router's outputs, routed and identity."""
    logits = _linear(u, params["experts.gate.weight"])
    assert logits.shape[-1] == n_routed(config) + config["zero_expert_num"], \
        "the router keeps its published width"
    p = jax.nn.softmax(logits, axis=-1)
    _, chosen = jax.lax.top_k(p + _f32(params["experts.e_score_correction_bias"]), config["moe_topk"])
    g = jnp.take_along_axis(p, chosen, axis=-1)
    if config["assumed"]["norm_topk_prob"]:
        g = g / (g.sum(-1, keepdims=True) + 1e-20)
    return chosen, g * config["routed_scaling_factor"]


def routed_part(config: dict, params: dict, u):
    """What the experts held here add for ``u (T, d)``: nothing is dropped, and what the
    absent experts would add is left out."""
    lo, hi = experts_held(config)
    chosen, g = route(config, params, u)

    def one_expert(total, args):
        e, w_gate, w_up, w_down = args
        weight = jnp.where(chosen == e, g, 0.0).sum(-1)                       # (T,), 0 where not chosen
        return total + weight[:, None] * _swiglu(u, _f32(w_gate), _f32(w_up), _f32(w_down)), None

    panels = tuple(jnp.asarray(params[f"experts.{n}"]) for n in ("w_gate", "w_up", "w_down"))
    total, _ = jax.lax.scan(one_expert, jnp.zeros_like(u), (jnp.arange(lo, hi), *panels))
    return total


def identity_part(config: dict, params: dict, u):
    """What the zero-compute experts add: the token itself, once for each it chose, by its
    weight. ``zero_expert_type`` ``none`` (the control's) adds nothing."""
    kind = config["zero_expert_type"]
    if kind == "none":
        return jnp.zeros_like(u)
    if kind != "identity":
        raise ValueError(f"unknown zero_expert_type {kind!r}")
    chosen, g = route(config, params, u)
    return jnp.where(chosen >= n_routed(config), g, 0.0).sum(-1)[:, None] * u


def control(config: dict) -> tuple:
    """``(wrong_config, what_is_wrong)``: a configuration the same weights must *not* agree
    with: the zero-compute experts add nothing, as if a token that chose one had chosen no
    expert at all."""
    return dict(config, zero_expert_type="none"), "zero_expert_type none: the identity experts add nothing"


def layer_params(params: dict, index: int, prefix: str = "") -> dict:
    """Half ``index``'s parameters under the names ``layer`` reads: those below ``h.<index>.``."""
    pre = f"{prefix}h.{index}."
    return {name[len(pre):]: p for name, p in params.items() if name.startswith(pre)}


def embed(config: dict, params: dict, tokens, *, prefix: str = ""):
    """The carried rows ``(T, 2, d)`` float32 for token ids ``(T,)``: the embedding table's
    rows, and nothing on the shortcut."""
    x = _f32(jnp.asarray(params[prefix + "wte.weight"])[tokens])
    return jnp.stack([x, jnp.zeros_like(x)], axis=1)


def layer(config: dict, params: dict, x):
    """One HALF of a double layer on the carried rows ``x (T, 2, d)`` with its own parameters
    (``layer_params``): the first half (it has ``experts.*``) puts the experts' result on the
    shortcut, the second adds it to the stream and clears it. Returns ``(x, made)``, ``made`` the
    rows a cache would hold of it: ``c_kv (T, kv_lora_rank)`` and ``k_rope (T, qk_rope_head_dim)``."""
    with jax.default_matmul_precision("highest"):
        eps = config["rms_norm_eps"]
        stream, carried = x[:, 0], x[:, 1]
        a, c_kv, k_rope = _attention(config, params, _rms_norm(stream, params["norm_1.weight"], eps))
        stream = stream + a
        u = _rms_norm(stream, params["norm_2.weight"], eps)
        if "experts.gate.weight" in params:
            carried = routed_part(config, params, u) + identity_part(config, params, u)
            stream = stream + dense_ffn(config, params, u)
        else:
            stream = stream + dense_ffn(config, params, u) + carried
            carried = jnp.zeros_like(carried)
        return jnp.stack([stream, carried], axis=1), {"c_kv": c_kv, "k_rope": k_rope}


def head(config: dict, params: dict, x, *, prefix: str = ""):
    """Logits of the carried rows ``x (n, 2, d)``; ``lm_head.weight`` may be a block of its rows."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x[:, 0], params[prefix + "ln_f.weight"], config["rms_norm_eps"])
        return _linear(x, params[prefix + "lm_head.weight"])


def forward(config: dict, params: dict, tokens, *, prefix: str = "", rows=None):
    """Logits ``(T, V)`` for one sequence of token ids ``(T,)``; with ``rows`` only at those
    positions."""
    if config["model_type"] != "longcat_flash" or config["num_hidden_layers"] != 2 * config["num_layers"]:
        raise ValueError(f"this reference knows model_type longcat_flash only, not {config['model_type']!r}, and "
                         f"num_hidden_layers counts the attention blocks, two a double layer")
    x = embed(config, params, tokens, prefix=prefix)
    for i in range(config["num_hidden_layers"]):
        x, _ = layer(config, layer_params(params, i, prefix), x)
    if rows is not None:
        x = x[rows]
    return head(config, params, x, prefix=prefix)


def loss(config: dict, params: dict, tokens, targets, *, prefix: str = ""):
    """Mean next-token cross-entropy of one sequence (the model is served only; kept because the
    harness asks every reference for one)."""
    logits = forward(config, params, tokens, prefix=prefix)
    logz = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(logz - jnp.take_along_axis(logits, jnp.asarray(targets)[:, None], axis=-1)[:, 0])
