"""Plain reference for the configurations the ``latent_moe`` builder runs
(``model_type`` ``mistral4``: Mistral-Small-4-119B-2603, whose keys and layer are
DeepSeek-V3's), as ONE CHIP of an expert-parallel group holds them.

The forward pass in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: one sequence at a time, an explicit
T x T causal mask, per-head keys and values EXPANDED from the latent at every
position (never the absorbed form the served model decodes with), every held
expert over every token with the others' rows masked, no cache, no chunks, no
kernel, and nothing imported from ``thunder_tpu``. It reads the published
``config.json`` keys itself and what that file has no key for from ``assumed``.

Written from the published descriptions (DeepSeek-V2, arXiv:2405.04434, section
2.1; DeepSeek-V3, arXiv:2412.19437, section 2.1; YaRN, arXiv:2309.00071):

* every layer: ``h = x + MLA(RMSNorm(x))``, ``y = h + Experts(RMSNorm(h))``, eps
  ``rms_norm_eps``, no biases; a last RMSNorm and an untied head.
* MLA: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb``, a head ``[q_nope | q_rope]``;
  ``[c | k_r] = x W_kva``; ``c_kv = RMSNorm(c)``; ``k_rope = rope(k_r)``, one head
  for all; a head's ``[k_nope | v] = c_kv W_kvb``; ``score = (q_nope . k_nope +
  rope(q_rope) . k_rope) * scale``, causal softmax, ``softmax . v``, heads side by
  side through ``W_o``. Rope turns the interleaved pairs ``(2i, 2i + 1)`` by
  YaRN's frequencies; queries are multiplied by ``1 + beta ln(1 + floor(pos /
  original))`` (``llama_4_scaling_beta``); ``scale = qk_head_dim ** -0.5 * m ** 2``,
  ``m = 0.1 mscale_all_dim ln(factor) + 1`` (``assumed.softmax_scale``).
* Experts: ``s = sigmoid(x W_g)`` over ALL ``reduced_from.n_routed_experts`` in
  float32; the ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``
  are chosen; ``w = s[chosen] / sum(s[chosen])`` times ``routed_scaling_factor``;
  the layer adds ``w_e SwiGLU_e(x)`` for the chosen experts inside
  ``experts_held = [lo, hi)`` only — what the absent ones would add is left out —
  and the shared expert's ``SwiGLU(x)``.
* the vocabulary is the slice ``vocab_size`` of the published one.

``forward`` is ``embed``, then ``layer`` for every index, then ``head``; the three
are exported so that a caller short of memory runs them one at a time
(``layer_params``). Inside a layer the experts go one at a time through
``lax.scan`` and the heads through ``lax.map``, each cast to float32 for its own
turn only: a layer's panels are 1.6 GB in bfloat16. ``layer`` also hands back
the latent rows (``c_kv``, ``k_rope``) it computed, for the comparison with the
rows a served model keeps.

Departures: parameter names and layouts (``kv_b`` rows by head as ``[k_nope |
v]``; expert panels ``(E, d, w)``, ``(E, w, d)``) are the program's. With seeded
random weights a layout is a convention, not a property of the model.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


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
    """The router's width: the published count of experts, of which
    ``n_routed_experts`` are held here."""
    return int(config.get("reduced_from", {}).get("n_routed_experts", config["n_routed_experts"]))


def yarn_inv_freq(config: dict) -> np.ndarray:
    rp, dim = config["rope_parameters"], config["qk_rope_head_dim"]
    theta, factor = float(rp["rope_theta"]), float(rp["factor"])
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp.get("rope_type", "default") != "yarn" or factor <= 1.0:
        return 1.0 / pos_freqs
    original = rp["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rp["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rp["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    extrapolated = 1.0 - ramp
    return (1.0 / (factor * pos_freqs)) * (1.0 - extrapolated) + (1.0 / pos_freqs) * extrapolated


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(config: dict) -> float:
    rp = config["rope_parameters"]
    scale = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5
    if config["assumed"]["softmax_scale"] == "yarn_mscale_all_dim_squared" and rp.get("mscale_all_dim"):
        scale *= _mscale(float(rp["factor"]), float(rp["mscale_all_dim"])) ** 2
    return scale


def _rope(config: dict, x, pos):
    """Interleaved rope of ``x (T, ..., rope)`` at positions ``pos (T,)``."""
    rp = config["rope_parameters"]
    factor = 1.0
    if rp.get("mscale_all_dim"):
        factor = _mscale(float(rp["factor"]), float(rp["mscale"])) / _mscale(float(rp["factor"]),
                                                                             float(rp["mscale_all_dim"]))
    angle = np.asarray(yarn_inv_freq(config))[None, :] * jnp.asarray(pos, F32)[:, None]   # (T, rope / 2)
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    assert config["rope_interleave"]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def _attention(config: dict, params: dict, u):
    """MLA over the normed rows ``u (T, d)`` -> ``(output (T, d), c_kv (T, r), k_rope (T, rope))``."""
    T = u.shape[0]
    H = config["num_attention_heads"]
    nope, rope, v, r = (config[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                                             "kv_lora_rank"))
    eps = config["rms_norm_eps"]
    rp = config["rope_parameters"]
    pos = jnp.arange(T)
    q = _linear(_rms_norm(_linear(u, params["attn.q_a.weight"]), params["attn.q_norm.weight"], eps),
                params["attn.q_b.weight"]).reshape(T, H, nope + rope)
    beta = float(rp.get("llama_4_scaling_beta", 0.0))
    q = q * (1.0 + beta * jnp.log1p(jnp.floor(pos / rp["original_max_position_embeddings"])))[:, None, None]
    q_nope, q_rope = q[..., :nope], _rope(config, q[..., nope:], pos)
    ckr = _linear(u, params["attn.kv_a.weight"])
    c_kv = _rms_norm(ckr[:, :r], params["attn.kv_norm.weight"], eps)
    k_rope = _rope(config, ckr[:, r:], pos)
    w_kvb = jnp.asarray(params["attn.kv_b.weight"]).reshape(H, nope + v, r)
    mask = pos[None, :] <= pos[:, None]
    scale = softmax_scale(config)

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


def route(config: dict, params: dict, x):
    """``(chosen (T, k), weights (T, k))`` over all the router's experts."""
    k = config["num_experts_per_tok"]
    logits = _linear(x, params["experts.gate.weight"])
    func = config["assumed"]["scoring_func"]
    if func == "sigmoid":
        s = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(s + _f32(params["experts.e_score_correction_bias"]), k)
    elif func == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(s, k)
    else:
        raise ValueError(f"unknown scoring_func {func!r}")
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if config["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return chosen, w * config["routed_scaling_factor"]


def routed_part(config: dict, params: dict, x):
    """What the experts held here add for ``x (T, d)``: nothing is dropped, and
    what the absent experts would add is left out."""
    lo, hi = experts_held(config)
    chosen, w = route(config, params, x)
    assert params["experts.gate.weight"].shape[0] == n_routed(config), "the router keeps its published width"

    def one_expert(total, args):
        e, w_gate, w_up, w_down = args
        weight = jnp.where(chosen == e, w, 0.0).sum(-1)                       # (T,), 0 where not chosen
        return total + weight[:, None] * _swiglu(x, _f32(w_gate), _f32(w_up), _f32(w_down)), None

    panels = tuple(jnp.asarray(params[f"experts.{n}"]) for n in ("w_gate", "w_up", "w_down"))
    total, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), (jnp.arange(lo, hi), *panels))
    return total


def shared_part(config: dict, params: dict, x):
    if not config["n_shared_experts"]:
        return jnp.zeros_like(x)
    return _swiglu(x, *(_f32(params[f"experts.shared_{n}.weight"]).T for n in ("gate", "up", "down")))


def control(config: dict) -> tuple:
    """``(wrong_config, what_is_wrong)``: a configuration the same weights must
    *not* agree with: every token takes half as many experts, so the weights of
    those it keeps change and the held ones among those it loses fall away."""
    return dict(config, num_experts_per_tok=config["num_experts_per_tok"] // 2), "num_experts_per_tok / 2"


def layer_params(params: dict, index: int, prefix: str = "") -> dict:
    """Layer ``index``'s parameters under the names ``layer`` reads: those below
    ``h.<index>.``."""
    pre = f"{prefix}h.{index}."
    return {name[len(pre):]: p for name, p in params.items() if name.startswith(pre)}


def embed(config: dict, params: dict, tokens, *, prefix: str = ""):
    """``(T, d)`` float32 rows of the embedding table for token ids ``(T,)``."""
    return _f32(jnp.asarray(params[prefix + "wte.weight"])[tokens])


def layer(config: dict, params: dict, x):
    """One block on ``x (T, d)`` with its own parameters (``layer_params``).
    Returns ``(x, made)``, ``made`` the rows a cache would hold of it: ``c_kv (T,
    kv_lora_rank)`` and ``k_rope (T, qk_rope_head_dim)``."""
    with jax.default_matmul_precision("highest"):
        eps = config["rms_norm_eps"]
        a, c_kv, k_rope = _attention(config, params, _rms_norm(x, params["norm_1.weight"], eps))
        x = x + a
        u = _rms_norm(x, params["norm_2.weight"], eps)
        return x + routed_part(config, params, u) + shared_part(config, params, u), \
            {"c_kv": c_kv, "k_rope": k_rope}


def head(config: dict, params: dict, x, *, prefix: str = ""):
    """Logits of the rows ``x (n, d)``; ``lm_head.weight`` may be a block of its rows."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, params[prefix + "ln_f.weight"], config["rms_norm_eps"])
        return _linear(x, params[prefix + "lm_head.weight"])


def forward(config: dict, params: dict, tokens, *, prefix: str = "", rows=None):
    """Logits ``(T, V)`` for one sequence of token ids ``(T,)``; with ``rows``
    only at those positions."""
    if config["model_type"] != "mistral4":
        raise ValueError(f"this reference does not know model_type {config['model_type']!r}")
    x = embed(config, params, tokens, prefix=prefix)
    for i in range(config["num_hidden_layers"]):
        x, _ = layer(config, layer_params(params, i, prefix), x)
    if rows is not None:
        x = x[rows]
    return head(config, params, x, prefix=prefix)


def loss(config: dict, params: dict, tokens, targets, *, prefix: str = ""):
    """Mean next-token cross-entropy of one sequence (the model is served only;
    kept because every reference has one)."""
    logits = forward(config, params, tokens, prefix=prefix)
    logz = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(logz - jnp.take_along_axis(logits, jnp.asarray(targets)[:, None], axis=-1)[:, 0])
