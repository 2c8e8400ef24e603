"""Plain reference for the configurations the ``litgpt`` builder runs.

The forward pass and loss of the published block, in straightforward
``jax.numpy`` and float32 with ``jax.default_matmul_precision("highest")``: no
kernel, no cache, no batching, and nothing imported from ``thunder_tpu``. It
reads the published ``config.json`` keys itself.

Written from the published descriptions:

* ``mistral`` (Jiang et al. 2023; HF ``modeling_mistral``): pre-norm block,
  ``x += attn(rmsnorm(x)); x += mlp(rmsnorm(x))``, grouped-query attention with
  rotary embeddings over the whole head, ``mlp = down(silu(gate(h)) * up(h))``,
  no biases.
* ``gpt_neox`` (Black et al. 2022; HF ``modeling_gpt_neox``): LayerNorm with
  bias, biases on every linear layer, rotary embeddings over the first
  ``rotary_pct`` of each head, and with ``use_parallel_residual`` the block
  ``x += attn(ln1(x)) + mlp(ln2(x))``, ``mlp = out(gelu(in(h)))``.

Rotary embedding, both: for position ``t`` and pair index ``i`` the angle is
``t * base ** (-2 i / n)`` over the ``n`` rotated elements, and the pairs are
(element ``i``, element ``i + n/2``) — the half-split ("rotate_half") form.

Departures, each because the program does the same and ``correct`` is to
judge the compiler and not these choices:

* GELU is the tanh approximation (the published ``hidden_act`` is the exact
  form; ``models/litgpt.py`` has no key for it);
* parameter names and the fused QKV layout are the program's: the QKV weight's
  rows are grouped per key/value head as ``[q_0 .. q_{g-1}, k, v]``, each
  ``head_dim`` rows. With seeded random weights a layout is a convention, not a
  property of the model.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(x):
    return jnp.asarray(x).astype(F32)


def _head_dim(config: dict) -> int:
    return int(config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"])


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _rope(x, n_rot: int, base: float):
    """x: (T, heads, head_dim); rotate the first ``n_rot`` elements of each head."""
    if n_rot <= 0:
        return x
    T = x.shape[0]
    half = n_rot // 2
    inv_freq = 1.0 / (base ** (jnp.arange(0, n_rot, 2, dtype=F32) / n_rot))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]        # (T, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:n_rot], x[..., n_rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _linear(x, params, name):
    y = x @ _f32(params[name + ".weight"]).T
    bias = params.get(name + ".bias")
    return y if bias is None else y + _f32(bias)


def _attention(config, params, pre, h):
    T = h.shape[0]
    nh = config["num_attention_heads"]
    ng = config.get("num_key_value_heads", nh)
    hs = _head_dim(config)
    per = nh // ng
    qkv = _linear(h, params, pre + "attn.attn").reshape(T, ng, per + 2, hs)
    q = qkv[:, :, :per].reshape(T, nh, hs)
    k, v = qkv[:, :, per], qkv[:, :, per + 1]                      # (T, ng, hs)
    if config["model_type"] == "gpt_neox":
        n_rot, base = int(config["rotary_pct"] * hs), float(config["rotary_emb_base"])
    else:
        n_rot, base = hs, float(config["rope_theta"])
    q, k = _rope(q, n_rot, base), _rope(k, n_rot, base)
    k, v = jnp.repeat(k, per, axis=1), jnp.repeat(v, per, axis=1)  # (T, nh, hs)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(hs)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    y = jnp.einsum("hts,shd->thd", probs, v).reshape(T, nh * hs)
    return _linear(y, params, pre + "attn.proj")


def _norm(config, params, name, x):
    if config["model_type"] == "gpt_neox":
        return _layer_norm(x, _f32(params[name + ".weight"]), _f32(params[name + ".bias"]),
                           config["layer_norm_eps"])
    return _rms_norm(x, _f32(params[name + ".weight"]), config["rms_norm_eps"])


def _mlp(config, params, pre, h):
    if config["model_type"] == "gpt_neox":
        return _linear(_gelu_tanh(_linear(h, params, pre + "mlp.fc")), params, pre + "mlp.proj")
    gate, up = _linear(h, params, pre + "mlp.fc_1"), _linear(h, params, pre + "mlp.fc_2")
    return _linear(jax.nn.silu(gate) * up, params, pre + "mlp.proj")


def control(config: dict) -> tuple:
    """``(wrong_config, what_is_wrong)``: a configuration the same weights must
    *not* agree with, for the test that the comparison has teeth: the rope base
    a hundredth of the published one, so every angle past the first pair turns
    at another rate."""
    key = "rotary_emb_base" if config["model_type"] == "gpt_neox" else "rope_theta"
    return dict(config, **{key: config[key] / 100.0}), f"{key} / 100"


def forward(config: dict, params: dict, tokens, *, prefix: str = "", rows=None):
    """Logits ``(T, vocab)`` of one sequence of token ids ``(T,)``; with
    ``rows`` (an index array) only those positions' logits."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params[prefix + "wte.weight"])[tokens]
        for layer in range(config["num_hidden_layers"]):
            pre = f"{prefix}h.{layer}."
            attn = _attention(config, params, pre, _norm(config, params, pre + "norm_1", x))
            if config.get("use_parallel_residual", False):
                x = x + attn + _mlp(config, params, pre, _norm(config, params, pre + "norm_2", x))
            else:
                x = x + attn
                x = x + _mlp(config, params, pre, _norm(config, params, pre + "norm_2", x))
        x = _norm(config, params, prefix + "ln_f", x)
        if rows is not None:
            x = x[rows]
        return _linear(x, params, prefix + "lm_head")


def loss(config: dict, params: dict, tokens, targets, *, prefix: str = ""):
    """Mean cross-entropy of one sequence against ``targets`` ``(T,)``."""
    logits = forward(config, params, tokens, prefix=prefix)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))
