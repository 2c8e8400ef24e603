"""Hand-written plain-JAX GPT training step — the honest benchmark baseline.

The reference's headline compares thunder against PyTorch eager
(reference README.md:23); on TPU the competitor a user would actually write
is a straight ``jax.jit`` program. This module implements the same LitGPT
``Config`` model (models/litgpt.py) directly in jax.numpy — no thunder_tpu
IR, no executors, no transforms — with the standard mixed-precision recipe
(fp32 master weights, bf16 compute) and a fused AdamW step, jit-compiled
with donation. ``bench.py``'s ``vs_baseline`` is thunder_tpu ÷ this.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------------
# parameter init (mirrors nn.Linear / nn.Embedding defaults in nn/module.py)
# --------------------------------------------------------------------------


def init_params(cfg, seed: int = 0, dtype=jnp.float32) -> dict:
    rng = np.random.RandomState(seed)

    def linear(key, fan_in, fan_out, bias):
        bound = 1.0 / math.sqrt(fan_in)
        p = {f"{key}.weight": jnp.asarray(
            rng.uniform(-bound, bound, (fan_out, fan_in)), dtype)}
        if bias:
            p[f"{key}.bias"] = jnp.asarray(rng.uniform(-bound, bound, (fan_out,)), dtype)
        return p

    def norm(key):
        p = {f"{key}.weight": jnp.ones((cfg.n_embd,), dtype)}
        if cfg.norm_class_name == "LayerNorm":
            p[f"{key}.bias"] = jnp.zeros((cfg.n_embd,), dtype)
        return p

    params: dict[str, Any] = {
        # N(0,1): the torch.nn.Embedding default, matching nn/module.py so
        # both bench phases train the same model
        "wte.weight": jnp.asarray(
            rng.randn(cfg.padded_vocab_size, cfg.n_embd), dtype),
    }
    qkv_out = (cfg.n_head + 2 * cfg.n_query_groups) * cfg.head_size
    for i in range(cfg.n_layer):
        b = f"h.{i}"
        params.update(norm(f"{b}.norm_1"))
        params.update(linear(f"{b}.attn.attn", cfg.n_embd, qkv_out, cfg.bias))
        params.update(linear(f"{b}.attn.proj", cfg.n_head * cfg.head_size, cfg.n_embd, cfg.bias))
        params.update(norm(f"{b}.norm_2"))
        if cfg.mlp_class_name == "LLaMAMLP":
            params.update(linear(f"{b}.mlp.fc_1", cfg.n_embd, cfg.intermediate_size, cfg.bias))
            params.update(linear(f"{b}.mlp.fc_2", cfg.n_embd, cfg.intermediate_size, cfg.bias))
            params.update(linear(f"{b}.mlp.proj", cfg.intermediate_size, cfg.n_embd, cfg.bias))
        else:
            params.update(linear(f"{b}.mlp.fc", cfg.n_embd, cfg.intermediate_size, cfg.bias))
            params.update(linear(f"{b}.mlp.proj", cfg.intermediate_size, cfg.n_embd, cfg.bias))
    params.update(norm("ln_f"))
    params.update(linear("lm_head", cfg.n_embd, cfg.padded_vocab_size, cfg.lm_head_bias))
    return params


def rope_cache(cfg, dtype=jnp.float32):
    n_elem = cfg.rope_n_elem
    if n_elem <= 0:
        z = jnp.zeros((cfg.block_size, 0), dtype)
        return z, z
    theta = 1.0 / (cfg.rope_base ** (jnp.arange(0, n_elem, 2, dtype=jnp.float32) / n_elem))
    idx = jnp.outer(jnp.arange(cfg.block_size, dtype=jnp.float32), theta)
    idx = jnp.concatenate([idx, idx], -1)
    return jnp.cos(idx).astype(dtype), jnp.sin(idx).astype(dtype)


# --------------------------------------------------------------------------
# forward (bf16 compute, f32 norms/softmax/loss — same policy as autocast)
# --------------------------------------------------------------------------


def _norm_f(cfg, x, w, b, eps):
    x32 = x.astype(jnp.float32)
    if cfg.norm_class_name == "RMSNorm":
        out = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps) * w
    else:
        mu = jnp.mean(x32, -1, keepdims=True)
        var = jnp.mean((x32 - mu) ** 2, -1, keepdims=True)
        out = (x32 - mu) * jax.lax.rsqrt(var + eps) * w + b
    return out


def _rope(x, cos, sin, n_elem):
    if n_elem <= 0:
        return x
    rot = x[..., :n_elem]
    x1, x2 = rot[..., : n_elem // 2], rot[..., n_elem // 2:]
    roped = rot * cos + jnp.concatenate([-x2, x1], -1) * sin
    if n_elem < x.shape[-1]:
        return jnp.concatenate([roped, x[..., n_elem:]], -1)
    return roped


def forward(cfg, params, idx, targets, cos, sin, compute_dtype=jnp.bfloat16):
    B, T = idx.shape
    nh, ng, hs = cfg.n_head, cfg.n_query_groups, cfg.head_size
    q_per_kv = nh // ng

    def w(k):
        return params[k].astype(compute_dtype)

    cos_t, sin_t = cos[:T], sin[:T]
    x = w("wte.weight")[idx]
    use_ckpt = bool(getattr(cfg, "activation_checkpoint", False))
    for i in range(cfg.n_layer):
        blk = f"h.{i}"
        body = functools.partial(_block_body, cfg, params, blk, w, cos_t, sin_t,
                                 compute_dtype, B, T)
        x = jax.checkpoint(body)(x) if use_ckpt else body(x)
    x = _norm_f(cfg, x, params["ln_f.weight"], params.get("ln_f.bias"),
                cfg.norm_eps).astype(compute_dtype)
    logits = x @ w("lm_head.weight").T
    if "lm_head.bias" in params:
        logits = logits + w("lm_head.bias")
    logits = logits.reshape(B * T, -1).astype(jnp.float32)
    tgt = targets.reshape(B * T)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tgt[:, None], 1)[:, 0]
    return jnp.mean(lse - picked)


def _block_body(cfg, params, blk, w, cos_t, sin_t, compute_dtype, B, T, x):
    nh, ng, hs = cfg.n_head, cfg.n_query_groups, cfg.head_size
    q_per_kv = nh // ng
    h = _norm_f(cfg, x, params[f"{blk}.norm_1.weight"],
                params.get(f"{blk}.norm_1.bias"), cfg.norm_eps).astype(compute_dtype)
    qkv = h @ w(f"{blk}.attn.attn.weight").T
    if f"{blk}.attn.attn.bias" in params:
        qkv = qkv + w(f"{blk}.attn.attn.bias")
    qkv = qkv.reshape(B, T, ng, q_per_kv + 2, hs)
    q = qkv[:, :, :, :q_per_kv].reshape(B, T, nh, hs).transpose(0, 2, 1, 3)
    k = qkv[:, :, :, q_per_kv: q_per_kv + 1].reshape(B, T, ng, hs).transpose(0, 2, 1, 3)
    v = qkv[:, :, :, q_per_kv + 1:].reshape(B, T, ng, hs).transpose(0, 2, 1, 3)
    q = _rope(q, cos_t, sin_t, cfg.rope_n_elem)
    k = _rope(k, cos_t, sin_t, cfg.rope_n_elem)
    if ng != nh:
        k = jnp.repeat(k, q_per_kv, axis=1)
        v = jnp.repeat(v, q_per_kv, axis=1)
    # the attention a jax user writes today: jax's library pallas flash
    # kernel where the composite would materialize B·H·T² probabilities for
    # backward (OOM at llama-350m B=4 T=2048 on one 16 GB chip), the fused
    # composite elsewhere
    score_bytes = B * nh * T * T * 2
    big_attention = T >= 4096 or (T >= 2048 and score_bytes >= 256 * 2**20)
    if big_attention and T % 128 == 0 and hs >= 64:
        from jax.experimental.pallas.ops.tpu.flash_attention import flash_attention

        y = flash_attention(q.astype(compute_dtype), k.astype(compute_dtype),
                            v.astype(compute_dtype), causal=True,
                            sm_scale=1.0 / math.sqrt(hs))
        y = y.transpose(0, 2, 1, 3).reshape(B, T, nh * hs)
    else:
        # rope promotes q/k to f32 (f32 cos/sin); the composite requires
        # uniform dtypes
        y = jax.nn.dot_product_attention(
            q.astype(compute_dtype).transpose(0, 2, 1, 3),
            k.astype(compute_dtype).transpose(0, 2, 1, 3),
            v.astype(compute_dtype).transpose(0, 2, 1, 3),
            scale=1.0 / math.sqrt(hs), is_causal=True)
        y = y.reshape(B, T, nh * hs)
    y = y @ w(f"{blk}.attn.proj.weight").T
    if f"{blk}.attn.proj.bias" in params:
        y = y + w(f"{blk}.attn.proj.bias")
    if cfg.parallel_residual:
        h2 = _norm_f(cfg, x, params[f"{blk}.norm_2.weight"],
                     params.get(f"{blk}.norm_2.bias"), cfg.norm_eps).astype(compute_dtype)
        x = x + y + _mlp(cfg, params, blk, h2, w)
    else:
        x = x + y
        h2 = _norm_f(cfg, x, params[f"{blk}.norm_2.weight"],
                     params.get(f"{blk}.norm_2.bias"), cfg.norm_eps).astype(compute_dtype)
        x = x + _mlp(cfg, params, blk, h2, w)
    return x


def _mlp(cfg, params, blk, h, w):
    if cfg.mlp_class_name == "LLaMAMLP":
        a = h @ w(f"{blk}.mlp.fc_1.weight").T
        b = h @ w(f"{blk}.mlp.fc_2.weight").T
        return (jax.nn.silu(a) * b) @ w(f"{blk}.mlp.proj.weight").T
    a = h @ w(f"{blk}.mlp.fc.weight").T
    if f"{blk}.mlp.fc.bias" in params:
        a = a + w(f"{blk}.mlp.fc.bias")
    out = jax.nn.gelu(a, approximate=True) @ w(f"{blk}.mlp.proj.weight").T
    if f"{blk}.mlp.proj.bias" in params:
        out = out + w(f"{blk}.mlp.proj.bias")
    return out


# --------------------------------------------------------------------------
# AdamW (same formula as thunder_tpu.optim.AdamW) + jitted step
# --------------------------------------------------------------------------


def adamw_init(params):
    return {
        "step": jnp.zeros((), jnp.int32),
        "m": jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        "v": jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
    }


def adamw_update(params, grads, state, lr=1e-4, beta1=0.9, beta2=0.999,
                 eps=1e-8, weight_decay=0.01):
    step = state["step"] + 1
    t = step.astype(jnp.float32)
    bc1, bc2 = 1.0 - beta1**t, 1.0 - beta2**t

    def upd(p, g, m, v):
        g32 = g.astype(jnp.float32)
        m2 = beta1 * m + (1 - beta1) * g32
        v2 = beta2 * v + (1 - beta2) * g32 * g32
        p32 = p.astype(jnp.float32)
        p32 = p32 - lr * weight_decay * p32
        p32 = p32 - lr * (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
        return p32.astype(p.dtype), m2, v2

    out = {k: upd(params[k], grads[k], state["m"][k], state["v"][k]) for k in params}
    return ({k: o[0] for k, o in out.items()},
            {"step": step,
             "m": {k: o[1] for k, o in out.items()},
             "v": {k: o[2] for k, o in out.items()}})


def make_train_step(cfg, lr=1e-4, compute_dtype=jnp.bfloat16):
    cos, sin = rope_cache(cfg)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, idx, targets):
        loss, grads = jax.value_and_grad(
            lambda p: forward(cfg, p, idx, targets, cos, sin, compute_dtype))(params)
        params, opt_state = adamw_update(params, grads, opt_state, lr=lr)
        return loss, params, opt_state

    return step
