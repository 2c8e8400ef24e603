"""Plain reference for the configurations the ``block_moe`` builder runs: SDAR's mixture-of-experts
chat models (``model_type`` ``sdar_moe``, JetLM 2025; the layer is Qwen3-MoE's), which generate by
DIFFUSION OVER BLOCKS.

The forward pass and the generation loop in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: one sequence at a time, an explicit T x T mask, the
experts one at a time over every token with the others' rows masked, no cache, no pages, no
kernel, no batching, and nothing imported from ``thunder_tpu``. It reads the published
``config.json`` keys itself and, from the file's ``generation`` and ``assumed`` groups, what that
file has no key for.

Written from these equations. A sequence is its prompt followed by blocks of ``K =
generation.block_length`` positions; position ``t`` lies in block ``b(t) = floor(t / K)``. ``N``
is RMSNorm with eps ``rms_norm_eps`` and a learned weight, no biases:

* layer, input ``X (T, hidden_size)``: ``h = N1(X)``; ``Q = h Wq`` (``num_attention_heads`` heads
  of ``head_dim``), ``Kk = h Wk``, ``V = h Wv`` (``num_key_value_heads`` heads); every head of
  ``Q`` and of ``Kk`` through an RMSNorm over its ``head_dim`` columns with a learned weight
  (Qwen3's ``q_norm`` / ``k_norm``; ``assumed.qk_norm``), before rope; rope at base
  ``rope_theta`` over the whole head, pairs (element ``i``, element ``i + head_dim / 2``), at the
  TRUE position ``t``; ``A = softmax(Q Kk^T / sqrt(head_dim) + M)`` with ``num_attention_heads /
  num_key_value_heads`` query heads a key head and ``M[t, s] = 0`` where ``b(s) <= b(t)``, else
  ``-inf`` (BLOCK-causal: a position sees all earlier blocks and the whole of its own);
  ``X' = X + (A V) Wo``; ``u = N2(X')``; ``r = softmax(u Wr)`` over ``num_experts`` in float32;
  ``S`` = the ``num_experts_per_tok`` largest; ``g_e = r_e / sum_S r`` (``norm_topk_prob``);
  ``Y = X' + sum_{e in S} g_e W_down^e (silu(W_gate^e u) * W_up^e u)``. After the last layer an
  RMSNorm and an untied head. The logits at position ``t`` are for the token AT ``t`` (no shift).
* generation of one block (``generate``; JetLM/SDAR's public ``generate.py``,
  ``block_diffusion_generate``, AS REMEMBERED: no copy is on this machine, so every point is
  also under ``assumed`` in the configuration file): the prompt's whole blocks are context; its
  last ``L mod K`` tokens open the first generated block as given tokens and every other position
  of the block starts as ``mask_token_id``. A DENOISE pass runs the sequence up to the block's end
  (the block's own rows see the mask tokens' embeddings), takes at each masked position ``x0 =
  argmax`` over every token but the mask token (a position is never filled with it: its logit is
  left out, ``assumed.mask_token_is_no_candidate``) and its confidence ``c = softmax(logits)[x0]``,
  and unmasks: ``low_confidence_static``
  the ``n = ceil(K / denoising_steps)`` masked positions of largest ``c``;
  ``low_confidence_dynamic`` every masked position with ``c > confidence_threshold`` and, where
  those are fewer than ``n``, the ``n`` of largest ``c`` instead. Ties go to the lower position.
  When no position is masked the block is finished: ONE MORE pass over the finished block is the
  one whose keys and values a cache would keep (they differ from any denoise pass's: those saw
  mask embeddings), and the next block begins. Greedy only (temperature 0).

Departures, each because the program does the same and ``correct`` is to judge the compiler and
the engine, not these choices: parameter names and layouts are the program's (the fused QKV
weight's rows grouped per key/value head as ``[q_0 .. q_{g-1}, k, v]``; expert panels ``(E, d,
w)``, ``(E, w, d)``); with seeded random weights a layout is a convention. ``n`` is the same in
every pass (the published schedule hands a remainder of ``K / denoising_steps`` to the first
passes; the cell's 4 / 2 has none). The router's ``e_score_correction_bias`` the program's expert
layer carries is zero for this family and is not read.

How it is cut into blocks of work. ``forward`` is ``embed``, then ``layer`` for every layer, then
``head``; the three are exported with ``layer_params`` so that a caller short of memory runs them
one at a time on bfloat16 weights (a layer's experts are 1.2 GB in bfloat16 at the published
widths): inside a layer the experts go one at a time through ``lax.scan``, each cast to float32
for its own turn. ``layer`` also hands back the keys it computed (after the head norm and rope),
for the comparison with the rows a served model keeps.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(x):
    return jnp.asarray(x).astype(F32)


def block_length(config: dict) -> int:
    return int(config["generation"]["block_length"])


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def _rope(x, base: float):
    """x (T, heads, head_dim) at positions 0 .. T - 1, the whole head rotated, half-split pairs."""
    T, _, hd = x.shape
    half = hd // 2
    inv_freq = 1.0 / (base ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(config, p, h):
    """``(what attention adds (T, d), the keys (T, kv heads, head_dim) normed and roped)``."""
    T = h.shape[0]
    nh, ng, hd = config["num_attention_heads"], config["num_key_value_heads"], config["head_dim"]
    per = nh // ng
    qkv = (h @ _f32(p["attn.attn.weight"]).T).reshape(T, ng, per + 2, hd)
    q = qkv[:, :, :per].reshape(T, nh, hd)
    k, v = qkv[:, :, per], qkv[:, :, per + 1]
    if config["assumed"]["qk_norm"]:
        eps = config["rms_norm_eps"]
        q, k = _rms_norm(q, p["attn.norm_q.weight"], eps), _rms_norm(k, p["attn.norm_k.weight"], eps)
    base = float(config["rope_theta"])
    q, k = _rope(q, base), _rope(k, base)
    blk = jnp.arange(T) // block_length(config)
    seen = blk[:, None] >= blk[None, :]
    scores = jnp.einsum("thd,shd->hts", q, jnp.repeat(k, per, axis=1)) / math.sqrt(hd)
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    y = jnp.einsum("hts,shd->thd", probs, jnp.repeat(v, per, axis=1)).reshape(T, nh * hd)
    return y @ _f32(p["attn.proj.weight"]).T, k


def _experts(config, p, u):
    """What the routed experts add for the tokens ``u (T, d)``."""
    k = config["num_experts_per_tok"]
    router = p["experts.gate.weight"]
    if config["assumed"]["router_dtype"] != "float32":  # the control of a router in a narrower type
        narrow = jnp.dtype(config["assumed"]["router_dtype"])
        r = jax.nn.softmax((u.astype(narrow) @ jnp.asarray(router).astype(narrow).T).astype(F32), axis=-1)
    else:
        r = jax.nn.softmax(u @ _f32(router).T, axis=-1)
    top, idx = jax.lax.top_k(r, k)
    g = top / jnp.sum(top, axis=-1, keepdims=True) if config["norm_topk_prob"] else top
    weight = jnp.zeros_like(r).at[jnp.arange(u.shape[0])[:, None], idx].set(g)   # (T, E)

    def one(acc, e):
        w_gate, w_up, w_down, g_e = e
        y = (jax.nn.silu(u @ _f32(w_gate)) * (u @ _f32(w_up))) @ _f32(w_down)
        return acc + g_e[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (p["experts.w_gate"], p["experts.w_up"], p["experts.w_down"], weight.T))
    return out


def layer_params(params: dict, i: int, prefix: str = "") -> dict:
    pre = f"{prefix}h.{i}."
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def embed(config: dict, p: dict, tokens):
    return _f32(p["wte.weight"])[tokens]


def layer(config: dict, p: dict, x):
    """One layer over ``x (T, d)`` under the block-causal mask: ``(y, {"k": keys})``."""
    with jax.default_matmul_precision("highest"):
        eps = config["rms_norm_eps"]
        a, keys = _attention(config, p, _rms_norm(x, p["norm_1.weight"], eps))
        x = x + a
        return x + _experts(config, p, _rms_norm(x, p["norm_2.weight"], eps)), {"k": keys}


def head(config: dict, p: dict, x):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, p["ln_f.weight"], config["rms_norm_eps"]) @ _f32(p["lm_head.weight"]).T


def forward(config: dict, params: dict, tokens, *, prefix: str = "", rows=None):
    """Logits ``(T, vocab)`` of one sequence of token ids ``(T,)`` under the block-causal mask;
    with ``rows`` (an index array) only those positions' logits."""
    x = embed(config, {"wte.weight": params[prefix + "wte.weight"]}, tokens)
    for i in range(config["num_hidden_layers"]):
        x, _ = layer(config, layer_params(params, i, prefix), x)
    if rows is not None:
        x = x[rows]
    return head(config, {k: params[prefix + k] for k in ("ln_f.weight", "lm_head.weight")}, x)


def loss(config: dict, params: dict, tokens, targets, *, prefix: str = ""):
    """Mean cross-entropy of one sequence's logits against ``targets`` (the token AT each
    position: no shift). The model is served only; kept because the harness asks every reference
    for one."""
    logits = forward(config, params, tokens, prefix=prefix)
    logz = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(logz - jnp.take_along_axis(logits, jnp.asarray(targets)[:, None], axis=-1)[:, 0])


def replay(config: dict, params: dict, prompt, states) -> dict:
    """For a recorded list of one request's block states (``(first position, tokens (K,), masked
    flags (K,))`` going INTO each pass, as ``generate`` returns them and a served request
    records them): ``{"logits": [the block's rows' logits (K, vocab) a pass], "keys": the last
    layer's keys over the finished sequence}``: what each commit pass would cache (under the
    block-causal mask the finished sequence's keys at a block ARE those its commit pass
    computed). By full forward passes, one a pass. ``benchmark/drivers/serve_denoise.py`` runs the
    same walk a layer at a time on bfloat16 weights at the published widths."""
    K = block_length(config)
    seq = [int(t) for t in prompt][:len(prompt) // K * K]
    logits = []
    for pos, toks, masked in states:
        toks = [int(t) for t in toks]
        logits.append(np.asarray(forward(config, params, jnp.asarray(seq + toks, jnp.int32)))[pos:pos + K])
        if not np.asarray(masked).any():
            seq += toks
    x = embed(config, {"wte.weight": params["wte.weight"]}, jnp.asarray(seq, jnp.int32))
    for i in range(config["num_hidden_layers"]):
        x, made = layer(config, layer_params(params, i), x)
    return {"logits": logits, "keys": np.asarray(made["k"])}


def control(config: dict) -> tuple:
    """``(wrong_config, what_is_wrong)``: the same weights under a block length of 1, which is
    plain causal attention: a position no longer sees the later positions of its own block, so
    the same weights must NOT agree."""
    return dict(config, generation=dict(config["generation"], block_length=1)), "block_length 1 (plain causal)"


# -- generation --------------------------------------------------------------------------------

def choose(conf, masked, spec: dict):
    """Which masked positions of a block a denoise pass fills: ``conf (K,)`` the confidence of
    each position's candidate, ``masked (K,)`` bool. Returns (K,) bool."""
    conf, masked = np.asarray(conf, np.float64), np.asarray(masked, bool)
    K = len(conf)
    n = min(-(-K // int(spec["denoising_steps"])), int(masked.sum()))
    c = np.where(masked, conf, -np.inf)
    best = np.zeros(K, bool)
    best[np.argsort(-c, kind="stable")[:n]] = True
    if spec["remasking_strategy"] == "low_confidence_static":
        return best
    if spec["remasking_strategy"] != "low_confidence_dynamic":
        raise ValueError(f"unknown remasking_strategy {spec['remasking_strategy']!r}")
    high = masked & (conf > float(spec["confidence_threshold"]))
    return high if high.sum() >= n else best


def generate(config: dict, params: dict, prompt, n_new: int, *, fn=None) -> dict:
    """The published loop for one sequence by full forward passes, greedy: ``{"tokens": the
    first n_new generated tokens, "order": [(position, pass)] in the order the positions were
    filled (within a pass by position; passes numbered from 0 over the whole request, commit
    passes included), "states": [(first position of the block, its K tokens going INTO the
    pass, its K masked flags)] a pass, "passes": their number}``. ``fn(tokens) -> logits``
    replaces the full forward (a jitted one of fixed length: the caller pads)."""
    spec = config["generation"]
    K, mask_id = int(spec["block_length"]), int(spec["mask_token_id"])
    prompt = [int(t) for t in prompt]
    L = len(prompt)
    run = fn or (lambda toks: np.asarray(forward(config, params, jnp.asarray(toks, jnp.int32))))
    seq = prompt[:(L // K) * K]
    given = prompt[len(seq):]
    out, order, states, n_pass = [], [], [], 0
    while len(out) < n_new:
        start = len(seq)
        block = given + [mask_id] * (K - len(given))
        masked = np.array([False] * len(given) + [True] * (K - len(given)))
        while True:
            states.append((start, list(block), masked.copy()))
            logits = run(seq + block)[start:start + K]
            commit = not masked.any()
            n_pass += 1
            if commit:
                break
            logits = np.asarray(logits, np.float64)
            logits[:, mask_id] = -np.inf  # the mask token is no candidate
            x0 = logits.argmax(-1)
            z = np.exp(logits - logits.max(-1, keepdims=True))
            conf = (z / z.sum(-1, keepdims=True))[np.arange(K), x0]
            fill = choose(conf, masked, spec)
            for j in np.flatnonzero(fill):
                block[j] = int(x0[j])
                order.append((start + int(j), n_pass - 1))
            masked &= ~fill
        out.extend(block[len(given):])
        seq = seq + block
        given = []
    return {"tokens": out[:n_new], "order": order, "states": states, "passes": n_pass}
