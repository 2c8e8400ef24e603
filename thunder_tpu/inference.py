"""Inference engine: KV-cached autoregressive generation.

Capability counterpart of the reference's inference stack
(thunder/benchmarks/benchmark_inference.py:1-11: throughput, ms/token, TTFT,
TBOT; HF generate via thunder.jit + CUDA graphs). TPU-native design:

  - static shapes: the KV cache is a fixed (B, H, max_seq, D) buffer updated
    with dynamic_update_slice; prefill and decode are two cached trace
    specializations (the role CUDA graphs play in the reference is played by
    XLA whole-program compilation — each decode step is ONE dispatch).
  - the decode step is compiled once and reused for every token.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Optional

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

_NULL = contextlib.nullcontext()

from . import nn
from .observability import events as _obs
from .observability import flight_recorder as _obs_flight
from .observability import runtime as _obs_runtime
from .observability import telemetry as _obs_tel
from .ops import clang, ltorch


@dataclass
class GenerationMetrics:
    """TTFT/TBOT/throughput, mirroring the reference harness metrics."""

    ttft_s: float = 0.0
    tbot_s: float = 0.0
    tokens_per_sec: float = 0.0
    ms_per_token: float = 0.0
    n_new_tokens: int = 0


class KVCache:
    """Per-layer static-shape KV cache."""

    def __init__(self, n_layer: int, batch: int, n_kv_heads: int, max_seq: int, head_dim: int, dtype=jnp.bfloat16):
        shape = (batch, n_kv_heads, max_seq, head_dim)
        self.k = [jnp.zeros(shape, dtype) for _ in range(n_layer)]
        self.v = [jnp.zeros(shape, dtype) for _ in range(n_layer)]

    def as_tuple(self):
        return tuple(self.k), tuple(self.v)


def cached_sdpa(q, k_cache, v_cache, pos, scale=None):
    """Attention against the cache prefix [0, pos+q_len); pos may be a traced
    scalar so the same compiled decode step serves every position."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    kt = clang.matrix_transpose(k_cache)
    scores = ltorch.matmul(q, kt) * scale
    Lq = q.shape[-2]
    Lk = k_cache.shape[-2]
    import jax.numpy as _jnp

    q_pos = clang.ensure_proxy(_jnp.arange(Lq, dtype=_jnp.int32))
    if isinstance(pos, int):
        q_pos = q_pos + pos
    else:
        q_pos = q_pos + ltorch.reshape(pos, (1,))
    k_pos = clang.ensure_proxy(_jnp.arange(Lk, dtype=_jnp.int32))
    mask = ltorch.le(clang.unsqueeze(k_pos, 0), clang.unsqueeze(q_pos, 1))
    scores = ltorch.where(mask, scores, float("-inf"))
    probs = ltorch.softmax(scores, -1)
    probs = clang.maybe_convert_to_dtype(probs, v_cache.dtype)
    return ltorch.matmul(probs, v_cache)


def split_qkv_rope(block, cfg, x_n, cos, sin):
    """Project + split + rope one block's q/k/v for T tokens: the per-block
    attention-input plumbing shared by the dense decode engine below and the
    paged serving runner (serving/runner.py) — ONE implementation, so block
    math can never drift between solo and continuously-batched decoding
    (the serving tests pin exact token equality between the two).
    Returns q (B, nh, T, hs), k/v (B, ng, T, hs)."""
    from .models.litgpt import _apply_rope, norm_qk

    B, T, _ = x_n.shape
    nh, ng, hs = cfg.n_head, cfg.n_query_groups, cfg.head_size
    q_per_kv = nh // ng
    qkv = block.attn.attn(x_n)
    qkv = ltorch.reshape(qkv, (B, T, ng, q_per_kv + 2, hs))
    q = ltorch.reshape(qkv[:, :, :, :q_per_kv, :], (B, T, nh, hs))
    k = ltorch.reshape(qkv[:, :, :, q_per_kv: q_per_kv + 1, :], (B, T, ng, hs))
    v = ltorch.reshape(qkv[:, :, :, q_per_kv + 1:, :], (B, T, ng, hs))
    q, k = norm_qk(block.attn, q, k)
    q = ltorch.permute(q, (0, 2, 1, 3))
    k = ltorch.permute(k, (0, 2, 1, 3))
    v = ltorch.permute(v, (0, 2, 1, 3))
    q = _apply_rope(q, cos, sin, cfg.rope_n_elem)
    k = _apply_rope(k, cos, sin, cfg.rope_n_elem)
    return q, k, v


class GPTInference:
    """Greedy/temperature generation over a models.litgpt.GPT or
    models.moe.MoEGPT (Mixtral-style MoE decoder).

    The model's sdpa path is swapped for cache-aware attention by running the
    blocks manually (the GPT module structure is reused; no retracing of the
    whole prefix per token)."""

    def __init__(self, gpt, *, max_seq: Optional[int] = None, dtype=jnp.bfloat16):
        from . import jit as _jit

        self.gpt = gpt
        cfg = gpt.cfg
        self.cfg = cfg
        self.max_seq = max_seq or cfg.block_size
        self.dtype = dtype
        self._decode_cfn = None
        self._prefill_cfn = None

    # --- functional single-step over the module tree ---
    def _forward_cached(self, idx, ks, vs, pos):
        """idx (B, T); ks/vs per-layer cache tuples; pos: start position —
        either a python int (prefill) or a scalar int32 tensor (decode, so one
        compiled decode step serves every position)."""
        from .core import prims

        cfg = self.cfg
        gpt = self.gpt
        B, T = idx.shape
        n_elem = cfg.rope_n_elem
        cos_full = clang.ensure_proxy(gpt.cos)
        sin_full = clang.ensure_proxy(gpt.sin)
        cos = prims.dynamic_slice(cos_full, (pos, 0), (T, n_elem))
        sin = prims.dynamic_slice(sin_full, (pos, 0), (T, n_elem))
        x = gpt.wte(idx)
        new_ks, new_vs = [], []
        nh, ng = cfg.n_head, cfg.n_query_groups
        q_per_kv = nh // ng
        for li, block in enumerate(gpt.h):
            from .models.litgpt import _repeat_kv

            q, k, v = split_qkv_rope(block, cfg, block.norm_1(x), cos, sin)
            # insert into cache at pos
            k_cache = prims.dynamic_update_slice(ks[li], k, (0, 0, pos, 0))
            v_cache = prims.dynamic_update_slice(vs[li], v, (0, 0, pos, 0))
            new_ks.append(k_cache)
            new_vs.append(v_cache)
            kq = _repeat_kv(k_cache, q_per_kv) if ng != nh else k_cache
            vq = _repeat_kv(v_cache, q_per_kv) if ng != nh else v_cache
            y = cached_sdpa(q, kq, vq, pos)
            y = ltorch.reshape(ltorch.permute(y, (0, 2, 1, 3)), (B, T, nh * cfg.head_size))
            x = block.tail(x, block.attn.proj(y))
        x = gpt.ln_f(x)
        logits = gpt.lm_head(x[:, -1])  # only last position needed for generation
        return logits, tuple(new_ks), tuple(new_vs)

    def _build(self, B: int, prompt_len: int):
        from . import jit as _jit
        from .nn.module import functional_params

        gpt = self.gpt
        cfg = self.cfg

        def prefill(params, idx, ks, vs):
            with functional_params(gpt, params):
                return self._forward_cached(idx, ks, vs, 0)

        def decode(params, idx, ks, vs, pos):
            with functional_params(gpt, params):
                return self._forward_cached(idx, ks, vs, pos)

        prefill.__name__ = "prefill"
        decode.__name__ = "decode"
        self._prefill_cfn = _jit(prefill)
        self._decode_cfn = _jit(decode)

    def _build_scan_decode(self, n_steps: int):
        """Compile the WHOLE greedy decode loop into one XLA program via
        lax.scan over the compiled decode step (the role CUDA graphs play in
        the reference: per-token dispatch overhead drops to zero — one
        dispatch generates all n_steps tokens). The compiled decode entry is
        traceable because its generated prologue/computation are pure jax."""
        decode = self._decode_cfn

        def scan_decode(params, first_tok, ks, vs, start_pos):
            def step(carry, _):
                tok, ks, vs, pos = carry
                logits, ks, vs = decode(params, tok[:, None], ks, vs, pos)
                nxt = jnp.argmax(logits, -1).astype(tok.dtype)
                return (nxt, ks, vs, pos + 1), nxt

            (last, ks, vs, _), toks = jax.lax.scan(
                step, (first_tok, ks, vs, jnp.asarray(start_pos, jnp.int32)),
                None, length=n_steps)
            return toks, ks, vs  # toks: (n_steps, B)

        self._scan_jitted = jax.jit(scan_decode, static_argnames=())
        self._scan_steps = n_steps
        return self._scan_jitted

    _scan_jitted = None
    _scan_steps = None
    _scan_sig = None

    def generate(self, prompt, max_new_tokens: int = 32, *, temperature: float = 0.0,
                 seed: Optional[int] = None, collect_metrics: bool = False,
                 scan_decode: bool = True):
        """prompt: (B, T) int array. Returns (tokens (B, T+max_new), metrics).

        scan_decode=True (greedy only): all decode steps compile into one XLA
        program — one dispatch for the whole generation.

        seed keys temperature sampling: the token at position p draws from
        fold_in(PRNGKey(seed), p), so two generations with the same seed are
        identical and the stream matches the serving engine's
        (serving/scheduler.py) for the same request seed."""
        cfg = self.cfg
        B, T = prompt.shape
        if T + max_new_tokens > self.max_seq:
            # an overlong generation would let dynamic_update_slice clamp its
            # writes at the cache edge, silently corrupting the KV tail —
            # refuse up front instead
            raise ValueError(
                f"prompt_len={T} + max_new_tokens={max_new_tokens} exceeds "
                f"max_seq={self.max_seq}; build the engine with a larger "
                f"max_seq (or shorten the generation)")
        if self._decode_cfn is None:
            self._build(B, T)
        # seeds are canonicalized mod 2^32 so the stream matches the serving
        # engine's (whose packed seed array is uint32) for any Python int
        sample_key = jax.random.PRNGKey(
            (seed if seed is not None else 0) & 0xFFFFFFFF)
        # raw arrays: Parameter wrappers don't abstract under the jitted scan
        params = {k: p.data for k, p in self.gpt.named_parameters()}
        cache = KVCache(cfg.n_layer, B, cfg.n_query_groups, self.max_seq, cfg.head_size, self.dtype)
        ks, vs = cache.as_tuple()

        # one enabled() read gates the per-request observability (span +
        # flight-recorder records); disabled mode adds zero work here
        obs_on = _obs.enabled()
        t_start = time.perf_counter()
        with _obs_runtime.step_span("infer_prefill", B=B, T=T) if obs_on else _NULL:
            logits, ks, vs = self._prefill_cfn(params, prompt, ks, vs)
            if temperature > 0.0:
                next_tok = jax.random.categorical(
                    jax.random.fold_in(sample_key, T),
                    logits / temperature, -1).astype(prompt.dtype)
            else:
                next_tok = jnp.argmax(logits, -1).astype(prompt.dtype)
            jax.block_until_ready(next_tok)
        ttft = time.perf_counter() - t_start
        if obs_on:
            _obs_flight.record_step(ttft * 1e3, fn="infer_prefill", B=B, T=T)
            _obs_tel.observe("infer.ttft_ms", ttft * 1e3)

        n_steps = max_new_tokens - 1
        use_scan = scan_decode and temperature == 0.0 and n_steps > 0
        t_decode = time.perf_counter()
        if use_scan:
            sig = (n_steps, B, str(next_tok.dtype))
            if self._scan_jitted is None or self._scan_sig != sig:
                # warm-compile the decode entry with CONCRETE inputs first —
                # compiling it inside the scan trace would bake tracers into
                # the cached entry (outputs discarded; caches stay untouched).
                # Keyed on the full (steps, batch, dtype) signature: a new
                # batch size means a new decode cache entry to warm.
                self._decode_cfn(params, next_tok[:, None], ks, vs, jnp.asarray(T, jnp.int32))
                self._build_scan_decode(n_steps)
                self._scan_sig = sig
            with _obs_runtime.annotate_call("tt_decode") if obs_on else _NULL:
                toks_scan, ks, vs = self._scan_jitted(params, next_tok, ks, vs, T)
                jax.block_until_ready(toks_scan)
            dt = time.perf_counter() - t_decode
            if obs_on:
                # one record per generation: the scan is ONE dispatch, so
                # per-token wall time is the window divided by its length
                _obs_flight.record_step(dt * 1e3, fn="infer_decode",
                                        n_tokens=n_steps, scan=True)
                _obs_tel.observe("infer.tbot_ms", dt * 1e3 / max(1, n_steps))
            out = jnp.concatenate([prompt, next_tok[:, None], toks_scan.T.astype(prompt.dtype)], axis=1)
            metrics = GenerationMetrics(
                ttft_s=ttft,
                tbot_s=dt / max(1, n_steps),
                tokens_per_sec=B * max_new_tokens / (ttft + dt),
                ms_per_token=1e3 * (ttft + dt) / max_new_tokens,
                n_new_tokens=max_new_tokens,
            )
            return out, metrics
        else:
            toks = [next_tok]
            pos = T
            for _ in range(n_steps):
                logits, ks, vs = self._decode_cfn(params, next_tok[:, None], ks, vs,
                                                  jnp.asarray(pos, jnp.int32))
                if temperature > 0.0:
                    # position-keyed split of the per-request key: the OLD
                    # PRNGKey(pos) drew the SAME stream for every generation
                    # at the same position, whatever the request
                    key = jax.random.fold_in(sample_key, pos + 1)
                    next_tok = jax.random.categorical(key, logits / temperature, -1).astype(prompt.dtype)
                else:
                    next_tok = jnp.argmax(logits, -1).astype(prompt.dtype)
                toks.append(next_tok)
                pos += 1
            jax.block_until_ready(next_tok)
            dt = time.perf_counter() - t_decode
            if obs_on:
                _obs_flight.record_step(dt * 1e3, fn="infer_decode",
                                        n_tokens=n_steps, scan=False)
                _obs_tel.observe("infer.tbot_ms", dt * 1e3 / max(1, n_steps))

        out = jnp.concatenate([prompt] + [t[:, None] for t in toks], axis=1)
        metrics = GenerationMetrics(
            ttft_s=ttft,
            tbot_s=dt / max(1, n_steps),
            tokens_per_sec=B * max_new_tokens / (ttft + dt),
            ms_per_token=1e3 * (ttft + dt) / max_new_tokens,
            n_new_tokens=max_new_tokens,
        )
        return out, metrics
