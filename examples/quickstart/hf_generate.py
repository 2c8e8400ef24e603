"""Quickstart: timed KV-cache generation for a real HF model on TPU.

    python examples/quickstart/hf_generate.py [--tokens 64] [--prompt-len 32] [--tiny]

A `transformers` GPT-2 runs greedy decode through the torch interop frontend
with TRUE cache reuse: two compiled programs total (prefill + decode) over a
StaticCache whose key/value buffers are runtime inputs — HF's own
`index_copy_` cache update is captured functionally, so the sequence grows
with zero recompiles. Parity is checked greedy-token-exact against torch
eager on the same weights, and the decode path's logits are compared with
torch eager's fed the same tokens.

Weights are random-init at the real gpt2-124M config (no checkpoint
download); the parity checks are weight-agnostic.

(Counterpart of the reference's headline interop artifact — the timed HF
``generate()`` in its README.)
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

import argparse
import json
import time

import numpy as np


def build_static_step(model, config, max_cache_len: int):
    """A torch module computing one cached step (prefill or decode by input
    shape): (input_ids, cache_position, ks, vs) -> (logits, ks', vs')."""
    import torch
    from transformers.cache_utils import StaticCache

    class StaticStep(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.inner = model

        def forward(self, input_ids, cache_position, ks, vs):
            cache = StaticCache(config=config, max_batch_size=input_ids.shape[0],
                                max_cache_len=max_cache_len)
            for layer, k, v in zip(cache.layers, ks, vs):
                # install the traced buffers; update() then index_copy_'s
                # into them in-place (functionalized by the interop frontend)
                layer.keys = k
                layer.values = v
                layer.max_batch_size = input_ids.shape[0]
                layer.dtype = k.dtype
                layer.device = k.device
                layer.is_initialized = True
            # a ready 4-D additive mask: HF's own mask construction routes
            # through torch.vmap (functorch), which bypasses
            # __torch_function__ tracing; building it with plain ops keeps
            # the whole step traceable
            kv_idx = torch.arange(max_cache_len)
            visible = kv_idx[None, :] <= cache_position[:, None]  # (Tq, M)
            mask4d = torch.where(visible, 0.0, torch.finfo(torch.float32).min)
            mask4d = mask4d[None, None].expand(input_ids.shape[0], 1, -1, -1)
            out = self.inner(input_ids=input_ids, past_key_values=cache,
                             cache_position=cache_position,
                             attention_mask=mask4d, use_cache=True)
            return (out.logits[:, -1, :],
                    tuple(l.keys for l in cache.layers),
                    tuple(l.values for l in cache.layers))

    return StaticStep()


def generate_interop(model, config, prompt_ids: np.ndarray, new_tokens: int,
                     max_cache_len: int | None = None):
    """Greedy KV-cache generation through the compiled interop path.

    Returns (token list, prefill_seconds, decode_seconds_per_token)."""
    import jax
    import jax.numpy as jnp

    from thunder_tpu.interop.torch_frontend import compile_torch_module

    B, T0 = prompt_ids.shape
    M = max_cache_len or (T0 + new_tokens)
    H = config.n_head if hasattr(config, "n_head") else config.num_attention_heads
    D = (config.n_embd if hasattr(config, "n_embd") else config.hidden_size) // H
    L = config.n_layer if hasattr(config, "n_layer") else config.num_hidden_layers

    step = compile_torch_module(build_static_step(model, config, M))
    ks = tuple(jnp.zeros((B, H, M, D), jnp.float32) for _ in range(L))
    vs = tuple(jnp.zeros((B, H, M, D), jnp.float32) for _ in range(L))

    ids = jnp.asarray(prompt_ids, jnp.int64)
    # compile the prefill shape (fresh zero caches after; timing excludes it)
    jax.block_until_ready(step(ids, jnp.arange(T0, dtype=jnp.int64), ks, vs)[0])
    t0 = time.perf_counter()
    logits, ks, vs = step(ids, jnp.arange(T0, dtype=jnp.int64), ks, vs)
    nxt = jnp.argmax(logits, -1).astype(jnp.int64)
    float(logits[0, 0])  # sync
    prefill_s = time.perf_counter() - t0

    toks_dev = [nxt]
    # compile the decode shape once
    logits, ks, vs = step(nxt[:, None], jnp.asarray([T0], jnp.int64), ks, vs)
    nxt = jnp.argmax(logits, -1).astype(jnp.int64)
    toks_dev.append(nxt)

    # async decode: tokens stay on device so steps pipeline through the
    # dispatch queue (one host sync at the end, not per token)
    t1 = time.perf_counter()
    for i in range(1, new_tokens - 1):
        logits, ks, vs = step(nxt[:, None], jnp.asarray([T0 + i], jnp.int64), ks, vs)
        nxt = jnp.argmax(logits, -1).astype(jnp.int64)
        toks_dev.append(nxt)
    jax.block_until_ready(nxt)
    decode_s_per_tok = (time.perf_counter() - t1) / max(1, new_tokens - 2)
    return [int(t[0]) for t in toks_dev], prefill_s, decode_s_per_tok


def generate_torch_eager(model, prompt_ids: np.ndarray, new_tokens: int):
    """Greedy generation with torch eager + its own KV cache (the reference
    competitor), timed the same way."""
    import torch

    ids = torch.as_tensor(prompt_ids)
    with torch.no_grad():
        t0 = time.perf_counter()
        out = model(input_ids=ids, use_cache=True)
        past = out.past_key_values
        nxt = out.logits[:, -1, :].argmax(-1)
        prefill_s = time.perf_counter() - t0
        tokens = [int(nxt[0])]
        t1 = time.perf_counter()
        for _ in range(new_tokens - 1):
            out = model(input_ids=nxt[:, None], past_key_values=past, use_cache=True)
            past = out.past_key_values
            nxt = out.logits[:, -1, :].argmax(-1)
            tokens.append(int(nxt[0]))
        decode_s_per_tok = (time.perf_counter() - t1) / max(1, new_tokens - 1)
    return tokens, prefill_s, decode_s_per_tok


def logits_parity(model, config, prompt_ids: np.ndarray, steps: int = 8,
                  max_cache_len: int = 128) -> float:
    """Max-abs-err between interop and torch-eager *logits* along the decode
    path, both fed torch's greedy tokens (identical inputs at every step) —
    the decisive parity check, independent of argmax tie-breaking."""
    import jax.numpy as jnp
    import torch

    from thunder_tpu.interop.torch_frontend import compile_torch_module

    B, T0 = prompt_ids.shape
    M = max_cache_len
    H = config.n_head if hasattr(config, "n_head") else config.num_attention_heads
    D = (config.n_embd if hasattr(config, "n_embd") else config.hidden_size) // H
    L = config.n_layer if hasattr(config, "n_layer") else config.num_hidden_layers

    step = compile_torch_module(build_static_step(model, config, M))
    ks = tuple(jnp.zeros((B, H, M, D), jnp.float32) for _ in range(L))
    vs = tuple(jnp.zeros((B, H, M, D), jnp.float32) for _ in range(L))

    ids_t = torch.as_tensor(prompt_ids)
    errs = []
    with torch.no_grad():
        out_t = model(input_ids=ids_t, use_cache=True)
        past = out_t.past_key_values
        logits_t = out_t.logits[:, -1, :]
        logits_j, ks, vs = step(jnp.asarray(prompt_ids, jnp.int64),
                                jnp.arange(T0, dtype=jnp.int64), ks, vs)
        errs.append(float(jnp.max(jnp.abs(logits_j - jnp.asarray(logits_t.numpy())))))
        nxt_t = logits_t.argmax(-1)
        for i in range(steps):
            out_t = model(input_ids=nxt_t[:, None], past_key_values=past, use_cache=True)
            past = out_t.past_key_values
            logits_t = out_t.logits[:, -1, :]
            logits_j, ks, vs = step(jnp.asarray(nxt_t.numpy()[:, None], jnp.int64),
                                    jnp.asarray([T0 + i], jnp.int64), ks, vs)
            errs.append(float(jnp.max(jnp.abs(logits_j - jnp.asarray(logits_t.numpy())))))
            nxt_t = logits_t.argmax(-1)
    return max(errs)


def run_gpt2(new_tokens: int = 64, prompt_len: int = 32, tiny: bool = False) -> dict:
    import torch
    from transformers import GPT2Config, GPT2LMHeadModel

    cfg = (GPT2Config(n_layer=2, n_embd=64, n_head=4) if tiny else GPT2Config())
    torch.manual_seed(0)
    model = GPT2LMHeadModel(cfg).eval()
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg.vocab_size, (1, prompt_len))

    tok_i, pre_i, dec_i = generate_interop(model, cfg, prompt, new_tokens)
    tok_e, pre_e, dec_e = generate_torch_eager(model, prompt, new_tokens)
    n_match = sum(a == b for a, b in zip(tok_i, tok_e))
    # same max_cache_len as generate_interop so the parity probe reuses the
    # persistent-cache executables instead of compiling a third shape
    max_logit_err = logits_parity(model, cfg, prompt, steps=8,
                                  max_cache_len=prompt_len + new_tokens)
    return {
        "decode_logits_max_abs_err": round(max_logit_err, 6),
        "model": "gpt2-124M (real config, random init: zero-egress env)" if not tiny else "gpt2-tiny",
        "new_tokens": new_tokens,
        "prompt_len": prompt_len,
        "greedy_tokens_match": f"{n_match}/{min(len(tok_i), len(tok_e))}",
        "interop_decode_tok_per_s": round(1.0 / dec_i, 1),
        "torch_eager_decode_tok_per_s": round(1.0 / dec_e, 1),
        "speedup_vs_eager": round(dec_e / dec_i, 2),
        "interop_prefill_s": round(pre_i, 3),
        "eager_prefill_s": round(pre_e, 3),
    }



def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tiny", action="store_true", help="2-layer config for a fast demo")
    args = ap.parse_args()

    res = run_gpt2(new_tokens=args.tokens, prompt_len=args.prompt_len, tiny=args.tiny)
    print(json.dumps(res, indent=2))


if __name__ == "__main__":
    main()
