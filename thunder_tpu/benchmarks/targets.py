"""Microbenchmark suite: per-op / per-block targets.

Counterpart of reference thunder/benchmarks/targets.py:190-1010 (LitGPT GELU /
SwiGLU / RMSNorm / SDPA / MLP / QKV+RoPE, nanoGPT blocks, full GPTs). Run as
pytest (`pytest thunder_tpu/benchmarks/targets.py --benchmark-only` style) or
directly: `python -m thunder_tpu.benchmarks.targets [pattern]`.

Every target derives its shapes through ``_d()`` and its model configs through
the ``_*_cfg`` helpers, so the CPU smoke test can clamp the whole suite to
tiny shapes (``_CLAMP``) and run all targets end-to-end — no hard-coded
literals that break under clamping."""
from __future__ import annotations

import math
import sys
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

import thunder_tpu as tt
from thunder_tpu import nn, optim
from thunder_tpu.ops import ltorch

# smoke mode: when set, every shape dimension is capped here and model
# configs collapse to their tiny "test" variants — the CPU suite runs all
# targets end-to-end in seconds (real timing happens on chip, unclamped)
_CLAMP: int | None = None


def _d(n: int) -> int:
    """A shape dimension, capped in smoke mode."""
    return n if _CLAMP is None else min(n, _CLAMP)


def _litgpt_cfg(name: str, **overrides):
    from thunder_tpu.models.litgpt import Config

    if _CLAMP is not None:
        return Config.from_name("tiny-llama2")
    return Config.from_name(name, **overrides)


def _nanogpt_cfg(name: str):
    from thunder_tpu.models.nanogpt import configs

    return configs["test" if _CLAMP is not None else name]


def _force(out):
    jax.block_until_ready(out)


def _timeit(fn, *args, iters=20, warmup=3) -> float:
    for _ in range(warmup):
        _force(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _force(out)
    return (time.perf_counter() - t0) / iters


def _tensor(rng, shape, dtype=jnp.bfloat16):
    return jnp.asarray(rng.randn(*shape), dtype)


BENCHMARKS: dict[str, Callable] = {}

# executor mode for the current run: 'fused' (XLA regions, default) or
# 'opbyop' (per-prim jaxex dispatch) — the reference's per-executor benchmark
# matrix (thunder/benchmarks/targets.py:190-1010 runs each target under
# eager/torch.compile/thunder(+nvfuser...))
_MODE = "fused"


def _jit(fn, **kw):
    if _MODE == "opbyop":
        kw["disable_fusion"] = True
    return tt.jit(fn, **kw)


def register(name):
    def deco(fn):
        if name in BENCHMARKS:
            raise ValueError(f"benchmark target '{name}' is already registered")
        BENCHMARKS[name] = fn
        return fn

    return deco


@register("litgpt_gelu")
def bench_gelu(rng):
    x = _tensor(rng, (_d(16), _d(2048), _d(4096)))
    cf = _jit(lambda x: ltorch.gelu(x, approximate="tanh"))
    return _timeit(cf, x)


@register("litgpt_swiglu")
def bench_swiglu(rng):
    gate = _tensor(rng, (_d(8), _d(2048), _d(11008)))
    up = _tensor(rng, (_d(8), _d(2048), _d(11008)))
    cf = _jit(lambda g, u: ltorch.silu(g) * u)
    return _timeit(cf, gate, up)


@register("litgpt_rmsnorm")
def bench_rmsnorm(rng):
    D = _d(4096)
    x = _tensor(rng, (_d(16), _d(2048), D))
    w = jnp.ones((D,), jnp.bfloat16)
    cf = _jit(lambda x, w: ltorch.rms_norm(x, (D,), w))
    return _timeit(cf, x, w)


@register("litgpt_sdpa")
def bench_sdpa(rng):
    B, H, T, D = _d(8), _d(32), _d(2048), _d(128)
    q = _tensor(rng, (B, H, T, D))
    k = _tensor(rng, (B, H, T, D))
    v = _tensor(rng, (B, H, T, D))
    cf = _jit(lambda q, k, v: ltorch.sdpa(q, k, v, is_causal=True))
    return _timeit(cf, q, k, v, iters=10)


@register("litgpt_mlp")
def bench_mlp(rng):
    from thunder_tpu.models.litgpt import LLaMAMLP

    cfg = _litgpt_cfg("Llama-2-7b-hf")
    mlp = LLaMAMLP(cfg, dtype=jnp.bfloat16)
    tm = _jit(mlp)
    x = _tensor(rng, (_d(4), min(_d(2048), cfg.block_size), cfg.n_embd))
    return _timeit(tm, x, iters=10)


@register("nanogpt_block")
def bench_nanogpt_block(rng):
    from thunder_tpu.models.nanogpt import NanoBlock

    cfg = _nanogpt_cfg("gpt2")
    blk = NanoBlock(cfg, dtype=jnp.bfloat16)
    tm = _jit(blk)
    x = _tensor(rng, (_d(8), min(_d(1024), cfg.block_size), cfg.n_embd))
    return _timeit(tm, x, iters=10)


@register("nanogpt_gpt2_fwd")
def bench_gpt2_fwd(rng):
    from thunder_tpu.models.nanogpt import NanoGPT

    cfg = _nanogpt_cfg("gpt2")
    model = NanoGPT(cfg, dtype=jnp.bfloat16)
    tm = _jit(model)
    T = min(_d(1024), cfg.block_size)
    idx = jnp.asarray(rng.randint(0, cfg.vocab_size, (_d(4), T)), jnp.int32)
    return _timeit(tm, idx, iters=5)


@register("litgpt_qkv_rope")
def bench_qkv_rope(rng):
    """QKV projection + split + RoPE (reference targets.py litgpt qkv+rope)."""
    from thunder_tpu.models.litgpt import build_rope_cache, _apply_rope

    cfg = _litgpt_cfg("Llama-2-7b-hf")
    T = min(_d(2048), cfg.block_size)
    w = _tensor(rng, ((cfg.n_head + 2 * cfg.n_query_groups) * cfg.head_size, cfg.n_embd))
    x = _tensor(rng, (1, T, cfg.n_embd))
    cos, sin = build_rope_cache(T, cfg.rope_n_elem, cfg.rope_base, jnp.bfloat16)

    def qkv_rope(x, w, cos, sin):
        B = x.shape[0]
        nh, ng, hs = cfg.n_head, cfg.n_query_groups, cfg.head_size
        qkv = ltorch.reshape(ltorch.linear(x, w), (B, T, ng, nh // ng + 2, hs))
        q = ltorch.reshape(qkv[:, :, :, : nh // ng, :], (B, T, nh, hs))
        q = ltorch.permute(q, (0, 2, 1, 3))
        return _apply_rope(q, cos, sin, cfg.rope_n_elem)

    cf = _jit(qkv_rope)
    return _timeit(cf, x, w, cos, sin, iters=10)


@register("fused_cross_entropy")
def bench_cross_entropy(rng):
    N, V = _d(8192), _d(32000)
    logits = _tensor(rng, (N, V), jnp.float32)
    tgt = jnp.asarray(rng.randint(0, V, (N,)), jnp.int32)
    cf = _jit(lambda l, t: ltorch.cross_entropy(l, t))
    return _timeit(cf, logits, tgt, iters=10)


@register("train_step_tiny_gpt")
def bench_train_step(rng):
    from thunder_tpu.models.litgpt import GPTForCausalLM
    from thunder_tpu.training import TrainStep

    cfg = _litgpt_cfg("tiny-llama2")
    step = TrainStep(GPTForCausalLM(cfg), optim.AdamW(lr=1e-4))
    T = min(_d(128), cfg.block_size)
    idx = jnp.asarray(rng.randint(0, cfg.vocab_size, (_d(4), T)), jnp.int32)
    tgt = jnp.asarray(rng.randint(0, cfg.vocab_size, (_d(4), T)), jnp.int32)
    step(idx, tgt)  # compile

    def run(i, t):
        return step(i, t)

    return _timeit(run, idx, tgt, iters=10)


@register("resnet50_fwd")
def bench_resnet50(rng):
    from thunder_tpu.models.resnet import build

    model = build("test" if _CLAMP is not None else "resnet50", dtype=jnp.bfloat16)
    tm = _jit(model)
    x = _tensor(rng, (_d(8), 3, _d(224), _d(224)))
    return _timeit(tm, x, iters=5)


@register("moe_block")
def bench_moe_block(rng):
    from thunder_tpu.models.moe import MoEConfig, MoEMLP

    cfg = MoEConfig(n_embd=_d(1024), n_expert=8, n_expert_per_token=2)
    mlp = MoEMLP(cfg, dtype=jnp.bfloat16)
    tm = _jit(mlp)
    x = _tensor(rng, (_d(8), _d(512), cfg.n_embd))
    return _timeit(tm, x, iters=10)


@register("vit_b16_fwd")
def bench_vit(rng):
    from thunder_tpu.models.vit import ViT, configs

    cfg = configs["test" if _CLAMP is not None else "vit-b16"]
    model = ViT(cfg, dtype=jnp.bfloat16)
    tm = _jit(model)
    x = _tensor(rng, (_d(8), cfg.channels, cfg.image_size, cfg.image_size))
    return _timeit(tm, x, iters=5)


@register("llama2_7b_attention")
def bench_llama2_7b_attention(rng):
    """One Llama-2-7B attention layer at full dims (reference targets.py
    llama2 7B attention target)."""
    from thunder_tpu.models.litgpt import CausalSelfAttention, build_rope_cache

    cfg = _litgpt_cfg("Llama-2-7b-hf", block_size=2048)
    attn = CausalSelfAttention(cfg, dtype=jnp.bfloat16)
    tm = _jit(attn)
    T = min(_d(2048), cfg.block_size)
    x = _tensor(rng, (1, T, cfg.n_embd))
    cos, sin = build_rope_cache(T, cfg.rope_n_elem, cfg.rope_base, jnp.bfloat16)
    return _timeit(tm, x, cos, sin, iters=5)


@register("llama_mlp_7b")
def bench_llama_mlp_7b(rng):
    from thunder_tpu.models.litgpt import LLaMAMLP

    cfg = _litgpt_cfg("Llama-2-7b-hf")
    mlp = LLaMAMLP(cfg, dtype=jnp.bfloat16)
    tm = _jit(mlp)
    x = _tensor(rng, (1, min(_d(2048), cfg.block_size), cfg.n_embd))
    return _timeit(tm, x, iters=5)


@register("gpt2_xl_block")
def bench_gpt2_xl_block(rng):
    """GPT-2 XL dims block fwd (reference nanogpt/gpt2-xl family)."""
    from thunder_tpu.models.litgpt import Block, build_rope_cache

    cfg = _litgpt_cfg("nanogpt-124m", n_embd=1600, n_head=25, block_size=1024)
    blk = Block(cfg, dtype=jnp.bfloat16)
    tm = _jit(blk)
    T = min(_d(1024), cfg.block_size)
    x = _tensor(rng, (_d(4), T, cfg.n_embd))
    cos, sin = build_rope_cache(T, cfg.rope_n_elem, cfg.rope_base, jnp.bfloat16)
    return _timeit(tm, x, cos, sin, iters=5)


@register("hf_gpt2_module")
def bench_hf_gpt2(rng):
    """HF GPT-2 through the torch interop frontend (reference
    test_hf_transformers benchmark family)."""
    try:
        import torch
        from transformers import GPT2Config, GPT2LMHeadModel
    except Exception:
        return float("nan")
    V, T = _d(50257), _d(512)
    cfg = GPT2Config(n_layer=2 if _CLAMP else 4, n_head=8, n_embd=_d(512),
                     vocab_size=V, n_positions=T, use_cache=False)
    torch.manual_seed(0)
    model = GPT2LMHeadModel(cfg).eval()
    ctm = tt.jit(model)
    ids = jnp.asarray(rng.randint(0, V, (_d(4), T)), jnp.int32)

    def run(i):
        out = ctm(input_ids=i, use_cache=False)
        return out["logits"] if isinstance(out, dict) else out[0]

    return _timeit(run, ids, iters=5)


@register("hf_llama_module")
def bench_hf_llama(rng):
    try:
        import torch
        from transformers import LlamaConfig, LlamaForCausalLM
    except Exception:
        return float("nan")
    V, T = _d(32000), _d(512)
    cfg = LlamaConfig(vocab_size=V, hidden_size=_d(512),
                      intermediate_size=_d(1376),
                      num_hidden_layers=2 if _CLAMP else 4,
                      num_attention_heads=8, num_key_value_heads=8,
                      use_cache=False, max_position_embeddings=_d(1024))
    torch.manual_seed(0)
    model = LlamaForCausalLM(cfg).eval()
    ctm = tt.jit(model)
    ids = jnp.asarray(rng.randint(0, V, (_d(2), T)), jnp.int32)

    def run(i):
        out = ctm(input_ids=i)
        return out["logits"] if isinstance(out, dict) else out[0]

    return _timeit(run, ids, iters=5)


@register("adamw_update_124m")
def bench_adamw_update(rng):
    """Fused AdamW over a 124M-param tree — isolates the optimizer fusion
    cost seen in the llama-350m profile. Standalone it pays a dispatch per
    call; inside a TrainStep the update fuses into the one whole-step
    program."""
    from thunder_tpu import optim

    # few large tensors: per-arg dispatch marshaling would otherwise
    # dominate (the real step passes params as one fused program)
    shapes = [(_d(50304), _d(768)), (_d(12), _d(768), _d(3072)),
              (_d(12), _d(3072), _d(768)), (_d(48), _d(768), _d(768))]
    params = {f"p{i}": _tensor(rng, s, jnp.float32) for i, s in enumerate(shapes)}
    grads = {k: _tensor(rng, v.shape, jnp.float32) for k, v in params.items()}
    opt = optim.AdamW(lr=1e-4)
    state = opt.init(params)
    # no donation: the bench reuses the same buffers every iteration
    step = jax.jit(opt.update)

    def run(p, g, st):
        newp, newst = step(p, g, st)
        return newp["p0"]

    return _timeit(run, params, grads, state, iters=10)


@register("embedding_lmhead")
def bench_embedding_lmhead(rng):
    """Embedding gather + LM-head matmul + fused xent — the vocab-bound tail
    of every LM step."""
    V, D, N = _d(32000), _d(1024), _d(8192)
    wte = _tensor(rng, (V, D))
    ids = jnp.asarray(rng.randint(0, V, (N,)), jnp.int32)
    tgt = jnp.asarray(rng.randint(0, V, (N,)), jnp.int32)

    def fn(wte, ids, tgt):
        h = ltorch.embedding(ids, wte)
        logits = ltorch.matmul(h, ltorch.transpose(wte, 0, 1))
        return ltorch.cross_entropy(logits, tgt)

    cf = _jit(fn)
    return _timeit(cf, wte, ids, tgt, iters=5)


@register("layer_norm_bwd")
def bench_layer_norm_bwd(rng):
    N, D = _d(8192), _d(1024)
    x = _tensor(rng, (N, D), jnp.float32)
    w = _tensor(rng, (D,), jnp.float32)
    b = _tensor(rng, (D,), jnp.float32)

    def loss(x, w, b):
        return ltorch.sum(ltorch.layer_norm(x, (D,), w, b))

    vag = tt.value_and_grad(loss)
    vag(x, w, b)

    def run(x, w, b):
        return vag(x, w, b)[0]

    return _timeit(run, x, w, b, iters=10)


@register("rmsnorm_bwd")
def bench_rmsnorm_bwd(rng):
    N, D = _d(8192), _d(1024)
    x = _tensor(rng, (N, D), jnp.float32)
    w = _tensor(rng, (D,), jnp.float32)

    def loss(x, w):
        return ltorch.sum(ltorch.rms_norm(x, (D,), w))

    vag = tt.value_and_grad(loss)
    vag(x, w)
    return _timeit(lambda: vag(x, w)[0], iters=10)


@register("deepseek_moe_router")
def bench_deepseek_moe(rng):
    """Larger expert count + top-k routing (reference DeepSeek MoE target)."""
    from thunder_tpu.models.moe import MoEConfig, MoEMLP

    cfg = MoEConfig(n_embd=_d(1024), n_expert=32, n_expert_per_token=4)
    mlp = MoEMLP(cfg, dtype=jnp.bfloat16)
    tm = _jit(mlp)
    x = _tensor(rng, (_d(4), _d(512), cfg.n_embd))
    return _timeit(tm, x, iters=5)


def main(pattern: str = "", modes=("fused", "opbyop")):
    """Per-target x per-executor matrix with a winner column (reference
    targets.py benchmark CI table)."""
    global _MODE
    rng = np.random.RandomState(0)
    rows = []
    for name, fn in BENCHMARKS.items():
        if pattern and pattern not in name:
            continue
        row = {"target": name}
        for mode in modes:
            _MODE = mode
            try:
                row[mode] = fn(rng) * 1e3
            except Exception as e:
                row[mode] = None
                row.setdefault("errors", {})[mode] = str(e)[:80]
        rows.append(row)
    _MODE = "fused"
    hdr = f"{'target':28s}" + "".join(f"{m:>12s}" for m in modes) + f"{'winner':>10s}"
    print(hdr)
    print("-" * len(hdr))
    for row in rows:
        cells = ""
        best, best_t = "-", None
        for m in modes:
            v = row.get(m)
            cells += f"{v:12.3f}" if v is not None else f"{'FAIL':>12s}"
            if v is not None and (best_t is None or v < best_t):
                best, best_t = m, v
        print(f"{row['target']:28s}{cells}{best:>10s}")
        for m, err in row.get("errors", {}).items():
            print(f"    {m} error: {err}")
    return rows


if __name__ == "__main__":
    pat = sys.argv[1] if len(sys.argv) > 1 else ""
    modes = tuple(sys.argv[2].split(",")) if len(sys.argv) > 2 else ("fused", "opbyop")
    main(pat, modes)
