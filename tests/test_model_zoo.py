"""A zoo of 23 programs traced through the package's entry points, each held
to a result (reference thunder/benchmarks/targets.py:190-1010 names the same
programs: LitGPT GELU / SwiGLU / RMSNorm / SDPA / MLP / QKV+RoPE, nanoGPT
blocks, whole GPTs, vision models, MoE, HF modules, optimizer and norm
backwards).

Sizes are small: the ``test`` and ``tiny-llama2`` configurations, every other
dimension at most 64. A program compiled by ``tt.jit`` must match the same
program run op by op through jaxex (``disable_fusion=True``) within its
dtype's tolerance; an HF module must match torch eager; a ``TrainStep`` must
give a finite loss that falls on a repeated batch; a ``tt.value_and_grad``
and the optimizer update must match plain-JAX / NumPy references."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import optim
from thunder_tpu.ops import ltorch

# the largest of every dimension that no configuration fixes
_D = 64

# relative tolerance a program's output is held to, by dtype: bf16 keeps an
# 8-bit mantissa, and the fused program may keep float32 where op by op rounds
_TOL = {jnp.dtype(jnp.bfloat16): 3e-2, jnp.dtype(jnp.float32): 1e-5}


def _tensor(rng, shape, dtype=jnp.bfloat16):
    return jnp.asarray(rng.randn(*shape), dtype)


def _close(actual, expected, tol):
    """Elementwise within ``tol``, the absolute part scaled by the output's
    magnitude (a bf16 logit of 8 carries an error of 1/32)."""
    a = np.asarray(actual, np.float64)
    e = np.asarray(expected, np.float64)
    assert a.shape == e.shape, f"shape {a.shape} != {e.shape}"
    assert np.isfinite(a).all()
    np.testing.assert_allclose(a, e, rtol=tol, atol=tol * max(1.0, float(np.abs(e).max())))


def _fused_matches_opbyop(program, *args):
    """``program`` through ``tt.jit`` against the same program op by op."""
    fused = tt.jit(program)(*args)
    opbyop = tt.jit(program, disable_fusion=True)(*args)
    outs = jax.tree_util.tree_leaves(fused)
    refs = jax.tree_util.tree_leaves(opbyop)
    assert outs and len(outs) == len(refs)
    for out, ref in zip(outs, refs):
        _close(out, ref, _TOL[jnp.dtype(ref.dtype)] if jnp.issubdtype(ref.dtype, jnp.floating)
               else 0.0)


def _llama_cfg():
    from thunder_tpu.models.litgpt import Config

    return Config.from_name("tiny-llama2")


def _nanogpt_cfg():
    from thunder_tpu.models.nanogpt import configs

    return configs["test"]


def _rope(cfg, T):
    from thunder_tpu.models.litgpt import build_rope_cache

    return build_rope_cache(T, cfg.rope_n_elem, cfg.rope_base, jnp.bfloat16)


def litgpt_gelu(rng):
    x = _tensor(rng, (16, _D, _D))
    _fused_matches_opbyop(lambda x: ltorch.gelu(x, approximate="tanh"), x)


def litgpt_swiglu(rng):
    gate, up = _tensor(rng, (8, _D, _D)), _tensor(rng, (8, _D, _D))
    _fused_matches_opbyop(lambda g, u: ltorch.silu(g) * u, gate, up)


def litgpt_rmsnorm(rng):
    x = _tensor(rng, (16, _D, _D))
    w = jnp.ones((_D,), jnp.bfloat16)
    _fused_matches_opbyop(lambda x, w: ltorch.rms_norm(x, (_D,), w), x, w)


def litgpt_sdpa(rng):
    q, k, v = (_tensor(rng, (8, 32, _D, _D)) for _ in range(3))
    _fused_matches_opbyop(lambda q, k, v: ltorch.sdpa(q, k, v, is_causal=True), q, k, v)


def litgpt_mlp(rng):
    from thunder_tpu.models.litgpt import LLaMAMLP

    cfg = _llama_cfg()
    _fused_matches_opbyop(LLaMAMLP(cfg, dtype=jnp.bfloat16), _tensor(rng, (4, _D, cfg.n_embd)))


def litgpt_qkv_rope(rng):
    """QKV projection, the queries split out of it, and RoPE on them."""
    from thunder_tpu.models.litgpt import _apply_rope

    cfg = _llama_cfg()
    nh, ng, hs, T = cfg.n_head, cfg.n_query_groups, cfg.head_size, _D
    w = _tensor(rng, ((nh + 2 * ng) * hs, cfg.n_embd))
    x = _tensor(rng, (1, T, cfg.n_embd))
    cos, sin = _rope(cfg, T)

    def qkv_rope(x, w, cos, sin):
        qkv = ltorch.reshape(ltorch.linear(x, w), (1, T, ng, nh // ng + 2, hs))
        q = ltorch.reshape(qkv[:, :, :, : nh // ng, :], (1, T, nh, hs))
        return _apply_rope(ltorch.permute(q, (0, 2, 1, 3)), cos, sin, cfg.rope_n_elem)

    _fused_matches_opbyop(qkv_rope, x, w, cos, sin)


def nanogpt_block(rng):
    from thunder_tpu.models.nanogpt import NanoBlock

    cfg = _nanogpt_cfg()
    _fused_matches_opbyop(NanoBlock(cfg, dtype=jnp.bfloat16),
                          _tensor(rng, (8, cfg.block_size, cfg.n_embd)))


def nanogpt_gpt2_fwd(rng):
    from thunder_tpu.models.nanogpt import NanoGPT

    cfg = _nanogpt_cfg()
    idx = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, cfg.block_size)), jnp.int32)
    _fused_matches_opbyop(NanoGPT(cfg, dtype=jnp.bfloat16), idx)


def fused_cross_entropy(rng):
    logits = _tensor(rng, (_D, _D), jnp.float32)
    tgt = jnp.asarray(rng.randint(0, _D, (_D,)), jnp.int32)
    _fused_matches_opbyop(lambda l, t: ltorch.cross_entropy(l, t), logits, tgt)


def train_step_tiny_gpt(rng):
    from thunder_tpu.models.litgpt import GPTForCausalLM
    from thunder_tpu.training import TrainStep

    cfg = _llama_cfg()
    step = TrainStep(GPTForCausalLM(cfg), optim.AdamW(lr=1e-3))
    idx = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, _D)), jnp.int32)
    tgt = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, _D)), jnp.int32)
    losses = [float(step(idx, tgt)) for _ in range(4)]
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses


def resnet50_fwd(rng):
    from thunder_tpu.models.resnet import build

    _fused_matches_opbyop(build("test", dtype=jnp.bfloat16), _tensor(rng, (8, 3, _D, _D)))


def moe_block(rng):
    from thunder_tpu.models.moe import MoEConfig, MoEMLP

    cfg = MoEConfig(n_embd=_D, n_expert=8, n_expert_per_token=2)
    _fused_matches_opbyop(MoEMLP(cfg, dtype=jnp.bfloat16), _tensor(rng, (8, _D, cfg.n_embd)))


def vit_b16_fwd(rng):
    from thunder_tpu.models.vit import ViT, configs

    cfg = configs["test"]
    x = _tensor(rng, (8, cfg.channels, cfg.image_size, cfg.image_size))
    _fused_matches_opbyop(ViT(cfg, dtype=jnp.bfloat16), x)


def llama2_7b_attention(rng):
    from thunder_tpu.models.litgpt import CausalSelfAttention

    cfg = _llama_cfg()
    x = _tensor(rng, (1, _D, cfg.n_embd))
    _fused_matches_opbyop(CausalSelfAttention(cfg, dtype=jnp.bfloat16), x, *_rope(cfg, _D))


def llama_mlp_7b(rng):
    from thunder_tpu.models.litgpt import LLaMAMLP

    cfg = _llama_cfg()
    _fused_matches_opbyop(LLaMAMLP(cfg, dtype=jnp.bfloat16), _tensor(rng, (1, _D, cfg.n_embd)))


def gpt2_xl_block(rng):
    from thunder_tpu.models.litgpt import Block

    cfg = _llama_cfg()
    x = _tensor(rng, (4, _D, cfg.n_embd))
    _fused_matches_opbyop(Block(cfg, dtype=jnp.bfloat16), x, *_rope(cfg, _D))


def _matches_torch_eager(model, ids):
    import torch

    with torch.no_grad():
        ref = model(input_ids=torch.as_tensor(np.array(ids))).logits.numpy()
    out = tt.jit(model)(input_ids=ids)
    logits = out["logits"] if isinstance(out, dict) else out[0]
    _close(logits, ref, 1e-4)


def hf_gpt2_module(rng):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    cfg = transformers.GPT2Config(n_layer=2, n_head=8, n_embd=_D, vocab_size=_D,
                                  n_positions=_D, use_cache=False)
    torch.manual_seed(0)
    model = transformers.GPT2LMHeadModel(cfg).eval()
    _matches_torch_eager(model, jnp.asarray(rng.randint(0, _D, (4, _D)), jnp.int32))


def hf_llama_module(rng):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    cfg = transformers.LlamaConfig(vocab_size=_D, hidden_size=_D, intermediate_size=_D,
                                   num_hidden_layers=2, num_attention_heads=8,
                                   num_key_value_heads=8, use_cache=False,
                                   max_position_embeddings=_D)
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(cfg).eval()
    _matches_torch_eager(model, jnp.asarray(rng.randint(0, _D, (2, _D)), jnp.int32))


def adamw_update_124m(rng):
    """The AdamW update of a GPT-2-shaped parameter tree (embedding, stacked
    MLP and attention weights), two steps against a NumPy AdamW."""
    shapes = [(_D, _D), (12, _D, _D), (12, _D, _D), (48, _D, _D)]
    params = {f"p{i}": _tensor(rng, s, jnp.float32) for i, s in enumerate(shapes)}
    opt = optim.AdamW(lr=1e-2)
    update = jax.jit(opt.update)
    state = opt.init(params)
    ref = {k: np.asarray(v, np.float64) for k, v in params.items()}
    m = {k: np.zeros_like(p) for k, p in ref.items()}
    v = {k: np.zeros_like(p) for k, p in ref.items()}
    for t in (1, 2):
        grads = {k: _tensor(rng, p.shape, jnp.float32) for k, p in params.items()}
        params, state = update(params, grads, state)
        for k, g in grads.items():
            g = np.asarray(g, np.float64)
            m[k] = opt.beta1 * m[k] + (1 - opt.beta1) * g
            v[k] = opt.beta2 * v[k] + (1 - opt.beta2) * g * g
            upd = (m[k] / (1 - opt.beta1**t)) / (np.sqrt(v[k] / (1 - opt.beta2**t)) + opt.eps)
            ref[k] = ref[k] * (1 - opt.lr * opt.weight_decay) - opt.lr * upd
    assert int(state["step"]) == 2
    for k in ref:
        _close(params[k], ref[k], 1e-5)


def embedding_lmhead(rng):
    """Embedding gather, LM head on the tied table, cross-entropy."""
    wte = _tensor(rng, (_D, _D))
    ids = jnp.asarray(rng.randint(0, _D, (_D,)), jnp.int32)
    tgt = jnp.asarray(rng.randint(0, _D, (_D,)), jnp.int32)

    def fn(wte, ids, tgt):
        logits = ltorch.matmul(ltorch.embedding(ids, wte), ltorch.transpose(wte, 0, 1))
        return ltorch.cross_entropy(logits, tgt)

    _fused_matches_opbyop(fn, wte, ids, tgt)


def _vag_matches_jax(loss, ref_loss, *args):
    """``tt.value_and_grad`` over every argument against ``jax.value_and_grad``."""
    argnums = tuple(range(len(args)))
    val, (grads, _) = tt.value_and_grad(loss, argnums=argnums)(*args)
    rval, rgrads = jax.value_and_grad(ref_loss, argnums=argnums)(*args)
    _close(val, rval, 1e-5)
    for g, r in zip(grads, rgrads):
        _close(g, r, 1e-5)


def layer_norm_bwd(rng):
    x = _tensor(rng, (_D, _D), jnp.float32)
    w, b = _tensor(rng, (_D,), jnp.float32), _tensor(rng, (_D,), jnp.float32)

    def ref(x, w, b):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return jnp.sum((x - mu) / jnp.sqrt(var + 1e-5) * w + b)

    _vag_matches_jax(lambda x, w, b: ltorch.sum(ltorch.layer_norm(x, (_D,), w, b)), ref, x, w, b)


def rmsnorm_bwd(rng):
    x, w = _tensor(rng, (_D, _D), jnp.float32), _tensor(rng, (_D,), jnp.float32)

    def ref(x, w):
        return jnp.sum(x / jnp.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * w)

    _vag_matches_jax(lambda x, w: ltorch.sum(ltorch.rms_norm(x, (_D,), w)), ref, x, w)


def deepseek_moe_router(rng):
    """More experts and more of them a token."""
    from thunder_tpu.models.moe import MoEConfig, MoEMLP

    cfg = MoEConfig(n_embd=_D, n_expert=32, n_expert_per_token=4)
    _fused_matches_opbyop(MoEMLP(cfg, dtype=jnp.bfloat16), _tensor(rng, (4, _D, cfg.n_embd)))


ZOO = [
    litgpt_gelu, litgpt_swiglu, litgpt_rmsnorm, litgpt_sdpa, litgpt_mlp, litgpt_qkv_rope,
    nanogpt_block, nanogpt_gpt2_fwd, fused_cross_entropy, train_step_tiny_gpt, resnet50_fwd,
    moe_block, vit_b16_fwd, llama2_7b_attention, llama_mlp_7b, gpt2_xl_block, hf_gpt2_module,
    hf_llama_module, adamw_update_124m, embedding_lmhead, layer_norm_bwd, rmsnorm_bwd,
    deepseek_moe_router,
]


@pytest.mark.parametrize("program", ZOO, ids=lambda f: f.__name__)
def test_program(program, rng):
    program(rng)
