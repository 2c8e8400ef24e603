"""Torch-frontend acquisition tests: real torch.nn.Modules traced into
thunder_tpu and compared against torch eager.

The reference's acquisition suite is interpreter-based
(thunder/tests/test_jit_general.py); here the same guarantee — arbitrary
torch code acquired without graph breaks or silent fallbacks — is checked
through the __torch_function__ frontend."""
import numpy as np
import pytest

torch = pytest.importorskip("torch")
import jax.numpy as jnp  # noqa: E402
import torch.nn as tnn  # noqa: E402

import thunder_tpu as tt  # noqa: E402
from thunder_tpu.interop.torch_frontend import compile_torch_module  # noqa: E402


def _check(module, *torch_args, atol=1e-5, **torch_kwargs):
    module = module.eval()
    with torch.no_grad():
        ref = module(*torch_args, **torch_kwargs)
    ctm = compile_torch_module(module)
    jax_args = [jnp.asarray(a.numpy()) if isinstance(a, torch.Tensor) else a for a in torch_args]
    jax_kwargs = {k: jnp.asarray(v.numpy()) if isinstance(v, torch.Tensor) else v
                  for k, v in torch_kwargs.items()}
    out = ctm(*jax_args, **jax_kwargs)
    ref_arr = ref.detach().numpy() if isinstance(ref, torch.Tensor) else ref
    np.testing.assert_allclose(np.asarray(out), ref_arr, atol=atol, rtol=atol)


def test_torch_mlp():
    torch.manual_seed(0)

    class MLP(tnn.Module):
        def __init__(self):
            super().__init__()
            self.fc1 = tnn.Linear(8, 32)
            self.ln = tnn.LayerNorm(32)
            self.fc2 = tnn.Linear(32, 4)

        def forward(self, x):
            return self.fc2(self.ln(torch.nn.functional.gelu(self.fc1(x))))

    _check(MLP(), torch.randn(5, 8))


def test_torch_attention_block():
    torch.manual_seed(1)

    class Block(tnn.Module):
        def __init__(self, d=32, h=4):
            super().__init__()
            self.h = h
            self.qkv = tnn.Linear(d, 3 * d)
            self.proj = tnn.Linear(d, d)
            self.ln = tnn.LayerNorm(d)

        def forward(self, x):
            B, T, C = x.shape
            q, k, v = self.qkv(self.ln(x)).chunk(3, dim=-1)
            q = q.view(B, T, self.h, C // self.h).transpose(1, 2)
            k = k.view(B, T, self.h, C // self.h).transpose(1, 2)
            v = v.view(B, T, self.h, C // self.h).transpose(1, 2)
            y = torch.nn.functional.scaled_dot_product_attention(q, k, v, is_causal=True)
            y = y.transpose(1, 2).reshape(B, T, C)
            return x + self.proj(y)

    _check(Block(), torch.randn(2, 16, 32), atol=1e-4)


def test_torch_jit_autodetect():
    torch.manual_seed(2)
    m = tnn.Sequential(tnn.Linear(4, 8), tnn.ReLU(), tnn.Linear(8, 2)).eval()
    cm = tt.jit(m)
    x = torch.randn(3, 4)
    with torch.no_grad():
        ref = m(x).numpy()
    out = cm(jnp.asarray(x.numpy()))
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)


def test_hf_gpt2_matches_eager():
    transformers = pytest.importorskip("transformers")
    from transformers import GPT2Config, GPT2LMHeadModel

    cfg = GPT2Config(n_layer=2, n_head=2, n_embd=64, vocab_size=128, n_positions=64,
                     use_cache=False)
    torch.manual_seed(0)
    model = GPT2LMHeadModel(cfg).eval()
    model.config.use_cache = False
    ids = torch.randint(0, 128, (1, 16))
    with torch.no_grad():
        ref = model(input_ids=ids, use_cache=False).logits.numpy()
    ctm = compile_torch_module(model)
    out = ctm(input_ids=jnp.asarray(ids.numpy()), use_cache=False)
    logits = out["logits"] if isinstance(out, dict) else getattr(out, "logits", None)
    if logits is None:
        logits = out[0]
    np.testing.assert_allclose(np.asarray(logits), ref, atol=1e-4)


def test_fft_routes_to_auto_catalog():
    """torch.fft/linalg/special route to the auto-registered jax catalog
    (no eager fallback, no error — reference default_torch_ops.py role)."""
    import warnings

    class Weird(tnn.Module):
        def forward(self, x):
            return torch.fft.fft(x).real

    x = torch.randn(4)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out = compile_torch_module(Weird())(jnp.asarray(x.numpy()))
    assert not any("eagerly" in str(m.message) for m in w)
    np.testing.assert_allclose(np.asarray(out), torch.fft.fft(x).real.numpy(), atol=1e-4)


def test_hf_llama_gqa_matches_eager():
    """GQA head expansion + DynamicCache empty-cat handling (transformers
    LlamaForCausalLM with num_key_value_heads < num_attention_heads)."""
    pytest.importorskip("transformers")
    from transformers import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    torch.manual_seed(0)
    model = LlamaForCausalLM(cfg).eval()
    ids = torch.randint(0, 256, (2, 16))
    with torch.no_grad():
        ref = model(input_ids=ids).logits.numpy()
    ctm = compile_torch_module(model)
    out = ctm(input_ids=ids)
    logits = out["logits"] if isinstance(out, dict) else getattr(out, "logits", out[0])
    np.testing.assert_allclose(np.asarray(logits), ref, atol=1e-4)


def test_hf_recipe_compile():
    """tt.compile auto-detects PreTrainedModel -> HFTransformers recipe."""
    pytest.importorskip("transformers")
    from transformers import LlamaConfig, LlamaForCausalLM

    import thunder_tpu as tt
    from thunder_tpu.recipes import HFTransformers, resolve_recipe

    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2)
    model = LlamaForCausalLM(cfg).eval()
    assert isinstance(resolve_recipe("auto", model), HFTransformers)
    cm = tt.compile(model)
    ids = torch.randint(0, 64, (1, 8))
    out = cm(input_ids=ids)
    logits = out["logits"] if isinstance(out, dict) else getattr(out, "logits", out[0])
    with torch.no_grad():
        ref = model(input_ids=ids).logits.numpy()
    np.testing.assert_allclose(np.asarray(logits), ref, atol=1e-4)


def test_torch_cnn_with_pooling_and_norms(rng):
    """CNN using the wave-1/2 interop surface (conv+bn+hardswish+pools)."""
    import torch
    import torch.nn as tnn
    import torch.nn.functional as F

    class Net(tnn.Module):
        def __init__(self):
            super().__init__()
            self.conv = tnn.Conv2d(3, 8, 3, padding=1)
            self.bn = tnn.BatchNorm2d(8)
            self.fc = tnn.Linear(8, 10)

        def forward(self, x):
            h = F.hardswish(self.bn(self.conv(x)))
            h = F.max_pool2d(h, 2)
            h = F.adaptive_avg_pool2d(h, (1, 1)).flatten(1)
            return F.log_softmax(self.fc(h), dim=-1)

    net = Net().eval()
    x = torch.randn(2, 3, 16, 16)
    with torch.no_grad():
        want = net(x).numpy()
    got = np.asarray(tt.jit(net)(x))
    np.testing.assert_allclose(got, want, atol=1e-3)


def test_torch_losses_and_unary_surface(rng):
    import torch
    import torch.nn as tnn
    import torch.nn.functional as F

    class M(tnn.Module):
        def forward(self, x, y):
            return F.huber_loss(torch.log1p(torch.exp2(x).clamp_min(0.1)), y) + torch.logaddexp(x, y).sum()

    a = torch.randn(4, 6)
    b = torch.randn(4, 6)
    m = M()
    want = float(m(a, b))
    got = float(tt.jit(m)(a, b))
    np.testing.assert_allclose(got, want, atol=1e-3)


def test_unmapped_op_eager_fallback():
    """An op with no frontend mapping runs eagerly in torch on host instead of
    raising (the graph-split fallback role of reference dynamo/splitter.py:50);
    gradients flow through it via torch.func.vjp.

    The lowered surface now covers every differentiable+meta-safe torch op we
    know of, so the test temporarily unmaps torch.lerp to exercise the
    machinery deterministically."""
    import warnings

    from thunder_tpu.interop import torch_frontend as tf
    from thunder_tpu.ops import auto_register as ar

    class Exotic(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.lin = torch.nn.Linear(8, 8)

        def forward(self, x):
            h = self.lin(x)
            return torch.lerp(h, torch.ones(8, 8), 0.25).sum()

    saved = ar._auto_symbols.pop("auto.lerp")
    tf._eager_symbols.pop(torch.lerp, None)
    tf._eager_warned.discard(torch.lerp)
    try:
        m = Exotic()
        x_t = torch.randn(8, 8)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            cm = tt.jit(m)
            out = cm(jnp.asarray(x_t.numpy()))
        assert any("lerp" in str(x.message) and "eagerly" in str(x.message) for x in w)
        x_ref = x_t.clone().requires_grad_(True)
        ref = m(x_ref)
        np.testing.assert_allclose(float(out), float(ref), atol=1e-4)

        ref.backward()
        loss, grads = tt.value_and_grad(cm)(jnp.asarray(x_t.numpy()))
        name = next(k for k in grads if k.endswith("lin.weight"))
        np.testing.assert_allclose(np.asarray(grads[name]), m.lin.weight.grad.numpy(), atol=1e-3)
    finally:
        ar._auto_symbols["auto.lerp"] = saved
        tf._eager_symbols.pop(torch.lerp, None)


def test_inplace_methods_functionalized():
    """In-place tensor methods (relu_/mul_/add_) run their functional
    counterpart and rebind the receiver's proxy (the reference's interpreter
    in-place functionalization, thunder/core/jit_ext.py)."""

    class InplaceNet(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.lin = torch.nn.Linear(8, 8)

        def forward(self, x):
            h = self.lin(x)
            h = h.clone()
            h.relu_()
            h.mul_(2.0)
            h.add_(1.0)
            return h.sum()

    m = InplaceNet()
    x = torch.randn(4, 8)
    out = float(tt.jit(m)(jnp.asarray(x.numpy())))
    np.testing.assert_allclose(out, float(m(x)), rtol=1e-5)

    # no functional counterpart -> still a loud error, not silent drop
    class Bad(torch.nn.Module):
        def forward(self, x):
            y = x.clone()
            y.exponential_()
            return y.sum()

    with pytest.raises(NotImplementedError):
        tt.jit(Bad())(jnp.ones((4,), jnp.float32))


def test_setitem_and_buffer_mutation_functionalized():
    """y[mask]=v / y[1:3]=c rebind the receiver's proxy; in-place writes to
    module buffers persist across calls via the epilogue."""

    class Masked(torch.nn.Module):
        def forward(self, x):
            y = x.clone()
            y[y > 1] = 0.0
            y[0:1] = 5.0
            return y.sum()

    x = torch.arange(4.0)
    out = float(tt.jit(Masked())(jnp.asarray(x.numpy())))
    np.testing.assert_allclose(out, float(Masked()(x)))

    class Counter(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.register_buffer("step", torch.zeros(()))

        def forward(self, x):
            self.step.add_(1.0)
            return x.sum() + self.step

    cm = tt.jit(Counter())
    xs = jnp.ones((3,), jnp.float32)
    assert [float(cm(xs)) for _ in range(3)] == [4.0, 5.0, 6.0]

    class MF(torch.nn.Module):
        def forward(self, x):
            y = x.clone()
            y.masked_fill_(y > 1, 0.0)  # statement form: effect must persist
            return y.sum()

    out3 = float(tt.jit(MF())(jnp.asarray(x.numpy())))
    np.testing.assert_allclose(out3, float(MF()(x)))

    class DtypeChange(torch.nn.Module):
        def forward(self, x):
            y = x.clone()
            y.div_(2)  # int receiver: torch rejects; we must not silently cast
            return y.sum()

    with pytest.raises(NotImplementedError):
        tt.jit(DtypeChange())(jnp.arange(4))


def test_masked_setitem_element_placement():
    """y[mask] = v with a 1-D v of mask.sum() elements places elements in
    row-major order (torch semantics; advisor r2 finding)."""
    import torch

    class Place(torch.nn.Module):
        def forward(self, x, v):
            y = x.clone()
            y[y > 0] = v
            return y

    x = torch.tensor([[-1.0, 2.0], [3.0, -4.0]])
    v = torch.tensor([10.0, 20.0])
    ref = Place()(x, v)
    out = tt.jit(Place())(jnp.asarray(x.numpy()), jnp.asarray(v.numpy()))
    np.testing.assert_allclose(np.asarray(out), ref.numpy())

    # scalar fill still works
    class Fill(torch.nn.Module):
        def forward(self, x):
            y = x.clone()
            y[y > 0] = 0.5
            return y

    ref2 = Fill()(x)
    out2 = tt.jit(Fill())(jnp.asarray(x.numpy()))
    np.testing.assert_allclose(np.asarray(out2), ref2.numpy())

    # numel-1 multi-dim value broadcasts like a scalar (torch fill semantics)
    class Fill1(torch.nn.Module):
        def forward(self, x):
            y = x.clone()
            y[y > 0] = torch.full((1, 1), 5.0)
            return y

    ref1 = Fill1()(x)
    out1 = tt.jit(Fill1())(jnp.asarray(x.numpy()))
    np.testing.assert_allclose(np.asarray(out1), ref1.numpy())

    # 2-D value: clear NotImplementedError, not a broadcast RuntimeError
    class Bad(torch.nn.Module):
        def forward(self, x, v):
            y = x.clone()
            y[y > 0] = v
            return y

    with pytest.raises(NotImplementedError, match="element placement|1-D"):
        tt.jit(Bad())(jnp.asarray(x.numpy()), jnp.ones((2, 2), jnp.float32))


def test_eager_fallback_int_dtype_with_x64_disabled():
    """An unmapped torch op with integer outputs must produce specs matching
    runtime arrays when jax x64 is off (advisor r2: int64 spec truncation)."""
    import jax
    import torch

    class Buck(torch.nn.Module):
        def forward(self, x, bounds):
            idx = torch.bucketize(x, bounds)  # int64 out in torch
            return idx * 2

    x_np = np.array([0.2, 2.5, 7.0], np.float32)
    b_np = np.array([1.0, 3.0, 5.0], np.float32)
    ref = Buck()(torch.tensor(x_np), torch.tensor(b_np)).numpy()

    with jax.enable_x64(False):
        out = tt.jit(Buck())(jnp.asarray(x_np), jnp.asarray(b_np))
        got = np.asarray(out)
    np.testing.assert_array_equal(got, ref)


def test_tensor_metadata_methods():
    """Static metadata accessors (torch's auto-registered Tensor.* family)."""
    import torch

    class Meta(torch.nn.Module):
        def forward(self, x):
            assert x.ndimension() == 2 and x.nelement() == 6
            assert x.element_size() == 4 and x.is_signed()
            assert not x.is_conj() and x.is_contiguous()
            assert x.is_same_size(x)
            y = x.cpu().to_dense()
            return y.sum() * x.dim()

    out = tt.jit(Meta())(jnp.ones((2, 3), jnp.float32))
    np.testing.assert_allclose(float(out), 12.0)


def test_hf_coverage_harness_subset():
    """The HF coverage harness (reference jit_coverage_hf.py role): fwd+bwd
    parity on two architectures (the full matrix runs via
    `python -m thunder_tpu.interop.hf_coverage`)."""
    pytest.importorskip("transformers")
    from thunder_tpu.interop.hf_coverage import _configs, run_model

    cfgs = _configs()
    for name in ("qwen2", "bert"):
        cfg, kind = cfgs[name]
        rec = run_model(name, cfg, kind)
        assert rec["status"] == "ok", rec
        assert rec["max_abs_err"] < 1e-4 and rec["bwd_max_rel_err"] < 1e-4
        assert rec["fallbacks"] == []
