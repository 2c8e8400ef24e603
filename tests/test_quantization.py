"""NF4 / int8 / fp8 weight quantization (reference analogs:
BitsAndBytesLinearQuant4bit thunder/transforms/quantization.py:47,
TEInference8BitTransform thunder/transforms/te_inference.py:116)."""
import numpy as np
import jax.numpy as jnp
import pytest

import thunder_tpu as tt
from thunder_tpu import nn, optim
from thunder_tpu.ops import ltorch


class _Net(nn.Module):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(64, 32, seed=1)
        self.fc2 = nn.Linear(32, 8, seed=2)

    def forward(self, x):
        return self.fc2(ltorch.relu(self.fc1(x)))


def test_nf4_roundtrip(rng):
    from thunder_tpu.transforms.quantization import dequantize_nf4, quantize_nf4

    w = rng.randn(16, 64).astype(np.float32)
    packed, absmax = quantize_nf4(w)
    deq = np.asarray(dequantize_nf4(packed, absmax, (16, 64)))
    # NF4 is lossy, but per-block relative error should be bounded
    err = np.abs(deq - w).max() / np.abs(w).max()
    assert err < 0.15, err
    assert np.asarray(packed).dtype == np.uint8
    assert packed.size == w.size // 2


def test_nf4_transform_forward(rng):
    from thunder_tpu.transforms.quantization import QuantizeNF4Transform

    net = _Net()
    x = jnp.asarray(rng.rand(4, 64).astype(np.float32))
    ref = np.asarray(tt.jit(net)(x))
    net2 = _Net()
    tm = tt.jit(net2, transforms=[QuantizeNF4Transform(target_predicate=lambda n, m: n == "fc1")])
    out = np.asarray(tm(x))
    assert out.shape == ref.shape
    # quantized forward approximates the full-precision one
    assert np.abs(out - ref).max() < 0.2 * max(1.0, np.abs(ref).max())


def test_nf4_grad_flows_to_activations(rng):
    from thunder_tpu.transforms.quantization import QuantizeNF4Transform

    net = _Net()

    class Head(nn.Module):
        def __init__(self):
            super().__init__()
            self.body = net

        def forward(self, x, y):
            return ltorch.mse_loss(self.body(x), y)

    tm = tt.jit(Head(), transforms=[QuantizeNF4Transform(target_predicate=lambda n, m: n.endswith("fc1"))])
    from thunder_tpu.training import TrainStep

    step = TrainStep(tm, optim.AdamW(lr=0.05))
    x = jnp.asarray(rng.rand(8, 64).astype(np.float32))
    y = jnp.asarray(rng.rand(8, 8).astype(np.float32))
    l0 = float(step(x, y))
    for _ in range(5):
        step(x, y)
    assert float(step(x, y)) < l0


def test_fp8_weight_roundtrip(rng):
    from thunder_tpu.transforms.fp8_inference import quantize_fp8_weight

    w = rng.randn(16, 32).astype(np.float32)
    q, s = quantize_fp8_weight(w)
    deq = np.asarray(q, np.float32) * np.asarray(s)[:, None]
    rel = np.abs(deq - w).max() / np.abs(w).max()
    assert rel < 0.1, rel


def test_fp8_transform_forward(rng):
    from thunder_tpu.transforms.fp8_inference import FP8LinearInference

    net = _Net()
    x = jnp.asarray(rng.rand(4, 64).astype(np.float32))
    ref = np.asarray(tt.jit(net)(x))
    net2 = _Net()
    tm = tt.jit(net2, transforms=[FP8LinearInference(min_features=8)])
    out = np.asarray(tm(x))
    assert out.shape == ref.shape
    assert np.abs(out - ref).max() < 0.25 * max(1.0, np.abs(ref).max())


def test_extraction_only_prologue(rng):
    from thunder_tpu.transforms import ExtractionOnlyPrologueTransform
    from thunder_tpu.core.prims import PrimIDs

    tm = tt.jit(_Net(), transforms=[ExtractionOnlyPrologueTransform()])
    x = jnp.asarray(rng.rand(2, 64).astype(np.float32))
    tm(x)
    pro = tm.last_prologue_traces()[-1] if hasattr(tm, "last_prologue_traces") else None
    if pro is not None:
        check_ids = {PrimIDs.CHECK_TENSOR_SHAPE_AND_METADATA, PrimIDs.CHECK_NUMBER_TYPE_AND_VALUE}
        assert not [b for b in pro.bound_symbols if b.sym.id in check_ids]


def test_nf4_nondefault_block_size(rng):
    from thunder_tpu.transforms.quantization import QuantizeNF4Transform

    net = _Net()
    x = jnp.asarray(rng.rand(4, 64).astype(np.float32))
    ref = np.asarray(tt.jit(net)(x))
    net2 = _Net()
    tm = tt.jit(net2, transforms=[QuantizeNF4Transform(block_size=32)])
    out = np.asarray(tm(x))
    assert out.shape == ref.shape
    assert np.abs(out - ref).max() < 0.2 * max(1.0, np.abs(ref).max())


def test_quantized_bias_trains(rng):
    """Bias of a quantized linear must receive real (non-zero) gradients."""
    from thunder_tpu.transforms.quantization import QuantizeInt8Transform
    from thunder_tpu.training import TrainStep

    class Head(nn.Module):
        def __init__(self):
            super().__init__()
            self.body = _Net()

        def forward(self, x, y):
            return ltorch.mse_loss(self.body(x), y)

    net = Head()
    tm = tt.jit(net, transforms=[QuantizeInt8Transform(target_predicate=lambda n, m: n.endswith("fc2"))])
    b_before = np.asarray(net.body.fc2._parameters["bias"].data).copy()
    step = TrainStep(tm, optim.AdamW(lr=0.05))
    x = jnp.asarray(rng.rand(8, 64).astype(np.float32))
    y = jnp.asarray(rng.rand(8, 8).astype(np.float32))
    for _ in range(3):
        step(x, y)
    b_after = np.asarray(net.body.fc2._parameters["bias"].data)
    assert np.abs(b_after - b_before).max() > 1e-5, "bias froze under quantization"


class TestFusedInt8Linear:
    """The Pallas dequant-in-kernel linear (executors/pallasex.py int8_linear):
    weights stay int8-resident in HBM — XLA's separate-dequant path hoists the
    dequant out of loops and materializes bf16 weights, defeating weight-only
    quantization's memory saving."""

    def test_kernel_matches_dequant_reference(self, rng):
        import jax.numpy as jnp

        from thunder_tpu.executors import pallasex as px

        x = jnp.asarray(rng.randn(8, 512).astype(np.float32), jnp.bfloat16)
        w = jnp.asarray(np.clip(np.round(rng.randn(256, 512) * 40), -127, 127), jnp.int8)
        s = jnp.asarray(np.abs(rng.randn(256)) * 1e-3 + 1e-4, jnp.float32)
        got = np.asarray(px.int8_linear(x, w, s), np.float32)
        want = np.asarray(x, np.float32) @ (np.asarray(w, np.float32) * np.asarray(s)[:, None]).T
        np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)

    def test_pallas_claims_quantized_linear(self, rng, pallas_claims):
        import jax.numpy as jnp

        import thunder_tpu as tt
        from thunder_tpu import nn
        from thunder_tpu.executors import pallasex as px
        from thunder_tpu.transforms.quantization import QuantizeInt8Transform

        # the checker declines off-TPU (interpret mode is a debug path, not
        # a serving path); the fixture turns the claim on to exercise the kernel
        calls = {"n": 0}
        orig = px._int8_linear_impl

        def spy(*a, **k):
            calls["n"] += 1
            return orig(*a, **k)

        px.ex.register_implementation("quant.linear_int8", spy,
                                      checker=px._int8_linear_supported)
        try:
            class Net(nn.Module):
                def __init__(self):
                    super().__init__()
                    self.fc = nn.Linear(512, 256, seed=1)

                def forward(self, x):
                    return self.fc(x)

            net = Net()
            ref_w = np.asarray(net.fc.weight.data)
            tm = tt.jit(net, transforms=[QuantizeInt8Transform()])
            x = jnp.asarray(rng.randn(8, 512).astype(np.float32))
            out = np.asarray(tm(x), np.float32)
            assert calls["n"] >= 1, "pallas did not claim quant.linear_int8"
            want = np.asarray(x) @ ref_w.T
            np.testing.assert_allclose(out, want, atol=0.05, rtol=0.05)
        finally:
            px.ex.register_implementation("quant.linear_int8", orig,
                                          checker=px._int8_linear_supported)

    def test_checker_declines_large_m_and_odd_shapes(self, rng, pallas_claims):
        from thunder_tpu.core.proxies import TensorProxy
        from thunder_tpu.core import dtypes as dt
        from thunder_tpu.executors import pallasex as px

        def p(shape, dtype=dt.bfloat16):
            return TensorProxy(shape=shape, dtype=dtype, device=None)

        ok = px._int8_linear_supported(p((8, 512)), p((256, 512), dt.int8), p((256,), dt.float32))
        assert ok
        # prefill-size M stays on the XLA path
        assert not px._int8_linear_supported(p((4096, 512)), p((256, 512), dt.int8), p((256,), dt.float32))
        # non-128-multiple N declines
        assert not px._int8_linear_supported(p((8, 512)), p((250, 512), dt.int8), p((250,), dt.float32))
        # non-int8 weights decline
        assert not px._int8_linear_supported(p((8, 512)), p((256, 512)), p((256,), dt.float32))


class TestFusedNF4Linear:
    """Opt-in 4-bit serving kernel (executors/pallasex.py nf4_linear):
    weights stay PACKED in HBM (0.5 byte/element) at ~bf16 speed — the
    bitsandbytes footprint-over-speed trade, TPU-native."""

    def test_kernel_matches_canonical_dequant(self, rng):
        import jax.numpy as jnp

        from thunder_tpu.executors import pallasex as px
        from thunder_tpu.transforms.quantization import dequantize_nf4, quantize_nf4

        N, K, M = 512, 1024, 8
        w = rng.randn(N, K).astype(np.float32) * 0.05
        packed, absmax = quantize_nf4(jnp.asarray(w))
        pkl, akl = px.pack_nf4_kernel_layout(packed, absmax, (N, K))
        x = jnp.asarray(rng.randn(M, K).astype(np.float32), jnp.bfloat16)
        got = np.asarray(px.nf4_linear(x, pkl, akl), np.float32)
        want = (np.asarray(x, np.float32)
                @ np.asarray(dequantize_nf4(packed, absmax, (N, K)), np.float32).T)
        np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)

    def test_pack_roundtrip_is_bitexact(self, rng):
        import jax.numpy as jnp

        from thunder_tpu.executors import pallasex as px
        from thunder_tpu.transforms.quantization import quantize_nf4

        N, K = 128, 1024
        w = rng.randn(N, K).astype(np.float32)
        packed, absmax = quantize_nf4(jnp.asarray(w))
        pkl, _ = px.pack_nf4_kernel_layout(packed, absmax, (N, K))
        # un-permute the kernel layout and compare code streams bit-exactly
        bk = min(px.NF4_KERNEL_BLOCK_K, K)
        hi = (np.asarray(packed) >> 4) & 0xF
        lo = np.asarray(packed) & 0xF
        nat = np.zeros((N, K), np.uint8)
        nat.reshape(-1)[0::2] = hi
        nat.reshape(-1)[1::2] = lo
        rebuilt = np.zeros((N, K), np.uint8)
        pk = np.asarray(pkl)
        for j0 in range(0, K, bk):
            blk = pk[:, j0 // 2:(j0 + bk) // 2]
            rebuilt[:, j0:j0 + bk // 2] = (blk >> 4) & 0xF
            rebuilt[:, j0 + bk // 2:j0 + bk] = blk & 0xF
        np.testing.assert_array_equal(rebuilt, nat)
