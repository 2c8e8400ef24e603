"""The three MFU levers (ROADMAP #5a, profiler-driven): collective overlap
scheduling, the fused fp8 scaling kernel, and int8 weight-quantized decode.

Each lever's safety property is held EXACTLY, not approximately:

* the overlap compiler-option config rides the AOT step key, so a config
  flip must MISS the executable cache (never silently reuse a
  non-overlapped program);
* bucketed grad all-reduce (``ddp(..., bucket_mb=)``) is pure data movement
  around the same reduction — bit-identical losses and parameters vs the
  unbucketed program;
* the fused fp8 kernel (quantize + amax + e4m3 dot in one VMEM pass) is
  bit-identical to the unfused four-program reference, because e4m3 values
  are exactly representable in bf16 and both roads accumulate in f32;
* int8 weight-quantized decode is token-identical to bf16 at temperature 0
  when the weights are exactly int8-representable (q * power-of-two scale
  roundtrips through quantize_int8 without error).

Runs entirely under JAX_PLATFORMS=cpu (conftest: 8 virtual devices); the
pallas kernels run in interpret mode.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import nn, optim
from thunder_tpu.ops import ltorch

pytestmark = [
    pytest.mark.perf,
    pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices"),
]


class LossMLP(nn.Module):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(16, 64, seed=1)
        self.fc2 = nn.Linear(64, 8, seed=2)

    def forward(self, x, y):
        return ltorch.mse_loss(self.fc2(ltorch.gelu(self.fc1(x))), y)


def _batch(seed=0, n=16):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, 16), jnp.float32)
    y = jnp.asarray(rng.randn(n, 8), jnp.float32)
    return x, y


# ---------------------------------------------------------------------------
# lever (a): overlap scheduling — config must ride the AOT step key
# ---------------------------------------------------------------------------


class TestOverlapKey:
    def test_resolve_key_semantics(self):
        from thunder_tpu.parallel.overlap import resolve_overlap_options

        opts_off, key_off = resolve_overlap_options(False)
        assert key_off == "nooverlap" and opts_off == {}
        # probe=False: key semantics are backend-independent (the key
        # encodes the REQUESTED config, not the probed subset)
        _, key_on = resolve_overlap_options(True, probe=False)
        assert key_on.startswith("overlap[") and key_on != key_off
        _, key_extra = resolve_overlap_options(
            True, {"xla_something_else": 7}, probe=False)
        assert key_extra not in (key_on, key_off)
        # deterministic: same request, same key
        assert resolve_overlap_options(True, probe=False)[1] == key_on

    def test_probe_filters_unknown_options(self):
        from thunder_tpu.parallel.overlap import supported_compiler_options

        accepted = supported_compiler_options(
            {"xla_definitely_not_a_real_option_name": True})
        assert accepted == {}

    def test_overlap_flip_misses_aot_cache(self):
        """Two gspmd steps differing ONLY in overlap config must produce
        different AOT step keys — a flip is a cache miss, never a silent
        reuse of the other config's executable."""
        from thunder_tpu.parallel import (DistPlan, ParamStrategy, gspmd_step,
                                          make_mesh)

        mesh = make_mesh({"dp": 8})
        x, y = _batch()

        def build(overlap):
            tm = tt.jit(LossMLP())
            plan = DistPlan(mesh, {k: [ParamStrategy("replicate", "dp")]
                                   for k in tm.get_parameters()}, ("dp",))
            step = gspmd_step(tm, optim.AdamW(lr=0.05), plan, overlap=overlap)
            params = {k: p.data for k, p in tm.get_parameters().items()}
            step.opt_state = step.optimizer.init(params)
            return step, params

        step_on, params_on = build(True)
        step_off, params_off = build(False)
        assert step_on._overlap_key != step_off._overlap_key
        key_on = step_on._aot_key(params_on, {}, (x, y), {})
        key_off = step_off._aot_key(params_off, {}, (x, y), {})
        assert key_on != key_off


# ---------------------------------------------------------------------------
# lever (a), explicit road: bucketed grad-sync is bit-identical
# ---------------------------------------------------------------------------


class TestGradBucketing:
    def test_bucketed_bit_identical_to_unbucketed(self):
        """pack -> one all_reduce -> unpack is pure data movement around the
        same reduction: losses AND final params must be exactly equal."""
        from thunder_tpu.parallel import ddp, make_mesh

        x, y = _batch()
        m_ref = LossMLP()
        sd = {k: np.asarray(v).copy() for k, v in m_ref.state_dict().items()}

        def run(bucket_mb):
            m = LossMLP()
            m.load_state_dict(sd)
            tm = tt.jit(m)
            ddp(tm, make_mesh({"dp": 2}), bucket_mb=bucket_mb)
            from thunder_tpu.training import TrainStep

            step = TrainStep(tm, optim.AdamW(lr=1e-2))
            losses = [float(step(x, y)) for _ in range(3)]
            params = {k: np.asarray(v) for k, v in m.state_dict().items()}
            return losses, params

        losses_plain, params_plain = run(None)
        # tiny bucket cap so the pack actually splits into multiple buckets
        losses_bucketed, params_bucketed = run(0.001)
        assert losses_plain == losses_bucketed  # float-exact, not allclose
        for k in params_plain:
            np.testing.assert_array_equal(params_plain[k], params_bucketed[k])

    def test_bucketing_transform_in_repr(self):
        from thunder_tpu.parallel import ddp, make_mesh

        tm = tt.jit(LossMLP())
        ddp(tm, make_mesh({"dp": 2}), bucket_mb=25)
        reprs = [repr(t) for t in tm._cfn._transforms]
        assert any("GradBucketing" in r for r in reprs)


# ---------------------------------------------------------------------------
# lever (b): fused fp8 scaling kernel
# ---------------------------------------------------------------------------


class TestFusedFP8:
    def _ref_unfused(self, x, w, sx, sw, fmt_max):
        """The four-program reference the fusion replaces: quantize x,
        quantize w, e4m3 dot (f32 accumulation), amax reductions."""
        xq = jnp.clip(x.astype(jnp.float32) * sx, -fmt_max, fmt_max
                      ).astype(jnp.float8_e4m3fn)
        wq = jnp.clip(w.astype(jnp.float32) * sw, -fmt_max, fmt_max
                      ).astype(jnp.float8_e4m3fn)
        y = jax.lax.dot_general(
            xq.astype(jnp.bfloat16), wq.astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        y = (y / (sx * sw)).astype(x.dtype)
        ax = jnp.max(jnp.abs(x)).astype(jnp.float32)
        aw = jnp.max(jnp.abs(w)).astype(jnp.float32)
        return y, xq, wq, ax, aw

    def test_kernel_bit_identical_to_unfused(self):
        from thunder_tpu.executors.pallasex import fp8_linear_fused
        from thunder_tpu.transforms.fp8_training import E4M3_MAX

        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(64, 256), jnp.float32)
        w = jnp.asarray(rng.randn(128, 256), jnp.float32)
        sx = float(E4M3_MAX / float(jnp.max(jnp.abs(x))))
        sw = float(E4M3_MAX / float(jnp.max(jnp.abs(w))))
        y_ref, xq_ref, wq_ref, ax_ref, aw_ref = self._ref_unfused(
            x, w, sx, sw, E4M3_MAX)
        y, xq, wq, ax, aw = fp8_linear_fused(
            x, w, sx, sw, fmt_max=E4M3_MAX, save_quantized=True)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
        np.testing.assert_array_equal(np.asarray(xq).view(np.uint8),
                                      np.asarray(xq_ref).view(np.uint8))
        np.testing.assert_array_equal(np.asarray(wq).view(np.uint8),
                                      np.asarray(wq_ref).view(np.uint8))
        assert float(ax) == float(ax_ref) and float(aw) == float(aw_ref)

    def test_kernel_multi_k_block_accumulation(self):
        """K larger than one block exercises the grid-resident accumulator
        and the idempotent amax accumulation across k revisits."""
        from thunder_tpu.executors.pallasex import fp8_linear_fused
        from thunder_tpu.transforms.fp8_training import E4M3_MAX

        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(16, 1024), jnp.float32)
        w = jnp.asarray(rng.randn(128, 1024), jnp.float32)
        sx, sw = 8.0, 4.0  # power-of-two scales: quantize/de-scale exact
        bk = 256
        y_one, _, _, ax_ref, aw_ref = self._ref_unfused(x, w, sx, sw, E4M3_MAX)
        y, ax, aw = fp8_linear_fused(x, w, sx, sw, fmt_max=E4M3_MAX,
                                     block_k=bk)
        # bit-identity holds against a reference that sums partial e4m3
        # dots in the kernel's k-block order (each block dot is exact; only
        # the f32 accumulation split differs from a single whole-K dot)
        acc = jnp.zeros((16, 128), jnp.float32)
        for k0 in range(0, 1024, bk):
            xq = jnp.clip(x[:, k0:k0 + bk] * sx, -E4M3_MAX, E4M3_MAX
                          ).astype(jnp.float8_e4m3fn)
            wq = jnp.clip(w[:, k0:k0 + bk] * sw, -E4M3_MAX, E4M3_MAX
                          ).astype(jnp.float8_e4m3fn)
            acc = acc + jax.lax.dot_general(
                xq.astype(jnp.bfloat16), wq.astype(jnp.bfloat16),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        y_blocked = (acc / (sx * sw)).astype(x.dtype)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_blocked))
        # and the whole-K dot agrees to f32 rounding of the split
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_one),
                                   rtol=1e-5, atol=1e-4)
        assert float(ax) == float(ax_ref) and float(aw) == float(aw_ref)

    def test_checker_requires_tpu_or_force(self, request):
        from thunder_tpu.executors.pallasex import fp8_linear_fused_supported

        x = jnp.zeros((64, 256), jnp.float32)
        w = jnp.zeros((128, 256), jnp.float32)
        assert not fp8_linear_fused_supported(x, w)  # CPU: off by default
        request.getfixturevalue("pallas_claims")
        assert fp8_linear_fused_supported(x, w)
        # misaligned shapes never claim, even forced
        assert not fp8_linear_fused_supported(jnp.zeros((64, 250)), w)

    def test_forced_fused_training_matches_unfused(self, request):
        """End-to-end: the fp8 training transform produces the same losses
        whether the linears dispatch to the fused kernel or the unfused
        four-program road."""
        from thunder_tpu.training import TrainStep
        from thunder_tpu.transforms.fp8_training import FP8TrainingTransform

        rng = np.random.RandomState(2)
        d = 256
        x = jnp.asarray(rng.randn(32, d), jnp.float32)
        y = jnp.asarray(rng.randn(32, d), jnp.float32)

        class Net(nn.Module):
            def __init__(self):
                super().__init__()
                self.fc1 = nn.Linear(d, d, seed=3)
                self.fc2 = nn.Linear(d, d, seed=4)

            def forward(self, xx, yy):
                return ltorch.mse_loss(self.fc2(ltorch.relu(self.fc1(xx))), yy)

        def run():
            tm = tt.jit(Net(), transforms=[FP8TrainingTransform()])
            step = TrainStep(tm, optim.AdamW(lr=1e-2))
            return [float(step(x, y)) for _ in range(3)]

        losses_unfused = run()  # off the chip the checker declines
        request.getfixturevalue("pallas_claims")
        losses_fused = run()
        np.testing.assert_allclose(losses_fused, losses_unfused, rtol=1e-6)


# ---------------------------------------------------------------------------
# lever (c): int8 weight-quantized decode
# ---------------------------------------------------------------------------


def _make_int8_exact(gpt, seed=0):
    """Overwrite every nn.Linear weight with values that roundtrip through
    quantize_int8 without error: w = q * s with integer q (per-row max
    |q| = 127) and a power-of-two scale s. quantize_int8 recovers q and s
    exactly, and q * s is exactly representable in bf16 (7-bit magnitudes
    fit bf16's 8-bit mantissa), so the dequantized matmul sees bitwise the
    original weights."""
    rng = np.random.RandomState(seed)
    for name, mod in gpt.named_modules():
        if isinstance(mod, nn.Linear):
            out_f, in_f = np.asarray(mod.weight.data).shape
            q = rng.randint(-126, 127, size=(out_f, in_f)).astype(np.float64)
            q[:, 0] = 127.0  # pin the per-row amax so scale == s exactly
            s = 2.0 ** -9  # power of two: amax/127 divides out exactly
            mod.weight.data = jnp.asarray(q * s, jnp.float32)


class TestInt8Decode:
    def _gpt(self):
        from thunder_tpu.models.litgpt import GPT, Config

        cfg = Config.from_name("tiny-llama2", block_size=64)
        return GPT(cfg, dtype=jnp.float32)

    def test_quantize_int8_exact_roundtrip(self):
        from thunder_tpu.transforms.quantization import quantize_int8

        gpt = self._gpt()
        _make_int8_exact(gpt)
        w = jnp.asarray(gpt.lm_head.weight.data)
        q, s = quantize_int8(w)
        deq = (q.astype(jnp.bfloat16) * s.astype(jnp.bfloat16)[:, None]
               ).astype(jnp.float32)
        np.testing.assert_array_equal(np.asarray(deq), np.asarray(w))

    def test_int8_decode_token_identical(self):
        """Greedy streams from a bf16-weights engine and an int8-quantized
        engine over the SAME (exactly-representable) weights must match
        token for token."""
        from thunder_tpu.serving import ServingEngine

        gpt_a = self._gpt()
        _make_int8_exact(gpt_a)
        sd = {k: np.asarray(v).copy() for k, v in gpt_a.state_dict().items()}
        gpt_b = self._gpt()
        gpt_b.load_state_dict(sd)

        kw = dict(max_batch=4, page_size=8, max_seq=64, dtype=jnp.float32)
        eng_a = ServingEngine(gpt_a, **kw)
        eng_b = ServingEngine(gpt_b, quantize="int8", **kw)

        rng = np.random.RandomState(7)
        prompts = [rng.randint(0, 320, (n,)).astype(np.int32)
                   for n in (5, 11, 17)]
        futs_a = [eng_a.submit(p, max_new_tokens=8) for p in prompts]
        futs_b = [eng_b.submit(p, max_new_tokens=8) for p in prompts]
        eng_a.drain()
        eng_b.drain()
        for fa, fb in zip(futs_a, futs_b):
            ra, rb = fa.result(), fb.result()
            assert ra.n_new_tokens == 8
            np.testing.assert_array_equal(ra.new_tokens, rb.new_tokens)

    def test_quantize_for_serving_modes(self):
        from thunder_tpu.serving.runner import quantize_for_serving

        gpt = self._gpt()
        assert quantize_for_serving(gpt, None) is gpt
        assert quantize_for_serving(gpt, "none") is gpt
        with pytest.raises(ValueError, match="quantization mode"):
            quantize_for_serving(gpt, "int4")

    def test_int8_kernel_checker_gated_off_tpu(self, request):
        """Unless a test turns the claim on, the interpret-mode kernel must not
        claim the op on CPU — serving there measures the XLA dequant-matmul,
        not a per-call interpreter."""
        from thunder_tpu.executors.pallasex import _int8_linear_supported

        x = jnp.zeros((8, 256), jnp.bfloat16)
        q = jnp.zeros((128, 256), jnp.int8)
        s = jnp.zeros((128,), jnp.float32)
        assert not _int8_linear_supported(x, q, s)
        request.getfixturevalue("pallas_claims")
        assert _int8_linear_supported(x, q, s)
