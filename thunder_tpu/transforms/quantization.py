"""Weight quantization: int8 per-channel weight-only quantized linears.

Re-design of reference thunder/transforms/quantization.py:47
(BitsAndBytesLinearQuant4bit: swap params for quantized tensors + rewrite
linears to a dequant-matmul executor op). TPU-native: NF4/bnb is a CUDA
library, so the quantized format here is symmetric per-output-channel int8
(VPU-friendly dequant fused into the matmul's epilogue by XLA; an int4/Pallas
quantized-matmul kernel is the upgrade path)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dtypes
from ..core.proxies import TensorProxy
from ..core.symbol import OpTags, Symbol
from ..core.transform_common import Transform
from ..executors.jaxex import ex as jax_ex
from ..nn.module import Parameter
from ..ops import clang
from .autodiff import VJPResult, register_augmented_forward, register_backward


def quantize_int8(w) -> tuple:
    """w (out, in) -> (int8 weights, f32 per-row scales)."""
    amax = jnp.max(jnp.abs(w), axis=1, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / 127.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, scale[:, 0]


def _quantized_linear_meta(x, qweight, scale, bias=None):
    return TensorProxy(shape=x.shape[:-1] + (qweight.shape[0],), dtype=x.dtype, device=x.device)


def _quantized_linear_impl(x, qweight, scale, bias=None):
    w = qweight.astype(jnp.bfloat16) * scale.astype(jnp.bfloat16)[:, None]
    out = jnp.matmul(x, w.T.astype(x.dtype), preferred_element_type=jnp.float32).astype(x.dtype)
    if bias is not None:
        out = out + bias
    return out


quantized_linear = Symbol(
    "quantized_linear", _quantized_linear_meta, id="quant.linear_int8", is_prim=True, module="quant",
    tags=(OpTags.MATMUL_OP,),
)
jax_ex.register_implementation(quantized_linear.id, _quantized_linear_impl)


@register_augmented_forward(quantized_linear.id)
def _qlin_aug(x, qweight, scale, bias=None):
    return VJPResult(quantized_linear(x, qweight, scale, bias), (qweight, scale, bias is not None))


@register_backward(quantized_linear.id)
def _qlin_bwd(qweight, scale, has_bias, g):
    # weight frozen: dx through the dequantized matmul; bias stays trainable
    from ..core import prims

    wq = prims.convert_element_type(qweight, dtypes.bfloat16)
    w = prims.mul(wq, clang.expand_to(clang.unsqueeze(prims.convert_element_type(scale, dtypes.bfloat16), 1), wq.shape))
    gx = prims.matmul(prims.convert_element_type(g, dtypes.bfloat16), w)
    gx = prims.convert_element_type(gx, g.dtype)
    if has_bias:
        gbias = prims.sum_prim(g, tuple(range(g.ndim - 1))) if g.ndim > 1 else g
        # tensor-order grads: (x, qweight, scale, bias)
        return gx, None, None, gbias
    return gx, None, None


class QuantizedLinear:
    """Module stand-in recorded by QuantizeInt8Transform."""

    def __init__(self, qweight, scale, bias):
        self.qweight = qweight
        self.scale = scale
        self.bias = bias


class QuantizeInt8Transform(Transform):
    """Swap nn.Linear weights for int8 + rewrite forwards (transform_module
    hook, mirroring the reference's param-override approach,
    thunder/core/module.py:30 + quantization.py:47)."""

    def __init__(self, target_predicate=None):
        self.target_predicate = target_predicate or (lambda name, mod: True)

    def transform_module(self, tmodule) -> None:
        from .. import nn as _nn

        root = tmodule.module if hasattr(tmodule, "module") else tmodule
        for name, mod in list(root.named_modules()):
            if not isinstance(mod, _nn.Linear) or not self.target_predicate(name, mod):
                continue
            q, s = quantize_int8(jnp.asarray(mod.weight.data))
            qp = Parameter(q, requires_grad=False)
            sp = Parameter(s, requires_grad=False)
            mod._parameters["weight"] = qp
            mod.register_parameter("scale", sp)

            def make_fwd(m):
                def forward(x):
                    return quantized_linear(x, m._parameters["weight"], m._parameters["scale"],
                                            m._parameters.get("bias"))

                return forward

            mod.forward = make_fwd(mod)


# ---------------------------------------------------------------------------
# NF4 (4-bit normal-float) weight quantization — the direct analog of the
# reference's BitsAndBytesLinearQuant4bit (thunder/transforms/quantization.py:47),
# re-designed for TPU: codebook dequant is a 16-entry take (VPU gather),
# two 4-bit codes packed per int8, per-block absmax scales.
# ---------------------------------------------------------------------------

# bitsandbytes NF4 codebook (quantiles of a standard normal, public constant)
# (a numpy array: a jax array here would initialise the backend, and take
# the chip, when the package is merely imported)
NF4_CODE = np.asarray([
    -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
    -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
    0.07958029955625534, 0.16093020141124725, 0.24611230194568634, 0.33791524171829224,
    0.44070982933044434, 0.5626170039176941, 0.7229568362236023, 1.0,
], dtype=np.float32)


def quantize_nf4(w, block_size: int = 64) -> tuple:
    """w (out, in) -> (packed uint8 codes (out*in//2,), f32 absmax per block).

    in-dim must be divisible by block_size (pad upstream if not)."""
    out_f, in_f = w.shape
    flat = jnp.asarray(w, jnp.float32).reshape(-1, block_size)
    absmax = jnp.maximum(jnp.max(jnp.abs(flat), axis=1, keepdims=True), 1e-12)
    normed = flat / absmax
    codes = jnp.argmin(jnp.abs(normed[..., None] - NF4_CODE), axis=-1).astype(jnp.uint8)
    codes = codes.reshape(-1)
    packed = (codes[0::2] << 4) | codes[1::2]
    return packed, absmax[:, 0]


def dequantize_nf4(packed, absmax, shape, block_size: int = 64):
    hi = (packed >> 4) & 0xF
    lo = packed & 0xF
    codes = jnp.stack([hi, lo], axis=1).reshape(-1)
    vals = jnp.asarray(NF4_CODE)[codes].reshape(-1, block_size) * absmax[:, None]
    return vals.reshape(shape)


def _nf4_linear_meta(x, packed, absmax, out_features, in_features, block_size=64, bias=None):
    from ..core.proxies import pyval

    return TensorProxy(shape=x.shape[:-1] + (int(pyval(out_features)),), dtype=x.dtype, device=x.device)


def _nf4_linear_impl(x, packed, absmax, out_features, in_features, block_size=64, bias=None):
    w = dequantize_nf4(packed, absmax, (out_features, in_features), block_size).astype(jnp.bfloat16)
    out = jnp.matmul(x, w.T.astype(x.dtype), preferred_element_type=jnp.float32).astype(x.dtype)
    if bias is not None:
        out = out + bias
    return out


nf4_linear = Symbol(
    "nf4_linear", _nf4_linear_meta, id="quant.linear_nf4", is_prim=True, module="quant",
    tags=(OpTags.MATMUL_OP,),
)
jax_ex.register_implementation(nf4_linear.id, _nf4_linear_impl)


@register_augmented_forward(nf4_linear.id)
def _nf4_aug(x, packed, absmax, out_features, in_features, block_size=64, bias=None):
    return VJPResult(nf4_linear(x, packed, absmax, out_features, in_features, block_size, bias),
                     (packed, absmax, out_features, in_features, block_size, bias is not None))


@register_backward(nf4_linear.id)
def _nf4_bwd(packed, absmax, out_features, in_features, block_size, has_bias, g):
    from ..core import prims

    w = nf4_dequant_sym(packed, absmax, out_features, in_features, block_size)
    gx = prims.matmul(prims.convert_element_type(g, dtypes.bfloat16),
                      prims.convert_element_type(w, dtypes.bfloat16))
    gx = prims.convert_element_type(gx, g.dtype)
    if has_bias:
        gbias = prims.sum_prim(g, tuple(range(g.ndim - 1))) if g.ndim > 1 else g
        # tensor-order grads: (x, packed, absmax, bias)
        return gx, None, None, gbias
    return gx, None, None


def _nf4_dequant_meta(packed, absmax, out_features, in_features, block_size=64):
    from ..core.proxies import pyval

    return TensorProxy(shape=(int(pyval(out_features)), int(pyval(in_features))),
                       dtype=dtypes.float32, device=packed.device)


nf4_dequant_sym = Symbol("nf4_dequant", _nf4_dequant_meta, id="quant.nf4_dequant", is_prim=True, module="quant")
jax_ex.register_implementation(nf4_dequant_sym.id,
                               lambda packed, absmax, o, i, block_size=64: dequantize_nf4(packed, absmax, (o, i), block_size))


class QuantizeNF4Transform(Transform):
    """4-bit NF4 weight-only quantization of nn.Linear layers (reference
    BitsAndBytesLinearQuant4bit analog)."""

    def __init__(self, target_predicate=None, block_size: int = 64):
        self.target_predicate = target_predicate or (lambda name, mod: True)
        self.block_size = block_size

    def transform_module(self, tmodule) -> None:
        from .. import nn as _nn

        root = tmodule.module if hasattr(tmodule, "module") else tmodule
        for name, mod in list(root.named_modules()):
            if not isinstance(mod, _nn.Linear) or not self.target_predicate(name, mod):
                continue
            w = jnp.asarray(mod.weight.data)
            out_f, in_f = w.shape
            if in_f % self.block_size:
                continue  # non-divisible layers stay full precision
            packed, absmax = quantize_nf4(w, self.block_size)
            from ..executors.pallasex import nf4_kernel_block_k

            kernel_ok = (
                self.block_size == 64 and out_f % 128 == 0
                and nf4_kernel_block_k(in_f, self.block_size) is not None
            )
            if kernel_ok:
                # store the fused kernel's halves-per-slice layout: decode
                # steps read 4-bit weights directly, no per-step repack
                from ..executors.pallasex import pack_nf4_kernel_layout

                pkl, akl = pack_nf4_kernel_layout(packed, absmax, (out_f, in_f), self.block_size)
                mod._parameters["weight"] = Parameter(pkl, requires_grad=False)
                mod.register_parameter("absmax", Parameter(akl, requires_grad=False))

                def make_fwd_kl(m, o, i, bs):
                    def forward(x):
                        return nf4_linear_kl(x, m._parameters["weight"], m._parameters["absmax"],
                                             o, i, bs, m._parameters.get("bias"))

                    return forward

                mod.forward = make_fwd_kl(mod, out_f, in_f, self.block_size)
                continue
            mod._parameters["weight"] = Parameter(packed, requires_grad=False)
            mod.register_parameter("absmax", Parameter(absmax, requires_grad=False))

            def make_fwd(m, o, i, bs):
                def forward(x):
                    return nf4_linear(x, m._parameters["weight"], m._parameters["absmax"], o, i, bs,
                                      m._parameters.get("bias"))

                return forward

            mod.forward = make_fwd(mod, out_f, in_f, self.block_size)


# ---------------------------------------------------------------------------
# kernel-layout NF4 linear: weights stored in the fused Pallas kernel's
# halves-per-slice packing at TRANSFORM time, so decode steps never repack
# (repack ops inside a lax.scan body are not reliably hoisted by XLA)
# ---------------------------------------------------------------------------

NF4_KL_BLOCK_K = 512


def dequantize_nf4_kl(packed_kl, absmax_kl, shape, block_size: int = 64,
                      block_k=None):
    """Kernel-layout NF4 -> full weights (the jax fallback/dequant path:
    within each block_k slice of a row, hi nibbles cover the first half)."""
    from ..executors.pallasex import nf4_kernel_block_k

    N, K = shape
    bk = block_k or nf4_kernel_block_k(K, block_size)
    code = jnp.asarray(NF4_CODE)
    parts = []
    for j0 in range(0, K, bk):
        byts = packed_kl[:, j0 // 2:(j0 + bk) // 2].astype(jnp.int32)
        hi = (byts >> 4) & 0xF
        lo = byts & 0xF
        parts.append(jnp.concatenate([code[hi], code[lo]], axis=-1))
    w = jnp.concatenate(parts, axis=1)
    am = jnp.repeat(absmax_kl.reshape(N, K // block_size), block_size, axis=1)
    return w * am


def _nf4_linear_kl_meta(x, packed_kl, absmax_kl, out_features, in_features,
                        block_size=64, bias=None):
    from ..core.proxies import pyval

    return TensorProxy(shape=x.shape[:-1] + (int(pyval(out_features)),), dtype=x.dtype,
                       device=x.device)


def _nf4_linear_kl_impl(x, packed_kl, absmax_kl, out_features, in_features,
                        block_size=64, bias=None):
    w = dequantize_nf4_kl(packed_kl, absmax_kl, (out_features, in_features),
                          block_size).astype(jnp.bfloat16)
    out = jnp.matmul(x, w.T.astype(x.dtype), preferred_element_type=jnp.float32).astype(x.dtype)
    if bias is not None:
        out = out + bias
    return out


nf4_linear_kl = Symbol(
    "nf4_linear_kl", _nf4_linear_kl_meta, id="quant.linear_nf4_kl", is_prim=True,
    module="quant", tags=(OpTags.MATMUL_OP,),
)
jax_ex.register_implementation(nf4_linear_kl.id, _nf4_linear_kl_impl)


@register_augmented_forward(nf4_linear_kl.id)
def _nf4_kl_aug(x, packed_kl, absmax_kl, out_features, in_features, block_size=64, bias=None):
    return VJPResult(
        nf4_linear_kl(x, packed_kl, absmax_kl, out_features, in_features, block_size, bias),
        (packed_kl, absmax_kl, out_features, in_features, block_size, bias is not None))


@register_backward(nf4_linear_kl.id)
def _nf4_kl_bwd(packed_kl, absmax_kl, out_features, in_features, block_size, has_bias, g):
    from ..core import prims

    w = dequant_nf4_kl_sym(packed_kl, absmax_kl, out_features, in_features, block_size)
    wb = prims.convert_element_type(w, dtypes.bfloat16)
    gx = prims.matmul(prims.convert_element_type(g, dtypes.bfloat16), wb)
    gx = prims.convert_element_type(gx, g.dtype)
    if has_bias:
        gbias = prims.sum_prim(g, tuple(range(g.ndim - 1))) if g.ndim > 1 else g
        return gx, None, None, None, None, None, gbias
    return gx, None, None, None, None, None


def _dequant_nf4_kl_meta(packed_kl, absmax_kl, out_features, in_features, block_size=64):
    from ..core.proxies import pyval

    return TensorProxy(shape=(int(pyval(out_features)), int(pyval(in_features))),
                       dtype=dtypes.float32, device=packed_kl.device)


dequant_nf4_kl_sym = Symbol("nf4_dequant_kl", _dequant_nf4_kl_meta,
                            id="quant.nf4_dequant_kl", is_prim=True, module="quant")
jax_ex.register_implementation(
    dequant_nf4_kl_sym.id,
    lambda packed_kl, absmax_kl, o, i, block_size=64: dequantize_nf4_kl(
        packed_kl, absmax_kl, (o, i), block_size))
