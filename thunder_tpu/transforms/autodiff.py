"""Trace-level autodiff: augmented-forward + backward trace construction.

Re-design of reference thunder/transforms/autodiff.py:28 (grad transform),
:465 (forward/backward split) and the grad-rule registry in
thunder/core/transforms.py:668-1713. The transform walks the acquired trace
top-down: a bsym whose symbol id has a registered VJP rule is differentiated
at that level (this is how executor-claimed grads work — Pallas flash
attention registers a rule for `torch.sdpa` and is never decomposed);
otherwise the walk descends into subsymbols down to prims. The result is two
traces — augmented forward (returns outputs + saved-for-backward) and
backward (saved + cotangents → input grads) — each independently claimed and
XLA-fused.

Ops with no hand-written rule can fall back to `jax.vjp` of their jax impl
(kept out of fusion regions so the vjp closure can be carried as an opaque
saved object)."""
from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple, Optional, Sequence

from ..core import dtypes, prims
from ..core.prims import PrimIDs
from ..core.proxies import NumberProxy, Proxy, TensorProxy, variableify
from ..core.symbol import BoundSymbol, OpTags, Symbol
from ..core.trace import TraceCtx, from_trace, rebinding, tracectx
from ..core.transform_common import dce
from ..common import EpilogueMixin
from ..ops import clang


class VJPResult(NamedTuple):
    out: Any
    residuals: tuple


augmented_forward_impls: dict[Any, Callable] = {}
backward_impls: dict[Any, Callable] = {}


def register_augmented_forward(sym_id):
    def deco(fn):
        augmented_forward_impls[sym_id] = fn
        return fn

    return deco


def register_backward(sym_id):
    def deco(fn):
        backward_impls[sym_id] = fn
        return fn

    return deco


def register_grad(sym_id, aug_fwd, bwd):
    augmented_forward_impls[sym_id] = aug_fwd
    backward_impls[sym_id] = bwd


def has_grad_rule(sym_id) -> bool:
    return sym_id in augmented_forward_impls


# ops that fall back to jax.vjp of their jax impl (op-by-op, unfused)
JAX_VJP_FALLBACK: set = {
    PrimIDs.CONVOLUTION, PrimIDs.GROUPED_MM, PrimIDs.ATAN2, PrimIDs.CUMSUM,
    PrimIDs.CUMPROD, PrimIDs.REDUCE_WINDOW, PrimIDs.CONV_TRANSPOSE, PrimIDs.EINSUM,
    PrimIDs.DIGAMMA, PrimIDs.SCATTER, PrimIDs.COPY_WITH_SETITEM,
    PrimIDs.VAR,
}


# ---------------------------------------------------------------------------
# helpers used inside rules
# ---------------------------------------------------------------------------


def _sum_to_shape(g: TensorProxy, shape: tuple) -> TensorProxy:
    """Reduce a broadcasted gradient back to `shape`."""
    if tuple(g.shape) == tuple(shape):
        return g
    # sum leading extra dims
    extra = g.ndim - len(shape)
    if extra > 0:
        g = prims.sum_prim(g, tuple(range(extra)))
    # sum dims that were 1
    dims = tuple(i for i, s in enumerate(shape) if s == 1 and g.shape[i] != 1)
    if dims:
        g = prims.sum_prim(g, dims)
        # restore kept dims
        new_shape = tuple(1 if i in dims else s for i, s in enumerate(shape))
        g = prims.reshape(g, new_shape)
    return g


def _zeros_like(t: TensorProxy) -> TensorProxy:
    return clang.full_like(t, 0)


# ---------------------------------------------------------------------------
# elementwise rules
# ---------------------------------------------------------------------------


register_grad(PrimIDs.ADD, lambda a, b: VJPResult(prims.add(a, b), ()),
              lambda g: (g, g))
register_grad(PrimIDs.SUB, lambda a, b: VJPResult(prims.sub(a, b), ()),
              lambda g: (g, prims.neg(g)))


@register_augmented_forward(PrimIDs.MUL)
def _mul_aug(a, b):
    return VJPResult(prims.mul(a, b), (a, b))


@register_backward(PrimIDs.MUL)
def _mul_bwd(a, b, g):
    return prims.mul(g, b), prims.mul(g, a)


@register_augmented_forward(PrimIDs.DIV)
def _div_aug(a, b):
    out = prims.div(a, b)
    return VJPResult(out, (a, b))


@register_backward(PrimIDs.DIV)
def _div_bwd(a, b, g):
    ga = prims.div(g, b)
    gb = prims.neg(prims.div(prims.mul(g, prims.div(a, b)), b))
    return ga, gb


@register_augmented_forward(PrimIDs.POW)
def _pow_aug(a, b):
    out = prims.pow(a, b)
    return VJPResult(out, (a, b, out))


@register_backward(PrimIDs.POW)
def _pow_bwd(a, b, out, g):
    one = clang.full_like(b, 1)
    ga = prims.mul(g, prims.mul(b, prims.pow(a, prims.sub(b, one))))
    # d/db a^b = out * log(a); guard log of nonpositive
    safe_a = prims.maximum(a, clang.full_like(a, 1e-30))
    gb = prims.mul(g, prims.mul(out, prims.log(safe_a)))
    return ga, gb


register_grad(PrimIDs.NEG, lambda a: VJPResult(prims.neg(a), ()), lambda g: prims.neg(g))


@register_augmented_forward(PrimIDs.ABS)
def _abs_aug(a):
    return VJPResult(prims.abs(a), (a,))


@register_backward(PrimIDs.ABS)
def _abs_bwd(a, g):
    return prims.mul(g, prims.sign(a))


@register_augmented_forward(PrimIDs.EXP)
def _exp_aug(a):
    out = prims.exp(a)
    return VJPResult(out, (out,))


@register_backward(PrimIDs.EXP)
def _exp_bwd(out, g):
    return prims.mul(g, out)


@register_augmented_forward(PrimIDs.LOG)
def _log_aug(a):
    return VJPResult(prims.log(a), (a,))


@register_backward(PrimIDs.LOG)
def _log_bwd(a, g):
    return prims.div(g, a)


@register_augmented_forward(PrimIDs.LOG1P)
def _log1p_aug(a):
    return VJPResult(prims.log1p(a), (a,))


@register_backward(PrimIDs.LOG1P)
def _log1p_bwd(a, g):
    return prims.div(g, clang.add(a, 1.0))


@register_augmented_forward(PrimIDs.SQRT)
def _sqrt_aug(a):
    out = prims.sqrt(a)
    return VJPResult(out, (out,))


@register_backward(PrimIDs.SQRT)
def _sqrt_bwd(out, g):
    return prims.div(g, prims.mul(clang.full_like(out, 2.0), out))


@register_augmented_forward(PrimIDs.RSQRT)
def _rsqrt_aug(a):
    out = prims.rsqrt(a)
    return VJPResult(out, (a, out))


@register_backward(PrimIDs.RSQRT)
def _rsqrt_bwd(a, out, g):
    # d rsqrt(a) = -1/2 a^{-3/2} = -0.5 * out / a
    return prims.mul(g, prims.mul(clang.full_like(out, -0.5), prims.div(out, a)))


@register_augmented_forward(PrimIDs.SIN)
def _sin_aug(a):
    return VJPResult(prims.sin(a), (a,))


@register_backward(PrimIDs.SIN)
def _sin_bwd(a, g):
    return prims.mul(g, prims.cos(a))


@register_augmented_forward(PrimIDs.COS)
def _cos_aug(a):
    return VJPResult(prims.cos(a), (a,))


@register_backward(PrimIDs.COS)
def _cos_bwd(a, g):
    return prims.neg(prims.mul(g, prims.sin(a)))


@register_augmented_forward(PrimIDs.TANH)
def _tanh_aug(a):
    out = prims.tanh(a)
    return VJPResult(out, (out,))


@register_backward(PrimIDs.TANH)
def _tanh_bwd(out, g):
    return prims.mul(g, prims.sub(clang.full_like(out, 1.0), prims.mul(out, out)))


@register_augmented_forward(PrimIDs.ERF)
def _erf_aug(a):
    return VJPResult(prims.erf(a), (a,))


@register_backward(PrimIDs.ERF)
def _erf_bwd(a, g):
    c = 2.0 / math.sqrt(math.pi)
    return prims.mul(g, prims.mul(clang.full_like(a, c), prims.exp(prims.neg(prims.mul(a, a)))))


@register_augmented_forward(PrimIDs.ERFINV)
def _erfinv_aug(a):
    out = prims.erfinv(a)
    return VJPResult(out, (out,))


@register_backward(PrimIDs.ERFINV)
def _erfinv_bwd(out, g):
    # d/dx erfinv(x) = sqrt(pi)/2 * exp(erfinv(x)^2)
    c = math.sqrt(math.pi) / 2.0
    return prims.mul(g, prims.mul(clang.full_like(out, c), prims.exp(prims.mul(out, out))))


@register_augmented_forward(PrimIDs.EXPM1)
def _expm1_aug(a):
    out = prims.expm1(a)
    return VJPResult(out, (out,))


@register_backward(PrimIDs.EXPM1)
def _expm1_bwd(out, g):
    return prims.mul(g, clang.add(out, 1.0))


@register_augmented_forward(PrimIDs.RECIPROCAL)
def _recip_aug(a):
    out = prims.reciprocal(a)
    return VJPResult(out, (out,))


@register_backward(PrimIDs.RECIPROCAL)
def _recip_bwd(out, g):
    return prims.neg(prims.mul(g, prims.mul(out, out)))


@register_augmented_forward(PrimIDs.MAXIMUM)
def _maximum_aug(a, b):
    return VJPResult(prims.maximum(a, b), (a, b))


@register_backward(PrimIDs.MAXIMUM)
def _maximum_bwd(a, b, g):
    mask = prims.ge(a, b)
    zero = _zeros_like(g)
    return prims.where(mask, g, zero), prims.where(mask, zero, g)


@register_augmented_forward(PrimIDs.MINIMUM)
def _minimum_aug(a, b):
    return VJPResult(prims.minimum(a, b), (a, b))


@register_backward(PrimIDs.MINIMUM)
def _minimum_bwd(a, b, g):
    mask = prims.le(a, b)
    zero = _zeros_like(g)
    return prims.where(mask, g, zero), prims.where(mask, zero, g)


@register_augmented_forward(PrimIDs.WHERE)
def _where_aug(pred, a, b):
    return VJPResult(prims.where(pred, a, b), (pred,))


@register_backward(PrimIDs.WHERE)
def _where_bwd(pred, g):
    zero = _zeros_like(g)
    return None, prims.where(pred, g, zero), prims.where(pred, zero, g)


@register_augmented_forward(PrimIDs.CONVERT_ELEMENT_TYPE)
def _cvt_aug(a, dtype):
    out = prims.convert_element_type(a, dtype)
    in_dtype = a.dtype if isinstance(a, TensorProxy) else dtypes.to_dtype(type(a))
    return VJPResult(out, (in_dtype,))


@register_backward(PrimIDs.CONVERT_ELEMENT_TYPE)
def _cvt_bwd(in_dtype, g):
    if not in_dtype.is_inexact:
        return None
    return prims.convert_element_type(g, in_dtype)


register_grad(PrimIDs.STOP_GRADIENT, lambda a: VJPResult(prims.stop_gradient(a), ()), lambda g: None)

# piecewise-constant ops: zero gradient almost everywhere
for _pid, _prim in ((PrimIDs.FLOOR, prims.floor), (PrimIDs.CEIL, prims.ceil),
                    (PrimIDs.ROUND, prims.round), (PrimIDs.TRUNC, prims.trunc),
                    (PrimIDs.SIGN, prims.sign)):
    def _const_aug(a, _p=_prim):
        return VJPResult(_p(a), ())

    register_grad(_pid, _const_aug, lambda g: _zeros_like(g))


@register_augmented_forward(PrimIDs.EXP2)
def _exp2_aug(a):
    out = prims.exp2(a)
    return VJPResult(out, (out,))


@register_backward(PrimIDs.EXP2)
def _exp2_bwd(out, g):
    return prims.mul(g, prims.mul(out, clang.full_like(out, math.log(2.0))))


@register_augmented_forward(PrimIDs.LOG2)
def _log2_aug(a):
    return VJPResult(prims.log2(a), (a,))


@register_backward(PrimIDs.LOG2)
def _log2_bwd(a, g):
    return prims.div(g, prims.mul(a, clang.full_like(a, math.log(2.0))))


@register_augmented_forward(PrimIDs.TAN)
def _tan_aug(a):
    out = prims.tan(a)
    return VJPResult(out, (out,))


@register_backward(PrimIDs.TAN)
def _tan_bwd(out, g):
    return prims.mul(g, clang.add(prims.mul(out, out), 1.0))


@register_augmented_forward(PrimIDs.SINH)
def _sinh_aug(a):
    return VJPResult(prims.sinh(a), (a,))


@register_backward(PrimIDs.SINH)
def _sinh_bwd(a, g):
    return prims.mul(g, prims.cosh(a))


@register_augmented_forward(PrimIDs.COSH)
def _cosh_aug(a):
    return VJPResult(prims.cosh(a), (a,))


@register_backward(PrimIDs.COSH)
def _cosh_bwd(a, g):
    return prims.mul(g, prims.sinh(a))


@register_augmented_forward(PrimIDs.ASIN)
def _asin_aug(a):
    return VJPResult(prims.asin(a), (a,))


@register_backward(PrimIDs.ASIN)
def _asin_bwd(a, g):
    return prims.mul(g, prims.rsqrt(clang.sub(1.0, prims.mul(a, a))))


@register_augmented_forward(PrimIDs.ACOS)
def _acos_aug(a):
    return VJPResult(prims.acos(a), (a,))


@register_backward(PrimIDs.ACOS)
def _acos_bwd(a, g):
    return prims.neg(prims.mul(g, prims.rsqrt(clang.sub(1.0, prims.mul(a, a)))))


@register_augmented_forward(PrimIDs.ATAN)
def _atan_aug(a):
    return VJPResult(prims.atan(a), (a,))


@register_backward(PrimIDs.ATAN)
def _atan_bwd(a, g):
    return prims.div(g, clang.add(prims.mul(a, a), 1.0))


@register_augmented_forward(PrimIDs.ASINH)
def _asinh_aug(a):
    return VJPResult(prims.asinh(a), (a,))


@register_backward(PrimIDs.ASINH)
def _asinh_bwd(a, g):
    return prims.mul(g, prims.rsqrt(clang.add(prims.mul(a, a), 1.0)))


@register_augmented_forward(PrimIDs.ACOSH)
def _acosh_aug(a):
    return VJPResult(prims.acosh(a), (a,))


@register_backward(PrimIDs.ACOSH)
def _acosh_bwd(a, g):
    return prims.mul(g, prims.rsqrt(clang.sub(prims.mul(a, a), 1.0)))


@register_augmented_forward(PrimIDs.ATANH)
def _atanh_aug(a):
    return VJPResult(prims.atanh(a), (a,))


@register_backward(PrimIDs.ATANH)
def _atanh_bwd(a, g):
    return prims.div(g, clang.sub(1.0, prims.mul(a, a)))


@register_augmented_forward(PrimIDs.ERFC)
def _erfc_aug(a):
    return VJPResult(prims.erfc(a), (a,))


@register_backward(PrimIDs.ERFC)
def _erfc_bwd(a, g):
    c = -2.0 / math.sqrt(math.pi)
    return prims.mul(g, prims.mul(clang.full_like(a, c), prims.exp(prims.neg(prims.mul(a, a)))))


@register_augmented_forward(PrimIDs.FMOD)
def _fmod_aug(a, b):
    return VJPResult(prims.fmod(a, b), (a, b))


@register_backward(PrimIDs.FMOD)
def _fmod_bwd(a, b, g):
    return g, prims.neg(prims.mul(g, prims.trunc(prims.div(a, b))))


@register_augmented_forward(PrimIDs.REMAINDER)
def _remainder_aug(a, b):
    return VJPResult(prims.remainder(a, b), (a, b))


@register_backward(PrimIDs.REMAINDER)
def _remainder_bwd(a, b, g):
    return g, prims.neg(prims.mul(g, prims.floor(prims.div(a, b))))


# ---------------------------------------------------------------------------
# shape-op rules
# ---------------------------------------------------------------------------


@register_augmented_forward(PrimIDs.RESHAPE)
def _reshape_aug(a, shape):
    return VJPResult(prims.reshape(a, shape), (a.shape,))


@register_backward(PrimIDs.RESHAPE)
def _reshape_bwd(in_shape, g):
    return prims.reshape(g, in_shape)


@register_augmented_forward(PrimIDs.TRANSPOSE)
def _transpose_aug(a, permutation):
    inv = tuple(sorted(range(len(permutation)), key=lambda i: permutation[i]))
    return VJPResult(prims.transpose(a, permutation), (inv,))


@register_backward(PrimIDs.TRANSPOSE)
def _transpose_bwd(inv, g):
    return prims.transpose(g, inv)


@register_augmented_forward(PrimIDs.BROADCAST_IN_DIM)
def _bcast_aug(a, shape, broadcast_dimensions):
    return VJPResult(prims.broadcast_in_dim(a, shape, broadcast_dimensions), (a.shape, tuple(broadcast_dimensions)))


@register_backward(PrimIDs.BROADCAST_IN_DIM)
def _bcast_bwd(in_shape, bdims, g):
    # reduce over dims not in bdims, and over bdims where input had size 1
    reduce_dims = tuple(d for d in range(g.ndim) if d not in bdims)
    reduce_dims += tuple(d for i, d in enumerate(bdims) if in_shape[i] == 1)
    out = prims.sum_prim(g, reduce_dims) if reduce_dims else g
    return prims.reshape(out, in_shape)


@register_augmented_forward(PrimIDs.SLICE)
def _slice_aug(a, start_indices, limit_indices, strides=None):
    return VJPResult(
        prims.slice_prim(a, start_indices, limit_indices, strides),
        (a.shape, tuple(start_indices), tuple(limit_indices), tuple(strides) if strides else None),
    )


@register_backward(PrimIDs.SLICE)
def _slice_bwd(in_shape, starts, limits, strides, g):
    if strides is None:
        strides = (1,) * len(in_shape)
    cfg = []
    for i, (s, l, st) in enumerate(zip(starts, limits, strides)):
        n_out = g.shape[i]
        hi = in_shape[i] - (s + (n_out - 1) * st + 1)
        cfg.append((s, hi, st - 1))
    return prims.pad(g, 0.0, tuple(cfg))


@register_augmented_forward(PrimIDs.SQUEEZE)
def _squeeze_aug(a, dims):
    return VJPResult(prims.squeeze(a, dims), (a.shape,))


@register_backward(PrimIDs.SQUEEZE)
def _squeeze_bwd(in_shape, g):
    return prims.reshape(g, in_shape)


@register_augmented_forward(PrimIDs.CAT)
def _cat_aug(tensors, dim):
    sizes = tuple(t.shape[dim] for t in tensors)
    return VJPResult(prims.cat(tensors, dim), (sizes, dim))


@register_backward(PrimIDs.CAT)
def _cat_bwd(sizes, dim, g):
    grads = []
    ofs = 0
    for s in sizes:
        grads.append(clang.slice_in_dim(g, ofs, ofs + s, dim))
        ofs += s
    return tuple(grads)


@register_augmented_forward(PrimIDs.PAD)
def _pad_aug(a, padding_value, padding_config):
    return VJPResult(prims.pad(a, padding_value, padding_config), (a.shape, tuple(padding_config)))


@register_backward(PrimIDs.PAD)
def _pad_bwd(in_shape, cfg, g):
    starts = tuple(lo for lo, _, _ in cfg)
    strides = tuple(i + 1 for _, _, i in cfg)
    limits = tuple(lo + (n - 1) * st + 1 for (lo, _, _), n, st in zip(cfg, in_shape, strides))
    return prims.slice_prim(g, starts, limits, strides)


@register_augmented_forward(PrimIDs.FLIP)
def _flip_aug(a, dims):
    return VJPResult(prims.flip(a, dims), (dims,))


@register_backward(PrimIDs.FLIP)
def _flip_bwd(dims, g):
    return prims.flip(g, dims)


@register_augmented_forward(PrimIDs.TAKE)
def _take_aug(a, indices, dim):
    return VJPResult(prims.take(a, indices, dim), (a.shape, a.dtype, indices, dim))


@register_backward(PrimIDs.TAKE)
def _take_bwd(in_shape, in_dtype, indices, dim, g):
    zeros = prims.full(in_shape, 0.0, dtype=in_dtype)
    return prims.index_add(zeros, indices, g, dim), None


@register_augmented_forward(PrimIDs.TAKE_ALONG_AXIS)
def _taa_aug(a, indices, dim):
    return VJPResult(prims.take_along_axis(a, indices, dim), (a.shape, a.dtype, indices, dim))


@register_backward(PrimIDs.TAKE_ALONG_AXIS)
def _taa_bwd(in_shape, in_dtype, indices, dim, g):
    zeros = prims.full(in_shape, 0.0, dtype=in_dtype)
    return prims.scatter_add(zeros, indices, g, dim), None


@register_augmented_forward(PrimIDs.INDEX_ADD)
def _index_add_aug(a, indices, value, dim):
    return VJPResult(prims.index_add(a, indices, value, dim), (indices, dim))


@register_backward(PrimIDs.INDEX_ADD)
def _index_add_bwd(indices, dim, g):
    # out = a + scatter(value at indices): da = g, dvalue = gather of g
    return g, None, prims.take(g, indices, dim)


@register_augmented_forward(PrimIDs.INDEX_COPY)
def _index_copy_aug(a, indices, value, dim):
    return VJPResult(prims.index_copy(a, indices, value, dim),
                     (indices, dim, value.shape, value.dtype))


@register_backward(PrimIDs.INDEX_COPY)
def _index_copy_bwd(indices, dim, v_shape, v_dtype, g):
    # the overwritten slices of a get no gradient; value gets theirs
    zeros = prims.full(v_shape, 0.0, dtype=v_dtype)
    return prims.index_copy(g, indices, zeros, dim), None, prims.take(g, indices, dim)


@register_augmented_forward(PrimIDs.SCATTER_ADD)
def _scatter_add_aug(a, indices, value, dim):
    return VJPResult(prims.scatter_add(a, indices, value, dim), (indices, dim))


@register_backward(PrimIDs.SCATTER_ADD)
def _scatter_add_bwd(indices, dim, g):
    return g, None, prims.take_along_axis(g, indices, dim)


@register_augmented_forward(PrimIDs.EMBEDDING)
def _embedding_aug(indices, weight):
    indices = clang.ensure_proxy(indices)
    return VJPResult(prims.embedding(indices, weight), (indices, weight.shape, weight.dtype))


@register_backward(PrimIDs.EMBEDDING)
def _embedding_bwd(indices, w_shape, w_dtype, g):
    indices = clang.ensure_proxy(indices)
    zeros = prims.full(w_shape, 0.0, dtype=w_dtype)
    flat_idx = prims.reshape(indices, (indices.numel,)) if indices.ndim != 1 else indices
    flat_g = prims.reshape(g, (indices.numel, w_shape[1]))
    return None, prims.index_add(zeros, flat_idx, flat_g, 0)


@register_augmented_forward(PrimIDs.TOPK)
def _topk_aug(a, k, dim):
    values, indices = prims.topk(a, k, dim)
    return VJPResult((values, indices), (a.shape, a.dtype, indices, dim))


@register_backward(PrimIDs.TOPK)
def _topk_bwd(in_shape, in_dtype, indices, dim, g_values, g_indices=None):
    zeros = prims.full(in_shape, 0.0, dtype=in_dtype)
    return prims.scatter_add(zeros, indices, g_values, dim)


# ---------------------------------------------------------------------------
# reduction rules
# ---------------------------------------------------------------------------


@register_augmented_forward(PrimIDs.SUM)
def _sum_aug(a, dims, *, output_dtype=None):
    return VJPResult(prims.sum_prim(a, dims, output_dtype=output_dtype), (a.shape, tuple(dims), a.dtype))


@register_backward(PrimIDs.SUM)
def _sum_bwd(in_shape, dims, in_dtype, g):
    kept = tuple(d for d in range(len(in_shape)) if d not in dims)
    g = prims.convert_element_type(g, in_dtype) if g.dtype != in_dtype else g
    return prims.broadcast_in_dim(g, in_shape, kept)


@register_augmented_forward(PrimIDs.PROD)
def _prod_aug(a, dims, *, output_dtype=None):
    out = prims.prod_prim(a, dims, output_dtype=output_dtype)
    return VJPResult(out, (a, out, tuple(dims)))


@register_backward(PrimIDs.PROD)
def _prod_bwd(a, out, dims, g):
    # d prod / d a_i = g * prod_{j != i} a_j, kept finite for zero-containing
    # inputs (torch semantics): one zero in a reduced group -> only that
    # position gets the product of the other elements; two or more -> all 0.
    kept = tuple(d for d in range(len(a.shape)) if d not in dims)
    g_full = prims.broadcast_in_dim(g, a.shape, kept)
    if g_full.dtype != a.dtype:
        g_full = prims.convert_element_type(g_full, a.dtype)
    zero = _zeros_like(a)
    one = clang.full_like(a, 1)
    is_zero = prims.eq(a, zero)
    safe_a = prims.where(is_zero, one, a)
    # product over the reduced dims with zeros replaced by ones
    prod_nz = prims.broadcast_in_dim(prims.prod_prim(safe_a, dims), a.shape, kept)
    nz_dtype = g_full.dtype
    n_zeros = prims.broadcast_in_dim(
        prims.sum_prim(prims.convert_element_type(is_zero, nz_dtype), dims),
        a.shape, kept)
    nz0 = _zeros_like(n_zeros)
    nz1 = clang.full_like(n_zeros, 1)
    grad_no_zero = prims.mul(g_full, prims.div(prod_nz, safe_a))
    grad_one_zero = prims.where(is_zero, prims.mul(g_full, prod_nz), zero)
    grad = prims.where(prims.eq(n_zeros, nz0), grad_no_zero,
                       prims.where(prims.eq(n_zeros, nz1), grad_one_zero, zero))
    return grad


@register_augmented_forward(PrimIDs.LOG10)
def _log10_aug(a):
    return VJPResult(prims.log10(a), (a,))


@register_backward(PrimIDs.LOG10)
def _log10_bwd(a, g):
    return prims.div(g, prims.mul(a, math.log(10.0)))


@register_augmented_forward(PrimIDs.LGAMMA)
def _lgamma_aug(a):
    return VJPResult(prims.lgamma(a), (a,))


@register_backward(PrimIDs.LGAMMA)
def _lgamma_bwd(a, g):
    return prims.mul(g, prims.digamma(a))


@register_augmented_forward(PrimIDs.HYPOT)
def _hypot_aug(a, b):
    out = prims.hypot(a, b)
    return VJPResult(out, (a, b, out))


@register_backward(PrimIDs.HYPOT)
def _hypot_bwd(a, b, out, g):
    return prims.mul(g, prims.div(a, out)), prims.mul(g, prims.div(b, out))


@register_augmented_forward(PrimIDs.COPYSIGN)
def _copysign_aug(a, b):
    out = prims.copysign(a, b)
    return VJPResult(out, (a, out))


@register_backward(PrimIDs.COPYSIGN)
def _copysign_bwd(a, out, g):
    # d|a|·sign(b)/da = sign(a)·sign(b) = sign(out)·sign(a)
    return prims.mul(g, prims.mul(prims.sign(out), prims.sign(a))), None


@register_augmented_forward(PrimIDs.CUMMAX)
def _cummax_aug(a, dim):
    values, indices = prims.cummax(a, dim)
    return VJPResult((values, indices), (a.shape, a.dtype, indices, dim))


@register_backward(PrimIDs.CUMMAX)
def _cummax_bwd(in_shape, in_dtype, indices, dim, g_values, g_indices=None):
    zeros = prims.full(in_shape, 0.0, dtype=in_dtype)
    return prims.scatter_add(zeros, indices, g_values, dim)


@register_augmented_forward(PrimIDs.AMAX)
def _amax_aug(a, dims):
    out = prims.amax(a, dims)
    return VJPResult(out, (a, out, tuple(dims)))


def _minmax_bwd(a, out, dims, g):
    kept = tuple(d for d in range(a.ndim) if d not in dims)
    out_b = prims.broadcast_in_dim(out, a.shape, kept)
    g_b = prims.broadcast_in_dim(g, a.shape, kept)
    mask = prims.eq(a, out_b)
    maskf = prims.convert_element_type(mask, a.dtype)
    count = prims.sum_prim(maskf, dims)
    count_b = prims.broadcast_in_dim(count, a.shape, kept)
    return prims.div(prims.mul(maskf, g_b), count_b)


@register_backward(PrimIDs.AMAX)
def _amax_bwd(a, out, dims, g):
    return _minmax_bwd(a, out, dims, g)


@register_augmented_forward(PrimIDs.AMIN)
def _amin_aug(a, dims):
    out = prims.amin(a, dims)
    return VJPResult(out, (a, out, tuple(dims)))


@register_backward(PrimIDs.AMIN)
def _amin_bwd(a, out, dims, g):
    return _minmax_bwd(a, out, dims, g)


# ---------------------------------------------------------------------------
# matmul-family rules (MXU ops)
# ---------------------------------------------------------------------------


@register_augmented_forward(PrimIDs.MATMUL)
def _matmul_aug(a, b):
    return VJPResult(prims.matmul(a, b), (a, b))


@register_backward(PrimIDs.MATMUL)
def _matmul_bwd(a, b, g):
    if a.ndim == 1 and b.ndim == 1:
        return prims.mul(g_expand(g, a), b), prims.mul(g_expand(g, a), a)
    if a.ndim == 1:
        # (k) @ (..., k, n) -> (..., n)
        ga = prims.matmul(b, clang.unsqueeze(g, -1))  # (..., k, 1)
        ga = clang.squeeze(ga, -1)
        ga = _sum_to_shape(ga, a.shape)
        gb = prims.matmul(clang.unsqueeze(a, -1), clang.unsqueeze(g, -2))
        gb = _sum_to_shape(gb, b.shape)
        return ga, gb
    if b.ndim == 1:
        ga = prims.matmul(clang.unsqueeze(g, -1), clang.unsqueeze(b, 0))
        ga = _sum_to_shape(ga, a.shape)
        gb = prims.matmul(clang.matrix_transpose(a), clang.unsqueeze(g, -1))
        gb = clang.squeeze(gb, -1)
        gb = _sum_to_shape(gb, b.shape)
        return ga, gb
    ga = prims.matmul(g, clang.matrix_transpose(b))
    gb = prims.matmul(clang.matrix_transpose(a), g)
    return _sum_to_shape(ga, a.shape), _sum_to_shape(gb, b.shape)


def g_expand(g, like):
    return prims.broadcast_in_dim(g, like.shape, ()) if g.ndim == 0 else g


@register_augmented_forward(PrimIDs.LINEAR)
def _linear_aug(a, w, bias=None):
    return VJPResult(prims.linear(a, w, bias), (a, w))


@register_backward(PrimIDs.LINEAR)
def _linear_bwd(a, w, g):
    # a: (..., in), w: (out, in), g: (..., out)
    ga = prims.matmul(g, w)
    batch = 1
    for s in a.shape[:-1]:
        batch *= s
    g2 = prims.reshape(g, (batch, g.shape[-1]))
    a2 = prims.reshape(a, (batch, a.shape[-1]))
    gw = prims.matmul(clang.matrix_transpose(g2), a2)
    return ga, gw


# ---------------------------------------------------------------------------
# the transform itself
# ---------------------------------------------------------------------------


class TapeEntry(NamedTuple):
    sym_id: Any
    inputs: tuple  # mapped (aug-fwd) flat tensor input proxies
    outputs: tuple  # mapped flat tensor output proxies
    residuals: tuple
    fallback_impl: Optional[Callable]
    origin: BoundSymbol  # the forward symbol: its named_scope path names the backward's


def _flat_tensors(x) -> tuple:
    from ..core.codeutils import flat_tensor_proxies

    return tuple(flat_tensor_proxies(x))


def _is_diff_dtype(p) -> bool:
    return isinstance(p, TensorProxy) and p.dtype.is_inexact


def _plan_recompute(fwd: TraceCtx, saved: list, recompute_names: set):
    """Shrink the saved-for-backward list by re-deriving tagged residuals.

    Returns (kept_saved, subgraph): subgraph is the minimal ordered list of fwd
    bsyms whose replay in the backward reproduces every dropped residual;
    external inputs the subgraph needs are appended to kept_saved (saving a
    trace *arg* costs nothing — the array is alive regardless)."""
    if not recompute_names:
        return saved, []
    produced: dict[str, Any] = {}
    for b in fwd.bound_symbols:
        for o in b.flat_proxy_outs():
            produced[o.name] = b

    targets = {s.name for s in saved
               if isinstance(s, TensorProxy) and s.name in recompute_names and s.name in produced}
    if not targets:
        return saved, []

    need = set(targets)
    subgraph: list = []
    for b in reversed(fwd.bound_symbols):
        outs = [o.name for o in b.flat_proxy_outs()]
        if not outs or not any(o in need for o in outs):
            continue
        if all(o in recompute_names for o in outs):
            subgraph.append(b)
            for p in b.flat_proxy_args():
                need.add(p.name)
    subgraph.reverse()

    recomputed = {o.name for b in subgraph for o in b.flat_proxy_outs()}
    # proxies the subgraph consumes but does not itself produce must be saved
    external = []
    ext_seen = set()
    for b in subgraph:
        for p in b.flat_proxy_args():
            if p.name not in recomputed and p.name not in ext_seen:
                ext_seen.add(p.name)
                external.append(p)

    kept = [s for s in saved if s.name not in targets]
    kept_names = {s.name for s in kept}
    for p in external:
        if p.name not in kept_names:
            kept_names.add(p.name)
            kept.append(p)
    return kept, subgraph


def res_lookup_early(x, saved_mirror: dict):
    """Map fwd proxies to their bwd mirrors (recompute replay)."""
    if isinstance(x, Proxy):
        return saved_mirror.get(x.name, x)
    if isinstance(x, (tuple, list)):
        return type(x)(res_lookup_early(e, saved_mirror) for e in x)
    if isinstance(x, dict):
        return {k: res_lookup_early(v, saved_mirror) for k, v in x.items()}
    return x


def _map_into(old, new, saved_mirror: dict):
    if isinstance(old, Proxy):
        saved_mirror[old.name] = new
        return
    if isinstance(old, (tuple, list)) and isinstance(new, (tuple, list)):
        for o, n in zip(old, new):
            _map_into(o, n, saved_mirror)
    elif isinstance(old, dict) and isinstance(new, dict):
        for k in old:
            _map_into(old[k], new[k], saved_mirror)


class ForwardBackwardTraces(NamedTuple):
    forward_trace: TraceCtx
    backward_trace: TraceCtx
    n_saved: int
    grad_arg_names: tuple  # names of fwd-trace args receiving grads, in order



def forward_and_backward_traces(trace: TraceCtx, *, grad_all_inexact_args: bool = False) -> ForwardBackwardTraces:
    """Build (augmented forward, backward) traces from an acquired trace."""
    # which args get grads
    grad_args = [
        p
        for p in trace.args
        if isinstance(p, TensorProxy) and (p.requires_grad or (grad_all_inexact_args and p.dtype.is_inexact))
    ]
    grad_arg_names = tuple(p.name for p in grad_args)

    fwd = TraceCtx(trace.fn)
    fwd.args = trace.args
    fwd._name = "augmented_forward"
    for p in trace.args:
        fwd.add_name(p.name)

    env: dict[str, Any] = {p.name: p for p in trace.args}
    diff: set[str] = set(grad_arg_names)
    tape: list[TapeEntry] = []
    fwd_output = None
    has_effects = bool(getattr(trace, "side_effects", ()))
    fwd_effects: tuple = ()
    # proxies produced while processing RECOMPUTE_IN_BACKWARD-tagged bsyms:
    # eligible to be re-derived in the backward instead of saved
    recompute_names: set[str] = set()

    def lookup(x):
        if isinstance(x, Proxy):
            return env.get(x.name, x)
        if isinstance(x, (tuple, list)):
            t = type(x)(lookup(e) for e in x)
            return t
        if isinstance(x, dict):
            return {k: lookup(v) for k, v in x.items()}
        return x

    def map_out(old, new):
        if isinstance(old, Proxy):
            env[old.name] = new
            return
        if isinstance(old, (tuple, list)) and isinstance(new, (tuple, list)):
            for o, n in zip(old, new):
                map_out(o, n)
            return
        if isinstance(old, dict) and isinstance(new, dict):
            for k in old:
                map_out(old[k], new[k])

    def process(bsym: BoundSymbol, in_recompute: bool = False):
        from ..core.symbol import OpTags

        tagged = in_recompute or (OpTags.RECOMPUTE_IN_BACKWARD in getattr(bsym, "tags", ()))
        scope_start = len(fwd.bound_symbols)
        try:
            # the augmented forward's symbols keep the named_scope path of the one processed
            with rebinding(bsym):
                _process_inner(bsym, tagged)
        finally:
            if tagged:
                for nb in fwd.bound_symbols[scope_start:]:
                    for o in nb.flat_proxy_outs():
                        recompute_names.add(o.name)

    def _process_inner(bsym: BoundSymbol, in_recompute: bool):
        nonlocal fwd_output, fwd_effects
        if bsym.sym.id == PrimIDs.RETURN:
            ret = bsym.args[0] if len(bsym.args) == 1 else bsym.args
            if has_effects:
                # acquire_trace packed (result, effect_values)
                result_part, effects_part = ret
                fwd_output = lookup(result_part)
                fwd_effects = tuple(lookup(e) for e in effects_part)
            else:
                fwd_output = lookup(ret)
            return
        if bsym.sym.id in (PrimIDs.DEL, PrimIDs.COMMENT, PrimIDs.UNPACK_TRIVIAL):
            return
        margs = lookup(bsym.args)
        mkwargs = lookup(bsym.kwargs)
        in_tensors = _flat_tensors((margs, mkwargs))
        needs_grad = any(t.name in diff for t in in_tensors)
        out_is_diff = any(_is_diff_dtype(o) for o in bsym.flat_proxy_outs())

        if needs_grad and out_is_diff and has_grad_rule(bsym.sym.id):
            rule = augmented_forward_impls[bsym.sym.id]
            res = rule(*margs, **mkwargs)
            if res is not NotImplemented:  # rules may decline (e.g. kernel shape checkers)
                map_out(bsym.output, res.out)
                new_outs = _flat_tensors(res.out)
                tape.append(TapeEntry(bsym.sym.id, in_tensors, new_outs, tuple(res.residuals), None, bsym))
                for o in new_outs:
                    if _is_diff_dtype(o):
                        diff.add(o.name)
                return
        if needs_grad and out_is_diff and bsym.sym.id in JAX_VJP_FALLBACK:
            _process_fallback(bsym, margs, mkwargs, in_tensors)
            return
        if needs_grad and out_is_diff and bsym.subsymbols:
            for sub in bsym.subsymbols:
                process(sub, in_recompute)
            # map composite outputs: subsymbol processing populated env for
            # the proxies the composite returns
            map_out(bsym.output, lookup(bsym.output))
            return
        if needs_grad and out_is_diff and not bsym.sym.is_prim:
            # composite that recorded nothing: a pure pass-through (e.g. a
            # full-range getitem); outputs are existing proxies
            map_out(bsym.output, lookup(bsym.output))
            return
        if needs_grad and out_is_diff:
            raise NotImplementedError(
                f"no grad rule for {bsym.sym.name} (id={bsym.sym.id}) and no decomposition"
            )
        # non-differentiable: re-emit
        out = bsym.sym(*margs, **mkwargs)
        map_out(bsym.output, out)

    def _process_fallback(bsym, margs, mkwargs, in_tensors):
        from ..executors import jaxex

        impl = jaxex.ex.get_impl(bsym.sym.id)
        fwd_sym, bwd_sym = _make_fallback_symbols(bsym.sym, impl)
        outs_and_res = fwd_sym(*margs, **mkwargs)
        new_out, res_proxy = outs_and_res
        map_out(bsym.output, new_out)
        new_outs = _flat_tensors(new_out)
        tape.append(TapeEntry(("fallback", bsym.sym.id), in_tensors, new_outs, (res_proxy,), bwd_sym, bsym))
        for o in new_outs:
            if _is_diff_dtype(o):
                diff.add(o.name)

    with tracectx(fwd):
        for bsym in trace.bound_symbols:
            process(bsym)

        # saved-for-backward = union of residual proxies (dedup, trace order)
        saved: list[Proxy] = []
        seen: set = set()
        for entry in tape:
            for r in entry.residuals:
                if isinstance(r, Proxy) and r.name not in seen:
                    seen.add(r.name)
                    saved.append(r)
        saved, recompute_subgraph = _plan_recompute(fwd, saved, recompute_names)
        if has_effects:
            prims.python_return(((fwd_output, fwd_effects), tuple(saved)))
        else:
            prims.python_return((fwd_output, tuple(saved)))

    fwd_out_tensors = _flat_tensors(fwd_output)

    # ---- build backward trace ----
    bwd = TraceCtx(None)
    bwd._name = "backward"
    saved_mirror: dict[str, Proxy] = {}
    bwd_args: list[Proxy] = []
    with tracectx(bwd):
        for p in saved:
            if isinstance(p, TensorProxy):
                m = TensorProxy(None, shape=p.shape, dtype=p.dtype, device=p.device)
            elif isinstance(p, NumberProxy):
                m = NumberProxy(p.value, p.python_type)
            else:  # AnyProxy (opaque residuals, e.g. vjp closures)
                from ..core.proxies import AnyProxy

                m = AnyProxy(None)
            saved_mirror[p.name] = m
            bwd_args.append(m)
        cot_map: dict[str, Proxy] = {}
        for o in fwd_out_tensors:
            if _is_diff_dtype(o):
                c = TensorProxy(None, shape=o.shape, dtype=o.dtype, device=o.device)
                cot_map[o.name] = c
                bwd_args.append(c)
        bwd.args = tuple(bwd_args)

        # lazy replay of checkpointed segments: each tagged residual is
        # re-derived right before its first consuming grad rule, so (e.g.)
        # ZeRO-3 re-gathers keep only one layer's full params alive at a time
        # (reference: RECOMPUTE_IN_BACKWARD handling in the fwd/bwd split,
        # thunder/core/jit_ext.py:1080 + symbol.py:99)
        recompute_producer: dict[str, Any] = {}
        for rb in recompute_subgraph:
            for o in rb.flat_proxy_outs():
                recompute_producer[o.name] = rb
        _replayed: set = set()

        def materialize(name: str):
            rb = recompute_producer.get(name)
            if rb is None or id(rb) in _replayed or name in saved_mirror:
                return
            _replayed.add(id(rb))
            for p in rb.flat_proxy_args():
                materialize(p.name)
            rmargs = tuple(res_lookup_early(a, saved_mirror) for a in rb.args)
            rmkwargs = {k: res_lookup_early(v, saved_mirror) for k, v in rb.kwargs.items()}
            with rebinding(rb, "recompute"):
                new_out = rb.sym(*rmargs, **rmkwargs)
            _map_into(rb.output, new_out, saved_mirror)

        grad_map: dict[str, Proxy] = dict(cot_map)

        def res_lookup(r):
            if isinstance(r, Proxy) and r.name in saved_mirror:
                return saved_mirror[r.name]
            if isinstance(r, (tuple, list)):
                return type(r)(res_lookup(e) for e in r)
            return r

        def accumulate(p: TensorProxy, g):
            if g is None:
                return
            if tuple(g.shape) != tuple(p.shape):
                g = _sum_to_shape(g, p.shape)
            if g.dtype != p.dtype and p.dtype.is_inexact:
                g = prims.convert_element_type(g, p.dtype)
            prev = grad_map.get(p.name)
            grad_map[p.name] = g if prev is None else prims.add(prev, g)

        for entry in reversed(tape):
            if not any(o.name in grad_map for o in entry.outputs):
                continue
            # what the rule binds, the zeros it is fed and the sums its gradients
            # go into carry `bwd/<named_scope path of the forward symbol>`
            with rebinding(entry.origin, "bwd"):
                # fill missing cotangents with zeros for multi-output rules
                cots = [grad_map.get(o.name) if o.name in grad_map
                        else clang.full(o.shape, 0.0, dtype=o.dtype, device=o.device)
                        for o in entry.outputs if _is_diff_dtype(o) or o.name in grad_map]
                for r in entry.residuals:
                    if isinstance(r, Proxy):
                        materialize(r.name)
                if entry.fallback_impl is not None:
                    res = res_lookup(entry.residuals[0])
                    meta_spec = tuple((p.shape, p.dtype, p.device) for p in entry.inputs)
                    grads = entry.fallback_impl(res, meta_spec, *cots)
                else:
                    rule = backward_impls[entry.sym_id]
                    res = tuple(res_lookup(r) for r in entry.residuals)
                    grads = rule(*res, *cots)
                if not isinstance(grads, tuple):
                    grads = (grads,)
                for p, g in zip(entry.inputs, grads):
                    if isinstance(p, TensorProxy) and g is not None and _is_diff_dtype(p):
                        accumulate(p, g)

        grads_out = []
        for p in grad_args:
            g = grad_map.get(p.name)
            if g is None:
                g = clang.full(p.shape, 0.0, dtype=p.dtype, device=p.device)
            grads_out.append(g)
        prims.python_return(tuple(grads_out))

    fwd = dce(fwd)
    bwd = dce(bwd)
    fwd.set_provenance("Augmented forward (autodiff)")
    bwd.set_provenance("Backward (autodiff)")
    return ForwardBackwardTraces(fwd, bwd, len(saved), grad_arg_names)


class _TLeaf:
    """Marker for an extracted tensor leaf inside a fallback op's argument
    structure (index into the flat leaves list)."""

    __slots__ = ("i",)

    def __init__(self, i: int):
        self.i = i


def _extract_tensor_leaves(x, leaves: list):
    """Replace every array-like leaf in a nested structure with a _TLeaf,
    appending the array to ``leaves``. Traversal order mirrors
    codeutils.flat_proxies (tuple/list elements in order, dict values in
    order, slice start/stop/step) so runtime grads align with trace-time
    flattened tensor proxies."""
    if isinstance(x, tuple) and hasattr(x, "_fields"):  # namedtuple
        return type(x)(*(_extract_tensor_leaves(e, leaves) for e in x))
    if isinstance(x, (tuple, list)):
        return type(x)(_extract_tensor_leaves(e, leaves) for e in x)
    if isinstance(x, dict):
        return {k: _extract_tensor_leaves(v, leaves) for k, v in x.items()}
    if isinstance(x, slice):
        return slice(
            _extract_tensor_leaves(x.start, leaves),
            _extract_tensor_leaves(x.stop, leaves),
            _extract_tensor_leaves(x.step, leaves),
        )
    if hasattr(x, "shape") and hasattr(x, "dtype") and not isinstance(x, (bool, int, float, complex)):
        leaves.append(x)
        return _TLeaf(len(leaves) - 1)
    return x


def _fill_tensor_leaves(x, tensors):
    if isinstance(x, _TLeaf):
        return tensors[x.i]
    if isinstance(x, tuple) and hasattr(x, "_fields"):  # namedtuple
        return type(x)(*(_fill_tensor_leaves(e, tensors) for e in x))
    if isinstance(x, (tuple, list)):
        return type(x)(_fill_tensor_leaves(e, tensors) for e in x)
    if isinstance(x, dict):
        return {k: _fill_tensor_leaves(v, tensors) for k, v in x.items()}
    if isinstance(x, slice):
        return slice(
            _fill_tensor_leaves(x.start, tensors),
            _fill_tensor_leaves(x.stop, tensors),
            _fill_tensor_leaves(x.step, tensors),
        )
    return x


def _check_fallback_grads(name: str, grads: tuple, meta_spec: tuple) -> None:
    """Loud-failure guard: a vjp fallback must produce exactly one gradient per
    traced tensor input. A silent mismatch means some tensor input would get a
    None/zero cotangent and part of the model would quietly stop training
    (reference treats auto-registered grads via thunder/core/vjp_utils.py —
    there, too, a missing grad is an error, not a None)."""
    if len(grads) != len(meta_spec):
        raise RuntimeError(
            f"vjp fallback for '{name}' produced {len(grads)} input gradients but "
            f"{len(meta_spec)} tensor inputs were traced. This usually means a tensor "
            f"argument is nested in a container the fallback extraction does not walk; "
            f"fix _extract_tensor_leaves or register an explicit grad rule for '{name}'."
        )


_fallback_sym_cache: dict = {}


def _make_fallback_symbols(sym: Symbol, impl: Callable):
    """Create fwd/bwd symbols whose impls use jax.vjp of the op's jax impl at
    runtime. The residual (the vjp closure) is carried as an opaque AnyProxy
    between the forward and backward callables; both symbols are DONT_FUSE so
    the closure never has to cross an XLA boundary."""
    import jax

    from ..core.proxies import AnyProxy

    key = sym.id
    if key in _fallback_sym_cache:
        return _fallback_sym_cache[key]

    def fwd_meta(*args, **kwargs):
        out = sym.meta(*args, **kwargs)
        res = AnyProxy(None)
        return out, res

    def fwd_impl(*args, **kwargs):
        # Extract tensor leaves from the FULL nested structure (lists/tuples/
        # dicts/slices), in the same deterministic order codeutils.flat_proxies
        # walks proxies at trace time — so grads returned by the vjp closure
        # align 1:1 with the TapeEntry's flattened tensor inputs. Top-level-only
        # extraction silently dropped grads for list-input ops (dstack et al.).
        leaves: list = []
        extracted = _extract_tensor_leaves((list(args), dict(kwargs)), leaves)

        def call(*tensors):
            f_args, f_kwargs = _fill_tensor_leaves(extracted, tensors)
            return impl(*f_args, **f_kwargs)

        out, vjp_fn = jax.vjp(call, *leaves)
        return out, vjp_fn

    fwd_sym = Symbol(f"{sym.name}_vjp_fwd", fwd_meta, id=f"vjp_fwd.{sym.name}", is_prim=True,
                     module="autodiff", tags=(OpTags.DONT_FUSE,), python_impl=fwd_impl)

    def bwd_meta(res, meta_spec, *cots):
        return tuple(TensorProxy(shape=s, dtype=d, device=dev) for (s, d, dev) in meta_spec)

    def bwd_impl(res, meta_spec, *cots):
        vjp_fn = res
        grads = tuple(vjp_fn(cots[0] if len(cots) == 1 else tuple(cots)))
        _check_fallback_grads(sym.name, grads, meta_spec)
        return grads

    bwd_sym = Symbol(f"{sym.name}_vjp_bwd", bwd_meta, id=f"vjp_bwd.{sym.name}", is_prim=True,
                     module="autodiff", tags=(OpTags.DONT_FUSE,), python_impl=bwd_impl)

    _fallback_sym_cache[key] = (fwd_sym, bwd_sym)
    return _fallback_sym_cache[key]


# ---------------------------------------------------------------------------
# runtime wrappers: value_and_grad / grad
# ---------------------------------------------------------------------------


class _VAGEntry(NamedTuple):
    fwd_fn: Callable
    bwd_fn: Callable
    fwd_trc: TraceCtx
    bwd_trc: TraceCtx
    grad_leaf_positions: tuple  # positions (within tensor leaves) receiving grads
    treedef: Any
    tensor_mask: tuple
    effect_keys: tuple = ()  # (owner, name) epilogue targets
    prologue_fn: Callable | None = None  # interpreter-frontend acquisition only


class ThunderValueAndGrad(EpilogueMixin):
    """Callable returning (value, grads). grads is a pytree matching (args,
    kwargs) with arrays at differentiated tensor leaves and None elsewhere.

    Reference analog: thunder/core/transforms.py:3068 value_and_grad, combined
    with the ThunderFunction autograd bridge (torch_autograd.py:17) — TPU-
    native there is no runtime autograd tape, so the API is functional."""

    def __init__(self, fn: Callable, argnums=None, transforms: Sequence = (),
                 interpretation: str | None = None, donated_argnums=None,
                 check_traces: bool = False):
        self.fn = fn
        self.argnums = (argnums,) if isinstance(argnums, int) else (tuple(argnums) if argnums is not None else None)
        self.transforms = list(transforms)
        self.interpretation = interpretation
        # positional args whose buffers the caller donates at the jax.jit
        # level (TrainStep donates params/opt state); the acquired trace is
        # annotated so the alias analysis can verify read-after-donation
        self.donated_argnums = (
            (donated_argnums,) if isinstance(donated_argnums, int)
            else (tuple(donated_argnums) if donated_argnums else ()))
        # per-function pass-interposed checking (DebugOptions.check_traces
        # threaded from the owning jit); TT_CHECK_TRACES covers everything
        # without it
        self.check_traces = bool(check_traces)
        self._cache: dict = {}
        self._cs = None  # CompileStats of last compile

    def _grad_mask(self, args, kwargs):
        """Per-leaf requires-grad mask: argnums positions (or Parameter flags)."""
        from ..core.pytree import tree_flatten

        masks = []
        if self.argnums is None:
            leaves, _ = tree_flatten((args, kwargs))
            return [bool(getattr(l, "requires_grad", False)) for l in leaves]
        for i, a in enumerate(args):
            leaves, _ = tree_flatten(a)
            masks.extend([i in self.argnums] * len(leaves))
        leaves, _ = tree_flatten(kwargs)
        masks.extend([False] * len(leaves))
        return masks

    def _compile(self, args, kwargs, key):
        import time as _time

        from .. import ThunderCompiledFunction, _is_tensor_like, acquire_trace, resolve_executors
        from ..common import CompileStats
        from ..core.transform_common import dce as _dce
        from ..executors.passes import transform_for_execution

        from ..analysis import manager as _an

        cs = CompileStats()
        self._cs = cs
        grad_mask = self._grad_mask(args, kwargs)
        where = getattr(self.fn, "__name__", "value_and_grad")
        chk = self.check_traces

        t0 = _time.perf_counter_ns()
        prologue_fn = None
        if self.interpretation is not None:
            # bytecode-interpreter acquisition (reference framework.py:381-472
            # runs grads under every frontend): the prologue unpacks user
            # tensors + captured closure/module tensors into computation args
            from ..frontend.jit_ext import general_jit

            res, treedef, tensor_mask, leaves = general_jit(
                self.fn, args, kwargs, grad_mask=grad_mask)
            trc = res.computation_trc
            prologue_fn = res.prologue_trc.python_callable()
        else:
            trc, treedef, tensor_mask, leaves = acquire_trace(self.fn, args, kwargs, grad_mask=grad_mask)
        cs.last_trace_tracing_time_ns = _time.perf_counter_ns() - t0
        if self.donated_argnums:
            # mark the trace-arg proxies backing donated positional args:
            # every later checkpoint verifies no pass introduces a read of a
            # donated buffer after the write that consumes it
            from .. import donated_arg_names

            trc.donated = donated_arg_names(trc, args, kwargs, tensor_mask,
                                            self.donated_argnums)
        _an.checkpoint("acquisition", trc, where=where, force=chk)

        t1 = _time.perf_counter_ns()
        for tf in self.transforms:
            prev = trc
            _, trc = tf.transform_traces_pre_autodiff(None, trc, compile_data=None)
            _an.checkpoint(f"transform:{type(tf).__name__}", trc, before=prev,
                           where=where, force=chk)
        prev = trc
        trc = _dce(trc)
        _an.checkpoint("transform:dce", trc, before=prev, where=where, force=chk)
        fb = forward_and_backward_traces(trc)
        fwd_trc, bwd_trc = fb.forward_trace, fb.backward_trace
        # the split rebuilds both traces from scratch (not via from_trace);
        # the donated annotation follows the forward, whose param proxies —
        # and so their names — survive the tape replay
        donated = getattr(trc, "donated", None)
        if donated:
            fwd_trc.donated = set(donated)
        # effect order is checked against the differentiated trace (names
        # survive the tape replay)
        _an.checkpoint("autodiff:augmented-forward", fwd_trc, before=trc,
                       where=where, force=chk)
        _an.checkpoint("autodiff:backward", bwd_trc, where=where, force=chk)
        for tf in self.transforms:
            prev_f, prev_b = fwd_trc, bwd_trc
            fwd_trc = tf.transform_trace_post_optimization(fwd_trc, compile_data=None)
            bwd_trc = tf.transform_trace_post_optimization(bwd_trc, compile_data=None)
            _an.checkpoint(f"transform_post:{type(tf).__name__}:fwd", fwd_trc,
                           before=prev_f, where=where, force=chk)
            _an.checkpoint(f"transform_post:{type(tf).__name__}:bwd", bwd_trc,
                           before=prev_b, where=where, force=chk)
        fwd_claimed = transform_for_execution(fwd_trc, resolve_executors(None),
                                              check_traces=chk)
        bwd_claimed = transform_for_execution(bwd_trc, resolve_executors(None),
                                              check_traces=chk)
        cs.last_trace_transform_time_ns = _time.perf_counter_ns() - t1

        t2 = _time.perf_counter_ns()
        fwd_fn = fwd_claimed.python_callable()
        bwd_fn = bwd_claimed.python_callable()
        cs.last_compile_time_ns = _time.perf_counter_ns() - t2
        cs.last_traces = [trc, fwd_trc, fwd_claimed]
        cs.last_backward_traces = [bwd_trc, bwd_claimed]

        arg_name_to_pos = {p.name: i for i, p in enumerate(trc.args)}
        grad_positions = tuple(arg_name_to_pos[n] for n in fb.grad_arg_names)
        entry = _VAGEntry(fwd_fn, bwd_fn, fwd_claimed, bwd_claimed, grad_positions, treedef,
                          tuple(tensor_mask),
                          tuple((o, n) for o, n, _ in getattr(trc, "side_effects", ())),
                          prologue_fn)
        self._cache[key] = entry
        return entry

    def __call__(self, *args, **kwargs):
        import jax
        import jax.numpy as jnp

        from .. import _cache_key, _is_tensor_like, _unwrap
        from ..core.pytree import tree_flatten, tree_unflatten

        leaves, treedef = tree_flatten((args, kwargs))
        tensor_mask = [_is_tensor_like(l) for l in leaves]
        key = _cache_key(leaves, tensor_mask)
        extra = getattr(self.fn, "__cache_extra__", None)
        if extra is not None:
            key = key + (extra(),)  # e.g. module train/eval mode
        # Under an ambient jax trace (TrainStep's jit/shard_map), compiled
        # entries bake that trace's tracers as constants — they must not
        # outlive it. Key such entries by the tracer's trace identity so a
        # retrace recompiles instead of resurrecting stale tracers (a strong
        # ref to the trace object pins its id against reuse).
        tracer_leaves = [l for l in leaves if isinstance(l, jax.core.Tracer)]
        if tracer_leaves:
            trace_obj = getattr(tracer_leaves[0], "_trace", None)
            key = key + (("ambient_trace", id(trace_obj)),)
            self._trace_refs = getattr(self, "_trace_refs", {})
            self._trace_refs[key] = trace_obj
        entry = self._cache.get(key)
        if entry is None:
            entry = self._compile(args, kwargs, key)
        tensor_leaves = [_unwrap(l) for l, m in zip(leaves, tensor_mask) if m]
        if entry.prologue_fn is not None:
            tensor_leaves = entry.prologue_fn(*tensor_leaves)
        out, saved = entry.fwd_fn(*tensor_leaves)
        if entry.effect_keys:
            out, effects = out
            self.apply_effects(entry.effect_keys, effects)
        # cotangent: scalar loss -> 1.0
        cot = jnp.ones((), dtype=jnp.asarray(out).dtype) if hasattr(out, "dtype") else 1.0
        grads_flat = entry.bwd_fn(*saved, cot)
        # scatter grads back into the input pytree
        grads_by_tensor_pos = {p: g for p, g in zip(entry.grad_leaf_positions, grads_flat)}
        grad_leaves = []
        ti = 0
        for m in tensor_mask:
            if m:
                grad_leaves.append(grads_by_tensor_pos.get(ti))
                ti += 1
            else:
                grad_leaves.append(None)
        grads = tree_unflatten(treedef, grad_leaves)
        return out, grads


def value_and_grad(fn, argnums=None, *, interpretation=None):
    """(value, grads) over a callable, Module, or compiled function.

    interpretation="python interpreter" acquires the program through the
    bytecode-interpreter frontend (closure/module tensors captured via
    provenance-built prologues) instead of direct proxy tracing."""
    from .. import ThunderCompiledFunction
    from ..frontend.compiled import InterpretedFunction
    from ..nn.module import Module, ThunderModule

    if isinstance(fn, ThunderModule):
        return ModuleValueAndGrad(fn)
    if isinstance(fn, Module):
        from .. import jit

        return ModuleValueAndGrad(jit(fn))
    if type(fn).__name__ == "CompiledTorchModule":  # torch-frontend wrapper
        return TorchModuleValueAndGrad(fn)
    if isinstance(fn, InterpretedFunction):
        return ThunderValueAndGrad(fn.fn, argnums, transforms=fn.transforms,
                                   interpretation="python interpreter")
    if isinstance(fn, ThunderCompiledFunction):
        fn = fn._cd.fn
    return ThunderValueAndGrad(fn, argnums, interpretation=interpretation)


def grad(fn, argnums=None):
    vag = value_and_grad(fn, argnums)

    def grad_fn(*args, **kwargs):
        _, g = vag(*args, **kwargs)
        return g

    grad_fn.__wrapped_vag__ = vag
    return grad_fn


class TorchModuleValueAndGrad:
    """value_and_grad over a CompiledTorchModule: (loss, {param_name: grad}).

    The torch-frontend wrapper's traced fn takes (params, args, kwargs) like
    ThunderModule's; params are plain jax arrays, so argnums=0 marks them."""

    def __init__(self, ctm):
        self.ctm = ctm
        self._vag = ThunderValueAndGrad(ctm._cfn._cd.fn, argnums=0)

    @property
    def _cs(self):
        return self._vag._cs

    def __call__(self, *args, **kwargs):
        from ..interop.torch_frontend import torch_to_jax

        def conv(x):
            # accept torch tensors like CompiledTorchModule.__call__ does
            if type(x).__module__.startswith("torch") and hasattr(x, "detach"):
                return torch_to_jax(x)
            if isinstance(x, (tuple, list)):
                return type(x)(conv(e) for e in x)
            if isinstance(x, dict):
                return {k: conv(v) for k, v in x.items()}
            return x

        state = {**self.ctm.get_parameters(), **self.ctm.get_buffers()}
        loss, grads = self._vag(state, conv(args), conv(kwargs))
        param_names = set(self.ctm.get_parameters())
        return loss, {k: g for k, g in grads[0][0].items() if k in param_names}


class ModuleValueAndGrad:
    """value_and_grad over a ThunderModule: returns (loss, {param_name: grad}).

    The traced wrapper takes (params_dict, args, kwargs); parameters are
    requires_grad leaves, so grads land exactly on them."""

    def __init__(self, tmodule):
        self.tmodule = tmodule
        self._vag = ThunderValueAndGrad(tmodule._cfn._cd.fn, argnums=None)

    @property
    def _cs(self):
        return self._vag._cs

    def __call__(self, *args, **kwargs):
        # buffers ride as (requires_grad=False) inputs so mutable state is
        # not baked into the trace as constants (same as ThunderModule.__call__)
        state = {**self.tmodule.get_parameters(), **self.tmodule.get_buffers()}
        loss, grads = self._vag(state, args, kwargs)
        # grads mirrors ((state, args, kwargs), {}) -> params grads dict
        all_grads = grads[0][0]
        param_names = set(self.tmodule.get_parameters())
        param_grads = {k: g for k, g in all_grads.items() if k in param_names}
        return loss, param_grads
