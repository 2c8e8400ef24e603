"""jaxex: op-by-op JAX executor — every prim lowered 1:1 to jax.numpy/lax.

This is the TPU stack's "always" executor and numerics reference, the role
torchex plays in the reference (thunder/executors/torchex.py:1, ~180
register_implementation calls). All impls are pure jax functions, so any
contiguous region of them is XLA-fusible by the fusion executor."""
from __future__ import annotations

import builtins
import functools
import math
from numbers import Number

import jax
import jax.numpy as jnp
from jax import lax

from ..core import dtypes, prims
from ..core.dtypes import to_jax_dtype
from ..core.prims import PrimIDs
from ..core.proxies import TensorProxy
from ..extend import OperatorExecutor, register_executor, add_always_executor

ex = OperatorExecutor("jax")
register_executor(ex)
add_always_executor(ex)


def _jd(dtype):
    """framework dtype -> jnp dtype, downgrading 64-bit when x64 is disabled."""
    if dtype is None:
        return None
    jd = to_jax_dtype(dtype)
    if not jax.config.jax_enable_x64:
        jd = {jnp.int64: jnp.int32, jnp.uint32: jnp.uint32, jnp.float64: jnp.float32,
              jnp.complex128: jnp.complex64}.get(jd, jd)
    return jd


def _reg(pid, fn):
    ex.register_implementation(pid, fn)
    return fn


# ---- structure / checks ----
_reg(PrimIDs.PRINT, print)
_reg(PrimIDs.CHECK_TENSOR_SHAPE_AND_METADATA, prims.check_tensor_shape_and_metadata.python_impl)
_reg(PrimIDs.CHECK_NUMBER_TYPE_AND_VALUE, prims.check_number_type_and_value.python_impl)

# ---- dtype/device ----
_reg(PrimIDs.CONVERT_ELEMENT_TYPE, lambda a, dtype: jnp.asarray(a).astype(_jd(dtype)))
_reg(PrimIDs.DEVICE_PUT, lambda a, device: jax.device_put(a, device.jax_device()))
_reg(PrimIDs.STOP_GRADIENT, lax.stop_gradient)
_reg(PrimIDs.BITCAST, lambda a, dtype: lax.bitcast_convert_type(a, _jd(dtype)))


# ---- factories ----
_reg(PrimIDs.TENSOR_CONSTANT, jnp.asarray)


def _full(shape, fill_value, *, device=None, dtype=None):
    return jnp.full(shape, fill_value, dtype=_jd(dtype))


_reg(PrimIDs.FULL, _full)


def _iota(length, *, start=0, step=1, device=None, dtype=None):
    return jnp.arange(start, start + length * step, step, dtype=_jd(dtype))[:length]


_reg(PrimIDs.IOTA, _iota)


def _uniform(shape, minval, maxval, *, key, device=None, dtype=None):
    return jax.random.uniform(key, tuple(shape), _jd(dtype) or jnp.float32, minval, maxval)


_reg(PrimIDs.UNIFORM, _uniform)


def _normal(shape, mean, std, *, key, device=None, dtype=None):
    return jax.random.normal(key, tuple(shape), _jd(dtype) or jnp.float32) * std + mean


_reg(PrimIDs.NORMAL, _normal)


def _randint(shape, low, high, *, key, device=None, dtype=None):
    return jax.random.randint(key, tuple(shape), low, high, _jd(dtype) or jnp.int32)


_reg(PrimIDs.RANDINT, _randint)


def _rng_split(key):
    k = jax.random.split(key, 2)
    return k[0], k[1]


_reg(PrimIDs.RNG_SPLIT, _rng_split)

# ---- shape ops ----
_reg(PrimIDs.RESHAPE, lambda a, shape: jnp.reshape(a, shape))
_reg(PrimIDs.TRANSPOSE, lambda a, permutation: jnp.transpose(a, permutation))
_reg(PrimIDs.BROADCAST_IN_DIM, lambda a, shape, broadcast_dimensions: lax.broadcast_in_dim(a, shape, broadcast_dimensions))
_reg(PrimIDs.SLICE, lambda a, start_indices, limit_indices, strides=None: lax.slice(a, start_indices, limit_indices, strides))
_reg(PrimIDs.SQUEEZE, lambda a, dims: lax.squeeze(a, dims))
_reg(PrimIDs.CAT, lambda tensors, dim: jnp.concatenate(tensors, axis=dim))


def _pad(a, padding_value, padding_config):
    pv = jnp.asarray(padding_value, dtype=a.dtype) if not hasattr(padding_value, "dtype") else padding_value.astype(a.dtype)
    return lax.pad(a, pv, tuple(tuple(int(x) for x in cfg) for cfg in padding_config))


_reg(PrimIDs.PAD, _pad)
_reg(PrimIDs.FLIP, lambda a, dims: jnp.flip(a, dims))
_reg(PrimIDs.TAKE, lambda a, indices, dim: jnp.take(a, indices, axis=dim))
_reg(PrimIDs.TAKE_ALONG_AXIS, lambda a, indices, dim: jnp.take_along_axis(a, indices, axis=dim))


def _at_dim(a, indices, dim):
    """a.at[:, ..., indices, ...] with `indices` along dim."""
    idx = [builtins.slice(None)] * a.ndim
    idx[dim] = indices
    return a.at[tuple(idx)]


_reg(PrimIDs.INDEX_ADD, lambda a, indices, value, dim: _at_dim(a, indices, dim).add(value))
_reg(PrimIDs.INDEX_COPY, lambda a, indices, value, dim: _at_dim(a, indices, dim).set(value))

# time steps a block of the scan holds at once: the (b, block, d, n) decay and
# input terms of a block are the working set, the state is carried between blocks
_SCAN_BLOCK = 64


def _selective_scan(x, dt, A, B, C, h0):
    f32 = jnp.float32
    b, T, d = x.shape
    A = A.astype(f32)
    blk = _SCAN_BLOCK if T % _SCAN_BLOCK == 0 else T

    def combine(left, right):
        (a1, b1), (a2, b2) = left, right
        return a1 * a2, a2 * b1 + b2

    def block(h, inp):
        xb, dtb, Bb, Cb = (v.astype(f32) for v in inp)               # (b, blk, ..)
        decay = jnp.exp(dtb[..., None] * A)                           # (b, blk, d, n)
        drive = (dtb * xb)[..., None] * Bb[:, :, None, :]
        drive = drive.at[:, 0].add(decay[:, 0] * h)                   # the carried-in state
        _, hs = lax.associative_scan(combine, (decay, drive), axis=1)
        return hs[:, -1], jnp.einsum("btdn,btn->btd", hs, Cb)

    def blocks(v):  # (b, T, k) -> (T // blk, b, blk, k)
        return jnp.moveaxis(v.reshape(b, T // blk, blk, v.shape[-1]), 1, 0)

    hT, y = lax.scan(block, h0.astype(f32), tuple(blocks(v) for v in (x, dt, B, C)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, T, d)
    return y.astype(x.dtype), hT.astype(h0.dtype)


_reg(PrimIDs.SELECTIVE_SCAN, _selective_scan)


def _scatter_add(a, indices, value, dim):
    return a.at[indices].add(value) if dim == 0 else _scatter_add_general(a, indices, value, dim)


def _scatter_add_general(a, indices, value, dim):
    # torch.scatter_add semantics: indices same rank as a/value
    dnums = jnp.indices(indices.shape)
    gather_idx = list(dnums)
    gather_idx[dim] = indices
    return a.at[tuple(gather_idx)].add(value)


_reg(PrimIDs.SCATTER_ADD, _scatter_add_general)
def _norm_idx(start_indices):
    return tuple(jnp.asarray(i, jnp.int32) for i in start_indices)


_reg(PrimIDs.DYNAMIC_SLICE, lambda a, start_indices, slice_sizes: lax.dynamic_slice(a, _norm_idx(start_indices), slice_sizes))
_reg(PrimIDs.DYNAMIC_UPDATE_SLICE, lambda a, update, start_indices: lax.dynamic_update_slice(a, update, _norm_idx(start_indices)))

# ---- elementwise unary ----
_unary_impls = {
    PrimIDs.ABS: jnp.abs, PrimIDs.NEG: jnp.negative, PrimIDs.EXP: jnp.exp, PrimIDs.EXP2: jnp.exp2,
    PrimIDs.EXPM1: jnp.expm1, PrimIDs.LOG: jnp.log, PrimIDs.LOG1P: jnp.log1p, PrimIDs.LOG2: jnp.log2,
    PrimIDs.SQRT: jnp.sqrt, PrimIDs.RSQRT: lax.rsqrt, PrimIDs.SIN: jnp.sin, PrimIDs.COS: jnp.cos,
    PrimIDs.TAN: jnp.tan, PrimIDs.TANH: jnp.tanh, PrimIDs.ASIN: jnp.arcsin, PrimIDs.ACOS: jnp.arccos,
    PrimIDs.ATAN: jnp.arctan, PrimIDs.SINH: jnp.sinh, PrimIDs.COSH: jnp.cosh, PrimIDs.ASINH: jnp.arcsinh,
    PrimIDs.ACOSH: jnp.arccosh, PrimIDs.ATANH: jnp.arctanh, PrimIDs.ERF: lax.erf, PrimIDs.ERFC: lax.erfc,
    PrimIDs.ERFINV: lax.erf_inv, PrimIDs.FLOOR: jnp.floor, PrimIDs.CEIL: jnp.ceil,
    PrimIDs.ROUND: jnp.round, PrimIDs.TRUNC: jnp.trunc, PrimIDs.SIGN: jnp.sign,
    PrimIDs.ISFINITE: jnp.isfinite, PrimIDs.ISNAN: jnp.isnan, PrimIDs.ISINF: jnp.isinf,
    PrimIDs.RECIPROCAL: jnp.reciprocal, PrimIDs.LOGICAL_NOT: jnp.logical_not,
    PrimIDs.BITWISE_NOT: jnp.invert, PrimIDs.REAL: jnp.real, PrimIDs.IMAG: jnp.imag,
    PrimIDs.LOG10: jnp.log10, PrimIDs.LGAMMA: lax.lgamma, PrimIDs.DIGAMMA: lax.digamma,
    PrimIDs.SIGNBIT: jnp.signbit,
}
for pid, fn in _unary_impls.items():
    _reg(pid, fn)

# float unary on int inputs should produce f32 (framework semantics)
for pid in (PrimIDs.EXP, PrimIDs.LOG, PrimIDs.SQRT, PrimIDs.RSQRT, PrimIDs.SIN, PrimIDs.COS,
            PrimIDs.TANH, PrimIDs.ERF):
    base = ex.get_impl(pid)

    def _floatify(fn):
        def wrapped(a):
            if jnp.issubdtype(jnp.asarray(a).dtype, jnp.integer) or jnp.asarray(a).dtype == jnp.bool_:
                a = jnp.asarray(a).astype(jnp.float32)
            return fn(a)

        return wrapped

    _reg(pid, _floatify(base))

# ---- elementwise binary ----
_binary_impls = {
    PrimIDs.ADD: jnp.add, PrimIDs.SUB: jnp.subtract, PrimIDs.MUL: jnp.multiply,
    PrimIDs.DIV: jnp.true_divide, PrimIDs.POW: jnp.power, PrimIDs.FMOD: jnp.fmod,
    PrimIDs.REMAINDER: jnp.remainder, PrimIDs.MAXIMUM: jnp.maximum, PrimIDs.MINIMUM: jnp.minimum,
    PrimIDs.ATAN2: jnp.arctan2, PrimIDs.BITWISE_AND: jnp.bitwise_and,
    PrimIDs.BITWISE_OR: jnp.bitwise_or, PrimIDs.BITWISE_XOR: jnp.bitwise_xor,
    PrimIDs.SHIFT_LEFT: jnp.left_shift, PrimIDs.SHIFT_RIGHT: jnp.right_shift,
    PrimIDs.EQ: jnp.equal, PrimIDs.NE: jnp.not_equal, PrimIDs.LT: jnp.less,
    PrimIDs.LE: jnp.less_equal, PrimIDs.GT: jnp.greater, PrimIDs.GE: jnp.greater_equal,
    PrimIDs.NEXTAFTER: jnp.nextafter, PrimIDs.COPYSIGN: jnp.copysign, PrimIDs.HYPOT: jnp.hypot,
    PrimIDs.GCD: jnp.gcd, PrimIDs.LCM: jnp.lcm,
}
for pid, fn in _binary_impls.items():
    _reg(pid, fn)


def _div_torch(a, b):
    # torch true_divide on ints promotes to float, and clang.true_divide
    # pre-promotes (int_to_float=True) — so float operands take the plain
    # divide. Int operands reach DIV only via clang.floor_divide, whose
    # meta keeps the integer dtype: execute integer (floor) division so the
    # runtime dtype matches the trace (true_divide here returned f32 and
    # broke downstream integer consumers, e.g. gather indices).
    if jnp.issubdtype(jnp.result_type(a, b), jnp.integer):
        return jnp.floor_divide(a, b)
    return jnp.true_divide(a, b)


_reg(PrimIDs.DIV, _div_torch)
_reg(PrimIDs.WHERE, jnp.where)

# ---- reductions ----
_reg(PrimIDs.SUM, lambda a, dims, *, output_dtype=None: jnp.sum(a, axis=dims, dtype=_jd(output_dtype)))
_reg(PrimIDs.PROD, lambda a, dims, *, output_dtype=None: jnp.prod(a, axis=dims, dtype=_jd(output_dtype)))
_reg(PrimIDs.AMAX, lambda a, dims: jnp.max(a, axis=dims))
def _var_impl(a, dims, correction=1):
    n = 1
    for d in dims:
        n *= a.shape[d]
    m = jnp.mean(a, axis=dims, keepdims=True)
    centered = a - m
    sq = (centered * jnp.conj(centered)).real if jnp.iscomplexobj(a) else centered * centered
    # torch divides by max(0, n - correction): inf for over-corrected counts
    return jnp.sum(sq, axis=dims) / max(0, n - correction)


_reg(PrimIDs.VAR, _var_impl)
_reg(PrimIDs.AMIN, lambda a, dims: jnp.min(a, axis=dims))
_reg(PrimIDs.ARGMAX, lambda a, dim: jnp.argmax(a, axis=dim).astype(_jd(dtypes.int64)))
_reg(PrimIDs.ARGMIN, lambda a, dim: jnp.argmin(a, axis=dim).astype(_jd(dtypes.int64)))
_reg(PrimIDs.ANY, lambda a, dims: jnp.any(a, axis=dims))
_reg(PrimIDs.CUMSUM, lambda a, dim: jnp.cumsum(a, axis=dim))
_reg(PrimIDs.CUMPROD, lambda a, dim: jnp.cumprod(a, axis=dim))


def _cummax(a, dim):
    # joint (value, index) scan so indices stay correct through NaNs and ties
    # (torch: NaN propagates and carries its position; ties keep the latest).
    dim = dim % a.ndim
    idx = jnp.arange(a.shape[dim], dtype=jnp.int32)
    idx = jnp.broadcast_to(idx.reshape((-1,) + (1,) * (a.ndim - 1 - dim)), a.shape)
    is_float = jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)

    def combine(x, y):
        xv, xi = x
        yv, yi = y
        take_y = yv >= xv
        if is_float:
            # NaN absorbs (a NaN on the right always wins, incl. over an
            # earlier NaN); a non-NaN right never beats a NaN left
            take_y = jnp.logical_or(jnp.isnan(yv), jnp.logical_and(take_y, ~jnp.isnan(xv)))
        return jnp.where(take_y, yv, xv), jnp.where(take_y, yi, xi)

    values, indices = lax.associative_scan(combine, (a, idx), axis=dim)
    return values, indices


_reg(PrimIDs.CUMMAX, _cummax)


def _reduce_window(a, window_dims, strides, padding, *, op="max"):
    import numpy as np

    dt = jnp.asarray(a).dtype
    is_float = jnp.issubdtype(dt, jnp.floating)
    init, fn = {
        "max": (-np.inf if is_float else np.iinfo(dt).min, lax.max),
        "min": (np.inf if is_float else np.iinfo(dt).max, lax.min),
        "sum": (0, lax.add),
    }[op]
    # concrete numpy scalar init: required for jax's monoid fast-path, which
    # is what makes reduce_window reverse-mode differentiable
    init = np.array(init, dt)[()]
    return lax.reduce_window(a, init, fn, tuple(int(w) for w in window_dims),
                             tuple(int(s) for s in strides), tuple((int(l), int(h)) for l, h in padding))


_reg(PrimIDs.REDUCE_WINDOW, _reduce_window)
_reg(PrimIDs.TOPK, lambda a, k, dim: _topk(a, k, dim))


def _topk(a, k, dim):
    if dim != a.ndim - 1 and dim != -1:
        a_m = jnp.moveaxis(a, dim, -1)
        v, i = lax.top_k(a_m, k)
        return jnp.moveaxis(v, -1, dim), jnp.moveaxis(i, -1, dim).astype(jnp.int32)
    v, i = lax.top_k(a, k)
    return v, i.astype(jnp.int32)


_reg(PrimIDs.ARGSORT, lambda a, dim, descending=False: (
    jnp.argsort(-a if descending else a, axis=dim).astype(jnp.int32)))
_reg(PrimIDs.SORT, lambda a, dim, descending=False: (-jnp.sort(-a, axis=dim) if descending else jnp.sort(a, axis=dim)))


# ---- linear algebra / NN: MXU ops with bf16-friendly accumulation ----
def _matmul(a, b):
    # accumulate in f32 on the MXU regardless of input precision
    return jnp.matmul(a, b, preferred_element_type=_preferred_acc(a))


def _preferred_acc(a):
    d = jnp.asarray(a).dtype
    if d in (jnp.bfloat16, jnp.float16):
        return jnp.float32
    return None


def _matmul_cast(a, b):
    out = jnp.matmul(a, b, preferred_element_type=_preferred_acc(a))
    return out.astype(jnp.asarray(a).dtype)


_reg(PrimIDs.MATMUL, _matmul_cast)


def _linear(a, w, bias=None):
    out = jnp.matmul(a, w.T, preferred_element_type=_preferred_acc(a)).astype(jnp.asarray(a).dtype)
    return out


_reg(PrimIDs.LINEAR, _linear)


def _convolution(a, weight, bias, stride, padding, dilation, groups):
    n_spatial = a.ndim - 2
    dim_chars = "DHW"[-n_spatial:] if n_spatial <= 3 else None
    lhs_spec = "NC" + dim_chars
    rhs_spec = "OI" + dim_chars
    out = lax.conv_general_dilated(
        a, weight,
        window_strides=tuple(stride),
        padding=tuple((p, p) for p in padding),
        rhs_dilation=tuple(dilation),
        dimension_numbers=(lhs_spec, rhs_spec, lhs_spec),
        feature_group_count=groups,
        preferred_element_type=_preferred_acc(a),
    ).astype(jnp.asarray(a).dtype)
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * n_spatial)
    return out


_reg(PrimIDs.CONVOLUTION, _convolution)


def _conv_transpose(a, weight, bias, stride, padding, output_padding, dilation, groups):
    # torch layout: a (N, Cin, *S), weight (Cin, Cout/groups, *K).
    # Implemented as the gradient of a forward conv (lhs-dilated conv), which
    # matches torch.nn.functional.conv_transpose semantics exactly.
    n_spatial = a.ndim - 2
    dim_chars = "DHW"[-n_spatial:]
    lhs_spec = "NC" + dim_chars
    rhs_spec = "IO" + dim_chars
    k_eff = [(weight.shape[2 + i] - 1) * dilation[i] + 1 for i in range(n_spatial)]
    pads = tuple(
        (k_eff[i] - 1 - padding[i], k_eff[i] - 1 - padding[i] + output_padding[i])
        for i in range(n_spatial)
    )
    w = jnp.flip(weight, axis=tuple(range(2, 2 + n_spatial)))
    if groups > 1:
        # regroup (Cin, Cout/g, *K) -> feature groups over output channels
        cin, coutg = w.shape[0], w.shape[1]
        w = w.reshape((groups, cin // groups, coutg) + w.shape[2:])
        w = jnp.moveaxis(w, 2, 1).reshape((groups * coutg, cin // groups) + w.shape[3:])
        rhs_spec = "OI" + dim_chars
    out = lax.conv_general_dilated(
        a, w,
        window_strides=(1,) * n_spatial,
        padding=pads,
        lhs_dilation=tuple(stride),
        rhs_dilation=tuple(dilation),
        dimension_numbers=(lhs_spec, rhs_spec, lhs_spec),
        feature_group_count=groups,
        preferred_element_type=_preferred_acc(a),
    ).astype(jnp.asarray(a).dtype)
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * n_spatial)
    return out


_reg(PrimIDs.CONV_TRANSPOSE, _conv_transpose)
_reg(PrimIDs.EMBEDDING, lambda indices, weight: jnp.take(weight, indices, axis=0))


def _einsum_impl(spec, *operands):
    return jnp.einsum(spec, *operands, preferred_element_type=_preferred_acc(operands[0])).astype(
        jnp.asarray(operands[0]).dtype)


_reg(PrimIDs.EINSUM, _einsum_impl)


def _scatter(a, indices, value, dim):
    return jnp.put_along_axis(a, indices, value, axis=dim, inplace=False)


_reg(PrimIDs.SCATTER, _scatter)


def _grouped_mm(a, b, group_sizes):
    return lax.ragged_dot(a, b, group_sizes.astype(jnp.int32),
                          preferred_element_type=_preferred_acc(a)).astype(jnp.asarray(a).dtype)


_reg(PrimIDs.GROUPED_MM, _grouped_mm)

# ---- memory / interop ----
_reg(PrimIDs.ITEM, lambda a: a.item())


def _copy_with_setitem(a, key, value):
    return a.at[key].set(value)


_reg(PrimIDs.COPY_WITH_SETITEM, _copy_with_setitem)
_reg(PrimIDs.UPDATE_ALIASES, lambda tensors: tuple(tensors))


# ---------------------------------------------------------------------------
# eager escape hatch: execute a symbol on concrete values by tracing it
# ---------------------------------------------------------------------------


def eager_execute(sym, *args, **kwargs):
    from ..core.proxies import proxy_from_jax, Proxy
    from ..core.trace import TraceCtx, tracectx
    from ..core import prims as _p

    trc = TraceCtx(None)
    flat_concrete = []
    with tracectx(trc):
        def proxify(x):
            if isinstance(x, (Number, str, type(None), tuple, list, dict, dtypes.dtype)):
                return x
            p = proxy_from_jax(x)
            if isinstance(p, Proxy) and not isinstance(x, Proxy):
                flat_concrete.append((p, x))
            return p

        pargs = [proxify(a) for a in args]
        pkwargs = {k: proxify(v) for k, v in kwargs.items()}
        out = sym(*pargs, **pkwargs)
        _p.python_return(out)
    trc.args = tuple(p for p, _ in flat_concrete)
    from .passes import transform_for_execution

    trc = transform_for_execution(trc, [ex])
    fn = trc.python_callable()
    return fn(*[v for _, v in flat_concrete])
