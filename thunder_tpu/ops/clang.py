"""Core operation language: broadcasting, type promotion, indexing.

Counterpart of reference thunder/clang/__init__.py:44 (132 clang ops). These
are plain helper functions (not Symbols) that normalize arguments and call
prims; the torch-like Symbol layer above them (ops/ltorch.py) is what records
into traces as named composite ops."""
from __future__ import annotations

from numbers import Number
from typing import Any, Sequence

from ..core import dtypes, prims
from ..core.baseutils import canonicalize_dim, canonicalize_dims, check
from ..core.proxies import NumberProxy, TensorProxy, pyval


def is_tensor(x) -> bool:
    return isinstance(x, TensorProxy)


def constant(array) -> TensorProxy:
    """Wrap a concrete array (model buffer, rope cache, ...) as a trace-level
    constant tensor. The array is carried out-of-line and becomes an XLA
    constant inside fused regions."""
    return prims.tensor_constant(array)


def _is_concrete_array(x) -> bool:
    return (not isinstance(x, TensorProxy)) and hasattr(x, "shape") and hasattr(x, "dtype") \
        and not isinstance(x, (Number, NumberProxy))


def ensure_proxy(x):
    """Arrays become constant proxies; proxies and numbers pass through."""
    if _is_concrete_array(x):
        return constant(x)
    return x


# ---------------------------------------------------------------------------
# dtype conversion & promotion
# ---------------------------------------------------------------------------


def maybe_convert_to_dtype(a, dtype: dtypes.dtype):
    if isinstance(a, TensorProxy):
        if a.dtype == dtype:
            return a
        return prims.convert_element_type(a, dtype)
    if isinstance(a, (Number, NumberProxy)):
        return dtypes.dtype_to_numbertype(dtype)(pyval(a))
    raise ValueError(f"cannot convert {a} to {dtype}")


def _result_dtype(*args, int_to_float=False) -> dtypes.dtype:
    parts = []
    for a in args:
        if isinstance(a, TensorProxy):
            parts.append(a.dtype)
        elif isinstance(a, (bool,)):
            parts.append(bool)
        elif isinstance(a, int):
            parts.append(int)
        elif isinstance(a, float):
            parts.append(float)
        elif isinstance(a, complex):
            parts.append(complex)
        elif isinstance(a, NumberProxy):
            parts.append(a.python_type)
    d = dtypes.promote_dtypes(*parts)
    if int_to_float and not d.is_inexact:
        d = dtypes.float32
    return d


# ---------------------------------------------------------------------------
# broadcasting
# ---------------------------------------------------------------------------


def compute_broadcast_shape(*shapes) -> tuple:
    shapes = [s for s in shapes if s is not None]
    rank = max(len(s) for s in shapes)
    out = [1] * rank
    for s in shapes:
        off = rank - len(s)
        for i, d in enumerate(s):
            if d != 1:
                check(out[off + i] in (1, d), lambda: f"cannot broadcast shapes {shapes}")
                out[off + i] = d
    return tuple(out)


def maybe_broadcast(*args):
    """Broadcast all tensor args to a common shape (numbers pass through)."""
    shapes = [a.shape for a in args if isinstance(a, TensorProxy)]
    if not shapes:
        return args
    common = compute_broadcast_shape(*shapes)
    out = []
    for a in args:
        if isinstance(a, TensorProxy):
            out.append(expand_to(a, common))
        else:
            out.append(a)
    return tuple(out)


def expand_to(a: TensorProxy, shape: tuple) -> TensorProxy:
    if a.shape == tuple(shape):
        return a
    off = len(shape) - a.ndim
    bdims = tuple(range(off, len(shape)))
    return prims.broadcast_in_dim(a, tuple(shape), bdims)


def _elementwise_binary(prim, a, b, *, int_to_float=False, bool_out=False):
    a, b = ensure_proxy(a), ensure_proxy(b)
    dt = _result_dtype(a, b, int_to_float=int_to_float)
    a, b = maybe_broadcast(a, b)
    if not bool_out:
        a = maybe_convert_to_dtype(a, dt) if isinstance(a, TensorProxy) else a
        b = maybe_convert_to_dtype(b, dt) if isinstance(b, TensorProxy) else b
    else:
        # comparisons: make tensor dtypes agree, output bool
        ta = a.dtype if isinstance(a, TensorProxy) else None
        tb = b.dtype if isinstance(b, TensorProxy) else None
        if ta is not None and tb is not None and ta != tb:
            a = maybe_convert_to_dtype(a, dt)
            b = maybe_convert_to_dtype(b, dt)
    if not isinstance(a, TensorProxy) and not isinstance(b, TensorProxy):
        raise NotImplementedError("number-number ops should be computed statically")
    # NumberProxy operands stay runtime inputs to full (symbolic caching);
    # plain numbers are baked as before
    if not isinstance(a, TensorProxy):
        a = full_like(b, a if isinstance(a, NumberProxy) else pyval(a), dtype=dt if not bool_out else None)
    if not isinstance(b, TensorProxy):
        b = full_like(a, b if isinstance(b, NumberProxy) else pyval(b), dtype=dt if not bool_out else None)
    return prim(a, b)


# elementwise binary wrappers ------------------------------------------------


def add(a, b):
    return _elementwise_binary(prims.add, a, b)


def sub(a, b):
    return _elementwise_binary(prims.sub, a, b)


def mul(a, b):
    return _elementwise_binary(prims.mul, a, b)


def true_divide(a, b):
    return _elementwise_binary(prims.div, a, b, int_to_float=True)


def floor_divide(a, b):
    q = _elementwise_binary(prims.div, a, b)
    if q.dtype.is_float:
        return prims.floor(q)
    return q


def pow_(a, b):
    return _elementwise_binary(prims.pow, a, b)


def remainder(a, b):
    return _elementwise_binary(prims.remainder, a, b)


def fmod(a, b):
    return _elementwise_binary(prims.fmod, a, b)


def maximum(a, b):
    return _elementwise_binary(prims.maximum, a, b)


def minimum(a, b):
    return _elementwise_binary(prims.minimum, a, b)


def atan2(a, b):
    return _elementwise_binary(prims.atan2, a, b, int_to_float=True)


def bitwise_and(a, b):
    return _elementwise_binary(prims.bitwise_and, a, b)


def bitwise_or(a, b):
    return _elementwise_binary(prims.bitwise_or, a, b)


def bitwise_xor(a, b):
    return _elementwise_binary(prims.bitwise_xor, a, b)


def eq(a, b):
    return _elementwise_binary(prims.eq, a, b, bool_out=True)


def ne(a, b):
    return _elementwise_binary(prims.ne, a, b, bool_out=True)


def lt(a, b):
    return _elementwise_binary(prims.lt, a, b, bool_out=True)


def le(a, b):
    return _elementwise_binary(prims.le, a, b, bool_out=True)


def gt(a, b):
    return _elementwise_binary(prims.gt, a, b, bool_out=True)


def ge(a, b):
    return _elementwise_binary(prims.ge, a, b, bool_out=True)


def logical_and(a, b):
    return bitwise_and(to_bool(a), to_bool(b))


def logical_or(a, b):
    return bitwise_or(to_bool(a), to_bool(b))


def to_bool(a):
    if isinstance(a, TensorProxy) and not a.dtype.is_bool:
        return prims.ne(a, full_like(a, 0))
    return a


def where(pred, a, b):
    pred, a, b = ensure_proxy(pred), ensure_proxy(a), ensure_proxy(b)
    dt = _result_dtype(a, b)
    pred, a, b = maybe_broadcast(pred, a, b)
    if isinstance(a, TensorProxy):
        a = maybe_convert_to_dtype(a, dt)
    if isinstance(b, TensorProxy):
        b = maybe_convert_to_dtype(b, dt)
    if not isinstance(a, TensorProxy):
        a = full_like(pred, pyval(a), dtype=dt)
    if not isinstance(b, TensorProxy):
        b = full_like(pred, pyval(b), dtype=dt)
    return prims.where(pred, a, b)


# factories ------------------------------------------------------------------


def full(shape, fill_value, *, device=None, dtype=None):
    return prims.full(tuple(shape), fill_value, device=device, dtype=dtype)


def full_like(a: TensorProxy, fill_value, *, device=None, dtype=None):
    return prims.full(a.shape, fill_value, device=device or a.device, dtype=dtype or a.dtype)


def arange(start, stop=None, step=1, *, device=None, dtype=None):
    if stop is None:
        start, stop = 0, start
    length = max(0, -(-(pyval(stop) - pyval(start)) // pyval(step)))
    if dtype is None:
        if any(isinstance(pyval(x), float) for x in (start, stop, step)):
            dtype = dtypes.float32
        else:
            dtype = dtypes.int64
    return prims.iota(length, start=pyval(start), step=pyval(step), device=device, dtype=dtype)


# shape ops ------------------------------------------------------------------


def reshape(a: TensorProxy, shape) -> TensorProxy:
    shape = tuple(int(pyval(s)) for s in shape)
    if -1 in shape:
        known = 1
        for s in shape:
            if s != -1:
                known *= s
        shape = tuple(a.numel // known if s == -1 else s for s in shape)
    if shape == a.shape:
        return a
    return prims.reshape(a, shape)


def permute(a: TensorProxy, dims) -> TensorProxy:
    dims = canonicalize_dims(a.ndim, tuple(dims))
    if dims == tuple(range(a.ndim)):
        return a
    return prims.transpose(a, dims)


def transpose(a: TensorProxy, dim0: int, dim1: int) -> TensorProxy:
    dim0, dim1 = canonicalize_dim(a.ndim, dim0), canonicalize_dim(a.ndim, dim1)
    perm = list(range(a.ndim))
    perm[dim0], perm[dim1] = perm[dim1], perm[dim0]
    return permute(a, perm)


def matrix_transpose(a: TensorProxy) -> TensorProxy:
    if a.ndim < 2:
        return a
    return transpose(a, -2, -1)


def unsqueeze(a: TensorProxy, dim: int) -> TensorProxy:
    dim = canonicalize_dim(a.ndim + 1, dim)
    shape = a.shape[:dim] + (1,) + a.shape[dim:]
    return prims.reshape(a, shape)


def squeeze(a: TensorProxy, dim=None) -> TensorProxy:
    if dim is None:
        dims = tuple(i for i, s in enumerate(a.shape) if s == 1)
    elif isinstance(dim, (tuple, list)):
        dims = tuple(canonicalize_dim(a.ndim, pyval(d)) for d in dim)
        dims = tuple(d for d in dims if a.shape[d] == 1)
    else:
        dims = (canonicalize_dim(a.ndim, pyval(dim)),)
        if a.shape[dims[0]] != 1:
            return a
    if not dims:
        return a
    return prims.squeeze(a, dims)


def flatten(a: TensorProxy, start_dim=0, end_dim=-1) -> TensorProxy:
    start_dim = canonicalize_dim(a.ndim, start_dim)
    end_dim = canonicalize_dim(a.ndim, end_dim)
    mid = 1
    for s in a.shape[start_dim : end_dim + 1]:
        mid *= s
    shape = a.shape[:start_dim] + (mid,) + a.shape[end_dim + 1 :]
    return reshape(a, shape)


def slice_in_dim(a: TensorProxy, start, stop, dim=0, stride=1) -> TensorProxy:
    dim = canonicalize_dim(a.ndim, dim)
    starts = [0] * a.ndim
    limits = list(a.shape)
    strides = [1] * a.ndim
    starts[dim], limits[dim], strides[dim] = start, stop, stride
    return prims.slice_prim(a, tuple(starts), tuple(limits), tuple(strides))


def split(a: TensorProxy, split_size_or_sections, dim=0):
    dim = canonicalize_dim(a.ndim, dim)
    n = a.shape[dim]
    if isinstance(split_size_or_sections, int):
        sizes = [split_size_or_sections] * (n // split_size_or_sections)
        if n % split_size_or_sections:
            sizes.append(n % split_size_or_sections)
    else:
        sizes = list(split_size_or_sections)
    out, ofs = [], 0
    for s in sizes:
        out.append(slice_in_dim(a, ofs, ofs + s, dim))
        ofs += s
    return tuple(out)


def chunk(a: TensorProxy, chunks: int, dim=0):
    dim = canonicalize_dim(a.ndim, dim)
    size = -(-a.shape[dim] // chunks)
    return split(a, size, dim)


def cat(tensors, dim=0):
    tensors = [ensure_proxy(t) for t in tensors]
    dim = canonicalize_dim(tensors[0].ndim, pyval(dim))
    dt = _result_dtype(*tensors)
    tensors = [maybe_convert_to_dtype(t, dt) for t in tensors]
    return prims.cat(tensors, dim)


def stack(tensors, dim=0):
    tensors = [unsqueeze(t, dim) for t in tensors]
    return cat(tensors, dim)


def expand(a: TensorProxy, shape) -> TensorProxy:
    shape = tuple(int(pyval(s)) for s in shape)
    off = len(shape) - a.ndim
    shape = tuple(a.shape[i - off] if s == -1 else s for i, s in enumerate(shape))
    return expand_to(a, shape)


def flip(a: TensorProxy, dims) -> TensorProxy:
    dims = canonicalize_dims(a.ndim, tuple(dims))
    return prims.flip(a, dims)


def pad(a: TensorProxy, padding_value, padding_config) -> TensorProxy:
    return prims.pad(a, padding_value, tuple(padding_config))


def movedim(a: TensorProxy, source, destination) -> TensorProxy:
    src = [canonicalize_dim(a.ndim, s) for s in (source if isinstance(source, (tuple, list)) else (source,))]
    dst = [canonicalize_dim(a.ndim, d) for d in (destination if isinstance(destination, (tuple, list)) else (destination,))]
    perm = [d for d in range(a.ndim) if d not in src]
    for d, s in sorted(zip(dst, src)):
        perm.insert(d, s)
    return permute(a, perm)


# indexing -------------------------------------------------------------------


def getitem(a: TensorProxy, key):
    """Basic indexing (int/slice/None/Ellipsis/tensor) — the subset models use.
    Python-list index elements (x[[0, 2]] advanced indexing) lower as int
    tensor indices."""
    if not isinstance(key, tuple):
        key = (key,)
    def _lower_list(k):
        if isinstance(k, bool):
            # numpy/torch treat a scalar bool index as a new size-int(k) dim;
            # misrouting through the int branch silently returns row 0/1
            raise NotImplementedError(
                "scalar boolean indexing (x[True]/x[False]) is not supported; "
                "use unsqueeze / an explicit empty slice")
        if not (isinstance(k, list) and k):
            return k
        if all(isinstance(e, bool) for e in k):
            # a bool list is a MASK in torch/numpy — dynamic output shape
            raise NotImplementedError(
                "boolean mask list indexing (x[[True, False]]) has a "
                "data-dependent output shape; use jnp-level masking or "
                "masked_select via the torch interop host fallback")
        if all(isinstance(e, (int, NumberProxy)) and not isinstance(e, bool) for e in k):
            return tensor_from_sequence(k, dtype=dtypes.int32, device=a.device)
        return k

    key = tuple(_lower_list(k) for k in key)
    # expand Ellipsis — identity checks only: `in`/`.index` would run
    # TensorProxy.__eq__ against Ellipsis and bake bogus comparisons
    n_specified = sum(1 for k in key if k is not None and k is not Ellipsis)
    ell = [i for i, k in enumerate(key) if k is Ellipsis]
    if ell:
        i = ell[0]
        key = key[:i] + (slice(None),) * (a.ndim - n_specified) + key[i + 1 :]
    else:
        key = key + (slice(None),) * (a.ndim - n_specified)

    # advanced: single integer-tensor index
    tensor_idxs = [i for i, k in enumerate(key) if isinstance(k, TensorProxy)]
    if tensor_idxs:
        check(len(tensor_idxs) == 1, lambda: "multiple tensor indices not supported yet")
        ti = tensor_idxs[0]
        pre = key[:ti]
        check(all(k == slice(None) for k in pre), lambda: "tensor index after nontrivial basic index unsupported")
        idx = key[ti]
        if idx.dtype.is_bool:
            raise NotImplementedError("boolean mask indexing not supported yet")
        out = prims.take(a, idx, ti)
        rest = key[ti + 1 :]
        check(all(k == slice(None) for k in rest), lambda: "mixed advanced indexing unsupported")
        return out

    starts, limits, strides = [], [], []
    squeeze_dims = []
    unsqueeze_positions = []
    dim = 0
    out_pos = 0
    for k in key:
        if k is None:
            unsqueeze_positions.append(out_pos)
            out_pos += 1
            continue
        if isinstance(k, (int, NumberProxy)):
            kv = canonicalize_dim(a.shape[dim], int(pyval(k))) if a.shape[dim] > 0 else 0
            starts.append(kv)
            limits.append(kv + 1)
            strides.append(1)
            squeeze_dims.append(dim)
            dim += 1
            continue
        if isinstance(k, slice):
            start, stop, step = k.indices(a.shape[dim])
            check(step > 0, lambda: "negative slice steps unsupported")
            starts.append(start)
            limits.append(stop)
            strides.append(step)
            dim += 1
            out_pos += 1
            continue
        raise NotImplementedError(f"unsupported index element {k!r}")
    out = a
    if starts and (tuple(starts) != (0,) * a.ndim or tuple(limits) != a.shape or set(strides) != {1}):
        out = prims.slice_prim(a, tuple(starts), tuple(limits), tuple(strides))
    if squeeze_dims:
        out = prims.squeeze(out, tuple(squeeze_dims))
    for pos in unsqueeze_positions:
        out = unsqueeze(out, pos)
    return out


def take(a, indices, dim):
    return prims.take(a, indices, dim)


def take_along_axis(a, indices, dim):
    dim = canonicalize_dim(a.ndim, dim)
    return prims.take_along_axis(a, indices, dim)


def index_add(a, indices, value, dim):
    return prims.index_add(a, indices, value, canonicalize_dim(a.ndim, dim))


def scatter_add(a, indices, value, dim):
    return prims.scatter_add(a, indices, value, canonicalize_dim(a.ndim, dim))


# reductions -----------------------------------------------------------------


def _reduction_dims(a, dim):
    if dim is None:
        return tuple(range(a.ndim))
    if isinstance(dim, (int, NumberProxy)):
        dim = (int(pyval(dim)),)
    return canonicalize_dims(a.ndim, tuple(int(pyval(d)) for d in dim))


def _maybe_keepdim(out, a, dims, keepdim):
    if not keepdim:
        return out
    shape = tuple(1 if i in dims else s for i, s in enumerate(a.shape))
    return reshape(out, shape)


def sum_(a, dim=None, keepdim=False, *, dtype=None):
    dims = _reduction_dims(a, dim)
    if dtype is None and (a.dtype.is_bool or (a.dtype.is_int and a.dtype.bytes < 8)):
        dtype = dtypes.int64
    out = prims.sum_prim(a, dims, output_dtype=dtypes.to_dtype(dtype) if dtype else None)
    return _maybe_keepdim(out, a, dims, keepdim)


def mean(a, dim=None, keepdim=False, *, dtype=None):
    dims = _reduction_dims(a, dim)
    count = 1
    for d in dims:
        count *= a.shape[d]
    if dtype is None:
        dtype = a.dtype if a.dtype.is_inexact else dtypes.float32
    s = sum_(maybe_convert_to_dtype(a, dtypes.to_dtype(dtype)), dim, keepdim)
    return true_divide(s, count)


def var(a, dim=None, keepdim=False, *, correction=1):
    dims = _reduction_dims(a, dim)
    out = prims.var_prim(a, dims, correction=correction)
    return _maybe_keepdim(out, a, dims, keepdim)


def var_mean(a, dim=None, keepdim=False, *, correction=1):
    return var(a, dim, keepdim, correction=correction), mean(a, dim, keepdim)


def amax(a, dim=None, keepdim=False):
    dims = _reduction_dims(a, dim)
    out = prims.amax(a, dims)
    return _maybe_keepdim(out, a, dims, keepdim)


def amin(a, dim=None, keepdim=False):
    dims = _reduction_dims(a, dim)
    out = prims.amin(a, dims)
    return _maybe_keepdim(out, a, dims, keepdim)


def argmax(a, dim=None, keepdim=False):
    out = prims.argmax(a, dim)
    if dim is not None and keepdim:
        return _maybe_keepdim(out, a, (canonicalize_dim(a.ndim, pyval(dim)),), keepdim)
    return out


def argmin(a, dim=None, keepdim=False):
    out = prims.argmin(a, dim)
    if dim is not None and keepdim:
        return _maybe_keepdim(out, a, (canonicalize_dim(a.ndim, pyval(dim)),), keepdim)
    return out


def prod(a, dim=None, keepdim=False):
    dims = _reduction_dims(a, dim)
    out = prims.prod_prim(a, dims)
    return _maybe_keepdim(out, a, dims, keepdim)


def any_(a, dim=None, keepdim=False):
    dims = _reduction_dims(a, dim)
    out = prims.any_prim(to_bool(a), dims)
    return _maybe_keepdim(out, a, dims, keepdim)


def all_(a, dim=None, keepdim=False):
    return prims.logical_not(any_(prims.logical_not(to_bool(a)), dim, keepdim))


def cumsum(a, dim):
    return prims.cumsum(a, canonicalize_dim(a.ndim, dim))


# ---------------------------------------------------------------------------
# elementwise core-language wrappers (reference clang's elementwise family,
# thunder/clang/__init__.py — thin delegations: normalization/promotion
# happens in the prims metas; kept at clang level so the core language is
# complete without reaching into ltorch)
# ---------------------------------------------------------------------------


def _unary(prim):
    def op(a):
        return prim(ensure_proxy(a))

    op.__name__ = prim.name if hasattr(prim, "name") else getattr(prim, "__name__", "op")
    return op


abs = _unary(prims.abs)  # noqa: A001 — mirrors reference clang naming
acos = _unary(prims.acos)
acosh = _unary(prims.acosh)
asin = _unary(prims.asin)
asinh = _unary(prims.asinh)
atan = _unary(prims.atan)
atanh = _unary(prims.atanh)
ceil = _unary(prims.ceil)
cos = _unary(prims.cos)
cosh = _unary(prims.cosh)
digamma = _unary(prims.digamma)
erf = _unary(prims.erf)
erfc = _unary(prims.erfc)
erfinv = _unary(prims.erfinv)
exp = _unary(prims.exp)
exp2 = _unary(prims.exp2)
expm1 = _unary(prims.expm1)
floor = _unary(prims.floor)
isfinite = _unary(prims.isfinite)
isnan = _unary(prims.isnan)
lgamma = _unary(prims.lgamma)
log = _unary(prims.log)
log10 = _unary(prims.log10)
log1p = _unary(prims.log1p)
log2 = _unary(prims.log2)
logical_not = _unary(prims.logical_not)
neg = _unary(prims.neg)
reciprocal = _unary(prims.reciprocal)
round = _unary(prims.round)  # noqa: A001
rsqrt = _unary(prims.rsqrt)
sign = _unary(prims.sign)
signbit = _unary(prims.signbit)
sin = _unary(prims.sin)
sinh = _unary(prims.sinh)
sqrt = _unary(prims.sqrt)
tan = _unary(prims.tan)
tanh = _unary(prims.tanh)
trunc = _unary(prims.trunc)


def sigmoid(a):
    return prims.reciprocal(add(prims.exp(prims.neg(ensure_proxy(a))), 1.0))


def silu(a):
    a = ensure_proxy(a)
    return mul(a, sigmoid(a))


def pow(a, b):  # noqa: A001
    return _elementwise_binary(prims.pow, a, b)


def copysign(a, b):
    return _elementwise_binary(prims.copysign, a, b)


def nextafter(a, b):
    return _elementwise_binary(prims.nextafter, a, b)


def zeta(a, b):
    from ..ops.auto_register import get_auto_symbol

    return get_auto_symbol("special_zeta")(ensure_proxy(a), ensure_proxy(b))


def logical_xor(a, b):
    return ne(maybe_convert_to_dtype(ensure_proxy(a), dtypes.bool8),
              maybe_convert_to_dtype(ensure_proxy(b), dtypes.bool8))


def bitwise_not(a):
    return prims.bitwise_not(ensure_proxy(a))


def bitwise_left_shift(a, b):
    return prims.shift_left(ensure_proxy(a), ensure_proxy(b))


def bitwise_right_shift(a, b):
    return prims.shift_right(ensure_proxy(a), ensure_proxy(b))


def mod(a, b):
    return _elementwise_binary(prims.remainder, a, b)


def trunc_divide(a, b):
    return trunc(true_divide(a, b))


def lerp(start, end, weight):
    start, end = ensure_proxy(start), ensure_proxy(end)
    return add(start, mul(weight, sub(end, start)))


# ---------------------------------------------------------------------------
# indexing / structure core ops
# ---------------------------------------------------------------------------


def gather(a, indices, dim):
    """take_along_axis semantics (reference clang.gather)."""
    return take_along_axis(a, indices, dim)


def scatter(a, indices, src, dim):
    from . import ltorch

    return ltorch.scatter(a, dim, indices, src)


def index_copy(a, dim, indices, src):
    """Copy rows of src into a at positions `indices` along dim."""
    return prims.index_copy(ensure_proxy(a), indices, src,
                            canonicalize_dim(a.ndim, pyval(dim)))


def index_put(a, indices, values, accumulate=False):
    """a[indices] = values (or += with accumulate) — advanced-index write."""
    a = ensure_proxy(a)
    if len(indices) == 1 and not accumulate:
        idx = indices[0]
        bshape = (idx.shape[0],) + tuple(a.shape[1:])
        src = values if tuple(values.shape) == bshape else expand(values, bshape)
        return prims.index_copy(a, idx, src, 0)
    if len(indices) == 1 and accumulate:
        idx = indices[0]
        bshape = list(a.shape)
        bshape[0] = idx.shape[0]
        idx_shape = [1] * a.ndim
        idx_shape[0] = -1
        full_idx = expand(reshape(idx, tuple(idx_shape)), tuple(bshape))
        src = values if tuple(values.shape) == tuple(bshape) else expand(values, tuple(bshape))
        return scatter_add(a, full_idx, src, 0)
    if len(indices) > 1 and all(getattr(i, "ndim", None) == 1 for i in indices):
        # multiple 1-D index vectors over the LEADING dims (the paged-KV
        # write pattern: pool[page_ids, slots] = token_kv): linearize to one
        # flat index over the collapsed leading dims and recurse into the
        # single-index path. Same-length vectors index jointly, numpy-style.
        # Each vector is canonicalized with remainder (Python-modulo
        # semantics) so numpy-style negative indices land in THEIR dim
        # before linearization — a raw -1 in dim d would otherwise address
        # the previous row's last slot.
        n = len(indices)
        check(a.ndim >= n,
              lambda: f"index_put: {n} index tensors over a rank-{a.ndim} input")
        flat = remainder(indices[0], a.shape[0])
        for d in range(1, n):
            flat = flat * a.shape[d] + remainder(indices[d], a.shape[d])
        lead = 1
        for d in range(n):
            lead *= a.shape[d]
        a_flat = reshape(a, (lead,) + tuple(a.shape[n:]))
        out = index_put(a_flat, (flat,), values, accumulate)
        return reshape(out, tuple(a.shape))
    raise NotImplementedError("index_put with multiple >1-D index tensors")


def diagonal(a, offset=0, dim1=0, dim2=1):
    from . import ltorch

    return ltorch.diagonal_op(a, offset, dim1, dim2)


def sort(a, dim=-1, descending=False):
    from . import ltorch

    return ltorch.sort(a, dim, descending)


def topk(a, k, dim=-1):
    from . import ltorch

    return ltorch.topk(a, k, dim)


def unfold(a, dim, size, step):
    """Sliding windows along `dim` (tensor.unfold semantics)."""
    from ..ops.auto_register import get_auto_symbol

    return get_auto_symbol("unfold_dim")(ensure_proxy(a), pyval(dim), pyval(size), pyval(step))


def tensor_from_sequence(seq, *, dtype=None, device=None):
    import numpy as _np

    def conv(x):
        if isinstance(x, NumberProxy):
            return pyval(x)
        if isinstance(x, (list, tuple)):
            return [conv(e) for e in x]
        return x

    arr = _np.asarray(conv(list(seq)))
    if dtype is not None:
        arr = arr.astype(dtypes.to_jax_dtype(dtypes.to_dtype(dtype)))
    elif arr.dtype == _np.float64:
        arr = arr.astype(_np.float32)  # match jax x64-off default
    elif arr.dtype == _np.int64:
        arr = arr.astype(_np.int32)
    return constant(arr)


def empty(shape, *, dtype=dtypes.float32, device=None):
    """Uninitialized-by-contract tensor (implemented as zeros: XLA has no
    uninitialized allocation; the contract is only that values are unread)."""
    return full(tuple(shape), 0, dtype=dtype, device=device)


def uniform(shape, minval=0.0, maxval=1.0, *, dtype=dtypes.float32, device=None, key=None):
    return prims.uniform(tuple(shape), minval, maxval, dtype=dtype, key=key)


def uniform_like(a, minval=0.0, maxval=1.0, *, key=None):
    return prims.uniform(tuple(a.shape), minval, maxval, dtype=a.dtype, key=key)


def real(a):
    from ..ops.auto_register import get_auto_symbol

    return get_auto_symbol("real")(ensure_proxy(a))


def imag(a):
    from ..ops.auto_register import get_auto_symbol

    return get_auto_symbol("imag")(ensure_proxy(a))
