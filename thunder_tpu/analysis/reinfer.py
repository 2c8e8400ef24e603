"""Shape/dtype re-inference: an independent second opinion on recorded
proxy metadata.

Two layers, both diffing against what the trace *records*:

- **rule re-inference** (``reinfer_trace``): a small, independently-written
  set of inference rules per prim (shape arithmetic + dtype semantics,
  NOT the prim meta functions — those produced the recorded metadata in
  the first place, so re-running them proves nothing). Catches transforms
  that rewrite args/outputs inconsistently (metadata drift) and hand-built
  bsyms whose outputs disagree with their op.
- **impl re-inference** (``reinfer_executed``, deep mode): for claimed
  bsyms with a concrete executor impl, run ``jax.eval_shape`` over the
  impl with abstract inputs built from the recorded proxies and compare
  the abstract result against the recorded outputs. This is the check
  that would have caught the DIV int->f32 lowering bug statically (the
  trace said int32, ``jnp.true_divide`` returned f32): the dtype
  *category* (bool/int/float) of the lowered result must match the trace.
  Category-level on purpose — x64 mode and weak-type promotion legitimately
  widen within a category.

Prims with no rule are skipped and counted, never guessed: a verifier that
flags correct traces is worse than none.
"""
from __future__ import annotations

from typing import Callable, Optional

from ..core import dtypes
from ..core.prims import PrimIDs
from ..core.proxies import NumberProxy, TensorProxy, pyval
from ..core.trace import TraceCtx
from . import errors as E
from .errors import TraceCheckError

# rule: bsym -> list of (shape, dtype) per tensor output, or None to skip
_RULES: dict = {}


def rule(*pids):
    def deco(fn: Callable):
        for pid in pids:
            _RULES[pid] = fn
        return fn

    return deco


class _TMeta:
    """Normalized tensor metadata: traces embed both TensorProxies and
    concrete arrays (interned constants, e.g. captured weights riding as
    backward residuals) — rules see one shape/dtype surface for both."""

    __slots__ = ("shape", "dtype")

    def __init__(self, shape, dtype):
        self.shape = tuple(shape)
        self.dtype = dtype

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def numel(self):
        n = 1
        for s in self.shape:
            n *= s
        return n


def _tmeta(x):
    if isinstance(x, TensorProxy):
        return _TMeta(x.shape, x.dtype)
    if (hasattr(x, "shape") and hasattr(x, "dtype")
            and not isinstance(x, (bool, int, float, complex))):
        try:
            return _TMeta(tuple(int(s) for s in x.shape), dtypes.to_dtype(x))
        except Exception:
            return None
    return None


def _tensors(bsym):
    # TensorProxy args only — mirrors the prim metas' `_tensor_args` filter,
    # so dtype expectations match what the meta recorded (array constants
    # are invisible to elementwise metas and stay invisible here)
    return [_TMeta(p.shape, p.dtype) for p in bsym.flat_proxy_args()
            if isinstance(p, TensorProxy)]


# -- elementwise -------------------------------------------------------------

_BINARY_SAME = (
    PrimIDs.ADD, PrimIDs.SUB, PrimIDs.MUL, PrimIDs.DIV, PrimIDs.POW,
    PrimIDs.FMOD, PrimIDs.REMAINDER, PrimIDs.MAXIMUM, PrimIDs.MINIMUM,
    PrimIDs.ATAN2, PrimIDs.BITWISE_AND, PrimIDs.BITWISE_OR, PrimIDs.BITWISE_XOR,
    PrimIDs.NEXTAFTER, PrimIDs.COPYSIGN, PrimIDs.HYPOT, PrimIDs.GCD, PrimIDs.LCM,
)


@rule(*_BINARY_SAME)
def _binary_same(bsym):
    ts = _tensors(bsym)
    if not ts:
        return None
    shape = ts[0].shape
    if any(t.shape != shape for t in ts):
        return None  # malformed operands are the verifier's problem, not ours
    return [(shape, ts[0].dtype)]


@rule(PrimIDs.EQ, PrimIDs.NE, PrimIDs.LT, PrimIDs.LE, PrimIDs.GT, PrimIDs.GE)
def _comparison(bsym):
    ts = _tensors(bsym)
    if not ts:
        return None
    return [(ts[0].shape, dtypes.bool8)]


@rule(PrimIDs.ABS, PrimIDs.NEG, PrimIDs.FLOOR, PrimIDs.CEIL, PrimIDs.ROUND,
      PrimIDs.TRUNC, PrimIDs.SIGN, PrimIDs.BITWISE_NOT)
def _unary_same(bsym):
    a = _tmeta(bsym.args[0]) if bsym.args else None
    return [(a.shape, a.dtype)] if a else None


@rule(PrimIDs.EXP, PrimIDs.LOG, PrimIDs.SQRT, PrimIDs.RSQRT, PrimIDs.TANH,
      PrimIDs.SIN, PrimIDs.COS, PrimIDs.ERF, PrimIDs.RECIPROCAL, PrimIDs.EXP2,
      PrimIDs.LOG1P, PrimIDs.LOG2, PrimIDs.EXPM1)
def _unary_float(bsym):
    a = _tmeta(bsym.args[0]) if bsym.args else None
    return [(a.shape, dtypes.float_math_dtype(a.dtype))] if a else None


@rule(PrimIDs.ISFINITE, PrimIDs.ISNAN, PrimIDs.ISINF, PrimIDs.LOGICAL_NOT)
def _unary_bool(bsym):
    a = _tmeta(bsym.args[0]) if bsym.args else None
    return [(a.shape, dtypes.bool8)] if a else None


@rule(PrimIDs.WHERE)
def _where(bsym):
    ts = _tensors(bsym)
    if not ts:
        return None
    dt = None
    for t in bsym.args[1:]:
        if isinstance(t, TensorProxy):
            dt = t.dtype
            break
    if dt is None:
        return None
    return [(ts[0].shape, dt)]


# -- dtype / shape movement --------------------------------------------------


@rule(PrimIDs.CONVERT_ELEMENT_TYPE)
def _convert(bsym):
    a = _tmeta(bsym.args[0])
    if a is None:
        return None
    return [(a.shape, dtypes.to_dtype(bsym.args[1]))]


@rule(PrimIDs.RESHAPE)
def _reshape(bsym):
    a, shape = _tmeta(bsym.args[0]), bsym.args[1]
    if a is None:
        return None
    shape = tuple(int(pyval(s)) for s in shape)
    n = 1
    for s in shape:
        n *= s
    if n != a.numel:
        return None
    return [(shape, a.dtype)]


@rule(PrimIDs.TRANSPOSE)
def _transpose(bsym):
    a, perm = _tmeta(bsym.args[0]), bsym.args[1]
    if a is None:
        return None
    perm = tuple(int(pyval(p)) % a.ndim for p in perm)
    if sorted(perm) != list(range(a.ndim)):
        return None
    return [(tuple(a.shape[i] for i in perm), a.dtype)]


@rule(PrimIDs.BROADCAST_IN_DIM)
def _broadcast(bsym):
    a, shape = _tmeta(bsym.args[0]), bsym.args[1]
    if a is None:
        return None
    return [(tuple(int(pyval(s)) for s in shape), a.dtype)]


@rule(PrimIDs.SQUEEZE)
def _squeeze(bsym):
    a, dims = _tmeta(bsym.args[0]), bsym.args[1]
    if a is None:
        return None
    dims = {int(pyval(d)) % a.ndim for d in dims}
    return [(tuple(s for i, s in enumerate(a.shape) if i not in dims), a.dtype)]


@rule(PrimIDs.SLICE)
def _slice(bsym):
    a = _tmeta(bsym.args[0])
    if a is None:
        return None
    start, limit = bsym.args[1], bsym.args[2]
    strides = bsym.args[3] if len(bsym.args) > 3 and bsym.args[3] else tuple(1 for _ in a.shape)
    shape = tuple(
        max(0, -(-(int(pyval(l)) - int(pyval(s))) // int(pyval(st))))
        for s, l, st in zip(start, limit, strides))
    return [(shape, a.dtype)]


@rule(PrimIDs.CAT)
def _cat(bsym):
    tensors = [_tmeta(t) for t in bsym.args[0]]
    dim = bsym.args[1]
    if not tensors or any(t is None for t in tensors):
        return None
    t0 = tensors[0]
    dim = int(pyval(dim)) % t0.ndim
    total = sum(t.shape[dim] for t in tensors)
    return [(tuple(total if i == dim else s for i, s in enumerate(t0.shape)), t0.dtype)]


@rule(PrimIDs.DYNAMIC_UPDATE_SLICE, PrimIDs.SCATTER, PrimIDs.SCATTER_ADD,
      PrimIDs.INDEX_ADD, PrimIDs.INDEX_COPY, PrimIDs.COPY_WITH_SETITEM)
def _same_as_first(bsym):
    a = _tmeta(bsym.args[0]) if bsym.args else None
    return [(a.shape, a.dtype)] if a else None


# -- linear algebra ----------------------------------------------------------


@rule(PrimIDs.MATMUL)
def _matmul(bsym):
    a, b = _tmeta(bsym.args[0]), _tmeta(bsym.args[1])
    if a is None or b is None or a.ndim < 2 or b.ndim < 2:
        return None
    batch = []
    sa, sb = a.shape[:-2], b.shape[:-2]
    for i in range(max(len(sa), len(sb))):
        da = sa[len(sa) - 1 - i] if i < len(sa) else 1
        db = sb[len(sb) - 1 - i] if i < len(sb) else 1
        batch.append(max(da, db))
    shape = tuple(reversed(batch)) + (a.shape[-2], b.shape[-1])
    return [(shape, a.dtype)]


@rule(PrimIDs.LINEAR)
def _linear(bsym):
    a, w = _tmeta(bsym.args[0]), _tmeta(bsym.args[1])
    if a is None or w is None:
        return None
    return [(a.shape[:-1] + (w.shape[0],), a.dtype)]


@rule(PrimIDs.EMBEDDING)
def _embedding(bsym):
    idx, w = _tmeta(bsym.args[0]), _tmeta(bsym.args[1])
    if idx is None or w is None:
        return None
    return [(idx.shape + (w.shape[1],), w.dtype)]


# -- reductions --------------------------------------------------------------


def _reduce_shape(a, dims, keepdims=False):
    if dims is None:
        dims = tuple(range(a.ndim))
    dims = {int(pyval(d)) % max(a.ndim, 1) for d in dims}
    if keepdims:
        return tuple(1 if i in dims else s for i, s in enumerate(a.shape))
    return tuple(s for i, s in enumerate(a.shape) if i not in dims)


@rule(PrimIDs.SUM, PrimIDs.PROD, PrimIDs.AMAX, PrimIDs.AMIN)
def _reduction(bsym):
    a = _tmeta(bsym.args[0])
    if a is None:
        return None
    dims = bsym.args[1] if len(bsym.args) > 1 else None
    out_dt = bsym.kwargs.get("output_dtype")
    dt = dtypes.to_dtype(out_dt) if out_dt else a.dtype
    return [(_reduce_shape(a, dims, bool(bsym.kwargs.get("keepdims", False))), dt)]


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------


def reinfer_bsym(bsym) -> Optional[list]:
    """Expected (shape, dtype) list for a bsym's tensor outputs, or None
    when no rule applies (unknown prim / non-tensor case)."""
    fn = _RULES.get(bsym.sym.id)
    if fn is None:
        return None
    try:
        return fn(bsym)
    except Exception:
        return None  # a rule must never crash the verifier on odd operands


def reinfer_trace(trace: TraceCtx) -> dict:
    """Rule re-inference over a whole trace. Raises TraceCheckError on the
    first mismatch; returns {"checked": n, "skipped": m} on success."""
    checked = skipped = 0
    for i, bsym in enumerate(trace.bound_symbols):
        expected = reinfer_bsym(bsym)
        if expected is None:
            skipped += 1
            continue
        outs = [o for o in bsym.flat_proxy_outs() if isinstance(o, TensorProxy)]
        if len(outs) != len(expected):
            skipped += 1
            continue
        checked += 1
        for o, (shape, dt) in zip(outs, expected):
            if tuple(o.shape) != tuple(shape) or o.dtype != dt:
                raise TraceCheckError(
                    f"bsym {i} ({bsym.sym.name}): recorded output metadata of "
                    f"'{o.name}' is {tuple(o.shape)}/{o.dtype} but the "
                    f"{bsym.sym.name} rule re-infers {tuple(shape)}/{dt} "
                    f"from the recorded inputs (metadata drift)",
                    kind=E.KIND_REINFER, bsym_index=i,
                    trace_name=trace.name_of_fn())
    return {"checked": checked, "skipped": skipped}


def _dtype_category(dt) -> str:
    if dt.is_bool:
        return "bool"
    if dt.is_int:
        return "int"
    if dt.is_float:
        return "float"
    return "complex"


def reinfer_executed(trace: TraceCtx) -> dict:
    """Deep re-inference: eval_shape each claimed impl against recorded
    outputs, flagging dtype-CATEGORY disagreements (the DIV int->f32 class)
    and shape disagreements. Best-effort per bsym — ops whose abstract
    evaluation fails (opaque closures, python-side effects) are skipped."""
    import jax
    import jax.numpy as jnp

    from ..core.dtypes import to_jax_dtype

    checked = skipped = 0
    for i, bsym in enumerate(trace.bound_symbols):
        impl = bsym.impl or bsym.sym.python_impl
        if impl is None or not bsym.sym.is_prim:
            skipped += 1
            continue
        outs = [o for o in bsym.flat_proxy_outs() if isinstance(o, TensorProxy)]
        if not outs:
            skipped += 1
            continue

        def absify(x):
            if isinstance(x, TensorProxy):
                return jax.ShapeDtypeStruct(tuple(x.shape), to_jax_dtype(x.dtype))
            if isinstance(x, NumberProxy):
                return x.value
            return x

        try:
            args = [absify(a) for a in bsym.args]
            kwargs = {k: absify(v) for k, v in bsym.kwargs.items()}
            res = jax.eval_shape(lambda *a: impl(*a, **kwargs), *args)
        except Exception:
            skipped += 1
            continue
        leaves = [l for l in jax.tree_util.tree_leaves(res) if hasattr(l, "dtype")]
        if len(leaves) != len(outs):
            skipped += 1
            continue
        checked += 1
        for o, got in zip(outs, leaves):
            got_cat = ("bool" if got.dtype == jnp.bool_ else
                       "int" if jnp.issubdtype(got.dtype, jnp.integer) else
                       "float" if jnp.issubdtype(got.dtype, jnp.floating) else "complex")
            want_cat = _dtype_category(o.dtype)
            if tuple(got.shape) != tuple(o.shape) or got_cat != want_cat:
                raise TraceCheckError(
                    f"bsym {i} ({bsym.sym.name}): the bound executor impl "
                    f"computes {tuple(got.shape)}/{got.dtype} but the trace "
                    f"records '{o.name}' as {tuple(o.shape)}/{o.dtype} — the "
                    f"lowering disagrees with the recorded metadata "
                    f"(the class of bug behind the int-DIV f32 regression)",
                    kind=E.KIND_REINFER, bsym_index=i,
                    trace_name=trace.name_of_fn())
    return {"checked": checked, "skipped": skipped}
