"""Per-symbol FLOPs/bytes cost model and roofline classification.

The paper's design delegates all compute to external executors, so knowing
*which* executor/kernel choice to fix requires joining measured device time
(observability/profiler.py) with an analytic cost per trace region. This
module is that cost model: ``bsym_cost`` prices one BoundSymbol,
``region_cost`` aggregates a fusion region's subsymbols, and
``roofline_tag`` classifies a region as compute-, memory-, or comms-bound
against the chip's peak FLOP/s and HBM bandwidth.

The model is cross-checkable against XLA's own numbers: ``xla_cost`` reads
``cost_analysis()`` off a lowered executable (tests/test_profiler.py does
this for a lone matmul).

Conventions: FLOPs count multiply-accumulate as 2 ops (matching XLA's
cost_analysis and the 6N training-step accounting in bench.py); bytes are
the HBM-visible traffic — every input read once plus every output written
once (fusion means intermediates stay in registers/VMEM, so a REGION's
bytes are its fused interface, not the sum of its members').
"""
from __future__ import annotations

from typing import Iterable, Optional

# (bf16 MXU peak TFLOP/s, HBM GB/s) by ``jax.devices()[0].device_kind``: the
# one peaks table (bench.py and benchmarks/litgpt_bench.py read it). Source:
# Google Cloud TPU documentation, per-chip figures; only the v5e kind string
# has been seen on a chip from this repo. The "cpu" row is nominal — it keeps
# roofline tags defined in the CPU tests and is no measured peak.
DEVICE_PEAKS = {
    "TPU v4": (275.0, 1228.0),
    "TPU v5 lite": (197.0, 819.0),
    "TPU v5p": (459.0, 2765.0),
    "TPU v6 lite": (918.0, 1640.0),
    "cpu": (1.0, 50.0),
}


def device_peaks(device_kind: Optional[str] = None) -> tuple[float, float]:
    """(peak_tflops, peak_hbm_gbs) of ``device_kind`` (default: the local
    device). A device that is not in the table is an error, not a default."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak FLOP/s and HBM bandwidth recorded for device_kind "
            f"{device_kind!r}; add it to observability/flops.py DEVICE_PEAKS "
            f"with its source") from None


def _numel(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _tensor_nbytes(p) -> int:
    shape = getattr(p, "shape", None)
    dtype = getattr(p, "dtype", None)
    if shape is None or dtype is None:
        return 0
    itemsize = getattr(dtype, "bytes", None) or getattr(dtype, "itemsize", None) or 4
    return _numel(shape) * int(itemsize)


def _io_bytes(bsym) -> int:
    return (sum(_tensor_nbytes(p) for p in bsym.flat_proxy_args())
            + sum(_tensor_nbytes(p) for p in bsym.flat_proxy_outs()))


def _out_numel(bsym) -> int:
    return sum(_numel(p.shape) for p in bsym.flat_proxy_outs()
               if getattr(p, "shape", None) is not None)


def _in_numel(bsym) -> int:
    return sum(_numel(p.shape) for p in bsym.flat_proxy_args()
               if getattr(p, "shape", None) is not None)


def _matmul_flops(bsym) -> float:
    """2 * prod(out) * K for the contraction, generically over batched args."""
    args = [a for a in bsym.flat_proxy_args() if getattr(a, "shape", None) is not None]
    outs = [o for o in bsym.flat_proxy_outs() if getattr(o, "shape", None) is not None]
    if not args or not outs:
        return 0.0
    a = args[0]
    k = int(a.shape[-1]) if len(a.shape) >= 1 else 1
    return 2.0 * _numel(outs[0].shape) * k


def _linear_flops(bsym) -> float:
    # linear(x, w, b): out = x @ w.T (+ b) — 2*M*N*K plus the bias add
    flops = _matmul_flops(bsym)
    if len(bsym.args) > 2 and bsym.args[2] is not None:
        flops += _out_numel(bsym)
    return flops


def _conv_flops(bsym) -> float:
    args = [a for a in bsym.flat_proxy_args() if getattr(a, "shape", None) is not None]
    outs = [o for o in bsym.flat_proxy_outs() if getattr(o, "shape", None) is not None]
    if len(args) < 2 or not outs:
        return 0.0
    w = args[1]
    # per output element: one MAC per weight-kernel element over in-channels
    per_out = 2.0 * _numel(w.shape) / max(1, int(w.shape[0]))
    return per_out * _numel(outs[0].shape)


def _zero(bsym) -> float:
    return 0.0


def _ew1(bsym) -> float:
    return float(_out_numel(bsym))


def _reduction_flops(bsym) -> float:
    return float(_in_numel(bsym))


# -- collective cost (ring model) -------------------------------------------

# mesh-axis sizes for collectives whose bsym carries no ``world_size``
# kwarg (dist.all_reduce, dist.synchronize take only (x, axis)): the
# parallel frontends register {axis name: size} when a plan materializes,
# so the ring model prices the mesh that will actually run, not a guess
_AXIS_SIZES: dict[str, int] = {}


def set_axis_sizes(sizes: Optional[dict]) -> None:
    """Register (or clear, with None/{}) mesh axis sizes for collective
    pricing: ``set_axis_sizes({"dp": 8, "tp": 4})``."""
    _AXIS_SIZES.clear()
    if sizes:
        _AXIS_SIZES.update({str(k): int(v) for k, v in sizes.items()})


def _collective_world_size(bsym) -> int:
    """Participant count N for a collective bsym: the ``world_size`` kwarg
    when the prim carries one, else the registered size of its mesh axis,
    else 2 — the smallest real multi-device mesh, which reproduces the old
    one-buffer-width model for an all-reduce instead of zeroing comms."""
    kwargs = getattr(bsym, "kwargs", None) or {}
    ws = kwargs.get("world_size")
    if ws is None:
        axis = kwargs.get("axis")
        if axis is None:
            axis = next((a for a in getattr(bsym, "args", ()) or ()
                         if isinstance(a, str)), None)
        if axis is not None:
            ws = _AXIS_SIZES.get(str(axis))
    try:
        n = int(ws)
    except (TypeError, ValueError):
        n = 0
    return n if n >= 2 else 2


# bytes a ring algorithm moves per participant, as a multiple of the full
# buffer S (NCCL/ICI accounting): all-reduce = reduce-scatter + all-gather
# = 2(N-1)/N * S; one-pass collectives move (N-1)/N * S
_COLL_TWO_PASS = ("all_reduce", "pmean")
_COLL_ONE_PASS = ("all_gather", "reduce_scatter", "all_to_all")


def collective_bytes(bsym) -> int:
    """ICI bytes one participant moves for a collective, per the ring
    model. S is the FULL (post-gather / pre-scatter) buffer — the max
    single-tensor size on the interface, so a sharded input doesn't halve
    an all-gather's priced traffic."""
    op = str(getattr(bsym.sym, "id", None) or bsym.sym.name)
    tail = op.rsplit(".", 1)[-1]
    n = _collective_world_size(bsym)
    size = max(
        [_tensor_nbytes(p) for p in bsym.flat_proxy_args()]
        + [_tensor_nbytes(p) for p in bsym.flat_proxy_outs()]
        + [0])
    if tail in _COLL_TWO_PASS:
        factor = 2.0 * (n - 1) / n
    elif tail in _COLL_ONE_PASS:
        factor = (n - 1) / n
    else:
        # broadcast / ppermute / synchronize barriers: one buffer width
        factor = 1.0
    return int(size * factor)


def _prim_cost_table():
    """PrimID -> flops fn. Built lazily: prims imports symbol (cycle)."""
    from ..core.prims import PrimIDs as P

    table = {
        P.MATMUL: _matmul_flops,
        P.EINSUM: _matmul_flops,
        P.GROUPED_MM: _matmul_flops,
        P.LINEAR: _linear_flops,
        P.CONVOLUTION: _conv_flops,
        P.CONV_TRANSPOSE: _conv_flops,
        P.EMBEDDING: _zero,  # a gather: bytes-bound, no arithmetic
        P.WHERE: _ew1,
        P.REDUCE_WINDOW: _reduction_flops,
        P.CUMSUM: _reduction_flops, P.CUMPROD: _reduction_flops, P.CUMMAX: _reduction_flops,
        P.VAR: _reduction_flops,
        P.TOPK: _reduction_flops, P.SORT: _reduction_flops, P.ARGSORT: _reduction_flops,
    }
    for pid in (P.SUM, P.PROD, P.AMAX, P.AMIN, P.ARGMAX, P.ARGMIN, P.ANY):
        table[pid] = _reduction_flops
    return table


_PRIM_COSTS = None
_STRUCTURAL_IDS = None


def _tables():
    global _PRIM_COSTS, _STRUCTURAL_IDS
    if _PRIM_COSTS is None:
        from ..core.prims import PrimIDs as P

        _PRIM_COSTS = _prim_cost_table()
        _STRUCTURAL_IDS = frozenset((
            P.RETURN, P.DEL, P.COMMENT, P.PRINT, P.UNPACK_TRIVIAL,
            P.UNPACK_GLOBAL, P.UNPACK_CLOSURE, P.UNPACK_ATTR, P.UNPACK_ITEM,
            P.UNPACK_TENSOR_DATA, P.CHECK_TENSOR_SHAPE_AND_METADATA,
            P.CHECK_NUMBER_TYPE_AND_VALUE, P.CHECK_LITERAL_LIKE,
            P.GET_GRAD, P.PUT_GRAD, P.ITEM,
        ))
    return _PRIM_COSTS, _STRUCTURAL_IDS


def bsym_cost(bsym) -> dict:
    """{"flops": float, "bytes": int} for one BoundSymbol.

    Priority: the symbol's own ``cost_fn`` annotation (core/symbol.py) →
    the prim table → recurse into subsymbols (composites price as the sum
    of their decomposition's flops, with interface bytes) → tag heuristics.
    """
    from ..core.symbol import OpTags

    cost_fn = getattr(bsym.sym, "cost_fn", None)
    if cost_fn is not None:
        c = cost_fn(bsym)
        return {"flops": float(c.get("flops", 0.0)), "bytes": int(c.get("bytes", _io_bytes(bsym)))}

    table, structural = _tables()
    sid = bsym.sym.id
    if sid in structural:
        return {"flops": 0.0, "bytes": 0}
    fn = table.get(sid)
    if fn is not None:
        return {"flops": fn(bsym), "bytes": _io_bytes(bsym)}
    tags = bsym.sym.tags
    if OpTags.MATMUL_OP in tags:
        return {"flops": _matmul_flops(bsym), "bytes": _io_bytes(bsym)}
    if OpTags.SHAPE_OP in tags:
        return {"flops": 0.0, "bytes": _io_bytes(bsym)}
    if OpTags.REDUCTION_OP in tags:
        return {"flops": _reduction_flops(bsym), "bytes": _io_bytes(bsym)}
    if OpTags.COLLECTIVE in tags:
        # collectives move bytes over ICI per the ring model (an N-way
        # all-reduce moves 2(N-1)/N of the buffer, not one buffer width);
        # arithmetic is the reduce itself
        return {"flops": float(_out_numel(bsym)), "bytes": collective_bytes(bsym)}
    if bsym.subsymbols:
        flops = sum(bsym_cost(s)["flops"] for s in bsym.subsymbols)
        return {"flops": flops, "bytes": _io_bytes(bsym)}
    if OpTags.ELEMENTWISE in tags:
        return {"flops": _ew1(bsym), "bytes": _io_bytes(bsym)}
    # unknown prim: price as elementwise over the output (never zero-cost a
    # compute op silently; shape/structural ids were already filtered)
    return {"flops": _ew1(bsym), "bytes": _io_bytes(bsym)}


def region_cost(bsyms: Iterable, *, inputs=None, outputs=None) -> dict:
    """Aggregate cost of a fusion region: flops sum over members, bytes as
    the region INTERFACE — fused intermediates never touch HBM, so summing
    member bytes would overstate traffic and misclassify compute-bound
    regions as memory-bound. Pass the fusion bsym's own ``inputs``/
    ``outputs`` when known (xlaex regions); otherwise inputs are inferred
    as proxies read before being produced and outputs as every member out
    (a conservative over-count)."""
    bsyms = list(bsyms)
    flops = sum(bsym_cost(b)["flops"] for b in bsyms)
    if inputs is None:
        produced: set = set()
        seen: dict = {}
        for b in bsyms:
            for p in b.flat_proxy_args():
                name = getattr(p, "name", None)
                if name is not None and name not in produced and name not in seen:
                    seen[name] = p
            for p in b.flat_proxy_outs():
                name = getattr(p, "name", None)
                if name is not None:
                    produced.add(name)
        inputs = list(seen.values())
    if outputs is None:
        outputs = [p for b in bsyms for p in b.flat_proxy_outs()]
    nbytes = (sum(_tensor_nbytes(p) for p in inputs)
              + sum(_tensor_nbytes(p) for p in outputs))
    return {"flops": flops, "bytes": nbytes}


def fusion_cost(fusion_bsym) -> dict:
    """Cost of a formed fusion region bsym: flops from its subsymbols,
    bytes from its own (interface) args/outs."""
    return region_cost(fusion_bsym.subsymbols,
                       inputs=fusion_bsym.flat_proxy_args(),
                       outputs=fusion_bsym.flat_proxy_outs())


def arithmetic_intensity(flops: float, nbytes: int) -> Optional[float]:
    if not nbytes:
        return None
    return flops / nbytes


def roofline_tag(flops: float, nbytes: int, *, category: str = "compute",
                 peaks: Optional[tuple[float, float]] = None) -> str:
    """"compute-bound" | "memory-bound" | "comms-bound" for one region.

    Collective/transfer regions are comms-bound by construction; compute
    regions compare arithmetic intensity (flops/byte) against the chip's
    ridge point peak_flops / peak_bw."""
    if category in ("collective", "transfer"):
        return "comms-bound"
    peak_tflops, peak_gbs = peaks or device_peaks()
    ridge = (peak_tflops * 1e12) / (peak_gbs * 1e9)  # flops per byte
    ai = arithmetic_intensity(flops, nbytes)
    if ai is None:
        return "memory-bound" if nbytes or not flops else "compute-bound"
    return "compute-bound" if ai >= ridge else "memory-bound"


def measured_mfu(flops: float, device_us: float,
                 peak_tflops: Optional[float] = None) -> Optional[float]:
    """Model FLOPs / (measured device seconds × peak) — the measured
    counterpart of bench.py's analytic `mfu` (docs/performance.md)."""
    if not device_us or device_us <= 0:
        return None
    if peak_tflops is None:
        peak_tflops = device_peaks()[0]
    return (flops / (device_us * 1e-6)) / (peak_tflops * 1e12)


def xla_cost(compiled) -> Optional[dict]:
    """{"flops", "bytes"} from XLA's cost_analysis() on a compiled
    executable (jax.stages.Compiled), tolerating the list/dict return-shape
    drift across jax versions. None when the backend doesn't support it."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if ca is None:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return None
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed")
    if flops is None and nbytes is None:
        return None
    return {"flops": float(flops or 0.0), "bytes": float(nbytes or 0.0)}
