"""xlaex: the XLA fusion executor — the TPU analog of nvFuser.

Reference counterpart: thunder/executors/nvfuserex_impl.py:301-836 (region
claiming + FusionDefinition translation + compilation cache). Here a claimed
region's subtrace is compiled once with ``jax.jit`` — XLA does the actual
kernel fusion, MXU tiling and latency hiding; the executor's job is region
formation and caching. On a typical trace the whole computation collapses
into one fusion, which is exactly the right shape for TPU (whole-program
XLA compilation; no CUDA-graph analog needed)."""
from __future__ import annotations

import time
import warnings
from typing import Sequence

import jax

from ..core.prims import PrimIDs
from ..core.proxies import Proxy, TensorProxy, variableify
from ..core.symbol import BoundSymbol, OpTags, Symbol
from ..core.trace import TraceCtx, from_trace
from ..extend import FusionExecutor, register_executor
from ..observability import events as _obs
from ..observability import runtime as _obs_runtime

_STRUCTURAL = (PrimIDs.RETURN, PrimIDs.DEL, PrimIDs.COMMENT, PrimIDs.UNPACK_TRIVIAL)
_NOFUSE_IDS = (PrimIDs.ITEM, PrimIDs.PRINT, PrimIDs.DEVICE_PUT,
               PrimIDs.CHECK_TENSOR_SHAPE_AND_METADATA, PrimIDs.CHECK_NUMBER_TYPE_AND_VALUE)


class XLAFusionExecutor(FusionExecutor):
    def __init__(self):
        super().__init__("xla")
        self._fusion_counter = 0
        self.fusion_cache: dict = {}

    def _fusible(self, bsym: BoundSymbol) -> bool:
        if bsym.sym.id in _STRUCTURAL or bsym.sym.id in _NOFUSE_IDS:
            return False
        if OpTags.DONT_FUSE in bsym.sym.tags or OpTags.DONT_FUSE in bsym.tags:
            return False
        if OpTags.DEVICE_SYNC_OP in bsym.sym.tags:
            return False
        return bsym.impl is not None or bsym.sym.python_impl is not None

    def fusion_pass(self, trace: TraceCtx) -> TraceCtx:
        start = time.perf_counter()
        bsyms = trace.bound_symbols

        # consumed-after map: for each position, proxies read at or after it
        consumed_after: list[set] = [set() for _ in range(len(bsyms) + 1)]
        acc: set = set()
        for i in range(len(bsyms) - 1, -1, -1):
            acc = acc | {variableify(p) for p in bsyms[i].flat_proxy_args()}
            consumed_after[i] = acc

        new_bsyms: list[BoundSymbol] = []
        region: list[BoundSymbol] = []

        def flush(next_idx: int):
            nonlocal region
            if not region:
                return
            if len(region) == 1 and not _worth_fusing_alone(region[0]):
                new_bsyms.extend(region)
                region = []
                return
            new_bsyms.append(self._make_fusion(region, consumed_after[next_idx], trace))
            region = []

        for i, bsym in enumerate(bsyms):
            if self._fusible(bsym):
                region.append(bsym)
            else:
                flush(i)
                new_bsyms.append(bsym)
        flush(len(bsyms))

        out = from_trace(trace)
        out.bound_symbols = new_bsyms
        out.set_provenance(f"XLA fusion pass (took {(time.perf_counter()-start)*1000:.2f} ms)")
        return out

    def _make_fusion(self, region: Sequence[BoundSymbol], consumed_later: set, trace: TraceCtx) -> BoundSymbol:
        produced: dict = {}
        inputs: list[Proxy] = []
        seen_in: set = set()
        for bsym in region:
            for p in bsym.flat_proxy_args():
                v = variableify(p)
                if v not in produced and v not in seen_in:
                    seen_in.add(v)
                    inputs.append(p)
            for p in bsym.flat_proxy_outs():
                produced[variableify(p)] = p

        outputs = [p for v, p in produced.items() if v in consumed_later]

        subtrace = TraceCtx(None)
        subtrace.args = tuple(inputs)
        subtrace.names = set(trace.names)
        subtrace.bound_symbols = list(region)
        from ..core import prims as _p

        subtrace.bound_symbols.append(_p.python_return.bind(tuple(outputs), output=None))
        self._fusion_counter += 1
        name = f"xla_fusion_{self._fusion_counter - 1}"
        subtrace._name = name

        raw_fn = subtrace.python_callable(scoped=True)  # traced by the jax.jit below

        def scoped_fn(*args):
            # the HLO traced under this scope carries the fusion name, so
            # device profiles (xprof) map rows back to trace symbols
            with _obs_runtime.fusion_scope(name):
                return raw_fn(*args)

        # the jitted module is named after the wrapped callable
        # ("jit_xla_fusion_N"): device trace events carry it in
        # args.hlo_module, which is the profiler's primary join back to
        # this region — it works even on backends (CPU) whose per-op
        # events drop the named_scope metadata
        scoped_fn.__name__ = name
        # buffer donation (tt.jit(donated_argnums=...) -> trace.donated): a
        # region input is given to XLA when the caller gave it up, nothing
        # after the region reads it and the trace does not return it as it
        # came in (the RETURN's operands are in consumed_later too). Inlined
        # into an outer program (TrainStep's whole-step jax.jit) a region
        # cannot donate: that program's own jax.jit does. With nothing to
        # donate the call is the bare jax.jit it always was, so every other
        # program's HLO and compile-cache key stay what they are.
        donated = getattr(trace, "donated", None)
        donate = ()
        if donated and jax.core.trace_ctx.is_top_level():
            donate = tuple(i for i, p in enumerate(inputs)
                           if p.name in donated and variableify(p) not in consumed_later)
        # tt.jit(round_every_op=True) -> trace.round_every_op: XLA keeps no intermediate of a
        # fused chain in a wider type than the op's own (it would, by default, and which chains
        # it fuses follows from the shapes), so a row's bits do not depend on what else the
        # program holds. Without it the call is the jax.jit it always was.
        options = ({"xla_allow_excess_precision": False}
                   if getattr(trace, "round_every_op", False) else None)
        jit_kw = dict(**({"donate_argnums": donate} if donate else {}),
                      **({"compiler_options": options} if options else {}))
        jfn = jax.jit(scoped_fn, **jit_kw)

        fusion_sym = Symbol(name, None, id=f"xla.{name}", is_prim=True, executor=self, module="xla")

        first_call = [True]

        def impl(*args):
            # compile_service/parallel_compile.py installs an AOT-compiled
            # (or store-deserialized) executable here: dispatch uses it
            # directly — no lazy jit compile — and a mismatch it cannot
            # serve (tracer args under an ambient trace and aval drift raise
            # TypeError/ValueError from the Compiled call layer, ABI drift
            # JaxRuntimeError) falls back to the jfn path permanently;
            # prewarming must never change semantics, only when the compile
            # happened. Anything else is a bug and propagates.
            pw = impl._prewarmed
            if pw is not None:
                try:
                    # annotate like the steady-state jfn path: a STORE-served
                    # executable carries the PUBLISHING process's HLO module
                    # name, so this runtime annotation (and the named_scope
                    # inside the program) is what keeps device-time
                    # attribution joined to this process's region registry
                    if _obs._BUS.enabled:
                        with _obs_runtime.annotate_call(name):
                            return pw(*args)
                    return pw(*args)
                except (TypeError, ValueError, jax.errors.JaxRuntimeError) as e:
                    # the fallback is semantics-preserving but NOT free (a
                    # lazy recompile follows) — warn and record it so a fleet
                    # whose prewarmed regions disengage is distinguishable
                    # from one that never prewarmed
                    impl._prewarmed = None
                    warnings.warn(
                        f"prewarmed executable for {name} could not serve this "
                        f"call ({type(e).__name__}: {e}); recompiling lazily",
                        stacklevel=2)
                    if _obs._BUS.enabled:
                        _obs.inc("compile.prewarm_fallback")
                        _obs.event("prewarm_fallback", fusion=name,
                                   error=type(e).__name__)
            if first_call[0]:
                # jax.jit compiles lazily: the first dispatch pays jax
                # trace + StableHLO lowering + XLA backend compile
                first_call[0] = False
                with _obs.span("xla_compile", fusion=name, n_ops=len(region)):
                    return jfn(*args)
            if _obs._BUS.enabled:
                with _obs_runtime.annotate_call(name):
                    return jfn(*args)
            return jfn(*args)

        impl.__name__ = name
        impl.jitted = jfn
        impl.donate_argnums = donate  # part of the region's store key
        impl.compiler_options = options  # and so are these
        impl.subtrace = subtrace
        impl._prewarmed = None
        bsym = BoundSymbol(fusion_sym, tuple(inputs), {}, tuple(outputs), subsymbols=tuple(region), impl=impl)
        return bsym


def _worth_fusing_alone(bsym: BoundSymbol) -> bool:
    # singleton regions still get jitted when they are matmul-class (MXU) ops;
    # trivial singletons stay op-by-op to avoid pointless dispatch
    return OpTags.MATMUL_OP in bsym.sym.tags


ex = XLAFusionExecutor()
register_executor(ex)
