"""Execution passes: executor claiming and fusion.

Re-design of reference thunder/executors/passes.py:32-288. Priority-order
claiming: executor execution-transform → executor impl at the bsym's level →
descend into subsymbols → error on unclaimed prims. Then each FusionExecutor's
fusion_pass groups claimed ops into XLA-compiled regions."""
from __future__ import annotations

import time
from typing import Sequence

from ..analysis import manager as _an
from ..core.prims import PrimIDs
from ..core.symbol import BoundSymbol, OpTags
from ..core.trace import TraceCtx, from_trace, rebinding, tracectx
from ..extend import Executor, FusionExecutor, get_always_executors
from ..observability import events as _obs
from ..observability import metrics as _obs_metrics

_STRUCTURAL = (PrimIDs.RETURN, PrimIDs.DEL, PrimIDs.COMMENT, PrimIDs.UNPACK_TRIVIAL)


def transform_for_execution(trace: TraceCtx, executors: Sequence[Executor],
                            *, check_traces: bool = False) -> TraceCtx:
    start = time.perf_counter()
    executors = list(executors)
    for al in get_always_executors():
        if al not in executors:
            executors.append(al)

    out_bsyms: list[BoundSymbol] = []

    def lower(bsym: BoundSymbol):
        if bsym.sym.id in _STRUCTURAL:
            out_bsyms.append(bsym)
            return
        if bsym.sym.python_impl is not None and bsym.impl is None and bsym.sym.executor is None:
            # pure-python symbols (prologue checks) execute directly
            out_bsyms.append(bsym.with_impl(bsym.sym.python_impl))
            return
        if bsym.sym.executor is not None:
            # already executor-bound (e.g. registered operator symbols)
            impl = bsym.sym.executor.get_impl(bsym.sym.id)
            if impl is not None:
                out_bsyms.append(bsym.with_impl(impl))
                return
        for ex in executors:
            if ex.is_fusion_executor():
                continue
            if ex.can_execute(bsym):
                info = ex.implmap.get(bsym.sym.id)
                if info is not None and info.execution_transform is not None:
                    # re-trace the replacement into prims/ops of the executor,
                    # under the named_scope path of the symbol it replaces
                    new_trc = TraceCtx(None)
                    with tracectx(new_trc), rebinding(bsym):
                        info.execution_transform(*bsym.args, **bsym.kwargs)
                    for sub in new_trc.bound_symbols:
                        lower(sub)
                    return
                impl = ex.get_impl(bsym.sym.id)
                if impl is not None:
                    out_bsyms.append(bsym.with_impl(impl))
                    return
        if bsym.subsymbols:
            for sub in bsym.subsymbols:
                lower(sub)
            return
        if not bsym.sym.is_prim:
            # composite that recorded nothing: a pure pass-through (e.g. a
            # full-range getitem) — outputs are existing proxies, nothing to run
            out_names = {o.name for o in bsym.flat_proxy_outs()}
            in_names = {a.name for a in bsym.flat_proxy_args()}
            if out_names <= in_names:
                return
        raise RuntimeError(
            f"no executor can run {bsym.sym.name} (id={bsym.sym.id}); "
            f"tried {[e.name for e in executors]}"
        )

    with _obs.span("claim", bsyms=len(trace.bound_symbols)) as sp:
        for bsym in trace.bound_symbols:
            lower(bsym)
        sp.set(claimed=len(out_bsyms))

    claimed = from_trace(trace)
    claimed.bound_symbols = out_bsyms
    claimed.set_provenance(
        f"Transform for execution (took {(time.perf_counter()-start)*1000:.2f} ms)"
    )
    # pass-interposed verification (TT_CHECK_TRACES=1 / debug_options): the
    # claim pass and every fusion pass verify their output, so a violation
    # is attributed to the exact pass that introduced it
    where = trace.name_of_fn()
    _an.checkpoint("executor:claim", claimed, before=trace, where=where,
                   force=check_traces)

    for ex in executors:
        if isinstance(ex, FusionExecutor) or ex.is_fusion_executor():
            with _obs.span(f"fusion:{ex.name}") as sp:
                pre_fusion = claimed
                claimed = ex.fusion_pass(claimed)
                regions = [b for b in claimed.bound_symbols if b.sym.executor is ex]
                sp.set(regions=len(regions))
            _obs_metrics.record_fusion(ex.name, len(regions),
                                       sum(len(b.subsymbols) for b in regions))
            _an.checkpoint(f"executor:fusion:{ex.name}", claimed,
                           before=pre_fusion, where=where, force=check_traces)

    # region-name <-> symbol registry: every fusion region formed above is
    # registered (name -> member bsym ids + flops/bytes cost) so device
    # profiles (observability/profiler.py) can join measured device time
    # back to the trace symbols the region was built from
    from ..observability import profiler as _obs_profiler

    _obs_profiler.register_trace_regions(claimed)
    # region handoff to the compile service: with the service enabled
    # (TT_PARALLEL_COMPILE=1 or an artifact store configured), independent
    # regions lower + XLA-compile concurrently NOW — on a worker pool, from
    # the store when warm — instead of serially at first dispatch
    # (compile_service/parallel_compile.py; a no-op by default on CPU)
    from ..compile_service import parallel_compile as _pc

    _pc.maybe_prewarm(claimed, where=where)
    # eager frees for op-by-op execution (reference passes.py:261); fused
    # regions don't need it but the DELs between them are harmless
    from ..core.transform_common import del_last_used

    final = del_last_used(claimed)
    _an.checkpoint("executor:del_last_used", final, before=claimed, where=where,
                   force=check_traces)
    return final
