"""Trace re-interpretation: run an existing trace symbol-by-symbol into a new
trace, substituting or expanding chosen bsyms.

Counterpart of reference thunder/core/trace_interpreter.py:246
(TraceSubstitutionProcessor) — the engine under executor dispatch, grad
transforms and tensor-parallel visitors."""
from __future__ import annotations

from typing import Any, Callable, Optional

from .prims import PrimIDs
from .proxies import Proxy
from .symbol import BoundSymbol
from .trace import TraceCtx, from_trace, rebinding, tracectx


class TraceSubstitutionProcessor:
    """Re-record a trace, letting a visitor replace individual bsyms.

    visitor(bsym, call_args, call_kwargs) returns either:
      - None: re-emit the bsym unchanged (its symbol is re-called), or
      - a result pytree: used as the bsym's new output (the visitor is
        expected to have recorded replacement symbols itself).
    """

    def __init__(self, trace: TraceCtx, visitor: Callable):
        self.trace = trace
        self.visitor = visitor
        self.env: dict[str, Any] = {}

    def lookup(self, x):
        if isinstance(x, Proxy):
            return self.env.get(x.name, x)
        if isinstance(x, (tuple, list)):
            return type(x)(self.lookup(e) for e in x)
        if isinstance(x, dict):
            return {k: self.lookup(v) for k, v in x.items()}
        return x

    def map_out(self, old, new):
        if isinstance(old, Proxy):
            self.env[old.name] = new
        elif isinstance(old, (tuple, list)) and isinstance(new, (tuple, list)):
            for o, n in zip(old, new):
                self.map_out(o, n)
        elif isinstance(old, dict) and isinstance(new, dict):
            for k in old:
                self.map_out(old[k], new.get(k))

    def __call__(self) -> TraceCtx:
        from . import prims

        new_trace = TraceCtx(self.trace.fn)
        new_trace.args = self.trace.args
        new_trace._name = self.trace._name
        for p in self.trace.args:
            new_trace.add_name(p.name)
        with tracectx(new_trace):
            for bsym in self.trace.bound_symbols:
                if bsym.sym.id == PrimIDs.RETURN:
                    prims.python_return(self.lookup(bsym.args[0] if len(bsym.args) == 1 else bsym.args))
                    continue
                if bsym.sym.id in (PrimIDs.DEL, PrimIDs.COMMENT, PrimIDs.UNPACK_TRIVIAL):
                    continue
                margs = self.lookup(bsym.args)
                mkwargs = self.lookup(bsym.kwargs)
                scope_start = len(new_trace.bound_symbols)
                # what the visitor binds in the symbol's place (autocast's casts), and the
                # members of a composite bound again, carry the symbol's named_scope path
                with rebinding(bsym):
                    replaced = self.visitor(bsym, margs, mkwargs)
                    if replaced is None:
                        out = bsym.sym(*margs, **mkwargs)
                    else:
                        out = replaced
                if bsym.tags:
                    # tags (e.g. RECOMPUTE_IN_BACKWARD) survive the rewrite —
                    # losing them silently disables activation checkpointing
                    for nb in new_trace.bound_symbols[scope_start:]:
                        nb.tags |= bsym.tags
                self.map_out(bsym.output, out)
        # side effects survive the rewrite, with proxies remapped through the
        # substitution env (else effect metadata silently vanishes while the
        # packed RETURN keeps referencing the values)
        new_trace.side_effects = [
            (owner, name, self.lookup(p)) for owner, name, p in getattr(self.trace, "side_effects", ())
        ]
        return new_trace


def substitute_symbols(trace: TraceCtx, mapping: dict, provenance: str = "Symbol substitution") -> TraceCtx:
    """Replace bsyms whose sym.id is in `mapping` with mapping[id](*args, **kwargs)."""

    def visitor(bsym, args, kwargs):
        fn = mapping.get(bsym.sym.id)
        if fn is None:
            return None
        return fn(*args, **kwargs)

    out = TraceSubstitutionProcessor(trace, visitor)()
    out.set_provenance(provenance)
    return out
