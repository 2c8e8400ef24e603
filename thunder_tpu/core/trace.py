"""TraceCtx: the function-shaped program representation.

Re-design of reference thunder/core/trace.py:46-661. A trace is a signature
plus an ordered list of BoundSymbols; it prints to real Python source and
compiles to a callable whose ops are bound executor implementations. On TPU
the compiled callable is typically a single ``jax.jit`` fusion call produced
by the XLA fusion executor — trace printing is retained for inspectability
(``last_traces`` parity)."""
from __future__ import annotations

import time
from contextlib import contextmanager
from contextvars import ContextVar
from typing import Any, Callable, Optional

from . import baseutils
from .codeutils import ContextInterner, prettyprint, flat_proxies
from .proxies import Proxy, variableify

_tracectx = ContextVar("tracectx", default=None)


def get_tracectx() -> Optional["TraceCtx"]:
    return _tracectx.get()


@contextmanager
def tracectx(trace: "TraceCtx | None"):
    tok = _tracectx.set(trace)
    try:
        yield trace
    finally:
        _tracectx.reset(tok)


SCOPE_TAG = "scope:"


@contextmanager
def named_scope(name: str):
    """Every symbol bound inside runs under ``jax.named_scope`` of the open
    scopes' path (``attn/rope``) where it is traced into a compiled program,
    so that a device profile can put an op down to the part of the model it
    belongs to (observability/profiler.py: ``op_scopes``). The path rides on
    the bound symbol as the tag ``scope:<path>`` (symbol.BoundSymbol.exec_lines
    reads it), which every pass keeps. Outside a trace it does nothing."""
    trc = get_tracectx()
    if trc is None:
        yield
        return
    trc.labels.append(name)
    try:
        yield
    finally:
        trc.labels.pop()


def scope_of(bsym) -> Optional[str]:
    """The ``named_scope`` path a bound symbol carries (``bwd/attn/rope``), or None."""
    for t in bsym.tags:
        if isinstance(t, str) and t.startswith(SCOPE_TAG):
            return t[len(SCOPE_TAG):]
    return None


@contextmanager
def rebinding(bsym, pass_name: str = ""):
    """For a transform that binds new symbols in place of ``bsym``: what is bound
    inside carries ``bsym``'s scope path, below ``pass_name`` (``bwd``,
    ``recompute``) where one is given. A ``bsym`` without a scope leaves the
    open scopes as they are, so the members of a composite that is being taken
    apart inherit; with a ``pass_name`` they become the pass alone."""
    trc = get_tracectx()
    path = scope_of(bsym)
    if trc is None or not (path or pass_name):
        yield
        return
    saved, trc.labels = trc.labels, [p for p in (pass_name, path) if p]
    try:
        yield
    finally:
        trc.labels = saved


class TraceProvenance:
    """Reference thunder/core/trace.py:25 — 'Constructed by <pass> (took N ms)'."""

    def __init__(self, pss: str):
        self.pss = pss

    def __repr__(self) -> str:
        return f"# Constructed by {self.pss}"


class TraceCtx(baseutils.TraceInterface):
    def __init__(self, fn: Callable | None = None, *, prologue: bool = False):
        self.fn = fn
        self.bound_symbols: list = []
        self.scopes: list[list] = [self.bound_symbols]
        self.args: tuple = ()
        self.kwargs: dict = {}
        self._name = None
        self.names: set[str] = set()
        self._counters: dict[str, int] = {}
        self._provenance: TraceProvenance | None = None
        self._any_call_ctx: dict = {}
        self.is_prologue = prologue
        self.tags: set = set()
        # (owner, attr_name, proxy) mutations recorded during tracing, replayed
        # by the epilogue after computation (reference epilogue trace,
        # thunder/core/jit_ext.py:2149)
        self.side_effects: list = []
        # names of the open `named_scope`s, innermost last: a symbol bound
        # under them carries their path as a tag and is traced under jax.named_scope
        self.labels: list[str] = []

    # ---- naming ----
    def make_name(self, prefix: str = "t") -> str:
        while True:
            c = self._counters.get(prefix, -1) + 1
            self._counters[prefix] = c
            name = f"{prefix}{c}"
            if name not in self.names:
                self.names.add(name)
                return name

    def add_name(self, name: str) -> None:
        self.names.add(name)

    def has_name(self, name: str) -> bool:
        return name in self.names

    # ---- recording ----
    def add_bound_symbol(self, bsym) -> None:
        self.scopes[-1].append(bsym)

    @contextmanager
    def push_scope(self):
        scope: list = []
        self.scopes.append(scope)
        try:
            yield scope
        finally:
            popped = self.scopes.pop()
            assert popped is scope

    def set_provenance(self, p: "TraceProvenance | str"):
        self._provenance = p if isinstance(p, TraceProvenance) else TraceProvenance(p)

    # ---- structure ----
    @property
    def output(self):
        """args of the RETURN bsym, if present."""
        from .prims import PrimIDs

        for bsym in reversed(self.bound_symbols):
            if bsym.sym.id == PrimIDs.RETURN:
                return bsym.args[0] if len(bsym.args) == 1 else bsym.args
        return None

    def name_of_fn(self) -> str:
        if self._name:
            return self._name
        base = getattr(self.fn, "__name__", None) or "computation"
        if not base.isidentifier():  # e.g. "<lambda>"
            base = "computation"
        return "prologue" if self.is_prologue else base

    # ---- printing ----
    def python(self, include_decorators: bool = True) -> str:
        interner = ContextInterner()
        lines, _ = self._build_lines(interner)
        sig = ", ".join(p.name for p in self.args)
        header = []
        if self._provenance is not None:
            header.append(repr(self._provenance))
        header.append(f"def {self.name_of_fn()}({sig}):")
        body = [f"  {ln}" for ln in lines] or ["  pass"]
        return "\n".join(header + body)

    def _build_lines(self, interner: ContextInterner):
        lines: list[str] = []
        for i, bsym in enumerate(self.bound_symbols):
            lines.extend(bsym.python_lines(i, interner))
        return lines, interner

    def __repr__(self) -> str:
        return self.python()

    # ---- compiling to a callable ----
    def python_callable(self, *, scoped: Optional[bool] = None, **ctx_overrides) -> Callable:
        """exec() the printed source with op implementations bound in the namespace.

        ``scoped`` says whether the callable is traced into a compiled program: only
        then does a symbol enter the ``jax.named_scope`` of its ``named_scope`` path,
        which is a trace-time name and must cost a program that runs op by op on the
        host nothing. A fusion region's callable is (executors/xlaex.py); any other is
        where it is built under an outer ``jax.jit`` (TrainStep's whole step)."""
        if scoped is None:
            import jax

            scoped = not jax.core.trace_ctx.is_top_level()
        interner = ContextInterner()
        lines: list[str] = []
        for i, bsym in enumerate(self.bound_symbols):
            lines.extend(bsym.exec_lines(i, interner, scoped=scoped))
        sig = ", ".join(p.name for p in self.args)
        fname = self.name_of_fn()
        body = [f"  {ln}" for ln in lines] or ["  pass"]
        src = f"def {fname}({sig}):\n" + "\n".join(body)
        ctx = dict(interner.ctx)
        ctx.update(ctx_overrides)
        code = compile(src, f"<thunder_tpu.gen.{fname}>", "exec")
        exec(code, ctx)
        fn = ctx[fname]
        fn.__source__ = src
        fn.__trace__ = self
        return fn


def from_trace(trace: TraceCtx) -> TraceCtx:
    """Empty trace inheriting signature/names (reference thunder/core/trace.py from_trace)."""
    t = TraceCtx(trace.fn, prologue=trace.is_prologue)
    t.args = trace.args
    t.kwargs = trace.kwargs
    t.names = set(trace.names)
    t._counters = dict(trace._counters)
    t._name = trace._name
    t.tags = set(trace.tags)
    t.side_effects = list(trace.side_effects)
    # donated-buffer annotation (arg names whose buffers the runtime
    # donates) rides through every pass so the alias analysis
    # (analysis/alias.py) can check read-after-donation at each checkpoint
    donated = getattr(trace, "donated", None)
    if donated:
        t.donated = set(donated)
    # the caller's word that every op of the program rounds to its own type (tt.jit's
    # round_every_op): the XLA executor compiles the regions accordingly
    if getattr(trace, "round_every_op", False):
        t.round_every_op = True
    return t


@contextmanager
def detached_trace():
    with tracectx(TraceCtx()) as t:
        yield t
