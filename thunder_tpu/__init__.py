"""thunder_tpu: a TPU-native deep-learning trace compiler.

A ground-up re-design of the capabilities of lightning-thunder
(reference: rdspring1/lightning-thunder, thunder/__init__.py:315 `thunder.jit`)
for TPU: programs are acquired by direct proxy tracing into a printable
trace IR, rewritten by trace-to-trace transforms (autodiff, DDP/FSDP/TP/CP
distribution, autocast, quantization), claimed by a prioritized executor list
(Pallas kernels, XLA fusion, op-by-op jax), and compiled into python callables
whose hot path is a single XLA executable per trace.

Public API mirrors the reference where it makes sense:
  jit, compile, grad, value_and_grad, last_traces, last_backward_traces,
  list_executors, ...
"""
from __future__ import annotations

import functools
import time
from numbers import Number
from typing import Any, Callable, Optional, Sequence

import jax

from .core import dtypes, devices, prims
from .core.dtypes import *  # noqa: F401,F403 — re-export dtype names
from .core.proxies import NumberProxy, Proxy, TensorProxy, proxy_from_jax
from .core.pytree import tree_flatten, tree_unflatten
from .core.trace import TraceCtx, tracectx
from .core.transform_common import Transform, cse, dce
from .common import CacheEntry, CompileData, CompileStats, EpilogueMixin
from .extend import (
    Executor,
    FusionExecutor,
    OperatorExecutor,
    get_all_executors,
    get_always_executors,
    get_default_executors,
    get_executor,
    register_executor,
    resolve_executors,
    set_default_executors,
)

# importing executors registers them
from .executors import jaxex  # noqa: E402
from .executors import xlaex  # noqa: E402
from .ops import ltorch  # noqa: E402  (registers tensor methods)
from .ops import clang  # noqa: E402
from .ops import auto_register  # noqa: E402  (registers fallback op catalog)

from .executors import pallasex  # noqa: E402

set_default_executors([pallasex.ex, xlaex.ex])

# persistent XLA compile cache: warm processes skip the multi-second
# whole-step compile. Enabled lazily at the first jit() call so the backend
# check sees post-import jax.config.update("jax_platforms") changes
# (utils/compile_cache.py; TT_NO_COMPILE_CACHE=1 disables)
from .utils.compile_cache import enable_persistent_cache  # noqa: E402

# structured spans/counters over the whole pipeline (stdlib-only; enabled by
# TT_OBS=1 / TT_OBS_FILE=... or observability.enable())
from . import observability  # noqa: E402

__version__ = "0.1.0"

_obs_key_digest = observability.key_digest


# ---------------------------------------------------------------------------
# trace acquisition (direct proxy tracing — reference thunder/common.py:535
# shows the minimal tracer; the bytecode-interpreter frontend is a later layer)
# ---------------------------------------------------------------------------


def _is_tensor_like(x) -> bool:
    from .core.baseutils import is_tensor_like as _itl
    return _itl(x) and not isinstance(x, Proxy)


def _unwrap(x):
    """Parameter -> raw jax array (keeps generated code jax-native)."""
    data = getattr(x, "data", None)
    return data if data is not None and hasattr(x, "requires_grad") else x


def _acquire_with(fn: Callable, args, kwargs, grad_mask, call) -> tuple[TraceCtx, Any, list, list]:
    """Shared acquisition core: proxify tensor leaves, run `call(pargs,
    pkwargs)` under the trace context, pack side effects. The direct and
    interpreted frontends differ only in the call strategy."""
    leaves, treedef = tree_flatten((args, kwargs))
    trc = TraceCtx(fn)
    proxy_leaves = []
    tensor_mask = []
    with tracectx(trc):
        for i, leaf in enumerate(leaves):
            if _is_tensor_like(leaf):
                rg = bool(getattr(leaf, "requires_grad", False)) or bool(grad_mask[i] if grad_mask else False)
                p = proxy_from_jax(leaf, requires_grad=rg)
                proxy_leaves.append(p)
                tensor_mask.append(True)
            else:
                proxy_leaves.append(leaf)
                tensor_mask.append(False)
        trc.args = tuple(p for p, m in zip(proxy_leaves, tensor_mask) if m)
        pargs, pkwargs = tree_unflatten(treedef, proxy_leaves)
        result = call(pargs, pkwargs)
        if trc.side_effects:
            # recorded mutations ride as extra outputs; the epilogue replays
            # them onto their owners after execution (reference epilogue
            # trace, thunder/core/jit_ext.py:2149)
            prims.python_return((result, tuple(p for _, _, p in trc.side_effects)))
        else:
            prims.python_return(result)
    return trc, treedef, tensor_mask, leaves


def acquire_trace(fn: Callable, args, kwargs, grad_mask: Sequence[bool] | None = None) -> tuple[TraceCtx, Any, list, list]:
    """Trace fn by calling it with proxies. Returns (trace, treedef, tensor_mask, leaves)."""
    return _acquire_with(fn, args, kwargs, grad_mask,
                         lambda pargs, pkwargs: fn(*pargs, **pkwargs))


def acquire_trace_interpreted(fn: Callable, args, kwargs,
                              grad_mask: Sequence[bool] | None = None,
                              sharp_edges: str = "allow"):
    """acquire_trace through the bytecode-interpreter frontend: same proxy
    passing and return convention, but fn's python executes opcode-by-opcode
    (lookasides, sharp-edge checks). This is how ThunderModule runs under
    interpretation="python interpreter" — every tensor still arrives as an
    explicit arg (the params dict), so the direct-path prologue machinery
    applies unchanged and distributed/quantization transforms compose."""
    import warnings

    from .frontend.interpreter import Interpreter, InterpreterError, Provenance, unwrap, wrap

    def on_sharp_edge(msg: str) -> None:
        if sharp_edges == "error":
            raise InterpreterError(f"sharp edge: {msg}")
        if sharp_edges == "warn":
            warnings.warn(f"thunder_tpu jit sharp edge: {msg}")

    def call(pargs, pkwargs):
        interp = Interpreter(on_sharp_edge=on_sharp_edge)
        return unwrap(interp.call(
            wrap(fn),
            [wrap(a, Provenance("arg", i)) for i, a in enumerate(pargs)],
            {k: wrap(v, Provenance("arg", k)) for k, v in pkwargs.items()},
        ))

    return _acquire_with(fn, args, kwargs, grad_mask, call)


def donated_arg_names(trc: TraceCtx, args, kwargs, tensor_mask, donated_argnums) -> set:
    """Names of the trace-arg proxies behind the positional args whose
    buffers the caller gives up (``donated_argnums``): what goes on the
    acquired trace as ``trace.donated`` (core/trace.py carries it through
    every pass, analysis/alias.py checks it for a read after the consuming
    write, executors/xlaex.py donates those region inputs)."""
    dmask: list = []
    for i, a in enumerate(args):
        dmask.extend([i in donated_argnums] * len(tree_flatten(a)[0]))
    dmask.extend([False] * len(tree_flatten(kwargs)[0]))
    tensor_dmask = [d for d, t in zip(dmask, tensor_mask) if t]
    return {p.name for p, d in zip(trc.args, tensor_dmask) if d}


def _argnums(donated_argnums) -> tuple:
    if isinstance(donated_argnums, int):
        return (donated_argnums,)
    return tuple(donated_argnums) if donated_argnums else ()


def build_prologue(trc: TraceCtx, tensor_mask, leaves) -> TraceCtx:
    """Prologue trace validating inputs (reference thunder/__init__.py:711-743:
    a cache hit is a prologue that runs without raising)."""
    pro = TraceCtx(None, prologue=True)
    pro._name = "prologue"
    with tracectx(pro):
        arg_proxies = []
        ti = 0
        for leaf, is_t in zip(leaves, tensor_mask):
            if is_t:
                p = trc.args[ti]
                q = TensorProxy(p.name, shape=p.shape, dtype=p.dtype, device=p.device)
                arg_proxies.append(q)
                prims.check_tensor_shape_and_metadata(q, p.shape, p.dtype, str(p.device))
                ti += 1
        pro.args = tuple(arg_proxies)
        prims.python_return(tuple(arg_proxies))
    return pro


def _tensor_storage_token(leaf):
    """A token identifying the underlying buffer of a tensor-like arg, for
    runtime alias-group detection (reference thunder/__init__.py:408-437
    computes alias groups of call-time args per call). None = unknown
    storage (treated as unaliased)."""
    dp = getattr(leaf, "data_ptr", None)  # torch tensors
    if callable(dp):
        try:
            return ("torch", dp())
        except Exception:
            return None
    base = getattr(leaf, "base", None)  # numpy views carry .base
    iface = getattr(base if base is not None else leaf, "__array_interface__", None)
    if isinstance(iface, dict) and "data" in iface:
        return ("np", iface["data"][0])
    return None


def _alias_groups(leaves, tensor_mask) -> tuple:
    """Group signature of tensor leaves sharing a buffer: () when all args
    are distinct (the common case, adds nothing to the key); otherwise a
    tuple of index-groups, so a call with different aliasing structure gets
    its own specialization instead of reusing a stale one."""
    by_store: dict = {}
    ti = 0
    for leaf, is_t in zip(leaves, tensor_mask):
        if not is_t:
            continue
        tok = _tensor_storage_token(leaf)
        if tok is None:
            tok = ("id", id(leaf))
        by_store.setdefault(tok, []).append(ti)
        ti += 1
    groups = tuple(tuple(g) for g in by_store.values() if len(g) > 1)
    return groups


def _cache_key(leaves, tensor_mask) -> tuple:
    key = []
    for leaf, is_t in zip(leaves, tensor_mask):
        if is_t:
            key.append(("T", tuple(leaf.shape), str(leaf.dtype)))
        else:
            try:
                hash(leaf)
                key.append(("S", leaf))
            except TypeError:
                key.append(("S", repr(leaf)))
    groups = _alias_groups(leaves, tensor_mask)
    if groups:
        key.append(("aliases", groups))
    return tuple(key)


class ThunderCompiledFunction(EpilogueMixin):
    """The callable returned by jit() (reference thunder/__init__.py:881 fn_)."""

    def __init__(self, cd: CompileData, donated_argnums=()):
        self._cd = cd
        self._donated_argnums = _argnums(donated_argnums)
        self._cs = CompileStats()
        self._cache: dict = {}
        self._transforms: list[Transform] = list(cd.transforms)
        fn = cd.fn
        self.__name__ = getattr(fn, "__name__", type(fn).__name__)
        # per-function trace checking (DebugOptions.check_traces) — the env
        # switch TT_CHECK_TRACES covers every function at once
        dbg = cd.compile_options.get("debug_options")
        self._check_traces = bool(dbg is not None and getattr(dbg, "check_traces", False))

    # -- compilation pipeline (reference thunder/__init__.py:439-635) --
    def _compile(self, args, kwargs, key) -> CacheEntry:
        cd, cs = self._cd, self._cs
        key_digest = _obs_key_digest(key)
        phases: list = []
        root = observability.span("compile", fn=self.__name__, cache_key=key_digest,
                                  frontend="interpreter" if cd.compile_options.get(
                                      "_acquire_interpretation") else "direct")
        with root:
            t0 = time.perf_counter_ns()
            if cd.compile_options.get("_acquire_interpretation"):
                acquire = functools.partial(
                    acquire_trace_interpreted,
                    sharp_edges=cd.compile_options.get("_sharp_edges", "allow"))
            else:
                acquire = acquire_trace
            with observability.span("acquisition") as sp:
                trc, treedef, tensor_mask, leaves = acquire(cd.fn, args, kwargs)
                sp.set(bsyms=len(trc.bound_symbols))
            phases.append(sp)
            cs.last_trace_tracing_time_ns = time.perf_counter_ns() - t0
            if self._donated_argnums:
                trc.donated = donated_arg_names(trc, args, kwargs, tensor_mask,
                                                self._donated_argnums)
            if cd.compile_options.get("round_every_op"):
                trc.round_every_op = True

            # pass-interposed verification (thunder_tpu/analysis): under
            # TT_CHECK_TRACES=1 (or DebugOptions(check_traces=True)) every
            # pass's output trace is checked, blaming violations on the
            # pass that produced them
            from . import analysis as _an

            chk = self._check_traces
            _an.checkpoint("acquisition", trc, where=self.__name__, force=chk)

            t1 = time.perf_counter_ns()
            traces = [trc]
            pro = build_prologue(trc, tensor_mask, leaves)
            _an.checkpoint("build_prologue", pro, where=self.__name__, force=chk)

            for tf in self._transforms:
                with observability.span(f"transform:{type(tf).__name__}") as sp:
                    prev, prev_pro = trc, pro
                    pro, trc = tf.transform_traces_pre_autodiff(pro, trc, compile_data=cd)
                    sp.set(bsyms=len(trc.bound_symbols))
                phases.append(sp)
                traces.append(trc)
                _an.checkpoint(f"transform:{type(tf).__name__}", trc, before=prev,
                               where=self.__name__, force=chk)
                if pro is not prev_pro:
                    # transforms may rewrite the prologue too (e.g. pruning
                    # checks); a corrupted prologue must blame its pass, not
                    # surface as a baffling guard failure at dispatch
                    _an.checkpoint(f"transform:{type(tf).__name__}:prologue", pro,
                                   where=self.__name__, force=chk)

            with observability.span("transform:dce") as sp:
                prev = trc
                trc = dce(trc)
                sp.set(bsyms=len(trc.bound_symbols))
            phases.append(sp)
            traces.append(trc)
            _an.checkpoint("transform:dce", trc, before=prev, where=self.__name__,
                           force=chk)

            from .executors.passes import transform_for_execution

            executors = resolve_executors(cd.executors or None)
            if cd.disable_fusion:
                executors = [e for e in executors if not e.is_fusion_executor()]
            with observability.span("executor_dispatch",
                                    executors=[e.name for e in executors]) as sp:
                ex_trc = transform_for_execution(trc, executors, check_traces=chk)
                sp.set(bsyms=len(ex_trc.bound_symbols),
                       fusions=sum(1 for b in ex_trc.bound_symbols
                                   if getattr(b.sym, "module", None) == "xla"))
            phases.append(sp)
            traces.append(ex_trc)

            for tf in self._transforms:
                with observability.span(f"transform_post:{type(tf).__name__}") as sp:
                    prev = ex_trc
                    ex_trc = tf.transform_trace_post_optimization(ex_trc, compile_data=cd)
                phases.append(sp)
                traces.append(ex_trc)
                _an.checkpoint(f"transform_post:{type(tf).__name__}", ex_trc,
                               before=prev, where=self.__name__, force=chk)

            cs.last_trace_transform_time_ns = time.perf_counter_ns() - t1

            t2 = time.perf_counter_ns()
            with observability.span("codegen") as sp:
                computation_fn = ex_trc.python_callable()
                prologue_fn = pro.python_callable()
            phases.append(sp)
            cs.last_compile_time_ns = time.perf_counter_ns() - t2

        cs.last_compile_report = {
            "fn": self.__name__,
            "cache_key": key_digest,
            "total_ms": round(root.dur_ms, 3),
            "phases": [{"name": p.name, "dur_ms": round(p.dur_ms, 3), **p.attrs}
                       for p in phases],
        }
        cs.last_traces = traces
        cs.last_prologue_traces = [pro]
        entry = CacheEntry(
            prologue_fn=prologue_fn,
            computation_fn=computation_fn,
            prologue_trc=pro,
            computation_trc=ex_trc,
            treedef=treedef,
            tensor_mask=tensor_mask,
            key=key,
            effect_keys=[(owner, name) for owner, name, _ in trc.side_effects],
        )
        self._cache[key] = entry
        return entry

    def __call__(self, *args, **kwargs):
        cs = self._cs
        cs.calls += 1
        leaves, _ = tree_flatten((args, kwargs))
        from .core.proxies import Proxy as _Proxy

        if any(isinstance(l, _Proxy) for l in leaves):
            # called under an ambient thunder trace (e.g. value_and_grad over
            # a wrapper that closes over this compiled fn): inline-trace the
            # original function into the ambient trace instead of executing a
            # cached concrete entry on proxies
            return self._cd.fn(*args, **kwargs)
        tensor_mask = [_is_tensor_like(l) for l in leaves]
        key = _cache_key(leaves, tensor_mask)
        extra = getattr(self._cd.fn, "__cache_extra__", None)
        if extra is not None:
            # e.g. module train/eval mode: changes the traced program without
            # changing any input metadata
            key = key + (extra(),)
        entry = self._cache.get(key)
        if entry is None:
            cs.cache_misses += 1
            if observability.enabled():
                from .observability import metrics as _m

                _m.record_cache("trace", "miss", fn=self.__name__)
                _m.record_recompile(
                    _m.REASON_SHAPE_CHANGE if self._cache else _m.REASON_CACHE_MISS,
                    fn=self.__name__, cache_key=_obs_key_digest(key))
            entry = self._compile(args, kwargs, key)
        else:
            cs.cache_hits += 1
            if observability.enabled():
                from .observability import metrics as _m

                _m.record_cache("trace", "hit", fn=self.__name__)
        tensor_leaves = [_unwrap(l) for l, m in zip(leaves, tensor_mask) if m]
        flat_inputs = entry.prologue_fn(*tensor_leaves)
        out = entry.computation_fn(*flat_inputs)
        if entry.effect_keys:
            out, effects = out
            self.apply_effects(entry.effect_keys, effects)
        return out



    def prewarm(self, *args, **kwargs) -> bool:
        """Compile the specialization for these args WITHOUT executing it —
        the compile service's pre-dispatch entry point. The executor pass
        hands fusion regions to compile_service/parallel_compile.py, so
        with the service enabled the regions XLA-compile concurrently (from
        the artifact store when warm) before any dispatch. Returns True
        when a new entry was compiled, False when one already matched."""
        leaves, _ = tree_flatten((args, kwargs))
        tensor_mask = [_is_tensor_like(l) for l in leaves]
        key = _cache_key(leaves, tensor_mask)
        extra = getattr(self._cd.fn, "__cache_extra__", None)
        if extra is not None:
            key = key + (extra(),)
        if key in self._cache:
            return False
        self._compile(args, kwargs, key)
        return True

    # -- introspection (reference thunder/__init__.py:944-1106) --
    @property
    def cache_hits(self):
        return int(self._cs.cache_hits)

    @property
    def cache_misses(self):
        return int(self._cs.cache_misses)


def jit(
    fn: Callable,
    *,
    executors: Sequence | None = None,
    cache: str = "constant values",
    transforms: Sequence[Transform] | None = None,
    disable_fusion: bool = False,
    interpretation: str | None = None,
    sharp_edges: str = "allow",
    donated_argnums=None,
    **compile_options,
):
    """Compile a callable or Module for TPU execution (reference thunder/__init__.py:315).

    interpretation="python interpreter" acquires the program with the bytecode
    interpreter frontend (provenance-tracked captures, generated prologues) —
    required for arbitrary callables that close over tensors/modules; the
    default direct proxy tracing is faster to compile for framework-native code.

    donated_argnums (an int or a sequence of ints) names the positional
    arguments whose buffers the caller gives up with every call, as
    ``jax.jit``'s ``donate_argnums`` does: the caller promises never to read
    an array it passed there again and to use what the function returns
    instead. Every array under such an argument may be consumed by the call
    (``is_deleted()`` afterwards), which lets XLA write an update in place
    where it would otherwise copy the whole buffer first. A donated array
    that the program still reads after the region that writes it, or that
    it returns as it came in, is simply not consumed; a trace that reads a
    donated buffer after the write that consumed it is refused by
    ``analysis.check_alias_safety``. It states the caller's calling
    convention and is no tuning knob; only the direct tracing front end of
    a plain callable takes it, every other one refuses it.
    """
    from .nn.module import Module, ThunderModule

    enable_persistent_cache()  # lazy: sees the backend the compile will use

    _is_torch_module = type(fn).__module__.partition(".")[0] == "torch" or any(
        c.__module__.startswith("torch.nn") for c in type(fn).__mro__[:-1]
    )
    if donated_argnums is not None and (
            interpretation is not None or cache in ("symbolic values", "same input")
            or isinstance(fn, Module) or _is_torch_module):
        raise ValueError(
            "donated_argnums is honoured only by the direct tracing front end "
            "of a plain callable (no interpretation=, no symbolic cache, no "
            "Module): this front end cannot donate, so it refuses the argument")
    if cache in ("symbolic values", "same input") and (isinstance(fn, Module) or _is_torch_module):
        raise ValueError(
            f"cache={cache!r} is only supported for plain callables "
            f"(modules always take tensor inputs; use 'constant values')")
    if interpretation is not None:
        if interpretation not in ("python interpreter", "interpreter"):
            raise ValueError(f"unknown interpretation mode {interpretation!r}")
        if isinstance(fn, Module):
            # modules keep the full ThunderModule surface (overrides,
            # distributed transforms, TrainStep); only the ACQUISITION runs
            # through the bytecode interpreter (acquire_trace_interpreted)
            return ThunderModule(fn, executors=executors, cache=cache, transforms=transforms,
                                 disable_fusion=disable_fusion,
                                 _acquire_interpretation=interpretation,
                                 _sharp_edges=sharp_edges, **compile_options)
        from .frontend.compiled import InterpretedFunction

        return InterpretedFunction(fn, executors=executors, sharp_edges=sharp_edges,
                                   transforms=transforms or (), cache=cache,
                                   disable_fusion=disable_fusion, **compile_options)
    if sharp_edges != "allow":
        raise ValueError(
            "sharp_edges checking requires the bytecode-interpreter frontend: "
            "pass interpretation='python interpreter'")
    if cache in ("symbolic values", "same input"):
        # these cache modes live on the prologue machinery of the
        # interpreter frontend (reference thunder/core/options.py:45-49)
        from .frontend.compiled import InterpretedFunction

        return InterpretedFunction(fn, executors=executors,
                                   transforms=transforms or (), cache=cache,
                                   disable_fusion=disable_fusion, **compile_options)
    if isinstance(fn, Module):
        return ThunderModule(fn, executors=executors, cache=cache, transforms=transforms,
                             disable_fusion=disable_fusion, **compile_options)
    # torch.nn.Module -> __torch_function__ tracing frontend (lazy torch import)
    if _is_torch_module:
        from .interop.torch_frontend import compile_torch_module

        return compile_torch_module(fn, executors=executors, cache=cache, transforms=transforms,
                                    disable_fusion=disable_fusion, **compile_options)
    cd = CompileData(
        fn=fn,
        executors=resolve_executors(executors),
        cache_option=cache,
        transforms=transforms or (),
        disable_fusion=disable_fusion,
        compile_options=compile_options,
    )
    return ThunderCompiledFunction(cd, donated_argnums=donated_argnums)


def compile(fn: Callable, *, recipe=None, plugins=None, **kwargs):
    """Recipe-based entry point (reference thunder/__init__.py:274)."""
    from .recipes import resolve_recipe

    r = resolve_recipe(recipe, fn)
    return r.apply(fn, plugins=plugins, **kwargs)


# ---------------------------------------------------------------------------
# introspection helpers
# ---------------------------------------------------------------------------


def _get_cs(cfn) -> CompileStats:
    if isinstance(cfn, ThunderCompiledFunction):
        return cfn._cs
    cs = getattr(cfn, "_cs", None)
    if cs is None:
        raise ValueError(f"{cfn} is not a thunder_tpu-compiled function")
    return cs


def last_traces(cfn) -> list:
    return _get_cs(cfn).last_traces


def last_backward_traces(cfn) -> list:
    return _get_cs(cfn).last_backward_traces


def last_prologue_traces(cfn) -> list:
    return _get_cs(cfn).last_prologue_traces


def last_interpreter_log(cfn) -> list:
    """Instruction log of the last acquisition (bytecode-interpreter frontend
    with record_interpreter_log=True; reference thunder/__init__.py:1032)."""
    log = getattr(_get_cs(cfn), "last_interpreter_log", None)
    if log is None:
        raise ValueError("no interpreter log recorded — compile with "
                         "interpretation='python interpreter' and record_interpreter_log=True")
    return log


def print_last_interpreter_log(cfn, limit: int = 200) -> None:
    """Render the last acquisition's interpreted-instruction trace
    (reference print_last_interpreter_log, thunder/__init__.py:1032-1062)."""
    log = last_interpreter_log(cfn)
    shown = log[:limit]
    print("\n".join(shown))
    if len(log) > limit:
        print(f"... ({len(log) - limit} more instructions)")


def cache_hits(cfn) -> int:
    return int(_get_cs(cfn).cache_hits)


def cache_misses(cfn) -> int:
    return int(_get_cs(cfn).cache_misses)


def compile_stats(cfn) -> CompileStats:
    return _get_cs(cfn)


def list_executors() -> tuple:
    return get_all_executors()


# autodiff entry points (populated by transforms.autodiff at import)
def grad(cfn, argnums=0):
    from .transforms.autodiff import grad as _grad

    return _grad(cfn, argnums=argnums)


def value_and_grad(cfn, argnums=0, *, interpretation=None):
    from .transforms.autodiff import value_and_grad as _vag

    return _vag(cfn, argnums=argnums, interpretation=interpretation)


def examine(fn, *args, **kwargs):
    from .utils.examine import examine as _examine

    return _examine(fn, *args, **kwargs)


def custom_op(qualname, *, like=None, meta=None, tags=()):
    # The impl lives in `_custom_op` (underscored so importing it can never
    # bind a submodule named `custom_op` over this function on the package).
    from ._custom_op import custom_op as _custom_op

    return _custom_op(qualname, like=like, meta=meta, tags=tags)


def __getattr__(name):
    # lazy submodule access: tt.nn, tt.optim, tt.models, tt.parallel, ...
    import importlib

    if name in ("nn", "optim", "models", "parallel", "training", "inference",
                "transforms", "utils", "recipes", "plugins", "frontend",
                "robustness", "data", "compile_service", "serving"):
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module 'thunder_tpu' has no attribute '{name}'")
