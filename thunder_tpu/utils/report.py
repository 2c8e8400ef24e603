"""Reproducer and timing reports — the analog of ThunderFX's report tooling
(reference thunder/dynamo/report.py: per-graph repro script generation,
timing comparisons vs eager/inductor; thunder/dynamo/compiler.py:331
thunder_profile).

On this stack a "graph" is a compiled cache entry; reproducers serialize the
final computation trace (which is executable Python over jax) together with
the input specs, and timing compares the fused program against op-by-op
dispatch of the same trace."""
from __future__ import annotations

import os
import time
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp


def _last_trace(cfn, *, executable: bool = False):
    from .. import last_traces

    traces = last_traces(cfn)
    if not traces:
        raise ValueError("no compiled entries yet — call the function first")
    if executable:
        # reproducers need symbol-level ops (executable eagerly); fusion
        # regions hold compiled closures the printed form cannot carry
        for trc in reversed(traces):
            if not any(getattr(b.sym, "module", None) == "xla" for b in trc.bound_symbols):
                return trc
        raise ValueError("every recorded trace contains fusion regions; "
                         "no symbol-level trace available for a reproducer")
    return traces[-1]


def _input_specs(trace) -> list[tuple]:
    from ..core.proxies import NumberProxy, TensorProxy

    specs = []
    for p in trace.args:
        if isinstance(p, TensorProxy):
            specs.append((p.name, tuple(p.shape), p.dtype.name))
        elif isinstance(p, NumberProxy):
            specs.append((p.name, None, p.python_type.__name__))
        else:
            raise ValueError(
                f"cannot build a reproducer: trace arg {p!r} is neither a "
                f"tensor nor a number proxy")
    return specs


def _printed_with_ctx(trace) -> tuple[str, dict]:
    from ..core.codeutils import ContextInterner

    interner = ContextInterner()
    lines, _ = trace._build_lines(interner)
    sig = ", ".join(p.name for p in trace.args)
    src = f"def {trace.name_of_fn()}({sig}):\n" + "\n".join(f"  {ln}" for ln in lines or ["pass"])
    return src, dict(interner.ctx)


def save_reproducer(cfn, path: str) -> str:
    """Write a standalone python script reproducing the compiled computation
    (reference report.py reproducer scripts). The printed trace executes
    eagerly through the default executor (core/trace_exec.py); interned
    dtype/device constants are reconstructed, array constants are saved in a
    sidecar .npz next to the script."""
    import numpy as np

    from ..core import devices as _devices, dtypes as _dtypes

    trace = _last_trace(cfn, executable=True)
    src, ctx = _printed_with_ctx(trace)
    specs = _input_specs(trace)
    name = trace.name_of_fn()

    const_lines = []
    arrays: dict[str, Any] = {}
    for k, v in ctx.items():
        if isinstance(v, _dtypes.dtype):
            const_lines.append(f"{k} = thunder_tpu.core.dtypes.to_dtype({v.name!r})")
        elif isinstance(v, _devices.Device):
            const_lines.append(f"{k} = thunder_tpu.core.devices.to_device({str(v)!r})")
        elif hasattr(v, "shape") and hasattr(v, "dtype"):
            arrays[k] = np.asarray(v)
            const_lines.append(f"{k} = jnp.asarray(_DATA[{k!r}])")
        elif isinstance(v, (int, float, bool, str, tuple, list, type(None))):
            const_lines.append(f"{k} = {v!r}")
        else:
            const_lines.append(f"{k} = None  # unserializable: {type(v).__name__}")

    npz_path = path + ".npz"
    if arrays:
        np.savez(npz_path, **arrays)

    lines = [
        '"""thunder_tpu reproducer — auto-generated (utils/report.py).',
        "",
        f"fn: {getattr(cfn, '__name__', str(cfn))}",
        f"trace: {name}",
        '"""',
        "import numpy as np",
        "import jax",
        "import jax.numpy as jnp",
        "",
        "import thunder_tpu",
        "import thunder_tpu.core.dtypes",
        "import thunder_tpu.core.devices",
        "from thunder_tpu.core.trace_exec import make_trace_namespace",
        "",
        "import os as _os",
        f"_DATA = (np.load(_os.path.join(_os.path.dirname(_os.path.abspath(__file__)), "
        f"{os.path.basename(npz_path)!r})) if {bool(arrays)} else None)",
        "",
        "SRC = " + repr(src),
        "",
        "INPUT_SPECS = " + repr(specs),
        "",
        "",
        "def make_inputs(seed=0):",
        "    rng = np.random.RandomState(seed)",
        "    out = []",
        "    for name, shape, dtype in INPUT_SPECS:",
        "        if shape is None:",
        "            out.append({'int': 1, 'bool': True}.get(dtype, 0.5))",
        "        elif dtype.startswith('int') or dtype.startswith('uint'):",
        "            out.append(jnp.asarray(rng.randint(0, 10, shape), 'int32'))",
        "        elif dtype == 'bool8':",
        "            out.append(jnp.asarray(rng.rand(*shape) > 0.5))",
        "        else:",
        "            out.append(jnp.asarray(rng.randn(*shape), dtype))",
        "    return out",
        "",
        "",
        "ns = make_trace_namespace()",
    ]
    lines += const_lines and ["# interned constants"] + const_lines or []
    lines += [
        "for _k in dir():",
        "    if _k.startswith('_dtype') or _k.startswith('_dev') or _k.startswith('_c') or _k.startswith('_obj'):",
        "        ns[_k] = globals()[_k]",
        "",
        "if __name__ == '__main__':",
        "    exec(compile(SRC, 'repro', 'exec'), ns)",
        f"    fn = ns[{name!r}]",
        "    outs = fn(*make_inputs())",
        "    print(jax.tree_util.tree_map(lambda t: getattr(t, 'shape', t), outs))",
    ]
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")

    # repro bundles carry the observability timeline when one is being
    # recorded: the compile-phase spans and cache/recompile events that led
    # to this trace are exactly the context a bug report needs
    from ..observability import events as _obs
    from ..observability import flight_recorder as _obs_flight

    if _obs.enabled() and _obs.records():
        _obs.dump(path + ".obs.jsonl")
    # the step-time flight recorder rides along too: "what did the last N
    # steps look like before this trace was saved" is post-mortem gold
    if _obs_flight.recorder().records():
        _obs_flight.recorder().dump(path + ".flight.json")
    # a recent trace-check failure (analysis/manager.py) is attached with the
    # failing trace: the blamed pass + minimized repro + full trace text is
    # exactly what a transform-bug report needs. Consumed on attach — a
    # failure rides into at most one bundle, never a later unrelated one.
    from ..analysis import manager as _an_manager

    failure = _an_manager.take_last_failure()
    if failure is not None:
        with open(path + ".trace_check.txt", "w") as f:
            f.write(failure.render() + "\n")
            if failure.trace is not None:
                f.write("\n# failing trace (full)\n")
                try:
                    f.write(failure.trace.python() + "\n")
                except Exception as e:
                    f.write(f"# <unprintable: {e}>\n")
    return path


def opbyop_callable(cfn):
    """Eager op-by-op executable of the compiled function's symbol-level trace
    (every op dispatches separately through the default executor — the
    'eager' baseline)."""
    from ..core.trace_exec import make_trace_namespace

    trace = _last_trace(cfn, executable=True)
    src, ctx = _printed_with_ctx(trace)
    ns = make_trace_namespace()
    ns.update(ctx)
    exec(compile(src, "<opbyop>", "exec"), ns)
    return ns[trace.name_of_fn()], trace


def _bind_trace_inputs(cfn, trace, args, kwargs) -> list:
    """Bind concrete values to the trace's positional args by spec matching.

    Candidates are the flattened call args/kwargs plus any prologue-captured
    parameters (modules capture them outside the call signature). Each trace
    arg is matched by name first, then by (shape, dtype) against the unused
    remainder — positional slicing silently mis-binds when captures or kwarg
    ordering shuffle the flat list."""
    from ..core.dtypes import to_jax_dtype
    from ..core.proxies import NumberProxy, TensorProxy

    def _unwrap(v):
        return getattr(v, "data", v) if type(v).__name__ == "Parameter" else v

    named: dict[str, Any] = {k: v for k, v in kwargs.items()
                             if hasattr(v, "shape") or isinstance(v, (int, float, bool))}
    getp = getattr(cfn, "get_parameters", None)
    if callable(getp):
        named.update({k: _unwrap(v) for k, v in getp().items()})
    # pool = call args + params; kwargs are reachable by name AND in the pool,
    # so a name match must consume the pool entry too (identity scan below)
    pool: list[Any] = [v for v in jax.tree_util.tree_leaves(args)
                       if hasattr(v, "shape") or isinstance(v, (int, float, bool))]
    pool += list(named.values())
    used = [False] * len(pool)
    import numpy as np

    pool_dtype = [np.dtype(v.dtype) if hasattr(v, "dtype") else None for v in pool]

    def _consume(val):
        for i, v in enumerate(pool):
            if not used[i] and v is val:
                used[i] = True
                break

    bound = []
    for p in trace.args:
        cand = named.get(p.name)
        if cand is not None:
            _consume(cand)
        if cand is None and isinstance(p, TensorProxy):
            want_shape, want_dt = tuple(p.shape), np.dtype(to_jax_dtype(p.dtype))
            for i, v in enumerate(pool):
                if used[i] or not hasattr(v, "shape"):
                    continue
                if tuple(v.shape) == want_shape and pool_dtype[i] == want_dt:
                    cand, used[i] = v, True
                    break
        elif cand is None and isinstance(p, NumberProxy):
            for i, v in enumerate(pool):
                # exact python-type match (bool is an int subclass: check first)
                if not used[i] and not hasattr(v, "shape") and type(v) is p.python_type:
                    cand, used[i] = v, True
                    break
        if cand is None:
            raise ValueError(f"could not bind trace arg {p.name!r} "
                             f"({getattr(p, 'shape', None)}) to any call input")
        bound.append(cand)
    return bound


def timing_report(cfn, *args, iters: int = 10, warmup: int = 2,
                  compare_opbyop: bool = True, **kwargs) -> dict:
    """Compare the compiled function against op-by-op execution of the same
    trace (reference report.py timing tables vs eager)."""
    out = cfn(*args, **kwargs)  # ensure compiled
    for _ in range(warmup):
        out = cfn(*args, **kwargs)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = cfn(*args, **kwargs)
    jax.block_until_ready(out)
    fused_s = (time.perf_counter() - t0) / iters

    report = {
        "fused_ms": fused_s * 1e3,
        "iters": iters,
    }

    if compare_opbyop:
        try:
            eager_fn, trace = opbyop_callable(cfn)
            bound = _bind_trace_inputs(cfn, trace, args, kwargs)
            n_eager = max(1, min(iters, 3))
            eager_out = eager_fn(*bound)
            jax.block_until_ready(eager_out)
            t1 = time.perf_counter()
            for _ in range(n_eager):
                eager_out = eager_fn(*bound)
            jax.block_until_ready(eager_out)
            eager_s = (time.perf_counter() - t1) / n_eager
            report["opbyop_ms"] = eager_s * 1e3
            report["speedup_vs_opbyop"] = eager_s / fused_s if fused_s else None
        except Exception as e:  # comparison is best-effort (e.g. captured args)
            report["opbyop_error"] = str(e)[:200]

    cs = getattr(cfn, "_cs", None)
    if cs is not None:
        for attr in ("last_trace_tracing_time_ns", "last_trace_transform_time_ns", "last_compile_time_ns"):
            v = getattr(cs, attr, None)
            if v:
                report[attr.replace("last_", "").replace("_ns", "_ms")] = v / 1e6
        # int() — the counters are AtomicCounter (json-unserializable as-is)
        hits = getattr(cs, "cache_hits", None)
        misses = getattr(cs, "cache_misses", None)
        report["cache_hits"] = None if hits is None else int(hits)
        report["cache_misses"] = None if misses is None else int(misses)
        report["compile_report"] = getattr(cs, "last_compile_report", None)

    from ..observability import events as _obs
    from ..observability import metrics as _obs_metrics

    if _obs.enabled():
        report["obs_cache_stats"] = _obs_metrics.cache_stats()
    return report


def profile_report(cfn, *args, trace_dir: Optional[str] = None, **kwargs) -> str:
    """Run one call under jax.profiler and return the trace directory
    (open with tensorboard / xprof; reference NvtxProfileTransform's role,
    thunder/dev_utils/nvtx_profile_transform.py:41)."""
    trace_dir = trace_dir or os.path.join("/tmp", f"thunder_tpu_profile_{os.getpid()}")
    with jax.profiler.trace(trace_dir):
        out = cfn(*args, **kwargs)
        jax.block_until_ready(out)
    return trace_dir


def profile_summary(fn, *args, steps: int = 3, top: int = 12, trace_dir: Optional[str] = None,
                    **kwargs) -> dict:
    """Run ``fn`` under jax.profiler and aggregate device time by op bucket.

    The programmatic form of a profile's per-step breakdown (reference
    report.py's timing tables): buckets pallas kernels by fusion name,
    groups XLA fusions/copies by kind, and returns ms-per-step numbers —
    enough to name a bottleneck without opening tensorboard.

    Returns {"buckets": [(name, ms_per_step), ...], "total_ms_per_step",
    "trace_dir"}. Events overlap (async copies run under compute), so bucket
    sums can exceed wall clock.
    """
    import glob as _glob
    import re as _re

    os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")
    trace_dir = trace_dir or os.path.join("/tmp", f"thunder_tpu_profile_{os.getpid()}")
    out = fn(*args, **kwargs)
    jax.block_until_ready(out)
    with jax.profiler.trace(trace_dir):
        for _ in range(steps):
            out = fn(*args, **kwargs)
        jax.block_until_ready(out)

    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except Exception as e:  # pragma: no cover
        return {"error": f"xplane parser unavailable: {e}", "trace_dir": trace_dir}

    buckets: dict = {}
    for path in _glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True):
        xs = xplane_pb2.XSpace()
        xs.ParseFromString(open(path, "rb").read())
        for plane in xs.planes:
            if "TPU" not in plane.name:
                continue
            ev_names = {k: v.name for k, v in plane.event_metadata.items()}
            for line in plane.lines:
                for ev in line.events:
                    nm = ev_names.get(ev.metadata_id, "?")
                    if nm.startswith("jit_"):  # whole-module envelope event
                        continue
                    if "custom-call" in nm and "xla_fusion" in nm:
                        key = "pallas:" + _re.match(r"%?(xla_fusion_\d+)", nm).group(1)
                    else:
                        m = _re.match(r"%?([A-Za-z_]+[A-Za-z_0-9-]*?)(?:[.\d]*) =", nm)
                        key = m.group(1) if m else nm.split(" ")[0]
                    buckets[key] = buckets.get(key, 0.0) + ev.duration_ps / 1e9 / steps
    ranked = sorted(buckets.items(), key=lambda kv: -kv[1])[:top]
    return {"buckets": [(k, round(v, 3)) for k, v in ranked],
            "total_ms_per_step": round(sum(buckets.values()), 2),
            "trace_dir": trace_dir}
