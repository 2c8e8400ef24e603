"""Parallel region compilation: compile fusion regions concurrently, at
transform time, from the store when warm.

``transform_for_execution`` forms XLA fusion regions whose ``jax.jit``
callables historically compiled serially at FIRST DISPATCH — a multi-region
trace paid trace-order-serialized XLA compiles, and every process paid all
of them again. This module, called from the region handoff in
``executors/passes.py``:

* collects the trace's fusion regions (the same regions the profiler's
  region registry indexes — ``observability/profiler.py``);
* for each region, probes the artifact store for a content-addressed
  executable (key: canonical subtrace text + input avals + environment) —
  a hit deserializes instead of compiling (``compile_artifact_hit``);
* misses lower + XLA-compile CONCURRENTLY on a worker pool, each under a
  per-region ``compile_region`` span, and publish to the store;
* the resulting ``Compiled`` is installed on the region impl
  (``impl._prewarmed`` — executors/xlaex.py consults it before the lazy
  ``jax.jit`` path, with fallback on any argument/ABI mismatch so
  prewarming can never change semantics).

Enablement: ``TT_PARALLEL_COMPILE=1/0`` forces on/off; the default follows
the artifact store (on when a store directory is configured — i.e. when
the operator opted into the compile service — off otherwise, so plain CPU
test runs keep the lazy path and its timing).
"""
from __future__ import annotations

import os
import re
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

from ..observability import events as _obs
from . import store as _store

_DEF_NAME = re.compile(r"def \w+\(")


def parallel_compile_enabled() -> bool:
    env = os.environ.get("TT_PARALLEL_COMPILE")
    if env is not None:
        return env not in ("0", "false", "no", "off", "")
    return _store.store_enabled()


def _workers(n_regions: int) -> int:
    env = os.environ.get("TT_COMPILE_WORKERS")
    cap = int(env) if env else 8
    return max(1, min(cap, n_regions))


def fusion_regions(trace) -> list:
    """The trace's prewarmable fusion regions: bsyms whose impl carries the
    xlaex contract (``.jitted`` + ``.subtrace`` + a ``._prewarmed`` slot)."""
    out = []
    for bsym in trace.bound_symbols:
        impl = getattr(bsym, "impl", None)
        if (impl is not None and hasattr(impl, "jitted")
                and hasattr(impl, "subtrace") and hasattr(impl, "_prewarmed")):
            out.append(bsym)
    return out


def _region_avals(bsym) -> Optional[tuple]:
    """jax.ShapeDtypeStruct specs for the region's inputs; None when any
    input is not a plain tensor (number-proxy regions compile lazily — a
    concrete value may be baked into the lowering)."""
    import jax

    from ..core import dtypes as _dt
    from ..core.proxies import TensorProxy

    specs = []
    for p in bsym.args:
        if not isinstance(p, TensorProxy):
            return None
        jdt = _dt.to_jax_dtype(p.dtype)
        if jdt is None:
            return None
        specs.append(jax.ShapeDtypeStruct(tuple(p.shape), jdt))
    return tuple(specs)


def region_key(bsym, avals) -> str:
    """Content address of one region executable: canonical subtrace text +
    input avals (+ the environment fingerprint artifact_key embeds). The
    region's auto-assigned name (``xla_fusion_N`` — a per-process counter,
    not program identity) is stripped so identical programs compiled in
    different processes/orders share one artifact. The input positions the
    region donates (executors/xlaex.py) are part of the executable and not
    of the text, so they go into the key — only where there are any, so a
    region that donates nothing keeps the key it always had."""
    sub = bsym.impl.subtrace
    head, nl, body = sub.python().partition("\n")
    donate = getattr(bsym.impl, "donate_argnums", ())
    options = getattr(bsym.impl, "compiler_options", None)
    return _store.artifact_key(
        kind="region",
        trace=_DEF_NAME.sub("def region(", head, count=1) + nl + body,
        avals="|".join(f"{s.shape}:{s.dtype}" for s in avals),
        **({"donate": ",".join(map(str, donate))} if donate else {}),
        **({"options": ",".join(f"{k}={v}" for k, v in sorted(options.items()))} if options else {}),
    )


def prewarm_regions(trace, *, where: str = "", store=None,
                    use_store: Optional[bool] = None) -> dict:
    """Compile (or load) every fusion region of ``trace`` concurrently.
    Returns {"regions", "prewarmed", "store_hits", "compiled"}; failures
    are contained per region (the region falls back to its lazy path)."""
    regions = fusion_regions(trace)
    stats = {"regions": len(regions), "prewarmed": 0, "store_hits": 0,
             "compiled": 0}
    if not regions:
        return stats
    if use_store is None:
        use_store = _store.store_enabled()
    st = store if store is not None else (_store.get_store() if use_store else None)

    def one(bsym):
        name = bsym.sym.name
        avals = _region_avals(bsym)
        if avals is None:
            return None
        key = region_key(bsym, avals)
        with _obs.span("compile_region", region=name, fn=where,
                       n_ops=len(bsym.subsymbols)) as sp:
            compiled = None
            if st is not None:
                compiled = st.get_executable(key)
                if compiled is not None:
                    sp.set(outcome="store-hit")
                    return bsym, compiled, "hit"
            try:
                compiled = bsym.impl.jitted.lower(*avals).compile()
            except Exception as e:  # contained: the lazy path still works
                sp.set(outcome="failed", error=type(e).__name__)
                return None
            sp.set(outcome="compiled")
            if st is not None:
                st.put_executable(key, compiled, kind="region",
                                  meta={"region": name, "fn": where})
        return bsym, compiled, "compiled"

    results = []
    if len(regions) == 1:
        results.append(one(regions[0]))
    else:
        with ThreadPoolExecutor(max_workers=_workers(len(regions)),
                                thread_name_prefix="tt-compile") as pool:
            results = list(pool.map(one, regions))
    for res in results:
        if res is None:
            continue
        bsym, compiled, outcome = res
        bsym.impl._prewarmed = compiled
        stats["prewarmed"] += 1
        stats["store_hits" if outcome == "hit" else "compiled"] += 1
    if _obs.enabled() and stats["prewarmed"]:
        _obs.inc("compile.regions_prewarmed", stats["prewarmed"])
        if stats["store_hits"]:
            _obs.inc("compile.region_store_hits", stats["store_hits"])
    return stats


def maybe_prewarm(trace, *, where: str = "") -> Optional[dict]:
    """The region handoff called by ``transform_for_execution``: a no-op
    unless parallel compilation is enabled; never raises."""
    if not parallel_compile_enabled():
        return None
    import jax

    # under an ambient jax trace (a ThunderValueAndGrad compiling inside
    # TrainStep's whole-step jax.jit, a shard_map body) the regions will
    # be INLINED into the outer program — a standalone region executable
    # would never be dispatched, so compiling one is pure cold-start
    # overhead (and the whole-step artifact already covers that path)
    if not jax.core.trace_ctx.is_top_level():
        return None
    try:
        return prewarm_regions(trace, where=where)
    except Exception:
        return None
