"""thunder_tpu.observability: structured spans, metrics, and diagnostics.

The compile pipeline and runtime emit a machine-readable timeline of what
they did — compile-phase spans (acquisition, transforms, executor dispatch,
XLA compile), cache hit/miss/evict counters, reason-coded recompile events,
fusion formation, per-step latency. See docs/observability.md for the JSONL
schema and tools/obs_summary.py for the CLI view.

Quick start:
    import thunder_tpu as tt
    tt.observability.enable("/tmp/tt.jsonl")   # or TT_OBS=1 / TT_OBS_FILE=...
    cfn = tt.jit(fn); cfn(x)
    tt.observability.summary()                 # aggregated spans/counters
    tt.observability.last_compile_report(cfn)  # last compile, phase by phase
    tt.observability.snapshot()                # live counters/gauges + online
                                               # p50/p90/p99 per series
    tt.observability.start_exporter(9100)      # or TT_OBS_EXPORT=<port|path>
"""
from __future__ import annotations

from .events import (  # noqa: F401
    counters,
    disable,
    dump,
    enable,
    enabled,
    event,
    inc,
    key_digest,
    records,
    reset,
    span,
    summary,
)
from .metrics import (  # noqa: F401
    REASON_CACHE_MISS,
    REASON_CODES,
    REASON_FALLBACK,
    REASON_SHAPE_CHANGE,
    REASON_STALE_KEY,
    cache_stats,
    record_artifact,
    record_cache,
    record_executable_size,
    record_fusion,
    record_recompile,
)
from .runtime import (  # noqa: F401
    annotate_call,
    fusion_scope,
    phase,
    sample_rate,
    set_sample_rate,
    step_sampled,
    step_span,
)
from . import fleet  # noqa: F401
from . import flight_recorder  # noqa: F401
from . import flops  # noqa: F401
from . import profiler  # noqa: F401
from . import slo  # noqa: F401
from . import telemetry  # noqa: F401
from . import tracing  # noqa: F401
from .fleet import StragglerDetector, fleet_snapshot, incidents  # noqa: F401
from .flight_recorder import install_crash_hook, uninstall_crash_hook  # noqa: F401
from .slo import SLOMonitor, SLOPolicy  # noqa: F401
from .tracing import chrome_trace, new_trace_id, trace_event, trace_step  # noqa: F401
from .telemetry import (  # noqa: F401
    MetricsExporter,
    StreamingHistogram,
    gauge,
    gauges,
    histogram,
    histogram_snapshots,
    observe,
    render_prometheus,
    set_gauge,
    snapshot,
    start_exporter,
    stop_exporter,
)
from .profiler import (  # noqa: F401
    DeviceProfile,
    attribute,
    op_scopes,
    profile,
    profile_steps,
    region_info,
    regions,
    register_region,
    resolve,
    scope_of,
)


def last_compile_report(cfn) -> dict | None:
    """Phase-by-phase report of a compiled function's most recent compile:
    {"fn", "trace", "cache_key", "total_ms", "phases": [{"name", "dur_ms",
    ...tags}]}. Populated on every compile, even with recording disabled
    (the driver always times its phases). Accepts anything jit() returns —
    a ThunderCompiledFunction, InterpretedFunction, or ThunderModule."""
    cs = getattr(cfn, "_cs", None)
    if cs is None:
        cfn_inner = getattr(cfn, "_cfn", None)
        cs = getattr(cfn_inner, "_cs", None)
    if cs is None:
        raise ValueError(f"{cfn!r} is not a thunder_tpu-compiled function")
    return getattr(cs, "last_compile_report", None)
