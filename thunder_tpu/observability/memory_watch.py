"""Live memory observability: HBM watermarks, census, OOM forensics.

``analysis/memory.py`` predicts a step's peak bytes before it runs; this
module measures what actually happened, so the estimate can be reconciled
against reality and an OOM stops being an opaque RESOURCE_EXHAUSTED crash:

* ``on_step`` — step-boundary sampling of ``device.memory_stats()`` into
  ``mem.*`` gauges/histograms plus a bounded watermark ring. Like every
  per-step observability touch it is gated behind ONE ``events.enabled()``
  read: disabled, it does no sampling, takes no lock, allocates nothing.
  On backends without device memory introspection (the CPU backend returns
  ``memory_stats() is None``) it falls back to host RSS so the series —
  and the bench key ``mem_peak_measured`` — exist everywhere, tagged with
  their source.
* ``census`` — a ``jax.live_arrays()`` inventory grouped by (shape, dtype),
  top-N by resident bytes. Walking every live buffer is NOT a per-step
  price, so the periodic timeline emission hides behind the deep flag
  ``TT_MEM_DEEP=1``; the census always runs inside an OOM post-mortem,
  where the step is already dead.
* ``oom_post_mortem`` — the forensic bundle writer. A RESOURCE_EXHAUSTED
  raised through TrainStep/ServingEngine dispatch dumps live-array census,
  serving page-pool state (registered by the engine), the watermark ring,
  and the last ``analysis.budget.estimate_step_peak`` to
  ``TT_OOM_FILE`` (default <tmp>/tt_oom_<pid>.json) — the same contract as
  the flight-recorder crash hook — and emits an ``oom`` event the flight
  recorder and fleet ``incidents()`` rank as a top-priority cause. The file
  write is unconditional (forensics must survive a disabled bus); only the
  bus emission is gated.
* reconciliation — ``note_estimate`` remembers the budget prediction;
  when the measured peak diverges from it by more than ``_DRIFT_RATIO``
  in either direction, one deduplicated ``mem.estimate_drift`` event fires
  so drift is a searchable timeline fact, not a post-hoc diff.

The ``mem.*`` gauges/histograms are recorded through telemetry, so they
ride the PR-17 fleet snapshot merge (host_snapshot publishes gauges and
histogram states) with zero extra wiring here.
"""
from __future__ import annotations

import json
import os
import threading
from collections import deque
from typing import Any, Callable, Optional

from . import events as _obs
from . import telemetry as _tel

_TRUTHY = ("1", "true", "yes", "on")

_RING_CAP = 512          # watermark ring entries (one per sampled step)
_PRESSURE_FRAC = 0.92    # bytes_in_use / bytes_limit that counts as pressure
_PRESSURE_CLEAR = 0.85   # re-arm threshold (hysteresis)
_DRIFT_RATIO = 2.0       # measured vs estimated peak divergence that alerts
_CENSUS_EVERY = 16       # deep-flag census cadence (steps)

_LOCK = threading.Lock()
_RING: deque = deque(maxlen=_RING_CAP)
_PEAK_SEEN = 0.0         # high-water bytes_in_use across the run
_ESTIMATE: Optional[dict] = None  # last noted analysis.budget estimate
_PRESSURE_ON = False
_DRIFT_NOTED = False
_N_SAMPLES = 0
_POOL_STATE_FN: Optional[Callable[[], dict]] = None


def deep_census_enabled() -> bool:
    return os.environ.get("TT_MEM_DEEP", "").lower() in _TRUTHY


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------


def _host_rss() -> Optional[dict]:
    """Host-process RSS fallback (Linux /proc + getrusage): current resident
    bytes and the process high-water mark. Keeps mem.* measurable on the
    CPU backend, where ``memory_stats()`` is None."""
    try:
        import resource

        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        page = os.sysconf("SC_PAGE_SIZE")
        with open("/proc/self/statm") as f:
            rss = int(f.read().split()[1]) * page
        return {"bytes_in_use": rss, "peak_bytes_in_use": max(peak, rss),
                "source": "host_rss"}
    except (OSError, ValueError, ImportError, IndexError):
        return None


def sample() -> Optional[dict]:
    """One memory sample: device ``memory_stats()`` when the backend exposes
    it (``source: "device"``, with ``bytes_limit`` when reported), else host
    RSS (``source: "host_rss"``), else None."""
    try:
        import jax

        stats = jax.devices()[0].memory_stats()
    except Exception:  # noqa: BLE001 - uninitialized backend: fall through
        stats = None
    if stats:
        out = {"bytes_in_use": int(stats.get("bytes_in_use", 0) or 0),
               "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0) or 0),
               "source": "device"}
        limit = stats.get("bytes_limit")
        if limit:
            out["bytes_limit"] = int(limit)
        return out
    return _host_rss()


def on_step(step: Optional[int] = None, *, source: str = "train") -> None:
    """Step-boundary memory sample → ``mem.*`` gauges/histogram + watermark
    ring. The entire body hides behind one ``events.enabled()`` read."""
    global _PEAK_SEEN, _PRESSURE_ON, _DRIFT_NOTED, _N_SAMPLES
    if not _obs.enabled():
        return
    stats = sample()
    if stats is None:
        return
    in_use = float(stats["bytes_in_use"])
    peak = float(stats["peak_bytes_in_use"])
    _tel.set_gauge("mem.bytes_in_use", in_use)
    _tel.set_gauge("mem.peak_bytes_in_use", peak)
    _tel.observe("mem.step_bytes_in_use", in_use)
    limit = stats.get("bytes_limit")
    frac = (in_use / limit) if limit else None
    if frac is not None:
        _tel.set_gauge("mem.utilization", frac)
    with _LOCK:
        _N_SAMPLES += 1
        n = _N_SAMPLES
        new_high = peak > _PEAK_SEEN
        if new_high:
            _PEAK_SEEN = peak
        _RING.append({"step": step, "source": source,
                      "bytes_in_use": int(in_use), "peak_bytes_in_use": int(peak)})
    if new_high:
        _obs.event("mem_sample", step=step, source=source,
                   bytes_in_use=int(in_use), peak_bytes_in_use=int(peak),
                   mem_source=stats["source"])
    # pressure: transition-deduped with hysteresis, so a fleet stall can be
    # attributed to memory without one event per step at 93% occupancy
    if frac is not None:
        if frac >= _PRESSURE_FRAC and not _PRESSURE_ON:
            _PRESSURE_ON = True
            _obs.inc("mem.pressure")
            _obs.event("mem_pressure", step=step, source=source,
                       utilization=round(frac, 4), bytes_in_use=int(in_use))
        elif frac < _PRESSURE_CLEAR:
            _PRESSURE_ON = False
    # estimate-vs-measured reconciliation (one event per noted estimate).
    # Device truth only: host RSS includes the whole python process, so
    # comparing it to a device-bytes budget would alert on every CPU run.
    est = _ESTIMATE
    if est and not _DRIFT_NOTED and stats["source"] == "device":
        est_peak = float(est.get("peak_bytes") or 0.0)
        if est_peak > 0 and peak > 0:
            ratio = peak / est_peak
            if ratio > _DRIFT_RATIO or ratio < 1.0 / _DRIFT_RATIO:
                _DRIFT_NOTED = True
                _obs.event("mem.estimate_drift", step=step, source=source,
                           measured_peak_bytes=int(peak),
                           estimated_peak_bytes=int(est_peak),
                           ratio=round(ratio, 3))
    if deep_census_enabled() and n % _CENSUS_EVERY == 1:
        try:
            _obs.event("mem_census", step=step, groups=census(top_n=8))
        except Exception:  # noqa: BLE001 - census must never take a step down
            pass


def note_estimate(estimate: Optional[dict]) -> None:
    """Remember the latest ``analysis.budget.estimate_step_peak`` result so
    the drift check and the OOM bundle can cite it."""
    global _ESTIMATE, _DRIFT_NOTED
    with _LOCK:
        _ESTIMATE = dict(estimate) if estimate else None
        _DRIFT_NOTED = False


def reconcile(measured_peak_bytes: Optional[float],
              estimated_peak_bytes: Optional[float], *,
              context: str = "bench") -> Optional[float]:
    """One explicit estimate-vs-measured check (bench rows call this with
    the device peak next to ``mem_peak_estimated``): returns the
    measured/estimated ratio, emitting one ``mem.estimate_drift`` event
    when they diverge beyond ``_DRIFT_RATIO`` in either direction."""
    if not measured_peak_bytes or not estimated_peak_bytes:
        return None
    ratio = float(measured_peak_bytes) / float(estimated_peak_bytes)
    if (ratio > _DRIFT_RATIO or ratio < 1.0 / _DRIFT_RATIO) and _obs.enabled():
        _obs.event("mem.estimate_drift", context=context,
                   measured_peak_bytes=int(measured_peak_bytes),
                   estimated_peak_bytes=int(estimated_peak_bytes),
                   ratio=round(ratio, 3))
    return ratio


def register_pool_state(fn: Optional[Callable[[], dict]]) -> None:
    """Serving engine hands over a zero-arg callable returning its page-pool
    state (pages in use, utilization, fragmentation) for OOM bundles."""
    global _POOL_STATE_FN
    _POOL_STATE_FN = fn


def pool_state() -> Optional[dict]:
    fn = _POOL_STATE_FN
    if fn is None:
        return None
    try:
        return fn()
    except Exception:  # noqa: BLE001 - forensics never raise
        return None


def watermarks() -> list[dict]:
    with _LOCK:
        return list(_RING)


def peak_seen() -> float:
    with _LOCK:
        return _PEAK_SEEN


def census(top_n: int = 10) -> list[dict]:
    """Group ``jax.live_arrays()`` by (shape, dtype): count and resident
    bytes per group, top-N by bytes. Empty list when jax is unavailable."""
    try:
        import jax

        arrays = jax.live_arrays()
    except Exception:  # noqa: BLE001
        return []
    groups: dict[tuple, dict] = {}
    for a in arrays:
        try:
            shape = tuple(a.shape)
            dtype = str(a.dtype)
            nbytes = int(getattr(a, "nbytes", 0) or 0)
        except Exception:  # noqa: BLE001 - deleted/donated buffer mid-walk
            continue
        g = groups.setdefault((shape, dtype), {"shape": list(shape),
                                               "dtype": dtype,
                                               "count": 0, "bytes": 0})
        g["count"] += 1
        g["bytes"] += nbytes
    return sorted(groups.values(), key=lambda g: -g["bytes"])[:max(1, top_n)]


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------


def is_oom(exc: BaseException) -> bool:
    """RESOURCE_EXHAUSTED shape check: the JaxRuntimeError the allocator
    raises, or anything whose message says it ran out of device memory."""
    import jax

    msg = str(exc).upper()
    if "RESOURCE_EXHAUSTED" in msg or "OUT OF MEMORY" in msg:
        return True
    return isinstance(exc, jax.errors.JaxRuntimeError) and "EXHAUSTED" in msg


def oom_post_mortem(exc: BaseException, *, step: Optional[int] = None,
                    source: str = "train",
                    estimate: Optional[dict] = None) -> Optional[str]:
    """Dump the forensic bundle for an OOM and emit the ``oom`` cause event.

    The JSON bundle (error, live-array census, page-pool state, watermark
    ring, last budget estimate, memory sample, counters, flight-recorder
    stats) goes to ``TT_OOM_FILE`` or <tmp>/tt_oom_<pid>.json — written even
    with the bus disabled, because the crash is the one moment forensics
    must not be opt-in. Returns the bundle path (None if the write failed);
    never raises."""
    from . import flight_recorder as _fr

    bundle = {
        "kind": "oom_post_mortem",
        "error": str(exc)[:500],
        "error_type": type(exc).__name__,
        "step": step,
        "source": source,
        "memory": sample(),
        "watermarks": watermarks(),
        "live_array_census": census(top_n=16),
        "page_pool": pool_state(),
        "budget_estimate": estimate if estimate is not None else _ESTIMATE,
        "counters": _obs.counters(),
        "flight": None,
    }
    try:
        bundle["flight"] = _fr.stats()
    except Exception:  # noqa: BLE001
        pass
    path = os.environ.get(
        "TT_OOM_FILE",
        os.path.join(_fr.tempfile_dir(), f"tt_oom_{os.getpid()}.json"))
    try:
        with open(path, "w") as f:
            json.dump(bundle, f, indent=1)
    except OSError:
        path = None
    if _obs.enabled():
        mem = bundle["memory"] or {}
        _obs.inc("mem.oom")
        _obs.event("oom", step=step, source=source, bundle=path,
                   error=str(exc)[:200],
                   bytes_in_use=mem.get("bytes_in_use"),
                   estimated_peak_bytes=(bundle["budget_estimate"] or {}).get(
                       "peak_bytes"))
    return path


def maybe_post_mortem(exc: BaseException, *, step: Optional[int] = None,
                      source: str = "train") -> Optional[str]:
    """``oom_post_mortem`` iff ``exc`` looks like an OOM; the one-call hook
    dispatch paths use from their exception handlers."""
    if not is_oom(exc):
        return None
    return oom_post_mortem(exc, step=step, source=source)


def reset() -> None:
    """Clear watermark/pressure/drift state (tests, phase boundaries).
    Chained from ``events.reset()``. The pool-state registration survives —
    it is wiring, not run state."""
    global _PEAK_SEEN, _ESTIMATE, _PRESSURE_ON, _DRIFT_NOTED, _N_SAMPLES
    with _LOCK:
        _RING.clear()
        _PEAK_SEEN = 0.0
        _ESTIMATE = None
        _PRESSURE_ON = False
        _DRIFT_NOTED = False
        _N_SAMPLES = 0
