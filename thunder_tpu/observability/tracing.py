"""End-to-end request tracing: one trace id from submit() to retirement.

Dapper-style per-request tracing over the existing event bus. A ``trace_id``
is minted when ``ServingEngine.submit()`` accepts a request (and ONLY when
the bus is enabled — with the bus off the request carries ``trace_id=None``
and every downstream site exits on one attribute read, the same zero-work
contract as every other observability touch). The id then propagates
through admission, prefix-cache lookup, every prefill chunk, every decode
iteration the request participates in, speculation verify steps,
preemption/resume, and retirement.

Two emission shapes keep the timeline volume proportional to requests, not
to batch size × steps:

* ``trace_event(trace_id, phase, ...)`` — one bus event per REQUEST phase
  (submitted, prefix_lookup, admitted, prefill, prefill_chunk, preempted,
  resumed, retired, failed), carrying ``trace_id`` and ``request``.
* ``trace_step(trace_ids, phase, ...)`` — one bus event per SHARED batch
  step (decode, spec_verify), carrying the full participant list in
  ``trace_ids``. A 32-wide decode step is one record, not 32; readers
  expand it per participant.

The ``trace.spans`` counter still counts per participant, so counters stay
comparable with ``serve.decode_steps`` accounting.

Readers: ``timeline(records, ...)`` flattens one request's records into
ordered phase entries; ``chrome_trace(records, ...)`` converts them to
Chrome trace-event JSON (load in chrome://tracing or Perfetto — "X"
complete events for phases with a duration, "i" instants otherwise).
``tools/obs_summary.py trace <request_id>`` wraps both for the CLI.

``disabled_overhead_us()`` times the disabled-path guard sequence a
serving step pays, so that the trace-id plumbing can be seen if it ever
grows the hot path.
"""
from __future__ import annotations

import itertools
import json
import os
import time
from typing import Iterable, Optional

from . import events as _events

# phases in canonical lifecycle order (ordering key for timeline rendering;
# ties on ts_ms sort by lifecycle position)
PHASES = ("submitted", "prefix_lookup", "admitted", "prefill",
          "prefill_chunk", "decode", "spec_verify", "preempted", "resumed",
          "retired", "failed")
_PHASE_ORDER = {p: i for i, p in enumerate(PHASES)}

# phases recorded as durations (Chrome "X" complete events); the rest are
# instants
_DURATION_PHASES = frozenset(
    ("prefill", "prefill_chunk", "decode", "spec_verify"))

_seq = itertools.count(1)
_host_tag: Optional[str] = None


def _host() -> str:
    """Short host/process tag baked into every trace id so ids minted on
    different hosts of one fleet never collide. TT_MP_PROC (the harness
    env, set before jax initializes) wins; a bare process falls back to
    its pid."""
    global _host_tag
    if _host_tag is None:
        proc = os.environ.get("TT_MP_PROC")
        _host_tag = f"h{proc}" if proc is not None else f"{os.getpid():x}"
    return _host_tag


def new_trace_id() -> str:
    """Mint a fleet-unique trace id: ``<host>-<pid hex>-<seq>``. Call sites
    gate on ``events.enabled()`` — a disabled bus mints nothing."""
    _events.inc("trace.requests")
    return f"{_host()}-{os.getpid():x}-{next(_seq)}"


def trace_event(trace_id: Optional[str], phase: str, *,
                request=None, dur_ms: Optional[float] = None,
                **attrs) -> None:
    """One per-request lifecycle phase. No-op (one ``is None`` test) when
    the request was submitted with the bus off."""
    if trace_id is None or not _events.enabled():
        return
    if dur_ms is not None:
        attrs["dur_ms"] = round(float(dur_ms), 3)
    _events.event("trace", trace_id=trace_id, phase=phase, request=request,
                  **attrs)
    _events.inc("trace.spans")


def trace_step(trace_ids: Iterable[Optional[str]], phase: str, *,
               dur_ms: Optional[float] = None, **attrs) -> None:
    """One SHARED batch step (decode / spec_verify): a single bus event
    carrying every participating trace id, so timeline volume scales with
    steps, not steps × batch width. Ids of untraced requests (None) are
    dropped; an all-None batch emits nothing."""
    if not _events.enabled():
        return
    ids = [t for t in trace_ids if t is not None]
    if not ids:
        return
    if dur_ms is not None:
        attrs["dur_ms"] = round(float(dur_ms), 3)
    _events.event("trace", trace_ids=ids, phase=phase, **attrs)
    _events.inc("trace.spans", len(ids))


# -- read side ---------------------------------------------------------------


def resolve_trace_id(records: list[dict], request_id) -> Optional[str]:
    """Find the trace id minted for ``request_id`` (string compare, so int
    ids from the scheduler and strings from the CLI both work)."""
    want = str(request_id)
    for rec in records:
        if rec.get("kind") != "event" or rec.get("name") != "trace":
            continue
        a = rec.get("attrs") or {}
        if a.get("trace_id") and str(a.get("request")) == want:
            return a["trace_id"]
    return None


def timeline(records: list[dict], *, trace_id: Optional[str] = None,
             request_id=None) -> list[dict]:
    """One request's trace records, ordered, with shared step events
    (``trace_ids`` lists) expanded to this request's participation. Each
    entry: {"phase", "ts_ms", "dur_ms" (maybe), "pid", "attrs"}."""
    if trace_id is None:
        if request_id is None:
            raise ValueError("need trace_id or request_id")
        trace_id = resolve_trace_id(records, request_id)
        if trace_id is None:
            return []
    out = []
    for rec in records:
        if rec.get("kind") != "event" or rec.get("name") != "trace":
            continue
        a = rec.get("attrs") or {}
        if a.get("trace_id") != trace_id and \
                trace_id not in (a.get("trace_ids") or ()):
            continue
        entry = {"phase": a.get("phase", "?"), "ts_ms": rec.get("ts_ms", 0.0),
                 "pid": rec.get("pid"),
                 "attrs": {k: v for k, v in a.items()
                           if k not in ("trace_id", "trace_ids", "phase",
                                        "dur_ms")}}
        if a.get("dur_ms") is not None:
            entry["dur_ms"] = a["dur_ms"]
        out.append(entry)
    out.sort(key=lambda e: (e["ts_ms"],
                            _PHASE_ORDER.get(e["phase"], len(PHASES))))
    return out


def chrome_trace(records: list[dict], *, trace_id: Optional[str] = None,
                 request_id=None) -> list[dict]:
    """Convert one request's trace to Chrome trace-event JSON (the
    ``traceEvents`` array form; chrome://tracing and Perfetto load it
    directly). Phases with a duration become "X" complete events whose
    start is the emit time minus the duration (the bus stamps records at
    phase END); instant phases become "i" events."""
    tl = timeline(records, trace_id=trace_id, request_id=request_id)
    tid = trace_id or (request_id is not None
                       and resolve_trace_id(records, request_id)) or "?"
    out = []
    for e in tl:
        args = {k: v for k, v in e["attrs"].items() if v is not None}
        base = {"name": e["phase"], "cat": "serving",
                "pid": e.get("pid") or 0, "tid": str(tid), "args": args}
        dur_ms = e.get("dur_ms")
        if dur_ms is not None:
            base.update(ph="X", ts=round((e["ts_ms"] - dur_ms) * 1e3, 1),
                        dur=round(dur_ms * 1e3, 1))
        else:
            base.update(ph="i", ts=round(e["ts_ms"] * 1e3, 1), s="t")
        out.append(base)
    return out


def write_chrome_trace(path: str, records: list[dict], *,
                       trace_id: Optional[str] = None,
                       request_id=None) -> str:
    evs = chrome_trace(records, trace_id=trace_id, request_id=request_id)
    with open(path, "w") as f:
        json.dump({"traceEvents": evs, "displayTimeUnit": "ms"}, f)
    return path


# -- disabled-path overhead probe --------------------------------------------


def disabled_overhead_us(n: int = 20_000, repeats: int = 5) -> float:
    """Per-step cost, in microseconds, of the observability guards a
    serving decode step pays with the bus in its CURRENT state — run it
    after ``observability.disable()`` to measure the disabled path.

    One probe iteration touches the same guard sequence a decode iteration
    does: the bus-enabled read, a shared trace_step call, and a
    trace_event call on an untraced request — all of which must exit
    within a few attribute reads. Min-of-repeats over a large n keeps the
    number stable enough to compare without slack."""
    enabled = _events.enabled
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _i in range(n):
            if enabled():
                pass
            trace_step((), "decode")
            trace_event(None, "retired")
        dt = time.perf_counter() - t0
        best = min(best, dt)
    return best / n * 1e6
