"""Live telemetry: bounded-memory streaming percentiles + a metrics exporter.

Everything the bus records post-hoc (JSONL replayed by tools/obs_summary.py)
is ALSO available online here, so a running trainer or serving engine can
read its own TTFT/TBOT/step-time percentiles without re-parsing a timeline:

* ``StreamingHistogram`` — a DDSketch-style log-bucketed histogram with a
  relative-accuracy guarantee: ``quantile(q)`` is within ``alpha`` (default
  1%) of the true sample at that rank, using O(max_buckets) memory however
  many samples stream through. The hot recording paths (``observe``) feed
  one per series (``serve.ttft_ms``, ``serve.tbot_ms``, ``train.step_ms``,
  ...) and pay a dict lookup + one bucket increment per sample — and, like
  every other per-step touch, NOTHING when the bus is disabled.

* gauges — last-value-wins instruments (page-pool utilization, pages in
  use, serving goodput) set by the runtime; ``snapshot()`` adds derived
  gauges (compile-cache hit rates, flight-recorder spike count) computed
  from the live counters at read time.

* ``snapshot()`` — the pull API for in-process consumers (the scheduler's
  future SLO-aware admission lanes, harnesses, tests): one dict with
  counters, gauges, and per-series histogram summaries.

* the exporter — ``TT_OBS_EXPORT=<port|path>`` (or ``start_exporter()``)
  runs an opt-in background thread serving (HTTP) or atomically writing
  (file) Prometheus-text-format snapshots of all counters, gauges, and
  histogram buckets. A numeric target is a port (0 picks one; read it back
  from ``exporter().port``), anything else is a file path rewritten every
  ``interval`` seconds. Setting TT_OBS_EXPORT implies TT_OBS=1: exporting
  an idle bus would scrape empty forever.

Sampling note: ``TT_OBS_SAMPLE`` thins *timeline* records (spans, events) —
the histograms stay unsampled, exactly like the flight recorder, so online
percentiles are computed over every step rather than a sampled subset
(docs/observability.md, "Sampling").
"""
from __future__ import annotations

import atexit
import math
import os
import threading
from typing import Optional, Union

from . import events


def percentile(xs, q: float):
    """Nearest-rank percentile over a concrete sample list — THE rank
    convention of the SLO monitors, mirrored by tools/obs_summary.py (kept
    standalone-stdlib, so its copy is deliberate). StreamingHistogram.quantile matches it within alpha —
    the documented online/offline agreement depends on one convention."""
    if not xs:
        return 0.0
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(round(q * (len(xs) - 1))))]


class StreamingHistogram:
    """Log-bucketed streaming histogram (the DDSketch scheme, SIGMOD '19).

    A positive value v lands in bucket ``ceil(log_gamma(v))`` where
    ``gamma = (1 + alpha) / (1 - alpha)``; the bucket's representative value
    ``2 * gamma^i / (gamma + 1)`` is within ``alpha`` relative error of
    anything that mapped to it, so any quantile comes back within ``alpha``
    of the exact sample at that rank. Non-positive values (a 0.0 TBOT
    placeholder) collapse into one zero bucket. When the index map outgrows
    ``max_buckets``, the two lowest buckets merge — accuracy degrades only
    at the cheap end of the distribution, never at the tail percentiles a
    latency SLO reads."""

    __slots__ = ("alpha", "gamma", "_log_gamma", "_counts", "_zero", "count",
                 "sum", "min", "max", "max_buckets", "_lock")

    def __init__(self, alpha: float = 0.01, max_buckets: int = 1024):
        if not (0.0 < alpha < 1.0):
            raise ValueError(f"alpha must be in (0, 1), got {alpha}")
        self.alpha = alpha
        self.gamma = (1.0 + alpha) / (1.0 - alpha)
        self._log_gamma = math.log(self.gamma)
        self.max_buckets = max_buckets
        self._counts: dict[int, int] = {}
        self._zero = 0
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        v = float(value)
        with self._lock:
            self.count += 1
            self.sum += v
            if v < self.min:
                self.min = v
            if v > self.max:
                self.max = v
            if v <= 0.0:
                self._zero += 1
                return
            i = math.ceil(math.log(v) / self._log_gamma)
            self._counts[i] = self._counts.get(i, 0) + 1
            if len(self._counts) > self.max_buckets:
                # collapse the two lowest buckets (DDSketch's policy): tail
                # quantiles — the ones SLOs bind — keep full accuracy
                lo = sorted(self._counts)[:2]
                self._counts[lo[1]] += self._counts.pop(lo[0])

    def _value_of(self, index: int) -> float:
        return 2.0 * self.gamma ** index / (self.gamma + 1.0)

    def quantile(self, q: float) -> Optional[float]:
        """Estimate the q-quantile (nearest-rank, matching the offline
        tools' convention) within ``alpha`` relative error."""
        if not (0.0 <= q <= 1.0):
            raise ValueError(f"quantile must be in [0, 1], got {q}")
        with self._lock:
            if self.count == 0:
                return None
            rank = int(round(q * (self.count - 1)))
            if rank >= self.count - 1:
                return self.max  # the top rank is tracked exactly
            if rank < self._zero:
                return max(0.0, self.min)
            seen = self._zero
            for i in sorted(self._counts):
                seen += self._counts[i]
                if seen > rank:
                    # clamp to the observed extremes: the bucket midpoint of
                    # a one-sample tail bucket must not exceed the real max
                    return min(max(self._value_of(i), self.min), self.max)
            return self.max

    def snapshot(self) -> dict:
        """Summary dict: count/sum/min/max plus p50/p90/p99."""
        with self._lock:
            if self.count == 0:
                return {"count": 0}
            base = {"count": self.count, "sum": round(self.sum, 3),
                    "min": round(self.min, 3), "max": round(self.max, 3),
                    "mean": round(self.sum / self.count, 3)}
        base["p50"] = round(self.quantile(0.50), 3)
        base["p90"] = round(self.quantile(0.90), 3)
        base["p99"] = round(self.quantile(0.99), 3)
        return base

    def buckets(self) -> list[tuple[float, int]]:
        """Cumulative (upper_bound, count) pairs for Prometheus-format
        export; the caller appends the +Inf bucket (== count)."""
        with self._lock:
            out = []
            cum = 0
            if self._zero:
                cum = self._zero
                out.append((0.0, cum))
            for i in sorted(self._counts):
                cum += self._counts[i]
                out.append((self.gamma ** i, cum))
            return out

    def n_buckets(self) -> int:
        with self._lock:
            return len(self._counts) + (1 if self._zero else 0)

    # -- fleet merge (observability/fleet.py) --------------------------------
    #
    # Two histograms with the same alpha share one bucket-index space
    # (i = ceil(log_gamma(v))), so adding their count maps produces EXACTLY
    # the map a single histogram fed both streams would hold — fleet
    # percentiles are merge-exact, not averages-of-percentiles
    # (tests/test_fleet.py pins the identity).

    def state(self) -> dict:
        """JSON-serializable raw state for cross-host merging: alpha, the
        sparse bucket-count map, the zero bucket, and the exact count/sum/
        min/max moments."""
        with self._lock:
            return {
                "alpha": self.alpha,
                "counts": {str(i): c for i, c in self._counts.items()},
                "zero": self._zero,
                "count": self.count,
                "sum": self.sum,
                "min": None if self.count == 0 else self.min,
                "max": None if self.count == 0 else self.max,
            }

    def merge_state(self, state: dict) -> None:
        """Fold another histogram's ``state()`` into this one bucket-wise.
        Requires an identical ``alpha`` (same gamma, same index space) —
        merging across accuracy settings would silently misbucket."""
        alpha = float(state["alpha"])
        if abs(alpha - self.alpha) > 1e-12:
            raise ValueError(
                f"cannot merge histograms with different alpha: "
                f"{alpha} != {self.alpha}")
        with self._lock:
            for k, c in state.get("counts", {}).items():
                i = int(k)
                self._counts[i] = self._counts.get(i, 0) + int(c)
            self._zero += int(state.get("zero", 0))
            self.count += int(state.get("count", 0))
            self.sum += float(state.get("sum", 0.0))
            if state.get("min") is not None:
                self.min = min(self.min, float(state["min"]))
            if state.get("max") is not None:
                self.max = max(self.max, float(state["max"]))
            while len(self._counts) > self.max_buckets:
                lo = sorted(self._counts)[:2]
                self._counts[lo[1]] += self._counts.pop(lo[0])

    @classmethod
    def from_states(cls, states, max_buckets: int = 1024) -> "StreamingHistogram":
        """Rebuild one histogram from per-host ``state()`` dicts (bucket-wise
        sum). The result is bit-identical to a single histogram that observed
        every host's stream, modulo float summation order in ``sum``."""
        states = list(states)
        if not states:
            return cls()
        h = cls(alpha=float(states[0]["alpha"]), max_buckets=max_buckets)
        for st in states:
            h.merge_state(st)
        return h


# -- process-global registry -------------------------------------------------

_lock = threading.Lock()
_hists: dict[str, StreamingHistogram] = {}
_gauges: dict[str, float] = {}


def observe(name: str, value: float) -> None:
    """Stream one sample into the named histogram series. Recording only:
    with the bus disabled this returns after one attribute read, the same
    zero-work contract as ``events.event``."""
    if not events.enabled():
        return
    h = _hists.get(name)
    if h is None:
        with _lock:
            h = _hists.setdefault(name, StreamingHistogram())
    h.observe(value)


def histogram(name: str) -> Optional[StreamingHistogram]:
    return _hists.get(name)


def histogram_snapshots() -> dict[str, dict]:
    return {name: h.snapshot() for name, h in sorted(_hists.items())}


def histogram_states() -> dict[str, dict]:
    """Raw per-series bucket states for fleet snapshots (fleet.py): the
    mergeable representation, not the summarized one."""
    return {name: h.state() for name, h in sorted(_hists.items())}


def set_gauge(name: str, value: float) -> None:
    """Last-value-wins instrument (pool utilization, goodput). Recording
    only — one attribute read when the bus is disabled."""
    if not events.enabled():
        return
    _gauges[name] = float(value)


def gauge(name: str) -> Optional[float]:
    return _gauges.get(name)


def gauges() -> dict[str, float]:
    """Set gauges plus the derived ones computed from live state: per-cache
    hit rates and the flight recorder's spike count."""
    out = dict(_gauges)
    from . import flight_recorder as _fr
    from .metrics import cache_stats

    for cache, st in cache_stats().items():
        hit, miss = st.get("hit", 0), st.get("miss", 0)
        if hit + miss:
            out[f"{cache}.hit_rate"] = round(hit / (hit + miss), 4)
    out["flight.spikes"] = float(_fr.recorder().spikes)
    return out


def snapshot() -> dict:
    """The pull API: one dict with everything a live consumer (scheduler,
    harness, exporter) needs — counters, gauges (set + derived), and the
    per-series histogram summaries with online p50/p90/p99."""
    return {
        "enabled": events.enabled(),
        "counters": events.counters(),
        "gauges": gauges(),
        "histograms": histogram_snapshots(),
    }


def reset() -> None:
    """Clear histograms and gauges (tests; events.reset() calls this too so
    one reset clears the whole recorded state)."""
    with _lock:
        _hists.clear()
        _gauges.clear()


# -- Prometheus text exposition ---------------------------------------------


def _prom_name(name: str) -> str:
    safe = "".join(c if c.isalnum() or c == "_" else "_" for c in name)
    if safe and safe[0].isdigit():
        safe = "_" + safe
    return "tt_" + safe


def _prom_num(v: float) -> str:
    if v != v:
        return "NaN"
    if v == math.inf:
        return "+Inf"
    if v == -math.inf:
        return "-Inf"
    return repr(round(v, 9)) if isinstance(v, float) else str(v)


def render_prometheus() -> str:
    """The full metric surface in Prometheus text exposition format:
    counters as `counter`, gauges as `gauge`, histogram series as native
    `histogram` metrics with cumulative log-spaced `le` buckets."""
    lines: list[str] = []
    emitted: set[str] = set()
    for name, v in sorted(events.counters().items()):
        p = _prom_name(name)
        emitted.add(p)
        lines.append(f"# TYPE {p} counter")
        lines.append(f"{p} {v}")
    for name, v in sorted(gauges().items()):
        p = _prom_name(name)
        if p in emitted:
            # a bus counter already claimed this family (e.g. the
            # `flight.spikes` counter vs the derived gauge): a second TYPE
            # line for the same name would invalidate the whole scrape
            continue
        lines.append(f"# TYPE {p} gauge")
        lines.append(f"{p} {_prom_num(v)}")
    for name, h in sorted(_hists.items()):
        p = _prom_name(name)
        lines.append(f"# TYPE {p} histogram")
        for le, cum in h.buckets():
            lines.append(f'{p}_bucket{{le="{_prom_num(le)}"}} {cum}')
        lines.append(f'{p}_bucket{{le="+Inf"}} {h.count}')
        lines.append(f"{p}_sum {_prom_num(h.sum)}")
        lines.append(f"{p}_count {h.count}")
    return "\n".join(lines) + "\n"


class MetricsExporter:
    """Opt-in background exporter of ``render_prometheus()`` snapshots.

    target: an int / digit-string is a TCP port to serve ``GET /metrics``
    on (0 binds an ephemeral port — read ``.port`` back); anything else is
    a file path atomically rewritten every ``interval`` seconds (for
    node-exporter textfile collection or plain tailing).

    ``fleet=True`` serves the merged cross-host view instead of the local
    one: each scrape publishes this host's snapshot through the
    coordination KV, collects every host's latest, and renders merged
    ``tt_*`` series carrying a ``host`` label (per-host samples plus a
    ``host="fleet"`` bucket-wise-merged aggregate — fleet.py). Falls back
    to the local render if the merge fails mid-run (a peer died), so a
    scrape never comes back empty."""

    def __init__(self, target: Union[int, str], interval: float = 2.0,
                 fleet: bool = False):
        self.target = target
        self.interval = interval
        self.fleet = fleet
        self.port: Optional[int] = None
        self.path: Optional[str] = None
        self._server = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    def start(self) -> "MetricsExporter":
        t = self.target
        if isinstance(t, int) or (isinstance(t, str) and t.isdigit()):
            self._start_http(int(t))
        else:
            self._start_file(str(t))
        return self

    def _render(self) -> str:
        if self.fleet:
            from . import fleet as _fleet  # deferred: fleet imports this module
            try:
                return _fleet.render_prometheus_fleet()
            except Exception:  # noqa: BLE001 - a dead peer or KV hiccup must
                # not blank the scrape; serve the local view instead
                pass
        return render_prometheus()

    def _start_http(self, port: int) -> None:
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
        exporter_self = self

        class Handler(BaseHTTPRequestHandler):
            def do_GET(handler):  # noqa: N805 - stdlib handler convention
                body = exporter_self._render().encode()
                handler.send_response(200)
                handler.send_header("Content-Type",
                                    "text/plain; version=0.0.4; charset=utf-8")
                handler.send_header("Content-Length", str(len(body)))
                handler.end_headers()
                handler.wfile.write(body)

            def log_message(handler, *args):  # quiet: scrapes are periodic
                pass

        self._server = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        name="tt-metrics-exporter", daemon=True)
        self._thread.start()

    def _start_file(self, path: str) -> None:
        self.path = path
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        self._write_file()  # one immediate snapshot: a crash-fast process
        # still leaves a scrape behind

        def loop():
            while not self._stop.wait(self.interval):
                self._write_file()

        self._thread = threading.Thread(target=loop, name="tt-metrics-exporter",
                                        daemon=True)
        self._thread.start()

    def _write_file(self) -> None:
        tmp = f"{self.path}.tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                f.write(self._render())
            os.replace(tmp, self.path)  # atomic: a scraper never reads half
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def stop(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        if self.path is not None:
            self._write_file()  # final snapshot reflects shutdown state


_exporter: Optional[MetricsExporter] = None


def start_exporter(target: Union[int, str], *, interval: float = 2.0,
                   fleet: bool = False) -> MetricsExporter:
    """Start (or replace) the process-global exporter; also enables the bus
    — an exporter over a disabled bus would scrape empty forever.
    ``fleet=True`` (or TT_OBS_EXPORT_FLEET=1 for the env-driven start)
    serves the merged cross-host view with ``host`` labels."""
    global _exporter
    stop_exporter()
    if not events.enabled():
        events.enable()
    _exporter = MetricsExporter(target, interval=interval, fleet=fleet).start()
    return _exporter


def stop_exporter() -> None:
    global _exporter
    if _exporter is not None:
        _exporter.stop()
        _exporter = None


def exporter() -> Optional[MetricsExporter]:
    return _exporter


atexit.register(stop_exporter)

# TT_OBS_EXPORT=<port|path> starts the exporter at import (and enables the
# bus). Failures (port in use, unwritable path) must not take the process
# down — telemetry is never load-bearing.
_env_export = os.environ.get("TT_OBS_EXPORT")
if _env_export:
    try:
        start_exporter(_env_export, fleet=os.environ.get(
            "TT_OBS_EXPORT_FLEET", "").lower() in ("1", "true", "yes", "on"))
    except Exception as e:  # noqa: BLE001 - port in use, bad port (>65535
        # raises OverflowError, not OSError), unwritable path: telemetry
        # must never take the importing process down
        import warnings

        warnings.warn(f"TT_OBS_EXPORT={_env_export!r}: exporter failed to "
                      f"start ({type(e).__name__}: {e}); continuing without it")
