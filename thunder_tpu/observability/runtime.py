"""Runtime-side observability: per-step latency spans and profiler mapping.

Three concerns live here, all strictly opt-in on the hot path:

* ``step_span`` — a latency span per training/inference step (TrainStep
  wraps its ``__call__``). With the bus disabled it returns a shared no-op
  context manager: one attribute read, no allocation, so the bench step
  time is untouched (the acceptance bar is < 1% regression).

* ``fusion_scope`` — ``jax.named_scope`` around each fusion region's traced
  computation, so the ops inside a device profile (xprof/tensorboard) carry
  the trace-symbol-derived fusion name (``xla_fusion_3``) instead of
  anonymous HLO. Name metadata is baked at trace time and costs nothing at
  run time, so it is always on. ``annotate_call`` adds the matching
  host-side ``jax.profiler.TraceAnnotation`` per dispatch when recording.

* ``phase`` — one named interval on BOTH clocks: a bus span (``perf_counter``,
  parent ids, attributes) and a ``TraceAnnotation`` of the same name over the
  same interval, which puts it on the profiler's clock beside the device's
  ops. It is what lets an idle gap of the device be put down to the part of
  a host loop that was running (the serving engine's ``engine:*`` phases).
"""
from __future__ import annotations

import contextlib
import itertools
import os
import threading
from . import events

_NULL = contextlib.nullcontext()

# TT_OBS_SAMPLE=<rate in (0, 1]> samples step spans / per-step events so
# always-on telemetry has bounded overhead: rate 0.1 records every 10th
# step. Deterministic (counter modulo, not random) so tests can assert
# exact counts; 1.0 (the default) records everything. The gate applies
# only when the bus is enabled — disabled mode never reaches it.
# Counters are PER SITE (per span name / per compiled function): a single
# shared counter would alias across streams — two sites each consuming a
# tick per step at rate 0.5 would leave one recorded 100% and the other 0%.
_sample_every = 1
_sample_counters: dict = {}
_sample_lock = threading.Lock()


def set_sample_rate(rate: float) -> None:
    """Record roughly ``rate`` of per-step records (1.0 = all)."""
    global _sample_every
    if not (0.0 < rate <= 1.0):
        raise ValueError(f"sample rate must be in (0, 1], got {rate}")
    with _sample_lock:
        _sample_every = max(1, round(1.0 / rate))
        _sample_counters.clear()


def sample_rate() -> float:
    return 1.0 / _sample_every


def step_sampled(site: str = "step") -> bool:
    """One sampling decision per step for one record stream (``site``);
    the caller applies it to every per-step record it emits (span +
    host_overhead) so a sampled step is complete rather than a random
    subset of its records. Each site advances its own counter, so
    interleaved streams are each sampled at the configured rate.
    itertools.count is a single C-level increment — thread-safe and
    nearly free once created."""
    if _sample_every == 1:
        return True
    c = _sample_counters.get(site)
    if c is None:
        with _sample_lock:
            c = _sample_counters.setdefault(site, itertools.count())
    return next(c) % _sample_every == 0


def step_span(name: str = "step", **attrs):
    """Latency span for one runtime step; no-op unless recording (and, under
    TT_OBS_SAMPLE, on non-sampled steps)."""
    if not events.enabled():
        return _NULL
    if not step_sampled(name):
        return _NULL
    return events.span(name, **attrs)


_env_rate = os.environ.get("TT_OBS_SAMPLE")
if _env_rate:
    try:
        set_sample_rate(float(_env_rate))
    except ValueError:
        import warnings

        warnings.warn(f"ignoring invalid TT_OBS_SAMPLE={_env_rate!r} "
                      f"(expected a rate in (0, 1])")


def fusion_scope(name: str):
    """Trace-time name scope: HLO produced under it carries ``name`` in its
    metadata, mapping device-profile rows back to trace symbols."""
    try:
        import jax

        return jax.named_scope(name)
    except Exception:
        return contextlib.nullcontext()


def annotate_call(name: str):
    """Host-side profiler annotation for one dispatch (recording only)."""
    if not events.enabled():
        return _NULL
    try:
        import jax

        return jax.profiler.TraceAnnotation(name)
    except Exception:
        return contextlib.nullcontext()


class _Phase:
    """A bus span and a profiler annotation entered and left together."""

    __slots__ = ("_span", "_ann")

    def __init__(self, name: str, attrs: dict):
        self._span = events.Span(name, attrs)
        self._ann = annotate_call(name)

    def __enter__(self) -> events.Span:
        self._ann.__enter__()
        return self._span.__enter__()

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._span.__exit__(exc_type, exc, tb)
        self._ann.__exit__(exc_type, exc, tb)
        return False


def phase(name: str, **attrs):
    """One named interval on both clocks (recording only): a bus span with
    ``attrs`` and a ``jax.profiler.TraceAnnotation`` called ``name`` round the
    same statements. The annotation is inert while no profiler session runs.
    With the bus off it returns the shared null context; call sites that
    compute attributes guard the whole call with the ``enabled()`` they
    already read, so a disabled bus computes none."""
    if not events.enabled():
        return _NULL
    return _Phase(name, attrs)
