"""What every driver needs around the system under test: the device it runs
on, a count of compilations, the harness's own spans, the profiler, and the
proofs (copied from ``chip_smoke.py``) that the chip's kernels ran.
"""
from __future__ import annotations

import contextlib
import operator
import os
import shutil
import time
from collections import Counter
from dataclasses import dataclass, field
from typing import Optional

from . import xplane


# a --trace 1 run profiles this part of its window: it starts TRACE_AT of the way in and
# lasts TRACE_SECONDS (traces are large, and tracing slows the host)
TRACE_AT = 0.4
TRACE_SECONDS = 5.0


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


# -- the device -------------------------------------------------------------------

def device_info(devices) -> dict:
    first = devices[0]
    return {"platform": first.platform, "kind": first.device_kind, "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks)) if peaks else 0


def memory_in_use_bytes(devices) -> list:
    return [int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in devices]


# -- compilations -------------------------------------------------------------------

class CompileWatch:
    """Counts, through ``jax.monitoring``, every executable JAX builds or
    loads (``builds``), how many of those the persistent cache served
    (``cache_hits``) and how many it had to compile (``cache_misses``). It sees
    every ``jax.jit`` in the process, the program's and the benchmark's, and
    needs no help from the program. Executables the program's own artifact
    store deserialises do not pass through here; they compile nothing."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring as monitoring

        self.builds = self.cache_hits = self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == self.BUILD:
            self.builds += 1

    def _on_event(self, event: str, **_) -> None:
        if event == self.HIT:
            self.cache_hits += 1
        elif event == self.MISS:
            self.cache_misses += 1

    def snapshot(self) -> dict:
        # with no persistent cache every build is a compilation
        compiled = self.cache_misses if (self.cache_hits or self.cache_misses) else self.builds
        return {"builds": self.builds, "cache_hits": self.cache_hits, "compiled": compiled}

    @staticmethod
    def delta(after: dict, before: dict) -> dict:
        return {k: after[k] - before[k] for k in after}


# -- the harness's own spans ----------------------------------------------------------

class Spans:
    """Named host spans around the calls into the system, on ``perf_counter``.
    While a trace is being taken each span is also a
    ``jax.profiler.TraceAnnotation`` (``bench:<name>``), which puts it on the
    profiler's clock beside the device's operations."""

    def __init__(self):
        self.durations: dict = {}
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation("bench:" + name)
        t0 = time.perf_counter()
        with ann:
            yield
        self.durations.setdefault(name, []).append(time.perf_counter() - t0)


# -- the profiler -----------------------------------------------------------------------

class Profiler:
    """One traced window. ``start`` and ``stop`` bracket it; between them the
    host plane carries a ``bench:window`` span that the reduction clips to."""

    def __init__(self, out_dir: str, spans: Spans):
        self.dir, self.spans = out_dir, spans
        self._window = None
        self.active = False

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        # no Python tracer: it hooks every call of the host loop under test
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.spans.annotate = True
        self._window = jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN)
        self._window.__enter__()
        self.active = True

    def stop(self) -> None:
        import jax

        self._window.__exit__(None, None, None)
        self.spans.annotate = False
        jax.profiler.stop_trace()
        self.active = False

    def reduce(self, *, host_ops_as_device: bool = False) -> xplane.Reduction:
        planes = xplane.load(xplane.find_xplane(self.dir))
        return xplane.reduce_trace(planes, host_ops_as_device=host_ops_as_device)


# -- proofs that the chip's kernels ran (copied from chip_smoke.py) -------------------------

def pallas_claims(trace) -> Counter:
    """Symbols of an executed trace that the Pallas executor runs, by id,
    looking inside the XLA fusion regions. A composite the executor did not
    claim is not here at all: it was decomposed into its prims."""
    from thunder_tpu.executors import pallasex, xlaex

    out: Counter = Counter()

    def walk(bsyms):
        for b in bsyms:
            if b.sym.executor is xlaex.ex:
                walk(b.subsymbols)
            elif b.sym.executor is pallasex.ex or (
                    b.impl is not None and b.impl is pallasex.ex.get_impl(b.sym.id)):
                out[b.sym.id] += 1

    walk(trace.bound_symbols)
    return out


def mosaic_calls(compiled) -> int:
    """Mosaic (compiled Pallas) kernels in a jax executable: the
    tpu_custom_call custom-calls of its HLO. Also answers for an executable
    the artifact store served, which no trace in this process describes."""
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def wanted_claims(cell, programs) -> dict:
    """``{program: {symbols: count}}`` for a driver's ``programs``, from the
    builder's ``kernel_claims(config)``: what this model needs Pallas to have
    claimed in each compiled program. A builder without one, or one that leaves
    out a program its cell's driver judges, is an error and not a run without
    the check."""
    kernel_claims = getattr(cell.builder, "kernel_claims", None)
    if kernel_claims is None:
        raise AttributeError(f"builder {cell.config['builder']!r} exports no kernel_claims(config): "
                             f"the {cell.traffic['driver']} driver needs it for {list(programs)}")
    claims = kernel_claims(cell.config)
    missing = [p for p in programs if p not in claims]
    if missing:
        raise KeyError(f"kernel_claims of builder {cell.config['builder']!r} names no {missing}; "
                       f"it names {sorted(claims)}")
    return {p: dict(claims[p]) for p in programs}


def unheld_claims(want: dict, claims: dict, rule: str, compared: dict) -> list:
    """Every stated symbol of every program of ``want`` that ``claims`` has,
    judged by ``rule`` against its count and recorded in ``compared``. A key of
    several symbol ids joined by ``+`` has their claims summed. Returns the
    ``(program, symbols, claimed, count)`` that do not hold."""
    out = []
    for program, stated in want.items():
        if program not in claims:
            continue
        for symbols, count in stated.items():
            got = sum(claims[program].get(s, 0) for s in symbols.split("+"))
            if not held(compared, f"{program}.{symbols}", got, rule, int(count)):
                out.append((program, symbols, got, count))
    return out


def steady_state_faults(counters: dict) -> dict:
    """Counters of the program that must stay at zero once every program is compiled."""
    return {k: v for k, v in counters.items()
            if v and (k.startswith("recompile.") or k == "compile.prewarm_fallback"
                      or k == "aot.save_failed")}


# -- each number that decides `correct`, beside its limit -----------------------------------------

RULES = {"<": operator.lt, "<=": operator.le, "==": operator.eq, ">=": operator.ge}


def held(compared: dict, name: str, value, rule: str, limit) -> bool:
    """Records one compared number beside its limit (``run.py`` prints them)
    and says whether it holds; a NaN holds nothing."""
    compared[name] = (value, rule, limit)
    return bool(RULES[rule](value, limit))


# -- what a driver hands back -----------------------------------------------------------------

@dataclass
class Run:
    """One measured run, as the per-layer readers see it."""
    cell: object                      # manifest.Cell
    device_kind: str
    chips: int
    window_s: float                   # the measured window's length
    attempted: int
    failed: int
    end_to_end: dict                  # metric name -> value, taken by the driver itself
    spans: dict = field(default_factory=dict)        # harness span name -> [seconds]
    records: list = field(default_factory=list)      # loadgen.Record per request (serve)
    stats: dict = field(default_factory=dict)        # what the system reports of itself
    counters: dict = field(default_factory=dict)     # the program's bus counters, window only
    bus: list = field(default_factory=list)          # the program's bus records, window only
    compiles: dict = field(default_factory=dict)     # CompileWatch delta over the window
    trace: Optional[xplane.Reduction] = None
    traced: dict = field(default_factory=dict)       # what the harness counted while tracing
    notes: list = field(default_factory=list)        # why the run is not correct, if it is not
    compared: dict = field(default_factory=dict)     # name -> (number, rule, limit): harness.held

    @property
    def correct(self) -> bool:
        return not self.notes
