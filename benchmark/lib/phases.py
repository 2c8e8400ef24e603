"""The chip's idle time put down to the phases of the serving loop.

``ServingEngine`` runs its loop under ``engine:*`` phases, each a span on the
program's bus and an annotation on the profiler's clock: ``engine:iteration``
round one pass of the loop, and inside it ``admit``, ``prefill``, ``upload``,
``dispatch``, ``fetch`` and ``commit``; ``engine:wait`` while nothing is
outstanding. Every idle gap of the traced window is split here **by overlap**:

* what lies inside a run of a device program (an event of the ``XLA Modules``
  line) is ``in_program``: bubbles between the ops of one executable, which no
  host code causes, and which would otherwise be booked on ``fetch``, where
  the host sits while the device works;
* the rest goes to the innermost ``engine:*`` host event that overlaps it;
* what lies under ``engine:iteration`` alone, or under nothing, is
  ``unattributed``.

The eight shares and the time under ``engine:wait`` add up to the chip's idle
time. A program without the phases (an earlier commit) has no
``engine:iteration`` event: the readers then return ``None`` and the line
leaves their metrics out.
"""
from __future__ import annotations

import os

from . import loadgen, xplane
from .harness import say

PREFIX = "engine:"
ITERATION = PREFIX + "iteration"
PHASES = ("admit", "prefill", "upload", "dispatch", "fetch", "commit")
SHARES = PHASES + ("unattributed", "in_program")
# observability/events.py keeps the newest 50,000 records: a window that fills the ring
# has lost its oldest serve_decode spans
BUS_RING = 50_000


def innermost(events: list) -> dict:
    """``name -> merged (start, end) intervals`` in which an event of that name
    was the innermost one open. The events are one thread's, so they nest."""
    order = sorted(events, key=lambda e: (e.start, -e.dur))
    inner = [[] for _ in order]  # what the events nested directly inside each one cover
    stack = []
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            inner[stack[-1]].append((e.start, min(e.end, order[stack[-1]].end)))
        stack.append(i)
    own = {}
    for e, holes in zip(order, inner):
        own.setdefault(e.name, []).extend(xplane.subtract([(e.start, e.end)], xplane.union(holes)))
    return {name: xplane.union(parts) for name, parts in own.items()}


def covered(intervals: list, by: list) -> float:
    """Length of the part of merged ``intervals`` that merged ``by`` covers."""
    return xplane.total(intervals) - xplane.total(xplane.subtract(intervals, by))


def split_idle(gaps: list, modules: list, host: list) -> dict:
    """Nanoseconds of the idle ``gaps`` of one chip by what they are put down
    to: each of :data:`SHARES`, and ``wait``. ``modules`` are the events of the
    chip's ``XLA Modules`` line, ``host`` the host threads' events."""
    gaps = xplane.union(gaps)
    outside = xplane.subtract(gaps, xplane.union((e.start, e.end) for e in modules))
    own = innermost([e for e in host if e.name.startswith(PREFIX)])
    out = {name: covered(outside, own.get(PREFIX + name, [])) for name in PHASES + ("wait",)}
    out["unattributed"] = xplane.total(outside) - sum(out.values())
    out["in_program"] = xplane.total(gaps) - xplane.total(outside)
    return out


def traced_planes(run) -> list:
    """The planes of the run's trace, read again: ``Reduction`` keeps only the
    sums of the ``XLA Modules`` line, and the split needs its intervals."""
    trace_dir = os.path.join(run.cell.root, ".bench_out", run.cell.name, "trace")
    return xplane.load(xplane.find_xplane(trace_dir))


def iterations(run) -> int:
    """Passes of the engine's loop that began inside the traced window (the
    loop opens ``engine:iteration`` only when work is outstanding)."""
    return run.trace.host_count(ITERATION) if run.trace is not None else 0


def idle_by_phase(run):
    """:func:`split_idle` of the cell's chip over the traced window, worked out
    once per run and logged; ``None`` where the trace holds no iteration."""
    n = iterations(run)
    if not n or not run.trace.devices:
        return None
    if "idle_by_phase" not in run.traced:
        chips = xplane.device_planes(traced_planes(run))
        modules = chips[0].line(xplane.MODULES_LINE) if chips else []
        chip = run.trace.devices[0]
        split = run.traced["idle_by_phase"] = split_idle(chip.gaps, modules, run.trace.host)
        idle_ns = chip.window_ns - chip.busy_ns
        say(f"idle by phase: {n} engine iterations in the traced window, "
            f"{idle_ns / n / 1e6:.3f} ms of idle chip an iteration ((window - busy) / iterations); "
            + ", ".join(f"{k} {split[k] / n / 1e6:.3f}" for k in SHARES)
            + f"; under engine:wait {split['wait'] / n / 1e6:.3f}")
        say(f"bus records at the window's end: {len(run.bus)}"
            + (" (the ring is full: the oldest fell out)" if len(run.bus) >= BUS_RING else ""))
    return run.traced["idle_by_phase"]


def idle_ms_per_iter(run, share: str):
    """Milliseconds of idle chip per engine iteration put down to ``share``;
    a share with nothing under it reads ``0.0``."""
    split = idle_by_phase(run)
    return None if split is None else split[share] / 1e6 / iterations(run)


def queue_wait_ms(run) -> list:
    """Submit-to-admitted of every request admitted in the window, as the
    program stamps it on its ``admitted`` trace event."""
    return [r["attrs"]["queued_ms"] for r in run.bus
            if r.get("kind") == "event" and r.get("name") == "trace"
            and r["attrs"].get("phase") == "admitted" and "queued_ms" in r["attrs"]]


def queue_wait_percentile_ms(run, q: float):
    xs = queue_wait_ms(run)
    return loadgen.percentile(xs, q) if xs else None
