"""Reduction of a profiler trace to the numbers the benchmark reports.

The JAX profiler writes an ``.xplane.pb``; :func:`load` reads it with nothing
but JAX (``jax.profiler.ProfileData``) into plain tuples, and :func:`load_json`
reads the same structure from a JSON file (the recorded fixture the tests
check this arithmetic on). Everything below works on that plain structure:

* a *device plane* is one chip (``/device:TPU:<n>``). Its ``XLA Ops`` line
  holds one event per executed HLO instruction, *named by the instruction's
  whole HLO text* (``%fusion.20 = (f32[..]{..}, ..) fusion(..), kind=kLoop``;
  :func:`parse_hlo` takes it apart); container instructions (``while``,
  ``call``, ``conditional``) enclose the events of their bodies on the same
  line, so durations nest and may never simply be added up;
* *busy* time is the union of the op intervals, *idle* share is one minus busy
  over the traced window, per device;
* an op's *self* time is its duration minus the part its enclosed events
  cover: self times add up to the busy time and are what rankings and
  per-class sums use;
* a *collective* is an op whose opcode is in :data:`COLLECTIVES`. A
  synchronous one is an event of ``XLA Ops``. An asynchronous one is in flight
  from its ``<op>-start`` to the end of its ``<op>-done``: the
  ``Async XLA Ops`` line holds that whole interval as one event named by the
  start, and where only the pair of short ops on ``XLA Ops`` is there they are
  matched by number. The *exposed* part of collective time is the part in which
  no other op runs on the same device;
* the ``XLA Modules`` line holds one event per executed program
  (``jit_tt_train_step(<id>)``, ``jit_xla_fusion_4(<id>)``).

Times are nanoseconds on the profiler's clock throughout.
"""
from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute", "all-to-all",
               "collective-broadcast", "ragged-all-to-all")
# the window the harness traced, written into the host plane by run.py
WINDOW_SPAN = "bench:window"


@dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns
    dur: float    # ns
    stats: dict = field(default_factory=dict, compare=False)

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Line:
    name: str
    events: list


@dataclass
class Plane:
    name: str
    lines: list

    def line(self, name: str) -> list:
        for ln in self.lines:
            if ln.name == name:
                return ln.events
        return []


# -- reading ------------------------------------------------------------------

def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler.start_trace`` directory."""
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> list:
    """Planes of an ``.xplane.pb`` as plain :class:`Plane` objects."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = [Event(e.name, float(e.start_ns), float(e.duration_ns), dict(e.stats))
                      for e in line.events]
            lines.append(Line(line.name, events))
        planes.append(Plane(plane.name, lines))
    return planes


def load_json(path: str) -> list:
    """The same structure from JSON:
    ``{"planes": [{"name", "lines": [{"name", "events": [[name, start, dur, stats?]]}]}]}``."""
    with open(path) as f:
        doc = json.load(f)
    return [Plane(p["name"], [Line(ln["name"], [Event(e[0], float(e[1]), float(e[2]),
                                                      e[3] if len(e) > 3 else {})
                                                for e in ln["events"]])
                              for ln in p["lines"]])
            for p in doc["planes"]]


def device_planes(planes: list, *, host_ops_as_device: bool = False) -> list:
    """The chips' planes, ordered by device number. ``host_ops_as_device`` is
    for the CPU rehearsal only: the CPU backend runs HLO ops on host threads,
    and their events (the ones with an ``hlo_op`` stat) stand in for a device
    so that the rest of the reduction has something to work on."""
    out = sorted((p for p in planes if DEVICE_PLANE.match(p.name)),
                 key=lambda p: int(DEVICE_PLANE.match(p.name).group(1)))
    if out or not host_ops_as_device:
        return out
    ops = [e for p in planes if p.name == "/host:CPU" for ln in p.lines for e in ln.events
           if "hlo_op" in e.stats]
    return [Plane("/device:CPU:0", [Line(OPS_LINE, ops)])] if ops else []


# -- interval arithmetic --------------------------------------------------------

def union(intervals) -> list:
    """Merged, sorted ``(start, end)`` pairs covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def subtract(intervals, holes) -> list:
    """The parts of merged ``intervals`` that no merged ``holes`` interval covers."""
    out = []
    holes = list(holes)
    j = 0
    for s, e in intervals:
        cur = s
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < e:
            hs, he = holes[k]
            if hs > cur:
                out.append((cur, hs))
            cur = max(cur, he)
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events: list) -> list:
    """``(event, self_ns)`` for every event of one line: duration minus what
    the events nested inside it cover."""
    order = sorted(events, key=lambda e: (e.start, -e.dur))
    selfs = [e.dur for e in order]
    stack = []  # indexes into order of the open enclosing events
    for i, e in enumerate(order):
        while stack and order[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            parent = order[stack[-1]]
            selfs[stack[-1]] -= max(0.0, min(e.end, parent.end) - e.start)
        stack.append(i)
    return [(e, max(0.0, s)) for e, s in zip(order, selfs)]


# -- the reduction --------------------------------------------------------------

def parse_hlo(name: str) -> tuple:
    """``(instruction, opcode, result)`` of an op event's name. On a TPU the
    name is the instruction's HLO text, ``%copy.9 = bf16[8,128]{1,0:T(8,128)}
    copy(bf16[8,128]{1,0} %x)``; elsewhere it is just ``dot_general.1``, and
    the opcode is then what stands before the number. ``result`` is the result
    type without layouts, ``bf16[8,128]`` or ``(f32[4,2048], bf16[4,2048,1024])``."""
    head = re.match(r"^%?(\S+) = ", name)
    if not head:
        return name, re.sub(r"[._]\d+$", "", name), ""
    rest = name[head.end():]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        result, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        result, _, rest = rest.partition(" ")
    op = re.match(r"^([\w-]+)\(", rest)
    # the result type without layouts and without the /*index=5*/ marks of long tuples
    result = re.sub(r"/\*.*?\*/", "", re.sub(r"\{[^{}]*\}", "", result))
    return head.group(1), op.group(1) if op else "", result


def collective_kind(name: str):
    """``(kind, phase)`` where the op is a collective, phase ``""``, ``"-start"``
    or ``"-done"``; otherwise ``None``."""
    opcode = parse_hlo(name)[1]
    for kind in COLLECTIVES:
        if opcode == kind:
            return kind, ""
        if opcode in (kind + "-start", kind + "-done"):
            return kind, opcode[len(kind):]
    return None


def is_collective(name: str) -> bool:
    return collective_kind(name) is not None


def collective_intervals(ops: list, async_ops: list = ()) -> list:
    """In-flight intervals of the collectives on one device, from its
    ``XLA Ops`` events and, where the trace has them, its ``Async XLA Ops``."""
    out, open_starts = [], {}
    for e in sorted(ops, key=lambda e: e.start):
        found = collective_kind(e.name)
        if not found:
            continue
        kind, phase = found
        number = re.search(r"(\.\d+)?$", parse_hlo(e.name)[0]).group(0)
        if phase == "-start":
            open_starts.setdefault((kind, number), []).append(e)
        elif phase == "-done":
            starts = open_starts.get((kind, number))
            begin = starts.pop(0).start if starts else e.start
            out.append((begin, e.end))
        else:
            out.append((e.start, e.end))
    # a start whose done fell outside the trace still occupied the link
    for starts in open_starts.values():
        out += [(s.start, s.end) for s in starts]
    out += [(e.start, e.end) for e in async_ops
            if (collective_kind(e.name) or ("", ""))[1] == "-start"]
    return out


def op_label(name: str) -> str:
    """A short name for a kind of op: opcode, the fusion's kind where it has
    one, and the result type — ``fusion kLoop (f32[50304,1024] x3)``."""
    instr, opcode, result = parse_hlo(name)
    if not result:
        return opcode or instr
    kind = re.search(r"\bkind=(k\w+)", name)
    parts = [p.strip() for p in result.strip("()").split(", ")] if result.startswith("(") else [result]
    if len(parts) > 1 and len(set(parts)) == 1:
        result = f"({parts[0]} x{len(parts)})"
    return " ".join(x for x in (opcode, kind.group(1) if kind else "", result) if x)[:120]


def strip_run_id(name: str) -> str:
    """``jit_serve_decode(1234567)`` -> ``jit_serve_decode``."""
    return re.sub(r"\(\d+\)$", "", name)


@dataclass
class DeviceReduction:
    plane: str
    window: tuple            # (start, end) ns
    busy_ns: float
    gaps: list               # [(start, end)] idle intervals inside the window
    op_self_ns: dict         # op name -> summed self time
    op_count: dict           # op name -> events
    collective_ns: float     # union of collective in-flight intervals
    collective_exposed_ns: float
    modules: dict            # program name -> (runs, summed ns)
    ops: list = field(default_factory=list, repr=False)  # [(Event, self_ns)] clipped to the window

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns if self.window_ns > 0 else 0.0


def traced_window(planes: list, devices: list) -> tuple:
    """The harness's ``bench:window`` span where the host plane has one,
    otherwise from the first device op's start to the last one's end."""
    spans = [e for p in planes if p.name == "/host:CPU" for ln in p.lines for e in ln.events
             if e.name == WINDOW_SPAN]
    if spans:
        return spans[0].start, spans[0].end
    evs = [e for p in devices for e in p.line(OPS_LINE)]
    if not evs:
        return 0.0, 0.0
    return min(e.start for e in evs), max(e.end for e in evs)


def reduce_device(plane: Plane, window: tuple) -> DeviceReduction:
    lo, hi = window
    ops = [e for e in plane.line(OPS_LINE) if e.end > lo and e.start < hi]
    busy = clip(union((e.start, e.end) for e in ops), lo, hi)
    gaps = subtract([(lo, hi)], busy) if hi > lo else []
    op_self, op_count, selfs = {}, {}, []
    for e, s in self_times(ops):
        # an op that straddles the window's edge counts with its part inside
        inside = max(0.0, min(e.end, hi) - max(e.start, lo))
        s = min(s, inside)
        selfs.append((e, s))
        op_self[e.name] = op_self.get(e.name, 0.0) + s
        op_count[e.name] = op_count.get(e.name, 0) + 1
    in_flight = [e for e in plane.line(ASYNC_LINE) if e.end > lo and e.start < hi]
    coll = clip(union(collective_intervals(ops, in_flight)), lo, hi)
    compute = clip(union((e.start, e.end) for e in ops if not is_collective(e.name)), lo, hi)
    exposed = subtract(coll, compute)
    modules = {}
    for e in plane.line(MODULES_LINE):
        if e.end > lo and e.start < hi:
            n, t = modules.get(strip_run_id(e.name), (0, 0.0))
            modules[strip_run_id(e.name)] = (n + 1, t + min(e.end, hi) - max(e.start, lo))
    return DeviceReduction(plane.name, window, total(busy), gaps, op_self, op_count,
                           total(coll), total(exposed), modules, selfs)


@dataclass
class Reduction:
    devices: list            # [DeviceReduction]
    host: list = field(default_factory=list, repr=False)  # host-thread events inside the window
    host_events: dict = field(default_factory=dict)       # their name -> (count, summed ns)

    @property
    def window_s(self) -> float:
        return self.devices[0].window_ns / 1e9 if self.devices else 0.0

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips used."""
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9 if self.devices else 0.0

    @property
    def worst_idle_share(self) -> float:
        return max(d.idle_share for d in self.devices)

    def op_seconds(self, pick) -> float:
        """Self time, averaged over the chips, of the ops ``pick(event)`` keeps."""
        if not self.devices:
            return 0.0
        return sum(s for d in self.devices for e, s in d.ops if pick(e)) / len(self.devices) / 1e9

    def op_events(self, pick) -> list:
        """``(event, self_ns)`` of the first chip's ops that ``pick`` keeps."""
        return [(e, s) for e, s in self.devices[0].ops if pick(e)] if self.devices else []

    def host_count(self, name: str) -> int:
        """Host events called ``name`` that began inside the window: how often
        a ``TraceAnnotation`` of the program (``serve_decode``) or a dispatch
        (``PjitFunction(<fn>)``) happened."""
        return self.host_events.get(name, (0, 0.0))[0]

    def module_seconds(self, pattern: str) -> float:
        if not self.devices:
            return 0.0
        rx = re.compile(pattern)
        return sum(t for name, (_, t) in self.devices[0].modules.items() if rx.search(name)) / 1e9

    def top_ops(self, n: int = 10, label=None) -> list:
        """``[label, seconds]`` of the kinds of op with most self time, averaged
        over the chips. Ops are grouped by ``label(event)``, by default
        :func:`op_label`: a model's layers run the same fusion under a
        different number each, and the ranking is of the work, not the number."""
        label = label or (lambda e: op_label(e.name))
        acc, calls = {}, {}
        for i, d in enumerate(self.devices):
            for e, ns in d.ops:
                key = label(e)
                acc[key] = acc.get(key, 0.0) + ns
                calls.setdefault(key, [0] * len(self.devices))[i] += 1
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[f"{key} x{max(calls[key])}", ns / len(self.devices) / 1e9] for key, ns in ranked]

    def longest_gaps(self, n: int = 5) -> list:
        """``[what the host was doing, seconds]`` for the longest idle gaps of
        the chip that idled most: the innermost host event open at the gap's
        middle — a span of the harness (``bench:step``), an annotation of the
        program (``serve_decode``) or one of the runtime's own
        (``PjitFunction(..)``, ``DevicePut``) — or ``no host event``."""
        if not self.devices:
            return []
        worst = max(self.devices, key=lambda d: d.idle_share)
        out = []
        for s, e in sorted(worst.gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = (s + e) / 2
            open_now = [ev for ev in self.host if ev.start <= mid < ev.end]
            label = min(open_now, key=lambda ev: ev.dur).name[:80] if open_now else "no host event"
            out.append([label, (e - s) / 1e9])
        return out


def host_thread_events(planes: list, window: tuple) -> list:
    """Events of the process's own threads (the host plane's lines that carry
    ``TraceAnnotation``s and dispatches; the runtime's worker pools are left
    out) that overlap the window, the window's own span excepted."""
    out = []
    for p in planes:
        if p.name != "/host:CPU":
            continue
        for ln in p.lines:
            if ln.name.startswith(("python", "main")):
                out += [e for e in ln.events
                        if e.end > window[0] and e.start < window[1] and e.name != WINDOW_SPAN]
    return out


def reduce_trace(planes: list, *, host_ops_as_device: bool = False) -> Reduction:
    devices = device_planes(planes, host_ops_as_device=host_ops_as_device)
    window = traced_window(planes, devices)
    host = host_thread_events(planes, window)
    counts = {}
    for e in host:
        if e.start >= window[0]:
            n, t = counts.get(e.name, (0, 0.0))
            counts[e.name] = (n + 1, t + e.dur)
    return Reduction([reduce_device(p, window) for p in devices], host, counts)
