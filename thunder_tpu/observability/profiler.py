"""Device-time capture and attribution: jax.profiler → per-region breakdown.

The host side of the pipeline is already legible (events.py spans); this
module makes the *device* side legible. ``profile_steps`` wraps
``jax.profiler.trace`` around N step calls, parses the captured
trace-event stream (the perfetto JSON export — stdlib-parseable, available
on CPU and TPU), and joins device durations back to trace symbols in two steps:

  * **the op map** (``op_scopes``). A device event is ``(module, instruction)``:
    the program it ran in and the HLO instruction it is. What a profile does
    *not* carry is where the instruction came from (XLA's fusions keep their
    numbers and an event is named by the instruction's HLO text; measured on
    the v5e, PR 27). The executable knows: ``Compiled.as_text()`` prints the
    optimised module with ``metadata={op_name="jit(f)/../xla_fusion_0/bwd/mlp/dot_general"}``
    on every instruction, those inside fused computations included. The path is
    made of the ``jax.named_scope``s the op was traced under: the fusion region
    (executors/xlaex.py), the step's phases (``tt_fwd_bwd``, ``tt_optimizer``),
    and the ``core.trace.named_scope`` path of the trace symbol (``attn/rope``,
    below ``bwd`` or ``recompute`` for what autodiff bound). Every executable
    the program holds is registered here, weakly, where it is installed; its
    text is parsed on the first request and kept. Nothing runs at compile time
    or in a step.
  * **the region registry**: every fusion region the executor passes form is
    registered as ``name → {bsym ids, flops, bytes}``; ``attribute`` puts an
    event on the finest registered region on its instruction's path, and on
    the region its module is named after (``jit_xla_fusion_N``) where the map
    does not know the instruction.

The result is a ``DeviceProfile``: per-region device time split into
compute / collective / transfer, model FLOPs/bytes per region (the
observability/flops.py cost model), arithmetic intensity, a roofline tag,
and measured MFU. ``emit()`` writes it onto the event bus so JSONL shards
carry it for ``tools/obs_summary.py perf``.
"""
from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re
import tempfile
import threading
import time
import weakref
from collections import Counter
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Optional

from . import events as _obs
from . import flops as _flops

# ---------------------------------------------------------------------------
# region registry: fusion-region name <-> trace symbols (+ cost annotations)
# ---------------------------------------------------------------------------

_REGISTRY_LOCK = threading.Lock()
_REGIONS: dict[str, dict] = {}


def register_region(name: str, *, bsym_ids: Iterable = (), executor: str = "",
                    flops: float = 0.0, bytes: int = 0, kind: str = "compute",
                    level: int = 0) -> None:
    """Register (or refresh) one fusion region / named program phase.

    ``level`` is the attribution granularity: 0 = fusion region (finest),
    1 = program phase (tt_fwd_bwd / tt_optimizer), 2 = whole program
    (tt_train_step). Where several registered names lie on the scope path
    of one device event's instruction, the smallest level wins — time lands
    on the finest region that claims it."""
    info = {
        "name": name,
        "bsym_ids": [str(b) for b in bsym_ids],
        "executor": executor,
        "flops": float(flops),
        "bytes": int(bytes),
        "kind": kind,
        "level": int(level),
    }
    with _REGISTRY_LOCK:
        _REGIONS[name] = info


def register_trace_regions(trace) -> int:
    """Walk an execution trace and register every fusion-executor region
    (any executor's — xla, pallas, ...) under its region name, with the
    flops/bytes cost of its subsymbols. Called by executors/passes.py after
    the fusion passes; returns the number of regions registered. A region
    that runs as an executable of its own (not inlined into an outer
    ``jax.jit``) is also one ``op_scopes`` reads."""
    import jax

    from ..compile_service.parallel_compile import _region_avals

    n = 0
    standalone = jax.core.trace_ctx.is_top_level()
    for bsym in getattr(trace, "bound_symbols", ()):
        ex = getattr(bsym.sym, "executor", None)
        if ex is None or not getattr(ex, "is_fusion_executor", lambda: False)():
            continue
        if not bsym.subsymbols:
            continue
        cost = _flops.fusion_cost(bsym)
        register_region(
            bsym.sym.name,
            bsym_ids=[s.sym.name for s in bsym.subsymbols],
            executor=getattr(ex, "name", ""),
            flops=cost["flops"],
            bytes=cost["bytes"],
            kind="compute",
        )
        if standalone and hasattr(bsym.impl, "_prewarmed"):
            register_executable(bsym.impl, _RegionCompiled(_region_avals(bsym)),
                                region=bsym.sym.name)
        n += 1
    return n


def regions() -> dict[str, dict]:
    with _REGISTRY_LOCK:
        return {k: dict(v) for k, v in _REGIONS.items()}


def region_info(name: str) -> Optional[dict]:
    with _REGISTRY_LOCK:
        info = _REGIONS.get(name)
        return dict(info) if info is not None else None


def resolve(name: str) -> list[str]:
    """Region name → the BoundSymbol ids it was formed from (round-trip of
    the jax.named_scope annotation; [] for unknown names)."""
    info = region_info(name)
    return list(info["bsym_ids"]) if info else []


def clear_regions() -> None:
    with _REGISTRY_LOCK:
        _REGIONS.clear()
        _EXECUTABLES.clear()


# ---------------------------------------------------------------------------
# the op map: (module, instruction) -> the scope path its trace symbols ran under
# ---------------------------------------------------------------------------

# the scope names that are a part of a model: `attn/kv_write` is `kv_write`, and
# `attn/rope` is `attn` (`rope` is a finer name, for a reader of the path)
PARTS = frozenset({
    "embed", "attn", "kv_write", "mlp", "head",                           # models/litgpt.py, serving/runner.py
    "mamba", "gmu", "window_attn", "full_attn", "cross_attn",             # models/sambay.py
    "mla_attn", "moe_router", "moe_experts", "shared_expert",             # models/latent_moe.py, moe.py
    "zero_experts", "dense_ffn",                                          # models/moe.py, shortcut_moe.py
})
PASSES = ("fwd", "bwd", "recompute", "optimizer")
UNSCOPED = "unscoped"
_REGION_SEG = re.compile(r"^(xla_fusion_\d+|tt_optimizer)$")
_WRAPPER_SEG = re.compile(r"^\w*\((.*)\)$")  # jit(f), pjit(f), transpose(jvp(f))
# segments JAX and the step put on a path that name no part of a model
_STRUCTURAL_SEG = re.compile(r"^(tt_fwd_bwd|tt_train_step|shard_map|pmap|while|body|cond|branch_\d+_fun|"
                             r"closed_call|custom_[jv][jv]p_call|core_call|remat)$")
_DOT_OPCODES = ("dot", "convolution")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_HLO_OPCODE = re.compile(r"([\w\-]+)\(")
_HLO_OPERAND = re.compile(r"%([\w.\-]+)")
_RUN_ID = re.compile(r"\(\d+\)$")  # jit_tt_train_step(1234567): one run of the module


def path_scope(op_name: str) -> tuple:
    """``(region, pass, part)`` of one ``op_name``. ``region`` is the last
    ``xla_fusion_<n>`` / ``tt_optimizer`` segment (``""`` without one); ``pass``
    is ``recompute`` or ``bwd`` where the path says so (also for what JAX's own
    ``checkpoint`` and transposition name), ``optimizer`` under ``tt_optimizer``,
    else ``fwd``; ``part`` is the innermost segment that is one of ``PARTS``,
    else the outermost scope name a model gave, else ``optimizer`` or ``unscoped``."""
    return _path_scope(op_name)[:3]


def _path_scope(op_name: str) -> tuple:
    """:func:`path_scope` and, fourth, the model's own scope names on the path
    (``attn/rope``: what a reader of the finer names wants; ``""`` without any)."""
    # where XLA merged two instructions it joins their paths with ";": the first is the one kept
    segs = op_name.split(";")[0].split("/")[:-1]  # the last is the primitive's name
    region, pass_name, part, named = "", "fwd", None, []
    for seg in segs:
        if _REGION_SEG.match(seg):
            region = seg
        if seg == "tt_optimizer":
            pass_name = "optimizer"
        elif seg in ("recompute", "rematted_computation", "checkpoint"):
            pass_name = "recompute"
        elif seg == "bwd" or seg.startswith("transpose("):
            pass_name = pass_name if pass_name == "recompute" else "bwd"
        elif not (_WRAPPER_SEG.match(seg) or _REGION_SEG.match(seg) or _STRUCTURAL_SEG.match(seg)):
            named.append(seg)
            if seg in PARTS:
                part = seg
    if part is None:
        part = named[0] if named else ("optimizer" if pass_name == "optimizer" else UNSCOPED)
    return region, pass_name, part, "/".join(named)


class OpMap(dict):
    """``{instruction: op_name}`` of one executable, from its optimised HLO text.
    The members of a fused computation are listed under their fusion, as
    ``<fusion>/<member>``; an instruction without ``op_name`` metadata maps to ``""``.
    ``region`` is the name the executable was registered under (a region served by
    the artifact store prints the publishing process's name in its paths),
    ``others`` the further executables that print the same module name."""

    def __init__(self, module: str, region: str = ""):
        super().__init__()
        self.module, self.region = module, region
        self.members: dict = {}  # fusion instruction -> [(member key, opcode)]
        self.users: dict = {}    # instruction without an op_name -> the instructions that read it
        self.others: list = []
        self._scopes: dict = {}

    def holding(self, instructions) -> "OpMap":
        """Of the executables of this module name, the one that holds most of
        ``instructions`` (this one where there is no other)."""
        if not self.others:
            return self
        names = list(instructions)
        return max([self, *self.others], key=lambda m: sum(n in m for n in names))

    def scope(self, instruction: str) -> tuple:
        """``(region, pass, part)`` of one instruction. A fusion goes to the scope
        that holds most of its ``dot`` / ``convolution`` members, else to its own
        ``op_name``'s (to most of its members' where that names no part); one whose
        members span more than one part reads as a pair, ``mlp+optimizer``, the
        part it goes to first. An instruction XLA gave no ``op_name`` at all (a
        copy it inserted, the ``-start`` / ``-done`` of a prefetch) goes where the
        first instruction that reads its result goes. An unknown instruction is
        ``("", "fwd", "unscoped")``."""
        return self._found(instruction)[:3]

    def finer(self, instruction: str) -> str:
        """The model's own scope names on the path of the scope the instruction goes
        to, ``attn/rope`` (``""`` for none): the finer names below a part."""
        return self._found(instruction)[3]

    def _found(self, instruction: str, hops: int = 4) -> tuple:
        found = self._scopes.get(instruction)
        if found is None:
            found = self._scope(instruction)
            if found[2] == UNSCOPED and self.get(instruction) == "" and hops:
                by_reader = (self._found(user, hops - 1) for user in self.users.get(instruction, ()))
                found = next((sc for sc in by_reader if sc[2] != UNSCOPED), found)
            self._scopes[instruction] = found
        return found

    def _scope(self, instruction: str) -> tuple:
        own = _path_scope(self.get(instruction, ""))
        members = [(_path_scope(self[key]), opcode) for key, opcode in self.members.get(instruction, ())
                   if self[key]]
        if members:
            dots = Counter(sc for sc, opcode in members if opcode in _DOT_OPCODES)
            spread = Counter(sc for sc, _ in members if sc[2] != UNSCOPED)
            if dots:
                own = dots.most_common(1)[0][0]
            elif own[2] == UNSCOPED and spread:
                own = spread.most_common(1)[0][0]
            other = Counter()
            for sc, n in spread.items():
                if sc[2] != own[2]:
                    other[sc[2]] += n
            if other:
                own = own[:2] + (f"{own[2]}+{other.most_common(1)[0][0]}", own[3])
        return (self.region or own[0],) + own[1:]


def parse_hlo_text(text: str, region: str = "") -> OpMap:
    """The :class:`OpMap` of one ``Compiled.as_text()``."""
    head = re.match(r"HloModule ([\w.\-]+)", text)
    ops = OpMap(head.group(1) if head else "", region)
    computations: dict = {}  # computation -> [(instruction, opcode, op_name)]
    callers: list = []       # (instruction, called computation) of fusions
    current = None
    for line in text.splitlines():
        if current is None:
            m = _HLO_COMPUTATION.match(line)
            if m:
                current = computations.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m:
            continue
        rest = line[m.end():]
        if rest.startswith("("):  # a tuple type: skip to its closing bracket
            depth = 0
            for i, ch in enumerate(rest):
                depth += ch == "("
                depth -= ch == ")"
                if depth == 0:
                    break
            rest = rest[i + 1:]
        else:
            rest = rest.partition(" ")[2]
        opcode = _HLO_OPCODE.match(rest.lstrip())
        opcode = opcode.group(1) if opcode else ""
        name = _HLO_OP_NAME.search(line)
        current.append((m.group(1), opcode, name.group(1) if name else ""))
        for operand in _HLO_OPERAND.findall(rest):
            if operand in ops.users:
                ops.users[operand].append(m.group(1))
        if not name and opcode not in ("parameter", "constant"):
            ops.users[m.group(1)] = []
        if opcode == "fusion":
            called = _HLO_CALLS.search(line)
            if called:
                callers.append((m.group(1), called.group(1)))
    fused = {called for _, called in callers}
    for comp, instructions in computations.items():
        if comp not in fused:
            for instruction, _, op_name in instructions:
                ops[instruction] = op_name
    for fusion, called in callers:
        for instruction, opcode, op_name in computations.get(called, ()):
            key = f"{fusion}/{instruction}"
            ops[key] = op_name
            ops.members.setdefault(fusion, []).append((key, opcode))
    return ops


class _RegionCompiled:
    """How ``op_scopes`` gets a fusion region's executable: the one the compile
    service installed (compiled ahead or served by the store), else the region's
    ``jax.jit`` lowered again for its inputs, which JAX's caches serve where the
    first call compiled it."""

    def __init__(self, avals):
        self.avals = avals
        self.lowered_again = None

    def __call__(self, impl):
        if impl._prewarmed is not None:
            return impl._prewarmed
        if self.lowered_again is None and self.avals is not None:
            self.lowered_again = impl.jitted.lower(*self.avals).compile()
        return self.lowered_again


@dataclass
class _Held:
    holder: Any              # weakref to what holds the executable
    compiled_of: Callable    # holder -> Compiled or None
    region: str
    compiled: Any = None     # the Compiled the map was parsed from
    ops: Optional[OpMap] = None


_EXECUTABLES: dict = {}  # id of the _Held -> _Held, dropped as its holder dies
# what the last ``op_scopes()`` cost: seconds to get the executables (a road that
# compiles shows here), seconds to print and parse their text, and their count
op_scopes_cost = {"executables": 0, "same_name": 0, "get_s": 0.0, "parse_s": 0.0}


def register_executable(holder, compiled_of: Callable, *, region: str = "") -> None:
    """Make the executable ``compiled_of(holder)`` one of those ``op_scopes``
    reads, for as long as ``holder`` lives. Costs a weak reference; the
    executable is asked for, printed and parsed on the first request only."""
    held = _Held(None, compiled_of, region)
    # no lock in the callback: it may run wherever the collector does
    held.holder = weakref.ref(holder, lambda _, key=id(held): _EXECUTABLES.pop(key, None))
    _EXECUTABLES[id(held)] = held


def op_scopes() -> dict:
    """``{module name: OpMap}`` for every executable the process holds: each
    fusion region that runs as a program of its own and each ``TrainStep``.
    The module name is the one the executable's text gives (``jit_tt_train_step``:
    what the profiler's ``XLA Modules`` line and an event's ``hlo_module`` carry).
    Two executables that print the same name, as two served by the artifact store
    may, are both kept: ``OpMap.others`` / ``OpMap.holding``."""
    out: dict = {}
    cost = {"executables": 0, "same_name": 0, "get_s": 0.0, "parse_s": 0.0}
    for h in list(_EXECUTABLES.values()):
        holder = h.holder()
        if holder is None:
            continue
        t0 = time.perf_counter()
        try:
            compiled = h.compiled_of(holder)
        except Exception as e:  # a map is missing; the profile is still read
            _obs.event("profile_error", stage="op_scopes", region=h.region, error=str(e)[:200])
            compiled = None
        t1 = time.perf_counter()
        cost["get_s"] += t1 - t0
        if compiled is None:
            continue
        if h.ops is None or h.compiled is not compiled:
            h.compiled, h.ops = compiled, parse_hlo_text(compiled.as_text(), h.region)
            cost["parse_s"] += time.perf_counter() - t1
        cost["executables"] += 1
        h.ops.others = []  # of this request: a map is kept, who shares its name may change
        first = out.setdefault(h.ops.module, h.ops)
        if first is not h.ops:
            cost["same_name"] += 1
            first.others.append(h.ops)
    op_scopes_cost.update(cost)
    return out


def scope_of(module: str, instruction: str) -> tuple:
    """``(region, pass, part)`` of one device event: ``module`` as the ``XLA
    Modules`` line or ``hlo_module`` names it (a run id in brackets is dropped),
    ``instruction`` as ``hlo_op`` or the event's HLO text names it.
    ``("", "fwd", "unscoped")`` where no executable holds it."""
    ops = op_scopes().get(_RUN_ID.sub("", module))
    if ops is None:
        return "", "fwd", UNSCOPED
    return ops.holding([instruction]).scope(instruction)


# ---------------------------------------------------------------------------
# trace-event capture + parsing
# ---------------------------------------------------------------------------

_COLLECTIVE_PAT = re.compile(
    r"all-reduce|all_reduce|all-gather|all_gather|reduce-scatter|reduce_scatter|"
    r"collective|all-to-all|psum|ppermute|permute", re.I)
_TRANSFER_PAT = re.compile(
    r"memcpy|copy-start|copy-done|infeed|outfeed|transfer|device_put|"
    r"h2d|d2h|dma|send|recv", re.I)


def _load_perfetto(log_dir: str) -> list[dict]:
    """Newest perfetto/trace JSON (possibly .gz) under a profiler log dir."""
    paths = sorted(
        glob.glob(os.path.join(log_dir, "**", "*.json.gz"), recursive=True)
        + glob.glob(os.path.join(log_dir, "**", "*.trace.json"), recursive=True),
        key=os.path.getmtime)
    # prefer the perfetto export; fall back to any trace json
    pref = [p for p in paths if "perfetto" in os.path.basename(p)] or paths
    if not pref:
        return []
    path = pref[-1]
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        data = json.load(f)
    evs = data if isinstance(data, list) else data.get("traceEvents", [])
    return [e for e in evs if isinstance(e, dict)]


@dataclass
class RegionTime:
    """Attributed device time for one region/bucket."""

    name: str
    us: float = 0.0
    count: int = 0
    category: str = "compute"  # compute | collective | transfer
    cat_us: dict = field(default_factory=dict)  # per-category accumulation
    bsym_ids: list = field(default_factory=list)
    flops: float = 0.0
    bytes: int = 0
    intensity: Optional[float] = None
    roofline: str = ""
    mfu: Optional[float] = None
    # comms-only concurrency split: of this region's collective/transfer
    # device time, how much ran concurrently with ANY compute slice on the
    # same device row (overlapped — hidden behind compute) vs serialized
    # against it (exposed — the part lever ROADMAP#5a can actually recover)
    overlapped_us: float = 0.0
    exposed_us: float = 0.0

    @property
    def overlap_frac(self) -> Optional[float]:
        comms = self.overlapped_us + self.exposed_us
        return (self.overlapped_us / comms) if comms else None

    def as_dict(self) -> dict:
        return {
            "name": self.name, "us": round(self.us, 3), "count": self.count,
            "category": self.category, "bsym_ids": self.bsym_ids,
            "flops": self.flops, "bytes": self.bytes,
            "intensity": None if self.intensity is None else round(self.intensity, 3),
            "roofline": self.roofline,
            "mfu": None if self.mfu is None else round(self.mfu, 4),
            "overlapped_us": round(self.overlapped_us, 3),
            "exposed_us": round(self.exposed_us, 3),
            "overlap_frac": (None if self.overlap_frac is None
                             else round(self.overlap_frac, 4)),
        }


@dataclass
class DeviceProfile:
    """Per-region device-time breakdown of a profiled window of steps."""

    n_steps: int = 0
    total_device_us: float = 0.0
    regions: dict = field(default_factory=dict)  # name -> RegionTime
    categories: dict = field(default_factory=dict)  # compute/collective/transfer -> us
    unattributed_us: float = 0.0
    wall_us: float = 0.0
    peak_tflops: float = 0.0
    overlapped_comms_us: float = 0.0
    exposed_comms_us: float = 0.0

    @property
    def attributed_us(self) -> float:
        return self.total_device_us - self.unattributed_us

    @property
    def overlap_frac(self) -> Optional[float]:
        """Fraction of collective+transfer device time hidden behind
        compute (None when the window had no comms at all)."""
        comms = self.overlapped_comms_us + self.exposed_comms_us
        return (self.overlapped_comms_us / comms) if comms else None

    @property
    def attributed_frac(self) -> Optional[float]:
        if not self.total_device_us:
            return None
        return self.attributed_us / self.total_device_us

    def mfu_measured(self, flops_per_step: Optional[float] = None) -> Optional[float]:
        """Measured MFU over the window: model FLOPs / device-time × peak.
        flops_per_step defaults to the cost-model sum over attributed
        compute regions. Region flops are PER STEP (the registry prices one
        execution of the region), while device time spans the whole
        window — both paths must scale by n_steps."""
        if flops_per_step is None:
            total = sum(r.flops for r in self.regions.values()
                        if r.category == "compute") * max(1, self.n_steps)
        else:
            total = flops_per_step * max(1, self.n_steps)
        busy = self.categories.get("compute", 0.0) or self.total_device_us
        return _flops.measured_mfu(total, busy, self.peak_tflops or None)

    def summary_dict(self, flops_per_step: Optional[float] = None) -> dict:
        return {
            "n_steps": self.n_steps,
            "total_device_us": round(self.total_device_us, 1),
            "wall_us": round(self.wall_us, 1),
            "compute_us": round(self.categories.get("compute", 0.0), 1),
            "collective_us": round(self.categories.get("collective", 0.0), 1),
            "transfer_us": round(self.categories.get("transfer", 0.0), 1),
            "unattributed_us": round(self.unattributed_us, 1),
            "overlapped_comms_us": round(self.overlapped_comms_us, 1),
            "exposed_comms_us": round(self.exposed_comms_us, 1),
            "overlap_frac": (None if self.overlap_frac is None
                             else round(self.overlap_frac, 4)),
            "attributed_frac": (None if self.attributed_frac is None
                                else round(self.attributed_frac, 4)),
            "mfu_measured": (lambda m: None if m is None else round(m, 4))(
                self.mfu_measured(flops_per_step)),
            "regions": {k: v.as_dict() for k, v in sorted(
                self.regions.items(), key=lambda kv: -kv[1].us)},
        }

    def table(self, top: int = 0) -> str:
        """The `perf report` view: regions by device time."""
        rows = sorted(self.regions.values(), key=lambda r: -r.us)
        if top:
            rows = rows[:top]
        lines = [f"device time: {self.total_device_us / 1e3:.3f} ms over "
                 f"{self.n_steps} step(s)"
                 + (f"  (attributed {self.attributed_frac:.0%})"
                    if self.attributed_frac is not None else "")]
        hdr = (f"  {'region':<28} {'time':>10} {'%':>6} {'calls':>6} "
               f"{'category':<10} {'GFLOP':>8} {'AI':>7} {'roofline':<13} {'mfu':>6}")
        lines.append(hdr)
        tot = self.total_device_us or 1.0
        for r in rows:
            ai = "-" if r.intensity is None else f"{r.intensity:.1f}"
            mfu = "-" if r.mfu is None else f"{r.mfu:.3f}"
            lines.append(
                f"  {r.name:<28} {r.us / 1e3:>8.3f}ms {100 * r.us / tot:>5.1f}% "
                f"{r.count:>6} {r.category:<10} {r.flops / 1e9:>8.2f} {ai:>7} "
                f"{r.roofline:<13} {mfu:>6}")
        if self.unattributed_us:
            lines.append(f"  {'(unattributed)':<28} {self.unattributed_us / 1e3:>8.3f}ms "
                         f"{100 * self.unattributed_us / tot:>5.1f}%")
        if self.overlap_frac is not None:
            lines.append(
                f"  comms overlap: {self.overlap_frac:.0%} hidden "
                f"({self.overlapped_comms_us / 1e3:.3f} ms overlapped, "
                f"{self.exposed_comms_us / 1e3:.3f} ms exposed)")
        return "\n".join(lines)

    def emit(self) -> None:
        """Record the breakdown on the event bus (JSONL export) so shards
        carry it for `tools/obs_summary.py perf`."""
        if _obs.enabled():
            _obs.event("device_profile", profile=self.summary_dict())


def _event_device_side(ev: dict, proc_names: dict, thread_names: dict) -> bool:
    """Is this trace event device work to account?

    Device-process rows (TPU: ``/device:TPU:N``) all count. On host
    processes only events carrying HLO/op metadata count — the CPU
    backend's executor threads also emit *wrapper* events (ThunkExecutor,
    ThreadpoolListener, Execute) that NEST over the per-op events; summing
    them would double-count every op and leave the wrapper share forever
    unattributable."""
    pname = proc_names.get(ev.get("pid"), "")
    if "/device:" in pname:
        return True
    args = ev.get("args") or {}
    return ("hlo_op" in args or "hlo_module" in args
            or "tf_op" in args or "long_name" in args)


def _classify(name: str, args: dict) -> str:
    hay = " ".join([name] + [str(v) for v in args.values()])
    if _COLLECTIVE_PAT.search(hay):
        return "collective"
    if _TRANSFER_PAT.search(hay):
        return "transfer"
    return "compute"


def _merge_intervals(ivals: list) -> list:
    """Sorted disjoint union of (start, end) intervals."""
    out: list = []
    for start, end in sorted(ivals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def _overlap_len(start: float, end: float, union: Iterable) -> float:
    """Total length of [start, end) covered by a sorted disjoint union."""
    total = 0.0
    for s, e in union:
        if e <= start:
            continue
        if s >= end:
            break
        total += min(end, e) - max(start, s)
    return total


def _region_of(ev_name: str, args: dict, reg: dict, op_map: dict) -> Optional[str]:
    """The registered region one device event belongs to: the finest (lowest
    ``level``) registered name that is a segment of its instruction's scope path
    (or the name its executable was registered under), by the op map; where the
    map does not hold the instruction, the region its module is named after."""
    mod = _RUN_ID.sub("", args.get("hlo_module", ""))
    ops = op_map.get(mod)
    if ops is not None:
        instruction = args.get("hlo_op") or ev_name
        ops = ops.holding([instruction])
        if instruction in ops:
            segs = [_WRAPPER_SEG.sub(r"\1", seg) for seg in ops[instruction].split("/")[:-1]]
            # innermost first, so that of two regions of one level the inner one wins
            on_path = [n for n in (ops.scope(instruction)[0], *reversed(segs)) if n in reg]
            if on_path:
                return min(on_path, key=lambda n: reg[n].get("level", 0))
    mod = mod[4:] if mod.startswith("jit_") else mod
    return mod if mod in reg else None


def attribute(trace_events: list[dict], *, region_map: Optional[dict] = None,
              n_steps: int = 1, op_map: Optional[dict] = None) -> DeviceProfile:
    """Join device-side trace events to registered regions.

    Join per event: an event is ``(hlo_module, hlo_op)``, and ``op_map``
    (``op_scopes()`` unless given) says which scope path that instruction was
    traced under; the finest (lowest ``level``) registered region on the path
    wins — an op of the whole-step program whose path is
    ``jit(tt_train_step)/tt_fwd_bwd/xla_fusion_3/bwd/mlp/dot_general`` lands on
    ``xla_fusion_3``, one under ``tt_optimizer`` alone on that. An event whose
    instruction no executable holds attributes to the region its module is
    named after (``jit_xla_fusion_3``), and else falls into the unattributed
    bucket."""
    reg = region_map if region_map is not None else regions()
    if op_map is None:
        op_map = op_scopes()

    proc_names: dict = {}
    thread_names: dict = {}
    for ev in trace_events:
        if ev.get("ph") == "M":
            if ev.get("name") == "process_name":
                proc_names[ev.get("pid")] = (ev.get("args") or {}).get("name", "")
            elif ev.get("name") == "thread_name":
                thread_names[(ev.get("pid"), ev.get("tid"))] = (
                    (ev.get("args") or {}).get("name", ""))

    prof = DeviceProfile(n_steps=max(1, n_steps))
    prof.peak_tflops = _flops.device_peaks()[0]
    region_times: dict[str, RegionTime] = {}
    t_min = None
    t_max = None
    # concurrency sweep inputs, collected per device row (pid) so two
    # devices' slices can't fake an overlap with each other: compute slice
    # intervals, and each comms slice with its eventual region target
    compute_ivals: dict[Any, list] = {}  # pid -> [(start, end), ...]
    comms_slices: list = []  # (pid, start_or_None, dur, target_name_or_None)

    for ev in trace_events:
        if ev.get("ph") != "X":
            continue
        dur = float(ev.get("dur") or 0.0)
        ts = ev.get("ts")
        if ts is not None:
            t_min = ts if t_min is None else min(t_min, ts)
            t_max = (ts + dur) if t_max is None else max(t_max, ts + dur)
        if not _event_device_side(ev, proc_names, thread_names):
            continue
        name = ev.get("name", "")
        args = ev.get("args") or {}
        cat = _classify(name, args)
        prof.total_device_us += dur
        prof.categories[cat] = prof.categories.get(cat, 0.0) + dur
        if cat == "compute" and ts is not None and dur > 0:
            compute_ivals.setdefault(ev.get("pid"), []).append((ts, ts + dur))

        target = _region_of(name, args, reg, op_map)
        if cat != "compute":
            comms_slices.append((ev.get("pid"), ts, dur, target))
        if target is None:
            prof.unattributed_us += dur
            continue
        rt = region_times.get(target)
        if rt is None:
            info = reg.get(target, {})
            rt = region_times[target] = RegionTime(
                name=target,
                bsym_ids=list(info.get("bsym_ids", [])),
                flops=info.get("flops", 0.0),
                bytes=info.get("bytes", 0),
            )
        rt.us += dur
        rt.count += 1
        rt.cat_us[cat] = rt.cat_us.get(cat, 0.0) + dur

    # concurrency sweep: merge each device row's compute slices into a
    # disjoint interval union, then split every comms slice into the part
    # inside the union (overlapped — hidden behind compute) and the rest
    # (exposed). Slices without a timestamp can't prove concurrency and
    # count fully exposed.
    compute_union = {pid: _merge_intervals(iv) for pid, iv in compute_ivals.items()}
    for pid, ts, dur, target in comms_slices:
        if ts is None or dur <= 0:
            overlapped = 0.0
        else:
            overlapped = _overlap_len(ts, ts + dur, compute_union.get(pid, ()))
        exposed = max(0.0, dur - overlapped)
        prof.overlapped_comms_us += overlapped
        prof.exposed_comms_us += exposed
        if target is not None and target in region_times:
            rt = region_times[target]
            rt.overlapped_us += overlapped
            rt.exposed_us += exposed

    for rt in region_times.values():
        # a region's category is where its TIME went, not whatever its last
        # event happened to be — one fused 0.1ms copy must not reclassify a
        # 30ms compute region as comms-bound
        if rt.cat_us:
            rt.category = max(rt.cat_us, key=rt.cat_us.get)
        rt.intensity = _flops.arithmetic_intensity(rt.flops, rt.bytes)
        rt.roofline = _flops.roofline_tag(rt.flops, rt.bytes, category=rt.category)
        if rt.category == "compute" and rt.us:
            rt.mfu = _flops.measured_mfu(rt.flops * prof.n_steps, rt.us,
                                         prof.peak_tflops or None)
    prof.regions = region_times
    if t_min is not None and t_max is not None:
        prof.wall_us = t_max - t_min
    return prof


# ---------------------------------------------------------------------------
# capture
# ---------------------------------------------------------------------------


class _Capture:
    """Handle yielded by ``profile()``; ``.profile`` holds the parsed
    DeviceProfile after the context exits."""

    def __init__(self, log_dir: str, n_steps: int):
        self.log_dir = log_dir
        self.n_steps = n_steps
        self.profile: Optional[DeviceProfile] = None
        self.events: list[dict] = []


@contextlib.contextmanager
def profile(log_dir: Optional[str] = None, *, n_steps: int = 1):
    """Capture a device profile around a block:

        with observability.profile() as cap:
            step(x); jax.block_until_ready(loss)
        print(cap.profile.table())

    The perfetto trace-event export is parsed on exit and attributed
    through the region registry. Capture failures degrade to an empty
    profile (``cap.profile is None``) — profiling must never take the
    step down with it."""
    import jax

    own_dir = log_dir is None
    if own_dir:
        log_dir = tempfile.mkdtemp(prefix="tt_profile_")
    cap = _Capture(log_dir, n_steps)
    started = False
    try:
        with _obs.span("profile_capture", log_dir=log_dir):
            try:
                jax.profiler.start_trace(log_dir, create_perfetto_trace=True)
                started = True
            except Exception as e:  # profiler already running / unsupported
                _obs.event("profile_error", stage="start", error=str(e)[:200])
            try:
                yield cap
            finally:
                if started:
                    try:
                        jax.profiler.stop_trace()
                    except Exception as e:
                        _obs.event("profile_error", stage="stop", error=str(e)[:200])
                        started = False
        if started:
            try:
                cap.events = _load_perfetto(log_dir)
                cap.profile = attribute(cap.events, n_steps=cap.n_steps)
                cap.profile.emit()
            except Exception as e:
                _obs.event("profile_error", stage="parse", error=str(e)[:200])
    finally:
        if own_dir:
            import shutil

            shutil.rmtree(log_dir, ignore_errors=True)


def profile_steps(step_fn: Callable[[], Any], n: int = 3, *,
                  warmup: int = 1, log_dir: Optional[str] = None) -> Optional[DeviceProfile]:
    """Profile ``n`` calls of ``step_fn`` and return the attributed
    DeviceProfile (None when capture failed). ``step_fn`` takes no args —
    close over the batch; its result is block_until_ready'd so device work
    lands inside the capture window. ``warmup`` un-profiled calls first
    keep one-time compiles out of the measured window."""
    import jax

    for _ in range(max(0, warmup)):
        jax.block_until_ready(step_fn())
    with profile(log_dir, n_steps=n) as cap:
        for _ in range(n):
            out = step_fn()
        jax.block_until_ready(out)
    return cap.profile
