"""The XLA regions' device time put down to the part of the model and the pass
its trace symbols came from.

A device event is ``(module, instruction)``: the module by the run of a program
it lies in (an event of the chip's ``XLA Modules`` line; in a CPU rehearsal,
which has no such line, by the event's ``hlo_module`` stat), the instruction by
the name its HLO text starts with, as ``xplane.parse_hlo`` takes it. The program says where that instruction came from:
``thunder_tpu.observability.profiler.op_scopes()`` reads the ``op_name`` paths out
of the HLO text of every executable the process holds, and ``OpMap.scope`` gives
``(region, pass, part)``: ``part`` one of the model's named scopes (``attn``,
``mlp``, ``head``, ...; a fusion whose members span two reads ``mlp+optimizer`` and
goes to the first), ``pass`` one of ``fwd``, ``bwd``, ``recompute``, ``optimizer``.
An op outside every run, or one no executable holds, is ``unscoped``.

:func:`table` works that out once a traced run, over the ops ``readers.xla_seconds``
sums unless told otherwise, averaged over the chips as that sum is, and logs one
line (``bench: xla by scope ...``). On a program without ``op_scopes`` (an earlier
commit) it returns ``None`` and so does every reader.
"""
from __future__ import annotations

import bisect
import re
import time

from . import phases, readers, reasoning, xplane
from .harness import say

UNSCOPED = "unscoped"
INSTRUCTION = re.compile(r"^%?(\S+) = ")  # of an event's name, which on a TPU is the instruction's HLO text
PASSES = ("fwd", "bwd", "recompute", "optimizer")
# the parts each family of metrics names; what is left goes to its `unscoped` metric
TRAIN = {"attn": ("attn",), "mlp": ("mlp",), "head": ("head", "embed"), "optimizer": ("optimizer",)}
CHAT = {"attn": ("attn",), "kv_write": ("kv_write",), "mlp": ("mlp",), "head": ("head", "embed")}
REASONING = {"mixer": ("mamba", "gmu", "window_attn", "full_attn", "cross_attn", "kv_write"),
             "mlp": ("mlp",), "head": ("head", "embed")}


def program_maps():
    """``(maps, cost)`` from the program's ``op_scopes()``, or ``None`` where it has none."""
    from thunder_tpu.observability import profiler

    if not hasattr(profiler, "op_scopes"):
        return None
    t0 = time.perf_counter()
    maps = profiler.op_scopes()
    return maps, dict(getattr(profiler, "op_scopes_cost", {}), seconds=time.perf_counter() - t0)


def module_runs(plane) -> tuple:
    """``(starts, ends, names)`` of one chip's ``XLA Modules`` events, by start."""
    runs = sorted((e.start, e.end, xplane.strip_run_id(e.name)) for e in plane.line(xplane.MODULES_LINE))
    return [r[0] for r in runs], [r[1] for r in runs], [r[2] for r in runs]


def split(ops: list, runs, maps: dict, keep) -> dict:
    """One chip's ``(event, self_ns)`` by scope. ``runs`` is :func:`module_runs` of the
    chip, or ``None`` where the events carry ``hlo_module`` / ``hlo_op`` stats themselves.
    ``keep(event, module)`` picks the ops. Returns nanoseconds: ``cells`` by ``(part,
    pass)``, ``pairs`` by ``(a+b, pass)`` for the fusions that span two parts (also
    counted under ``a``), ``finer`` by ``(path, pass)`` for the ops whose scope path names
    more than the part (``attn/rope``; also counted under the part), ``ops`` by ``(xplane.op_label,
    part, pass)``, ``unscoped_ops`` by ``xplane.op_label``, ``outside`` (in no
    run of a program), ``no_map`` by program (no executable is held for it) and
    ``not_in_map`` (the program's map lacks the instruction)."""
    out = {"cells": {}, "pairs": {}, "finer": {}, "ops": {}, "unscoped_ops": {}, "no_map": {}, "outside": 0.0,
           "not_in_map": 0.0, "total": 0.0}
    by_run: dict = {}  # (run index or module) -> [(instruction, event, self_ns)]
    for e, ns in ops:
        if runs is None:
            module, where = e.stats.get("hlo_module"), e.stats.get("hlo_module")
            instruction = e.stats.get("hlo_op") or e.name
        else:
            mid = e.start + e.dur / 2
            i = bisect.bisect_right(runs[0], mid) - 1
            inside = i >= 0 and mid < runs[1][i]
            module, where = (runs[2][i], i) if inside else (None, None)
            named = INSTRUCTION.match(e.name)
            instruction = named.group(1) if named else e.name
        if not keep(e, module):
            continue
        out["total"] += ns
        by_run.setdefault(where, (module, []))[1].append((instruction, e, ns))

    def add(key: str, k, ns: float) -> None:
        out[key][k] = out[key].get(k, 0.0) + ns

    for module, events in by_run.values():
        ops_map = maps.get(module) if module is not None else None
        if ops_map is not None:
            ops_map = ops_map.holding(i for i, _, _ in events)
        for instruction, e, ns in events:
            if ops_map is None:
                if module is None:
                    out["outside"] += ns
                else:
                    add("no_map", module, ns)
                pass_name, part = "fwd", UNSCOPED
            elif instruction not in ops_map:
                out["not_in_map"] += ns
                pass_name, part = "fwd", UNSCOPED
            else:
                _, pass_name, part = ops_map.scope(instruction)
                path = ops_map.finer(instruction)
                if path not in ("", part.split("+")[0]):
                    add("finer", (path, pass_name), ns)
            first = part.split("+")[0]
            add("cells", (first, pass_name), ns)
            add("ops", (xplane.op_label(e.name), first, pass_name), ns)
            if first != part:
                add("pairs", (part, pass_name), ns)
            if first == UNSCOPED:
                add("unscoped_ops", xplane.op_label(e.name), ns)
    return out


def xla_ops(run):
    """``keep`` of the ops ``readers.xla_seconds`` sums: neither a Pallas call nor a collective."""
    root = run.cell.root
    return lambda e, module: readers.pallas_class(root, e.name) is None and not xplane.is_collective(e.name)


def decode_program_rest(run):
    """``keep`` of what ``reasoning_xla_ms_per_iter`` sums: inside a run of the decode program,
    neither one of its paged kernels nor an op over the state-space layers' state."""
    regions = run.stats.get("decode_regions")
    if not regions:
        return None
    rx = re.compile("^jit_(" + "|".join(map(re.escape, regions)) + ")$")
    ssm = reasoning.state_space_pattern(run.cell.builder.dims(run.cell.config))
    root = run.cell.root

    def keep(e, module) -> bool:
        if module is None or not rx.search(module):
            return False
        cls = readers.pallas_class(root, e.name)
        return cls not in ("window_decode", "paged_decode") and (cls is not None or not ssm.search(e.name))

    return keep


def table(run, keep=None, *, key: str = "xla", units: int = 0, unit: str = "unit"):
    """The split of the run's trace, averaged over its chips: :func:`split`'s result in
    seconds, with ``maps`` (what getting and parsing the executables cost). Worked out once
    a run and ``key``, and logged in ms a ``unit`` where ``units`` says how many the traced
    window held. ``None`` without a trace or on a program without ``op_scopes``."""
    if run.trace is None or not run.trace.devices:
        return None
    cached = "scopes:" + key
    if cached in run.traced:
        return run.traced[cached]
    found = program_maps()
    if found is None:
        run.traced[cached] = None
        return None
    maps, cost = found
    keep = keep or xla_ops(run)
    chips = xplane.device_planes(phases.traced_planes(run))
    n = len(run.trace.devices)
    total: dict = {}
    for i, d in enumerate(run.trace.devices):
        runs = module_runs(chips[i]) if i < len(chips) else None
        for name, value in split(d.ops, runs, maps, keep).items():
            if isinstance(value, dict):
                acc = total.setdefault(name, {})
                for k, ns in value.items():
                    acc[k] = acc.get(k, 0.0) + ns / n / 1e9
            else:
                total[name] = total.get(name, 0.0) + value / n / 1e9
    total["maps"] = cost
    run.traced[cached] = total
    log(total, key, units, unit)
    return total


def log(t: dict, key: str, units: int, unit: str) -> None:
    per = (lambda s: s / units * 1e3) if units else (lambda s: s * 1e3)
    what = f"ms a {unit} ({units} traced)" if units else "ms of the traced window"
    parts = sorted({p for p, _ in t["cells"]}, key=lambda p: -sum(v for (q, _), v in t["cells"].items() if q == p))
    cells = "; ".join(
        f"{p} " + " ".join(f"{x} {per(t['cells'][(p, x)]):.3f}" for x in PASSES if (p, x) in t["cells"])
        for p in parts)
    pairs = ", ".join(f"{p} {x} {per(v):.3f}" for (p, x), v in sorted(t["pairs"].items(), key=lambda kv: -kv[1])[:8])
    finer = ", ".join(f"{p} {x} {per(v):.3f}" for (p, x), v in sorted(t["finer"].items(), key=lambda kv: -kv[1])[:8])
    ops = ", ".join(f"{label} {per(v):.3f}"
                    for label, v in sorted(t["unscoped_ops"].items(), key=lambda kv: -kv[1])[:10])
    largest = ", ".join(f"{label} -> {p} {x} {per(v):.3f}"
                        for (label, p, x), v in sorted(t["ops"].items(), key=lambda kv: -kv[1])[:8])
    whole = t["total"] or 1.0
    cost = t["maps"]
    no_map = ", ".join(f"{m} {per(v):.3f}" for m, v in sorted(t["no_map"].items(), key=lambda kv: -kv[1])[:5])
    say(f"xla by scope [{key}], {what}: {cells or 'nothing'}; sum {per(t['total']):.3f}"
        f"; fusions over two parts (counted under the first): {pairs or 'none'}"
        f"; finer names: {finer or 'none'}"
        f"; largest ops and where they go: {largest or 'none'}"
        f"; largest unscoped ops: {ops or 'none'}"
        f"; outside every program's run {100 * t['outside'] / whole:.2f}%, in a program with no map "
        f"{100 * sum(t['no_map'].values()) / whole:.2f}% ({no_map or 'none'}), instruction not in its program's map {100 * t['not_in_map'] / whole:.2f}%"
        f"; maps of {cost.get('executables', 0)} executables ({cost.get('same_name', 0)} under a name "
        f"another has) in {cost.get('seconds', 0.0):.2f} s: got in {cost.get('get_s', 0.0):.2f}, "
        f"text parsed in {cost.get('parse_s', 0.0):.2f}")


def ms_per_unit(t, family: dict, name: str, units: int):
    """Milliseconds a unit of the family's part ``name`` (all passes), or for ``unscoped``
    of everything no part of the family names. ``None`` without a table or units."""
    if t is None or not units:
        return None
    if name == UNSCOPED:
        named = {p for ps in family.values() for p in ps}
        seconds = sum(v for (p, _), v in t["cells"].items() if p not in named)
    else:
        seconds = sum(v for (p, _), v in t["cells"].items() if p in family[name])
    return seconds / units * 1e3


def train_ms_per_step(run, name: str):
    units = readers.train_steps_traced(run)
    return ms_per_unit(table(run, units=units, unit="step"), TRAIN, name, units)


def chat_ms_per_iter(run, name: str):
    units = readers.program_runs(run, "serve_decode")
    return ms_per_unit(table(run, units=units, unit="decode-program run"), CHAT, name, units)


def reasoning_ms_per_iter(run, name: str):
    keep = decode_program_rest(run) if run.trace is not None else None
    if keep is None:
        return None
    units = readers.program_runs(run, "serve_decode")
    t = table(run, keep, key="decode program, the rest", units=units, unit="decode-program run")
    return ms_per_unit(t, REASONING, name, units)
