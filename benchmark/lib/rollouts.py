"""Reductions the `rollouts_*` readers share: a program's device time and calls by kernel class
inside the runs of its own executables, the routing the decode steps counted, and the costs of the
mean decode call from them and from the requests the window completed.

The routed experts' kernel runs in the prefill and chunk programs too, so its calls are told apart
by the program they ran in: the driver reads the decode program's XLA regions off its executed
trace (``decode_regions``) and an op counts for it where one of its executables was running at
the op's midpoint (the XLA Modules line), as `lib/reasoning.py` does.
"""
from __future__ import annotations

import re

from . import costs_latent_moe, phases, readers, xplane
from .harness import say
from .reasoning import decode_contexts


def program_split(run, regions_key: str, program: str):
    """``{class or "other": (seconds, calls)}`` of the first chip's device self time inside runs
    of the executables named in ``run.stats[regions_key]``. Worked out once a run and logged, a
    run of the ``program`` annotation each. None without a trace or where the regions are unknown."""
    regions = run.stats.get(regions_key)
    if run.trace is None or not regions or not run.trace.devices:
        return None
    key = "split:" + regions_key
    if key not in run.traced:
        rx = re.compile("^jit_(" + "|".join(map(re.escape, regions)) + ")$")
        chips = xplane.device_planes(phases.traced_planes(run))
        spans = xplane.union((e.start, e.end) for e in (chips[0].line(xplane.MODULES_LINE) if chips else [])
                             if rx.search(xplane.strip_run_id(e.name)))
        split: dict = {}
        for e, self_ns in run.trace.devices[0].ops:
            mid = e.start + e.dur / 2
            if not any(lo <= mid < hi for lo, hi in spans):
                continue
            cls = readers.pallas_class(run.cell.root, e.name) or "other"
            seconds, calls = split.get(cls, (0.0, 0))
            split[cls] = (seconds + self_ns / 1e9, calls + 1)
        run.traced[key] = split
        runs = readers.program_runs(run, program)
        if runs and split:
            say(f"{program} by kind, ms a run: "
                + ", ".join(f"{k} {v / runs * 1e3:.3f} ({n / runs:.1f} calls)"
                            for k, (v, n) in sorted(split.items(), key=lambda kv: -kv[1][0])))
    return run.traced[key]


def decode_split(run):
    return program_split(run, "decode_regions", "serve_decode")


def decode_ms_per_iter(run, keys):
    """Milliseconds a decode-program run of the split's ``keys`` (a predicate)."""
    split = decode_split(run)
    if not split:
        return None
    return readers.per_unit_ms(sum(s for k, (s, _) in split.items() if keys(k)),
                               readers.program_runs(run, "serve_decode"))


def decode_roofline_pct(run, cls: str, cost_per_call):
    """Share of its roofline that class ``cls`` reached in the decode program: the least time the
    chip could take for its calls there (``cost_per_call`` each) over the time they took."""
    from . import costs
    from .peaks import peaks

    split = decode_split(run)
    if not split or cls not in split or cost_per_call is None:
        return None
    seconds, calls = split[cls]
    least, bound = costs.roofline_seconds(cost_per_call, peaks(run.device_kind))
    say(f"{cls} in the decode program: {calls} calls, {seconds / calls * 1e6:.1f} us each against "
        f"{least * 1e6:.1f} us at the {bound} roofline ({cost_per_call.flops / 1e9:.3f} GFLOP, "
        f"{cost_per_call.bytes / 1e6:.3f} MB a call)")
    return 100.0 * least * calls / seconds


def routing(run):
    """The window's routing counters as means: ``{"rows_routed", "rows_held", "experts_touched",
    "rows_max"}`` a decode step and layer. None where the program counted none."""
    c = run.counters
    steps = c.get("serve.decode_steps")
    if not steps or not c.get("serve.moe.rows_routed"):
        return None
    calls = steps * run.cell.builder.dims(run.cell.config)["n_layer"]
    return {k: c.get("serve.moe." + k, 0) / calls
            for k in ("rows_routed", "rows_held", "experts_touched", "rows_max")}


def experts_cost(run):
    """One decode call of one layer's routed experts, from what the steps counted."""
    r = routing(run)
    if r is None:
        return None
    d = run.cell.builder.dims(run.cell.config)
    return costs_latent_moe.ragged_experts(r["rows_held"], r["experts_touched"], d["d_model"],
                                           d["expert_width"])


def attention_cost(run):
    """One decode call of one layer's latent attention, from the requests the window completed."""
    found = decode_contexts(run)
    if found is None:
        return None
    d = run.cell.builder.dims(run.cell.config)
    active, rows = found
    return costs_latent_moe.latent_decode(rows, active, d["heads"], d["latent_width"], d["kv_rank"],
                                          d["latent_row"])
