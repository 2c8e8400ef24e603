"""Reductions the `trajectories_*` readers share beside `lib/rollouts.py`'s (the decode program's
device time by kernel class, the mean decode call's contexts): the routing the decode steps
counted where a model's expert layers are FEWER than its caching layers and some of the router's
outputs are identity experts, and the decode program's XLA time by trace scope.
"""
from __future__ import annotations

import re

from . import costs_latent_moe, readers, scopes

COUNTED = ("rows_routed", "rows_held", "experts_touched", "rows_max", "rows_zero")


def routing(run):
    """The window's routing counters as means a decode step and EXPERT layer (a double layer
    has one): ``{"rows_routed", "rows_held", "experts_touched", "rows_max", "rows_zero"}``.
    None where the program counted none."""
    c = run.counters
    steps = c.get("serve.decode_steps")
    if not steps or not c.get("serve.moe.rows_routed"):
        return None
    calls = steps * run.cell.builder.dims(run.cell.config)["n_expert_layers"]
    return {k: c.get("serve.moe." + k, 0) / calls for k in COUNTED}


def experts_cost(run):
    """One decode call of one double layer's held experts, from what the steps counted."""
    r = routing(run)
    if r is None:
        return None
    d = run.cell.builder.dims(run.cell.config)
    return costs_latent_moe.ragged_experts(r["rows_held"], r["experts_touched"], d["d_model"],
                                           d["expert_width"])


def decode_program_xla(run):
    """``keep`` for `scopes.table`: an op inside a run of the decode program that is no Pallas call."""
    regions = run.stats.get("decode_regions")
    if not regions:
        return None
    rx = re.compile("^jit_(" + "|".join(map(re.escape, regions)) + ")$")
    root = run.cell.root
    return lambda e, module: (module is not None and bool(rx.search(module))
                              and readers.pallas_class(root, e.name) is None)


def scope_ms_per_iter(run, *parts: str):
    """Milliseconds a decode-program run of the decode program's XLA ops whose trace symbols
    ran under one of the named scopes ``parts`` (a fusion over two parts under its first)."""
    keep = decode_program_xla(run) if run.trace is not None else None
    if keep is None:
        return None
    units = readers.program_runs(run, "serve_decode")
    t = scopes.table(run, keep, key="decode program, XLA", units=units, unit="decode-program run")
    return scopes.ms_per_unit(t, {"named": parts}, "named", units)
