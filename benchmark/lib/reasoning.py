"""Reductions the `reasoning_*` readers share: the decode program's device time by
kernel class and by the named scope of the layer kind, and the mean decode call
from the requests the window completed.

The state-space layers' work is found by its shapes: nothing else in the model has
an array ``[.., d_inner, d_state]`` (the scan state) or ``[.., d_conv - 1, d_inner]`` (the
conv tail), and XLA names its fusions by number, not by the ``jax.named_scope`` the
program runs them under (only a Pallas call's instruction carries that name:
``%cross_attn.9 = .. custom-call(..)``; my chip run, PR 27).
"""
from __future__ import annotations

import re

from . import costs_sambay, phases, readers, xplane
from .harness import say

def state_space_pattern(dims: dict):
    """Matches the HLO text of an op that reads or writes the scan state or the
    conv tail of a state-space layer."""
    return re.compile(r"\[(\d+,)*%d,%d\]|\[(\d+,)*%d,%d\]" % (
        dims["d_inner"], dims["d_state"], dims["d_conv"] - 1, dims["d_inner"]))


def decode_split(run):
    """Seconds of the first chip's device self time inside runs of the decode
    program, by what it went to: each Pallas class, ``state_space`` (the XLA ops
    over the scan state and the conv tail) and ``other``. Worked out once a run
    and logged. None without a trace or where the decode program's regions are
    unknown."""
    regions = run.stats.get("decode_regions")
    if run.trace is None or not regions or not run.trace.devices:
        return None
    if "decode_split" not in run.traced:
        rx = re.compile("^jit_(" + "|".join(map(re.escape, regions)) + ")$")
        chips = xplane.device_planes(phases.traced_planes(run))
        spans = xplane.union((e.start, e.end) for e in (chips[0].line(xplane.MODULES_LINE) if chips else [])
                             if rx.search(xplane.strip_run_id(e.name)))
        split: dict = {}
        ssm = state_space_pattern(run.cell.builder.dims(run.cell.config))
        for e, self_ns in run.trace.devices[0].ops:
            mid = e.start + e.dur / 2
            if not any(lo <= mid < hi for lo, hi in spans):
                continue
            key = (readers.pallas_class(run.cell.root, e.name)
                   or ("state_space" if ssm.search(e.name) else "other"))
            split[key] = split.get(key, 0.0) + self_ns / 1e9
        run.traced["decode_split"] = split
        runs = readers.program_runs(run, "serve_decode")
        if runs and split:
            say("decode program by kind, ms a run: "
                + ", ".join(f"{k} {v / runs * 1e3:.3f}" for k, v in sorted(split.items(), key=lambda kv: -kv[1])))
    return run.traced["decode_split"]


def decode_ms_per_iter(run, keys):
    """Milliseconds a decode-program run of the split's ``keys`` (a predicate)."""
    split = decode_split(run)
    if not split:
        return None
    return readers.per_unit_ms(sum(v for k, v in split.items() if keys(k)),
                               readers.program_runs(run, "serve_decode"))


def decode_contexts(run, window=None):
    """``(sequences a step, key positions a step)`` of the mean decode step of
    the window, from the requests completed in it: a request with ``n`` new
    tokens took ``n - 1`` decode steps at ``prompt + 1 .. prompt + n - 1`` key
    positions, of which a window layer sees ``window`` at most."""
    done = readers.measured_ok(run)
    steps = run.stats.get("decode_steps", 0)
    tokens = sum(r.n_new - 1 for r in done)
    if not steps or tokens <= 0:
        return None
    seen = (lambda c: min(c, window)) if window else (lambda c: c)
    keys = sum(seen(r.prompt_len + j) for r in done for j in range(1, r.n_new))
    active = run.counters.get("serve.tokens", tokens) / steps
    return active, active * keys / tokens


def attention_cost(run, window=None):
    """One decode call of one attending layer, by what the mathematics reads."""
    found = decode_contexts(run, window)
    if found is None:
        return None
    d = run.cell.builder.dims(run.cell.config)
    active, keys = found
    return costs_sambay.diff_attention_decode(keys, active, d["heads"], d["kv_heads"], d["head_dim"])
