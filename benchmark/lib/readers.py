"""Reductions the per-layer readers share.

A reader (``benchmark/layer_metrics/<metric>.py``) is a few lines that pick one
of these and name its arguments; it returns ``None`` where there is nothing to
read, and the harness then leaves the metric out of the line. Everything here
takes a ``harness.Run``.
"""
from __future__ import annotations

import json
import os
import re
from functools import lru_cache

from . import costs, loadgen
from .peaks import peaks
from .xplane import is_collective, op_label, parse_hlo


# -- host side ---------------------------------------------------------------------

def span_median_ms(run, name: str):
    xs = run.spans.get(name)
    return loadgen.percentile(xs, 50) * 1e3 if xs else None


def bus_span_percentile_ms(run, name: str, q: float):
    """Percentile of the durations of the program's bus spans called ``name``."""
    xs = [r["dur_ms"] for r in run.bus if r.get("kind") == "span" and r.get("name") == name]
    return loadgen.percentile(xs, q) if xs else None


def measured_ok(run) -> list:
    return [r for r in loadgen.measured(run.records, run.window_s) if r.ok]


def ttft_percentile_ms(run, q: float):
    """Time to first token from the due instant: the generator's lateness plus
    the program's own submit-to-first-token stamp; a failed request counts as
    the window."""
    xs = loadgen.ttft_ms(run.records, run.window_s)
    return loadgen.percentile(xs, q) if xs else None


def tbot_percentile_ms(run, q: float):
    """Over requests, the mean gap between output tokens by the program's stamps."""
    xs = loadgen.tbot_ms(run.records, run.window_s)
    return loadgen.percentile(xs, q) if xs else None


def out_tokens_per_s(run):
    """Generated tokens per second completed inside the window."""
    preroll = float(run.cell.traffic["loop"].get("preroll_s", 0.0))
    return loadgen.token_rate(run.records, run.window_s, lambda r: r.n_new, -preroll) or None


def page_pool_peak_pct(run):
    """Peak share of the KV page pool in use, from ``engine.stats()``."""
    peak = run.stats.get("engine", {}).get("peak_page_pool_utilization")
    return None if peak is None else 100.0 * peak


def recompiles_in_window(run) -> float:
    """Executables JAX built inside the window plus what the program's own
    counters say of recompiles and fallbacks there. Must be 0."""
    program = sum(v for k, v in run.counters.items()
                  if k.startswith("recompile.") or k in ("compile.prewarm_fallback", "aot.save_failed"))
    return float(run.compiles.get("builds", 0) + program)


# -- device side --------------------------------------------------------------------

@lru_cache(maxsize=None)
def kernel_classes(root: str) -> dict:
    """The kernel classes of ``benchmark/kernels/``: ``classes.json`` first,
    which alone says what marks a compiled Pallas kernel (``mosaic``), then
    every other ``*.json`` there in name order, each with a ``classes`` list of
    its own. The order is the order they are tried in, so a file a later PR
    adds can take no op from a class that was there before it."""
    kernels = os.path.join(root, "benchmark", "kernels")
    others = sorted(fn for fn in os.listdir(kernels) if fn.endswith(".json") and fn != "classes.json")
    mosaic, classes, came_from = None, [], {}
    for fn in ["classes.json", *others]:
        with open(os.path.join(kernels, fn)) as f:
            doc = json.load(f)
        if fn == "classes.json":
            mosaic = re.compile(doc["mosaic"])
        elif "mosaic" in doc:
            raise ValueError(f"benchmark/kernels/{fn} may not redefine `mosaic`: classes.json does")
        for c in doc["classes"]:
            if c["class"] in came_from:
                raise ValueError(f"kernel class {c['class']!r} is defined twice: in benchmark/kernels/"
                                 f"{came_from[c['class']]} and in benchmark/kernels/{fn}")
            came_from[c["class"]] = fn
            classes.append((c["class"], re.compile(c["pattern"])))
    return {"mosaic": mosaic, "classes": classes}


@lru_cache(maxsize=None)
def pallas_class(root: str, name: str):
    """The class of the Pallas kernel an op event is, by ``kernels/classes.json``:
    ``None`` for an op that is no compiled Pallas kernel, ``"unclassified"`` for
    one that matches no class. Searched in the event's name (on a TPU the
    instruction's HLO text); a model's layers repeat the same names step after
    step, hence the cache."""
    classes = kernel_classes(root)
    if not classes["mosaic"].search(name):
        return None
    return next((cls for cls, rx in classes["classes"] if rx.search(name)), "unclassified")


def breakdown_label(root: str):
    """The grouping of ``breakdown.device_ops``: a Pallas kernel by its class
    and result, every other op by :func:`xplane.op_label`."""
    def label(event) -> str:
        cls = pallas_class(root, event.name)
        if cls is None:
            return op_label(event.name)
        return f"pallas {cls} {parse_hlo(event.name)[2]}"[:120]

    return label


def class_time(run, cls: str) -> tuple:
    """``(seconds, calls)`` of the Pallas calls of class ``cls`` on the first
    chip inside the traced window."""
    if run.trace is None:
        return 0.0, 0
    picked = run.trace.op_events(lambda e: pallas_class(run.cell.root, e.name) == cls)
    return sum(s for _, s in picked) / 1e9, len(picked)


def xla_seconds(run) -> float:
    """Device self time of everything that is neither a Pallas call nor a
    collective: the XLA regions."""
    return run.trace.op_seconds(lambda e: pallas_class(run.cell.root, e.name) is None
                                and not is_collective(e.name))


def per_unit_ms(seconds: float, units: int):
    return seconds / units * 1e3 if units and seconds else None


def train_steps_traced(run) -> int:
    return int(run.traced.get("steps", 0)) if run.trace is not None else 0


def program_runs(run, name: str) -> int:
    """Dispatches of a serving program inside the traced window: the
    program annotates each (``serve_decode``, ``serve_chunk_prefill``, ...)."""
    return run.trace.host_count(name) if run.trace is not None else 0


def decode_program_seconds(run):
    """Device time of the decode program's executables (its XLA regions, which
    hold its kernels), from the XLA Modules line; the driver read the regions'
    names off the program's executed trace."""
    regions = run.stats.get("decode_regions")
    if run.trace is None or not regions:
        return None
    return run.trace.module_seconds("^jit_(" + "|".join(map(re.escape, regions)) + ")$")


def roofline_pct(run, cls: str, cost_per_call):
    """Share of its roofline that class ``cls`` reached: the least time the
    chip could take for the calls (``cost_per_call`` of them each) over the
    time they took. Logs which limit binds."""
    from .harness import say

    seconds, calls = class_time(run, cls)
    if not calls or cost_per_call is None:
        return None
    least, bound = costs.roofline_seconds(cost_per_call, peaks(run.device_kind))
    say(f"{cls}: {calls} calls, {seconds / calls * 1e6:.1f} us each against {least * 1e6:.1f} us "
        f"at the {bound} roofline ({cost_per_call.flops / 1e9:.3f} GFLOP, "
        f"{cost_per_call.bytes / 1e6:.3f} MB a call)")
    return 100.0 * least * calls / seconds


# -- costs of this repo's kernels from what a run knows ---------------------------------

def flash_cost(run, which: str):
    """One flash-attention call of a train step: the per-chip batch over the
    whole sequence."""
    d = run.cell.builder.dims(run.cell.config)
    spec = run.cell.traffic["step"]
    local_batch = max(1, int(spec["batch"]) // run.chips)
    fn = costs.flash_fwd if which == "fwd" else costs.flash_bwd
    T = int(spec["seq_len"])
    return fn(local_batch, d["heads"], d["kv_heads"], T, T, d["head_dim"])


def paged_decode_cost(run):
    """The mean decode call of the window: the requests completed in it say how
    many sequences a step held on average and how long their contexts were (a
    request with ``n`` new tokens took ``n - 1`` decode steps at contexts
    ``prompt + 1 .. prompt + n - 1``)."""
    d = run.cell.builder.dims(run.cell.config)
    done = measured_ok(run)
    steps = run.stats.get("decode_steps", 0)
    tokens = sum(r.n_new - 1 for r in done)
    if not steps or tokens <= 0:
        return None
    context = sum((r.n_new - 1) * r.prompt_len + (r.n_new - 1) * r.n_new / 2 for r in done)
    # the program's own count of tokens committed by decode steps, where the bus was on
    active = run.counters.get("serve.tokens", tokens) / steps
    return costs.paged_decode(active * context / tokens, active, d["heads"], d["kv_heads"],
                              d["head_dim"])


def paged_chunk_cost(run):
    """The mean chunk call over the prompts completed in the window: whole
    chunks of ``chunk_tokens`` and a final one with the remainder's queries."""
    d = run.cell.builder.dims(run.cell.config)
    C = int(run.cell.traffic["engine"]["chunk_tokens"])
    total, calls = costs.Cost(0.0, 0.0), 0
    for r in measured_ok(run):
        if r.prompt_len <= C:
            continue
        for start in range(0, r.prompt_len, C):
            total = total + costs.paged_chunk(min(C, r.prompt_len - start), start, d["heads"],
                                              d["kv_heads"], d["head_dim"])
            calls += 1
    return total.scaled(1.0 / calls) if calls else None
