"""Reductions of the cell of the ``parallel_moe`` builder's models (window layers and a full layer
in a parallel block beside held experts): the decode program's device time by kernel class, the
window and full layers' attention against what each reads, the routed experts, the prompt chunks
through the window, the pages the two pool kinds hold, and the share of the chip's peak the decode
steps reach.

``BENCHMARK.json`` lists three of these for the cell (``longctx_decode_device_ms_per_iter``,
``longctx_window_attn_roofline``, ``longctx_decode_mfu``); a traced run logs every one of them on
one line (``log``), so that what the manifest has no room for is read on every traced run all the
same. The decode program's calls are told apart from the mixed program's (a prompt chunk with the
decode rows beside it, which runs every kernel too) by the executable they ran in, as
``lib/rollouts.py`` does.
"""
from __future__ import annotations

import re

from . import costs, costs_parallel_moe, phases, readers, rollouts
from .harness import say
from .peaks import PEAKS
from .reasoning import decode_contexts

# the kernel classes of a window layer's calls and of the full layer's (benchmark/kernels/)
WINDOW_DECODE, FULL_DECODE, WINDOW_CHUNK = "window_decode", "paged_decode", "window_chunk"


def _dims(run) -> dict:
    return run.cell.builder.dims(run.cell.config)


def decode_device_ms_per_iter(run):
    """Device time of the decode program per dispatch: its executables on the XLA Modules line
    over the program's own ``serve_decode`` annotations in the traced window."""
    seconds = readers.decode_program_seconds(run)
    return None if seconds is None else readers.per_unit_ms(seconds, readers.program_runs(run, "serve_decode"))


def attn_ms_per_iter(run, cls: str):
    """Milliseconds a decode-program run of kernel class ``cls``."""
    return rollouts.decode_ms_per_iter(run, lambda k: k == cls)


def attention_cost(run, window):
    """One decode call of one layer's attention, the keys inside each query's window (``window``)
    or all of them (``None``), from the requests the window completed."""
    found = decode_contexts(run, window)
    if found is None:
        return None
    d = _dims(run)
    active, keys = found
    return costs.paged_decode(keys, active, d["heads"], d["kv_heads"], d["head_dim"])


def window_attn_roofline(run):
    return rollouts.decode_roofline_pct(run, WINDOW_DECODE, attention_cost(run, _dims(run)["window"]))


def full_attn_roofline(run):
    return rollouts.decode_roofline_pct(run, FULL_DECODE, attention_cost(run, None))


def moe_roofline(run):
    return rollouts.decode_roofline_pct(run, "ragged_mlp", rollouts.experts_cost(run))


def window_chunk_cost(run):
    """The mean window layer's chunk call over the prompts completed in the window: whole chunks
    of ``chunk_tokens`` and a final one with the remainder's queries."""
    d = _dims(run)
    C = int(run.cell.traffic["engine"]["chunk_tokens"])
    total, calls = costs.Cost(0.0, 0.0), 0
    for r in readers.measured_ok(run):
        for start in range(0, r.prompt_len, C):
            total = total + costs_parallel_moe.paged_chunk(min(C, r.prompt_len - start), start, d["heads"],
                                                           d["kv_heads"], d["head_dim"], d["window"])
            calls += 1
    return total.scaled(1.0 / calls) if calls else None


def window_chunk_roofline(run):
    return readers.roofline_pct(run, WINDOW_CHUNK, window_chunk_cost(run))


def prefill_share_pct(run):
    """Share of the device's program time spent outside the decode program: every executable of a
    region of the program's traces that is not one of the decode program's, over all executables."""
    decode = run.stats.get("decode_regions")
    if run.trace is None or not decode or not run.trace.devices:
        return None
    mine = re.compile("^jit_(" + "|".join(map(re.escape, decode)) + ")$")
    region = re.compile(r"^jit_xla_fusion_\d+$")
    modules = run.trace.devices[0].modules
    every = sum(t for _, t in modules.values())
    other = sum(t for name, (_, t) in modules.items() if region.search(name) and not mine.search(name))
    return 100.0 * other / every if every else None


def idle_ms_per_iter(run):
    split = phases.idle_by_phase(run)
    if split is None:
        return None
    return sum(split[s] for s in phases.SHARES) / 1e6 / phases.iterations(run)


def batch_occupancy(run):
    tokens, steps = run.counters.get("serve.tokens"), run.stats.get("decode_steps")
    if not tokens or not steps:
        return None
    return 100.0 * tokens / (steps * run.stats["max_batch"])


def window_pages_freed_per_iter(run):
    steps = run.stats.get("decode_steps")
    if not steps or "serve.state.window_pages" not in run.counters:
        return None
    return run.counters.get("serve.window_pages_freed", 0) / steps


def window_pool_peak_pct(run):
    """The most window pages in use at one decode step (``serve.state.window_pages_peak``) over
    the window pool's usable pages."""
    peak, usable = run.counters.get("serve.state.window_pages_peak"), run.stats.get("window_pages_usable")
    return 100.0 * peak / usable if peak is not None and usable else None


def kv_kb_per_token(run):
    """Keys and values a cached token takes, in kB, all layers: the pages of both kinds in use
    at each decode step (each kind's pages times its layers, ``page_size`` rows of keys and
    values of every KV head) over the positions the step's sequences had cached. Caching every
    layer's keys of every position would read ``n_layer x 4`` kB at the published widths."""
    c, steps = run.counters, run.counters.get("serve.decode_steps")
    found = decode_contexts(run)
    if not steps or found is None or "serve.state.shared_kv_pages" not in c:
        return None
    d = _dims(run)
    page = int(run.cell.traffic["engine"]["page_size"]) * 2 * d["kv_heads"] * d["head_dim"] * 2
    pages = (c["serve.state.shared_kv_pages"] * d["layers"]["full"]
             + c.get("serve.state.window_pages", 0) * d["layers"]["window"])
    return pages / steps * page / found[1] / 1e3


def decode_mfu(run):
    """Share of the chip's peak the window reached on the tokens its decode steps committed: the
    operations a token needs on this chip (``costs_parallel_moe.decode_flops_per_token``) at the
    window's mean context, over peak x window. The decode rows of a mixed program are counted as
    the decode program's are: ``serve.tokens`` counts every decode row committed."""
    full, window, routed = decode_contexts(run), decode_contexts(run, _dims(run)["window"]), rollouts.routing(run)
    tokens = run.counters.get("serve.tokens")
    if full is None or routed is None or not tokens or not run.window_s or run.device_kind not in PEAKS:
        return None
    d = _dims(run)
    held_per_token = d["experts_per_token"] * routed["rows_held"] / routed["rows_routed"]
    flops = costs_parallel_moe.decode_flops_per_token(d, full[1] / full[0], window[1] / window[0],
                                                      held_per_token)
    return 100.0 * tokens * flops / (PEAKS[run.device_kind].bf16_flops * run.window_s)


# everything a traced run of the cell reads, by the name it would have as a metric
READINGS = {
    "decode_device_ms_per_iter": decode_device_ms_per_iter,
    "idle_ms_per_iter": idle_ms_per_iter,
    "prefill_share_pct": prefill_share_pct,
    "batch_occupancy": batch_occupancy,
    "window_attn_ms_per_iter": lambda run: attn_ms_per_iter(run, WINDOW_DECODE),
    "window_attn_roofline": window_attn_roofline,
    "full_attn_ms_per_iter": lambda run: attn_ms_per_iter(run, FULL_DECODE),
    "full_attn_roofline": full_attn_roofline,
    "window_chunk_roofline": window_chunk_roofline,
    "moe_roofline": moe_roofline,
    "window_pages_freed_per_iter": window_pages_freed_per_iter,
    "window_pool_peak_pct": window_pool_peak_pct,
    "kv_kb_per_token": kv_kb_per_token,
    "decode_mfu": decode_mfu,
}


def log(run) -> dict:
    """Every reading of ``READINGS`` on one log line; returns them."""
    got = {}
    for name, read in READINGS.items():
        try:
            got[name] = read(run)
        except Exception as e:  # one reading that fails costs the line nothing else
            got[name] = f"failed: {type(e).__name__}: {e}"
    say("longctx readings: " + ", ".join(f"{k} {v}" for k, v in got.items()))
    return got
