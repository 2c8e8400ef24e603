"""Reductions the `denoise_*` readers share: what the engine's passes over blocks counted
(`serve.block_*`, `serve.tokens`, the routing counters), the mean pass's contexts from the requests
the window completed, and the block program's device time by kernel class (`lib/rollouts.py`'s
split, over the regions `drivers/serve_denoise.py` read off the block program's executed trace).

Counters of a pass (thunder_tpu/serving/scheduler.py: `_commit_block_pass`): `serve.block_passes`
passes fetched; `serve.block_slot_passes` the same summed over the live sequences each carried;
`serve.block_slot_commits` / `serve.blocks_done` those of them that were a sequence's commit pass;
`serve.tokens` positions unmasked. A traced run also logs here what BENCHMARK.json has no room
to list (the manifest holds 128 per-layer entries at most and had 120): `bench: denoise: ...`.
"""
from __future__ import annotations

from . import costs_block_moe, readers, rollouts
from .harness import say

PROGRAM = "serve_block"


def passes(run):
    """``(passes, slot passes, blocks done, tokens)`` of the window, or None where the program
    counted no pass over blocks."""
    c = run.counters
    if not c.get("serve.block_passes") or not c.get("serve.block_slot_passes"):
        return None
    return (c["serve.block_passes"], c["serve.block_slot_passes"], c.get("serve.blocks_done", 0),
            c.get("serve.tokens", 0))


def routing(run):
    """The window's routing counters as means a pass and layer."""
    c, found = run.counters, passes(run)
    if found is None or not c.get("serve.moe.rows_routed"):
        return None
    calls = found[0] * run.cell.builder.dims(run.cell.config)["n_layer"]
    return {k: c.get("serve.moe." + k, 0) / calls
            for k in ("rows_routed", "rows_held", "experts_touched", "rows_max")}


def contexts(run):
    """``(sequences a pass, key positions a pass)`` of the mean pass, from the requests the
    window completed: a request's blocks end at ``floor(prompt / K) * K + K, + 2 K, ..`` and a
    pass of a block sees every position up to the block's end."""
    found = passes(run)
    done = readers.measured_ok(run)
    if found is None or not done:
        return None
    K = run.cell.builder.dims(run.cell.config)["block_length"]
    ends = [e for r in done
            for e in range(r.prompt_len // K * K + K, -(-(r.prompt_len + r.n_new) // K) * K + 1, K)]
    active = found[1] / found[0]
    return active, active * sum(ends) / len(ends)


def experts_cost(run):
    r = routing(run)
    if r is None:
        return None
    d = run.cell.builder.dims(run.cell.config)
    return costs_block_moe.ragged_experts(r["rows_held"], r["experts_touched"], d["d_model"],
                                          d["expert_width"])


def attention_cost(run):
    found = contexts(run)
    if found is None:
        return None
    d = run.cell.builder.dims(run.cell.config)
    active, keys = found
    return costs_block_moe.block_attention(keys, active, d["block_length"], d["heads"], d["kv_heads"],
                                           d["head_dim"])


def roofline_pct(run, cls: str, cost):
    """``rollouts.decode_roofline_pct`` over the block program's regions, and once a run the log
    of what the manifest has no room for."""
    log_rest(run)
    return rollouts.decode_roofline_pct(run, cls, cost)


def log_rest(run) -> None:
    """The cell's readings BENCHMARK.json does not list, on one `bench: denoise:` line each."""
    if "denoise_logged" in run.traced:
        return
    run.traced["denoise_logged"] = True
    found, c = passes(run), run.counters
    if found is None:
        return
    n, slot, blocks, tokens = found
    d = run.cell.builder.dims(run.cell.config)
    say(f"denoise: {n} passes in the window, {slot / n:.2f} live sequences a pass of "
        f"{run.stats.get('max_batch')} slots ({100.0 * slot / n / run.stats.get('max_batch', 1):.1f}% "
        f"occupancy); commit passes {100.0 * c.get('serve.block_slot_commits', 0) / slot:.2f}% of "
        f"sequence passes; {tokens / run.window_s:.1f} tokens unmasked a second; "
        f"{c.get('serve.decode_discarded', 0)} sequence passes thrown away")
    r = routing(run)
    if r is not None:
        say(f"denoise: routing a pass and layer: {r['rows_routed']:.1f} rows, {r['experts_touched']:.1f} "
            f"of {d['experts_held']} experts with a row ({100.0 * r['experts_touched'] / d['experts_held']:.1f}%), "
            f"the most on one {r['rows_max']:.1f} (max over mean "
            f"{r['rows_max'] * d['experts_held'] / max(r['rows_held'], 1e-9):.2f})")
    peak = readers.page_pool_peak_pct(run)
    if peak is not None:
        say(f"denoise: page pool peak {peak:.1f}%")
    split = rollouts.program_split(run, "decode_regions", PROGRAM)
    runs = readers.program_runs(run, PROGRAM)
    seconds = readers.decode_program_seconds(run)
    if split and runs:
        xla = sum(s for k, (s, _) in split.items() if k == "other")
        say(f"denoise: block program {seconds / runs * 1e3 if seconds else float('nan'):.3f} ms of device "
            f"time a pass over {runs} traced passes; ragged_mlp "
            f"{split.get('ragged_mlp', (0.0, 0))[0] / runs * 1e3:.3f}, paged_chunk "
            f"{split.get('paged_chunk', (0.0, 0))[0] / runs * 1e3:.3f}, rms_norm "
            f"{split.get('rms_norm', (0.0, 0))[0] / runs * 1e3:.3f}, XLA regions {xla / runs * 1e3:.3f} ms a pass")
    if run.trace is not None and run.trace.devices and runs:
        import re

        modules = run.trace.devices[0].modules  # executable -> (runs, time), the units `every` has
        regions = re.compile("^jit_(" + "|".join(map(re.escape, run.stats.get("decode_regions") or ["$^"])) + ")$")
        every = sum(t for _, t in modules.values())
        block = sum(t for name, (_, t) in modules.items() if regions.search(name))
        unmask = sum(t for name, (_, t) in modules.items() if name == "jit_serve_unmask")
        if block and every:
            say(f"denoise: of the device's program time the block program is {100.0 * block / every:.1f}%, its "
                f"sampler (jit_serve_unmask, scope unmask) {100.0 * unmask / every:.1f}% "
                f"({unmask / block * (seconds or 0.0) / runs * 1e3:.3f} ms a pass), prompts and the rest "
                f"{100.0 * (1 - (block + unmask) / every):.1f}%")
