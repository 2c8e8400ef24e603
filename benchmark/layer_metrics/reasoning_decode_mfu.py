"""Share of the chip's peak the window reached on the tokens its decode steps committed: the
operations a token needs by the builder's own count (two a matrix-multiplied weight, attention by
context, the scan) at the mean context of the window's decode steps, over peak x window."""
from benchmark.lib import costs_sambay, reasoning
from benchmark.lib.peaks import PEAKS


def read(run):
    found = reasoning.decode_contexts(run)
    tokens = run.counters.get("serve.tokens")
    if found is None or not tokens or not run.window_s or run.device_kind not in PEAKS:
        return None
    active, keys = found
    flops = costs_sambay.decode_flops_per_token(run.cell.builder.dims(run.cell.config), keys / active)
    return 100.0 * tokens * flops / (PEAKS[run.device_kind].bf16_flops * run.window_s)
