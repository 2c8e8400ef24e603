"""Share of the chip's peak the window reached on the ROWS its passes computed, commit passes
included: a row's operations by the builder's own count (`lib/costs_block_moe.py: flops_per_row`)
at the mean pass's context, times K rows a sequence pass, over peak x window.
`denoise_tokens_per_pass` beside it says how much of that was delivered as tokens."""
from benchmark.lib import costs_block_moe, denoise
from benchmark.lib.peaks import PEAKS


def read(run):
    found, ctx = denoise.passes(run), denoise.contexts(run)
    if found is None or ctx is None or not run.window_s or run.device_kind not in PEAKS:
        return None
    d = run.cell.builder.dims(run.cell.config)
    active, keys = ctx
    rows = found[1] * d["block_length"]
    flops = costs_block_moe.flops_per_row(d, keys / active)
    return 100.0 * rows * flops / (PEAKS[run.device_kind].bf16_flops * run.window_s)
