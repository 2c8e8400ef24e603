"""Share of the chip's peak the window reached on the tokens its decode steps committed: the
operations a token needs on this chip by the builder's own count (`lib/costs_shortcut_moe.py`:
two a matrix-multiplied weight of both halves of every double layer with the held REAL experts
a token chose, NOTHING for an identity expert, latent attention by context in all eight blocks)
at the mean context of the window's decode steps, over peak x window."""
from benchmark.lib import costs_shortcut_moe, rollouts, trajectories
from benchmark.lib.peaks import PEAKS


def read(run):
    found, routed = rollouts.decode_contexts(run), trajectories.routing(run)
    tokens = run.counters.get("serve.tokens")
    if found is None or routed is None or not tokens or not run.window_s or run.device_kind not in PEAKS:
        return None
    active, rows = found
    d = run.cell.builder.dims(run.cell.config)
    held_per_token = d["experts_per_token"] * routed["rows_held"] / routed["rows_routed"]
    flops = costs_shortcut_moe.decode_flops_per_token(d, rows / active, held_per_token)
    return 100.0 * tokens * flops / (PEAKS[run.device_kind].bf16_flops * run.window_s)
