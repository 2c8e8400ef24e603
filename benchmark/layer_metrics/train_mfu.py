"""Model FLOP/s utilization: tokens/s/chip times the operations a trained token needs
(benchmark/lib/costs.py) over the chip's bf16 peak. Recomputed operations do not count."""
def read(run):
    mfu = run.stats.get("mfu")
    return None if mfu is None else 100.0 * mfu
