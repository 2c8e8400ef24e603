"""Share of the window's passes over blocks that were dispatched while the pass before them was
still unfetched (`serve.decode_overlapped` over `serve.decode_steps`, which count passes here)."""
from benchmark.lib import denoise, overlap


def read(run):
    return None if denoise.passes(run) is None else overlap.overlapped_pct(run)
