"""Positions unmasked a pass and live sequence: `serve.tokens` over `serve.block_slot_passes`
(a block of K tokens costs its denoise passes and one commit pass: 4 / 3 = 1.33 where every
block takes two denoise passes)."""
from benchmark.lib import denoise


def read(run):
    found = denoise.passes(run)
    return None if found is None else found[3] / found[1]
