"""Passes a committed block took, its commit pass included: `serve.block_slot_passes` over
`serve.blocks_done`."""
from benchmark.lib import denoise


def read(run):
    found = denoise.passes(run)
    return None if found is None or not found[2] else found[1] / found[2]
