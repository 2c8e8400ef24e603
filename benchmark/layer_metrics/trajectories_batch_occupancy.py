"""Tokens committed by decode steps over decode steps times max_batch, in percent
(`rollouts_batch_occupancy`'s reduction): how full the packed decode step of 256 slots ran. Under
100 where the page pool, not the slots, bounds admission."""
from benchmark.layer_metrics import rollouts_batch_occupancy


def read(run):
    return rollouts_batch_occupancy.read(run)
