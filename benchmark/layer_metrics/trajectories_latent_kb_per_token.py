"""Latent cache a cached token takes, in kB, all eight pools: the pages in use at each decode
step (`serve.state.latent_pages`, which counts the pages a sequence has reserved for its whole
answer) times a page's bytes (64 rows of 640 stored numbers, 2 bytes each) over the rows the
step's sequences had cached (`rollouts_latent_kb_per_token`'s reduction; the builder's `n_layer`
counts the pools). Per-head keys and values of 64 heads would be 328 kB a token."""
from benchmark.layer_metrics import rollouts_latent_kb_per_token


def read(run):
    return rollouts_latent_kb_per_token.read(run)
