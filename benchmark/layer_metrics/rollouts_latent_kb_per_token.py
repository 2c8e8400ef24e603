"""Latent cache a cached token takes, in kB, all layers: the pages in use at each decode step
(`serve.state.latent_pages`, which counts the pages a sequence has reserved for its whole answer)
times a page's bytes (page_size rows of the stored width, 2 bytes a number) over the rows the
step's sequences had cached (from the requests the window completed). Per-head keys and values
of 32 heads would be 98 kB a token."""
from benchmark.lib import rollouts


def read(run):
    pages, steps = run.counters.get("serve.state.latent_pages"), run.counters.get("serve.decode_steps")
    found = rollouts.decode_contexts(run)
    if not pages or not steps or found is None:
        return None
    d = run.cell.builder.dims(run.cell.config)
    page = int(run.cell.traffic["engine"]["page_size"]) * d["latent_row"] * 2
    return pages / steps * page * d["n_layer"] / found[1] / 1e3
