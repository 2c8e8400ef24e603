"""The most rows one held expert got in a decode step over the mean a held expert got, averaged
over steps and layers (`serve.moe.rows_max`, `serve.moe.rows_held`): how uneven the routing is.
An even router at 4 rows an expert reads about 2.5 (the largest of 32 Poisson draws)."""
from benchmark.lib import rollouts


def read(run):
    r = rollouts.routing(run)
    if r is None or not r["rows_held"]:
        return None
    held = run.cell.builder.dims(run.cell.config)["experts_held"]
    return r["rows_max"] / (r["rows_held"] / held)
