"""The most rows one held expert got in a decode step over the mean a held expert got, averaged
over steps and double layers (`serve.moe.rows_max`, `serve.moe.rows_held`): how uneven the
routing is. An even router at 4 rows an expert reads about 2 (the largest of 16 Poisson draws)."""
from benchmark.lib import trajectories


def read(run):
    r = trajectories.routing(run)
    if r is None or not r["rows_held"]:
        return None
    held = run.cell.builder.dims(run.cell.config)["experts_held"]
    return r["rows_max"] / (r["rows_held"] / held)
