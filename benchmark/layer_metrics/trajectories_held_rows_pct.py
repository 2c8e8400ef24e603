"""Share of the rows the decode steps routed (live tokens x 12) that chose an expert held here,
in percent: `serve.moe.rows_held` over `serve.moe.rows_routed`. With 16 of 768 outputs held and
an even router it reads about 2.1; a third chose identity experts and the rest left for the 31
absent chips."""
def read(run):
    routed = run.counters.get("serve.moe.rows_routed")
    return 100.0 * run.counters.get("serve.moe.rows_held", 0) / routed if routed else None
