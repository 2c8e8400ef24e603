"""Share of the rows the decode steps routed (live tokens x experts a token) that chose an
expert held here, in percent: `serve.moe.rows_held` over `serve.moe.rows_routed`. With 32 of 128
experts held and an even router it reads about 25; the rest left for the absent chips."""
def read(run):
    routed = run.counters.get("serve.moe.rows_routed")
    return 100.0 * run.counters.get("serve.moe.rows_held", 0) / routed if routed else None
