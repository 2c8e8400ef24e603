"""Share of the rows the decode steps routed (live tokens x 12) that chose a zero-compute
(identity) expert, in percent: `serve.moe.rows_zero` over `serve.moe.rows_routed`. With 256 of
the router's 768 outputs identity experts and an even router it reads about 33: a third of a
token's choices cost no expert's weights."""
def read(run):
    routed = run.counters.get("serve.moe.rows_routed")
    if not routed or "serve.moe.rows_zero" not in run.counters:
        return None
    return 100.0 * run.counters["serve.moe.rows_zero"] / routed
