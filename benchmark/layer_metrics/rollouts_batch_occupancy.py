"""Tokens committed by decode steps over decode steps times max_batch, in percent: how full
the packed decode step ran."""
def read(run):
    tokens, steps = run.counters.get("serve.tokens"), run.stats.get("decode_steps")
    if not tokens or not steps:
        return None
    return 100.0 * tokens / (steps * run.stats["max_batch"])
