"""How often the serving loop's plain decode path had a step in flight when it dispatched the next.

With its bus on, the program counts once a decode step (`thunder_tpu/serving/scheduler.py:
ServingEngine._dispatch`): `serve.decode_steps` for every step it dispatches, and
`serve.decode_overlapped` for a step dispatched while the step before it was still unfetched,
so that the chip had the next program before the host read the last one's tokens. A step is not
overlapped when nothing was in flight: the first after an activation (a prefill's first token
comes from the host and lands the step in flight), after a preemption or a failure.
`serve.decode_discarded` counts the tokens of such steps thrown away at their commit, because the
sequence had ended in between (`eos_id`, a cancelled request); `serve.tokens` never holds them.
An engine that generates by diffusion over blocks counts its passes the same way. A traced serve
run logs the share (`bench: decode overlapped % ...`).
"""
from .harness import say


def overlapped_pct(run):
    """100 x overlapped / decode steps over the window; None where the program has no
    `serve.decode_overlapped` (a program older than the counter: the loop was synchronous) or
    the window had no decode step. A loop that has the counter and never overlapped reads 0."""
    steps = run.counters.get("serve.decode_steps", 0)
    if "serve.decode_overlapped" not in run.counters or not steps:
        return None
    return 100.0 * run.counters["serve.decode_overlapped"] / steps


def log(run) -> None:
    say(f"decode overlapped % {overlapped_pct(run)} (serve.decode_overlapped "
        f"{run.counters.get('serve.decode_overlapped')} of serve.decode_steps "
        f"{run.counters.get('serve.decode_steps', 0)})")
