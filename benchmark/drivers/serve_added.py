"""Driver ``serve_added``: the ``serve`` driver for a model that keeps recurrent state, with a
correctness sample that also holds that state to the reference's.

Everything that offers load and measures is ``drivers/serve.py``'s. What differs is the sample
(``check_sample``):

* ``serve``'s number, how far the engine's chosen tokens sit under the reference's top logit,
  does not see a scan state: an engine that keeps it in bfloat16 reads what one that keeps it in
  float32 reads (PERF.md, PR 27). So after each request has been served alone, the rows the engine
  holds for it (``engine.cache.state``, the request's slot) are compared with the scan state the
  reference reaches at the same position, as a relative distance, twice over. Whole, every
  scanning layer, within ``correctness.state_margin``: that catches a state that is wrong (a
  slot not started from zero, padding that moved it, a chunk edge) and not one that is coarse,
  because the bfloat16 activations that drive the scan put either engine 2 to 5% from the
  reference in the deeper layers. And the first scanning layer alone (its input is the
  embedding, so it carries the least rounding from upstream), over the quarter of its channels
  that forget most slowly (the smallest mean step size, where a state rounded once a program
  adds up its roundings over the most steps), within ``correctness.slow_state_margin``: that one
  tells the two precisions apart.
* ``correctness.state_request`` is one more request, served alone after the sample for its state
  only: a short prompt and as many one-token updates as the traffic's shortest answer. Its
  tokens are not judged: the largest of 256 distances from the top logit is another quantity
  than the largest of 24 and would take a looser ``margin`` for every request.
* the reference runs one layer at a time, each layer's weights cast to float32 for that layer
  only, and the head in blocks of rows, so that it adds next to nothing to a process that holds
  7.7 GB of bfloat16 weights on a 16 GB chip.

The reference has to export its blocks for that: ``layer_kinds``, ``layer_params``, ``embed``,
``layer`` (handing back ``state`` and ``step`` for the layers that scan) and ``head``.

A name of its own, and not ``serve`` with an option, because a PR that adds a cell may edit no
file that is there, and because two test files of the harness (``test_phases.py``,
``test_pool_donated.py``) take every cell of the driver ``serve`` for the chat cell or else the
long-prompt cell. ``run`` and ``set_up`` are ``serve``'s with this sample in the place of its
own; a ``benchmark`` PR that lets ``serve.set_up`` take the sample from the cell's driver makes
that one line.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from benchmark.drivers import serve
from benchmark.drivers.serve import PROGRAMS, build_engine, check_kernels, serve_all  # noqa: F401
from benchmark.lib import harness, loadgen

# rows of the tied table a block of the reference's head takes
HEAD_ROWS = 25_008


def served_state(engine, layers) -> dict:
    """``{layer: scan state}`` of the sequence that was just served alone on an idle engine (it
    took the first slot, and its rows stay until a prefill starts another sequence there): the
    first of the arrays a recurrent layer declares, as float32 on the host."""
    return {i: np.asarray(engine.cache.state[i][0][0], np.float32) for i in layers}


class BlockwiseReference:
    """``cell.reference`` run one jitted block at a time on the served parameters."""

    def __init__(self, cell, params):
        import jax

        ref, config = cell.reference, cell.config
        self.ref, self.params = ref, params
        self.kinds = ref.layer_kinds(config)
        self.half = config["num_hidden_layers"] // 2
        self.embed = jax.jit(lambda prm, toks: ref.embed(config, prm, toks))
        self.layer = jax.jit(
            lambda kind, index, prm, x, memory, kv, at: ref.layer(config, kind, index, prm, x, memory=memory,
                                                                  kv=kv, state_at=at),
            static_argnums=0)
        self.head = jax.jit(lambda prm, x: ref.head(config, prm, x))

    def __call__(self, tokens, rows, state_at: int) -> tuple:
        """``(logits at rows, {layer: (scan state after position state_at, mean step size of each
        channel up to there)})``."""
        prm = self.params
        x = self.embed({"wte.weight": prm["wte.weight"]}, tokens)
        memory = kv = None
        states = {}
        for i, kind in enumerate(self.kinds):
            x, made = self.layer(kind, np.int32(i), self.ref.layer_params(prm, i), x, memory, kv,
                                 np.int32(state_at))
            if i == self.half:
                memory = made["memory"]
            kv = made.get("kv", kv)
            if "state" in made:
                states[i] = (np.asarray(made["state"]), np.asarray(made["step"]))
        x = x[rows]
        table = prm["wte.weight"]
        top = {k: prm[k] for k in ("ln_f.weight", "ln_f.bias")}
        logits = [np.asarray(self.head(dict(top, **{"wte.weight": table[a:a + HEAD_ROWS]}), x))
                  for a in range(0, table.shape[0], HEAD_ROWS)]
        return np.concatenate(logits, axis=-1), states


def distance(rows, want) -> float:
    return float(np.linalg.norm(rows - want) / np.linalg.norm(want))


def check_sample(cell, engine, seed: int, notes: list) -> dict:
    """``serve.check_sample`` (alone equals batched; every chosen token within ``margin`` of the
    reference's top logit) and, for each request of the sample and for ``state_request``, the
    scan state the engine keeps after serving it alone against the reference's, as
    ``|engine - reference| / |reference|`` (Frobenius norms): the largest over the requests and
    the scanning layers within ``state_margin``, and the largest over the requests of the first
    scanning layer's slowest quarter of channels within ``slow_state_margin``."""
    spec = cell.traffic["correctness"]
    vocab = cell.config["vocab_size"]
    reqs = [(int(p), int(n)) for p, n in spec["requests"]]
    every = reqs + [tuple(int(v) for v in spec["state_request"])]
    prompts = [loadgen.prompt_tokens(seed, 1_000_000 + i, p, vocab) for i, (p, _) in enumerate(every)]
    from thunder_tpu.serving.kv_pages import Recurrent

    scanning = [i for i, kept in enumerate(engine.cache.layers) if isinstance(kept, Recurrent)]
    t0 = time.perf_counter()
    alone, kept = [], []
    for p, (_, n) in zip(prompts, every):
        alone.append(serve_all(engine, [p], [n])[0])
        kept.append(served_state(engine, scanning))
    together = serve_all(engine, prompts[:len(reqs)], [n for _, n in reqs])
    differ = 0
    for i, (a, b) in enumerate(zip(alone, together)):
        if a.n_new_tokens != reqs[i][1] or not np.array_equal(a.new_tokens, b.new_tokens):
            notes.append(f"sample request {i} {reqs[i]}: alone and batched outputs differ")
            differ += 1

    t1 = time.perf_counter()
    reference = BlockwiseReference(cell, engine.params)
    t_max = max(p + n for p, n in every)
    n_max = max(n for _, n in every)
    worst, slow, by_layer = 0.0, 0.0, {i: 0.0 for i in scanning}
    for j, ((p, n), res, state) in enumerate(zip(every, alone, kept)):
        toks = np.zeros((t_max,), np.int32)
        toks[:p + n] = res.tokens
        # the logits that chose output token j are those at position p + j - 1; the last token
        # is handed back and never fed, so the engine's state is the one after position p + n - 2
        rows = np.minimum(np.arange(n_max) + p - 1, p + n - 2).astype(np.int32)
        logits, want = reference(toks, rows, p + n - 2)
        if j < len(reqs):
            gap = logits[:n].max(axis=-1) - logits[np.arange(n), res.new_tokens]
            worst = max(worst, float(gap.max()))
        for i in scanning:
            by_layer[i] = max(by_layer[i], distance(state[i], want[i][0]))
        first, step = want[scanning[0]]
        slowest = step <= np.quantile(step, 0.25)
        slow = max(slow, distance(state[scanning[0]][slowest], first[slowest]))
    apart = max(by_layer.values())
    margin, state_margin, slow_margin = (float(spec[k]) for k in ("margin", "state_margin",
                                                                  "slow_state_margin"))
    harness.say(f"correctness sample: {len(reqs)} requests alone == batched; largest distance of a "
                f"chosen token from the reference's top logit {worst:.4f} (margin {margin}); largest "
                f"relative distance of a scan state from the reference's {apart:.3g} (margin "
                f"{state_margin}), by layer " + ", ".join(f"{i}: {d:.3g}" for i, d in by_layer.items())
                + f"; of layer {scanning[0]}'s slowest channels {slow:.3g} (margin {slow_margin})")
    harness.say(f"correctness sample: served in {t1 - t0:.1f} s, reference in blocks "
                f"{time.perf_counter() - t1:.1f} s")
    if not worst <= margin:
        notes.append(f"a chosen token is {worst} below the reference's top logit (margin {margin})")
    if not apart <= state_margin:
        notes.append(f"a scan state is {apart} from the reference's, relative (margin {state_margin})")
    if not slow <= slow_margin:
        notes.append(f"the slowest channels of layer {scanning[0]}'s scan state are {slow} from the "
                     f"reference's, relative (margin {slow_margin}): a state kept coarser than float32")
    return {"sample_margin": worst, "sample_differ": differ, "sample_state_distance": apart,
            "sample_slow_state_distance": slow}


@contextlib.contextmanager
def _own_sample():
    """``serve.set_up`` finds ``check_sample`` in its own module: this one, for a while."""
    theirs, serve.check_sample = serve.check_sample, check_sample
    try:
        yield
    finally:
        serve.check_sample = theirs


def set_up(cell, seed: int, notes: list):
    with _own_sample():
        return serve.set_up(cell, seed, notes)


def run(cell, opts, env) -> harness.Run:
    with _own_sample():
        run = serve.run(cell, opts, env)
    # check_sample has put its notes down; the numbers go beside the others that decide `correct`
    spec = cell.traffic["correctness"]
    harness.held(run.compared, "sample_state_distance", run.stats["sample_state_distance"], "<=",
                 float(spec["state_margin"]))
    harness.held(run.compared, "sample_slow_state_distance", run.stats["sample_slow_state_distance"],
                 "<=", float(spec["slow_state_margin"]))
    return run
