"""Driver ``serve_rollouts``: the ``serve`` driver for a model that caches LATENT rows (multi-head
latent attention) and holds a share of its experts, with a correctness sample that also holds the
cached rows to the reference's.

Everything that offers load and measures is ``drivers/serve.py``'s. What differs is the sample
(``check_sample``):

* ``serve``'s two numbers as they are: the requests give identical tokens served alone and served
  together, and every chosen token lies within ``correctness.margin`` of the reference's top logit.
  The engine decodes in the ABSORBED form of latent attention and the reference expands per-head
  keys and values at every position, so the margin holds one form of the mathematics to the other.
* after each request has been served alone, the rows the engine holds for it (``engine.cache.state``,
  the pages the result names: a retired request's rows stay as written until another sequence
  takes the pages) against the ``c_kv`` and ``k_rope`` the reference computes at the same
  positions, as a relative distance, twice over. The LAST layer's within
  ``correctness.latent_margin``: those rows have been through every expert layer before them, so
  an expert left out, a weight misapplied or a row written to the wrong place shows there; they
  also carry every near-tie of a router that the bfloat16 activations tipped the other way, so
  that margin is wide. And the FIRST layer's within ``correctness.first_latent_margin``: its
  input is the embedding, so it carries one projection's rounding and nothing else, and a cache
  kept in a narrower type than the activations, a wrong norm or a wrong rope shows there. The
  padding columns of every row must be zero.
* the reference runs one layer at a time on the served bfloat16 parameters (its experts one at a
  time, its heads one at a time, its head in blocks of rows), so that it adds a few hundred MB
  to a process that holds 10.85 GB of weights and 2.4 GB of cache on a 16 GB chip.

A name of its own, and not ``serve`` with an option, for the reasons ``serve_added.py`` gives (a PR
that adds a cell edits no file that is there; two test files of the harness take every ``serve``
cell for the chat or the long-prompt cell), and not ``serve_added`` because that driver's sample
is the scan state's. ``run`` and ``set_up`` are ``serve``'s with this sample in its place.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from benchmark.drivers import serve
from benchmark.drivers.serve import PROGRAMS, build_engine, check_kernels, serve_all  # noqa: F401
from benchmark.lib import harness, loadgen

# rows of the head a block of the reference's head takes
HEAD_ROWS = 8192


def served_rows(engine, layer: int, pages, n: int) -> np.ndarray:
    """The first ``n`` rows layer ``layer`` caches on ``pages`` (a retired request's, in position
    order), whole rows (padding included), float32 on the host."""
    pool = engine.cache.state[layer][0]
    return np.asarray(pool[np.asarray(pages, np.int32)], np.float32).reshape(-1, pool.shape[-1])[:n]


class BlockwiseReference:
    """``cell.reference`` run one jitted block at a time on the served parameters."""

    def __init__(self, cell, params):
        import jax

        ref, config = cell.reference, cell.config
        self.ref, self.params, self.n_layer = ref, params, config["num_hidden_layers"]
        self.embed = jax.jit(lambda prm, toks: ref.embed(config, prm, toks))
        self.layer = jax.jit(lambda prm, x: ref.layer(config, prm, x))
        self.head = jax.jit(lambda prm, x: ref.head(config, prm, x))

    def __call__(self, tokens, rows) -> tuple:
        """``(logits at rows, [each layer's latent rows {"c_kv", "k_rope"} at every position])``."""
        prm = self.params
        x = self.embed({"wte.weight": prm["wte.weight"]}, tokens)
        made = []
        for i in range(self.n_layer):
            x, rows_i = self.layer(self.ref.layer_params(prm, i), x)
            made.append({k: np.asarray(v) for k, v in rows_i.items()})
        x = x[rows]
        table = prm["lm_head.weight"]
        logits = [np.asarray(self.head({"ln_f.weight": prm["ln_f.weight"],
                                        "lm_head.weight": table[a:a + HEAD_ROWS]}, x))
                  for a in range(0, table.shape[0], HEAD_ROWS)]
        return np.concatenate(logits, axis=-1), made


def distance(rows, want) -> float:
    return float(np.linalg.norm(rows - want) / np.linalg.norm(want))


def check_sample(cell, engine, seed: int, notes: list) -> dict:
    """``serve.check_sample`` (alone equals batched; every chosen token within ``margin`` of the
    reference's top logit) and, for each request of the sample, the latent rows the engine keeps
    after serving it alone against the reference's ``[c_kv | k_rope]`` of the same layer, as
    ``|engine - reference| / |reference|`` (Frobenius norms) over the rows the request's programs
    wrote, the largest over the requests: the FIRST layer's within ``first_latent_margin`` and
    the LAST layer's within ``latent_margin`` (the traffic file says what each can see)."""
    spec = cell.traffic["correctness"]
    vocab = cell.config["vocab_size"]
    reqs = [(int(p), int(n)) for p, n in spec["requests"]]
    prompts = [loadgen.prompt_tokens(seed, 1_000_000 + i, p, vocab) for i, (p, _) in enumerate(reqs)]
    from thunder_tpu.serving.kv_pages import PagedLatent

    latent = [i for i, kept in enumerate(engine.cache.layers) if isinstance(kept, PagedLatent)]
    width = engine.cache.layers[latent[0]].width
    t0 = time.perf_counter()
    alone, kept = [], []
    for prompt, (p, n) in zip(prompts, reqs):
        res = serve_all(engine, [prompt], [n])[0]
        alone.append(res)
        # the last token is handed back and never fed: rows 0 .. p + n - 2 are written
        kept.append({i: served_rows(engine, i, res.pages, p + n - 1) for i in latent})
    together = serve_all(engine, prompts, [n for _, n in reqs])
    differ = 0
    for i, (a, b) in enumerate(zip(alone, together)):
        if a.n_new_tokens != reqs[i][1] or not np.array_equal(a.new_tokens, b.new_tokens):
            notes.append(f"sample request {i} {reqs[i]}: alone and batched outputs differ")
            differ += 1

    t1 = time.perf_counter()
    reference = BlockwiseReference(cell, engine.params)
    t_max = max(p + n for p, n in reqs)
    n_max = max(n for _, n in reqs)
    worst, padding, by_layer = 0.0, 0.0, {i: 0.0 for i in latent}
    for (p, n), res, rows in zip(reqs, alone, kept):
        toks = np.zeros((t_max,), np.int32)
        toks[:p + n] = res.tokens
        # the logits that chose output token j are those at position p + j - 1
        at = np.minimum(np.arange(n_max) + p - 1, p + n - 2).astype(np.int32)
        logits, made = reference(toks, at)
        gap = logits[:n].max(axis=-1) - logits[np.arange(n), res.new_tokens]
        worst = max(worst, float(gap.max()))
        for i in latent:
            want = np.concatenate([made[i]["c_kv"], made[i]["k_rope"]], axis=-1)[:p + n - 1]
            ok = len(rows[i]) == len(want)  # a result without its pages reads as not a number
            by_layer[i] = max(by_layer[i], distance(rows[i][:, :width], want)) if ok else float("nan")
            if rows[i].shape[1] > width:
                padding = max(padding, float(np.abs(rows[i][:, width:]).max()))
    first, apart = by_layer[latent[0]], by_layer[latent[-1]]
    margin, first_margin, latent_margin = (float(spec[k]) for k in ("margin", "first_latent_margin",
                                                                    "latent_margin"))
    harness.say(f"correctness sample: {len(reqs)} requests alone == batched; largest distance of a "
                f"chosen token from the reference's top logit {worst:.4f} (margin {margin}); largest "
                f"relative distance of the cached latent rows from the reference's, by layer "
                + ", ".join(f"{i}: {d:.3g}" for i, d in by_layer.items())
                + f" (margins {first_margin} in layer {latent[0]}, {latent_margin} in layer {latent[-1]}); "
                f"largest padding column {padding:g}")
    harness.say(f"correctness sample: served in {t1 - t0:.1f} s, reference in blocks "
                f"{time.perf_counter() - t1:.1f} s")
    if not worst <= margin:
        notes.append(f"a chosen token is {worst} below the reference's top logit (margin {margin})")
    if not first <= first_margin:
        notes.append(f"the latent rows layer {latent[0]} caches are {first} from the reference's, "
                     f"relative (margin {first_margin}): a cache coarser than the activations, or a "
                     f"wrong norm or rope")
    if not apart <= latent_margin:
        notes.append(f"the latent rows layer {latent[-1]} caches are {apart} from the reference's, "
                     f"relative (margin {latent_margin})")
    if padding != 0.0:
        notes.append(f"a padding column of a cached latent row holds {padding}, not zero")
    return {"sample_margin": worst, "sample_differ": differ, "sample_latent_distance": apart,
            "sample_first_latent_distance": first, "sample_latent_padding": padding,
            "sample_latent_by_layer": by_layer}


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
    harness.held(run.compared, "sample_first_latent_distance", run.stats["sample_first_latent_distance"],
                 "<=", float(spec["first_latent_margin"]))
    harness.held(run.compared, "sample_latent_distance", run.stats["sample_latent_distance"], "<=",
                 float(spec["latent_margin"]))
    harness.held(run.compared, "sample_latent_padding", run.stats["sample_latent_padding"], "==", 0.0)
    return run
