"""Driver ``serve_longctx``: the ``serve`` driver for a model whose layers cache keys and values in
two kinds of pool, a window's and every position's (``parallel_moe``: three window layers, then a
full one, beside held experts), with a correctness sample that also holds the cached keys and the
window pages to the reference.

Everything that offers load and measures is ``drivers/serve.py``'s. What differs is the sample
(``check_sample``) and what a traced run logs (``lib/longctx.py``):

* ``serve``'s two numbers as they are: the requests give identical tokens served alone and served
  together, and every chosen token lies within ``correctness.margin`` of the reference's top
  logit.
* after each request has been served alone, the keys the engine holds for it (``engine.cache.state``
  on the pages the result names: a retired request's rows stay as written until another sequence
  takes the pages) against the keys the reference computes at the same positions, as a relative
  distance, twice over. The FIRST window layer's within ``correctness.window_key_margin``, on the
  window pages the request held when it retired: its input is the embedding, so it carries one
  projection's rounding and the rope, and a cache kept in a narrower type, rope on other pairs
  or at other positions shows there. The FULL layer's within ``correctness.full_key_margin``, on
  every position: its input has been through every window layer and expert layer before it, so a
  window left off, an expert left out or a row written to the wrong place shows there.
* the window pages a request held: never more at once than ``(window + chunk_tokens) /
  page_size`` (a chunk's queries see the window before its first one and write their own pages:
  ``pages_limit``), and at its end the pages of its last query's window and no others.
* the reference runs one layer at a time on the served bfloat16 parameters (its query heads one
  at a time in blocks of rows, its experts one at a time, its head in blocks of rows), so that it
  adds about a GB to a process that holds 9.47 GB of weights and 3.5 GB of pools on a 16 GB chip.

A name of its own, and not ``serve`` with an option, for the reasons ``serve_added.py`` gives (a PR
that adds a cell edits no file that is there). ``run`` and ``set_up`` are ``serve``'s with this
sample in its place.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from benchmark.drivers import serve
from benchmark.drivers.serve import PROGRAMS, build_engine, check_kernels, serve_all  # noqa: F401
from benchmark.lib import harness, loadgen, longctx

# rows of the head a block of the reference's head takes
HEAD_ROWS = 8192


def served_keys(engine, layer: int, pages) -> np.ndarray:
    """The keys layer ``layer`` caches on ``pages``, in position order, (len(pages) * page_size,
    kv heads, head_dim) float32 on the host, columns in the published order (the program keeps
    each head's even columns first, then the odd ones: ``parallel_moe.published_columns``)."""
    from thunder_tpu.models.parallel_moe import published_columns

    pool = engine.cache.state[layer][0]                                    # (P, Hkv, ps, D)
    rows = np.asarray(pool[np.asarray(pages, np.int32)], np.float32)
    rows = rows.transpose(0, 2, 1, 3).reshape(-1, pool.shape[1], pool.shape[3])
    out = np.empty_like(rows)
    out[..., published_columns(pool.shape[3])] = rows
    return out


class BlockwiseReference:
    """``cell.reference`` run one jitted block at a time on the served parameters; a layer's
    program is compiled once a layer KIND (the layers of one kind compute alike)."""

    def __init__(self, cell, params):
        import jax

        ref, config = cell.reference, cell.config
        self.ref, self.config, self.params = ref, config, params
        self.kinds = ref.kinds(config)
        self.embed = jax.jit(lambda prm, toks: ref.embed(config, prm, toks))
        self.layer = jax.jit(lambda prm, x, i: ref.layer(config, prm, x, i), static_argnums=2)
        self.head = jax.jit(lambda prm, x: ref.head(config, prm, x))

    def __call__(self, tokens, rows) -> tuple:
        """``(logits at rows, [each layer's keys (T, kv heads, head_dim), published order])``."""
        prm = self.params
        x = self.embed({"wte.weight": prm["wte.weight"]}, tokens)
        keys = []
        for i, kind in enumerate(self.kinds):
            x, made = self.layer(self.ref.layer_params(prm, i), x, self.kinds.index(kind))
            keys.append(self.ref.published_keys(made["k"], self.config, kind))
        x = x[rows]
        table = prm["wte.weight"]
        logits = [np.asarray(self.head({"ln_f.weight": prm["ln_f.weight"],
                                        "wte.weight": table[a:a + HEAD_ROWS]}, x))
                  for a in range(0, table.shape[0], HEAD_ROWS)]
        return np.concatenate(logits, axis=-1), keys


def distance(rows, want) -> float:
    return float(np.linalg.norm(rows - want) / np.linalg.norm(want))


def window_positions(window_pages, page_size: int) -> np.ndarray:
    """The positions the ``(page index, page)`` pairs of a result cover, in order."""
    return np.concatenate([np.arange(j * page_size, (j + 1) * page_size) for j, _ in window_pages])


def last_window_pages(last: int, window: int, page_size: int) -> list:
    """Page indices a sequence whose last written position is ``last`` holds after that step:
    those that intersect the window of the query at ``last``, ``[last - window + 1, last]``."""
    return list(range(max(last - window + 1, 0) // page_size, last // page_size + 1))


def pages_limit(window: int, chunk_tokens: int, page_size: int) -> int:
    """The most window pages a sequence may hold at once: a chunk's queries at ``[s, s + C)``
    see back to ``s - window + 1`` and write their own pages, ``(window + C) / page_size`` pages
    of a page-aligned window; a decode step's query ``window / page_size + 1`` at most."""
    return -(-(window + chunk_tokens) // page_size)


def check_sample(cell, engine, seed: int, notes: list) -> dict:
    """``serve.check_sample`` (alone equals batched; every chosen token within ``margin`` of the
    reference's top logit), and for each request of the sample the keys the engine keeps after
    serving it alone against the reference's, as ``|engine - reference| / |reference|``
    (Frobenius norms), the largest over the requests: the first window layer's over its held
    window pages within ``window_key_margin``, the full layer's over every position within
    ``full_key_margin``; and the window pages each request held."""
    spec = cell.traffic["correctness"]
    config = cell.config
    vocab = config["vocab_size"]
    reqs = [(int(p), int(n)) for p, n in spec["requests"]]
    prompts = [loadgen.prompt_tokens(seed, 1_000_000 + i, p, vocab) for i, (p, _) in enumerate(reqs)]
    kinds = cell.reference.kinds(config)
    first_window, full = kinds.index("window"), kinds.index("full")
    ps, window = engine.page_size, int(config["sliding_window"])
    t0 = time.perf_counter()
    alone, kept = [], []
    for prompt, (p, n) in zip(prompts, reqs):
        res = serve_all(engine, [prompt], [n])[0]
        alone.append(res)
        kept.append({"window": served_keys(engine, first_window, [pg for _, pg in res.window_pages]),
                     "full": served_keys(engine, full, res.pages)})
    together = serve_all(engine, prompts, [n for _, n in reqs])
    differ = 0
    for i, (a, b) in enumerate(zip(alone, together)):
        if a.n_new_tokens != reqs[i][1] or not np.array_equal(a.new_tokens, b.new_tokens):
            notes.append(f"sample request {i} {reqs[i]}: alone and batched outputs differ")
            differ += 1
    pages_most = max(r.window_pages_peak for r in alone)
    pages_wrong = sum(
        [j for j, _ in r.window_pages] != last_window_pages(p + n - 2, window, ps)
        for r, (p, n) in zip(alone, reqs))

    t1 = time.perf_counter()
    reference = BlockwiseReference(cell, engine.params)
    t_max = max(p + n for p, n in reqs)
    n_max = max(n for _, n in reqs)
    worst, apart = 0.0, {"window": 0.0, "full": 0.0}
    for (p, n), res, rows in zip(reqs, alone, kept):
        toks = np.zeros((t_max,), np.int32)
        toks[:p + n] = res.tokens
        # the logits that chose output token j are those at position p + j - 1
        at = np.minimum(np.arange(n_max) + p - 1, p + n - 2).astype(np.int32)
        logits, keys = reference(toks, at)
        gap = logits[:n].max(axis=-1) - logits[np.arange(n), res.new_tokens]
        worst = max(worst, float(gap.max()))
        written = p + n - 1  # the last token is handed back and never fed
        pos = window_positions(res.window_pages, ps)
        held = pos < written
        for kind, layer, got, at_pos in (("window", first_window, rows["window"][held], pos[held]),
                                         ("full", full, rows["full"][:written], np.arange(written))):
            want = keys[layer][at_pos]
            ok = len(got) == len(want) and len(want)  # a result without its pages reads as not a number
            apart[kind] = max(apart[kind], distance(got, want)) if ok else float("nan")
    margin, window_margin, full_margin = (float(spec[k]) for k in ("margin", "window_key_margin",
                                                                   "full_key_margin"))
    limit = pages_limit(window, engine.chunk_tokens, ps)
    harness.say(f"correctness sample: {len(reqs)} requests alone == batched; largest distance of a "
                f"chosen token from the reference's top logit {worst:.4f} (margin {margin}); largest "
                f"relative distance of the cached keys from the reference's: layer {first_window} "
                f"(window) {apart['window']:.4g} (margin {window_margin}), layer {full} (full) "
                f"{apart['full']:.4g} (margin {full_margin}); most window pages a request held "
                f"{pages_most} (limit {limit}), requests whose last window pages were not "
                f"the window's {pages_wrong}")
    harness.say(f"correctness sample: served in {t1 - t0:.1f} s, reference in blocks "
                f"{time.perf_counter() - t1:.1f} s")
    if not worst <= margin:
        notes.append(f"a chosen token is {worst} below the reference's top logit (margin {margin})")
    if not apart["window"] <= window_margin:
        notes.append(f"the keys window layer {first_window} caches are {apart['window']} from the "
                     f"reference's, relative (margin {window_margin}): a cache coarser than the "
                     f"activations, or rope on other pairs or at other positions")
    if not apart["full"] <= full_margin:
        notes.append(f"the keys full layer {full} caches are {apart['full']} from the reference's, "
                     f"relative (margin {full_margin})")
    if pages_most > limit or pages_wrong:
        notes.append(f"a request held {pages_most} window pages at once (limit {limit}), or "
                     f"{pages_wrong} ended holding other pages than its last window's")
    return {"sample_margin": worst, "sample_differ": differ,
            "sample_window_key_distance": apart["window"], "sample_full_key_distance": apart["full"],
            "sample_window_pages_most": pages_most, "sample_window_pages_wrong": pages_wrong,
            "window_pages_usable": engine.cache.n_window_pages - 1}


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
    spec, s = cell.traffic["correctness"], run.stats
    harness.held(run.compared, "sample_window_key_distance", s["sample_window_key_distance"], "<=",
                 float(spec["window_key_margin"]))
    harness.held(run.compared, "sample_full_key_distance", s["sample_full_key_distance"], "<=",
                 float(spec["full_key_margin"]))
    harness.held(run.compared, "sample_window_pages_most", s["sample_window_pages_most"], "<=",
                 pages_limit(int(cell.config["sliding_window"]), int(cell.traffic["engine"]["chunk_tokens"]),
                             int(cell.traffic["engine"]["page_size"])))
    harness.held(run.compared, "sample_window_pages_wrong", s["sample_window_pages_wrong"], "==", 0)
    if opts.trace:
        longctx.log(run)
    return run
