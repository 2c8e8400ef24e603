"""Driver ``serve_denoise``: the ``serve`` driver for a model that generates by DIFFUSION OVER
BLOCKS (``ServingEngine(block_diffusion=)``: a pass carries a block of K positions a sequence,
unmasks some of them, and the block's keys and values stay only from the pass that runs it with
none masked), with a correctness sample that follows a TRAJECTORY OF BLOCK STATES, not a chain of
tokens.

Everything that offers load and measures is ``drivers/serve.py``'s (``run`` below is that
``run`` with this module's pieces in their places, as ``serve_rollouts.py`` does it). What differs:

* the engine is built with ``block_diffusion=`` from the configuration file's ``generation`` group
  (``builders/block_moe.py: block_diffusion``) and with ``record_block_states`` set: a retired
  request's result then carries ``block_states``, the block going INTO each of its passes as
  ``(first position, tokens (K,), masked flags (K,))``, and ``unmasked``, the ``(position, pass)``
  of every generated position in the order it was filled. A later builder who needs more of a pass
  (its confidences, say) finds the record's layout in ``thunder_tpu/serving/scheduler.py:
  _block_sample`` (the (B, 4 K + 1) int32 array the host reads a pass late);
* prompts are drawn from the vocabulary WITHOUT the mask token (``prompt_tokens`` below);
* the programs whose kernel claims are held are the block program and the chunk program
  (``PROGRAMS``), and ``decode_regions`` are the block program's;
* the sample (``check_sample``). Four requests from the traffic's own lengths are served alone
  and then together: tokens AND orders of unmasking must be identical. Then the reference
  (``cell.reference``, float32, one layer at a time on the served bfloat16 weights, its head in
  blocks of rows) REPLAYS each request's recorded passes: for the tokens that went into a pass
  it gives the logits of the block's rows by a full forward under the block-causal mask, and
  for the finished sequence the keys a cache should hold. Held, every number through
  ``harness.held``:
  (a) ``sample_margin``: every filled token within ``margin`` of the reference's top logit at its
      position in that pass (the mask token left out);
  (b) ``sample_order_margin``: every position a pass chose to unmask within ``order_margin``, in
      log-confidence, of the reference's n-th best masked position of that pass, n the number the
      pass filled (with n = 2 a pass the second choice is judged against the reference's second,
      not against its best, which the second never equals);
  (c) ``sample_kv_distance``: the LAST layer's cached key rows of the request's positions
      (prompt blocks through the chunk program, generated blocks through their commit passes)
      within ``kv_margin`` of the reference's, relative (Frobenius norms): those rows have been
      through every layer before them, so a denoise pass's keys kept, a wrong mask or an expert
      layer's fault shows there; and ``sample_first_kv_distance``, the FIRST layer's within
      ``first_kv_margin``: its input is the embedding, so it carries one projection's, one head
      norm's and the rope's rounding and nothing else, and a cache kept in a narrower type than
      the activations shows there;
  no returned token is the mask token, and every request returns what it asked for.
  The traffic file gives each margin with the chip readings it was set from and what it catches
  (the reference's control ``block_length`` 1, an engine that keeps a denoise pass's keys, QK-norm
  left out, a bfloat16 router) and what it cannot see.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from benchmark.drivers import serve
from benchmark.drivers.serve import serve_all
from benchmark.lib import harness, loadgen

# the compiled programs of ``engine.runner`` whose kernels the builder's ``kernel_claims`` states
PROGRAMS = ("block_cfn", "chunk_cfn")
# rows of the head a block of the reference's head takes
HEAD_ROWS = 8192


def prompt_tokens(seed: int, index: int, length: int, config: dict) -> np.ndarray:
    """``loadgen.prompt_tokens`` over the vocabulary without the mask token."""
    mask_id = int(config["generation"]["mask_token_id"])
    toks = loadgen.prompt_tokens(seed, index, length, config["vocab_size"] - 1)
    return toks + (toks >= mask_id).astype(np.int32)


def build_engine(cell, seed: int):
    import jax.numpy as jnp

    from thunder_tpu.serving import ServingEngine

    spec = dict(cell.traffic["engine"])
    dtype = getattr(jnp, spec.pop("dtype", "bfloat16"))
    gpt = cell.builder.build_serving_model(cell.config, cell.config_name, dtype)
    cell.builder.reseed(dict(gpt.named_parameters()), seed, cell.config)
    engine = ServingEngine(gpt, dtype=dtype, block_diffusion=cell.builder.block_diffusion(cell.config),
                           **spec)
    engine.record_block_states = True
    return engine


def served_keys(engine, layer: int, pages, n: int) -> np.ndarray:
    """The first ``n`` key rows layer ``layer`` caches on ``pages`` (a retired request's, in
    position order), (n, key heads x head size), float32 on the host."""
    pool = np.asarray(engine.cache.state[layer][0][np.asarray(pages, np.int32)], np.float32)
    return pool.transpose(0, 2, 1, 3).reshape(-1, pool.shape[1] * pool.shape[3])[:n]


class BlockwiseReference:
    """``cell.reference`` run one jitted layer at a time on the served parameters, at one length
    (tokens past what a caller means are padding: under the block-causal mask no earlier row sees
    them)."""

    def __init__(self, cell, params, config=None):
        import jax

        ref = cell.reference
        self.config = config = config or cell.config
        self.ref, self.params, self.n_layer = ref, params, config["num_hidden_layers"]
        self.embed = jax.jit(lambda prm, toks: ref.embed(config, prm, toks))
        self.layer = jax.jit(lambda prm, x: ref.layer(config, prm, x))
        self.head = jax.jit(lambda prm, x: ref.head(config, prm, x))

    def __call__(self, tokens, rows=None) -> tuple:
        """``(logits at rows (None: no head), the FIRST and the LAST layer's keys, each (T, key
        heads x head size))``."""
        prm = self.params
        x = self.embed({"wte.weight": prm["wte.weight"]}, tokens)
        keys = []
        for i in range(self.n_layer):
            x, made = self.layer(self.ref.layer_params(prm, i), x)
            if i in (0, self.n_layer - 1):
                keys.append(np.asarray(made["k"]).reshape(len(tokens), -1))
        keys = (keys[0], keys[-1])
        if rows is None:
            return None, keys
        table = prm["lm_head.weight"]
        logits = [np.asarray(self.head({"ln_f.weight": prm["ln_f.weight"],
                                        "lm_head.weight": table[a:a + HEAD_ROWS]}, x[rows]))
                  for a in range(0, table.shape[0], HEAD_ROWS)]
        return np.concatenate(logits, axis=-1), keys


def replay(reference, result, prompt_len: int, spec: dict, t_max: int) -> dict:
    """One request's recorded passes against the reference: the worst ``margin`` (a filled
    token under the reference's top logit), the worst ``order`` (a chosen position's
    log-confidence under the reference's n-th best masked one) and the finished sequence's
    keys in the first and in the last layer (``rows`` of them)."""
    K, mask_id = int(spec["block_length"]), int(spec["mask_token_id"])
    seq = list(result.tokens[:prompt_len // K * K])
    states = list(result.block_states)
    margin = order = 0.0
    for n, (pos, toks, masked) in enumerate(states):
        if not masked.any():
            seq += [int(t) for t in toks]
            continue
        padded = np.zeros((t_max,), np.int32)
        padded[:pos + K] = seq + [int(t) for t in toks]
        logits, _ = reference(padded, np.arange(pos, pos + K))
        logits = np.asarray(logits, np.float64)
        logits[:, mask_id] = -np.inf
        top = logits.max(-1)
        logc = top - (np.log(np.exp(logits - top[:, None]).sum(-1)) + top)  # log softmax of the argmax
        _, nxt_toks, nxt_masked = states[n + 1]
        filled = np.flatnonzero(masked & ~nxt_masked)
        for j in filled:
            margin = max(margin, float(top[j] - logits[j, int(nxt_toks[j])]))
        nth = np.sort(logc[masked])[::-1][len(filled) - 1]
        order = max(order, float(max(nth - logc[j] for j in filled)))
    padded = np.zeros((t_max,), np.int32)
    padded[:len(seq)] = seq
    _, (first, keys) = reference(padded)
    return {"margin": margin, "order": order, "rows": len(seq), "keys": keys[:len(seq)],
            "first_keys": first[:len(seq)]}


def distance(rows, want) -> float:
    return float(np.linalg.norm(rows - want) / np.linalg.norm(want))


def check_sample(cell, engine, seed: int, notes: list) -> dict:
    """The module's docstring says what is held; returns the numbers ``run`` puts beside their
    limits."""
    spec, gen = cell.traffic["correctness"], cell.config["generation"]
    mask_id = int(gen["mask_token_id"])
    reqs = [(int(p), int(n)) for p, n in spec["requests"]]
    prompts = [prompt_tokens(seed, 1_000_000 + i, p, cell.config) for i, (p, _) in enumerate(reqs)]
    last = cell.config["num_hidden_layers"] - 1
    t0 = time.perf_counter()
    alone, kept = [], []
    for prompt, (p, n) in zip(prompts, reqs):
        res = serve_all(engine, [prompt], [n])[0]
        alone.append(res)
        kept.append([served_keys(engine, i, res.pages, len(res.pages) * engine.page_size) for i in (0, last)])
    together = serve_all(engine, prompts, [n for _, n in reqs])
    differ = masks = 0
    for i, (a, b) in enumerate(zip(alone, together)):
        if a.n_new_tokens != reqs[i][1] or not np.array_equal(a.new_tokens, b.new_tokens) \
                or a.unmasked != b.unmasked:
            notes.append(f"sample request {i} {reqs[i]}: alone and batched tokens or orders of unmasking differ")
            differ += 1
        masks += int((a.new_tokens == mask_id).sum())

    t1 = time.perf_counter()
    reference = BlockwiseReference(cell, engine.params)
    K = int(gen["block_length"])
    t_max = max(-(-(p + n) // K) * K for p, n in reqs)
    worst = order = apart = first = 0.0
    for (p, n), res, (rows0, rows) in zip(reqs, alone, kept):
        got = replay(reference, res, p, gen, t_max)
        worst, order = max(worst, got["margin"]), max(order, got["order"])
        ok = len(rows) >= got["rows"] > 0  # a result without its pages or states reads as not a number
        apart = max(apart, distance(rows[:got["rows"]], got["keys"])) if ok else float("nan")
        first = max(first, distance(rows0[:got["rows"]], got["first_keys"])) if ok else float("nan")
    margin, order_margin, kv_margin, first_margin = (float(spec[k]) for k in (
        "margin", "order_margin", "kv_margin", "first_kv_margin"))
    harness.say(f"correctness sample: {len(reqs)} requests alone == batched (tokens and orders); a filled "
                f"token at most {worst:.4f} under the reference's top logit of its pass (margin {margin}); "
                f"a chosen position at most {order:.4f} in log-confidence under the reference's n-th best "
                f"masked one (margin {order_margin}); cached keys from the reference's, relative: layer 0's "
                f"{first:.4g} (margin {first_margin}), layer {last}'s {apart:.4g} (margin {kv_margin}); "
                f"{masks} mask tokens returned")
    harness.say(f"correctness sample: served in {t1 - t0:.1f} s, reference replay "
                f"{time.perf_counter() - t1:.1f} s")
    if not worst <= margin:
        notes.append(f"a filled token is {worst} below the reference's top logit (margin {margin})")
    if not order <= order_margin:
        notes.append(f"a position chosen for unmasking is {order} in log-confidence under the reference's "
                     f"n-th best masked position (margin {order_margin})")
    if not apart <= kv_margin:
        notes.append(f"the keys layer {last} caches are {apart} from the reference's, relative (margin "
                     f"{kv_margin}): a denoise pass's keys kept, a wrong mask, norm or rope")
    if not first <= first_margin:
        notes.append(f"the keys layer 0 caches are {first} from the reference's, relative (margin "
                     f"{first_margin}): a cache coarser than the activations, or a wrong norm or rope")
    if masks:
        notes.append(f"{masks} returned tokens are the mask token")
    return {"sample_margin": worst, "sample_differ": differ, "sample_order_margin": order,
            "sample_kv_distance": apart, "sample_first_kv_distance": first, "sample_mask_tokens": masks}


def check_kernels(cell, claims, notes: list, compared: dict) -> dict:
    """``serve.check_kernels`` for ``PROGRAMS``: the block program in the decode program's place."""
    want = harness.wanted_claims(cell, PROGRAMS)
    if claims is None:
        return {}
    for name, symbols, got, count in harness.unheld_claims(want, claims, "==", compared):
        notes.append(f"{symbols} claimed by pallas {got} times in {name}, not {count}")
    if "block_cfn" not in claims:
        notes.append("the block program never ran")
    return claims


def decode_regions(engine) -> list:
    """Names of the XLA regions the BLOCK program executes, read off its executed trace."""
    import thunder_tpu as tt
    from thunder_tpu.executors import xlaex

    traces = tt.last_traces(engine.runner.block_cfn._cfn)
    if not traces:
        return []
    return sorted({b.sym.name for b in traces[-1].bound_symbols if b.sym.executor is xlaex.ex})


def make_loop(config: dict):
    def make(traffic: dict, engine, seconds: float, seed: int, vocab: int):
        def submit(req):
            return engine.submit(prompt_tokens(seed, req.index, req.prompt_len, config),
                                 max_new_tokens=req.output_len)

        def read(res):
            return res.ttft_s, res.tbot_s, res.n_new_tokens

        if traffic["loop"]["kind"] != "closed":
            raise ValueError("the serve_denoise driver offers a closed loop only")
        return loadgen.ClosedLoop(submit, read, loadgen.LengthStream(traffic, seed),
                                  int(traffic["loop"]["clients"]))

    return make


@contextlib.contextmanager
def _own(config: dict):
    """``serve.run`` and ``serve.set_up`` find these in their own module: this one's, for a while."""
    mine = {"check_sample": check_sample, "PROGRAMS": PROGRAMS, "check_kernels": check_kernels,
            "decode_regions": decode_regions, "build_engine": build_engine, "make_loop": make_loop(config)}
    theirs = {k: getattr(serve, k) for k in mine}
    for k, v in mine.items():
        setattr(serve, k, v)
    try:
        yield
    finally:
        for k, v in theirs.items():
            setattr(serve, k, v)


def set_up(cell, seed: int, notes: list):
    with _own(cell.config):
        return serve.set_up(cell, seed, notes)


def run(cell, opts, env) -> harness.Run:
    with _own(cell.config):
        run = serve.run(cell, opts, env)
    spec = cell.traffic["correctness"]
    harness.held(run.compared, "sample_order_margin", run.stats["sample_order_margin"], "<=",
                 float(spec["order_margin"]))
    harness.held(run.compared, "sample_first_kv_distance", run.stats["sample_first_kv_distance"], "<=",
                 float(spec["first_kv_margin"]))
    harness.held(run.compared, "sample_kv_distance", run.stats["sample_kv_distance"], "<=",
                 float(spec["kv_margin"]))
    harness.held(run.compared, "sample_mask_tokens", run.stats["sample_mask_tokens"], "==", 0)
    return run
