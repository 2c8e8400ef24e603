"""A model that is no dense GPT through the paged engine: state-space, window, full and
cross-layer attention and gated memory units side by side (`models/sambay.py`), at the rehearsal
size of `benchmark/configs/phi-4-mini-flash-reasoning.json`, against the plain reference
(`benchmark/reference/sambay.py`). Everything is float32 on the CPU; nothing here writes a file.
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from benchmark.lib import harness, manifest
from thunder_tpu import observability
from thunder_tpu.core.trace import named_scope
from thunder_tpu.executors import pallasex
from thunder_tpu.models.litgpt import Config as GPTConfig, GPT
from thunder_tpu.nn.module import functional_params
from thunder_tpu.ops import ltorch
from thunder_tpu.serving import ServingEngine
from thunder_tpu.serving.kv_pages import Recurrent

pytestmark = pytest.mark.serve

with open(os.path.join(manifest.ROOT, "benchmark", "configs", "phi-4-mini-flash-reasoning.json")) as f:
    _FILE = json.load(f)
CONFIG = manifest.merged(_FILE, _FILE["rehearsal"])
BUILDER = manifest.load_module(manifest.ROOT, "builders", "sambay")
REFERENCE = manifest.load_module(manifest.ROOT, "reference", "sambay")
WINDOW, PAGE = CONFIG["sliding_window"], 8
# float32 on both sides, the same mathematics in another order of summation (an associative scan
# in blocks against a sequential one, paged softmax against a dense one): logits with a standard
# deviation near 0.8 agree to a few float32 roundings. A wrong mask, page or state moves them by
# 0.1 or more (the halved-window control below reads 0.05 to 1).
LOGIT_TOL = 2e-4


@pytest.fixture(scope="module")
def model():
    m = BUILDER.build_serving_model(CONFIG, "tiny", jnp.float32)
    BUILDER.reseed(dict(m.named_parameters()), 3, CONFIG)
    return m


def engine_for(model, **kw):
    spec = dict(dtype=jnp.float32, max_batch=4, page_size=PAGE, max_seq=256, chunk_tokens=32,
                min_bucket=16)
    spec.update(kw)
    return ServingEngine(model, **spec)


@pytest.fixture(scope="module")
def engine(model):
    return engine_for(model)


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CONFIG["vocab_size"], (n,)).astype(np.int32) for n in lengths]


def serve(engine, ps, n_new):
    futures = [engine.submit(p, max_new_tokens=n) for p, n in zip(ps, n_new)]
    engine.drain()
    return [f.result() for f in futures]


def gap(config, params, res, p, n):
    """How far under the reference's top logit the engine's chosen tokens sit, at worst."""
    logits = np.asarray(REFERENCE.forward(config, params, res.tokens, rows=np.arange(n) + p - 1))
    return float((logits.max(-1) - logits[np.arange(n), res.new_tokens]).max())


def test_every_kind_of_layer_occurs_and_the_parameters_are_the_published_count():
    assert BUILDER.layer_counts(CONFIG) == {"mamba": 3, "window_attn": 2, "full_attn": 1, "gmu": 1,
                                            "cross_attn": 1}
    assert BUILDER.layer_counts(_FILE) == {"mamba": 9, "window_attn": 8, "full_attn": 1, "gmu": 7,
                                           "cross_attn": 7}
    from benchmark.lib import costs_sambay

    d = BUILDER.dims(_FILE)
    p = costs_sambay.matmul_params(d)
    weights = sum(d["layers"][k] * p[k] for k in d["layers"]) + d["n_layer"] * p["mlp"] + p["head"]
    assert 3.80e9 < weights < 3.86e9  # 3.85 B with the norms, biases, conv and scan vectors


def test_forward_agrees_with_the_reference(model):
    params = {k: p.data for k, p in model.named_parameters()}
    toks = np.stack(prompts([100, 100], seed=1))

    def fwd(params, idx):
        with functional_params(model, params):
            return model(idx)

    out = np.asarray(tt.jit(fwd)(params, jnp.asarray(toks)))
    for b in range(2):
        want = np.asarray(REFERENCE.forward(CONFIG, params, toks[b]))
        assert np.abs(out[b] - want).max() < LOGIT_TOL


# a whole-prompt bucket under the window, one over it, and two through chunked prefill (chunks of
# 32: the scan state and the conv tail cross chunk edges) that decode far past the window
SAMPLE = [(12, 6), (30, 40), (70, 60), (100, 30)]


def test_prefill_then_decode_agrees_with_the_reference_alone_and_batched(engine):
    ps = prompts([p for p, _ in SAMPLE])
    alone = [serve(engine, [p], [n])[0] for p, (_, n) in zip(ps, SAMPLE)]
    together = serve(engine, ps, [n for _, n in SAMPLE])
    for a, b, (p, n) in zip(alone, together, SAMPLE):
        assert a.n_new_tokens == n and np.array_equal(a.new_tokens, b.new_tokens)
        assert gap(CONFIG, engine.params, a, p, n) < LOGIT_TOL
    assert engine.cache.allocator.n_used == 0 and engine.cache.window_allocator.n_used == 0


def test_sequences_admitted_while_others_decode_give_what_each_gives_alone(model):
    """One decode step stays in flight: window pages are taken and handed back for the step
    being dispatched while the step before it may still read them, and an admission joins the
    step in flight: its final chunk's program runs behind it and the first token is merged into
    the next step's tokens on the device. Token for token nothing may show, no page of either
    kind is left, and only the first step finds no step before it unfetched."""
    eng = engine_for(model, max_batch=3)
    reqs = list(zip(prompts([p for p, _ in SAMPLE], seed=7), [n for _, n in SAMPLE]))
    alone = [serve(eng, [p], [n])[0] for p, n in reqs]
    observability.enable()
    observability.reset()
    try:
        futs = [eng.submit(p, max_new_tokens=n) for p, n in reqs[:2]]
        for _ in range(5):
            eng._step_once()
        assert eng._inflight is not None
        futs += [eng.submit(p, max_new_tokens=n) for p, n in reqs[2:]]
        eng.drain()
        counters = observability.counters()
    finally:
        observability.disable()
        observability.reset()
    for a, fut in zip(alone, futs):
        assert np.array_equal(a.new_tokens, fut.result(timeout=5).new_tokens)
    assert eng._inflight is None
    assert eng.cache.allocator.n_used == 0 and eng.cache.window_allocator.n_used == 0
    steps = counters["serve.decode_steps"]
    # four activations in a run of about ninety steps: two before any step, two that join
    assert counters["serve.decode_overlapped"] == steps - 1
    assert counters["serve.activations"] == 4 and counters["serve.activations_joined"] == 2
    assert "serve.decode_discarded" not in counters
    assert counters["serve.tokens"] == sum(n - 1 for _, n in SAMPLE)


def test_the_hybrid_never_mixes_a_chunk_with_the_decode_step(model):
    """Its layers offer no `mixed` (a scan state, a window, a memory another layer reads), so the
    engine keeps a chunk program without decode rows and a decode program a pass, and counts no
    `serve.decode_mixed`."""
    eng = engine_for(model)
    assert not eng.runner.mixes and not eng._mixes and eng._idle_rows is None
    assert not any(hasattr(layer, "mixed") for layer in eng.runner.model.layers)
    ps = prompts([20, 100], seed=11)
    observability.enable()
    try:
        observability.reset()
        first = eng.submit(ps[0], max_new_tokens=12)
        for _ in range(3):
            eng._step_once()
        second = eng.submit(ps[1], max_new_tokens=4)   # four chunks of 32 beside the first's steps
        eng.drain()
        counters = observability.counters()
    finally:
        observability.disable()
        observability.reset()
    assert first.result().n_new_tokens == 12 and second.result().n_new_tokens == 4
    assert "serve.decode_mixed" not in counters and counters["serve.decode_steps"] >= 11
    trace = tt.last_traces(eng.runner.chunk_cfn._cfn)[0]
    assert not any(b.sym.name == "paged_attention" for b in trace.bound_symbols)
    # and its programs are compiled as they were: no row of theirs runs at two shapes
    from thunder_tpu.compile_service import parallel_compile
    for cfn in (eng.runner.chunk_cfn, eng.runner.decode_cfn):
        regions = parallel_compile.fusion_regions(tt.last_traces(cfn._cfn)[-1])
        assert regions and all(b.impl.compiler_options is None for b in regions)


def recurrent_rows(eng, slot: int) -> list:
    """The rows slot ``slot`` holds in every recurrent array, on the host."""
    return [np.asarray(a[slot]) for layer, arrays in zip(eng.cache.layers, eng.cache.state)
            if isinstance(layer, Recurrent) for a in arrays]


def test_a_request_alone_leaves_its_slot_as_its_last_needed_step_left_it(model):
    """A sequence whose last token by count is in flight is not in the next step: n tokens are
    the prefill's and n - 1 decode steps, and the scan state of its slot is the one after step
    n - 1 (what `benchmark/drivers/serve_added.py` compares with the reference's). The witness
    is a longer request on a fresh engine, read after exactly n - 1 steps."""
    n, prompt = 12, prompts([40], seed=9)[0]
    eng = engine_for(model, max_batch=2)
    res = serve(eng, [prompt], [n])[0]
    assert res.n_new_tokens == n and eng.decode_steps == n - 1 and eng._inflight is None
    kept = recurrent_rows(eng, 0)
    assert kept and all(np.abs(r).max() > 0 for r in kept)
    eng._step_once()   # nothing outstanding: no step, and the slot's rows stay
    assert eng.decode_steps == n - 1
    witness = engine_for(model, max_batch=2)
    fut = witness.submit(prompt, max_new_tokens=n + 8)
    while witness.decode_steps < n - 1:
        witness._step_once()
    for got, want in zip(kept, recurrent_rows(witness, 0)):
        np.testing.assert_array_equal(got, want)
    witness.drain()
    assert np.array_equal(fut.result(timeout=5).new_tokens[:n], res.new_tokens)
    assert any(not np.array_equal(a, b) for a, b in zip(kept, recurrent_rows(witness, 0)))


@pytest.mark.parametrize("beside", [False, True], ids=["alone", "beside-a-decoding-request"])
@pytest.mark.parametrize("ended", [0, 2], ids=["whole-prompt", "chunked"])
def test_a_first_token_that_ends_its_request_leaves_its_slot_fit_for_the_next(model, ended, beside):
    """`eos_id` equal to the first token, which the host reads after the slot's first decode step
    was dispatched: that step has advanced the slot's scan state and conv tail and written a
    window page, and its token is the one thrown away. The request retires with one token and no
    page of either kind; the request that takes the slot next starts its recurrent rows anew in
    its prefill and gives the tokens it gives served alone."""
    reqs = list(zip(prompts([p for p, _ in SAMPLE], seed=13), [n for _, n in SAMPLE]))
    alone = [serve(engine_for(model), [p], [n])[0] for p, n in reqs]
    eng = engine_for(model, max_batch=2)
    observability.enable()
    observability.reset()
    try:
        futs = {}
        if beside:
            futs[1] = eng.submit(reqs[1][0], max_new_tokens=reqs[1][1])
            for _ in range(3):
                eng._step_once()
        held = (eng.cache.allocator.n_used, eng.cache.window_allocator.n_used)
        p, n = reqs[ended]
        first = eng.submit(p, max_new_tokens=n, eos_id=int(alone[ended].new_tokens[0]))
        while not first.done():
            eng._step_once()
        assert (eng.cache.allocator.n_used, eng.cache.window_allocator.n_used) == held
        slot = eng._slots.index(None)
        assert all(np.abs(r).max() > 0 for r in recurrent_rows(eng, slot))   # what the step left
        futs[3] = eng.submit(reqs[3][0], max_new_tokens=reqs[3][1])
        eng._step_once()
        assert eng._chunking.get(slot) is not None or eng._slots[slot] is not None
        eng.drain()
        counters = observability.counters()
    finally:
        observability.disable()
        observability.reset()
    res = first.result(timeout=5)
    assert res.finish_reason == "eos" and res.n_new_tokens == 1
    assert res.new_tokens[0] == alone[ended].new_tokens[0]
    for i, fut in futs.items():
        assert np.array_equal(fut.result(timeout=5).new_tokens, alone[i].new_tokens)
    assert counters["serve.decode_discarded"] == 1
    # alone, the discarded step lands in a pass with no live sequence and the next request's
    # first step has none before it
    assert counters["serve.decode_overlapped"] == counters["serve.decode_steps"] - (1 if beside else 2)
    assert eng._inflight is None and not eng._firsts
    assert eng.cache.allocator.n_used == 0 and eng.cache.window_allocator.n_used == 0


def test_a_request_that_wants_one_token_is_served_beside_a_decoding_one(model):
    """It takes no slot and no step: its prompt's program writes the slot's recurrent rows and
    nothing reads them, and the next request into the slot starts them anew."""
    reqs = list(zip(prompts([p for p, _ in SAMPLE], seed=17), [n for _, n in SAMPLE]))
    alone = [serve(engine_for(model), [p], [n])[0] for p, n in reqs]
    eng = engine_for(model, max_batch=2)
    long = eng.submit(reqs[1][0], max_new_tokens=reqs[1][1])
    for _ in range(3):
        eng._step_once()
    ones = [eng.submit(reqs[i][0], max_new_tokens=1) for i in (0, 2)]
    for _ in range(4):
        eng._step_once()
    last = eng.submit(reqs[3][0], max_new_tokens=reqs[3][1])
    eng.drain()
    for i, fut in zip((0, 2), ones):
        res = fut.result(timeout=5)
        assert res.n_new_tokens == 1 and res.new_tokens[0] == alone[i].new_tokens[0]
    assert np.array_equal(long.result(timeout=5).new_tokens, alone[1].new_tokens)
    assert np.array_equal(last.result(timeout=5).new_tokens, alone[3].new_tokens)
    assert eng.cache.allocator.n_used == 0 and eng.cache.window_allocator.n_used == 0


def test_the_halved_window_control_fails(engine):
    wrong, what = REFERENCE.control(CONFIG)
    assert wrong["sliding_window"] == WINDOW // 2 and what
    (p, n), prompt = SAMPLE[-1], prompts([SAMPLE[-1][0]], seed=5)[0]
    res = serve(engine, [prompt], [n])[0]
    assert gap(CONFIG, engine.params, res, p, n) < LOGIT_TOL
    assert gap(wrong, engine.params, res, p, n) > 100 * LOGIT_TOL


def test_a_prompt_through_three_chunks_equals_the_same_prompt_whole(model, engine):
    prompt = prompts([90], seed=2)[0]
    chunked = serve(engine, [prompt], [20])[0]                       # 32 + 32 + 26
    whole = serve(engine_for(model, chunk_tokens=128), [prompt], [20])[0]
    assert np.array_equal(chunked.new_tokens, whole.new_tokens)


def test_window_pages_are_freed_and_the_live_ones_stay_bounded(model):
    eng = engine_for(model, max_batch=2)
    live_most = WINDOW // PAGE + 1
    seen = []
    real = eng._window_trim

    def watching(req, pos):
        real(req, pos)
        seen.append(len(req.win_pages))

    eng._window_trim = watching
    observability.enable()
    observability.reset()
    try:
        res = serve(eng, prompts([40], seed=3), [150])[0]
        counters = observability.counters()
    finally:
        observability.disable()
    assert res.n_new_tokens == 150
    # 190 positions are 24 pages; a window of 16 holds 3 of 8 at a time
    assert counters["serve.window_pages_freed"] >= 190 // PAGE - live_most
    assert max(seen) <= live_most and eng.cache.window_allocator.n_used == 0
    assert counters["serve.state.window_pages"] <= live_most * counters["serve.decode_steps"]
    assert counters["serve.state.recurrent_bytes"] == (
        eng.cache.recurrent_bytes_per_slot() * counters["serve.tokens"])
    # one sequence began (its first chunk started from zero state); no dispatch copied the state
    assert counters["serve.recurrent_resets"] == 1 and counters.get("serve.pool_copied", 0) == 0


def test_a_reused_slot_gives_what_a_fresh_engine_gives(model):
    eng = engine_for(model, max_batch=1)
    first, second = prompts([50, 20], seed=4)
    serve(eng, [first], [30])                                        # leaves its state in slot 0
    again = serve(eng, [second], [12])[0]
    fresh = serve(engine_for(model, max_batch=1), [second], [12])[0]
    assert np.array_equal(again.new_tokens, fresh.new_tokens)


def test_a_preempted_sequence_resumes_to_the_same_tokens(model):
    eng = engine_for(model, max_batch=1)
    prompt = prompts([40], seed=6)[0]
    want = serve(eng, [prompt], [30])[0]
    fut = eng.submit(prompt, max_new_tokens=30, lane="batch")
    for _ in range(12):
        eng._step_once()
    assert eng._preempt_one() and eng.cache.window_allocator.n_used == 0
    eng.drain()
    assert eng.resumed == 1 and np.array_equal(fut.result().new_tokens, want.new_tokens)


def test_what_the_engine_refuses_for_a_recurrent_model(model):
    with pytest.raises(ValueError, match="prefix_sharing=True cannot serve a model with recurrent"):
        engine_for(model, prefix_sharing=True)
    draft = GPT(GPTConfig.from_name("tiny", vocab_size=CONFIG["vocab_size"], block_size=256),
                dtype=jnp.float32)
    with pytest.raises(ValueError, match="scan state cannot be rolled back"):
        engine_for(model, draft_gpt=draft)


@pytest.mark.parametrize("keyword,reason", [
    ("prefix_sharing", "window layers would find no keys for the end of the prefix"),
    ("draft_gpt", "window pages are taken for one position a step"),
])
def test_the_engine_refuses_the_same_for_window_layers_without_recurrent_ones(model, monkeypatch,
                                                                            keyword, reason):
    # the refusal follows from the declaration: a stack of this model's window layers alone
    served = model.serving()
    served.layers = [b for b in served.layers if b.kind == "window_attn"]
    assert served.layers and all(b.cache.window == WINDOW for b in served.layers)
    monkeypatch.setattr(type(model), "serving", lambda self: served)
    value = True if keyword == "prefix_sharing" else GPT(
        GPTConfig.from_name("tiny", vocab_size=CONFIG["vocab_size"], block_size=256), dtype=jnp.float32)
    with pytest.raises(ValueError, match=f"{keyword}.* cannot serve a model with window layers.*{reason}"):
        engine_for(model, **{keyword: value})


def test_the_shared_pool_exists_once_and_state_follows_the_declarations(engine):
    cache, kinds = engine.cache, [b.kind for b in engine.gpt.h]
    assert engine.runner.page_kinds == ("full", "window") and cache.window == WINDOW
    assert [len(s) for s in cache.state] == [{"mamba": 2, "window_attn": 2, "full_attn": 2}.get(k, 0)
                                             for k in kinds]
    # one pool pair for the full layer, which the cross-attention layer reads and does not copy
    assert len(cache.k_pages) == kinds.count("window_attn") + 1
    full = cache.state[kinds.index("full_attn")][0]
    assert full.shape[0] == cache.n_pages and cache.state[1][0].shape[0] == cache.n_window_pages
    nh, ng, hs = (CONFIG[k] for k in ("num_attention_heads", "num_key_value_heads", "hidden_size"))
    # a KV pair is one cached head: its two key heads side by side, as its values are
    assert full.shape[1:] == cache.state[kinds.index("full_attn")][1].shape[1:] == (
        ng // 2, PAGE, 2 * hs // nh)
    assert all(a.dtype == jnp.float32 for a in cache.state[0])       # the recurrent arrays


def _claims(cfn) -> dict:
    """What Pallas claimed of the paged symbols in a program's executed trace."""
    return {k: v for k, v in harness.pallas_claims(tt.last_traces(cfn._cfn)[-1]).items()
            if "paged" in k}


def test_the_programs_claim_what_the_builders_state(model, pallas_claims):
    """With the claim forced (interpret mode), the hybrid's programs hold its builder's counts and
    the dense GPT's four programs claim what they claimed: one paged kernel a layer."""
    eng = engine_for(model)
    res = serve(eng, prompts([20, 70], seed=7), [4, 4])
    assert max(gap(CONFIG, eng.params, r, p, 4) for r, p in zip(res, (20, 70))) < LOGIT_TOL
    want = BUILDER.kernel_claims(CONFIG)
    assert _claims(eng.runner.decode_cfn) == want["decode_cfn"] == {"thunder.paged_attention": 4}
    assert _claims(eng.runner.chunk_cfn) == want["chunk_cfn"] == {"thunder.paged_chunk_attention": 4}
    assert _claims(eng.runner.prefill_cfn) == {}

    cfg = GPTConfig.from_name("tiny-llama2", block_size=64)
    gpt = GPT(cfg, dtype=jnp.float32)
    spec = dict(dtype=jnp.float32, max_batch=2, page_size=8, max_seq=64, chunk_tokens=16)
    dense = ServingEngine(gpt, **spec)
    serve(dense, prompts([10, 40], seed=8), [5, 5])
    litgpt = manifest.load_module(manifest.ROOT, "builders", "litgpt")
    stated = litgpt.kernel_claims({"num_hidden_layers": cfg.n_layer})
    assert _claims(dense.runner.decode_cfn) == stated["decode_cfn"]
    # the chunk's rows through the chunk kernel as stated, and the decode rows that ride in the
    # program through the decode kernel, never through a second chunk call
    assert _claims(dense.runner.chunk_cfn) == dict(stated["chunk_cfn"],
                                                   **{"thunder.paged_attention": cfg.n_layer})
    assert _claims(dense.runner.prefill_cfn) == {}
    drafted = ServingEngine(gpt, draft_gpt=GPT(cfg, dtype=jnp.float32), spec_k=2, **spec)
    serve(drafted, prompts([10, 40], seed=9), [5, 5])
    assert _claims(drafted.runner.verify_cfn) == {"thunder.paged_chunk_attention": cfg.n_layer}
    # an engine with a draft model keeps the chunk program that takes no decode rows
    assert _claims(drafted.runner.chunk_cfn) == stated["chunk_cfn"]


# ---------------------------------------------------------------------------
# the ops the model brought
# ---------------------------------------------------------------------------


def _scan_reference(x, dt, A, B, C, h0):
    h, ys = np.asarray(h0, np.float64), []
    for t in range(x.shape[1]):
        h = np.exp(dt[:, t, :, None] * A) * h + (dt[:, t] * x[:, t])[..., None] * B[:, t, None, :]
        ys.append(np.einsum("bdn,bn->bd", h, C[:, t]))
    return np.stack(ys, 1), h


@pytest.mark.parametrize("T", [1, 7, 128])
def test_selective_scan_from_a_carried_state(T):
    rng = np.random.default_rng(T)
    b, d, n = 2, 6, 4
    x, dt = rng.normal(size=(b, T, d)), rng.uniform(0.01, 0.5, size=(b, T, d))
    A, B, C = -rng.uniform(0.5, 2.0, size=(d, n)), rng.normal(size=(b, T, n)), rng.normal(size=(b, T, n))
    h0 = rng.normal(size=(b, d, n))
    args = [jnp.asarray(v, jnp.float32) for v in (x, dt, A, B, C, h0)]
    y, hT = tt.jit(ltorch.selective_scan)(*args)
    want_y, want_h = _scan_reference(x, dt, A, B, C, h0)
    np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-5)
    np.testing.assert_allclose(np.asarray(hT), want_h, atol=2e-5)
    # two halves, the state carried between them, give what the whole gives
    if T > 1:
        k = T // 2
        y1, h1 = tt.jit(ltorch.selective_scan)(*(a[:, :k] if a.ndim == 3 and a.shape[1] == T else a
                                                 for a in args))
        y2, h2 = tt.jit(ltorch.selective_scan)(*(a[:, k:] if a.ndim == 3 and a.shape[1] == T else a
                                                 for a in args[:5]), h1)
        np.testing.assert_allclose(np.concatenate([y1, y2], 1), np.asarray(y), atol=2e-5)
        np.testing.assert_allclose(np.asarray(h2), np.asarray(hT), atol=2e-5)


def test_causal_conv1d_with_a_carried_tail():
    rng = np.random.default_rng(0)
    b, T, d, K = 2, 9, 5, 4
    x, w, bias = rng.normal(size=(b, T, d)), rng.normal(size=(d, K)), rng.normal(size=(d,))
    as_f32 = lambda v: jnp.asarray(v, jnp.float32)  # noqa: E731
    conv = tt.jit(ltorch.causal_conv1d)
    y, xp = conv(as_f32(x), as_f32(w), as_f32(bias), jnp.zeros((b, K - 1, d), jnp.float32))
    padded = np.concatenate([np.zeros((b, K - 1, d)), x], 1)
    want = sum(padded[:, j:j + T] * w[:, j] for j in range(K)) + bias
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)
    # the second half from the first half's tail
    y2, _ = conv(as_f32(x[:, 5:]), as_f32(w), as_f32(bias), xp[:, 5:5 + K - 1])
    np.testing.assert_allclose(np.asarray(y2), want[:, 5:], atol=1e-5)


@pytest.mark.parametrize("T", [None, 16], ids=["decode", "chunk"])
@pytest.mark.parametrize("heads,window", [((8, 2, 16, 16), None), ((8, 2, 16, 16), 12),
                                          ((8, 4, 16, 32), None), ((8, 4, 16, 32), 16),
                                          ((8, 2, 32, 16), 20)])
def test_paged_kernels_with_a_window_and_another_v_width_match_the_decomposition(heads, window, T):
    H, Hkv, D, Dv = heads
    rng = np.random.default_rng(1)
    B, ps, npm = 3, 8, 6
    P = 1 + B * npm
    k_pages = jnp.asarray(rng.normal(size=(P, Hkv, ps, D)), jnp.float32)
    v_pages = jnp.asarray(rng.normal(size=(P, Hkv, ps, Dv)), jnp.float32)
    table = jnp.asarray(1 + np.arange(B * npm).reshape(B, npm), jnp.int32)
    if T is None:
        q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
        where = jnp.asarray(rng.integers(1, npm * ps + 1, (B,)), jnp.int32)
        want = tt.jit(lambda *a: ltorch.paged_attention(*a, window=window))(q, k_pages, v_pages, table, where)
        got = pallasex.paged_attention_decode(q, k_pages, v_pages, table, where, None, window,
                                              interpret=True)
    else:
        q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
        start = rng.integers(0, npm * ps - T + 1, (B,))
        where = jnp.asarray(start[:, None] + np.arange(T)[None], jnp.int32)
        want = tt.jit(lambda *a: ltorch.paged_chunk_attention(*a, window=window))(q, k_pages, v_pages, table, where)
        got = pallasex.paged_chunk_decode(q, k_pages, v_pages, table, where, None, window,
                                          interpret=True)
    assert want.shape[-1] == Dv
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_a_window_never_reads_the_pages_below_it():
    """The table entries of freed pages point at the null page: poison it and every page below
    the window, and the result does not move."""
    rng = np.random.default_rng(2)
    B, H, D, ps, npm, window = 2, 4, 8, 8, 6, 16
    k_pages = rng.normal(size=(1 + B * npm, 2, ps, D)).astype(np.float32)
    v_pages = rng.normal(size=(1 + B * npm, 2, ps, D)).astype(np.float32)
    table = 1 + np.arange(B * npm).reshape(B, npm)
    lens = np.asarray([41, 30])
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    run = lambda k, v, t: np.asarray(pallasex.paged_attention_decode(  # noqa: E731
        q, jnp.asarray(k), jnp.asarray(v), jnp.asarray(t, jnp.int32), jnp.asarray(lens, jnp.int32),
        None, window, interpret=True))
    want = run(k_pages, v_pages, table)
    freed = table.copy()
    for b, n in enumerate(lens):
        freed[b, :(n - window) // ps] = 0
    k_pages[0], v_pages[0] = np.inf, np.inf
    np.testing.assert_array_equal(run(k_pages, v_pages, freed), want)


def test_named_scope_reaches_the_compiled_program():
    def f(x):
        with named_scope("mamba"):
            y = ltorch.exp(x) * 2.0
        return y + 1.0

    cf = tt.jit(f)
    x = jnp.ones((4, 4))
    np.testing.assert_allclose(np.asarray(cf(x)), np.exp(1.0) * 2 + 1, rtol=1e-6)
    trace = tt.last_traces(cf)[-1]
    (region,) = [b for b in trace.bound_symbols if b.subsymbols and b.sym.name.startswith("xla_fusion")]
    tagged = [b for b in region.subsymbols if "scope:mamba" in b.tags]
    assert tagged and len(tagged) < len(region.subsymbols)
    hlo = region.impl.jitted.lower(x).as_text(debug_info=True)
    assert "mamba" in hlo


def test_paged_pages_counters_sum_the_window_pools_and_the_shared_one(model):
    """A window pool's decode walks what the old grid spanned (the window's pages); the shared
    pool's walks the live context of a table `max_seq` wide; the two counters sum both kinds."""
    eng = engine_for(model, max_batch=2)
    observability.enable()
    observability.reset()
    try:
        res = serve(eng, prompts([40], seed=3), [150])[0]
        counters = observability.counters()
    finally:
        observability.disable()
    assert res.n_new_tokens == 150
    steps, width = counters["serve.decode_steps"], 256 // PAGE
    positions = range(40, 40 + steps)  # the step that writes position p reads p + 1 keys
    assert steps == 149
    ends = [-(-(p + 1) // PAGE) for p in positions]
    w_live = sum(e - max(p + 1 - WINDOW, 0) // PAGE + 1 for e, p in zip(ends, positions))  # + the idle slot
    w_spanned = steps * 2 * (WINDOW // PAGE + 1)
    live = sum(e + 1 for e in ends)
    assert counters["serve.paged.pages_live"] == w_live + live
    assert counters["serve.paged.pages_spanned"] == w_spanned + steps * 2 * width
    assert 0.5 < w_live / w_spanned <= 1.0 and live / (steps * 2 * width) < 0.3
    assert not [name for name in counters if name.startswith("serve.paged.window")]


def test_chunk_pages_counters_sum_the_window_pools_and_the_shared_one(model):
    """A chunk's queries see the shared pool from its first page and a window pool from the page of
    the first query's window on; the old grid stepped over the whole table, or over the span of the
    chunk's windows. The two counters sum both kinds, once a chunk dispatch."""
    eng = engine_for(model, max_batch=2)  # chunks of 32, a table 32 wide
    observability.enable()
    observability.reset()
    try:
        res = serve(eng, prompts([100], seed=3), [2])[0]
        counters = observability.counters()
    finally:
        observability.disable()
    assert res.n_new_tokens == 2 and counters["serve.prefill_tokens"] == 100
    chunks = [(0, 32), (32, 32), (64, 32), (96, 16)]  # the last: 4 tokens on the rung of 16
    ends = [-(-(start + cb) // PAGE) for start, cb in chunks]
    w_live = sum(e - max(start - WINDOW + 1, 0) // PAGE for e, (start, _) in zip(ends, chunks))
    w_spanned = sum(-(-(cb + WINDOW - 1) // PAGE) + 1 for _, cb in chunks)
    assert counters["serve.paged.chunk_pages_live"] == w_live + sum(ends) == 20 + 38
    assert counters["serve.paged.chunk_pages_spanned"] == w_spanned + len(chunks) * (256 // PAGE) == 26 + 128
