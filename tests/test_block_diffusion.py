"""Generation by diffusion over blocks (models/block_moe.py, ``ServingEngine(block_diffusion=)``)
against ``benchmark/reference/block_moe.py`` at tiny sizes, float32, seeded random weights."""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from benchmark.lib import manifest
from thunder_tpu import observability
from thunder_tpu.models import litgpt
from thunder_tpu.models.block_moe import tiny_block_moe
from thunder_tpu.serving import ServingEngine

pytestmark = pytest.mark.serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = manifest.load_module(ROOT, "reference", "block_moe")
MASK = 319
K = 4


def seeded(gpt, seed: int = 0):
    key = jax.random.PRNGKey(seed)
    for i, (n, p) in enumerate(gpt.named_parameters()):
        k = jax.random.fold_in(key, i)
        if p.data.ndim >= 2:
            p.data = 0.08 * jax.random.normal(k, p.data.shape, p.data.dtype)
        elif "norm" in n or "ln_f" in n:
            p.data = 1.0 + 0.2 * jax.random.normal(k, p.data.shape, p.data.dtype)
    return gpt


def config_of(gpt, *, block_length=K, steps=2, strategy="low_confidence_dynamic", threshold=0.9):
    cfg = gpt.cfg
    return {"model_type": "sdar_moe", "hidden_size": cfg.n_embd, "num_attention_heads": cfg.n_head,
            "num_key_value_heads": cfg.n_query_groups, "head_dim": cfg.head_size,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_base, "num_experts": cfg.n_expert,
            "num_experts_per_tok": cfg.n_expert_per_token, "norm_topk_prob": cfg.norm_topk_prob,
            "moe_intermediate_size": cfg.moe_intermediate_size, "vocab_size": cfg.vocab_size,
            "num_hidden_layers": cfg.n_layer,
            "assumed": {"qk_norm": True, "router_dtype": "float32"},
            "generation": {"block_length": block_length, "denoising_steps": steps,
                           "remasking_strategy": strategy, "confidence_threshold": threshold,
                           "mask_token_id": MASK}}


def params_of(gpt) -> dict:
    return {n: np.asarray(p.data) for n, p in gpt.named_parameters()}


def engine_of(gpt, *, strategy="low_confidence_dynamic", threshold=0.9, steps=2, block_length=K, **kw):
    keys = dict(max_batch=4, page_size=8, max_seq=128, dtype=jnp.float32, chunk_tokens=32)
    keys.update(kw)
    return ServingEngine(gpt, block_diffusion={"block_length": block_length, "denoising_steps": steps,
                                               "strategy": strategy, "threshold": threshold,
                                               "mask_id": MASK}, **keys)


@pytest.fixture(scope="module")
def gpt():
    return seeded(tiny_block_moe())


@pytest.fixture
def rng():
    return np.random.default_rng(7)


def prompt(rng, n):
    return rng.integers(0, MASK, size=n).astype(np.int32)


# -- the model -----------------------------------------------------------------------------------

@pytest.mark.parametrize("block_length", [4, 3, 1])
def test_compiled_forward_under_the_block_causal_mask_equals_the_reference(gpt, rng, block_length):
    toks = prompt(rng, 22)
    got = np.asarray(tt.jit(gpt)(jnp.asarray(toks[None]), block_length))[0]
    want = np.asarray(REF.forward(config_of(gpt, block_length=block_length), params_of(gpt), toks))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_forward_without_a_block_length_is_plain_causal_and_the_control_disagrees(gpt, rng):
    toks = prompt(rng, 22)
    plain = np.asarray(tt.jit(gpt)(jnp.asarray(toks[None])))[0]
    wrong, what = REF.control(config_of(gpt))
    assert "block_length 1" in what
    np.testing.assert_allclose(plain, np.asarray(REF.forward(wrong, params_of(gpt), toks)), atol=1e-5)
    under = np.asarray(REF.forward(config_of(gpt), params_of(gpt), toks))
    assert np.abs(under - plain).max() > 1e-2  # a position sees the rest of its block, or not


@pytest.mark.parametrize("norm_qk", [True, False])
def test_norm_qk_against_a_hand_written_norm(rng, norm_qk):
    cfg = litgpt.Config(n_layer=1, n_head=4, n_query_groups=2, n_embd=64, head_size=16,
                        norm_qk=norm_qk, norm_eps=1e-6)
    attn = seeded(litgpt.CausalSelfAttention(cfg))
    assert hasattr(attn, "norm_q") == norm_qk
    x = jnp.asarray(rng.standard_normal((1, 9, 64)), jnp.float32)
    cos, sin = litgpt.build_rope_cache(9, 16)
    got = np.asarray(tt.jit(attn)(x, cos, sin))[0]

    p = {n: np.asarray(q.data, np.float64) for n, q in attn.named_parameters()}
    qkv = (np.asarray(x[0], np.float64) @ p["attn.weight"].T).reshape(9, 2, 4, 16)
    q, k, v = qkv[:, :, :2].reshape(9, 4, 16), qkv[:, :, 2], qkv[:, :, 3]
    if norm_qk:
        def norm(a, w):
            return a / np.sqrt((a * a).mean(-1, keepdims=True) + 1e-6) * w
        q, k = norm(q, p["norm_q.weight"]), norm(k, p["norm_k.weight"])

    def rope(a):
        c, s = np.asarray(cos, np.float64)[:, None, :8], np.asarray(sin, np.float64)[:, None, :8]
        return np.concatenate([a[..., :8] * c - a[..., 8:] * s, a[..., 8:] * c + a[..., :8] * s], -1)

    q, k = rope(q), np.repeat(rope(k), 2, 1)
    scores = np.einsum("thd,shd->hts", q, k) / 4.0
    scores = np.where(np.tril(np.ones((9, 9), bool))[None], scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.einsum("hts,shd->thd", probs, np.repeat(v, 2, 1)).reshape(9, 64) @ p["proj.weight"].T
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_split_qkv_rope_norms_as_the_attention_does(gpt, rng):
    """The served layers' q and k (``inference.split_qkv_rope``) are the reference's: normed by
    head, then roped."""
    from thunder_tpu.inference import split_qkv_rope

    block, cfg = gpt.h[0], gpt.cfg
    x = jnp.asarray(rng.standard_normal((1, 6, cfg.n_embd)), jnp.float32)
    from thunder_tpu.nn.module import functional_params

    def qkv(prm, a, c, s):
        with functional_params(gpt, prm):
            return split_qkv_rope(block, cfg, a, c, s)

    _, k, _ = tt.jit(qkv)({n: q.data for n, q in gpt.named_parameters()}, x, gpt.cos[:6], gpt.sin[:6])
    p = {n[len("h.0."):]: v for n, v in params_of(gpt).items() if n.startswith("h.0.")}
    _, want = REF._attention(config_of(gpt), p, jnp.asarray(x[0]))
    np.testing.assert_allclose(np.asarray(k)[0].transpose(1, 0, 2), np.asarray(want), atol=1e-5)
    off = dict(config_of(gpt), assumed={"qk_norm": False, "router_dtype": "float32"})
    assert np.abs(np.asarray(REF._attention(off, p, jnp.asarray(x[0]))[1]) - np.asarray(want)).max() > 0.1


# -- the block program against the reference --------------------------------------------------------

def served_keys(engine, layer, pages, n):
    """The first n cached key rows of ``layer`` on ``pages``: (n, kv heads, head size)."""
    pool = np.asarray(engine.cache.state[layer][0])[np.asarray(pages, np.int32)]  # (p, H, ps, D)
    return pool.transpose(0, 2, 1, 3).reshape(-1, pool.shape[1], pool.shape[3])[:n]


def reference_keys(config, params, toks, layer):
    x = REF.embed(config, {"wte.weight": params["wte.weight"]}, toks)
    for i in range(layer + 1):
        x, made = REF.layer(config, REF.layer_params(params, i), x)
    return np.asarray(made["k"])


def pool_rows(pool, pages, n):
    """``served_keys`` of a pool already on the host, (P, H, ps, D)."""
    got = pool[np.asarray(pages, np.int32)]
    return got.transpose(0, 2, 1, 3).reshape(-1, pool.shape[1], pool.shape[3])[:n]


# (block length, page size, strategy, threshold): blocks of 3 on pages of 4 straddle page edges; a
# threshold of 0.9 no pass clears (two denoise passes a block), 0.01 some do, 0.0 all (one a block)
ROADS = [(4, 8, "low_confidence_static", 0.9), (4, 8, "low_confidence_dynamic", 0.01),
         (3, 4, "low_confidence_static", 0.9), (3, 4, "low_confidence_dynamic", 0.01),
         (4, 8, "low_confidence_dynamic", 0.0)]


@pytest.fixture(scope="module", params=ROADS, ids=lambda r: f"K{r[0]}-{r[2][15:]}-{r[3]}")
def spied(request):
    """One request served alone with every run of the block program noted: what it was given
    (the two blocks, the first's position, which are live), the logits it returned and the
    last layer's key pool before and after."""
    K_, ps, strategy, threshold = request.param
    gpt = seeded(tiny_block_moe(head_size=32), 1)
    config = config_of(gpt, block_length=K_, strategy=strategy, threshold=threshold)
    engine = engine_of(gpt, block_length=K_, page_size=ps, chunk_tokens=24, max_seq=96,
                       strategy=strategy, threshold=threshold)
    engine.record_block_states = True
    last, inner, calls = engine.cfg.n_layer - 1, engine.runner.block_cfn, []

    def block_cfn(params, toks, state, tables, pos, live):
        before = np.asarray(state[last][0])  # read before the program consumes it
        out = inner(params, toks, state, tables, pos, live)
        calls.append({"toks": np.asarray(toks)[0], "pos": int(np.asarray(pos)[0]), "live": np.asarray(live)[0],
                      "logits": np.asarray(out[0])[0], "before": before, "after": np.asarray(out[1][last][0])})
        return out

    engine.runner.block_cfn = block_cfn
    p = prompt(np.random.default_rng(11), 14)
    fut = engine.submit(p, max_new_tokens=9)
    engine.drain()
    res = fut.result()
    # the sequence as it was settled: the prompt's whole blocks, then every settled block
    seq = list(p[:len(p) // K_ * K_]) + [int(t) for _, toks, m in res.block_states if not m.any() for t in toks]
    return {"K": K_, "ps": ps, "engine": engine, "config": config, "params": params_of(gpt), "prompt": p,
            "res": res, "calls": calls, "seq": seq, "last": last}


def test_a_pass_that_settles_a_block_and_denoises_the_next_equals_the_reference_on_both(spied):
    """The logits of the K denoised rows are the reference's full forward over the settled
    sequence and the block going in, so they saw the first block's keys as THIS pass wrote them
    (the pool held a denoise pass's until then); the K settled rows' keys are the reference's
    over the final tokens."""
    K_, seq, res = spied["K"], spied["seq"], spied["res"]
    joined = 0
    for call in spied["calls"]:
        pos, (settles, denoises) = call["pos"], call["live"]
        if denoises:
            toks = np.asarray(seq[:pos + K_] + list(call["toks"][K_:]), np.int32)
            want = np.asarray(REF.forward(spied["config"], spied["params"], toks))[pos + K_:]
            np.testing.assert_allclose(call["logits"], want, atol=2e-4)
        if settles:
            assert list(call["toks"][:K_]) == seq[pos:pos + K_]
            want = reference_keys(spied["config"], spied["params"], np.asarray(seq[:pos + K_], np.int32), spied["last"])
            got = pool_rows(call["after"], res.pages, pos + K_)
            np.testing.assert_allclose(got[pos:].reshape(K_, -1), want[pos:].reshape(K_, -1), atol=2e-5)
            # ... and other rows than the pass found there: a denoise pass's, computed over mask tokens
            stale = pool_rows(call["before"], res.pages, pos + K_)
            assert np.abs(stale[pos:] - got[pos:]).max() > 1e-3
            joined += int(denoises)
    assert joined == spied["engine"].block_settles_joined > 0


def test_a_block_that_is_not_live_writes_no_row_of_the_sequence(spied):
    """A settled block is written once: in its later passes the first block is padding, and in
    the pass that settles a sequence's last block the second is. What a pass changes of the pool
    are the rows of its live blocks (and the null page, where padding rows go)."""
    K_, ps, res = spied["K"], spied["ps"], spied["res"]
    seen = set()
    for call in spied["calls"]:
        pos, live = call["pos"], tuple(bool(f) for f in call["live"])
        seen.add(live)
        before, after = (pool_rows(call[k], res.pages, len(res.pages) * ps) for k in ("before", "after"))
        changed = set(np.flatnonzero(np.abs(after - before).reshape(len(after), -1).max(-1) > 0).tolist())
        allowed = {pos + b * K_ + j for b in range(2) if live[b] for j in range(K_)}
        assert changed <= allowed and (changed or not any(live))
    # the pass after the prompt and a block's later passes; a settle beside a denoise; the last
    # block's settling; and the pass in flight when that landed, thrown away
    assert seen == {(False, True), (True, True), (True, False), (False, False)}


def test_the_last_block_is_settled_before_the_future_resolves(spied):
    """The pool's rows are the reference's over the finished tokens for EVERY returned position,
    the last block's included: its settling is a pass of its own (a commit pass), fetched before
    the request retires."""
    seq, res, engine = spied["seq"], spied["res"], spied["engine"]
    n = len(seq)
    assert n == -(-len(res.tokens) // spied["K"]) * spied["K"] and list(res.tokens) == seq[:len(res.tokens)]
    want = reference_keys(spied["config"], spied["params"], np.asarray(seq, np.int32), spied["last"])
    got = served_keys(engine, spied["last"], res.pages, n)
    np.testing.assert_allclose(got.reshape(n, -1), want.reshape(n, -1), atol=2e-5)
    final = [c for c in spied["calls"] if c["live"].any()][-1]
    assert tuple(final["live"]) == (True, False) and final["pos"] == n - spied["K"]
    assert res.n_new_tokens == 9 and np.array_equal(res.tokens[:14], spied["prompt"])


def test_block_states_and_unmasked_keep_the_order_a_replay_walks(spied):
    """``[.., denoise, denoise, settled, denoise, ..]``: a block's denoise entries with fewer and
    fewer masked, then its settled entry (none masked), BEFORE the next block's first denoise entry
    though one pass made both; ``unmasked`` numbers the entries. Each denoise entry's fills are the
    reference's choice for the tokens that went in (what ``drivers/serve_denoise.py: replay`` walks)."""
    K_, res, config = spied["K"], spied["res"], spied["config"]
    states, seq = res.block_states, list(spied["prompt"][:len(spied["prompt"]) // K_ * K_])
    want = REF.generate(config, spied["params"], spied["prompt"], 9)
    assert len(states) == want["passes"] and list(res.unmasked) == want["order"]
    for (pos, toks, masked), (w_pos, w_toks, w_masked) in zip(states, want["states"]):
        assert pos == w_pos and list(toks) == list(w_toks) and np.array_equal(masked, w_masked)
    filled_at = {}
    for position, n in res.unmasked:
        filled_at.setdefault(n, []).append(position)
    for n, (pos, toks, masked) in enumerate(states):
        assert pos == len(seq)
        if masked.any():
            nxt_pos, _, nxt_masked = states[n + 1]  # the same block, further on
            assert nxt_pos == pos and not (nxt_masked & ~masked).any()
            assert filled_at[n] == [pos + int(j) for j in np.flatnonzero(masked & ~nxt_masked)]
        else:
            assert n not in filled_at
            seq += [int(t) for t in toks]
    assert seq == spied["seq"] and not states[-1][2].any()


def test_joined_settles_and_the_last_block_make_up_the_blocks_done(spied):
    stats, res = spied["engine"].stats(), spied["res"]
    blocks = sum(1 for s in res.block_states if not s[2].any())
    assert stats["blocks_done"] == blocks == -(-(14 % spied["K"] + 9) // spied["K"])
    assert stats["block_settles_joined"] == blocks - 1  # every block but the sequence's last
    # a sequence pass a denoise, and one more for the last block's settling; then the pass in flight
    denoises = len(res.block_states) - blocks
    assert stats["block_passes"] == len(spied["calls"]) == denoises + 1 + 1
    if spied["config"]["generation"]["confidence_threshold"] == 0.0:
        assert denoises == blocks  # a block a pass


def test_reference_replay_of_generates_own_states_gives_its_choices_and_the_served_keys(gpt, rng):
    """``reference.replay`` over the states ``reference.generate`` recorded reproduces each pass's
    choice, and its keys are the rows the engine caches for the same request."""
    config, params = config_of(gpt), params_of(gpt)
    p = prompt(rng, 14)
    want = REF.generate(config, params, p, 7)
    got = REF.replay(config, params, p, want["states"])
    assert len(got["logits"]) == want["passes"] == len(want["states"])
    for (pos, toks, masked), nxt, logits in zip(want["states"], want["states"][1:], got["logits"]):
        if masked.any():
            filled = masked & ~nxt[2]
            l = np.array(logits)
            l[:, MASK] = -np.inf
            assert np.array_equal(np.asarray(nxt[1])[filled], l.argmax(-1)[filled])
    engine = engine_of(gpt)
    fut = engine.submit(p, max_new_tokens=7)
    engine.drain()
    res = fut.result()
    rows = got["keys"].shape[0]
    assert rows == 12 + 12  # the prompt's whole blocks and three generated ones
    np.testing.assert_allclose(served_keys(engine, gpt.cfg.n_layer - 1, res.pages, rows).reshape(rows, -1),
                               got["keys"].reshape(rows, -1), atol=2e-5)


# -- the engine against the reference's loop --------------------------------------------------------

@pytest.mark.parametrize("strategy,threshold", [("low_confidence_static", 0.9), ("low_confidence_dynamic", 0.9),
                                                ("low_confidence_dynamic", 0.01),
                                                ("low_confidence_dynamic", 0.0)])
@pytest.mark.parametrize("L,n_new", [(13, 10), (8, 12), (3, 5), (37, 7)])
def test_engine_follows_reference_generate_token_for_token_and_position_for_position(
        gpt, rng, strategy, threshold, L, n_new):
    """Prompts with and without a tail (13 = 12 + 1, 8, 3 < K, 37 = two chunks of 32 + a tail),
    answers on and off a block's edge; thresholds of 0.01 and 0 are ones that passes DO clear (some, all), so a
    block takes fewer passes and the host must read how many positions each filled."""
    config = config_of(gpt, strategy=strategy, threshold=threshold)
    engine = engine_of(gpt, strategy=strategy, threshold=threshold)
    p = prompt(rng, L)
    fut = engine.submit(p, max_new_tokens=n_new)
    engine.drain()
    res = fut.result()
    want = REF.generate(config, params_of(gpt), p, n_new)
    assert res.n_new_tokens == n_new and list(res.new_tokens) == want["tokens"]
    # the positions that were returned, in the order they were filled
    assert list(res.unmasked) == want["order"]
    assert MASK not in res.new_tokens
    if threshold == 0.0:  # one denoise and one settling a block by the reference's count: a pass a block, and one
        assert want["passes"] == 2 * -(-(L % K + n_new) // K) and engine.block_passes - 1 == want["passes"] // 2 + 1


@pytest.mark.parametrize("block_length,page_size,strategy,threshold", ROADS[:4])
def test_alone_equals_batched_tokens_and_orders(rng, block_length, page_size, strategy, threshold):
    gpt = seeded(tiny_block_moe(head_size=32), 1)
    keys = dict(block_length=block_length, page_size=page_size, chunk_tokens=24, max_seq=96, strategy=strategy,
                threshold=threshold)
    reqs = [(13, 10), (8, 12), (3, 5), (37, 7), (21, 9), (16, 4)]
    prompts = [prompt(rng, L) for L, _ in reqs]
    alone = []
    engine = engine_of(gpt, **keys)
    for p, (_, n) in zip(prompts, reqs):
        fut = engine.submit(p, max_new_tokens=n)
        engine.drain()
        alone.append(fut.result())
    engine = engine_of(gpt, **keys)  # 4 slots for 6 requests: slots are reused
    futs = [engine.submit(p, max_new_tokens=n) for p, (_, n) in zip(prompts, reqs)]
    engine.drain()
    for a, f in zip(alone, futs):
        b = f.result()
        assert np.array_equal(a.new_tokens, b.new_tokens) and a.unmasked == b.unmasked
    assert engine.cache.allocator.n_used == 0


def test_background_thread_and_temperature_draws_are_position_keyed(gpt, rng):
    p = prompt(rng, 10)
    outs = []
    for others in (0, 3):
        engine = engine_of(gpt)
        engine.start()
        futs = [engine.submit(prompt(rng, 9 + i), max_new_tokens=8, temperature=0.8, seed=i) for i in range(others)]
        fut = engine.submit(p, max_new_tokens=11, temperature=0.8, seed=123)
        outs.append(fut.result(timeout=120))
        [f.result(timeout=120) for f in futs]
        engine.stop()
    assert np.array_equal(outs[0].new_tokens, outs[1].new_tokens) and outs[0].unmasked == outs[1].unmasked
    greedy = engine_of(gpt)
    f = greedy.submit(p, max_new_tokens=11)
    greedy.drain()
    assert not np.array_equal(f.result().new_tokens, outs[0].new_tokens)


@pytest.mark.parametrize("n_new", [1, 3, 4, 5, 8])
def test_max_new_tokens_off_a_block_boundary_returns_what_was_asked(gpt, rng, n_new):
    engine = engine_of(gpt)
    p = prompt(rng, 10)  # a tail of 2: the first block gives 2 new tokens
    long = engine.submit(p, max_new_tokens=14)
    fut = engine.submit(p, max_new_tokens=n_new)
    engine.drain()
    res = fut.result()
    assert res.n_new_tokens == n_new == len(res.new_tokens) and res.finish_reason == "length"
    assert np.array_equal(res.new_tokens, long.result().new_tokens[:n_new])
    assert res.tbot_s == 0.0 if n_new <= K else res.tbot_s > 0.0


def test_eos_ends_a_sequence_at_its_blocks_settling(gpt, rng):
    p = prompt(rng, 12)
    engine = engine_of(gpt)
    free = engine.submit(p, max_new_tokens=12)
    engine.drain()
    toks = free.result().new_tokens
    eos = int(toks[5])  # inside the second block
    fut = engine.submit(p, max_new_tokens=12, eos_id=eos)
    engine.drain()
    res = fut.result()
    first = list(toks).index(eos)
    assert res.finish_reason == "eos" and list(res.new_tokens) == list(toks[:first + 1])
    assert engine.cache.allocator.n_used == 0 and not engine._has_work()


def test_eos_in_a_block_throws_away_the_rows_that_rode_along(gpt, rng):
    """The pass that settles the block with ``eos_id`` in it has denoised the next block too: those
    rows, and the pass in flight behind them, are counted as discarded, and none of their
    positions is returned."""
    p = prompt(rng, 12)
    engine = engine_of(gpt)
    free = engine.submit(p, max_new_tokens=12)
    engine.drain()
    toks = free.result().new_tokens
    eos = int(toks[5])
    first = list(toks).index(eos)
    observability.enable()
    try:
        observability.reset()
        engine = engine_of(gpt)
        engine.record_block_states = True
        fut = engine.submit(p, max_new_tokens=12, eos_id=eos)
        engine.drain()
        c = observability.counters()
    finally:
        observability.disable()
    res = fut.result()
    ended = 12 + first // K * K  # the block the sequence ended in
    assert res.finish_reason == "eos" and res.n_new_tokens == first + 1 < 12
    assert res.block_states[-1][0] == ended and not res.block_states[-1][2].any()
    assert all(pos < ended + K for pos, _ in res.unmasked)
    # the next block's first denoise in the settling pass, and its second in the pass behind it
    assert c["serve.decode_discarded"] == 2 and c["serve.block_settles_joined"] == c["serve.blocks_done"] == first // K + 1
    assert engine.stats()["block_settles_joined"] == engine.blocks_done
    # the rows routed are those of every block run, the thrown away ones too
    runs = len(res.block_states) + c["serve.decode_discarded"]
    assert c["serve.moe.rows_routed"] == runs * K * gpt.cfg.n_expert_per_token * gpt.cfg.n_layer
    assert c["serve.tokens"] == len(res.unmasked)  # what was thrown away counts for no token


def test_preempted_victim_resumes_from_its_committed_blocks(gpt, rng):
    p = prompt(rng, 13)
    solo = engine_of(gpt)
    f = solo.submit(p, max_new_tokens=16)
    solo.drain()
    engine = engine_of(gpt)
    fut = engine.submit(p, max_new_tokens=16, lane="batch")
    for _ in range(9):
        engine._step_once()
    assert engine._preempt_one() and engine.preempted == 1
    engine.drain()
    res = fut.result()
    assert engine.resumed == 1 and np.array_equal(res.new_tokens, f.result().new_tokens)
    assert sorted(pos for pos, _ in res.unmasked) == list(range(13, 13 + 16 + (-(13 + 16) % K)))


def test_quantized_weights_serve_blocks(rng):
    from thunder_tpu.models.litgpt import GPT, Config

    def run(n_others):
        gpt = seeded(GPT(Config.from_name("tiny-llama2")), 3)
        engine = ServingEngine(gpt, max_batch=4, page_size=8, max_seq=128, dtype=jnp.float32, quantize="int8",
                               block_diffusion={"block_length": 4, "denoising_steps": 2, "mask_id": MASK})
        others = [engine.submit(prompt(np.random.default_rng(i), 9 + i), max_new_tokens=6) for i in range(n_others)]
        fut = engine.submit(prompt(np.random.default_rng(99), 11), max_new_tokens=9)
        engine.drain()
        [o.result() for o in others]
        return fut.result()

    a, b = run(0), run(2)
    assert a.n_new_tokens == 9 and np.array_equal(a.new_tokens, b.new_tokens) and a.unmasked == b.unmasked


def test_unset_reproduces_the_plain_engine_on_tiny_moe(rng):
    """``block_diffusion=None`` is today's engine: the same tokens as the dense engine's solo
    generation, the pin the serving tests hold the plain path to."""
    from thunder_tpu.inference import GPTInference
    from thunder_tpu.models.moe import tiny_moe

    gpt = tiny_moe()
    engine = ServingEngine(gpt, max_batch=4, page_size=8, max_seq=128, dtype=jnp.float32)
    assert engine.block is None
    ps = [rng.integers(0, 320, size=n).astype(np.int32) for n in (5, 17, 30)]
    futs = [engine.submit(p, max_new_tokens=6) for p in ps]
    engine.drain()
    solo = GPTInference(gpt, max_seq=128, dtype=jnp.float32)
    for p, f in zip(ps, futs):
        want = np.asarray(solo.generate(jnp.asarray(p[None]), 6)[0])[0, len(p):]
        assert np.array_equal(f.result().new_tokens, want)
        assert f.result().unmasked == () and f.result().block_states == ()


# -- refusals ----------------------------------------------------------------------------------------

def _spec(**over):
    return {"block_length": 4, "denoising_steps": 2, "mask_id": MASK, **over}


@pytest.mark.parametrize("keys,words", [
    (dict(prefix_sharing=True), "prefix_sharing=True cannot go with block_diffusion="),
    (dict(draft_gpt="draft"), "draft_gpt= (speculative decoding) cannot go with block_diffusion="),
    (dict(chunk_tokens=24, page_size=8, block_diffusion=_spec(block_length=5, denoising_steps=5)),
     "must be a multiple of block_length=5"),
    (dict(block_diffusion=_spec(denoising_steps=5)), "denoising_steps=5 between 1 and it"),
    (dict(block_diffusion=_spec(strategy="random")), "is neither 'low_confidence_dynamic' nor"),
    (dict(block_diffusion=_spec(mask_id=4096)), "is no row of the embedding"),
    (dict(block_diffusion={"block_length": 4}), "block_length and mask_id always"),
    (dict(block_diffusion=_spec(steps=2)), "takes the keys"),
])
def test_refused_combinations_say_why(gpt, keys, words):
    keys = dict(keys)
    if keys.get("draft_gpt") == "draft":
        keys["draft_gpt"] = seeded(tiny_block_moe(n_layer=1), 5)
    base = dict(max_batch=2, page_size=8, max_seq=64, dtype=jnp.float32, block_diffusion=_spec())
    with pytest.raises(ValueError) as e:
        ServingEngine(gpt, **{**base, **keys})
    assert words in str(e.value)


@pytest.mark.parametrize("model", ["latent", "hybrid"])
def test_models_whose_layers_cannot_run_a_block_are_refused(model):
    if model == "latent":
        from thunder_tpu.models.latent_moe import Config, LatentMoE

        gpt = LatentMoE(Config())
    else:
        from thunder_tpu.models.sambay import Config, SambaY

        gpt = SambaY(Config())
    with pytest.raises(ValueError) as e:
        ServingEngine(gpt, max_batch=2, page_size=8, max_seq=64, dtype=jnp.float32, block_diffusion=_spec())
    assert "cannot serve a model with window, recurrent or latent layers" in str(e.value)


def test_a_request_whose_last_block_does_not_fit_is_refused(gpt, rng):
    engine = engine_of(gpt, max_seq=32)
    ok = engine.submit(prompt(rng, 20), max_new_tokens=12)   # ends at 32
    bad = engine.submit(prompt(rng, 21), max_new_tokens=10)  # 31 tokens, but the block ends at 32: fits
    worse = engine.submit(prompt(rng, 22), max_new_tokens=11)  # 33 -> 36
    engine.drain()
    assert ok.result().n_new_tokens == 12 and bad.result().n_new_tokens == 10
    with pytest.raises(ValueError) as e:
        worse.result()
    assert "the last block of 4 whole" in str(e.value)


# -- counters ------------------------------------------------------------------------------------------

@pytest.mark.parametrize("block_length,page_size,strategy,threshold", ROADS[:4])
def test_counters_add_up(rng, block_length, page_size, strategy, threshold):
    gpt = seeded(tiny_block_moe(head_size=32), 1)
    Kb = block_length
    observability.enable()
    try:
        observability.reset()
        engine = engine_of(gpt, block_length=Kb, page_size=page_size, chunk_tokens=24, max_seq=96,
                           strategy=strategy, threshold=threshold)
        reqs = [(13, 10), (8, 12), (3, 5), (21, 9), (37, 7)]
        futs = [engine.submit(prompt(rng, L), max_new_tokens=n) for L, n in reqs]
        engine.drain()
        results = [f.result() for f in futs]
        c = observability.counters()
    finally:
        observability.disable()
    unmasked = sum(len(r.unmasked) for r in results)
    assert c["serve.tokens"] == unmasked
    # every generated position is filled once; the last block is generated whole
    assert unmasked == sum(-(-(L % Kb + n) // Kb) * Kb - L % Kb for L, n in reqs)
    assert c["serve.block_passes"] == c["serve.decode_steps"] == engine.block_passes
    blocks = sum(-(-(L % Kb + n) // Kb) for L, n in reqs)
    assert c["serve.blocks_done"] == c["serve.block_slot_commits"] == engine.blocks_done == blocks
    # every block but a sequence's last is settled in the pass that first denoises the next
    assert c["serve.block_settles_joined"] == engine.block_settles_joined == blocks - len(reqs)
    # a sequence's passes: one a denoise (a settling rides in the next block's first), and one
    # more that settles its last block
    denoises = sum(len({n for _, n in r.unmasked}) for r in results)
    assert c["serve.block_slot_passes"] == denoises + len(reqs)
    assert c["serve.blocks_done"] * Kb >= c["serve.tokens"]
    assert 0 < c["serve.block_commits"] <= c["serve.block_passes"]
    assert c["serve.decode_overlapped"] >= c["serve.block_passes"] - 3
    # the routing counters of the passes: the rows of every block RUN (a settled and a denoised
    # block a pass are two) routed to n_expert_per_token experts, all held; padding routes nowhere
    assert c["serve.moe.rows_routed"] == c["serve.moe.rows_held"] \
        == (blocks + denoises) * Kb * gpt.cfg.n_expert_per_token * gpt.cfg.n_layer
    assert c["serve.decode_discarded"] == len(reqs)  # the pass in flight when a sequence's last block landed
    stats = engine.stats()
    assert stats["blocks_done"] == c["serve.blocks_done"] and stats["block_passes"] == c["serve.block_passes"]
    assert stats["block_settles_joined"] == c["serve.block_settles_joined"]


# -- the two kernels through the v5e's compiler at the published widths (no chip needed) -----------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("tokens_in", [256, 512], ids=["chunk-256", "pass-64-slots-x-two-blocks-or-chunk-512"])
def test_the_ragged_kernel_compiles_for_the_v5e_at_128_experts_of_768(one_chip, tokens_in):
    """The ragged kernel's third shape: 128 held experts of width 768 at d 2048, 8 a token; the
    ``vmem`` and ``lanes`` arms of analysis/memory.py pass it (a whole panel of 768 hidden
    columns is one weight tile)."""
    from thunder_tpu.analysis import budget
    from thunder_tpu.executors import pallasex
    from thunder_tpu.models import moe

    bf, E, D, H, k = jnp.bfloat16, 128, 2048, 768, 8
    tile = moe.ragged_tile(tokens_in * k, E)
    R = -(-tokens_in * k // tile) * tile + E * tile
    assert (tile, R) == ((32, 6144) if tokens_in == 256 else (64, 12288))
    assert budget.ragged_mlp_block_h(tile, D, H, 2, 2) == H and D % 128 == 0 and H % 128 == 0

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with jax.enable_x64(False):   # as on the chip: conftest.py turns x64 on for the CPU tests
        compiled = jax.jit(lambda r, g, u, d, s: pallasex.ragged_mlp_fused(r, g, u, d, s, tile, interpret=False)).lower(
            sds((R, D), bf), sds((E, D, H), bf), sds((E, D, H), bf), sds((E, H, D), bf), sds((E,), jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("B,T", [(64, 4), (64, 8), (1, 512)],
                         ids=["64-slots-x-a-block-of-4", "pass-64-slots-x-two-blocks", "chunk-512"])
def test_the_paged_chunk_kernel_compiles_for_the_v5e_at_a_block_of_4(one_chip, B, T):
    """The paged chunk kernel at one block a slot (64 sequences of 4 rows, 8 query heads a key head:
    32 rows a key head), at a pass's shape (two blocks a slot: 8 rows, 64 a key head) and at a prompt
    chunk's, over the cell's pools."""
    from thunder_tpu.executors import pallasex

    bf = jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with jax.enable_x64(False):
        compiled = jax.jit(lambda q, k, v, t, p: pallasex.paged_chunk_decode(q, k, v, t, p, 0.088, interpret=False)).lower(
            sds((B, 32, T, 128), bf), sds((2049, 4, 64, 128), bf), sds((2049, 4, 64, 128), bf),
            sds((B, 32), jnp.int32), sds((B, T), jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
