"""The latent-attention mixture-of-experts model (models/latent_moe.py, moe.HeldExperts) at small
sizes on the CPU: rope and YaRN against their formulae, the absorbed form against the expanded one,
the expert shares against the whole layer, the two Pallas kernels interpreted against jax.numpy,
the paged engine against the model's own forward, the latent cache declaration, the counters, and
both kernels compiled by the v5e's compiler at the published widths."""
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import observability
from thunder_tpu.executors import pallasex
from thunder_tpu.models import moe
from thunder_tpu.models.latent_moe import (Config, LatentMoE, rope_interleaved, rope_tables,
                                           yarn_inv_freq)
from thunder_tpu.ops import ltorch
from thunder_tpu.serving import ServingEngine
from thunder_tpu.serving.kv_pages import PagedKVCache, PagedLatent

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINE = dict(max_batch=4, page_size=8, max_seq=256, chunk_tokens=32, min_bucket=16, dtype=jnp.float32)


def reference():
    """benchmark/reference/latent_moe.py: plain jax.numpy, nothing of thunder_tpu."""
    spec = importlib.util.spec_from_file_location(
        "reference_latent_moe", os.path.join(ROOT, "benchmark", "reference", "latent_moe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def seeded(cfg: Config, seed: int = 0, std: float = 0.1):
    model = LatentMoE(cfg, dtype=jnp.float32)
    key = jax.random.key(seed)
    for i, (name, p) in enumerate(sorted(model.named_parameters())):
        if p.data.ndim >= 2 or name.endswith("bias"):
            p.data = std * jax.random.normal(jax.random.fold_in(key, i), p.data.shape, jnp.float32)
    return model


def tokens(n: int, vocab: int = 512, seed: int = 0):
    return np.random.RandomState(seed).randint(0, vocab, (n,)).astype(np.int32)


def claims(fn, symbol: str) -> int:
    """How often the Pallas executor runs ``symbol`` in ``fn``'s executed trace."""
    from benchmark.lib.harness import pallas_claims

    return pallas_claims(tt.last_traces(fn)[-1])[symbol]


TINY = Config(n_layer=2, n_routed_experts=8, experts_held=(2, 6), rope_factor=4.0, rope_original=32,
              mscale_all_dim=1.0, query_scaling_beta=0.1)


# -- rope ---------------------------------------------------------------------------------------

def test_yarn_keeps_the_fast_pairs_and_divides_the_slow_ones():
    dim, theta, factor, original = 64, 10000.0, 128.0, 8192
    inv = yarn_inv_freq(dim, theta, factor, original, 32.0, 1.0)
    plain = theta ** (-np.arange(0, dim, 2) / dim)
    # wavelength 2 pi / f: a pair that turns more than 32 times in 8192 positions is left alone,
    # one that turns less than once is divided by the factor, the ramp lies between
    turns = original * plain / (2 * math.pi)
    assert np.allclose(inv[turns > 40], plain[turns > 40], rtol=1e-6)
    assert np.allclose(inv[turns < 0.8], plain[turns < 0.8] / factor, rtol=1e-6)
    between = (turns < 30) & (turns > 1.2)
    assert between.any() and np.all(inv[between] < plain[between]) and np.all(inv[between] > plain[between] / factor)
    assert np.allclose(yarn_inv_freq(dim, theta, 1.0, original, 32.0, 1.0), plain, rtol=1e-6)
    ref = reference()
    config = {"qk_rope_head_dim": dim, "rope_parameters": {
        "rope_theta": theta, "factor": factor, "original_max_position_embeddings": original,
        "beta_fast": 32, "beta_slow": 1, "rope_type": "yarn"}}
    assert np.allclose(ref.yarn_inv_freq(config), inv, rtol=1e-6)


def test_rope_turns_interleaved_pairs():
    cfg = Config(qk_rope_head_dim=16, block_size=64)
    cos, sin = rope_tables(cfg)
    x = np.random.RandomState(1).randn(1, 64, 3, 16).astype(np.float32)
    got = np.asarray(tt.jit(lambda x, c, s: rope_interleaved(x, c, s))(
        jnp.asarray(x), cos[None, :, None], sin[None, :, None]))
    # pair (2i, 2i + 1) as one complex number times exp(i pos f_i)
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * np.exp(
        1j * np.arange(64)[:, None] * yarn_inv_freq(16, cfg.rope_theta, 1.0, 8192, 32, 1)[None])[None, :, None]
    want = np.stack([z.real, z.imag], -1).reshape(x.shape)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert cfg.softmax_scale == pytest.approx(32 ** -0.5)
    assert TINY.softmax_scale == pytest.approx(32 ** -0.5 * (0.1 * math.log(4.0) + 1.0) ** 2)


# -- the model against the plain reference ----------------------------------------------------------

def as_published(cfg: Config) -> dict:
    lo, hi = cfg.experts_held
    return {"model_type": "mistral4", "num_hidden_layers": cfg.n_layer, "hidden_size": cfg.n_embd,
            "num_attention_heads": cfg.n_head, "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim, "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "moe_intermediate_size": cfg.moe_intermediate_size,
            "n_routed_experts": hi - lo, "experts_held": [lo, hi],
            "reduced_from": {"n_routed_experts": cfg.n_routed_experts},
            "num_experts_per_tok": cfg.n_expert_per_token, "n_shared_experts": cfg.n_shared_experts,
            "norm_topk_prob": cfg.norm_topk_prob, "routed_scaling_factor": cfg.routed_scaling_factor,
            "rms_norm_eps": cfg.norm_eps, "rope_interleave": True, "vocab_size": cfg.vocab_size,
            "rope_parameters": {"rope_theta": cfg.rope_theta, "factor": cfg.rope_factor, "rope_type": "yarn",
                                "original_max_position_embeddings": cfg.rope_original,
                                "beta_fast": cfg.beta_fast, "beta_slow": cfg.beta_slow, "mscale": cfg.mscale,
                                "mscale_all_dim": cfg.mscale_all_dim or 1.0,
                                "llama_4_scaling_beta": cfg.query_scaling_beta},
            "assumed": {"scoring_func": "sigmoid",
                        "softmax_scale": "yarn_mscale_all_dim_squared" if cfg.mscale_all_dim else "plain"}}


def test_forward_is_the_reference_and_each_control_is_not():
    model, ref = seeded(TINY), reference()
    params = {k: p.data for k, p in model.named_parameters()}
    toks = tokens(70)
    got = np.asarray(tt.jit(model)(jnp.asarray(toks[None])))[0]
    config = as_published(TINY)
    want = np.asarray(ref.forward(config, params, toks))
    assert np.abs(got - want).max() < 2e-5 and np.abs(want).max() > 0.5
    # a mechanism that must fail: experts a token halved (the reference's own control), the rope base
    wrong, what = ref.control(config)
    assert what == "num_experts_per_tok / 2" and np.abs(np.asarray(ref.forward(wrong, params, toks)) - want).max() > 0.05
    rope = dict(config["rope_parameters"], rope_theta=100 * TINY.rope_theta)
    assert np.abs(np.asarray(ref.forward(dict(config, rope_parameters=rope), params, toks)) - want).max() > 0.05
    # the queries' position scale leaves 1 inside these 70 positions (original context 32): it is in both
    still = dict(config["rope_parameters"], llama_4_scaling_beta=0.0)
    assert np.abs(np.asarray(ref.forward(dict(config, rope_parameters=still), params, toks)) - want).max() > 1e-3


def test_the_shares_add_up_to_the_whole_layer():
    """8 experts held 2 a share: the four shares' routed parts, with the shared expert counted
    once, are the uncut reference's whole expert layer; and a share's program is its reference."""
    ref = reference()
    whole = seeded(Config(n_layer=1, n_routed_experts=8, experts_held=(0, 8)), seed=3).h[0].experts
    x = jnp.asarray(np.random.RandomState(2).randn(1, 37, 64).astype(np.float32))
    uncut = as_published(Config(n_layer=1, n_routed_experts=8, experts_held=(0, 8)))
    params = {"experts." + k: p.data for k, p in whole.named_parameters()}
    xf = x[0]
    want = np.asarray(ref.routed_part(uncut, params, xf) + ref.shared_part(uncut, params, xf))
    np.testing.assert_allclose(np.asarray(tt.jit(whole)(x))[0], want, atol=2e-5)
    total = np.asarray(ref.shared_part(uncut, params, xf))
    for lo in range(0, 8, 2):
        share = moe.HeldExperts(64, 64, 8, (lo, lo + 2), 2, dtype=jnp.float32)
        share.gate.weight.data = whole.gate.weight.data
        share.e_score_correction_bias.data = whole.e_score_correction_bias.data
        for name in ("w_gate", "w_up", "w_down"):
            getattr(share, name).data = getattr(whole, name).data[lo:lo + 2]
        for name in ("shared_gate", "shared_up", "shared_down"):
            getattr(share, name).weight.data = getattr(whole, name).weight.data
        held = dict(uncut, experts_held=[lo, lo + 2], n_routed_experts=2)
        mine = {"experts." + k: p.data for k, p in share.named_parameters()}
        routed = np.asarray(ref.routed_part(held, mine, xf))
        np.testing.assert_allclose(np.asarray(tt.jit(share)(x))[0],
                                   routed + np.asarray(ref.shared_part(held, mine, xf)), atol=2e-5)
        total = total + routed
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert np.abs(want).max() > 0.1


def test_no_token_is_dropped_when_every_token_chooses_one_expert():
    layer = seeded(Config(n_layer=1, n_routed_experts=8, experts_held=(0, 8), n_expert_per_token=1),
                   seed=4).h[0].experts
    layer.e_score_correction_bias.data = jnp.zeros((8,)).at[5].set(10.0)   # every token chooses expert 5
    x = jnp.asarray(np.random.RandomState(5).randn(2, 40, 64).astype(np.float32))
    got = np.asarray(tt.jit(layer)(x))
    xf = np.asarray(x).reshape(-1, 64)
    g, u = xf @ np.asarray(layer.w_gate.data[5]), xf @ np.asarray(layer.w_up.data[5])
    want = ((g / (1 + np.exp(-g))) * u) @ np.asarray(layer.w_down.data[5])    # weight s / s = 1
    config = as_published(Config(n_layer=1, n_routed_experts=8, experts_held=(0, 8), n_expert_per_token=1))
    shared = np.asarray(reference().shared_part(config, {"experts." + k: p.data for k, p in layer.named_parameters()},
                                                jnp.asarray(xf)))
    np.testing.assert_allclose(got.reshape(-1, 64), want + shared, atol=2e-5)
    # a capacity of N / E rows an expert would have dropped 70 of these 80
    assert moe.ragged_tile(80, 8) == 32


# -- the kernels, interpreted ------------------------------------------------------------------------

def ragged_case(rs, sizes, tile, D=128, H=256):
    E = len(sizes)
    sizes = np.asarray(sizes, np.int32)
    padded = -(-sizes // tile) * tile
    starts = np.cumsum(padded) - padded
    R = int(padded.sum()) + 2 * tile
    rows, want = np.zeros((R, D), np.float32), np.zeros((R, D), np.float32)
    wg, wu = (0.1 * rs.randn(E, D, H).astype(np.float32) for _ in range(2))
    wd = 0.1 * rs.randn(E, H, D).astype(np.float32)
    for e in range(E):
        x = rs.randn(sizes[e], D).astype(np.float32)
        rows[starts[e]:starts[e] + sizes[e]] = x
        g, u = x @ wg[e], x @ wu[e]
        want[starts[e]:starts[e] + sizes[e]] = ((g / (1 + np.exp(-g))) * u) @ wd[e]
    return [jnp.asarray(a) for a in (rows, wg, wu, wd, sizes)], want


@pytest.mark.parametrize("sizes", [[5, 0, 20, 3], [0, 0, 0, 0], [16, 16, 0, 1], [0, 0, 0, 33]],
                         ids=["ragged", "all-empty", "exact-tiles", "last-only"])
def test_ragged_kernel_interpreted_matches_numpy(sizes):
    args, want = ragged_case(np.random.RandomState(0), sizes, 16)
    got = pallasex.ragged_mlp_fused(*args, 16, block_h=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(tt.jit(lambda *a: ltorch.ragged_mlp(*a, 16))(*args)), want, atol=1e-5)


def test_ragged_kernel_is_claimed_and_declines_by_vmem(pallas_claims, monkeypatch):
    args, want = ragged_case(np.random.RandomState(1), [5, 0, 20, 3], 16)
    fn = tt.jit(lambda *a: ltorch.ragged_mlp(*a, 16))
    np.testing.assert_allclose(np.asarray(fn(*args)), want, atol=1e-5)
    assert claims(fn, "thunder.ragged_mlp") == 1
    assert pallasex.ragged_mlp_supported(*args, 16)
    from thunder_tpu.analysis import budget

    # the estimate follows the tiles: the published widths fit at 512 hidden columns a tile,
    # where the capacity-bin kernel's whole panels, twice, do not fit its 16 MiB
    assert budget.ragged_mlp_block_h(16, 4096, 2048, 2, 2) == 512
    assert not budget.within_vmem(budget.grouped_mlp_vmem_bytes(128, 4096, 2048, 2, 2))
    assert budget.grouped_mlp_vmem_bytes(16, 4096, 2048, 2, 2, 512) < budget.RAGGED_MLP_VMEM_LIMIT
    observability.enable()
    try:
        observability.reset()
        monkeypatch.setattr(budget, "RAGGED_MLP_VMEM_LIMIT", 1024)
        assert not pallasex.ragged_mlp_supported(*args, 16)
        assert observability.counters().get("pallas.decline.ragged_mlp.vmem") == 1
    finally:
        observability.disable()


def latent_case(rs, lens, ps=8, W=128, vw=64, H=4, npm=6):
    B, P = len(lens), 1 + sum(-(-n // ps) for n in lens)
    pool = rs.randn(P, ps, W).astype(np.float32)
    table, free = np.zeros((B, npm), np.int32), list(range(1, P))
    for b, n in enumerate(lens):
        if n > 1:  # a slot with one row is an idle one: null-page table, position 0
            table[b, :-(-n // ps)] = [free.pop() for _ in range(-(-n // ps))]
    q = rs.randn(B, H, W).astype(np.float32)
    want = np.zeros((B, H, vw), np.float32)
    for b, n in enumerate(lens):
        rows = pool[table[b]].reshape(-1, W)[:n]
        s = q[b] @ rows.T * 0.11
        p = np.exp(s - s.max(-1, keepdims=True))
        want[b] = (p / p.sum(-1, keepdims=True)) @ rows[:, :vw]
    return q, pool, table, np.asarray(lens, np.int32), want


def test_latent_decode_kernel_interpreted_matches_numpy(pallas_claims):
    q, pool, table, lens, want = latent_case(np.random.RandomState(0), [1, 13, 40, 8, 48])
    got = pallasex.paged_latent_decode(jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table),
                                       jnp.asarray(lens), 0.11, 64, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)
    # through the op: the decomposition for several queries a sequence, the kernel for one
    fn = tt.jit(lambda q, pool, t, pos: ltorch.paged_latent_attention(q, pool, t, pos, 0.11, 64))
    one = np.asarray(fn(jnp.asarray(q[:, :, None]), jnp.asarray(pool), jnp.asarray(table),
                        jnp.asarray((lens - 1)[:, None])))
    np.testing.assert_allclose(one[:, :, 0], want, atol=2e-6)
    assert claims(fn, "thunder.paged_latent_attention") == 1
    q3 = np.repeat(q[:, :, None], 3, axis=2)
    pos = np.stack([lens - 1, np.maximum(lens - 2, 0), lens - 1], 1)
    several = tt.jit(lambda q, pool, t, pos: ltorch.paged_latent_attention(q, pool, t, pos, 0.11, 64))
    many = np.asarray(several(jnp.asarray(q3), jnp.asarray(pool), jnp.asarray(table), jnp.asarray(pos)))
    assert claims(several, "thunder.paged_latent_attention") == 0
    np.testing.assert_allclose(many[:, :, 0], want, atol=2e-6)
    np.testing.assert_allclose(many[:, :, 2], want, atol=2e-6)
    assert np.abs(many[2, :, 1] - want[2]).max() > 1e-3     # one row fewer is another answer


# -- the cache and the engine ---------------------------------------------------------------------------

def test_a_latent_layer_declares_one_pool_of_padded_rows():
    model = seeded(TINY)
    decl = model.h[0].cache
    assert decl == PagedLatent(48) and decl.row == 128 and decl.kind == "full" and decl.window is None
    assert PagedLatent(320).row == 384 and PagedLatent(384).row == 384
    cache = PagedKVCache(0, 9, 8, 0, 0, dtype=jnp.float32, layers=[decl, decl])
    assert [tuple(a.shape) for arrs in cache.state for a in arrs] == [(9, 8, 128), (9, 8, 128)]
    assert cache.k_pages == ()          # no key pool, no value pool, no head axis
    cache.rebind(tuple((a.at[3].set(1.0),) for (a,) in cache.state))
    cache.copy_page(3, 5)               # copy-on-write works on the latent pool as on a K/V pool
    assert all(float(a[5].min()) == 1.0 and float(a[4].max()) == 0.0 for (a,) in cache.state)


def served(model, requests, in_turn: bool = False, **engine):
    eng = ServingEngine(model, **dict(ENGINE, **engine))
    eng.start()
    try:
        if in_turn:
            return [eng.submit(p, max_new_tokens=n).result(timeout=300) for p, n in requests], eng
        futures = [eng.submit(p, max_new_tokens=n) for p, n in requests]
        return [f.result(timeout=300) for f in futures], eng
    finally:
        eng.stop()


def test_prefill_chunks_and_paged_decode_agree_with_the_forward_alone_and_batched():
    model = seeded(TINY)
    forward = tt.jit(model)
    toks = tokens(140)
    # a whole-prompt bucket, two chunks, five chunks; 20 new tokens cross page edges (pages of 8)
    requests = [(toks[:12], 20), (toks[:50], 20), (toks[:130], 20)]
    alone = [served(model, [r])[0][0] for r in requests]
    together, eng = served(model, requests)
    for (prompt, n), a, b in zip(requests, alone, together):
        assert np.array_equal(a.new_tokens, b.new_tokens) and len(a.pages) == -(-(len(prompt) + n) // 8)
        logits = np.asarray(forward(jnp.asarray(a.tokens[None])))[0, len(prompt) - 1:-1]
        assert (logits.max(-1) - logits[np.arange(n), a.new_tokens]).max() < 1e-4
    # the cache holds the latent and the roped key of each token and zeros beside them
    ref = reference()
    params = {k: p.data for k, p in model.named_parameters()}
    last = together[-1]
    x = ref.embed(as_published(TINY), params, last.tokens)
    x, made = ref.layer(as_published(TINY), ref.layer_params(params, 0), x)
    rows = np.asarray(eng.cache.state[0][0][np.asarray(last.pages)]).reshape(-1, 128)[:len(last.tokens) - 1]
    want = np.concatenate([made["c_kv"], made["k_rope"]], -1)[:len(rows)]
    assert np.abs(rows[:, :48] - want).max() < 1e-5 and np.abs(rows[:, 48:]).max() == 0.0


def test_prefix_sharing_and_a_draft_model_serve_a_latent_model():
    from thunder_tpu.models.litgpt import GPT, Config as GPTConfig

    model = seeded(TINY)
    toks = tokens(70, seed=1)
    requests = [(toks[:50], 8), (toks[:60], 8)]     # the second shares 48 tokens of prefix
    plain, _ = served(model, requests)
    shared, eng = served(model, requests, in_turn=True, prefix_sharing=True)
    assert eng.stats()["prefix_hits"] == 1 and eng.stats()["prefix_tokens_saved"] == 48
    draft = GPT(GPTConfig(name="draft", block_size=256, vocab_size=512, padded_vocab_size=512, n_layer=1,
                          n_head=4, n_embd=32, n_query_groups=4, rotary_percentage=1.0, parallel_residual=False,
                          bias=False, norm_class_name="RMSNorm", mlp_class_name="LLaMAMLP",
                          intermediate_size=64), dtype=jnp.float32)
    spec, eng = served(model, requests, draft_gpt=draft, spec_k=3)
    assert eng.stats()["spec_proposed"] > 0
    for a, b, c in zip(plain, shared, spec):
        assert np.array_equal(a.new_tokens, b.new_tokens) and np.array_equal(a.new_tokens, c.new_tokens)


def test_the_decode_step_counts_its_routing_with_the_bus_on():
    model = seeded(TINY)
    toks = tokens(60, seed=2)
    observability.enable()
    try:
        observability.reset()
        out, eng = served(model, [(toks[:20], 12), (toks[:45], 12)])
        c = observability.counters()
    finally:
        observability.disable()
    steps, layers = c["serve.decode_steps"], TINY.n_layer
    # every live token routes its 2 choices in both layers; (2, 6) of 8 are held: about half of them
    assert c["serve.moe.rows_routed"] == 2 * layers * c["serve.tokens"]
    assert 0 < c["serve.moe.rows_held"] < c["serve.moe.rows_routed"]
    assert c["serve.moe.rows_max"] <= c["serve.moe.rows_held"] <= 2 * c["serve.moe.rows_max"] * 4
    assert 0 < c["serve.moe.experts_touched"] <= 4 * layers * steps
    assert c["serve.state.latent_pages"] == c["serve.state.shared_kv_pages"] > 0
    assert c.get("serve.pool_copied", 0) == 0 and c["serve.pool_donated"] > 0
    # with the bus off the program has no such output and the engine records nothing
    out2, _ = served(model, [(toks[:20], 12), (toks[:45], 12)])
    assert all(np.array_equal(a.new_tokens, b.new_tokens) for a, b in zip(out, out2))
    assert observability.counters().get("serve.moe.rows_routed", 0) in (0, c["serve.moe.rows_routed"])


def _beside(model, mixes: bool):
    """A short request decodes while prompts of 50 and 130 are chunked beside it (chunks of 32).
    Returns (tokens, counters with the bus on, the engine)."""
    toks = tokens(140, seed=3)
    eng = ServingEngine(model, **ENGINE)
    assert eng._mixes and eng.runner.mixes
    eng._mixes = mixes   # False: a chunk program and a decode program a pass, as before
    observability.enable()
    try:
        observability.reset()
        first = eng.submit(toks[:12], max_new_tokens=24)
        for _ in range(3):
            eng._step_once()
        rest = [eng.submit(toks[:n], max_new_tokens=6) for n in (50, 130)]
        eng.drain()
        counters = observability.counters()
    finally:
        observability.disable()
        observability.reset()
    return [f.result(timeout=5).new_tokens for f in [first] + rest], counters, eng


def test_decode_rows_beside_a_chunk_share_its_ragged_call_and_give_the_same_tokens():
    """The latent block offers `mixed`: in a pass with a chunk due the decode rows go through
    the chunk's program, ONE ragged expert call a layer over both kinds of rows, the chunk's
    queries through the chunk's attention and the decode rows through theirs. Token for token
    what the two programs give; the routing counters count the decode rows only, as the decode
    program's do, so their relations hold over mixed and plain steps alike."""
    model = seeded(TINY)
    want, two, _ = _beside(model, False)
    got, one, eng = _beside(model, True)
    for a, b in zip(want, got):
        assert np.array_equal(a, b)
    assert "serve.decode_mixed" not in two and one["serve.decode_mixed"] >= 5
    layers = TINY.n_layer
    for c in (one, two):
        assert c["serve.tokens"] == (24 - 1) + 2 * (6 - 1)
        assert c["serve.moe.rows_routed"] == 2 * layers * c["serve.tokens"]
        assert 0 < c["serve.moe.rows_held"] < c["serve.moe.rows_routed"]
        assert c["serve.moe.rows_max"] <= c["serve.moe.rows_held"]
        assert 0 < c["serve.moe.experts_touched"] <= 4 * layers * c["serve.decode_steps"]
        assert c.get("serve.pool_copied", 0) == 0
    # the same rows on the same experts whichever program a step rode in: the new sequences join
    # the decode step a pass later where they were chunked beside a step, so the steps differ
    # by which rows they hold and the sums do not
    assert one["serve.moe.rows_held"] == two["serve.moe.rows_held"]
    symbols = [b.sym.name for b in tt.last_traces(eng.runner.chunk_cfn._cfn)[0].bound_symbols]
    assert symbols.count("ragged_mlp") == layers
    assert symbols.count("paged_latent_attention") == 2 * layers   # the chunk's queries; the decode rows


@pytest.mark.parametrize("mixes", [True, False], ids=["chunks-mixed", "chunks-two-programs"])
def test_arrivals_join_the_step_in_flight_and_a_first_token_may_end_its_request(mixes):
    """A request decodes and three arrive two passes apart: a whole-prompt bucket whose first
    token is its `eos_id`, two through chunks. Nothing lands at an activation: every step but the
    first is dispatched with the step before unfetched and its routing counters land with it,
    every activation but the first finds a step in flight, the ended request costs one step
    whose token is thrown away (and whose rows the program still routed), and each request has the tokens it has served
    alone."""
    model = seeded(TINY)
    toks = tokens(140, seed=4)
    requests = [(toks[:12], 40), (toks[5:25], 9), (toks[:50], 6), (toks[:130], 6)]
    alone = [served(model, [r])[0][0].new_tokens for r in requests]
    eng = ServingEngine(model, **dict(ENGINE, max_batch=3))
    eng._mixes = mixes
    observability.enable()
    try:
        observability.reset()
        futs = []
        for i, (p, n) in enumerate(requests):
            futs.append(eng.submit(p, max_new_tokens=n, eos_id=int(alone[1][0]) if i == 1 else None))
            for _ in range(2):
                eng._step_once()
                assert eng._inflight is not None
        eng.drain()
        c = observability.counters()
    finally:
        observability.disable()
        observability.reset()
    got = [f.result(timeout=5) for f in futs]
    assert got[1].finish_reason == "eos" and np.array_equal(got[1].new_tokens, alone[1][:1])
    for i in (0, 2, 3):
        assert np.array_equal(got[i].new_tokens, alone[i])
    assert c["serve.decode_overlapped"] == c["serve.decode_steps"] - 1 == eng.decode_steps - 1
    assert c["serve.activations"] == 4 and c["serve.activations_joined"] == 3
    assert c["serve.decode_discarded"] == 1
    assert c["serve.tokens"] == (40 - 1) + 2 * (6 - 1)
    # the program routed the discarded row too: it cannot know
    assert c["serve.moe.rows_routed"] == 2 * TINY.n_layer * (c["serve.tokens"] + 1)
    assert c.get("serve.pool_copied", 0) == 0 and eng.cache.allocator.n_used == 0


# -- both kernels through the v5e's compiler, at the published widths (no chip needed) --------------------

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


@pytest.mark.parametrize("tokens_in", [128, 512], ids=["decode-128-slots", "chunk-512"])
def test_the_ragged_kernel_compiles_for_the_v5e_at_the_published_widths(one_chip, tokens_in):
    bf, E, D, H = jnp.bfloat16, 32, 4096, 2048
    tile = moe.ragged_tile(tokens_in * 4, 128)
    R = -(-tokens_in * 4 // tile) * tile + E * tile

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with jax.enable_x64(False):   # as on the chip: conftest.py turns x64 on for the CPU tests
        compiled = jax.jit(lambda r, g, u, d, s: pallasex.ragged_mlp_fused(r, g, u, d, s, tile, interpret=False)).lower(
            sds((R, D), bf), sds((E, D, H), bf), sds((E, D, H), bf), sds((E, H, D), bf), sds((E,), jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert (tile, R) == ((16, 1024) if tokens_in == 128 else (32, 3072))


def test_the_latent_decode_kernel_compiles_for_the_v5e_at_the_cells_shapes(one_chip):
    bf = jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with jax.enable_x64(False):
        compiled = jax.jit(lambda q, p, t, n: pallasex.paged_latent_decode(q, p, t, n, 0.13, 256, interpret=False)).lower(
            sds((128, 32, 384), bf), sds((8193, 64, 384), bf), sds((128, 64), jnp.int32), sds((128,), jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    with pytest.raises(ValueError, match="128 lanes"):     # a row that fills no lane group is refused by name
        pallasex.paged_latent_decode(jnp.zeros((2, 4, 320), bf), jnp.zeros((9, 64, 320), bf),
                                     jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), jnp.int32), 0.1, 256,
                                     interpret=False)


def test_the_rope_flash_pair_compiles_for_the_v5e_at_a_quarter_of_a_head_of_64(one_chip, monkeypatch):
    """pythia-410m's attention (heads of 64, 16 columns rotated, T 2,048, bf16) through Mosaic: the
    rope-fused forward and single-pass backward at a rotary width narrower than the head. Kept in
    this file because only one test file of a run may describe the TPU (it holds libtpu's lock)."""
    bf, (B, H, T, D, n_elem) = jnp.bfloat16, (1, 2, 2048, 64, 16)
    monkeypatch.setattr(pallasex, "_on_tpu", lambda: True)   # lower through Mosaic, not interpreted

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, table, lse = sds((B, H, T, D), bf), sds((T, n_elem), jnp.float32), sds((B, H, T), jnp.float32)
    with jax.enable_x64(False):
        fwd = jax.jit(lambda q, k, v, c, s: pallasex.flash_rope_attention_forward(
            q, k, v, c, s, causal=True)).lower(q, q, q, table, table).compile()
        bwd = jax.jit(lambda q, k, v, o, l, c, s, do: pallasex.flash_rope_attention_backward(
            q, k, v, o, l, c, s, do, causal=True)).lower(q, q, q, q, lse, table, table, q).compile()
    for compiled in (fwd, bwd):
        assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1


# -- the same two kernels at their second shape: rows 640, values 512, 64 heads; d 6144, 16 held ------------
# (LongCat-Flash's cut, `longcat-flash-omni-ep32-l4.serve-trajectories`; kept in this file for the lock too)

@pytest.mark.parametrize("tokens_in", [256, 768], ids=["decode-256-slots", "chunk-512-beside-256"])
def test_the_ragged_kernel_compiles_for_the_v5e_at_a_model_width_of_6144(one_chip, tokens_in):
    from thunder_tpu.analysis import budget

    bf, E, D, H, k = jnp.bfloat16, 16, 6144, 2048, 12
    tile = moe.ragged_tile(tokens_in * k, 512 + 256)      # the rows spread over routed and identity outputs
    R = -(-tokens_in * k // tile) * tile + E * tile

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with jax.enable_x64(False):
        compiled = jax.jit(lambda r, g, u, d, s: pallasex.ragged_mlp_fused(r, g, u, d, s, tile, interpret=False)).lower(
            sds((R, D), bf), sds((E, D, H), bf), sds((E, D, H), bf), sds((E, H, D), bf), sds((E,), jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    assert (tile, R) == ((16, 3328) if tokens_in == 256 else (32, 9728))
    # the checker's `vmem` arm at its second shape: 512 hidden columns a weight tile, as at d 4096
    assert budget.ragged_mlp_block_h(tile, D, H, 2, 2) == 512 and budget.ragged_mlp_block_h(128, 4 * D, H, 2, 2) == 0


def test_the_latent_decode_kernel_compiles_for_the_v5e_at_rows_of_640_and_64_heads(one_chip):
    from thunder_tpu.analysis import budget

    bf = jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with jax.enable_x64(False):
        compiled = jax.jit(lambda q, p, t, n: pallasex.paged_latent_decode(q, p, t, n, 192 ** -0.5, 512, interpret=False)).lower(
            sds((256, 64, 640), bf), sds((4609, 64, 640), bf), sds((256, 36), jnp.int32), sds((256,), jnp.int32)).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1
    # `analysis/budget.latent_pages_per_step` at both shapes: 8 pages a step fit either; rows of 576 fill no lane group
    assert budget.latent_pages_per_step(64, 640, 512, 64, 2, 2) == 8 == budget.latent_pages_per_step(64, 384, 256, 32, 2, 2)
    assert PagedLatent(576).row == 640
    with pytest.raises(ValueError, match="128 lanes"):
        pallasex.paged_latent_decode(jnp.zeros((2, 64, 576), bf), jnp.zeros((9, 64, 576), bf),
                                     jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), jnp.int32), 0.1, 512,
                                     interpret=False)


@pytest.mark.parametrize("rows", [256, 768], ids=["decode-256-slots", "chunk-512-beside-256"])
def test_the_rms_norm_kernel_compiles_for_the_v5e_at_a_width_of_6144(one_chip, rows):
    """256 rows of 6,144 bfloat16 in two buffers each way and a float32 copy are 18.11M of Mosaic's
    16M (PR 38's first chip run fell there): the block follows the width."""
    from thunder_tpu.analysis import budget

    assert [budget.rms_norm_block_rows(d, 2) for d in (512, 1536, 4096, 6144)] == [256, 256, 256, 128]
    bf = jnp.bfloat16

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, bf, sharding=one_chip)

    with jax.enable_x64(False), pytest.MonkeyPatch.context() as mp:
        mp.setattr(pallasex, "_interpret", lambda: False)   # through Mosaic, not interpreted
        text = jax.jit(lambda x, w: pallasex.fused_rms_norm(x, w, 1e-5)).lower(
            sds((rows, 6144)), sds((6144,))).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
