"""The shortcut-connected double layer over latent attention (models/shortcut_moe.py) and what it
asked of `moe.HeldExperts` (a softmax router, identity experts, a fifth routing counter) and of
`latent_moe.LatentAttention` (the two rescaled low-rank streams), at small sizes on the CPU: the
whole forward against the plain reference, the paged engine against the reference's full forward,
the shares of an expert layer against the uncut layer, tokens that choose only identity experts
and none, the control that must fail, the two Pallas kernels interpreted at the published row,
value and model widths, and the trace `HeldExperts` had before, held as it was. (Both kernels
through the v5e's compiler at the cell's shapes: tests/test_latent_moe.py, which alone may
describe a TPU in a run.)"""
import hashlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import nn, observability
from thunder_tpu.executors import pallasex
from thunder_tpu.models import latent_moe, moe
from thunder_tpu.models.shortcut_moe import Config, ShortcutMoE
from thunder_tpu.serving import ServingEngine
from thunder_tpu.serving.kv_pages import PagedLatent
from thunder_tpu.serving.runner import ROUTING_COUNTERS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENGINE = dict(max_batch=4, page_size=8, max_seq=256, chunk_tokens=32, min_bucket=16, dtype=jnp.float32)
# 2 double layers; 8 routed experts of which the middle 4 are held, 4 identity experts, 3 a token;
# QK heads 16 + 16 wide against V heads 32... ranks 32 and 16: s_q = 1.41, s_kv = 2
TINY = Config(n_layer=2, n_routed_experts=8, experts_held=(2, 6), n_zero_experts=4, n_expert_per_token=3,
              routed_scaling_factor=6.0, kv_lora_rank=16, qk_nope_head_dim=32, rope_theta=1e4)


def reference():
    """benchmark/reference/shortcut_moe.py: plain jax.numpy, nothing of thunder_tpu."""
    spec = importlib.util.spec_from_file_location(
        "reference_shortcut_moe", os.path.join(ROOT, "benchmark", "reference", "shortcut_moe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def seeded(cfg: Config, seed: int = 0, std: float = 0.1):
    model = ShortcutMoE(cfg, dtype=jnp.float32)
    key = jax.random.key(seed)
    for i, (name, p) in enumerate(sorted(model.named_parameters())):
        if p.data.ndim >= 2:
            p.data = std * jax.random.normal(jax.random.fold_in(key, i), p.data.shape, jnp.float32)
        elif name.endswith("bias"):   # tips near-ties of a softmax over 12 outputs, no more
            p.data = 0.02 * jax.random.normal(jax.random.fold_in(key, i), p.data.shape, jnp.float32)
    return model


def tokens(n: int, vocab: int = 512, seed: int = 0):
    return np.random.RandomState(seed).randint(0, vocab, (n,)).astype(np.int32)


def as_published(cfg: Config) -> dict:
    lo, hi = cfg.experts_held
    return {"model_type": "longcat_flash", "attention_method": "MLA", "attention_bias": False, "num_layers": cfg.n_layer,
            "num_hidden_layers": 2 * cfg.n_layer, "hidden_size": cfg.n_embd, "ffn_hidden_size": cfg.intermediate_size,
            "expert_ffn_hidden_size": cfg.moe_intermediate_size, "num_attention_heads": cfg.n_head,
            "q_lora_rank": cfg.q_lora_rank, "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim, "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "mla_scale_q_lora": True, "mla_scale_kv_lora": True, "routed_scaling_factor": cfg.routed_scaling_factor,
            "n_routed_experts": hi - lo, "experts_held": [lo, hi], "reduced_from": {"n_routed_experts": cfg.n_routed_experts},
            "zero_expert_num": cfg.n_zero_experts, "zero_expert_type": "identity", "moe_topk": cfg.n_expert_per_token,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta, "vocab_size": cfg.vocab_size,
            "max_position_embeddings": cfg.block_size,
            "assumed": {"mla_scale": "sqrt_hidden_over_rank", "rope_interleave": True, "norm_topk_prob": False,
                        "router_columns": "routed_then_identity", "tie_word_embeddings": False,
                        "rope_table_rows": cfg.block_size}}


class Calls(nn.Module):
    """``fn(module, *args)`` as a module, so that `tt.jit` hands it the module's parameters."""

    def __init__(self, module, fn):
        super().__init__()
        self.module, self.fn = module, fn

    def forward(self, *args):
        return self.fn(self.module, *args)


def params_of(module, prefix: str = "") -> dict:
    return {prefix + k: p.data for k, p in module.named_parameters()}


# -- the model against the reference -------------------------------------------------------------------
def test_forward_is_the_reference_and_each_control_is_not():
    model, ref = seeded(TINY), reference()
    assert (TINY.q_lora_scale, TINY.kv_lora_scale) == (2 ** 0.5, 2.0) and len(model.h) == 4
    assert [hasattr(h, "experts") for h in model.h] == [True, False, True, False]
    params, toks, config = params_of(model), tokens(70), as_published(TINY)
    got = np.asarray(tt.jit(model)(jnp.asarray(toks[None])))[0]
    want = np.asarray(ref.forward(config, params, toks))
    assert np.abs(got - want).max() < 5e-5 and np.abs(want).max() > 0.5
    # mechanisms that must fail: the identity experts adding nothing (the reference's own control),
    # the rescaled latent, the shortcut taken as a plain sequential expert layer's place
    wrong, what = ref.control(config)
    assert what.startswith("zero_expert_type none")
    assert np.abs(np.asarray(ref.forward(wrong, params, toks)) - want).max() > 0.05
    for broken in (dict(config, mla_scale_kv_lora=False), dict(config, rope_theta=100 * TINY.rope_theta),
                   dict(config, routed_scaling_factor=1.0)):
        assert np.abs(np.asarray(ref.forward(broken, params, toks)) - want).max() > 0.05


def test_the_experts_read_the_first_half_and_are_added_at_the_second_halfs_end():
    """The shortcut itself, on the model's own modules: y = a1 + FFN_1(u1) + Experts(u0)."""
    model = seeded(Config(n_layer=1, n_routed_experts=8, experts_held=(0, 8)), seed=6)
    x = jnp.asarray(np.random.RandomState(1).randn(1, 21, 64).astype(np.float32))
    pos = jnp.broadcast_to(jnp.arange(21, dtype=jnp.int32)[None], (1, 21))

    def by_hand(model, x, pos):
        first, second = model.h
        where = model.where(pos)

        def attend(half, x):
            u = half.norm_1(x)
            return x + half.attn.expanded(*half.attn.queries(u, where), *half.attn.latent(u, where))

        a0 = attend(first, x)
        u0 = first.norm_2(a0)
        m = first.experts(u0)
        a1 = attend(second, a0 + first.mlp(u0))
        return a1 + second.mlp(second.norm_2(a1)) + m

    got = tt.jit(Calls(model, lambda m, x, pos: m.through(x, m.where(pos))))(x, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(tt.jit(Calls(model, by_hand))(x, pos)), atol=1e-5)
    assert float(jnp.abs(got - x).max()) > 0.1


# -- the expert layer: shares, identity experts -----------------------------------------------------------
UNCUT = Config(n_layer=1, n_routed_experts=8, experts_held=(0, 8), n_expert_per_token=3, routed_scaling_factor=6.0)


def held_layer(held, *, n_zero=4, k=3, seed=3):
    layer = moe.HeldExperts(64, 64, 8, held, k, n_shared=0, norm_topk_prob=False, routed_scaling_factor=6.0,
                            score="softmax", n_zero=n_zero, dtype=jnp.float32)
    key = jax.random.key(seed)
    for i, (name, p) in enumerate(sorted(layer.named_parameters())):
        std = 0.02 if name.endswith("bias") else 0.1
        p.data = std * jax.random.normal(jax.random.fold_in(key, i), p.data.shape, jnp.float32)
    return layer


def test_the_shares_and_the_identity_part_counted_once_add_up_to_the_whole_layer():
    """8 routed experts held 2 a share beside 4 identity experts: the four shares' held parts, with
    the identity part counted once, are the uncut reference's expert layer; and a share's program
    is its reference's held part plus the identity part, which every chip computes for its tokens."""
    ref = reference()
    whole = held_layer((0, 8))
    x = jnp.asarray(np.random.RandomState(2).randn(1, 37, 64).astype(np.float32))
    uncut = as_published(UNCUT)
    params, xf = params_of(whole, "experts."), x[0]
    identity = np.asarray(ref.identity_part(uncut, params, xf))
    want = np.asarray(ref.routed_part(uncut, params, xf)) + identity
    np.testing.assert_allclose(np.asarray(tt.jit(whole)(x))[0], want, atol=2e-5)
    chosen = np.asarray(ref.route(uncut, params, xf)[0])
    assert 0.2 < (chosen >= 8).mean() < 0.5 and np.abs(identity).max() > 0.1      # both kinds are chosen
    total = identity
    for lo in range(0, 8, 2):
        share = moe.HeldExperts(64, 64, 8, (lo, lo + 2), 3, n_shared=0, norm_topk_prob=False,
                                routed_scaling_factor=6.0, score="softmax", n_zero=4, dtype=jnp.float32)
        share.gate.weight.data = whole.gate.weight.data
        share.e_score_correction_bias.data = whole.e_score_correction_bias.data
        for name in ("w_gate", "w_up", "w_down"):
            getattr(share, name).data = getattr(whole, name).data[lo:lo + 2]
        held = dict(uncut, experts_held=[lo, lo + 2], n_routed_experts=2)
        routed = np.asarray(ref.routed_part(held, params_of(share, "experts."), xf))
        np.testing.assert_allclose(np.asarray(tt.jit(share)(x))[0], routed + identity, atol=2e-5)
        total = total + routed
    np.testing.assert_allclose(total, want, atol=5e-5)


def with_tally(layer, x):
    """The layer's output and what it counted of the call (`ROUTING_COUNTERS`)."""
    counted = []
    return layer(x, None, counted), counted[-1]


@pytest.mark.parametrize("identity_bias,zero_rows", [(10.0, 3), (-10.0, 0)], ids=["only-identity", "no-identity"])
def test_a_token_whose_choices_are_all_identity_experts_and_one_with_none(identity_bias, zero_rows):
    """With the selection bias far up on the identity outputs every choice of every token is one:
    the layer gives (sum of their weights) x u, no row enters a ragged group and the counters say
    so; far down, no token chooses one and the layer is its held experts alone."""
    layer = held_layer((0, 8))
    layer.e_score_correction_bias.data = jnp.zeros((12,)).at[8:].set(identity_bias)
    x = jnp.asarray(np.random.RandomState(5).randn(1, 19, 64).astype(np.float32))
    out, tally = tt.jit(Calls(layer, with_tally))(x)
    xf = np.asarray(x)[0]
    p = np.asarray(jax.nn.softmax(xf @ np.asarray(layer.gate.weight.data).T, axis=-1))
    if zero_rows:
        want = 6.0 * np.sort(p[:, 8:], axis=-1)[:, -3:].sum(-1, keepdims=True) * xf
        np.testing.assert_allclose(np.asarray(out)[0], want, atol=1e-5)
    else:
        config = as_published(UNCUT)
        ref, params = reference(), params_of(layer, "experts.")
        assert np.abs(np.asarray(ref.identity_part(config, params, jnp.asarray(xf)))).max() == 0.0
        np.testing.assert_allclose(np.asarray(out)[0], np.asarray(ref.routed_part(config, params, jnp.asarray(xf))),
                                   atol=2e-5)
    routed, held, touched, most, zero = (int(v) for v in np.asarray(tally))
    assert (routed, zero) == (19 * 3, 19 * zero_rows) and held == routed - zero
    assert (touched == 0 and most == 0) if zero_rows else (touched > 0 and most >= held / 8)


def test_a_sigmoid_router_without_identity_experts_traces_what_it_traced_before():
    """`HeldExperts` as the latent mixture-of-experts model builds it: its trace, letter for letter,
    is the parent commit's (its sha256 taken there, at 7758df5), and it has four counters."""
    layer = moe.HeldExperts(64, 64, 8, (2, 6), 2, dtype=jnp.float32)
    x = jnp.asarray(np.random.RandomState(0).randn(1, 37, 64).astype(np.float32))
    fn = tt.jit(layer)
    fn(x)
    trace = tt.last_traces(fn)[0]
    names = [b.sym.name for b in trace.bound_symbols]
    assert len(names) == 63 and "softmax" not in names and names.count("ragged_mlp") == 1
    assert hashlib.sha256(str(trace).encode()).hexdigest()[:16] == "961f032a1c080c68"
    _, tally = tt.jit(Calls(layer, with_tally))(x)
    assert tuple(tally.shape) == (4,) and len(ROUTING_COUNTERS) == 5
    with pytest.raises(ValueError, match="sigmoid"):
        moe.HeldExperts(64, 64, 8, (2, 6), 2, score="tanh")
    # and the whole latent model's forward, whose attention gained two factors that are 1 there
    whole = tt.jit(latent_moe.LatentMoE(latent_moe.Config(n_layer=1), dtype=jnp.float32))
    whole(jnp.zeros((1, 16), jnp.int32))
    assert hashlib.sha256(str(tt.last_traces(whole)[0]).encode()).hexdigest()[:16] == "c299912e8f9687aa"


# -- served ------------------------------------------------------------------------------------------------
def served(model, requests, **engine):
    eng = ServingEngine(model, **dict(ENGINE, **engine))
    eng.start()
    try:
        futures = [eng.submit(p, max_new_tokens=n) for p, n in requests]
        return [f.result(timeout=300) for f in futures], eng
    finally:
        eng.stop()


def test_prefill_chunks_and_decode_through_the_engine_agree_with_the_references_full_forward():
    model, ref = seeded(TINY), reference()
    config, params = as_published(TINY), params_of(model)
    toks = tokens(140)
    # a whole-prompt bucket, two chunks, five chunks; 20 new tokens cross page edges (pages of 8)
    requests = [(toks[:12], 20), (toks[:50], 20), (toks[:130], 20)]
    alone = [served(model, [r])[0][0] for r in requests]
    together, eng = served(model, requests)
    assert [type(d) for d in eng.cache.layers] == [PagedLatent] * 4 and eng.cache.layers[0].row == 128
    assert eng.runner.mixes and len(eng.cache.state) == 4
    for (prompt, n), a, b in zip(requests, alone, together):
        assert np.array_equal(a.new_tokens, b.new_tokens) and len(a.pages) == -(-(len(prompt) + n) // 8)
        logits = np.asarray(ref.forward(config, params, a.tokens))[len(prompt) - 1:-1]
        assert (logits.max(-1) - logits[np.arange(n), a.new_tokens]).max() < 1e-4
    # every half's pool holds the scaled latent and the roped key of each token, zeros beside them
    last = together[-1]
    x = ref.embed(config, params, last.tokens)
    for i in range(4):
        x, made = ref.layer(config, ref.layer_params(params, i), x)
        rows = np.asarray(eng.cache.state[i][0][np.asarray(last.pages)]).reshape(-1, 128)[:len(last.tokens) - 1]
        want = np.concatenate([made["c_kv"], made["k_rope"]], -1)[:len(rows)]
        assert np.abs(rows[:, :32] - want).max() < 2e-5 and np.abs(rows[:, 32:]).max() == 0.0
    # the served tokens are no longer the best ones once the identity experts add nothing
    wrong = np.asarray(ref.forward(ref.control(config)[0], params, last.tokens))[129:-1]
    assert (wrong.max(-1) - wrong[np.arange(20), last.new_tokens]).max() > 0.01


def test_the_decode_step_counts_the_rows_that_chose_an_identity_expert():
    model = seeded(TINY)
    toks = tokens(60, seed=2)
    observability.enable()
    try:
        observability.reset()
        served(model, [(toks[:20], 12), (toks[:45], 12)])
        c = observability.counters()
    finally:
        observability.disable()
        observability.reset()
    # every live token routes its 3 choices in both double layers (the second halves route nothing)
    assert c["serve.moe.rows_routed"] == 3 * TINY.n_layer * c["serve.tokens"]
    assert 0 < c["serve.moe.rows_zero"] < c["serve.moe.rows_routed"]
    assert 0 < c["serve.moe.rows_held"] <= c["serve.moe.rows_routed"] - c["serve.moe.rows_zero"]
    assert c["serve.moe.rows_max"] <= c["serve.moe.rows_held"]
    assert 0 < c["serve.moe.experts_touched"] <= 4 * TINY.n_layer * c["serve.decode_steps"]
    assert c["serve.state.latent_pages"] > 0 and c.get("serve.pool_copied", 0) == 0


def test_decode_rows_beside_a_chunk_go_through_one_program_and_give_the_same_tokens():
    """Every half offers `mixed`, so a pass with a chunk due runs ONE program: one ragged expert
    call a double layer over both kinds of rows, the shortcut carried inside it."""
    model = seeded(TINY)
    toks = tokens(140, seed=3)

    def beside(mixes: bool):
        eng = ServingEngine(model, **ENGINE)
        assert eng._mixes
        eng._mixes = mixes
        first = eng.submit(toks[:12], max_new_tokens=24)
        for _ in range(3):
            eng._step_once()
        rest = [eng.submit(toks[:n], max_new_tokens=6) for n in (50, 130)]
        eng.drain()
        return [f.result(timeout=5).new_tokens for f in [first] + rest], eng

    want, _ = beside(False)
    got, eng = beside(True)
    assert all(np.array_equal(a, b) for a, b in zip(want, got))
    symbols = [b.sym.name for b in tt.last_traces(eng.runner.chunk_cfn._cfn)[0].bound_symbols]
    assert symbols.count("ragged_mlp") == TINY.n_layer
    assert symbols.count("paged_latent_attention") == 2 * 2 * TINY.n_layer   # a chunk's queries and the decode rows', a half


# -- the kernels, interpreted, at the cell's row, value and model widths ------------------------------------
def test_the_latent_decode_kernel_interpreted_at_rows_of_640_values_of_512_and_64_heads(pallas_claims):
    rs = np.random.RandomState(0)
    ps, W, vw, H, npm, lens = 8, 640, 512, 64, 4, [1, 13, 32, 9]
    B = len(lens)
    pool = rs.randn(1 + B * npm, ps, W).astype(np.float32) * 0.3
    pool[..., 576:] = 0.0
    table = np.zeros((B, npm), np.int32)
    for b, n in enumerate(lens):
        used = -(-n // ps)
        table[b, :used] = 1 + b * npm + np.arange(used)
    q = rs.randn(B, H, 1, W).astype(np.float32) * 0.3
    pos = (np.asarray(lens, np.int32) - 1)[:, None]
    from thunder_tpu.ops import ltorch

    got = np.asarray(tt.jit(lambda q, p, t, n: ltorch.paged_latent_attention(q, p, t, n, 192 ** -0.5, vw))(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table), jnp.asarray(pos)))
    for b, n in enumerate(lens):
        rows = pool[table[b]].reshape(-1, W)[:n]
        s = (q[b, :, 0] @ rows.T) * 192 ** -0.5
        pr = np.exp(s - s.max(-1, keepdims=True))
        want = (pr / pr.sum(-1, keepdims=True)) @ rows[:, :vw]
        np.testing.assert_allclose(got[b, :, 0], want, atol=2e-5)
    assert got.shape == (B, H, 1, vw)


def test_the_ragged_kernel_interpreted_at_a_model_width_of_6144():
    rs = np.random.RandomState(1)
    E, D, H, tile, sizes = 4, 6144, 128, 16, [4, 0, 17, 1]
    starts = np.cumsum([0] + [-(-s // tile) * tile for s in sizes])
    R = int(starts[-1]) + tile
    rows = np.zeros((R, D), np.float32)
    for e, s in enumerate(sizes):
        rows[starts[e]:starts[e] + s] = rs.randn(s, D) * 0.1
    wg, wu = (rs.randn(E, D, H).astype(np.float32) * 0.02 for _ in range(2))
    wd = rs.randn(E, H, D).astype(np.float32) * 0.02
    got = np.asarray(pallasex.ragged_mlp_fused(jnp.asarray(rows), jnp.asarray(wg), jnp.asarray(wu), jnp.asarray(wd),
                                               jnp.asarray(sizes, jnp.int32), tile, interpret=True))
    want = np.zeros_like(rows)
    for e, s in enumerate(sizes):
        x = rows[starts[e]:starts[e] + s]
        g = x @ wg[e]
        want[starts[e]:starts[e] + s] = ((g / (1 + np.exp(-g))) * (x @ wu[e])) @ wd[e]
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert moe.ragged_tile(256 * 12, 768) == 16 and moe.ragged_tile(768 * 12, 768) == 32
