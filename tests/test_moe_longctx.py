"""Sparse/long-context frontier tests (ISSUE 20): grouped-expert dispatch
bit-identity across ragged loads, the Pallas grouped kernel's interpret-mode
A/B and grad rule, streaming ring-flash vs dense attention, GQA-native ring
identity, EP×DP mesh wiring, moe.* telemetry, and 32k paged serving.

The grouped kernel and the streaming ring kernel both DECLINE via the
unified analysis/memory.py VMEM budget — the decline tests pin that the
pure-jax reference road produces the same numbers when the kernel bows out.
"""
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import nn, observability, optim
from thunder_tpu.analysis import budget
from thunder_tpu.models.moe import MoEConfig, MoEMLP, publish_moe_stats
from thunder_tpu.ops import ltorch
from thunder_tpu.parallel import make_mesh
from thunder_tpu.training import TrainStep, _shard_map_compat

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


# two orders of one float32 sum (test_grouped_vs_dense_bit_identity says why)
_SUM_ORDER = dict(rtol=0, atol=float(np.finfo(np.float32).eps))


def _moe_pair(cfg, rng, N=64):
    """One MoEMLP evaluated on BOTH dispatch roads (same instance, flipped
    cfg.dispatch — separate instantiations would seed different routers)."""
    x = jnp.asarray(rng.randn(1, N, cfg.n_embd), jnp.float32)
    m = MoEMLP(cfg)
    # fresh tt.jit per road: the dispatch flag is read at TRACE time, so a
    # shared wrapper would serve the first road's cached program
    cfg.dispatch = "grouped"
    out_g = np.asarray(tt.jit(m)(x))
    cfg.dispatch = "dense"
    out_d = np.asarray(tt.jit(m)(x))
    return m, x, out_g, out_d


@pytest.mark.moe
@pytest.mark.parametrize("scenario", ["drop_free", "over_capacity", "odd_E"])
def test_grouped_vs_dense_bit_identity(scenario, rng):
    """The grouped (packed-bins) road and the one-hot einsum road share the
    router and the capacity/drop decision, so their outputs are equal —
    including dropped tokens (zero weight vs never-binned) and ragged
    per-expert loads — up to the order of a float32 sum: the einsum adds a
    token's experts (and its zero-weighted non-choices) in one order, the
    bins' combine in another, and the sums differ in their last places. On
    this tree (outputs up to 0.25 in size): drop_free 262 of 2,048 elements
    by at most 1.5e-8 (one unit in the last place), over_capacity 1,019 by at
    most 6.7e-8, odd_E 1,742 by at most 5.2e-8, the empty-expert case below
    227 by at most 6.0e-8. Relative to an output near zero that is up to
    1.8e-2, so `_SUM_ORDER` is absolute: float32's epsilon."""
    cfg = {
        "drop_free": MoEConfig(n_embd=32, intermediate_size=48, n_expert=8,
                               n_expert_per_token=2, capacity_factor=None),
        "over_capacity": MoEConfig(n_embd=32, intermediate_size=48, n_expert=8,
                                   n_expert_per_token=2, capacity_factor=0.5),
        "odd_E": MoEConfig(n_embd=32, intermediate_size=48, n_expert=6,
                           n_expert_per_token=2, capacity_factor=1.0),
    }[scenario]
    _, _, out_g, out_d = _moe_pair(cfg, rng)
    np.testing.assert_allclose(out_g, out_d, **_SUM_ORDER)


@pytest.mark.moe
def test_grouped_vs_dense_empty_expert_and_drops(rng):
    """A zero router weight gives uniform logits; top-1 tie-breaks to expert
    0 for EVERY token, so experts 1..E-1 are EMPTY bins and cf=0.25 drops
    most of expert 0's FIFO queue — the raggedest load the dispatch sees."""
    cfg = MoEConfig(n_embd=32, intermediate_size=48, n_expert=4,
                    n_expert_per_token=1, capacity_factor=0.25)
    m = MoEMLP(cfg)
    sd = {k: np.asarray(v).copy() for k, v in m.state_dict().items()}
    sd["gate.weight"] = np.zeros_like(sd["gate.weight"])
    m.load_state_dict(sd)
    x = jnp.asarray(rng.randn(1, 64, cfg.n_embd), jnp.float32)
    cfg.dispatch = "grouped"
    out_g = np.asarray(tt.jit(m)(x))
    cfg.dispatch = "dense"
    out_d = np.asarray(tt.jit(m)(x))
    np.testing.assert_allclose(out_g, out_d, **_SUM_ORDER)
    # capacity(64) = ceil(0.25*64*1/4)=4 -> rounded to the 8-row sublane
    # tile; 64 assignments to expert 0 minus cap kept = 56 dropped, and the
    # dropped tokens contribute EXACT zeros (their row is all-zero output
    # only if every expert choice was dropped)
    assert m.capacity(64) == 8
    n_zero_rows = int(np.sum(np.all(out_g[0] == 0.0, axis=-1)))
    assert n_zero_rows == 56


def _grouped_args(rng, E=4, cap=16, D=32, H=48, fill=None):
    bins = rng.randn(E, cap, D).astype(np.float32)
    if fill is not None:
        for e, n in enumerate(fill):
            bins[e, n:] = 0.0  # rows past group_sizes[e] must be zero-filled
    s = 1.0 / math.sqrt(D)
    wg = (rng.rand(E, D, H).astype(np.float32) - 0.5) * 2 * s
    wu = (rng.rand(E, D, H).astype(np.float32) - 0.5) * 2 * s
    wd = (rng.rand(E, H, D).astype(np.float32) - 0.5) * s
    gs = np.asarray(fill if fill is not None else [cap] * E, np.int32)
    return (jnp.asarray(bins), jnp.asarray(wg), jnp.asarray(wu),
            jnp.asarray(wd), jnp.asarray(gs))


@pytest.mark.moe
def test_grouped_kernel_interpret_matches_decomposition(rng, request):
    """The `pallas_claims` fixture turns the Pallas kernel's claim on (interpret
    mode off-TPU); its output matches the pure-jax decomposition bit-closely,
    including ragged group_sizes (an empty expert and a partial bin)."""
    args = _grouped_args(rng, fill=[16, 0, 7, 16])
    fn = lambda *a: ltorch.sum(ltorch.grouped_mlp(*a))

    ref = float(tt.jit(fn)(*args))  # off the chip: the decomposition
    request.getfixturevalue("pallas_claims")
    got = float(tt.jit(fn)(*args))
    assert abs(got - ref) <= 1e-4 * max(1.0, abs(ref))


@pytest.mark.moe
def test_grouped_kernel_grad_rule_matches(rng, request):
    """The executor-claimed grad rule (pallas.grouped_mlp_fwd/bwd prims)
    produces the same gradients as differentiating the decomposition."""
    args = _grouped_args(rng, fill=[16, 0, 7, 16])
    loss = lambda b, wg, wu, wd, gs: ltorch.sum(
        ltorch.grouped_mlp(b, wg, wu, wd, gs) ** 2)

    grads = {}
    for claim in ("0", "1"):  # the decomposition, then the claimed kernel
        if claim == "1":
            request.getfixturevalue("pallas_claims")
        (g, _) = tt.grad(tt.jit(loss), argnums=(0, 1, 2, 3))(*args)
        # one entry per positional arg; the int group_sizes grad is None
        grads[claim] = [np.asarray(t) for t in g if t is not None]
        assert len(grads[claim]) == 4
    for a, b in zip(grads["0"], grads["1"]):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)


@pytest.mark.moe
@pytest.mark.analysis
def test_grouped_kernel_vmem_decline(rng, monkeypatch, request):
    """A tiny VMEM budget makes the checker DECLINE (even when it claims) —
    the decomposition fallback runs and the program still produces the
    reference numbers. The budget comes from analysis/memory.py, the same
    estimate the bench artifact commits."""
    from thunder_tpu.executors import pallasex

    args = _grouped_args(rng)
    fn = lambda *a: ltorch.sum(ltorch.grouped_mlp(*a))
    ref = float(tt.jit(fn)(*args))  # off the chip: the decomposition
    request.getfixturevalue("pallas_claims")
    assert pallasex.grouped_mlp_supported(*args)
    monkeypatch.setattr(budget, "vmem_limit", lambda: 4096)
    assert not pallasex.grouped_mlp_supported(*args)
    declined = float(tt.jit(fn)(*args))
    assert abs(declined - ref) <= 1e-5 * max(1.0, abs(ref))


def _dense_gqa_sdpa(q, k, v, causal=True):
    """Dense GQA reference: repeat KV heads, full-materialised softmax."""
    g = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    T, D = q.shape[2], q.shape[3]
    s = (q.astype(jnp.float32) @ jnp.swapaxes(k.astype(jnp.float32), -2, -1)
         / math.sqrt(D))
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    return (jax.nn.softmax(s, -1) @ v.astype(jnp.float32)).astype(q.dtype)


def _ring_harness(sp, spec_out=None):
    from jax.sharding import PartitionSpec as P

    from thunder_tpu.parallel.context_parallel import _ring_attention_impl

    mesh = make_mesh({"sp": sp})
    spec = P(None, None, "sp")

    def run(q, k, v, causal=True):
        fn = _shard_map_compat(
            lambda q, k, v: _ring_attention_impl(
                q, k, v, axis="sp", causal=causal, world_size=sp),
            mesh, (spec, spec, spec), spec)
        return fn(q, k, v)

    return mesh, spec, run


@pytest.mark.longctx
@pytest.mark.parametrize("T,causal", [(32, True), (64, True), (64, False)])
def test_gqa_ring_matches_dense(T, causal, rng):
    """The GQA-native ring (no KV replication on the ring) matches the
    dense GQA reference at mixed T, causal and full."""
    B, Hq, Hkv, D, sp = 2, 4, 2, 16, 4
    q = jnp.asarray(rng.randn(B, Hq, T, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, Hkv, T, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, Hkv, T, D), jnp.float32)
    _, _, run = _ring_harness(sp)
    np.testing.assert_allclose(np.asarray(run(q, k, v, causal)),
                               np.asarray(_dense_gqa_sdpa(q, k, v, causal)),
                               atol=2e-5)


@pytest.mark.longctx
@pytest.mark.slow  # interpret-mode shard_map grads; runs in the -m longctx lane
@pytest.mark.parametrize("T", [32, 64])
def test_streaming_ring_flash_matches_dense(T, rng, pallas_claims):
    """The `pallas_claims` fixture puts the streaming flash kernel into the ring
    (interpret mode off-TPU); forward AND backward match the dense GQA
    reference — the bwd runs the flash recompute, not a saved-probs path."""
    from jax.sharding import PartitionSpec as P

    from thunder_tpu.parallel.context_parallel import _ring_attention_impl

    B, Hq, Hkv, D, sp = 1, 4, 2, 16, 4
    q = jnp.asarray(rng.randn(B, Hq, T, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, Hkv, T, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, Hkv, T, D), jnp.float32)
    mesh = make_mesh({"sp": sp})
    spec = P(None, None, "sp")

    out = _shard_map_compat(
        lambda q, k, v: _ring_attention_impl(q, k, v, axis="sp", causal=True,
                                             world_size=sp),
        mesh, (spec, spec, spec), spec)(q, k, v)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense_gqa_sdpa(q, k, v)),
                               atol=2e-5)

    def ring_loss(q, k, v):
        def body(q, k, v):
            o = _ring_attention_impl(q, k, v, axis="sp", causal=True,
                                     world_size=sp)
            return jax.lax.psum(jnp.sum(o.astype(jnp.float32) ** 2), "sp")
        return _shard_map_compat(body, mesh, (spec, spec, spec), P())(q, k, v)

    def dense_loss(q, k, v):
        o = _dense_gqa_sdpa(q, k, v)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    gr = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.longctx
@pytest.mark.analysis
def test_ring_flash_vmem_decline(rng, monkeypatch, pallas_claims):
    """The streaming kernel's checker declines when one step's working set
    exceeds the VMEM budget — the ring still runs (pure-jax GQA road) and
    matches dense."""
    from thunder_tpu.executors import pallasex

    B, Hq, Hkv, D, sp, T = 1, 4, 2, 16, 4, 64
    q = jnp.asarray(rng.randn(B, Hq, T // sp, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, Hkv, T // sp, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, Hkv, T // sp, D), jnp.float32)
    assert pallasex.ring_flash_supported(q, k, v)
    monkeypatch.setattr(budget, "vmem_limit", lambda: 1024)
    assert not pallasex.ring_flash_supported(q, k, v)

    qf = jnp.asarray(rng.randn(B, Hq, T, D), jnp.float32)
    kf = jnp.asarray(rng.randn(B, Hkv, T, D), jnp.float32)
    vf = jnp.asarray(rng.randn(B, Hkv, T, D), jnp.float32)
    _, _, run = _ring_harness(sp)
    np.testing.assert_allclose(np.asarray(run(qf, kf, vf, True)),
                               np.asarray(_dense_gqa_sdpa(qf, kf, vf)),
                               atol=2e-5)


@pytest.mark.moe
@pytest.mark.dist
@pytest.mark.slow  # dist tests carry slow so tier-1 stays fast (conftest rule)
def test_moe_ep_dp_dryrun(rng):
    """EP×DP on ONE mesh: batch-sharding tokens over dp while experts live
    on ep produces the same numbers as single-axis EP (both drop-free), and
    the psum'd routing stats are fleet totals (load sums to 1)."""
    from thunder_tpu.parallel.expert_parallel import moe_ep_forward

    E, D, H, N, K = 8, 16, 24, 64, 2
    s = 1.0 / math.sqrt(D)
    params = {
        "gate_w": jnp.asarray(rng.randn(D, E).astype(np.float32) * s),
        "w_gate": jnp.asarray((rng.rand(E, D, H).astype(np.float32) - 0.5) * 2 * s),
        "w_up": jnp.asarray((rng.rand(E, D, H).astype(np.float32) - 0.5) * 2 * s),
        "w_down": jnp.asarray((rng.rand(E, H, D).astype(np.float32) - 0.5) * s),
    }
    x = jnp.asarray(rng.randn(N, D), jnp.float32)
    out_ep = moe_ep_forward(params, x, mesh=make_mesh({"ep": 4}), axis="ep",
                            n_expert_per_token=K)
    out_epdp, stats = moe_ep_forward(
        params, x, mesh=make_mesh({"dp": 2, "ep": 4}), axis="ep",
        dp_axis="dp", n_expert_per_token=K, return_stats=True)
    np.testing.assert_allclose(np.asarray(out_ep), np.asarray(out_epdp),
                               atol=1e-6)
    load = np.asarray(stats["expert_load"])
    assert load.shape == (E,)
    np.testing.assert_allclose(load.sum(), 1.0, atol=1e-6)
    assert float(stats["dropped_tokens"]) == 0.0
    assert float(stats["router_entropy"]) > 0.0


@pytest.mark.moe
@pytest.mark.telemetry
def test_moe_telemetry_zero_work_when_disabled(rng):
    """Disabled observability is a trace-time gate: the compiled MoE step
    contains no stat ops (buffers stay zero), record_moe is a no-op, and
    publish_moe_stats publishes nothing. Enabled, the buffers refresh and
    the moe.* counters/gauges appear."""
    from thunder_tpu.observability import metrics

    cfg = MoEConfig(n_embd=32, intermediate_size=48, n_expert=4,
                    n_expert_per_token=2, capacity_factor=1.0)
    x = jnp.asarray(rng.randn(2, 16, cfg.n_embd), jnp.float32)

    observability.disable()
    observability.reset()
    m = MoEMLP(cfg)
    tt.jit(m)(x)
    assert not any(np.any(np.asarray(v)) for _, v in m.named_buffers())
    metrics.record_moe([0.5, 0.5], 3, 1.0)  # no-op while disabled
    assert publish_moe_stats(m) == 0
    assert not any(k.startswith("moe.") for k in observability.counters())

    observability.enable()
    try:
        observability.reset()
        m2 = MoEMLP(cfg)
        tt.jit(m2)(x)
        load = np.asarray(dict(m2.named_buffers())["moe_expert_load"])
        np.testing.assert_allclose(load.sum(), 1.0, atol=1e-6)
        assert publish_moe_stats(m2) == 1
        counters = observability.counters()
        assert counters.get("moe.steps") == 1
        gauges = observability.gauges()
        assert "moe.router_entropy" in gauges
        assert any(k.startswith("moe.expert_load.e") for k in gauges)
    finally:
        observability.disable()


@pytest.mark.moe
def test_moe_train_step_both_roads(rng):
    """TrainStep drives the full fwd+bwd+optimizer program on both dispatch
    roads; losses decrease (the grouped road's custom grad rule trains)."""
    class MoELoss(nn.Module):
        def __init__(self, cfg):
            super().__init__()
            self.moe = MoEMLP(cfg)

        def forward(self, x):
            y = self.moe(x)
            return ltorch.sum(y * y)

    x = jnp.asarray(rng.randn(2, 16, 32), jnp.float32)
    for dispatch in ("grouped", "dense"):
        cfg = MoEConfig(n_embd=32, intermediate_size=48, n_expert=4,
                        n_expert_per_token=2, capacity_factor=1.0,
                        dispatch=dispatch)
        step = TrainStep(MoELoss(cfg), optim.AdamW(lr=1e-2))
        losses = [float(step(x)) for _ in range(4)]
        assert losses[-1] < losses[0], (dispatch, losses)


def _serve_longctx(block_size, chunk, prompt_len, new_tokens=4):
    from thunder_tpu.models.litgpt import Config, GPT
    from thunder_tpu.serving import ServingEngine

    cfg = Config.from_name("tiny", block_size=block_size, n_layer=1,
                           n_head=2, n_query_groups=1, n_embd=32,
                           vocab_size=512)
    gpt = GPT(cfg, dtype=jnp.float32)
    engine = ServingEngine(gpt, max_batch=2, page_size=16,
                           max_seq=block_size, dtype=jnp.float32,
                           chunk_tokens=chunk)
    rng = np.random.RandomState(3)
    observability.enable()
    try:
        engine.start()
        warm = rng.randint(0, cfg.vocab_size, (2 * chunk,)).astype(np.int32)
        engine.submit(warm, max_new_tokens=2).result(timeout=600)
        observability.reset()
        prompt = rng.randint(0, cfg.vocab_size, (prompt_len,)).astype(np.int32)
        res = engine.submit(prompt, max_new_tokens=new_tokens).result(
            timeout=3600)
        counters = observability.counters()
    finally:
        observability.disable()
        engine.stop()
    recompiles = sum(v for k, v in counters.items()
                     if k.startswith("recompile."))
    return res, recompiles


@pytest.mark.longctx
@pytest.mark.serve
def test_longctx_serve_checked_smoke(monkeypatch):
    """Chunked-prefill serving at a 4k page table under TT_CHECK_TRACES=1:
    every transform/executor pass verifies while the bucket ladder admits a
    multi-chunk prompt with zero steady-state recompiles."""
    monkeypatch.setenv("TT_CHECK_TRACES", "1")
    res, recompiles = _serve_longctx(4096, 256, 1536)
    assert res.n_new_tokens == 4
    assert recompiles == 0


@pytest.mark.longctx
@pytest.mark.serve
@pytest.mark.slow
def test_32k_paged_serve_e2e():
    """The 32k acceptance row as a test: a 31744-token prompt (62 full
    512-token chunks) prefills through the paged engine and decodes with
    ZERO steady-state recompiles — the page pool and bucket ladder admit
    32k contexts without re-lowering."""
    res, recompiles = _serve_longctx(32768, 512, 31744, new_tokens=8)
    assert res.n_new_tokens == 8
    assert recompiles == 0


@pytest.mark.longctx
@pytest.mark.slow
@pytest.mark.dist
def test_32k_context_parallel_train_step():
    """The 32k train acceptance row as a test: tt.jit + context_parallel
    over sp=8 runs a full fwd+bwd+sgd step at T=32768 and the loss is
    finite (the ring never materialises an O(T^2) or O(T) x O(T) buffer
    per device beyond its shard)."""
    from thunder_tpu.models.litgpt import Config, GPTForCausalLM
    from thunder_tpu.parallel.context_parallel import context_parallel

    T = 32768
    cfg = Config.from_name("tiny", block_size=T, n_layer=1, n_head=2,
                           n_query_groups=1, n_embd=32, vocab_size=512)
    tm = tt.jit(GPTForCausalLM(cfg))
    context_parallel(tm, make_mesh({"sp": 8}))
    step = TrainStep(tm, optim.SGD(lr=1e-4))
    rng = np.random.RandomState(0)
    idx = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, T)), jnp.int32)
    tgt = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, T)), jnp.int32)
    loss = float(step(idx, tgt))
    assert np.isfinite(loss)
