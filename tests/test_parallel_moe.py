"""The parallel block of window and position-free full attention beside held experts
(models/parallel_moe.py; Command A+'s layer) at the rehearsal size on the CPU: the forward and the
paged engine (whole prompts, chunks, chunks with the decode rows beside them, decode; XLA and the
interpreted kernels) against the plain reference, the expert shares against the whole layer, the
controls that must fail, the window pages a sequence holds and the counters, the three kernels
compiled by the v5e's compiler at the cell's shapes, and the serving programs of the models that
share the changed code, which must gain no operation."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from benchmark.lib import manifest
from thunder_tpu import observability
from thunder_tpu.models import block_moe, latent_moe, litgpt, moe, parallel_moe
from thunder_tpu.serving import ServingEngine

CELL = "command-a-plus-05-2026-ep8-l4.serve-longctx"


@pytest.fixture(scope="module")
def cell():
    return manifest.resolve(manifest.load_manifest(), CELL, rehearse=True)


@pytest.fixture(scope="module")
def ref(cell):
    return cell.reference


@pytest.fixture(scope="module")
def model(cell):
    m = cell.builder.build_serving_model(cell.config, "tiny", jnp.float32)
    cell.builder.reseed(dict(m.named_parameters()), 5, cell.config)
    return m


@pytest.fixture(scope="module")
def params(model):
    return {k: p.data for k, p in model.named_parameters()}


def engine_of(model, cell, **over):
    spec = dict(cell.traffic["engine"], **over)
    spec["dtype"] = getattr(jnp, spec["dtype"])
    return ServingEngine(model, **spec)


def serve(eng, requests):
    eng.start()
    try:
        futures = [eng.submit(p, max_new_tokens=n) for p, n in requests]
        return [f.result(timeout=300) for f in futures]
    finally:
        eng.stop()


def tokens(n: int, seed: int = 0, vocab: int = 512):
    return np.random.RandomState(seed).randint(0, vocab, (n,)).astype(np.int32)


T_PAD = 128
_JITTED = {}


def reference_run(ref, config, params, toks):
    """``(logits (T_PAD, vocab), [each layer's keys, published order])`` of the reference over the
    tokens padded to ``T_PAD``: one compile a configuration."""
    key = repr(sorted(config.items()))
    if key not in _JITTED:
        def run(prm, t):
            x = ref.embed(config, prm, t)
            keys = []
            for i in range(config["num_hidden_layers"]):
                x, made = ref.layer(config, ref.layer_params(prm, i), x, i)
                keys.append(made["k"])
            return ref.head(config, prm, x), keys

        _JITTED[key] = jax.jit(run)
    padded = np.zeros((T_PAD,), np.int32)
    padded[:len(toks)] = toks
    logits, keys = _JITTED[key](params, jnp.asarray(padded))
    return np.asarray(logits), [ref.published_keys(k, config, kind)
                                for k, kind in zip(keys, ref.kinds(config))]


def gap(ref, config, params, res, p: int, n: int) -> float:
    """How far below the reference's top logit the engine's chosen tokens lie, the largest."""
    logits = reference_run(ref, config, params, res.tokens)[0][p - 1:p - 1 + n]
    return float((logits.max(-1) - logits[np.arange(n), res.new_tokens]).max())


# prompts below the window of 16 (a whole-prompt bucket), crossing it while decoding, past it
# through chunks of 32 (two, and four with a short last one)
REQUESTS = [(10, 4), (12, 8), (40, 8), (100, 8)]


# -- the model and its reference ---------------------------------------------------------------

def test_the_stored_columns_turn_the_published_pairs(ref):
    """Rotate-half rope on the stored columns (even published ones first) is the published GPT-J
    rope on the interleaved pairs, column for column once put back."""
    hs, T = 32, 9
    x = np.random.RandomState(1).randn(T, 3, hs).astype(np.float32)
    cos, sin = litgpt.build_rope_cache(T, hs, 50000.0)
    order = parallel_moe.published_columns(hs)
    stored = jnp.asarray(x[..., order]).transpose(1, 0, 2)[None]            # (1, heads, T, hs)
    got = np.asarray(litgpt._apply_rope(stored, cos, sin, hs))[0].transpose(1, 0, 2)
    config = {"position_embedding_type": "rope_gptj", "rope_theta": 50000.0}
    want = ref.published_keys(ref._rope(jnp.asarray(x), jnp.arange(T), config), config, "window")
    back = np.empty_like(got)
    back[..., order] = got
    assert np.abs(back - want).max() < 1e-5


def test_the_forward_is_the_reference(cell, ref, model, params):
    toks = tokens(T_PAD)
    got = np.asarray(tt.jit(model)(jnp.asarray(toks[None])))[0]
    want = reference_run(ref, cell.config, params, toks)[0]
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()


def test_the_shares_add_up_to_the_whole_layer(cell, ref, params):
    """Two chips' shares of the routed experts, with the shared experts counted once, give the
    uncut layer: in the program's expert layer, and four shares in the reference."""
    x = jnp.asarray(np.random.RandomState(2).randn(2, 7, 64).astype(np.float32))
    whole = moe.HeldExperts(64, 64, 8, (0, 8), 2, n_shared=4, shared="average")
    for n, p in whole.named_parameters():
        p.data = 0.1 * jax.random.normal(jax.random.key(len(n)), p.data.shape, jnp.float32)
    panels = {n: p.data for n, p in whole.named_parameters()}
    total = 0.0
    for lo in (0, 4):
        share = moe.HeldExperts(64, 64, 8, (lo, lo + 4), 2, n_shared=4, shared="average")
        for n, p in share.named_parameters():
            p.data = panels[n][lo:lo + 4] if n in ("w_gate", "w_up", "w_down") else panels[n]
        total = total + np.asarray(tt.jit(share)(x))
    shared_alone = moe.HeldExperts(64, 64, 8, (0, 8), 2, n_shared=4, shared="average")
    for n, p in shared_alone.named_parameters():
        p.data = jnp.zeros_like(panels[n]) if n in ("w_gate", "w_up", "w_down") else panels[n]
    shared = np.asarray(tt.jit(shared_alone)(x))
    assert np.abs(total - shared - np.asarray(tt.jit(whole)(x))).max() < 1e-5
    # the reference's held share likewise: its routed parts over the four ranges are the whole
    config = dict(cell.config, num_experts=2)
    u = jnp.asarray(np.random.RandomState(3).randn(7, 64).astype(np.float32))
    p0 = ref.layer_params(params, 0)
    parts = []
    for lo in range(2, 6, 2):
        p = dict(p0, **{f"experts.{k}": p0[f"experts.{k}"][lo - 2:lo] for k in ("w_gate", "w_up", "w_down")})
        parts.append(ref._experts(dict(config, experts_held=[lo, lo + 2]), p, u)[0])
    routed, _ = ref._experts(cell.config, p0, u)
    assert np.abs(np.asarray(sum(parts)) - np.asarray(routed)).max() < 1e-5


def test_averaged_shared_experts_are_an_option_and_the_sum_traces_nothing_new():
    layer = moe.HeldExperts(64, 64, 8, (0, 8), 2, n_shared=4)
    assert layer.shared_scale == 1.0
    assert moe.HeldExperts(64, 64, 8, (0, 8), 2, n_shared=4, shared="average").shared_scale == 0.25
    with pytest.raises(ValueError, match="combine by"):
        moe.HeldExperts(64, 64, 8, (0, 8), 2, shared="max")


# -- served through the paged engine ------------------------------------------------------------

@pytest.fixture(scope="module")
def served(cell, model):
    """The requests alone (the mixed program: decode rows beside a chunk), with the first window
    layer's and the full layer's cached keys read after each, and together; the bus on from the
    first trace, so that the programs count their routing."""
    from benchmark.drivers import serve_longctx

    eng = engine_of(model, cell)
    assert eng.runner.mixes and eng.runner.page_kinds == ("full", "window")
    toks = tokens(120)
    reqs = [(toks[:p], n) for p, n in REQUESTS]
    alone, kept = [], []
    observability.enable()  # from the first trace on: the programs count their routing
    try:
        for r in reqs:
            res = serve(eng, [r])[0]
            alone.append(res)
            kept.append((serve_longctx.served_keys(eng, 0, [pg for _, pg in res.window_pages]),
                         serve_longctx.served_keys(eng, 3, res.pages)))
        observability.reset()
        together = serve(eng, reqs)
        counters = observability.counters()
    finally:
        observability.disable()
        observability.reset()
    return {"engine": eng, "alone": alone, "kept": kept, "together": together, "counters": counters}


def test_prefill_chunks_mixed_and_decode_agree_with_the_reference(cell, ref, params, served):
    for (p, n), a, b in zip(REQUESTS, served["alone"], served["together"]):
        assert np.array_equal(a.new_tokens, b.new_tokens)
        assert gap(ref, cell.config, params, a, p, n) < 1e-4


def key_distances(ref, config, params, served) -> tuple:
    """The largest relative distance over the requests of the engine's cached keys from the
    reference's under ``config``: the first window layer's on its held window pages, the full
    layer's on every position."""
    from benchmark.drivers import serve_longctx

    worst = [0.0, 0.0]
    for (p, n), res, (win, full) in zip(REQUESTS, served["alone"], served["kept"]):
        keys = reference_run(ref, config, params, res.tokens)[1]
        pos = serve_longctx.window_positions(res.window_pages, 8)
        at = pos < p + n - 1
        worst[0] = max(worst[0], serve_longctx.distance(win[at], keys[0][pos[at]]))
        worst[1] = max(worst[1], serve_longctx.distance(full[:p + n - 1], keys[3][:p + n - 1]))
    return tuple(worst)


def test_the_cached_keys_are_the_references(cell, ref, params, served):
    """The first window layer's keys on the window pages a request held at its end, the full
    layer's on every position, against the reference's in the published column order."""
    assert max(key_distances(ref, cell.config, params, served)) < 1e-5


CONTROLS = {  # a wrong reading of the layer, and the limit of the cell's comparison it breaks
    "rotate-half rope": ("margin", "window_key_margin"),
    "rope in the full layer": ("full_key_margin",),
    "no window": ("margin", "full_key_margin"),
    "shared experts summed": ("margin", "full_key_margin"),
    "routed and shared averaged": ("margin", "full_key_margin"),
}


@pytest.mark.parametrize("wrong", list(CONTROLS))
def test_each_control_breaks_its_limit_tenfold(cell, ref, params, served, wrong):
    config = cell.config
    bad = {"rotate-half rope": ref.control(config)[0],
           "rope in the full layer": dict(config, assumed=dict(config["assumed"], full_layer_positions="rope")),
           "no window": dict(config, sliding_window=10 ** 6),
           "shared experts summed": dict(config, shared_expert_combination_strategy="sum"),
           "routed and shared averaged": dict(config, shared_expert_combination_strategy="average_with_routed"),
           }[wrong]
    window, full = key_distances(ref, bad, params, served)
    read = {"margin": max(gap(ref, bad, params, res, p, n) for (p, n), res in zip(REQUESTS, served["alone"])),
            "window_key_margin": window, "full_key_margin": full}
    limits = cell.traffic["correctness"]
    for limit in CONTROLS[wrong]:
        assert read[limit] > 10 * limits[limit], (wrong, limit, read)


def test_a_sequence_holds_its_window_pages_and_no_more(cell, served):
    """No request holds more window pages at once than a chunk's queries can see; at its end it
    holds those of its last query's window; what the trims freed is on the bus beside the pages
    of each kind."""
    from benchmark.drivers import serve_longctx

    eng, window, ps, C = served["engine"], 16, 8, 32
    for (p, n), res in zip(REQUESTS, served["alone"]):
        assert res.window_pages_peak <= serve_longctx.pages_limit(window, C, ps) == 6
        assert [j for j, _ in res.window_pages] == serve_longctx.last_window_pages(p + n - 2, window, ps)
    # a whole prompt in one bucket, then decode steps: a window's pages and the one a new
    # position opens at most
    assert max(res.window_pages_peak for res in served["alone"][:2]) == window // ps + 1
    c = served["counters"]
    assert c["serve.window_pages_freed"] > 0
    assert 0 < c["serve.state.window_pages_peak"] <= eng.cache.n_window_pages - 1
    assert 0 < c["serve.state.full_pages_peak"] <= eng.cache.n_pages - 1
    assert c["serve.state.window_pages"] >= c["serve.state.window_pages_peak"]


def test_window_pages_freed_counts_every_trim(served):
    """Pages taken minus pages held when the requests retired is what the bus counts as freed."""
    eng, taken = served["engine"], []
    alloc = eng.cache.window_allocator.alloc

    def counting(n):
        taken.append(n)
        return alloc(n)

    eng.cache.window_allocator.alloc = counting
    observability.enable()
    try:
        observability.reset()
        out = serve(eng, [(tokens(40, 1), 12), (tokens(70, 2), 6)])
        freed = observability.counters().get("serve.window_pages_freed", 0)
    finally:
        observability.disable()
        observability.reset()
        del eng.cache.window_allocator.alloc
    assert freed == sum(taken) - sum(len(r.window_pages) for r in out) > 0


def test_the_decode_and_mixed_programs_count_their_routing(served):
    c, layers = served["counters"], 4
    assert c["serve.moe.rows_routed"] == 2 * layers * c["serve.tokens"]
    assert 0 < c["serve.moe.rows_held"] < c["serve.moe.rows_routed"]
    assert c.get("serve.pool_copied", 0) == 0 and c["serve.decode_mixed"] > 0


def test_the_kernels_serve_it_interpreted_through_two_programs_a_pass(cell, ref, pallas_claims):
    """The paged decode and chunk kernels (in a window layer and in the full one) and the ragged
    kernel, interpreted, give what the reference gives, a chunk's program and the decode program
    apart (``engine._mixes`` off). One window layer and the full one: an interpreted paged decode
    kernel takes seconds to compile a call."""
    from benchmark.lib.harness import pallas_claims as claims_of

    config = dict(cell.config, num_hidden_layers=2, layer_types=["sliding_attention", "full_attention"])
    model = cell.builder.build_serving_model(config, "tiny", jnp.float32)
    cell.builder.reseed(dict(model.named_parameters()), 6, config)
    eng = engine_of(model, cell, max_batch=2, chunk_tokens=16)
    eng._mixes = False
    res = serve(eng, [(tokens(32, 4), 3)])[0]   # two chunks past the window, two decode steps
    params = {k: p.data for k, p in model.named_parameters()}
    assert gap(ref, config, params, res, 32, 3) < 1e-4
    for program, symbols in cell.builder.kernel_claims(config).items():
        got = claims_of(tt.last_traces(getattr(eng.runner, program)._cfn)[-1])
        assert {s: got[s] for s in symbols} == symbols == {s: 2 for s in symbols}


# -- the programs of the models that share the changed code gain no operation -------------------

def _op_counts(model) -> dict:
    """Top-level operations of the decode program's and the chunk program's computation traces
    (the chunk program with the decode rows beside it where the model mixes)."""
    eng = ServingEngine(model, max_batch=2, page_size=8, max_seq=64, chunk_tokens=16, min_bucket=8,
                        dtype=jnp.float32)
    r, c, B = eng.runner, eng.cache, 2
    i32 = lambda *a: jnp.asarray(*a, dtype=jnp.int32)  # noqa: E731
    tables = tuple(i32(np.tile(np.arange(1, eng.n_pages_max + 1), (B, 1))) for _ in r.page_kinds)
    out = r.decode_cfn(eng.params, i32(np.ones((B, 1))), c.state, tables, i32(np.arange(B)))
    c.rebind(out[1])
    args = (eng.params, i32(np.ones((1, 16))), tuple(t[:1] for t in tables), c.state, i32(8), i32(15), i32(0))
    r.chunk_cfn(*args, (i32(np.ones((B, 1))), tables, i32(np.arange(B)))) if r.mixes else r.chunk_cfn(*args)
    return {name: len(tt.last_traces(getattr(r, name)._cfn)[0].bound_symbols)
            for name in ("decode_cfn", "chunk_cfn")}


def _dense():
    return litgpt.GPT(litgpt.Config(name="t", block_size=64, vocab_size=128, padded_vocab_size=128, n_layer=2,
                                    n_head=4, n_query_groups=2, n_embd=64, head_size=16, rotary_percentage=1.0,
                                    parallel_residual=False, bias=False, norm_class_name="RMSNorm",
                                    mlp_class_name="LLaMAMLP", intermediate_size=96))


# read on the commit before the averaged shared experts, the window's served layer and the page peaks
PARENT_OPS = {"DenseBlock": ({"decode_cfn": 129, "chunk_cfn": 177}, _dense),
              "RoutedBlock": ({"decode_cfn": 243, "chunk_cfn": 236}, lambda: block_moe.tiny_block_moe(block_size=64)),
              "HeldExperts": ({"decode_cfn": 304, "chunk_cfn": 387},
                              lambda: latent_moe.LatentMoE(latent_moe.Config(block_size=64)))}


@pytest.mark.parametrize("layer", sorted(PARENT_OPS))
def test_the_shared_serving_programs_gain_no_operation(layer):
    want, make = PARENT_OPS[layer]
    assert _op_counts(make()) == want


# -- the three kernels through the v5e's compiler at the cell's shapes (no chip needed) ---------

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


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


# the cell's pools: 32 slots of 14,336 positions in pages of 64 (the full pool at full residency)
# and the window pool of 32 windows of 4,096 and one chunk's pages (kv_pages.PagedKVCache)
POOLS = {"window": (1 + 32 * 65 + 8, 4096), "full": (1 + 32 * 224, None)}


@pytest.mark.parametrize("kind", sorted(POOLS))
def test_the_paged_decode_kernel_compiles_for_the_v5e_at_16_query_heads_a_key_head(one_chip, kind):
    from thunder_tpu.executors import pallasex

    P, window = POOLS[kind]
    bf, i32 = jnp.bfloat16, jnp.int32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with jax.enable_x64(False):   # as on the chip: conftest.py turns x64 on for the CPU tests
        compiled = jax.jit(lambda q, k, v, t, s: pallasex.paged_attention_decode(
            q, k, v, t, s, 128 ** -0.5, window, interpret=False)).lower(
            sds((32, 128, 128), bf), sds((P, 8, 64, 128), bf), sds((P, 8, 64, 128), bf),
            sds((32, 224), i32), sds((32,), i32)).compile()
    assert _mosaic_calls(compiled) == 1


@pytest.mark.parametrize("kind", sorted(POOLS))
def test_the_paged_chunk_kernel_compiles_for_the_v5e_at_a_chunk_of_512(one_chip, kind):
    from thunder_tpu.executors import pallasex

    P, window = POOLS[kind]
    bf, i32 = jnp.bfloat16, jnp.int32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with jax.enable_x64(False):
        compiled = jax.jit(lambda q, k, v, t, p: pallasex.paged_chunk_decode(
            q, k, v, t, p, 128 ** -0.5, window, interpret=False)).lower(
            sds((1, 128, 512, 128), bf), sds((P, 8, 64, 128), bf), sds((P, 8, 64, 128), bf),
            sds((1, 224), i32), sds((1, 512), i32)).compile()
    assert _mosaic_calls(compiled) == 1


@pytest.mark.parametrize("tokens_in", [32, 544], ids=["decode-32-slots", "chunk-512-beside-32"])
def test_the_ragged_kernel_compiles_for_the_v5e_at_16_experts_of_4096(one_chip, tokens_in):
    """Panels of 4,096 x 4,096: a weight tile of 512 hidden columns (analysis/memory.py
    ragged_mlp_block_h: 1,024 would not fit the kernel's VMEM limit)."""
    from thunder_tpu.analysis import budget
    from thunder_tpu.executors import pallasex

    bf, E, D, H, k = jnp.bfloat16, 16, 4096, 4096, 8
    tile = moe.ragged_tile(tokens_in * k, 128)
    R = -(-tokens_in * k // tile) * tile + E * tile
    assert budget.ragged_mlp_block_h(tile, D, H, 2, 2) == 512

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with jax.enable_x64(False):
        compiled = jax.jit(lambda r, g, u, d, s: pallasex.ragged_mlp_fused(r, g, u, d, s, tile, interpret=False)).lower(
            sds((R, D), bf), sds((E, D, H), bf), sds((E, D, H), bf), sds((E, H, D), bf), sds((E,), jnp.int32)).compile()
    assert _mosaic_calls(compiled) == 1
