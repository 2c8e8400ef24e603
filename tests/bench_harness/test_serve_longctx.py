"""The serve cell of the parallel block of window and full attention beside held experts,
`command-a-plus-05-2026-ep8-l4.serve-longctx`: its traffic names the driver `serve_longctx` (the
`serve` driver with a correctness sample that also holds the cached keys of a window layer and of
the full layer, and the window pages a request holds: `benchmark/drivers/serve_longctx.py`), so
the cases `test_reference.py` draws for the cells of the driver `serve` do not reach it. These do:
the sample against the reference and its control at the rehearsal size, planted faults that must
fail, the cut as the configuration states it, the traffic, the manifest's entries BY NAME, and the
reference in blocks."""
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import loadgen, manifest
from test_reference import served_sample

MAN = manifest.load_manifest()
CELL = "command-a-plus-05-2026-ep8-l4.serve-longctx"
CONFIG = "command-a-plus-05-2026-ep8-l4"
METRICS = {"longctx_decode_device_ms_per_iter", "longctx_window_attn_roofline", "longctx_decode_mfu"}
CUT = ["num_hidden_layers", "num_experts", "vocab_size"]
# the published config.json of Command A+ (command-a-plus-05-2026)
PUBLISHED = {"attention_bias": False, "expert_selection_fn": "sigmoid", "first_k_dense_replace": 0,
             "head_dim": 128, "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 4096,
             "layer_norm_eps": 1e-05, "layer_switch": 4,
             "layer_types": ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention"] * 8,
             "logit_scale": 1, "max_position_embeddings": 200000, "model_type": "cohere2_moe",
             "norm_topk_prob": True, "num_attention_heads": 128, "num_experts": 128, "num_experts_per_tok": 8,
             "num_hidden_layers": 32, "num_key_value_heads": 8, "num_shared_experts": 4,
             "order_of_interleaved_layers": "local_attn_first", "position_embedding_type": "rope_gptj",
             "prefix_dense_intermediate_size": 16384, "prefix_dense_sliding_window_pattern": 1,
             "rms_norm_eps": None, "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
             "rope_theta": 50000, "rotary_pct": 1, "shared_expert_combination_strategy": "average",
             "sliding_window": 4096, "tf_legacy_loss": False, "tie_word_embeddings": True,
             "use_embedding_sharing": True, "use_gated_activation": True, "use_parallel_block": True,
             "use_parallel_embedding": False, "use_qk_norm": False, "vocab_size": 262144}


@pytest.fixture(scope="module")
def served():
    """The rehearsal cell, its engine after the sample (started again), the sample's numbers."""
    cell = manifest.resolve(MAN, CELL, rehearse=True)
    engine, stats, notes = served_sample(cell)
    engine.start()
    yield cell, engine, stats, notes
    engine.stop()


def test_the_driver_offers_and_measures_as_serve_does_and_judges_a_sample_of_its_own():
    c = manifest.resolve(MAN, CELL)
    driver = c.driver
    serve = driver.serve
    assert c.traffic["driver"] == "serve_longctx" and serve.__name__ == "benchmark.drivers.serve"
    for name in ("build_engine", "serve_all", "check_kernels"):
        assert getattr(driver, name) is getattr(serve, name)
    assert driver.PROGRAMS == serve.PROGRAMS and driver.check_sample is not serve.check_sample
    with driver._own_sample():
        assert serve.check_sample is driver.check_sample
    assert serve.check_sample.__module__ == "benchmark.drivers.serve"
    assert {"margin", "window_key_margin", "full_key_margin", "why"} <= set(c.traffic["correctness"])


def test_the_traffic_is_the_cells_as_stated():
    t = manifest.resolve(MAN, CELL).traffic
    assert t["engine"] == {"dtype": "bfloat16", "max_batch": 32, "page_size": 64, "max_seq": 14336,
                           "chunk_tokens": 512, "min_bucket": 512}
    assert "prefix_sharing" not in t["engine"]
    assert t["loop"] == {"kind": "closed", "clients": 32, "preroll_s": 30.0}
    assert t["prompt_len"] == {"dist": "lognormal", "median": 6144, "sigma": 0.5, "min": 2048, "max": 12288}
    assert t["output_len"] == {"dist": "uniform", "min": 512, "max": 2048}
    # the sample: below the window, crossing it while decoding, past it through several chunks
    (p0, n0), (p1, n1), (p2, n2) = t["correctness"]["requests"]
    assert p0 + n0 <= 4096 < p1 + n1 and p1 < 4096 and p2 > 2 * 4096 and -(-p2 // 512) >= 16
    assert all(t["prompt_len"]["min"] <= p <= t["prompt_len"]["max"] for p, _ in t["correctness"]["requests"])


def test_the_configuration_is_the_published_one_cut_to_one_chip_of_eight():
    c = manifest.resolve(MAN, CELL)
    entry = next(e for e in MAN["configs"] if e["name"] == c.config_name)
    assert c.config_name == CONFIG and entry["reduced"] == CUT and c.config["reduced"] == CUT and c.chips == 1
    assert c.config["reduced_from"] == {"num_hidden_layers": 32, "num_experts": 128, "vocab_size": 262144}
    assert (c.config["num_hidden_layers"], c.config["num_experts"], c.config["vocab_size"]) == (4, 16, 32768)
    assert c.config["experts_held"] == [0, 16]
    # every width as published, one whole period of the layer pattern, the floors of a cut
    assert {k for k, v in PUBLISHED.items() if c.config.get(k) != v} == set(CUT)
    assert entry["source"] == c.config["source"] and "command-a-plus-05-2026" in entry["source"]
    keys = c.builder.model_keys(c.config)
    assert keys["layer_types"] == ("window", "window", "window", "full")
    assert keys["n_routed_experts"] == 128 and keys["experts_held"] == (0, 16) and keys["n_expert_per_token"] == 8
    assert keys["n_shared_experts"] == 4 and keys["sliding_window"] == 4096 and keys["block_size"] == 14336
    assert set(c.config["assumed"]["why"]) >= {k for k in c.config["assumed"] if k != "why"}
    assert c.builder.kernel_claims(c.config) == {
        "decode_cfn": {"thunder.paged_attention": 4, "thunder.ragged_mlp": 4},
        "chunk_cfn": {"thunder.paged_chunk_attention": 4, "thunder.ragged_mlp": 4}}
    assert not hasattr(c.builder, "build_loss_model")
    # the cut's arithmetic: 1,149.77 M parameters a layer, 4.733 B in all
    from benchmark.lib import costs_parallel_moe

    d = c.builder.dims(c.config)
    p = costs_parallel_moe.matmul_params(d)
    layer = p["attn"] + p["router"] + p["shared"] + d["experts_held"] * p["expert"] + 4096
    assert round(layer / 1e6, 2) == 1149.77 and round((4 * layer + p["head"]) / 1e9, 3) == 4.733
    assert d["layers"] == {"window": 3, "full": 1}


def test_the_sample_holds_the_keys_and_the_window_pages_to_the_reference(served):
    cell, _, stats, notes = served
    spec = cell.traffic["correctness"]
    assert notes == [] and stats["sample_differ"] == 0 and stats["sample_window_pages_wrong"] == 0
    assert stats["sample_margin"] <= spec["margin"] / 10
    assert stats["sample_window_key_distance"] <= spec["window_key_margin"] / 10
    assert stats["sample_full_key_distance"] <= spec["full_key_margin"] / 10
    assert stats["sample_window_pages_most"] <= cell.driver.pages_limit(16, 32, 8) == 6


def test_the_references_control_does_not_agree(served):
    """The reference's own control (rope on the rotate-half pairs) against what the engine
    cached and chose: the first window layer's keys no longer agree, by far more than the cell's
    limit, where the cell's configuration agrees; the chosen tokens stay within the margin of
    the cell's configuration (at this size a rope on other pairs need not move a top token)."""
    cell, engine, _, _ = served
    ref, driver, spec = cell.reference, cell.driver, cell.traffic["correctness"]
    wrong, what = ref.control(cell.config)
    assert wrong != cell.config and what == "rope on rotate-half pairs"
    p, n = spec["requests"][-1]
    prompt = loadgen.prompt_tokens(3, 1_000_003, p, cell.config["vocab_size"])
    res = engine.submit(prompt, max_new_tokens=n).result(timeout=300)
    pos = driver.window_positions(res.window_pages, engine.page_size)
    at = pos < p + n - 1
    cached = driver.served_keys(engine, 0, [pg for _, pg in res.window_pages])[at]
    for config_used, holds in ((cell.config, True), (wrong, False)):
        x = ref.embed(config_used, engine.params, res.tokens)
        _, made = ref.layer(config_used, ref.layer_params(engine.params, 0), x, 0)
        keys = ref.published_keys(made["k"], config_used, "window")[pos[at]]
        apart = driver.distance(cached, keys)
        assert (apart <= spec["window_key_margin"]) == holds, (what, apart)
        assert holds or apart > 10 * spec["window_key_margin"]
    logits = np.asarray(ref.forward(cell.config, engine.params, res.tokens, rows=np.arange(n) + p - 1))
    assert (logits.max(-1) - logits[np.arange(n), res.new_tokens]).max() <= spec["margin"]


@pytest.mark.parametrize("fault", ["keys two hundredths off", "a window page too many"])
def test_a_planted_fault_is_not_correct(served, monkeypatch, fault):
    cell, engine, _, _ = served
    driver, notes = cell.driver, []
    if fault == "keys two hundredths off":
        kept = driver.served_keys
        monkeypatch.setattr(driver, "served_keys", lambda *a: 1.02 * kept(*a))
    else:
        monkeypatch.setattr(driver, "pages_limit", lambda *a: 1)
    stats = driver.check_sample(cell, engine, 3, notes)
    if fault == "keys two hundredths off":
        assert stats["sample_window_key_distance"] == pytest.approx(0.02, rel=1e-3)
        assert stats["sample_full_key_distance"] == pytest.approx(0.02, rel=1e-3)
        assert len(notes) == 2 and all("keys" in n for n in notes)
    else:
        assert len(notes) == 1 and "window pages" in notes[0]
    assert stats["sample_margin"] <= cell.traffic["correctness"]["margin"] and stats["sample_differ"] == 0


def test_the_reference_in_blocks_is_the_reference():
    cell = manifest.resolve(MAN, CELL, rehearse=True)
    params = cell.builder.seeded_params(
        {n: p.data for n, p in cell.builder.build_serving_model(cell.config, "tiny", jnp.float32)
         .named_parameters()}, 5, cell.config)
    tokens = loadgen.prompt_tokens(5, 0, 90, cell.config["vocab_size"])
    rows = np.arange(40, 90, 7)
    logits, keys = cell.driver.BlockwiseReference(cell, params)(tokens, rows)
    whole = np.asarray(cell.reference.forward(cell.config, params, tokens, rows=rows))
    assert np.abs(logits - whole).max() < 1e-5 and len(keys) == cell.config["num_hidden_layers"]
    assert keys[0].shape == (90, cell.config["num_key_value_heads"], cell.config["head_dim"])
    # nothing of the program is in it
    with open(cell.reference.__file__) as f:
        assert "thunder_tpu" not in f.read().split('"""', 2)[2]


def check_listing(man):
    """The cell's entries by name: each listed for it alone, moving its `serve_tpot_p50_ms`; the
    cell on its configuration."""
    mine = {m["name"]: m for m in man["per_layer"] if m["name"].startswith("longctx_")}
    assert set(mine) == METRICS
    for m in mine.values():
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tpot_p50_ms"
        assert m["source"] == "device_trace"
        assert m["unit"] == "%" if m["name"].endswith("_roofline") or m["name"].endswith("_mfu") else True
    e2e, per_layer = manifest.listed(man, CELL)
    assert {m["name"] for m in per_layer} == METRICS | {"recompiles_in_window"}
    assert {m["name"] for m in e2e} == {"serve_tpot_p50_ms", "setup_s"}
    for other in (w["name"] for w in man["workloads"] if w["name"] != CELL):
        assert not any(m["name"] in mine for m in manifest.listed(man, other)[1])
    by_name = {w["name"]: w for w in man["workloads"]}
    assert by_name[CELL] == {"name": CELL, "config": CONFIG, "traffic": "serve-longctx", "chips": 1,
                             "why": by_name[CELL]["why"]} and len(by_name[CELL]["why"]) <= 200
    assert CONFIG in {c["name"] for c in man["configs"]}


def test_the_manifest_lists_the_longctx_metrics_for_this_cell_by_name():
    check_listing(MAN)
    cell = manifest.resolve(MAN, CELL)
    for name in METRICS:
        assert callable(cell.reader(name).read)
