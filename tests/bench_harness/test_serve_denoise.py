"""The serve cell PR 41 added, `sdar-30b-a3b-chat-l6.serve-denoise`: its traffic names the driver
`serve_denoise` (the `serve` driver for generation by diffusion over blocks:
`benchmark/drivers/serve_denoise.py` says what differs), so the cases `test_reference.py` draws
for the cells of the driver `serve` do not reach it. These do: the sample against the reference at
the rehearsal size and under the controls and planted faults that must fail, the cut as the
configuration states it, the traffic, the manifest's entries BY NAME, the readers, and what a
rehearsal reads."""
import numpy as np
import pytest

from benchmark.lib import manifest
from test_reference import served_sample
from test_rehearsal import last_line, run_cell

MAN = manifest.load_manifest()
CELL = "sdar-30b-a3b-chat-l6.serve-denoise"
CONFIG = "sdar-30b-a3b-chat-l6"
METRICS = {"denoise_pass_ms_p50", "denoise_tokens_per_pass", "denoise_passes_per_block",
           "denoise_idle_ms_per_pass", "denoise_moe_roofline", "denoise_block_attn_roofline",
           "denoise_decode_mfu"}
# the catalog row's `config` (model-configs guide, architectures.jsonl: SDAR-30B-A3B-Chat)
PUBLISHED = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
             "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
             "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
             "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
             "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
             "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
             "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}


@pytest.fixture(scope="module")
def served():
    """The rehearsal cell, its engine after the sample, the sample's numbers, and the four requests
    served alone again with their cached keys."""
    cell = manifest.resolve(MAN, CELL, rehearse=True)
    engine, stats, notes = served_sample(cell)
    driver = cell.driver
    reqs = cell.traffic["correctness"]["requests"]
    prompts = [driver.prompt_tokens(3, 1_000_000 + i, p, cell.config) for i, (p, _) in enumerate(reqs)]
    last = cell.config["num_hidden_layers"] - 1
    engine.start()
    try:
        alone = []
        for prompt, (_, n) in zip(prompts, reqs):
            res = engine.submit(prompt, max_new_tokens=n).result(timeout=300)
            alone.append((res, driver.served_keys(engine, last, res.pages, len(res.pages) * engine.page_size),
                          driver.served_keys(engine, 0, res.pages, len(res.pages) * engine.page_size)))
    finally:
        engine.stop()
    return cell, driver, engine, stats, notes, alone


def replayed(served, config=None):
    """``(worst margin, worst order, worst key distance in the last and in the first layer)`` of the served requests against the
    reference under ``config`` (None: the cell's)."""
    cell, driver, engine, _, _, alone = served
    reference = driver.BlockwiseReference(cell, engine.params, config)
    gen, reqs = cell.config["generation"], cell.traffic["correctness"]["requests"]
    t_max = max(-(-(p + n) // gen["block_length"]) * gen["block_length"] for p, n in reqs)
    out = [0.0, 0.0, 0.0, 0.0]
    for (p, _), (res, rows, rows0) in zip(reqs, alone):
        got = driver.replay(reference, res, p, gen, t_max)
        out = [max(out[0], got["margin"]), max(out[1], got["order"]),
               max(out[2], driver.distance(rows[:got["rows"]], got["keys"])),
               max(out[3], driver.distance(rows0[:got["rows"]], got["first_keys"]))]
    return out


def test_the_driver_offers_and_measures_as_serve_does_with_its_own_pieces_in_place():
    c = manifest.resolve(MAN, CELL)
    driver = c.driver            # a module loaded anew at every read: hold one
    serve = driver.serve
    assert c.traffic["driver"] == "serve_denoise" and serve.__name__ == "benchmark.drivers.serve"
    assert driver.serve_all is serve.serve_all and driver.PROGRAMS == ("block_cfn", "chunk_cfn")
    theirs = {k: getattr(serve, k) for k in ("check_sample", "PROGRAMS", "check_kernels", "decode_regions",
                                             "build_engine", "make_loop")}
    with driver._own(c.config):
        assert serve.check_sample is driver.check_sample and serve.PROGRAMS == driver.PROGRAMS
        assert serve.build_engine is driver.build_engine and serve.decode_regions is driver.decode_regions
    assert all(getattr(serve, k) is v for k, v in theirs.items())
    assert {"margin", "order_margin", "kv_margin", "first_kv_margin", "why"} <= set(c.traffic["correctness"])
    assert set(driver.PROGRAMS) == set(c.builder.kernel_claims(c.config))


def test_the_traffic_is_the_issues_letter_for_letter():
    c = manifest.resolve(MAN, CELL)
    t = c.traffic
    assert t["engine"] == {"dtype": "bfloat16", "max_batch": 64, "page_size": 64, "max_seq": 2048,
                           "chunk_tokens": 512, "n_pages": 2049}
    assert t["loop"] == {"kind": "closed", "clients": 64, "preroll_s": 30.0}
    assert t["prompt_len"] == {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 64, "max": 1024}
    assert t["output_len"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert c.builder.block_diffusion(c.config) == {
        "block_length": 4, "denoising_steps": 2, "strategy": "low_confidence_dynamic", "threshold": 0.9,
        "mask_id": 151669}
    reqs = t["correctness"]["requests"]
    C, K = t["engine"]["chunk_tokens"], 4
    # one prompt of one chunk, one of two, one with L mod 4 != 0, one ending off a block's edge
    assert reqs[0][0] <= C and reqs[0][0] % K == 0 and reqs[1][0] > C and reqs[2][0] % K and (sum(reqs[3])) % K
    assert all(t["prompt_len"]["min"] <= p <= t["prompt_len"]["max"] for p, _ in reqs)


def test_prompts_never_hold_the_mask_token():
    c = manifest.resolve(MAN, CELL, rehearse=True)
    mask = c.config["generation"]["mask_token_id"]
    toks = np.concatenate([c.driver.prompt_tokens(9, i, 200, c.config) for i in range(40)])
    assert mask not in toks and toks.max() == c.config["vocab_size"] - 1 and toks.min() == 0
    assert set(range(c.config["vocab_size"])) - set(toks.tolist()) == {mask}


def test_the_sample_holds_every_margin_at_the_rehearsal_size(served):
    cell, _, engine, stats, notes, _ = served
    spec = cell.traffic["correctness"]
    assert notes == [] and stats["sample_differ"] == 0 and stats["sample_mask_tokens"] == 0
    assert stats["sample_margin"] <= spec["margin"] / 10
    assert stats["sample_order_margin"] <= spec["order_margin"] / 10
    assert stats["sample_kv_distance"] <= spec["kv_margin"] / 10
    assert stats["sample_first_kv_distance"] <= spec["first_kv_margin"] / 10
    assert engine.stats()["blocks_done"] > 0 and engine.cache.allocator.n_used == 0


def test_the_replay_of_the_recorded_states_is_the_samples_reading(served):
    cell, _, _, stats, _, _ = served
    spec = cell.traffic["correctness"]
    margin, order, apart, first = replayed(served)
    assert margin <= spec["margin"] / 10 and order <= spec["order_margin"] / 10
    assert apart == pytest.approx(stats["sample_kv_distance"], abs=1e-6)
    assert first == pytest.approx(stats["sample_first_kv_distance"], abs=1e-6) and first <= spec["first_kv_margin"] / 10


def test_the_references_control_fails_the_margins(served):
    """``block_length`` 1 (plain causal): the tokens the passes filled are no longer the
    reference's best, and the cached keys are not the reference's."""
    cell = served[0]
    spec = cell.traffic["correctness"]
    wrong, what = cell.reference.control(cell.config)
    assert "block_length 1" in what and wrong["generation"]["block_length"] == 1
    # the replay still walks the recorded blocks of 4: only the reference's mask changes
    margin, _, apart, first = replayed(served, wrong)
    assert margin > 5 * spec["margin"] and apart > 5 * spec["kv_margin"]
    assert first <= spec["first_kv_margin"]  # the first layer's keys are before any attention: they see no mask


def test_qk_norm_left_out_fails_the_margins(served):
    cell = served[0]
    spec = cell.traffic["correctness"]
    off = dict(cell.config, assumed=dict(cell.config["assumed"], qk_norm=False))
    margin, _, apart, first = replayed(served, off)
    assert margin > 5 * spec["margin"] and apart > 5 * spec["kv_margin"] and first > 5 * spec["first_kv_margin"]


def test_keys_of_a_denoise_pass_kept_in_place_of_the_commit_passes_fail_the_key_margin(served):
    """What an engine that skipped the commit pass would hold for a block: the keys its last denoise
    pass computed, over the mask token where a position was not filled yet."""
    cell, driver, engine, _, _, alone = served
    spec, gen = cell.traffic["correctness"], cell.config["generation"]
    K = gen["block_length"]
    reference = driver.BlockwiseReference(cell, engine.params)
    (p, n), (res, rows, _) = cell.traffic["correctness"]["requests"][0], alone[0]
    t_max = -(-(p + n) // K) * K
    got = driver.replay(reference, res, p, gen, t_max)
    pos, toks, masked = [s for s in res.block_states if s[2].any()][-1]  # the last denoise pass
    seq = np.zeros((t_max,), np.int32)
    seq[:pos] = res.tokens[:pos] if pos <= p else np.concatenate(
        [res.tokens[:p // K * K], np.concatenate([s[1] for s in res.block_states if not s[2].any()])])[:pos]
    seq[pos:pos + K] = toks
    _, (_, denoise) = reference(seq)
    faulty = np.array(rows[:got["rows"]])
    faulty[pos:pos + K] = denoise[pos:pos + K]
    assert driver.distance(rows[:got["rows"]], got["keys"]) <= spec["kv_margin"] / 10
    assert driver.distance(faulty, got["keys"]) > 5 * spec["kv_margin"]


def test_a_key_row_that_is_two_hundredths_off_is_not_correct(monkeypatch):
    cell = manifest.resolve(MAN, CELL, rehearse=True)
    driver, notes = cell.driver, []
    kept = driver.served_keys
    monkeypatch.setattr(driver, "served_keys", lambda *a: 1.02 * kept(*a))
    engine, stats = driver.set_up(cell, seed=3, notes=notes)
    engine.stop()
    assert stats["sample_kv_distance"] == pytest.approx(0.02, rel=1e-3)
    assert stats["sample_first_kv_distance"] == pytest.approx(0.02, rel=1e-3)
    assert len(notes) == 2 and all("keys" in n for n in notes) and stats["sample_margin"] <= 1e-4


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    c = manifest.resolve(MAN, CELL)
    entry = {e["name"]: e for e in MAN["configs"]}[CONFIG]
    assert entry["source"] == c.config["source"] == "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
    assert entry["reduced"] == c.config["reduced"] == ["num_hidden_layers"]
    assert c.config["reduced_from"] == {"num_hidden_layers": 48} and c.config["num_hidden_layers"] == 6
    for key, value in PUBLISHED.items():
        assert c.config[key] == (6 if key == "num_hidden_layers" else value), key
    assert c.config["generation"] == {"block_length": 4, "denoising_steps": 2, "mask_token_id": 151669,
                                      "remasking_strategy": "low_confidence_dynamic", "confidence_threshold": 0.9}
    assumed = c.config["assumed"]
    assert set(assumed["why"]) >= {"generation", "qk_norm", "commit_pass", "logits_shift", "rope_pairing",
                                   "initializer_range"}
    assert c.config["deployment"]["stages"] * c.config["deployment"]["layers_a_stage"] == 48
    assert "no_share_test" in c.config["deployment"]
    # 4.36 B parameters, 8.72 GB in bfloat16, by the builder's own sizes
    from benchmark.lib import costs_block_moe

    d = c.builder.dims(c.config)
    p = costs_block_moe.matmul_params(d)
    layer = p["attn"] + p["router"] + d["experts_held"] * p["expert"] + 2 * d["d_model"] + 2 * d["head_dim"]
    total = d["n_layer"] * layer + 2 * p["head"] + d["d_model"]
    assert round(layer / 1e6, 1) == 623.1 and round(total / 1e9, 2) == 4.36


def test_the_builder_maps_the_file_onto_the_programs_model():
    c = manifest.resolve(MAN, CELL)
    keys = c.builder.model_keys(c.config)
    assert (keys["n_head"], keys["n_query_groups"], keys["head_size"], keys["n_embd"]) == (32, 4, 128, 2048)
    assert (keys["n_expert"], keys["n_expert_per_token"], keys["moe_intermediate_size"]) == (128, 8, 768)
    assert keys["norm_qk"] and keys["padded_vocab_size"] == keys["vocab_size"] == 151936 and keys["block_size"] == 2048
    assert c.builder.kernel_claims(c.config) == {
        p: {"thunder.ragged_mlp": 6, "thunder.paged_chunk_attention": 6} for p in ("block_cfn", "chunk_cfn")}
    with pytest.raises(ValueError):
        c.builder.model_keys(dict(c.config, model_type="qwen3_moe"))
    with pytest.raises(ValueError):
        c.builder.model_keys(dict(c.config, assumed=dict(c.config["assumed"], qk_norm=False)))


def test_the_costs_of_a_pass_are_the_issues_arithmetic():
    from benchmark.lib import costs_block_moe

    c = manifest.resolve(MAN, CELL)
    d = c.builder.dims(c.config)
    # a row's operations without attention's context: 1.30 GFLOP (ISSUE 41)
    assert round(costs_block_moe.flops_per_row(d, 0.0) / 1e9, 2) == 1.30
    moe = costs_block_moe.ragged_experts(2048, 128, 2048, 768)
    assert moe.bytes == 3 * 2048 * 768 * 2 * 128 + 2 * 2048 * 2048 * 2 and moe.flops == 6 * 2048 * 768 * 2048
    attn = costs_block_moe.block_attention(64 * 1000.0, 64, 4, 32, 4, 128)
    assert attn.flops == 4 * 32 * 128 * 4 * 64000 and attn.bytes == 2 * 4 * 64000 * 128 * 2 + 2 * 64 * 4 * 32 * 128 * 2


def check_listing(man):
    """The cell's entries by name: each listed for it alone, moving its `serve_tpot_p50_ms`; the
    cell on its configuration."""
    mine = {m["name"]: m for m in man["per_layer"] if m["name"].startswith("denoise_")}
    assert set(mine) == METRICS
    for m in mine.values():
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tpot_p50_ms"
        assert m["unit"] == "%" if m["name"].endswith("_roofline") or m["name"].endswith("_mfu") else True
    e2e, per_layer = manifest.listed(man, CELL)
    assert {m["name"] for m in per_layer} == METRICS | {"recompiles_in_window"}
    assert {m["name"] for m in e2e} == {"serve_tpot_p50_ms", "setup_s"}
    for other in (w["name"] for w in man["workloads"] if w["name"] != CELL):
        assert not any(m["name"] in mine for m in manifest.listed(man, other)[1])
    by_name = {w["name"]: w for w in man["workloads"]}
    assert by_name[CELL] == {"name": CELL, "config": CONFIG, "traffic": "serve-denoise", "chips": 1,
                             "why": by_name[CELL]["why"]} and len(by_name[CELL]["why"]) <= 200


def test_the_manifest_lists_the_denoise_metrics_for_this_cell_by_name():
    check_listing(MAN)
    cell = manifest.resolve(MAN, CELL)
    for name in METRICS:
        assert callable(cell.reader(name).read)


def test_the_readers_find_nothing_where_the_program_counted_no_pass():
    """On a program without the block passes' counters (the parent commit), and in another
    cell's run, every reader returns None and raises nothing."""
    from benchmark.lib import harness

    cell = manifest.resolve(MAN, CELL, rehearse=True)
    run = harness.Run(cell=cell, device_kind="cpu", chips=1, window_s=3.0, attempted=0, failed=0,
                      end_to_end={}, counters={"serve.decode_steps": 10, "serve.decode_overlapped": 9,
                                               "serve.tokens": 40})
    for name in METRICS:
        assert cell.reader(name).read(run) is None, name
    run.counters.update({"serve.block_passes": 10, "serve.block_slot_passes": 30, "serve.blocks_done": 10,
                         "serve.block_slot_commits": 10})
    assert cell.reader("denoise_tokens_per_pass").read(run) == pytest.approx(4 / 3)
    assert cell.reader("denoise_passes_per_block").read(run) == 3.0


def test_a_rehearsal_reads_what_needs_no_tpu():
    """Untraced: `test_rehearsal.py` rehearses every cell traced, into the one trace directory a cell has."""
    line = last_line(run_cell(["--workload", CELL, "--seed", "2147483777", "--seconds", "3",
                               "--trace", "0", "--rehearse"]))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["rehearsal"]["metrics_read"]) == {"serve_tpot_p50_ms", "setup_s"}
    assert {"sample_margin", "sample_order_margin", "sample_kv_distance", "sample_first_kv_distance", "sample_mask_tokens",
            "sample_alone_vs_batched_differ", "executables_built_in_window"} <= set(line["compared"])
