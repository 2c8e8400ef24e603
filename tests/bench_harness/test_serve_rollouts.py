"""The serve cell PR 31 added, `mistral-small-4-119b-ep4-l6.serve-rollouts`: its traffic names the
driver `serve_rollouts` (the `serve` driver with a correctness sample that also holds the cached
latent rows: `benchmark/drivers/serve_rollouts.py` says why), so the cases `test_reference.py`,
`test_phases.py` and `test_pool_donated.py` draw for the cells of the driver `serve` do not reach
it. These do: the serving margin against the reference and the controls that must fail, the rows
the engine keeps against the reference's and under planted faults, the cut as the configuration
states it, the manifest's entries, and what a rehearsal reads."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import loadgen, manifest
from test_reference import served_sample, serving_margin_holds_and_the_control_fails
from test_rehearsal import last_line, run_cell

MAN = manifest.load_manifest()
CELL = "mistral-small-4-119b-ep4-l6.serve-rollouts"
CONFIG = "mistral-small-4-119b-ep4-l6"
METRICS = {"rollouts_decode_iter_ms_p50", "rollouts_decode_device_ms_per_iter", "rollouts_out_tokens_per_s",
           "rollouts_batch_occupancy", "rollouts_idle_ms_per_iter", "rollouts_prefill_share_pct",
           "rollouts_moe_ms_per_iter", "rollouts_moe_roofline", "rollouts_mla_attn_ms_per_iter",
           "rollouts_mla_attn_roofline",
           "rollouts_xla_ms_per_iter", "rollouts_held_rows_pct", "rollouts_expert_rows_max_over_mean",
           "rollouts_latent_kb_per_token", "rollouts_decode_mfu"}


def test_the_driver_offers_and_measures_as_serve_does_and_judges_a_sample_of_its_own():
    c = manifest.resolve(MAN, CELL)
    driver = c.driver            # a module loaded anew at every read: hold one
    serve = driver.serve
    assert c.traffic["driver"] == "serve_rollouts" and serve.__name__ == "benchmark.drivers.serve"
    for name in ("build_engine", "serve_all", "check_kernels"):
        assert getattr(driver, name) is getattr(serve, name)
    assert driver.PROGRAMS == serve.PROGRAMS and driver.check_sample is not serve.check_sample
    with driver._own_sample():
        assert serve.check_sample is driver.check_sample
    assert serve.check_sample.__module__ == "benchmark.drivers.serve"
    assert {"margin", "first_latent_margin", "latent_margin", "why"} <= set(c.traffic["correctness"])


def test_the_traffic_is_the_issues_letter_for_letter():
    t = manifest.resolve(MAN, CELL).traffic
    assert t["engine"] == {"dtype": "bfloat16", "max_batch": 128, "page_size": 64, "max_seq": 4096,
                           "chunk_tokens": 512, "min_bucket": 128}
    assert t["loop"] == {"kind": "closed", "clients": 128, "preroll_s": 30.0}
    assert t["prompt_len"] == {"dist": "lognormal", "median": 768, "sigma": 0.8, "min": 128, "max": 3072}
    assert t["output_len"] == {"dist": "uniform", "min": 256, "max": 1024}
    # one whole-prompt bucket, one of 2 chunks, two of 4 or more chunks
    chunks = [-(-p // t["engine"]["chunk_tokens"]) for p, _ in t["correctness"]["requests"]]
    assert chunks[0] == 1 and chunks[1] == 2 and min(chunks[2:]) >= 4 and len(chunks) == 4
    assert all(t["prompt_len"]["min"] <= p <= t["prompt_len"]["max"] for p, _ in t["correctness"]["requests"])


def test_the_sample_holds_the_latent_rows_to_the_reference():
    cell = manifest.resolve(MAN, CELL, rehearse=True)
    spec = cell.traffic["correctness"]
    _, stats, notes = served_sample(cell)
    assert notes == [] and stats["sample_differ"] == 0 and stats["sample_latent_padding"] == 0.0
    assert stats["sample_first_latent_distance"] <= spec["first_latent_margin"] / 10
    assert stats["sample_latent_distance"] <= spec["latent_margin"] / 10
    assert sorted(stats["sample_latent_by_layer"]) == list(range(cell.config["num_hidden_layers"]))


def test_a_latent_row_that_is_two_hundredths_off_is_not_correct(monkeypatch):
    cell = manifest.resolve(MAN, CELL, rehearse=True)
    driver, notes = cell.driver, []
    kept = driver.served_rows
    monkeypatch.setattr(driver, "served_rows", lambda *a: 1.02 * kept(*a))
    engine, stats = driver.set_up(cell, seed=3, notes=notes)
    engine.stop()
    assert stats["sample_first_latent_distance"] == pytest.approx(0.02, rel=1e-3)
    assert stats["sample_latent_distance"] == pytest.approx(0.02, rel=1e-3)
    assert len(notes) == 2 and stats["sample_margin"] == 0.0 and stats["sample_differ"] == 0


def test_a_padding_column_that_is_not_zero_is_not_correct(monkeypatch):
    cell = manifest.resolve(MAN, CELL, rehearse=True)
    driver, notes = cell.driver, []
    kept = driver.served_rows

    def dirty(*a):
        rows = kept(*a).copy()
        rows[0, -1] = 0.5
        return rows

    monkeypatch.setattr(driver, "served_rows", dirty)
    engine, stats = driver.set_up(cell, seed=3, notes=notes)
    engine.stop()
    assert stats["sample_latent_padding"] == 0.5 and len(notes) == 1 and "padding column" in notes[0]


def test_an_expert_left_out_is_not_correct(monkeypatch):
    """A held expert whose rows never reach it: the first layer's cached rows are untouched (they
    come before any expert), the last layer's and the chosen tokens are not."""
    import thunder_tpu.models.moe as moe

    cell = manifest.resolve(MAN, CELL, rehearse=True)
    spec = cell.traffic["correctness"]
    real = moe.ragged_experts

    def one_short(xf, idx, w, panels, held, **kw):
        lo, hi = held
        return real(xf, idx, w, tuple(p[1:] for p in panels), (lo + 1, hi), **kw)

    monkeypatch.setattr(moe, "ragged_experts", one_short)
    _, stats, notes = served_sample(cell)
    assert stats["sample_first_latent_distance"] <= spec["first_latent_margin"] / 10
    assert stats["sample_latent_distance"] > 100 * spec["latent_margin"]
    assert any("latent rows" in n for n in notes)


def test_reseeding_frees_the_weights_that_were_there_first():
    # with both sets alive the cut model would take 21.7 of a chip's 16 GB
    cell = manifest.resolve(MAN, CELL, rehearse=True)
    builder = cell.builder
    model = builder.build_serving_model(cell.config, "tiny", jnp.float32)
    parameters = dict(model.named_parameters())
    old = {n: p.data for n, p in parameters.items()}
    builder.reseed(parameters, 7, cell.config)
    assert all(a.is_deleted() for a in old.values())
    assert all(p.data.shape == old[n].shape and p.data.dtype == old[n].dtype and not p.data.is_deleted()
               for n, p in parameters.items())
    again = builder.seeded_params({n: p.data for n, p in parameters.items()}, 7, cell.config)
    assert all(np.array_equal(np.asarray(again[n]), np.asarray(p.data)) for n, p in parameters.items())
    other = builder.seeded_params({n: p.data for n, p in parameters.items()}, 8, cell.config)
    assert not np.array_equal(np.asarray(other["h.0.experts.w_gate"]), np.asarray(again["h.0.experts.w_gate"]))
    # layers differ from each other though one compiled function makes them all; norm gains are one
    assert not np.array_equal(np.asarray(again["h.0.attn.o.weight"]), np.asarray(again["h.1.attn.o.weight"]))
    assert float(np.asarray(again["h.1.norm_2.weight"]).min()) == 1.0
    assert float(np.abs(np.asarray(again["h.0.experts.e_score_correction_bias"])).max()) > 0.0


def test_the_reference_in_blocks_is_the_reference():
    cell = manifest.resolve(MAN, CELL, rehearse=True)
    params = cell.builder.seeded_params(
        {n: p.data for n, p in cell.builder.build_serving_model(cell.config, "tiny", jnp.float32)
         .named_parameters()}, 5, cell.config)
    tokens = loadgen.prompt_tokens(5, 0, 90, cell.config["vocab_size"])
    rows = np.arange(40, 90, 7)
    logits, made = cell.driver.BlockwiseReference(cell, params)(tokens, rows)
    whole = np.asarray(cell.reference.forward(cell.config, params, tokens, rows=rows))
    assert np.abs(logits - whole).max() < 1e-5 and len(made) == cell.config["num_hidden_layers"]
    assert made[-1]["c_kv"].shape == (90, cell.config["kv_lora_rank"])
    assert made[-1]["k_rope"].shape == (90, cell.config["qk_rope_head_dim"])
    # nothing of the program is in it
    with open(cell.reference.__file__) as f:
        assert "thunder_tpu" not in f.read().split('"""', 2)[2]


def test_serving_margin_against_the_reference_and_each_control_fails_five_times_over():
    cell = manifest.resolve(MAN, CELL, rehearse=True)
    ref, margin = cell.reference, cell.traffic["correctness"]["margin"]
    assert ref.control(cell.config)[1] == "num_experts_per_tok / 2"
    serving_margin_holds_and_the_control_fails(cell)
    # by how much: the reference's own control (experts a token) and the rope base
    engine, stats, notes = served_sample(cell)
    p, n = cell.traffic["correctness"]["requests"][-1]
    prompt = loadgen.prompt_tokens(3, 1_000_003, p, cell.config["vocab_size"])
    engine.start()
    try:
        res = engine.submit(prompt, max_new_tokens=n).result(timeout=300)
    finally:
        engine.stop()
    rows = np.arange(n) + p - 1
    rope = dict(cell.config["rope_parameters"], rope_theta=100 * cell.config["rope_parameters"]["rope_theta"])
    for wrong in (ref.control(cell.config)[0], dict(cell.config, rope_parameters=rope)):
        logits = np.asarray(ref.forward(wrong, engine.params, res.tokens, rows=rows))
        assert (logits.max(-1) - logits[np.arange(n), res.new_tokens]).max() > 5 * margin


def test_the_configuration_is_the_published_one_cut_to_one_chip_of_four():
    c = manifest.resolve(MAN, CELL)
    entry = next(e for e in MAN["configs"] if e["name"] == c.config_name)
    cut = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert c.config_name == CONFIG and entry["reduced"] == cut and c.config["reduced"] == cut and c.chips == 1
    assert c.config["reduced_from"] == {"num_hidden_layers": 36, "n_routed_experts": 128, "vocab_size": 131072}
    assert (c.config["num_hidden_layers"], c.config["n_routed_experts"], c.config["vocab_size"]) == (6, 32, 32768)
    assert c.config["experts_held"] == [0, 32]
    # every width as published, and the floors of a cut: four layers, eight experts, an eighth of the rows
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(json.loads(ln) for ln in f if '"Mistral-Small-4-119B-2603"' in ln)["config"]
    assert {k for k, v in published.items() if c.config.get(k) != v} == set(cut)
    assert entry["source"] == c.config["source"] and "Mistral-Small-4-119B-2603" in entry["source"]
    keys = c.builder.model_keys(c.config)
    assert keys["n_routed_experts"] == 128 and keys["experts_held"] == (0, 32) and keys["n_expert_per_token"] == 4
    assert c.config["assumed"]["scoring_func"] == "sigmoid" and keys["block_size"] == 8192
    assert set(c.config["assumed"]["why"]) >= {k for k in c.config["assumed"] if k != "why"}
    assert c.builder.kernel_claims(c.config) == {
        "decode_cfn": {"thunder.ragged_mlp": 6, "thunder.paged_latent_attention": 6},
        "chunk_cfn": {"thunder.ragged_mlp": 6}}
    assert not hasattr(c.builder, "build_loss_model")
    d = c.builder.dims(c.config)
    assert (d["latent_width"], d["latent_row"], d["experts_held"], d["n_routed"]) == (320, 384, 32, 128)
    # the issue's arithmetic: 859.1 M parameters a layer, 5.42 B in all
    from benchmark.lib import costs_latent_moe

    p = costs_latent_moe.matmul_params(d)
    attn = 4096 * 1024 + 1024 * 4096 + 4096 * 320 + 256 * 6144 + 4096 * 4096
    layer = attn + p["router"] + p["shared"] + d["experts_held"] * p["expert"] + 2 * 4096 + 1024 + 256 + 128
    assert round(layer / 1e6, 1) == 859.1 and round((6 * layer + 2 * p["head"] + 4096) / 1e9, 2) == 5.42


def check_listing(man):
    """The cell's entries by name: each listed for it alone, moving its `serve_tpot_p50_ms`; the
    cell on its configuration."""
    mine = {m["name"]: m for m in man["per_layer"] if m["name"].startswith("rollouts_")}
    assert set(mine) == METRICS
    for m in mine.values():
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tpot_p50_ms"
        assert m["unit"] == "%" if m["name"].endswith("_roofline") or m["name"].endswith("_mfu") else True
    e2e, per_layer = manifest.listed(man, CELL)
    assert {m["name"] for m in per_layer} == METRICS | {"recompiles_in_window"}
    assert {m["name"] for m in e2e} == {"serve_tpot_p50_ms", "setup_s"}
    for other in (w["name"] for w in man["workloads"] if w["name"] != CELL):
        assert not any(m["name"] in mine for m in manifest.listed(man, other)[1])
    cell = {w["name"]: w for w in man["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "serve-rollouts", 1)
    assert CONFIG in {c["name"] for c in man["configs"]}


def test_the_manifest_lists_the_rollouts_metrics_for_this_cell_only():
    check_listing(MAN)
    cell = manifest.resolve(MAN, CELL)
    for name in METRICS:
        assert callable(cell.reader(name).read)


def test_the_new_kernel_classes_are_tried_after_the_ones_that_were_there():
    from benchmark.lib import readers

    classes = [c for c, _ in readers.kernel_classes(manifest.ROOT)["classes"]]
    assert classes[-2:] == ["ragged_mlp", "latent_decode"]
    mosaic = 'custom_call_target="tpu_custom_call"'
    latent = ("%mla_attn.3 = bf16[128,32,256]{2,1,0:T(8,128)(2,1)} custom-call(s32[128,64]{1,0} %copy, s32[128]{0} "
              "%l.1, bf16[128,32,384]{2,1,0} %q.1, bf16[8193,64,384]{2,1,0} %p.1), " + mosaic)
    ragged = ("%moe_experts.1 = bf16[1024,4096]{1,0} custom-call(s32[64]{0} %a, s32[1]{0} %b, bf16[1024,4096]{1,0} %c, "
              "bf16[32,4096,2048]{2,1,0} %d, bf16[32,4096,2048]{2,1,0} %e, bf16[32,2048,4096]{2,1,0} %f), " + mosaic)
    paged = ("%x = bf16[48,8,4,128]{3,2,1,0} custom-call(s32[48,64]{1,0} %a, s32[48]{0} %b, bf16[48,8,4,128]{3,2,1,0} %c, "
             "bf16[3073,8,64,128]{3,2,1,0} %d, bf16[3073,8,64,128]{3,2,1,0} %e), " + mosaic)
    assert readers.pallas_class(manifest.ROOT, latent) == "latent_decode"
    assert readers.pallas_class(manifest.ROOT, ragged) == "ragged_mlp"
    assert readers.pallas_class(manifest.ROOT, paged) == "paged_decode"


def test_a_rehearsal_reads_what_needs_no_tpu():
    line = last_line(run_cell(["--workload", CELL, "--seed", "2147483777", "--seconds", "3",
                               "--trace", "1", "--rehearse"]))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["rehearsal"]["metrics_read"]) >= {
        "recompiles_in_window", "rollouts_decode_iter_ms_p50", "rollouts_batch_occupancy",
        "rollouts_out_tokens_per_s", "rollouts_held_rows_pct", "rollouts_expert_rows_max_over_mean",
        "rollouts_latent_kb_per_token", "rollouts_idle_ms_per_iter"}
    assert {"sample_first_latent_distance", "sample_latent_distance", "sample_latent_padding",
            "sample_margin"} <= set(line["compared"])
