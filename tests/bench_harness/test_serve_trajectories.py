"""The serve cell PR 38 added, `longcat-flash-omni-ep32-l4.serve-trajectories`: LongCat-Flash's
text decoder as one chip of an expert-parallel 32, served by the driver `serve_rollouts` as it is
(every half of a double layer is a served layer with its own latent pool, so the traffic file is
data). These hold what `test_reference.py`'s cases, drawn for the driver `serve`, do not reach: the
serving margin against the reference and the control that must fail (the identity experts adding
nothing), the rows every pool keeps against the reference's and under a planted fault, the cut as
the configuration states it with its arithmetic, the manifest's entries, and what a rehearsal reads."""
import json

import jax.numpy as jnp
import numpy as np

from benchmark.lib import costs_shortcut_moe, loadgen, manifest
from test_reference import served_sample, serving_margin_holds_and_the_control_fails
from test_rehearsal import last_line, run_cell

MAN = manifest.load_manifest()
CELL = "longcat-flash-omni-ep32-l4.serve-trajectories"
CONFIG = "longcat-flash-omni-ep32-l4"
METRICS = ["trajectories_decode_iter_ms_p50", "trajectories_decode_device_ms_per_iter", "trajectories_moe_ms_per_iter",
           "trajectories_moe_roofline", "trajectories_mla_attn_ms_per_iter", "trajectories_mla_attn_roofline",
           "trajectories_dense_ffn_ms_per_iter", "trajectories_xla_ms_per_iter", "trajectories_zero_rows_pct",
           "trajectories_held_rows_pct", "trajectories_expert_rows_max_over_mean", "trajectories_prefill_share_pct",
           "trajectories_batch_occupancy", "trajectories_idle_ms_per_iter", "trajectories_page_pool_peak_pct",
           "trajectories_latent_kb_per_token", "trajectories_decode_mfu"]


def test_the_traffic_is_the_issues_letter_for_letter_and_the_driver_is_the_one_that_was_there():
    t = manifest.resolve(MAN, CELL).traffic
    assert t["driver"] == "serve_rollouts"
    assert t["engine"] == {"dtype": "bfloat16", "max_batch": 256, "page_size": 64, "max_seq": 2304,
                           "chunk_tokens": 512, "min_bucket": 128, "n_pages": 4609}
    assert t["loop"] == {"kind": "closed", "clients": 256, "preroll_s": 30.0}
    assert t["prompt_len"] == {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 64, "max": 1024}
    assert t["output_len"] == {"dist": "uniform", "min": 256, "max": 1280}
    assert t["prompt_len"]["max"] + t["output_len"]["max"] == t["engine"]["max_seq"]
    # half residency: a pool of half the pages 256 sequences of 2,304 positions would take
    assert t["engine"]["n_pages"] == 1 + 256 * (2304 // 64) // 2
    # whole-prompt buckets and prompts of two chunks (the longest prompt the traffic draws is two)
    chunks = [-(-p // t["engine"]["chunk_tokens"]) for p, _ in t["correctness"]["requests"]]
    assert sorted(chunks) == [1, 1, 2, 2] and len(chunks) == 4
    assert all(t["prompt_len"]["min"] <= p <= t["prompt_len"]["max"] for p, _ in t["correctness"]["requests"])
    assert {"margin", "first_latent_margin", "latent_margin", "why"} <= set(t["correctness"])


def test_the_configuration_is_the_published_one_cut_to_one_chip_of_32():
    c = manifest.resolve(MAN, CELL)
    entry = next(e for e in MAN["configs"] if e["name"] == c.config_name)
    cut = ["num_layers", "n_routed_experts", "vocab_size"]
    assert c.config_name == CONFIG and entry["reduced"] == cut and c.config["reduced"] == cut and c.chips == 1
    assert c.config["reduced_from"] == {"num_layers": 28, "n_routed_experts": 512, "vocab_size": 131072,
                                        "num_hidden_layers": 56}
    assert (c.config["num_layers"], c.config["n_routed_experts"], c.config["vocab_size"]) == (4, 16, 16384)
    assert c.config["experts_held"] == [0, 16] and c.config["num_hidden_layers"] == 8
    # every width as published, and the floors of a cut: four layers, eight experts, an eighth of the rows
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(json.loads(ln) for ln in f if '"name": "LongCat-Flash-Omni"' in ln)
    assert {k for k, v in row["config"].items() if c.config.get(k) != v} == set(cut)
    assert entry["source"] == c.config["source"] == row["source_url"]
    assert set(c.config["assumed"]["why"]) >= {k for k in c.config["assumed"] if k != "why"}
    keys = c.builder.model_keys(c.config)
    assert (keys["n_routed_experts"], keys["n_zero_experts"], keys["experts_held"]) == (512, 256, (0, 16))
    assert (keys["n_expert_per_token"], keys["routed_scaling_factor"], keys["n_layer"]) == (12, 6.0, 4)
    assert keys["block_size"] == 4096 >= manifest.resolve(MAN, CELL).traffic["engine"]["max_seq"]
    assert c.builder.kernel_claims(c.config) == {
        "decode_cfn": {"thunder.ragged_mlp": 4, "thunder.paged_latent_attention": 8},
        "chunk_cfn": {"thunder.ragged_mlp": 4}}
    assert not hasattr(c.builder, "build_loss_model") and c.config["model_type"] == "longcat_flash"
    d = c.builder.dims(c.config)
    assert (d["latent_width"], d["latent_row"], d["experts_held"], d["n_routed"], d["n_zero"]) == (576, 640, 16, 512, 256)
    assert (d["n_layer"], d["n_expert_layers"]) == (8, 4)
    # the issue's arithmetic: 638.8 M a double layer outside its experts, 1,242.8 M with the 16 held, 5.17 B in all
    p = costs_shortcut_moe.matmul_params(d)
    assert p["attn"] == 6144 * 1536 + 1536 * 12288 + 6144 * 576 + 512 * 16384 + 8192 * 6144
    outside = 2 * p["attn"] + 2 * p["ffn"] + p["router"]
    layer = outside + d["experts_held"] * p["expert"]
    assert round(outside / 1e6, 1) == 638.8 and round(layer / 1e6, 1) == 1242.8
    norms = 2 * (2 * 6144 + 1536 + 512) + 768      # a double layer's norm gains and its selection bias
    assert round((4 * (layer + norms) + 2 * p["head"] + 6144) / 1e9, 2) == 5.17
    # an identity expert costs a token nothing: a token with none of its experts here pays the dense path only
    none = costs_shortcut_moe.decode_flops_per_token(d, 0.0, 0.0)
    assert none == 2.0 * (4 * outside + p["head"])
    assert costs_shortcut_moe.decode_flops_per_token(d, 0.0, 1.0) - none == 2.0 * 4 * p["expert"]


def test_the_model_the_builder_makes_has_as_many_parameters_as_the_file_says():
    cell = manifest.resolve(MAN, CELL, rehearse=True)
    model = cell.builder.build_serving_model(cell.config, "tiny", jnp.float32)
    d = cell.builder.dims(cell.config)
    p = costs_shortcut_moe.matmul_params(d)
    dm, q, r = d["d_model"], d["q_rank"], d["kv_rank"]
    layer = (2 * p["attn"] + 2 * p["ffn"] + p["router"] + d["experts_held"] * p["expert"]
             + 2 * (2 * dm + q + r) + d["n_routed"] + d["n_zero"])
    assert sum(int(np.prod(v.data.shape)) for _, v in model.named_parameters()) == \
        d["n_expert_layers"] * layer + 2 * p["head"] + dm
    # weights from the seed through the latent builder's maker: both kinds of half, bias and norm gains
    parameters = dict(model.named_parameters())
    cell.builder.reseed(parameters, 7, cell.config)
    assert "h.0.experts.w_gate" in parameters and "h.1.experts.w_gate" not in parameters
    assert float(np.asarray(parameters["h.1.norm_2.weight"].data).min()) == 1.0
    assert float(np.abs(np.asarray(parameters["h.2.experts.e_score_correction_bias"].data)).max()) > 0.0
    assert not np.array_equal(np.asarray(parameters["h.1.mlp.up.weight"].data), np.asarray(parameters["h.3.mlp.up.weight"].data))


def check_listing(man):
    """The cell's entries by name: each listed for it alone, moving its `serve_tpot_p50_ms`; the
    cell on its configuration."""
    mine = {m["name"]: m for m in man["per_layer"] if m["name"].startswith("trajectories_")}
    assert set(mine) == set(METRICS)
    for m in mine.values():
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tpot_p50_ms"
        assert m["unit"] == "%" if m["name"].endswith("_roofline") or m["name"].endswith("_mfu") else True
        assert m["layer"] in {"entry", "executors", "kernels", "serving_state", "device"}
    e2e, per_layer = manifest.listed(man, CELL)
    assert {m["name"] for m in per_layer} == set(METRICS) | {"recompiles_in_window"}
    assert {m["name"] for m in e2e} == {"serve_tpot_p50_ms", "setup_s"}
    for other in (w["name"] for w in man["workloads"] if w["name"] != CELL):
        assert not any(m["name"] in mine for m in manifest.listed(man, other)[1])
    cell = {w["name"]: w for w in man["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "serve-trajectories", 1)


def test_the_manifest_lists_the_trajectories_metrics_for_this_cell_only():
    check_listing(MAN)
    for name in METRICS:
        assert callable(manifest.resolve(MAN, CELL).reader(name).read)
    # no kernel class of its own: the two kernels are recognised by the patterns that were there
    from benchmark.lib import readers

    mosaic = 'custom_call_target="tpu_custom_call"'
    latent = ("%mla_attn.3 = bf16[256,64,512]{2,1,0:T(8,128)(2,1)} custom-call(s32[256,36]{1,0} %copy, s32[256]{0} "
              "%l.1, bf16[256,64,640]{2,1,0} %q.1, bf16[4609,64,640]{2,1,0} %p.1), " + mosaic)
    ragged = ("%moe_experts.1 = bf16[3328,6144]{1,0} custom-call(s32[208]{0} %a, s32[1]{0} %b, bf16[3328,6144]{1,0} %c, "
              "bf16[16,6144,2048]{2,1,0} %d, bf16[16,6144,2048]{2,1,0} %e, bf16[16,2048,6144]{2,1,0} %f), " + mosaic)
    assert readers.pallas_class(manifest.ROOT, latent) == "latent_decode"
    assert readers.pallas_class(manifest.ROOT, ragged) == "ragged_mlp"


def test_the_sample_holds_every_pools_rows_to_the_reference_and_the_control_fails():
    cell = manifest.resolve(MAN, CELL, rehearse=True)
    spec = cell.traffic["correctness"]
    assert cell.reference.control(cell.config)[1].startswith("zero_expert_type none")
    serving_margin_holds_and_the_control_fails(cell)
    _, stats, notes = served_sample(cell)
    assert notes == [] and stats["sample_differ"] == 0 and stats["sample_latent_padding"] == 0.0
    assert stats["sample_first_latent_distance"] <= spec["first_latent_margin"] / 10
    assert stats["sample_latent_distance"] <= spec["latent_margin"] / 10
    # a pool a HALF: twice the double layers
    assert sorted(stats["sample_latent_by_layer"]) == list(range(2 * cell.config["num_layers"]))
    assert max(stats["sample_latent_by_layer"].values()) <= spec["latent_margin"] / 10


def test_the_identity_part_left_out_is_not_correct(monkeypatch):
    """A chip that forgets the zero-compute experts' part: the first pool's rows are untouched (they
    come before any expert layer), the last pool's and the chosen tokens are not."""
    import thunder_tpu.models.moe as moe
    import thunder_tpu.models.shortcut_moe as shortcut_moe

    class Forgets(moe.HeldExperts):
        def forward(self, x, *routing):
            kept, self.n_zero = self.n_zero, 0      # the router keeps its width; the part is not added
            try:
                return super().forward(x, *routing)
            finally:
                self.n_zero = kept

    monkeypatch.setattr(shortcut_moe, "HeldExperts", Forgets)
    cell = manifest.resolve(MAN, CELL, rehearse=True)
    spec = cell.traffic["correctness"]
    _, stats, notes = served_sample(cell)
    assert stats["sample_first_latent_distance"] <= spec["first_latent_margin"] / 10
    assert stats["sample_latent_distance"] > 100 * spec["latent_margin"]
    assert stats["sample_margin"] > spec["margin"] and any("latent rows" in n for n in notes)


def test_the_reference_in_blocks_is_the_reference_and_carries_the_shortcut_between_halves():
    cell = manifest.resolve(MAN, CELL, rehearse=True)
    params = cell.builder.seeded_params(
        {n: p.data for n, p in cell.builder.build_serving_model(cell.config, "tiny", jnp.float32)
         .named_parameters()}, 5, cell.config)
    tokens = loadgen.prompt_tokens(5, 0, 90, cell.config["vocab_size"])
    rows = np.arange(40, 90, 7)
    logits, made = cell.driver.BlockwiseReference(cell, params)(tokens, rows)
    ref = cell.reference
    whole = np.asarray(ref.forward(cell.config, params, tokens, rows=rows))
    assert np.abs(logits - whole).max() < 1e-5 and len(made) == cell.config["num_hidden_layers"] == 4
    assert made[-1]["c_kv"].shape == (90, cell.config["kv_lora_rank"])
    assert made[-1]["k_rope"].shape == (90, cell.config["qk_rope_head_dim"])
    # the carried rows: the first half leaves the experts' result on the shortcut, the second clears it
    x = ref.embed(cell.config, params, tokens)
    assert x.shape == (90, 2, cell.config["hidden_size"]) and float(np.abs(x[:, 1]).max()) == 0.0
    x, _ = ref.layer(cell.config, ref.layer_params(params, 0), x)
    assert float(np.abs(x[:, 1]).max()) > 0.01
    x, _ = ref.layer(cell.config, ref.layer_params(params, 1), x)
    assert float(np.abs(x[:, 1]).max()) == 0.0
    # nothing of the program is in it
    with open(ref.__file__) as f:
        assert "thunder_tpu" not in f.read().split('"""', 2)[2]


def test_a_rehearsal_reads_what_needs_no_tpu():
    line = last_line(run_cell(["--workload", CELL, "--seed", "2147483777", "--seconds", "3",
                               "--trace", "1", "--rehearse"]))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["rehearsal"]["metrics_read"]) >= {
        "recompiles_in_window", "trajectories_decode_iter_ms_p50", "trajectories_batch_occupancy",
        "trajectories_zero_rows_pct", "trajectories_held_rows_pct", "trajectories_expert_rows_max_over_mean",
        "trajectories_latent_kb_per_token", "trajectories_idle_ms_per_iter", "trajectories_page_pool_peak_pct",
        "trajectories_dense_ffn_ms_per_iter"}
    assert {"sample_first_latent_distance", "sample_latent_distance", "sample_latent_padding",
            "sample_margin"} <= set(line["compared"])
