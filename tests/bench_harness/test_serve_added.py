"""The serve cell PR 27 added, `phi-4-mini-flash-reasoning.serve-reasoning`: its traffic names the
driver `serve_added` (the `serve` driver with a correctness sample that also holds the scan state:
`benchmark/drivers/serve_added.py` says why), so the cases `test_reference.py`, `test_phases.py` and
`test_pool_donated.py` draw for the cells of the driver `serve` do not reach it. These do: the
serving margin against the reference and the control that must fail, the state the engine keeps
against the reference's and against a lower precision, the manifest's entries, and what a
rehearsal reads."""
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import loadgen, manifest
from test_reference import served_sample, serving_margin_holds_and_the_control_fails
from test_rehearsal import last_line, run_cell

MAN = manifest.load_manifest()
CELL = "phi-4-mini-flash-reasoning.serve-reasoning"


def test_the_driver_offers_and_measures_as_serve_does_and_judges_a_sample_of_its_own():
    c = manifest.resolve(MAN, CELL)
    driver = c.driver            # a module loaded anew at every read: hold one
    serve = driver.serve
    assert c.traffic["driver"] == "serve_added" and serve.__name__ == "benchmark.drivers.serve"
    for name in ("build_engine", "serve_all", "check_kernels"):
        assert getattr(driver, name) is getattr(serve, name)
    assert driver.PROGRAMS == serve.PROGRAMS
    assert driver.check_sample is not serve.check_sample
    # `set_up` and `run` put it in the place of serve's own for as long as they run, and no longer
    with driver._own_sample():
        assert serve.check_sample is driver.check_sample
    assert serve.check_sample.__module__ == "benchmark.drivers.serve"
    assert {"margin", "state_request", "state_margin", "slow_state_margin"} <= set(c.traffic["correctness"])


def test_the_sample_holds_the_scan_state_to_the_reference_and_a_coarser_state_fails(monkeypatch):
    cell = manifest.resolve(MAN, CELL, rehearse=True)
    spec = cell.traffic["correctness"]
    _, stats, notes = served_sample(cell)
    assert notes == [] and stats["sample_differ"] == 0
    assert stats["sample_state_distance"] <= spec["state_margin"] / 10
    assert stats["sample_slow_state_distance"] <= spec["slow_state_margin"] / 10
    # the lower-precision control: the same engine keeping its scan state in bfloat16 (float32
    # activations here, so both distances show it; under bfloat16 activations only the second)
    import thunder_tpu.models.sambay as sambay

    monkeypatch.setattr(sambay, "STATE_DTYPE", jnp.bfloat16)
    _, stats, notes = served_sample(cell)
    assert stats["sample_slow_state_distance"] > 10 * spec["slow_state_margin"]
    assert any("a state kept coarser than float32" in n for n in notes) and stats["sample_margin"] == 0.0


def test_a_state_that_is_two_hundredths_off_is_not_correct(monkeypatch):
    cell = manifest.resolve(MAN, CELL, rehearse=True)
    driver, notes = cell.driver, []
    kept = driver.served_state
    monkeypatch.setattr(driver, "served_state",
                        lambda engine, layers: {i: 1.02 * h for i, h in kept(engine, layers).items()})
    engine, stats = driver.set_up(cell, seed=3, notes=notes)
    engine.stop()
    assert stats["sample_state_distance"] == pytest.approx(0.02, rel=1e-3)
    assert stats["sample_slow_state_distance"] == pytest.approx(0.02, rel=1e-3)
    assert len(notes) == 2 and stats["sample_margin"] == 0.0 and stats["sample_differ"] == 0


def test_reseeding_frees_the_weights_that_were_there_first():
    # with both sets alive the published model takes 15.4 of a chip's 16 GB (read on the v5e, PR 27)
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


def test_the_reference_in_blocks_is_the_reference():
    cell = manifest.resolve(MAN, CELL, rehearse=True)
    params = cell.builder.seeded_params(
        {n: p.data for n, p in cell.builder.build_serving_model(cell.config, "tiny", jnp.float32)
         .named_parameters()}, 5, cell.config)
    tokens = loadgen.prompt_tokens(5, 0, 90, cell.config["vocab_size"])
    rows = np.arange(40, 90, 7)
    blocks = cell.driver.BlockwiseReference(cell, params)
    logits, states = blocks(tokens, rows, 60)
    whole = np.asarray(cell.reference.forward(cell.config, params, tokens, rows=rows))
    assert np.abs(logits - whole).max() < 1e-5
    # the state after position 60 is the last state of the first 61 tokens
    _, short = blocks(tokens[:61], rows[:1], 60)
    kinds = cell.reference.layer_kinds(cell.config)
    assert sorted(states) == [i for i, k in enumerate(kinds) if k == "mamba"]
    for i, (state, step) in states.items():
        assert state.shape == (cell.config["assumed"]["mamba_d_inner"], cell.config["assumed"]["mamba_d_state"])
        assert step.shape == state.shape[:1] and (step > 0).all()
        np.testing.assert_allclose(state, short[i][0], rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(step, short[i][1], rtol=1e-5)


def test_serving_margin_against_the_reference_and_the_halved_window_fails():
    cell = manifest.resolve(MAN, CELL, rehearse=True)
    assert cell.reference.control(cell.config)[1] == "sliding_window / 2"
    # the sample's last request is longer than the rehearsal's window several times over
    assert cell.traffic["correctness"]["requests"][-1][0] > 4 * cell.config["sliding_window"]
    serving_margin_holds_and_the_control_fails(cell)


def test_the_configuration_is_the_published_one_with_nothing_cut():
    c = manifest.resolve(MAN, CELL)
    entry = next(e for e in MAN["configs"] if e["name"] == c.config_name)
    assert entry["reduced"] == [] and c.config["reduced"] == [] and c.chips == 1
    assert (c.config["num_hidden_layers"], c.config["vocab_size"], c.config["hidden_size"]) == (32, 200064, 2560)
    assert c.builder.layer_counts(c.config) == {"mamba": 9, "window_attn": 8, "full_attn": 1, "gmu": 7,
                                                "cross_attn": 7}
    assert set(c.config["assumed"]["why"]) >= {k for k in c.config["assumed"] if k != "why"}
    assert c.builder.kernel_claims(c.config) == {"decode_cfn": {"thunder.paged_attention": 16},
                                                 "chunk_cfn": {"thunder.paged_chunk_attention": 16}}
    assert not hasattr(c.builder, "build_loss_model")


def test_the_manifest_lists_the_reasoning_metrics_for_this_cell_only():
    mine = {m["name"]: m for m in MAN["per_layer"] if m["name"].startswith("reasoning_")}
    assert len(mine) == 15
    for m in mine.values():
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tpot_p50_ms"
        assert callable(manifest.resolve(MAN, CELL).reader(m["name"]).read)
    listed = {m["name"] for m in manifest.resolve(MAN, CELL).per_layer}
    assert listed == set(mine) | {"recompiles_in_window"}
    assert {m["name"] for m in manifest.resolve(MAN, CELL).end_to_end} == {"serve_tpot_p50_ms", "setup_s"}
    for other in (w["name"] for w in MAN["workloads"] if w["name"] != CELL):
        assert not any(m["name"] in mine for m in manifest.resolve(MAN, other).per_layer)


def test_a_rehearsal_reads_what_needs_no_tpu():
    line = last_line(run_cell(["--workload", CELL, "--seed", "2147483777", "--seconds", "3",
                               "--trace", "1", "--rehearse"]))
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["rehearsal"]["metrics_read"]) >= {
        "recompiles_in_window", "reasoning_decode_iter_ms_p50", "reasoning_batch_occupancy",
        "reasoning_out_tokens_per_s", "reasoning_pool_donated_pct", "reasoning_state_mb_per_seq",
        "reasoning_window_pages_freed_per_iter", "reasoning_idle_ms_per_iter"}
