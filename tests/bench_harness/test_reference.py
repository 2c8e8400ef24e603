"""The plain reference (`benchmark/reference/<builder>.py`) against the system, at each
configuration's `rehearsal` size on the CPU: loss, gradients, and the serving margin test.
On the chip the same comparison runs at the published widths inside every run's set-up.

The cases follow the manifest: loss and gradients for the configurations that have a cell
whose traffic file's `driver` is `train`, the serving margin for those with a `serve` cell.
A configuration that is only served brings no train case, and the control that must fail is
the reference's own (`control(config)`), so a model without rope brings one that fits it."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import loadgen, manifest


def cells_by_driver(man: dict, root: str = manifest.ROOT) -> dict:
    """`{driver: {config: the first of its cells that driver runs}}`, by each traffic file's
    `driver`."""
    out: dict = {}
    for w in man["workloads"]:
        driver = manifest.load_json(root, "traffic", w["traffic"])["driver"]
        out.setdefault(driver, {}).setdefault(w["config"], w["name"])
    return out


MAN = manifest.load_manifest()
BY_DRIVER = cells_by_driver(MAN)
TRAIN_CELL, SERVE_CELL = BY_DRIVER.get("train", {}), BY_DRIVER.get("serve", {})

# float32 on both sides, the same mathematics in another order of summation: agreement to a
# few float32 roundings of a loss near 6. A wrong rope, bias or layout moves it by 1e-2 or more.
F32_LOSS_TOL = 2e-5
# relative l2 distance of a gradient tensor: float32 roundings accumulated through two layers
F32_GRAD_TOL = 2e-4
# bf16 autocast (8-bit mantissa on matmul and attention operands, f32 accumulation): what the
# cells run. Loose enough for bf16, tight enough that float16-free garbage or a dropped term fails.
BF16_LOSS_TOL = 2e-2
BF16_GRAD_TOL = 8e-2


def tiny_cell(name):
    cell = manifest.resolve(MAN, name, rehearse=True)
    cell.traffic["step"]["mesh"] = None     # the reference comparison is about one device
    cell.chips = 1
    return cell


def batch(cell, seed=0):
    spec = cell.traffic["step"]
    gen = cell.driver.batches(seed, 2, int(spec["seq_len"]), cell.config["vocab_size"])
    return next(gen)


def system_loss_and_grads(cell, autocast: bool, x, y):
    """Through the normal entry points: `tt.jit` + `TrainStep`. With plain SGD at lr 1 the
    step's update *is* the gradient: grad = old - new."""
    cell.traffic["step"]["autocast"] = autocast
    cell.traffic["step"]["optimizer"] = {"name": "SGD", "lr": 1.0}
    tm, step, _ = cell.driver.build_step(cell, jax.devices())
    cell.builder.reseed(tm.get_parameters(), 11, cell.config)
    old = {k: np.asarray(p.data) for k, p in tm.get_parameters().items()}
    loss = float(step(jnp.asarray(x), jnp.asarray(y)))
    grads = {k: old[k] - np.asarray(p.data) for k, p in tm.get_parameters().items()}
    return loss, grads, {k: jnp.asarray(v) for k, v in old.items()}


def reference_loss_and_grads(cell, params, x, y):
    ref = cell.reference

    def mean_loss(p):
        return jnp.mean(jnp.stack([ref.loss(cell.config, p, x[i], y[i], prefix="gpt.")
                                   for i in range(x.shape[0])]))

    loss, grads = jax.value_and_grad(mean_loss)(params)
    return float(loss), {k: np.asarray(v) for k, v in grads.items()}


@pytest.mark.parametrize("autocast", [False, True], ids=["f32", "bf16-autocast"])
@pytest.mark.parametrize("config", list(TRAIN_CELL))
def test_loss_and_gradients_agree_with_the_reference(config, autocast):
    cell = tiny_cell(TRAIN_CELL[config])
    x, y = batch(cell)
    loss, grads, params = system_loss_and_grads(cell, autocast, x, y)
    ref_loss, ref_grads = reference_loss_and_grads(cell, params, x, y)
    loss_tol, grad_tol = (BF16_LOSS_TOL, BF16_GRAD_TOL) if autocast else (F32_LOSS_TOL, F32_GRAD_TOL)
    assert abs(loss - ref_loss) < loss_tol
    assert set(grads) == set(ref_grads)
    worst = max(np.linalg.norm(grads[k] - ref_grads[k]) / (np.linalg.norm(ref_grads[k]) + 1e-12)
                for k in grads)
    assert worst < grad_tol, worst
    # every parameter got a gradient that is not zero: nothing is dead in the tiny model
    assert all(np.linalg.norm(g) > 0 for g in ref_grads.values())


def served_sample(cell):
    notes = []
    engine, stats = cell.driver.set_up(cell, seed=3, notes=notes)
    engine.stop()
    return engine, stats, notes


def serving_margin_holds_and_the_control_fails(cell):
    """The serving case, for a resolved (rehearsal) cell of any checkout."""
    engine, stats, notes = served_sample(cell)
    assert notes == [] and stats["sample_margin"] <= cell.traffic["correctness"]["margin"]
    # and the test has teeth: against the reference's own control (a configuration the same
    # weights must not agree with) the tokens the engine chose are no longer the best ones
    ref = cell.reference
    wrong, what = ref.control(cell.config)
    assert wrong != cell.config and what
    p, n = cell.traffic["correctness"]["requests"][-1]
    prompt = loadgen.prompt_tokens(3, 1_000_003, p, cell.config["vocab_size"])
    engine.start()
    try:
        res = engine.submit(prompt, max_new_tokens=n).result(timeout=300)
    finally:
        engine.stop()
    rows = np.arange(n) + p - 1
    for config_used, holds in ((cell.config, True), (wrong, False)):
        logits = np.asarray(ref.forward(config_used, engine.params, res.tokens, rows=rows))
        gap = (logits.max(-1) - logits[np.arange(n), res.new_tokens]).max()
        assert (gap <= cell.traffic["correctness"]["margin"]) == holds, (what, gap)


@pytest.mark.parametrize("config", list(SERVE_CELL))
def test_serving_margin_against_the_reference(config):
    serving_margin_holds_and_the_control_fails(manifest.resolve(MAN, SERVE_CELL[config], rehearse=True))


def test_the_cases_follow_the_manifest_by_driver():
    # the cases of PR 22 are still here (a later PR adds to these and takes none away)
    assert TRAIN_CELL.items() >= {"pythia-410m": "pythia-410m.train-b4-t2048",
                                  "mistral-7b-v0.3-l8": "mistral-7b-v0.3-l8.train-fsdp4-b4-t4096"}.items()
    assert SERVE_CELL.items() >= {"mistral-7b-v0.3-l8": "mistral-7b-v0.3-l8.serve-chat"}.items()
    assert "pythia-410m" not in SERVE_CELL  # it has no serve cell: no serving case, no KeyError
    for driver, cells in BY_DRIVER.items():
        for config, cell in cells.items():
            assert manifest.resolve(MAN, cell).traffic["driver"] == driver


def test_a_served_only_configuration_brings_a_serving_case_and_no_train_case(copy, add_served_only_cell):
    cell_name = add_served_only_cell(copy)
    config = cell_name.split(".")[0]
    man = manifest.load_manifest(str(copy))
    by_driver = cells_by_driver(man, str(copy))
    assert by_driver["serve"][config] == cell_name and config not in by_driver["train"]
    # the configurations that were there keep the cases they had
    assert {d: {c: w for c, w in cells.items() if c != config}
            for d, cells in by_driver.items()} == BY_DRIVER
    cell = manifest.resolve(man, cell_name, root=str(copy), rehearse=True)
    assert "rope_theta" not in cell.config and not hasattr(cell.builder, "build_loss_model")
    assert "rope" not in cell.reference.control(cell.config)[1]
    serving_margin_holds_and_the_control_fails(cell)


def test_partial_rope_in_serving():
    """Pythia's partial rope through the paged engine (no serve cell uses it yet): decode
    through the cache agrees with the reference's full forward."""
    cell = manifest.resolve(MAN, SERVE_CELL["mistral-7b-v0.3-l8"], rehearse=True)
    pythia = manifest.resolve(MAN, TRAIN_CELL["pythia-410m"], rehearse=True)
    cell.config, cell.config_name = pythia.config, pythia.config_name
    engine, stats, notes = served_sample(cell)
    assert notes == [] and stats["sample_margin"] <= cell.traffic["correctness"]["margin"]
