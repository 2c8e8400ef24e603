"""The plain reference (`benchmark/reference/litgpt.py`) against the system, at each
configuration's `rehearsal` size on the CPU: loss, gradients, and the serving margin test.
On the chip the same comparison runs at the published widths inside every run's set-up."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.lib import loadgen, manifest

MAN = manifest.load_manifest()
CONFIGS = [c["name"] for c in MAN["configs"]]
# the first train cell and the first serve cell of each configuration
TRAIN_CELL = {w["config"]: w["name"] for w in reversed(MAN["workloads"]) if ".train-" in w["name"]}
SERVE_CELL = {w["config"]: w["name"] for w in reversed(MAN["workloads"]) if ".serve-" in w["name"]}

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
@pytest.mark.parametrize("config", CONFIGS)
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


@pytest.mark.parametrize("config", [c for c in CONFIGS if c in SERVE_CELL])
def test_serving_margin_against_the_reference(config):
    cell = manifest.resolve(MAN, SERVE_CELL[config], rehearse=True)
    engine, stats, notes = served_sample(cell)
    assert notes == [] and stats["sample_margin"] <= cell.traffic["correctness"]["margin"]
    # and the test has teeth: against a reference with another rope base the tokens the
    # engine chose are no longer the reference's best ones
    wrong = manifest.merged(cell.config, {"rope_theta": cell.config["rope_theta"] / 100.0})
    ref = cell.reference
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
        assert (gap <= cell.traffic["correctness"]["margin"]) == holds, gap


def test_partial_rope_in_serving():
    """Pythia's partial rope through the paged engine (no serve cell uses it yet): decode
    through the cache agrees with the reference's full forward."""
    cell = manifest.resolve(MAN, SERVE_CELL["mistral-7b-v0.3-l8"], rehearse=True)
    pythia = manifest.resolve(MAN, TRAIN_CELL["pythia-410m"], rehearse=True)
    cell.config, cell.config_name = pythia.config, pythia.config_name
    engine, stats, notes = served_sample(cell)
    assert notes == [] and stats["sample_margin"] <= cell.traffic["correctness"]["margin"]
