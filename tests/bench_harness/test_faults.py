"""A run with the timed path broken underneath comes out not `correct`. The test skips
`run.py`'s look for a chip and drives the rest of a run (the driver's own `run`, at the cell's
rehearsal size on the CPU) with a fault planted in the program."""
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from benchmark.lib import harness, manifest
from benchmark.run import Env

MAN = manifest.load_manifest()


def drive(cell_name: str, tmp_path, seed: int = 2147483777, seconds: float = 2.0) -> harness.Run:
    cell = manifest.resolve(MAN, cell_name, rehearse=True)
    opts = SimpleNamespace(seed=seed, seconds=seconds, trace=0, rehearse=True)
    env = Env(devices=jax.devices(), watch=harness.CompileWatch(), spans=harness.Spans(),
              t_start=time.perf_counter(), out_dir=str(tmp_path))
    return cell.driver.run(cell, opts, env)


def test_a_token_altered_where_it_is_produced_is_not_correct(tmp_path, monkeypatch):
    from thunder_tpu.serving import scheduler

    def second_best(logits, seeds, pos, temps):
        return jnp.argsort(logits, axis=-1)[:, -2].astype(jnp.int32)

    sound = drive("mistral-7b-v0.3-l8.serve-chat", tmp_path)
    assert sound.correct and sound.compared["sample_margin"][0] <= sound.compared["sample_margin"][2]
    # the same requests give the same wrong tokens alone and batched: only the reference sees it
    monkeypatch.setattr(scheduler, "_sample_tokens", second_best)
    broken = drive("mistral-7b-v0.3-l8.serve-chat", tmp_path)
    value, rule, limit = broken.compared["sample_margin"]
    assert not broken.correct and rule == "<=" and value > 100 * limit
    assert broken.compared["sample_alone_vs_batched_differ"] == (0, "==", 0)
    assert any("below the reference's top logit" in note for note in broken.notes)
