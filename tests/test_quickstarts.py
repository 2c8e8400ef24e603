"""Every quickstart in examples/quickstart runs end-to-end on CPU (the
reference ships runnable notebook examples; these are the scriptable
equivalent and rot loudly here if an API they use drifts)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
QS = os.path.join(REPO, "examples", "quickstart")

# every quickstart runs, each at its tiny config (args select it where the
# script takes one)
SCRIPTS = {
    "pretrain.py": [],
    "interpreter_frontend.py": [],
    "serving_quantized.py": ["int8"],
    "serving_quantized_nf4": None,  # alias row, resolved below
    "continuous_batching.py": [],
    "distributed_fsdp.py": [],
    "gspmd_training.py": [],
    "fp8_training.py": [],
    "hf_llm.py": [],
    "hf_generate.py": ["--tiny"],
}


@pytest.mark.moe
@pytest.mark.slow  # tier-1 straddles its wall budget; the moe lane runs this
def test_quickstart_moe_pretrain():
    """The MoE quickstart trains end-to-end with grouped dispatch and
    prints routing health from the moe.* gauges."""
    path = os.path.join(QS, "moe_pretrain.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    out = subprocess.run([sys.executable, path, "--steps", "3"], env=env,
                         capture_output=True, text=True, timeout=900, cwd=REPO)
    assert out.returncode == 0, f"moe_pretrain.py failed:\n{out.stderr[-1500:]}"
    assert "routing health" in out.stdout


@pytest.mark.parametrize("script", sorted(SCRIPTS))
def test_quickstart_runs(script):
    if script == "serving_quantized_nf4":
        path, args = os.path.join(QS, "serving_quantized.py"), ["nf4"]
    else:
        path, args = os.path.join(QS, script), SCRIPTS[script]
    assert os.path.exists(path), f"{path} missing but listed in README"
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    out = subprocess.run([sys.executable, path, *args], env=env,
                         capture_output=True, text=True, timeout=900, cwd=REPO)
    assert out.returncode == 0, f"{script} failed:\n{out.stderr[-1500:]}"


@pytest.mark.slow
@pytest.mark.dist
def test_quickstart_multiprocess_resilience():
    """The distributed fault-tolerance smoke in the quickstart CI lane: a
    REAL 2-process gloo cluster (spawned inside the script) demonstrates
    lockstep NaN skipping, sharded checkpointing, and bit-identical resume.
    Rides slow+dist so tier-1 stays fast; the quickstart lane runs it with
    ``pytest -m dist tests/test_quickstarts.py``."""
    path = os.path.join(QS, "multiprocess_resilience.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    out = subprocess.run([sys.executable, path], env=env,
                         capture_output=True, text=True, timeout=600, cwd=REPO)
    assert out.returncode == 0, (
        f"multiprocess_resilience.py failed:\n{out.stdout[-800:]}\n"
        f"{out.stderr[-1200:]}")
    assert "bit-identical resume" in out.stdout


@pytest.mark.analysis
@pytest.mark.parametrize("script", [
    "pretrain.py", "continuous_batching.py",
    # the fleet quickstart's ONLY smoke is this checked run (it is not in
    # SCRIPTS above — one subprocess covers both); the serve mark puts the
    # prefix-sharing + chunk/verify programs in the `pytest -m serve` lane
    pytest.param("fleet_serving.py", marks=pytest.mark.serve),
])
def test_quickstart_runs_with_trace_checking(script):
    """The verifier in the quickstarts' CI path: a training and a serving
    quickstart run end-to-end with pass-interposed checking forced on —
    every transform and executor pass verifies with zero violations (a
    violation raises, failing the subprocess)."""
    path = os.path.join(QS, script)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["TT_CHECK_TRACES"] = "1"
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    out = subprocess.run([sys.executable, path], env=env,
                         capture_output=True, text=True, timeout=900, cwd=REPO)
    assert out.returncode == 0, (
        f"{script} under TT_CHECK_TRACES=1 failed:\n{out.stderr[-1500:]}")


def test_chip_smoke_refuses_without_a_tpu(tmp_path):
    """chip_smoke.py's contract off the chip: with no TPU it refuses to run —
    non-zero exit, the platform it found named, no result line — also from a
    directory that holds the script and nothing else of the repo. What it
    does on a TPU is the chip run itself (README, "Testing")."""
    import json
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode not in (0, None), out.stdout
    assert "platform: cpu" in out.stdout and "refusing to run" in out.stdout
    with pytest.raises(json.JSONDecodeError):
        json.loads(out.stdout.strip().splitlines()[-1])
