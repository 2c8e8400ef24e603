"""`benchmark/run.py` end to end on the CPU, at each cell's `rehearsal` sizes: both drivers,
every cell, traced and not. A rehearsal reports counts, `correct` and the names of the metrics
it could read — never a time or a rate — and says `platform: cpu`. Without `--rehearse` and
without a TPU the command exits non-zero and prints no result line."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import manifest

ROOT = manifest.ROOT
MAN = manifest.load_manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
# by the traffic file's driver, not by what a cell happens to be called
DRIVER = {c: manifest.resolve(MAN, c).traffic["driver"] for c in CELLS}
TRAIN = [c for c in CELLS if DRIVER[c] == "train"]
SERVE = [c for c in CELLS if DRIVER[c] == "serve"]


def run_cell(args, root=ROOT, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
                          capture_output=True, text=True, timeout=timeout, env=env, cwd=root)


def last_line(proc) -> dict:
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


# traced in every cell; untraced once for each driver
CASES = [(c, 1) for c in CELLS] + [(TRAIN[0], 0), (SERVE[0], 0)]


@pytest.mark.parametrize("cell,trace", CASES)
def test_cell_runs_end_to_end_on_the_cpu(cell, trace):
    proc = run_cell(["--workload", cell, "--seed", "5", "--seconds", "3",
                     "--trace", str(trace), "--rehearse"])
    line = last_line(proc)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device", "rehearsal", "compared"}
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    # each number that decided `correct` beside its limit: last in the line, last on stderr
    assert list(line)[-1] == "compared" and line["compared"]
    assert {"value", "rule", "limit"} == set(next(iter(line["compared"].values())))
    tail = proc.stderr.strip().splitlines()[-len(line["compared"]):]
    assert [ln.split()[:3] for ln in tail] == [["bench:", "compared", n + ":"] for n in line["compared"]]
    # no number from a CPU run under a metric's name, and no device time either
    assert line["metrics"] == {}
    assert line["device"]["platform"] == "cpu" and "busy_s" not in line["device"]
    assert line["rehearsal"]["compiles_in_window"] == 0
    read = set(line["rehearsal"]["metrics_read"])
    c = manifest.resolve(MAN, cell)
    if trace:
        # what needs no TPU to be read: the harness's spans, the program's spans and counters
        want = {"recompiles_in_window"}
        # train_step_xla_gib on both roads: the distributed one keeps no AOT executable
        want |= {"train_host_ms_per_step", "train_xla_ms_per_step",
                 "train_step_xla_gib"} if cell in TRAIN else set()
        want |= {n for n in ("chat_ttft_p50_ms", "chat_tbot_p50_ms", "chat_decode_iter_ms_p50",
                             "chat_batch_occupancy", "chat_loadgen_late_ms_p99",
                             "longprompt_ttft_p50_ms", "longprompt_out_tokens_per_s")
                 if n in {m["name"] for m in c.per_layer}}
        assert want <= read, sorted(want - read)
        assert read <= {m["name"] for m in c.per_layer}
        assert line["rehearsal"]["breakdown_read"] == ["device_ops", "idle_gaps"]
    else:
        assert read == {m["name"] for m in c.end_to_end}


def test_without_a_tpu_it_refuses_and_prints_no_result():
    proc = run_cell(["--workload", CELLS[0], "--seed", "0", "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert "refusing to measure" in proc.stdout
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_an_unknown_cell_fails_and_prints_no_result():
    proc = run_cell(["--workload", "no-such.cell", "--rehearse"])
    assert proc.returncode != 0
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_a_cell_added_as_files_runs_with_no_code_edited(copy, add_dummy_cell):
    cell = add_dummy_cell(copy)
    line = last_line(run_cell(["--workload", cell, "--seed", "1", "--seconds", "2",
                               "--trace", "1", "--rehearse"], root=str(copy)))
    assert line["correct"] is True and line["attempted"] > 0
    assert "dummy_steps" in line["rehearsal"]["metrics_read"]


def test_the_sweep_tool_judges_rates():
    sys.path.insert(0, os.path.join(ROOT, "benchmark", "tools"))
    try:
        import sweep
    finally:
        sys.path.pop(0)
    from benchmark.lib.loadgen import Record

    def rec(i, due, done):
        return Record(i, due, 10, 5, submitted=due, done=done, ok=True, ttft_s=0.05,
                      tbot_s=0.01, n_new=5)

    keeping_up = [rec(i, 0.5 * i, 0.5 * i + 1.0) for i in range(20)]
    row = sweep.judge(keeping_up, 10.0)
    assert row["sustained"] and row["backlog_middle"] == 2 and row["backlog_end"] == 1
    falling_behind = [rec(i, 0.5 * i, 0.5 * i + 1.0 + 0.6 * i) for i in range(20)]
    row = sweep.judge(falling_behind, 10.0)
    assert not row["sustained"] and row["backlog_end"] > row["backlog_middle"]
