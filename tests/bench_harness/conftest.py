"""The benchmark's own tests (listed under `paths` in BENCHMARK.json). They import the
harness as the package `benchmark` from the root of the checkout."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@pytest.fixture
def copy(tmp_path):
    """BENCHMARK.json and benchmark/ in a directory of their own."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


@pytest.fixture
def add_dummy_cell():
    return _add_dummy_cell


def _add_dummy_cell(root) -> str:
    """What a later PR does: files of its own and entries in the manifest, no edit of a file
    that is there. Returns the new cell's name."""
    bench = root / "benchmark"
    with open(bench / "configs" / "pythia-410m.json") as f:
        config = json.load(f)
    config["source"] = "https://example.org/dummy/config.json"
    config["num_hidden_layers"], config["reduced"] = 6, ["num_hidden_layers"]
    config["reduced_from"] = {"num_hidden_layers": 24}
    (bench / "configs" / "dummy-6l.json").write_text(json.dumps(config))
    with open(bench / "traffic" / "train-b4-t2048.json") as f:
        traffic = json.load(f)
    traffic["step"]["batch"] = 2
    traffic["rehearsal"]["step"] = {"batch": 1, "seq_len": 64}
    (bench / "traffic" / "train-b2-dummy.json").write_text(json.dumps(traffic))
    (bench / "layer_metrics" / "dummy_steps.py").write_text(
        '"""Steps completed in the window."""\n\n\ndef read(run):\n    return run.stats.get("steps")\n')
    with open(root / "BENCHMARK.json") as f:
        man = json.load(f)
    cell = "dummy-6l.train-b2-dummy"
    man["configs"].append({"name": "dummy-6l", "source": config["source"],
                           "file": "benchmark/configs/dummy-6l.json",
                           "reduced": ["num_hidden_layers"], "why": "a test"})
    man["workloads"].append({"name": cell, "config": "dummy-6l", "traffic": "train-b2-dummy",
                             "chips": 1, "why": "a test"})
    for m in man["end_to_end"]:
        if m["name"] == "train_tokens_per_s_per_chip":
            m["workloads"].append(cell)
    man["per_layer"].append({"name": "dummy_steps", "unit": "count", "better": "higher",
                             "source": "host_clock", "layer": "entry",
                             "moves": "train_tokens_per_s_per_chip", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return cell
