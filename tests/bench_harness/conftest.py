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
    return _add_entries(root, "dummy-6l", config, "train-b2-dummy", "train_tokens_per_s_per_chip",
                        ["dummy_steps"], "host_clock")


def _add_entries(root, config_name: str, config: dict, traffic: str, moves: str, metrics: list,
                 source: str) -> str:
    """The manifest entries of one new configuration with one cell, which reports the
    end-to-end metric ``moves`` and per-layer ``metrics`` of its own, each appended at the end of
    its list; the cell goes onto the ``workloads`` of ``moves`` and of ``recompiles_in_window``.
    Returns the cell's name."""
    with open(root / "BENCHMARK.json") as f:
        man = json.load(f)
    cell = f"{config_name}.{traffic}"
    man["configs"].append({"name": config_name, "source": config["source"],
                           "file": f"benchmark/configs/{config_name}.json",
                           "reduced": config["reduced"], "why": "a test"})
    man["workloads"].append({"name": cell, "config": config_name, "traffic": traffic,
                             "chips": 1, "why": "a test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] in (moves, "recompiles_in_window"):
            m["workloads"].append(cell)
    man["per_layer"] += [{"name": metric, "unit": "count", "better": "higher", "source": source,
                          "layer": "entry", "moves": moves, "workloads": [cell]} for metric in metrics]
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return cell


@pytest.fixture
def add_served_only_cell():
    return _add_served_only_cell


SERVED_ONLY_BUILDER = '''"""Builder ``dummy_hybrid`` (a test's): a configuration that is only served and whose model
is, as far as the harness can tell, no dense rope GPT: attention in ``attention_layers`` of its
layers and a state update in the others. It wraps the ``litgpt`` builder's model; what it
tells the harness is its own. No ``build_loss_model`` and no ``train_flops_per_token``: it has
no train cell."""
import os

from benchmark.lib import manifest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_litgpt = manifest.load_module(_ROOT, "builders", "litgpt")

dims, reseed, build_serving_model = _litgpt.dims, _litgpt.reseed, _litgpt.build_serving_model


def kernel_claims(config):
    attn = int(config["attention_layers"])
    state = int(config["num_hidden_layers"]) - attn
    return {"decode_cfn": {"thunder.paged_attention": attn, "dummy.state_update": state},
            "chunk_cfn": {"thunder.paged_chunk_attention": attn, "dummy.scan": state}}
'''

SERVED_ONLY_REFERENCE = '''"""Reference ``dummy_hybrid`` (a test's): the ``litgpt`` reference's equations, and a control
of its own, since this configuration has no ``rope_theta`` to spoil."""
import os

from benchmark.lib import manifest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_litgpt = manifest.load_module(_ROOT, "reference", "litgpt")

forward, loss = _litgpt.forward, _litgpt.loss


def control(config):
    wrong = dict(config, use_parallel_residual=not config["use_parallel_residual"])
    return wrong, "the residual in sequence where the model adds both branches to one input"
'''


def _add_served_only_cell(root) -> str:
    """What the PR of a served-only hybrid does: a configuration with a builder, a reference
    and a kernel-class file of its own, a serve cell and no train cell; files and entries only.
    Returns the new cell's name."""
    bench = root / "benchmark"
    with open(bench / "configs" / "pythia-410m.json") as f:
        config = json.load(f)
    assert "rope_theta" not in config
    config.update(builder="dummy_hybrid", source="https://example.org/dummy-hybrid/config.json",
                  num_hidden_layers=6, attention_layers=3, reduced=[], reduced_from={})
    config["rehearsal"]["attention_layers"] = 1
    (bench / "configs" / "dummy-hybrid.json").write_text(json.dumps(config))
    (bench / "builders" / "dummy_hybrid.py").write_text(SERVED_ONLY_BUILDER)
    (bench / "reference" / "dummy_hybrid.py").write_text(SERVED_ONLY_REFERENCE)
    (bench / "kernels" / "dummy_hybrid.json").write_text(json.dumps({
        "what": "the kernels only this builder's model runs",
        "classes": [{"class": "dummy_state_update", "why": "operands: state f32[B,N,D] and one token",
                     "pattern": r"custom-call\(f32\[\d+,\d+,\d+\]\S* %\S+, \w+\[\d+,1,\d+\]\S* %\S+\)"},
                    # would take every Pallas call, were it tried before the classes that were there
                    {"class": "dummy_anything", "why": "a greedy pattern", "pattern": "custom-call"}]}))
    with open(bench / "traffic" / "serve-chat.json") as f:
        traffic = json.load(f)
    traffic["loop"]["rate_rps"] = 2.0
    (bench / "traffic" / "serve-dummy.json").write_text(json.dumps(traffic))
    (bench / "layer_metrics" / "dummy_decode_steps.py").write_text(
        '"""Decode steps in the window."""\n\n\ndef read(run):\n'
        '    return run.stats.get("decode_steps")\n')
    return _add_entries(root, "dummy-hybrid", config, "serve-dummy", "serve_tpot_p50_ms",
                        ["dummy_decode_steps"], "program_counter")


@pytest.fixture
def add_next_cell():
    return _add_next_cell


NEXT_METRICS = [f"next_part_{i:02d}_ms_per_iter" for i in range(20)]


def _add_next_cell(root) -> str:
    """What the PR after the last accepted configuration does: one configuration more, one serve
    cell on it and twenty per-layer entries listed for that cell alone, files and entries only.
    Returns the new cell's name."""
    bench = root / "benchmark"
    with open(bench / "configs" / "mistral-7b-v0.3-l8.json") as f:
        config = json.load(f)
    config["source"] = "https://example.org/next/config.json"
    config["num_hidden_layers"], config["reduced"] = 4, ["num_hidden_layers"]
    config["reduced_from"] = {"num_hidden_layers": 32}
    (bench / "configs" / "next-l4.json").write_text(json.dumps(config))
    with open(bench / "traffic" / "serve-chat.json") as f:
        traffic = json.load(f)
    traffic["loop"]["rate_rps"] = 3.0
    (bench / "traffic" / "serve-next.json").write_text(json.dumps(traffic))
    for name in NEXT_METRICS:
        (bench / "layer_metrics" / f"{name}.py").write_text(
            '"""A part of the decode step, for a test."""\n\n\ndef read(run):\n'
            '    return run.stats.get("part_ms")\n')
    return _add_entries(root, "next-l4", config, "serve-next", "serve_tpot_p50_ms", NEXT_METRICS,
                        "device_trace")
