"""BENCHMARK.json against the contract, and the harness found by name: every cell resolves
its files, and a configuration, a traffic mix and a per-layer metric added as files (plus
entries in the manifest) are found with no code edited."""
import json
import os
import re

import pytest

from benchmark.lib import manifest

ROOT = manifest.ROOT
MAN = manifest.load_manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
PLAIN_PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# what `reduced` may never name: a width (the depth, `num_hidden_layers`, is no width)
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj\w*)_size$|_dim$|_rank$|head_size|"
                   r"expansion|experts_per_tok|num_experts_per")


def all_names():
    out = [(kind, e["name"]) for kind in ("configs", "workloads", "end_to_end", "per_layer")
           for e in MAN[kind]]
    return out


def test_top_level_keys_and_limits():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "benchmark/run.py"]
    assert MAN["paths"] == ["benchmark", "tests/bench_harness"]
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51
    assert 1 <= len(MAN["configs"]) <= 24 and 2 <= len(MAN["workloads"]) <= 24
    assert 1 <= len(MAN["end_to_end"]) <= 16 and 1 <= len(MAN["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    # a full check of 24 cells has to fit the driver's 43200 s
    assert (2 + 14 * 24) * (MAN["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("kind,name", all_names())
def test_names(kind, name):
    assert NAME.match(name), (kind, name)


def test_names_are_used_once():
    names = [n for _, n in all_names()]
    assert len(names) == len(set(names))


def test_files_under_paths_have_plain_names():
    for path in MAN["paths"]:
        for dirpath, dirnames, filenames in os.walk(os.path.join(ROOT, path)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in filenames:
                if fn.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(dirpath, fn), ROOT)
                assert PLAIN_PATH.match(rel), rel


def test_configs():
    used = {w["config"] for w in MAN["workloads"]}
    files = [c["file"] for c in MAN["configs"]]
    assert len(files) == len(set(files))
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used, f"{c['name']} is used by no cell"
        assert c["source"].startswith("https://") and len(c["why"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in MAN["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            doc = json.load(f)
        assert doc["source"] == c["source"] and doc["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in doc and not WIDTH.search(key), f"{key} may not be reduced"
            assert key in doc["reduced_from"], f"{key}: what was it reduced from?"


def test_workloads():
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))
    configs = {c["name"] for c in MAN["configs"]}
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert w["name"] == f"{w['config']}.{w['traffic']}"
    four = sum(1 for w in MAN["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MAN["workloads"]) // 4)
    assert four == 1  # this benchmark: exactly one cell across chips


def test_metrics():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1 and "workloads" not in e2e["setup_s"]
    for m in MAN["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["better"] in ("higher", "lower")
        assert m["source"] in ("host_clock", "device_trace")
    for m in MAN["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in SOURCES and m["better"] in ("higher", "lower")
        assert m["moves"] in e2e, f"{m['name']} moves {m['moves']}, which is no end-to-end metric"
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        for cell in m.get("workloads", []):
            assert cell in CELLS
            # a per-layer metric is reported only where the metric it moves is
            assert manifest.applies(e2e[m["moves"]], cell), (m["name"], cell)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_everything_by_name(cell):
    c = manifest.resolve(MAN, cell)
    assert c.config["builder"] and c.traffic["driver"]
    assert callable(c.driver.run)
    assert callable(c.builder.dims) and callable(c.builder.reseed)
    assert callable(c.reference.forward) and callable(c.reference.loss)
    assert {m["name"] for m in c.end_to_end} > {"setup_s"}, "setup_s and at least one other"
    assert c.per_layer, "at least one per-layer metric"
    for m in c.per_layer:
        assert callable(c.reader(m["name"]).read), m["name"]
    if c.traffic["driver"] == "train":
        mesh = c.traffic["step"].get("mesh")
        assert (c.chips == 1 and not mesh) or (mesh and eval("*".join(map(str, mesh.values()))) == c.chips)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_sizes_are_laid_over_the_real_ones(cell):
    real, tiny = manifest.resolve(MAN, cell), manifest.resolve(MAN, cell, rehearse=True)
    assert tiny.config["hidden_size"] < real.config["hidden_size"]
    assert tiny.config["model_type"] == real.config["model_type"]
    assert tiny.traffic["driver"] == real.traffic["driver"]
    if "engine" in real.traffic:
        assert tiny.traffic["engine"]["max_seq"] < real.traffic["engine"]["max_seq"]
        assert tiny.traffic["loop"]["kind"] == real.traffic["loop"]["kind"]


def test_every_reader_file_is_listed_and_every_listed_metric_has_a_file():
    listed = {m["name"] for m in MAN["per_layer"]}
    files = {fn[:-3] for fn in os.listdir(os.path.join(ROOT, "benchmark", "layer_metrics"))
             if fn.endswith(".py")}
    assert listed == files


def test_a_later_cell_is_files_and_entries_only(copy, add_dummy_cell):
    cell = add_dummy_cell(copy)
    man = manifest.load_manifest(str(copy))
    c = manifest.resolve(man, cell, root=str(copy))
    assert c.config["num_hidden_layers"] == 6 and c.traffic["step"]["batch"] == 2
    assert c.builder.dims(c.config)["n_layer"] == 6
    assert [m["name"] for m in c.per_layer if m["name"] == "dummy_steps"] == ["dummy_steps"]

    class Run:
        stats = {"steps": 7}

    assert c.reader("dummy_steps").read(Run()) == 7
    # the cells that were there resolve as before
    assert manifest.resolve(man, CELLS[0], root=str(copy)).config["num_hidden_layers"] == 24


def test_unknown_names_are_told_apart(copy):
    with pytest.raises(KeyError, match="no workload"):
        manifest.resolve(MAN, "no-such.cell")
    c = manifest.resolve(MAN, CELLS[0])
    with pytest.raises(FileNotFoundError):
        c.reader("no_such_metric")
