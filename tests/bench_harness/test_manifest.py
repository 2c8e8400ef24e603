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


def all_names(man=MAN):
    return [(kind, e["name"]) for kind in ("configs", "workloads", "end_to_end", "per_layer")
            for e in man[kind]]


# what holds of a whole manifest and the files it names: each a function of the manifest, so that
# `test_next_cell.py` can hold a longer one to the same rules


def top_level_keys_and_limits(man, root=ROOT):
    assert set(man) == {"command", "paths", "run_seconds", "configs", "workloads",
                        "end_to_end", "per_layer"}
    assert man["command"] == ["python3", "benchmark/run.py"]
    assert man["paths"] == ["benchmark", "tests/bench_harness"]
    assert isinstance(man["run_seconds"], int) and 1 <= man["run_seconds"] <= 51
    assert 1 <= len(man["configs"]) <= 24 and 1 <= len(man["workloads"]) <= 24
    assert 1 <= len(man["end_to_end"]) <= 16 and 1 <= len(man["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 * 1024
    # a full check of 24 cells has to fit the driver's 43200 s
    assert (2 + 14 * 24) * (man["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def names_are_used_once(man):
    names = [n for _, n in all_names(man)]
    assert len(names) == len(set(names))


def files_under_paths_have_plain_names(man, root=ROOT):
    for path in man["paths"]:
        for dirpath, dirnames, filenames in os.walk(os.path.join(root, path)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in filenames:
                if fn.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(dirpath, fn), root)
                assert PLAIN_PATH.match(rel), rel


def configs_hold(man, root=ROOT):
    used = {w["config"] for w in man["workloads"]}
    files = [c["file"] for c in man["configs"]]
    assert len(files) == len(set(files))
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used, f"{c['name']} is used by no cell"
        assert c["source"].startswith("https://") and len(c["why"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in man["paths"])
        with open(os.path.join(root, c["file"])) as f:
            doc = json.load(f)
        assert doc["source"] == c["source"] and doc["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in doc and not WIDTH.search(key), f"{key} may not be reduced"
            assert key in doc["reduced_from"], f"{key}: what was it reduced from?"


def workloads_hold(man):
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(pairs) == len(set(pairs))
    configs = {c["name"] for c in man["configs"]}
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert w["name"] == f"{w['config']}.{w['traffic']}"
    four = sum(1 for w in man["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(man["workloads"]) // 4)


def metrics_hold(man):
    cells = [w["name"] for w in man["workloads"]]
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.1 and "workloads" not in e2e["setup_s"]
    for m in man["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["better"] in ("higher", "lower")
        assert m["source"] in ("host_clock", "device_trace")
    for m in man["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in SOURCES and m["better"] in ("higher", "lower")
        assert m["moves"] in e2e, f"{m['name']} moves {m['moves']}, which is no end-to-end metric"
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        # every entry names its cells, so that a cell added later reports only what it lists
        assert m.get("workloads"), f"{m['name']} names no cells"
        for cell in m["workloads"]:
            assert cell in cells
            # a per-layer metric is reported only where the metric it moves is
            assert manifest.applies(e2e[m["moves"]], cell), (m["name"], cell)
    # the one that every cell reads: a later cell is appended to its list
    assert set({m["name"]: m for m in man["per_layer"]}["recompiles_in_window"]["workloads"]) == set(cells)


def cell_resolves_everything_by_name(man, cell, root=ROOT):
    c = manifest.resolve(man, cell, root=root)
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


def every_reader_file_is_listed_and_every_listed_metric_has_a_file(man, root=ROOT):
    listed = {m["name"] for m in man["per_layer"]}
    files = {fn[:-3] for fn in os.listdir(os.path.join(root, "benchmark", "layer_metrics"))
             if fn.endswith(".py")}
    assert listed == files


def check_limits(man, root=ROOT):
    """Every rule above, on ``man`` and the files under ``root``."""
    top_level_keys_and_limits(man, root)
    for kind, name in all_names(man):
        assert NAME.match(name), (kind, name)
    names_are_used_once(man)
    files_under_paths_have_plain_names(man, root)
    configs_hold(man, root)
    workloads_hold(man)
    metrics_hold(man)
    for w in man["workloads"]:
        cell_resolves_everything_by_name(man, w["name"], root)
    every_reader_file_is_listed_and_every_listed_metric_has_a_file(man, root)


def test_top_level_keys_and_limits():
    top_level_keys_and_limits(MAN)


@pytest.mark.parametrize("kind,name", all_names())
def test_names(kind, name):
    assert NAME.match(name), (kind, name)


def test_names_are_used_once():
    names_are_used_once(MAN)


def test_files_under_paths_have_plain_names():
    files_under_paths_have_plain_names(MAN)


def test_configs():
    configs_hold(MAN)


def test_workloads():
    workloads_hold(MAN)


def test_metrics():
    metrics_hold(MAN)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_everything_by_name(cell):
    cell_resolves_everything_by_name(MAN, cell)


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
    every_reader_file_is_listed_and_every_listed_metric_has_a_file(MAN)


LISTED = [(cell, m["name"]) for cell in CELLS for m in manifest.resolve(MAN, cell).per_layer]


@pytest.mark.parametrize("cell,metric", LISTED)
def test_a_reader_that_finds_nothing_returns_none(cell, metric):
    """A run with no trace, no counters, no records and no stats: each reader returns None, so
    that the line leaves its metric out, never 0 for a share of a roofline or a peak; only a
    count that is truly zero reads 0."""
    from benchmark.lib import harness

    c = manifest.resolve(MAN, cell)
    run = harness.Run(cell=c, device_kind="cpu", chips=c.chips, window_s=3.0, attempted=0,
                      failed=0, end_to_end={})
    got = c.reader(metric).read(run)
    unit = {m["name"]: m["unit"] for m in MAN["per_layer"]}[metric]
    assert got is None or (unit == "count" and got == 0), got


def test_a_later_cell_is_files_and_entries_only(copy, add_dummy_cell):
    cell = add_dummy_cell(copy)
    man = manifest.load_manifest(str(copy))
    c = manifest.resolve(man, cell, root=str(copy))
    assert c.config["num_hidden_layers"] == 6 and c.traffic["step"]["batch"] == 2
    assert c.builder.dims(c.config)["n_layer"] == 6
    assert {m["name"] for m in c.per_layer} == {"recompiles_in_window", "dummy_steps"}

    class Run:
        stats = {"steps": 7}

    assert c.reader("dummy_steps").read(Run()) == 7
    # the cells that were there resolve as before
    pythia = manifest.resolve(man, "pythia-410m.train-b4-t2048", root=str(copy))
    assert pythia.config["num_hidden_layers"] == 24
    check_limits(man, copy)


def test_unknown_names_are_told_apart(copy):
    with pytest.raises(KeyError, match="no workload"):
        manifest.resolve(MAN, "no-such.cell")
    c = manifest.resolve(MAN, CELLS[0])
    with pytest.raises(FileNotFoundError):
        c.reader("no_such_metric")
