"""The XLA regions' device time by the scope of the trace symbols (benchmark/lib/scopes.py) on a
hand-built trace with a small map (benchmark/fixtures/trace_scopes.json: its `what` says what
each op is), the fourteen readers without a map, and two rehearsals that read them end to end."""
import json
import os
from types import SimpleNamespace

import pytest

from benchmark.lib import manifest, phases, readers, scopes, xplane
from test_rehearsal import last_line, run_cell

ROOT = manifest.ROOT
MAN = manifest.load_manifest()
FIXTURE = os.path.join(ROOT, "benchmark", "fixtures", "trace_scopes.json")
FAMILIES = {
    "train": (scopes.TRAIN, "train_xla_%s_ms_per_step", ["pythia-410m.train-b4-t2048",
                                                         "mistral-7b-v0.3-l8.train-fsdp4-b4-t4096"]),
    "chat": (scopes.CHAT, "chat_xla_%s_ms_per_iter", ["mistral-7b-v0.3-l8.serve-chat"]),
    "reasoning": (scopes.REASONING, "reasoning_xla_%s_ms_per_iter",
                  ["phi-4-mini-flash-reasoning.serve-reasoning"]),
}
NEW = [pattern % name for family, pattern, _ in FAMILIES.values() for name in [*family, "unscoped"]]


@pytest.fixture(scope="module")
def planes():
    return xplane.load_json(FIXTURE)


@pytest.fixture(scope="module")
def maps():
    from thunder_tpu.observability.profiler import parse_hlo_text

    with open(FIXTURE) as f:
        parsed = [parse_hlo_text(e["text"], e["region"]) for e in json.load(f)["executables"]]
    return {ops.module: ops for ops in parsed}


@pytest.fixture
def run(planes, maps, monkeypatch):
    """A traced train run of three steps over the fixture, as a reader sees it."""
    monkeypatch.setattr(phases, "traced_planes", lambda run: planes)
    monkeypatch.setattr(scopes, "program_maps", lambda: (maps, {"executables": 1, "seconds": 0.0}))
    cell = SimpleNamespace(root=ROOT, name="fixture")
    return SimpleNamespace(cell=cell, trace=xplane.reduce_trace(planes), traced={"steps": 3}, stats={})


def test_the_parts_add_up_to_what_xla_seconds_sums(run):
    t = scopes.table(run)
    assert sum(t["cells"].values()) == pytest.approx(readers.xla_seconds(run)) == pytest.approx(3710e-9)
    assert t["total"] == pytest.approx(3710e-9)
    per_step = [scopes.train_ms_per_step(run, name) for name in [*scopes.TRAIN, "unscoped"]]
    assert sum(per_step) == pytest.approx(readers.xla_seconds(run) / 3 * 1e3)


@pytest.mark.parametrize("cell, ns", [
    (("mlp", "bwd"), 2000), (("attn", "fwd"), 500 + 200), (("attn", "recompute"), 400),
    (("unscoped", "fwd"), 300 + 250 + 60),
])
def test_each_op_goes_to_the_part_and_the_pass_of_its_trace_symbols(run, cell, ns):
    t = scopes.table(run)
    assert t["cells"][cell] == pytest.approx(ns * 1e-9)
    assert set(t["cells"]) == {("mlp", "bwd"), ("attn", "fwd"), ("attn", "recompute"), ("unscoped", "fwd")}


def test_a_fusion_over_two_parts_is_kept_as_a_pair_and_counted_once(run):
    t = scopes.table(run)
    assert t["pairs"] == {("mlp+optimizer", "bwd"): pytest.approx(2000e-9)}
    assert scopes.train_ms_per_step(run, "optimizer") == 0.0
    assert scopes.train_ms_per_step(run, "mlp") == pytest.approx(2000e-6 / 3)


def test_an_op_outside_every_programs_run_or_in_no_map_is_unscoped_and_said_to_be(run):
    t = scopes.table(run)
    assert t["outside"] == pytest.approx(250e-9)          # fusion.7: in no XLA Modules interval
    assert t["no_map"] == {"jit__sample_step": pytest.approx(60e-9)}
    assert t["not_in_map"] == pytest.approx(300e-9)       # fusion.9: the step's text has none
    assert set(t["unscoped_ops"]) == {"fusion kLoop f32[8,8]", "fusion kLoop f32[4]", "fusion kLoop s32[4]"}


def test_the_finer_names_below_a_part_are_kept_for_the_log_line(run, capsys):
    t = scopes.table(run, units=3, unit="step")
    assert t["finer"] == {("attn/rope", "fwd"): pytest.approx(500e-9)}  # copy.3 is attn, no finer
    assert "finer names: attn/rope fwd 0.000" in capsys.readouterr().out


def test_the_split_is_worked_out_once_a_run_and_logged(run, capsys):
    first = scopes.table(run, units=3, unit="step")
    assert scopes.table(run) is first
    out = capsys.readouterr().out
    assert out.count("bench: xla by scope") == 1
    assert "mlp bwd 0.001" in out and "attn fwd 0.000 recompute 0.000" in out
    assert "mlp+optimizer bwd" in out and "jit__sample_step" in out


def test_in_a_rehearsal_the_module_and_the_instruction_come_from_the_events_stats(maps):
    ops = [(xplane.Event("dot.1", 0, 10, {"hlo_module": "jit_tt_train_step", "hlo_op": "fusion.2"}), 10.0),
           (xplane.Event("thunk", 20, 5, {"hlo_module": "jit_other", "hlo_op": "add.1"}), 5.0)]
    got = scopes.split(ops, None, maps, lambda e, module: True)
    assert got["cells"] == {("attn", "fwd"): 10.0, ("unscoped", "fwd"): 5.0}
    assert got["no_map"] == {"jit_other": 5.0}


@pytest.mark.parametrize("metric", NEW)
def test_a_reader_returns_none_without_a_trace_and_without_a_map(metric, run, monkeypatch):
    reader = manifest.load_module(ROOT, "layer_metrics", metric)
    bare = SimpleNamespace(cell=run.cell, trace=None, traced={}, stats={})
    assert reader.read(bare) is None
    monkeypatch.setattr(scopes, "program_maps", lambda: None)  # a program without op_scopes
    run.stats["decode_regions"] = ["xla_fusion_1"]
    run.cell.builder = SimpleNamespace(dims=lambda config: {"d_inner": 64, "d_state": 16, "d_conv": 4})
    run.cell.config = {}
    assert reader.read(run) is None


def test_the_manifest_lists_the_fourteen_for_their_cells_appended_in_order():
    entries = MAN["per_layer"][-len(NEW):]
    assert [m["name"] for m in entries] == NEW and len(MAN["per_layer"]) == 89 + 14
    by_name = {m["name"]: m for m in entries}
    for family, pattern, cells in FAMILIES.values():
        for name in [*family, "unscoped"]:
            m = by_name[pattern % name]
            assert (m["workloads"], m["layer"], m["source"], m["unit"], m["better"]) == (
                cells, "executors", "device_trace", "ms", "lower")
            assert m["moves"] == ("train_tokens_per_s_per_chip" if cells[0].count("train") else
                                  "serve_tpot_p50_ms")


@pytest.mark.parametrize("cell, family", [("pythia-410m.train-b4-t2048", "train"),
                                          ("mistral-7b-v0.3-l8.serve-chat", "chat")])
def test_a_traced_rehearsal_reads_the_new_metrics_and_logs_the_split(cell, family):
    proc = run_cell(["--workload", cell, "--seed", "2147483999", "--seconds", "3", "--trace", "1",
                     "--rehearse"])
    line = last_line(proc)
    parts, pattern, _ = FAMILIES[family]
    assert {pattern % name for name in [*parts, "unscoped"]} <= set(line["rehearsal"]["metrics_read"])
    logged = [ln for ln in proc.stdout.splitlines() if ln.startswith("bench: xla by scope")]
    assert len(logged) == 1 and "instruction not in its program's map 0.00%" in logged[0]
    assert " attn fwd " in logged[0] and ("bwd" in logged[0]) == (family == "train")
