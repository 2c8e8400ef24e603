"""The two readers of `serve.pool_donated` / `serve.pool_copied` (benchmark/lib/pool.py): what
they make of a run with both counters, with one and with neither, where the manifest lists them,
and both end to end in a rehearsal, where the CPU backend donates like the chip."""
from types import SimpleNamespace

import pytest

from benchmark.lib import manifest, pool
from test_rehearsal import SERVE, last_line, run_cell

MAN = manifest.load_manifest()
NEW = {"chat": "chat_pool_donated_pct", "longprompt": "longprompt_pool_donated_pct"}
MOVES = {"chat": "serve_tpot_p50_ms", "longprompt": "serve_total_tokens_per_s"}


def prefix_of(cell: str) -> str:
    return "chat" if cell.endswith("serve-chat") else "longprompt"


@pytest.mark.parametrize("counters,want", [
    ({"serve.pool_donated": 142, "serve.pool_copied": 0, "serve.tokens": 9}, 100.0),
    ({"serve.pool_donated": 3, "serve.pool_copied": 1}, 75.0),
    ({"serve.pool_donated": 7}, 100.0),
    ({"serve.pool_copied": 5}, 0.0),                 # a true zero is a reading
    ({"serve.tokens": 9, "serve.decode_steps": 3}, None),   # the parent: neither counter
    ({}, None),
])
@pytest.mark.parametrize("name", sorted(NEW.values()))
def test_the_readers_on_a_run_with_both_counters_with_one_and_with_neither(name, counters, want):
    run = SimpleNamespace(counters=counters)
    got = manifest.load_module(manifest.ROOT, "layer_metrics", name).read(run)
    assert got == want == pool.donated_pct(run)
    assert want is None or isinstance(got, float)


@pytest.mark.parametrize("cell", SERVE)
def test_the_manifest_lists_each_for_its_own_cell_under_executors(cell):
    listed = {m["name"]: m for m in manifest.resolve(MAN, cell).per_layer}
    mine, other = NEW[prefix_of(cell)], NEW[{"chat": "longprompt", "longprompt": "chat"}[prefix_of(cell)]]
    assert other not in listed
    m = listed[mine]
    assert (m["unit"], m["better"], m["source"], m["layer"]) == ("%", "higher", "program_counter",
                                                                 "executors")
    assert m["moves"] == MOVES[prefix_of(cell)] and m["workloads"] == [cell]
    assert [e["name"] for e in MAN["per_layer"][-2:]] == sorted(NEW.values())  # appended, last


@pytest.mark.parametrize("cell", SERVE)
def test_a_rehearsal_reads_the_metric_of_its_cell(cell, copy):
    # in a copy of the benchmark: test_rehearsal.py and test_phases.py trace the same cells
    # into the checkout's .bench_out, and the files of one directory run side by side
    line = last_line(run_cell(["--workload", cell, "--seed", "2147483777", "--seconds", "3",
                               "--trace", "1", "--rehearse"], root=str(copy)))
    assert line["correct"] is True and line["failed"] == 0
    assert NEW[prefix_of(cell)] in line["rehearsal"]["metrics_read"]
    assert line["rehearsal"]["compiles_in_window"] == 0
