"""The three readers of `serve.decode_overlapped` / `serve.decode_steps` (benchmark/lib/overlap.py):
what they make of a run with the counter, with a true zero and without it (the parent, whose loop
fetched before it dispatched), where the manifest lists them, and each end to end in a rehearsal of
its cell, run in a copy of the benchmark so that its trace has an output directory of its own."""
from types import SimpleNamespace

import pytest

from benchmark.lib import manifest, overlap
from test_rehearsal import last_line, run_cell

MAN = manifest.load_manifest()
NEW = {"mistral-7b-v0.3-l8.serve-chat": ("chat_decode_overlapped_pct", "serve_tpot_p50_ms"),
       "phi-4-mini-flash-reasoning.serve-reasoning": ("reasoning_decode_overlapped_pct",
                                                      "serve_tpot_p50_ms"),
       "mistral-7b-v0.3-l8.serve-longprompt": ("longprompt_decode_overlapped_pct",
                                               "serve_total_tokens_per_s")}
NAMES = sorted(name for name, _ in NEW.values())


@pytest.mark.parametrize("counters,want", [
    ({"serve.decode_steps": 200, "serve.decode_overlapped": 192, "serve.tokens": 3600}, 96.0),
    ({"serve.decode_steps": 4, "serve.decode_overlapped": 1, "serve.decode_discarded": 2}, 25.0),
    ({"serve.decode_steps": 7, "serve.decode_overlapped": 0}, 0.0),   # a true zero is a reading
    ({"serve.decode_steps": 7, "serve.tokens": 9}, None),             # the parent: no such counter
    ({"serve.decode_overlapped": 0}, None),                           # a window with no decode step
    ({}, None),
])
@pytest.mark.parametrize("name", NAMES)
def test_the_readers_with_the_counter_with_a_true_zero_and_without_it(name, counters, want):
    run = SimpleNamespace(counters=counters)
    got = manifest.load_module(manifest.ROOT, "layer_metrics", name).read(run)
    assert got == want == overlap.overlapped_pct(run)
    assert want is None or isinstance(got, float)


@pytest.mark.parametrize("cell", sorted(NEW))
def test_the_manifest_lists_each_for_its_own_cell_under_entry(cell):
    listed = {m["name"]: m for m in manifest.resolve(MAN, cell).per_layer}
    mine, moves = NEW[cell]
    assert [n for n in NAMES if n in listed] == [mine]
    m = listed[mine]
    assert (m["unit"], m["better"], m["source"], m["layer"]) == ("%", "higher", "program_counter",
                                                                 "entry")
    assert m["moves"] == moves and m["workloads"] == [cell]
    # the cell reports the end-to-end metric its share moves
    assert moves in {e["name"] for e in manifest.resolve(MAN, cell).end_to_end}


@pytest.mark.parametrize("cell", sorted(NEW))
def test_a_rehearsal_reads_the_metric_of_its_cell(cell, copy):
    line = last_line(run_cell(["--workload", cell, "--seed", "2147483999", "--seconds", "3",
                               "--trace", "1", "--rehearse"], root=str(copy)))
    assert line["correct"] is True and line["failed"] == 0
    assert NEW[cell][0] in line["rehearsal"]["metrics_read"]
    assert line["rehearsal"]["compiles_in_window"] == 0
