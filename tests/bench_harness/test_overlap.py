"""`serve.decode_overlapped` / `serve.decode_steps` (benchmark/lib/overlap.py): the share of decode
steps dispatched with the step before still unfetched read 100 (99.97 in long prompts) in every run
of every cell, so it is a line on a traced serve run's log (`bench: decode overlapped % ...`) and no
entry of the manifest. What the share and its line make of a run with the counter, with a true zero
and without it (a loop that fetched before it dispatched), for each retired entry; that the manifest
lists none of them; and the line end to end in a rehearsal of each cell that listed one, run in a
copy of the benchmark so that its trace has an output directory of its own."""
import re
from types import SimpleNamespace

import pytest

from benchmark.lib import manifest, overlap
from test_rehearsal import last_line, run_cell

MAN = manifest.load_manifest()
# the entries the line took the place of, by the cell they were listed for
RETIRED = {"mistral-7b-v0.3-l8.serve-chat": "chat_decode_overlapped_pct",
           "phi-4-mini-flash-reasoning.serve-reasoning": "reasoning_decode_overlapped_pct",
           "mistral-7b-v0.3-l8.serve-longprompt": "longprompt_decode_overlapped_pct",
           "mistral-small-4-119b-ep4-l6.serve-rollouts": "rollouts_decode_overlapped_pct",
           "sdar-30b-a3b-chat-l6.serve-denoise": "denoise_pass_overlapped_pct"}
REHEARSED = ["mistral-7b-v0.3-l8.serve-chat", "mistral-7b-v0.3-l8.serve-longprompt",
             "phi-4-mini-flash-reasoning.serve-reasoning"]
LINE = re.compile(r"^bench: decode overlapped % (\S+) \(serve\.decode_overlapped (\S+) of "
                  r"serve\.decode_steps (\d+)\)$", re.M)


@pytest.mark.parametrize("counters,want", [
    ({"serve.decode_steps": 200, "serve.decode_overlapped": 192, "serve.tokens": 3600}, 96.0),
    ({"serve.decode_steps": 4, "serve.decode_overlapped": 1, "serve.decode_discarded": 2}, 25.0),
    ({"serve.decode_steps": 7, "serve.decode_overlapped": 0}, 0.0),   # a true zero is a reading
    ({"serve.decode_steps": 7, "serve.tokens": 9}, None),             # no such counter
    ({"serve.decode_overlapped": 0}, None),                           # a window with no decode step
    ({}, None),
])
@pytest.mark.parametrize("name", sorted(RETIRED.values()))
def test_the_log_line_carries_what_the_retired_entry_read(name, counters, want, capsys):
    run = SimpleNamespace(counters=counters)
    assert overlap.overlapped_pct(run) == want
    assert want is None or isinstance(overlap.overlapped_pct(run), float)
    overlap.log(run)
    (value, overlapped, steps), = LINE.findall(capsys.readouterr().out)
    assert value == repr(want)
    assert overlapped == repr(counters.get("serve.decode_overlapped"))
    assert int(steps) == counters.get("serve.decode_steps", 0)
    assert not any(m["name"] == name for m in MAN["per_layer"])


def check_listing(man):
    """No cell lists an overlapped share; what each cell that listed one lists under `entry` is
    its own."""
    names = {m["name"] for m in man["per_layer"]}
    assert not names & set(RETIRED.values())
    for cell, name in RETIRED.items():
        prefix = name.split("_")[0]
        mine = [m for m in manifest.listed(man, cell)[1] if m["layer"] == "entry"]
        assert mine
        for m in mine:
            assert m["name"].startswith(prefix + "_") and m["workloads"] == [cell], m["name"]


@pytest.mark.parametrize("cell", REHEARSED)
def test_the_manifest_lists_each_for_its_own_cell_under_entry(cell):
    check_listing(MAN)
    assert RETIRED[cell] not in {m["name"] for m in manifest.listed(MAN, cell)[1]}


@pytest.mark.parametrize("cell", REHEARSED)
def test_a_rehearsal_logs_the_share_of_its_cell(cell, copy):
    proc = run_cell(["--workload", cell, "--seed", "2147483999", "--seconds", "3",
                     "--trace", "1", "--rehearse"], root=str(copy))
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    (value, overlapped, steps), = LINE.findall(proc.stdout)
    assert 0.0 <= float(value) <= 100.0 and int(overlapped) <= int(steps) and int(steps) > 0
    assert line["rehearsal"]["compiles_in_window"] == 0
