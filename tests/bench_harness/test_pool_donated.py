"""`serve.pool_donated` / `serve.pool_copied` (benchmark/lib/pool.py): the share of dispatches that
consumed the KV pool read 100 in every run of every cell, so it is a line on a traced serve run's
log (`bench: pool donated % ...`) and no entry of the manifest. What the share and its line make of
a run with both counters, with one and with neither, for each retired entry; that the manifest lists
none of them; and the line end to end in a rehearsal, where the CPU backend donates like the chip."""
import re
from types import SimpleNamespace

import pytest

from benchmark.lib import manifest, pool
from test_rehearsal import last_line, run_cell

MAN = manifest.load_manifest()
# the entries the line took the place of, by the cell they were listed for
RETIRED = {"mistral-7b-v0.3-l8.serve-chat": "chat_pool_donated_pct",
           "mistral-7b-v0.3-l8.serve-longprompt": "longprompt_pool_donated_pct",
           "phi-4-mini-flash-reasoning.serve-reasoning": "reasoning_pool_donated_pct",
           "mistral-small-4-119b-ep4-l6.serve-rollouts": "rollouts_pool_donated_pct"}
PREFIX = {"mistral-7b-v0.3-l8.serve-chat": "chat", "mistral-7b-v0.3-l8.serve-longprompt": "longprompt"}
LINE = re.compile(r"^bench: pool donated % (\S+) \(serve\.pool_donated (\d+), serve\.pool_copied (\d+)\)$",
                  re.M)


@pytest.mark.parametrize("counters,want", [
    ({"serve.pool_donated": 142, "serve.pool_copied": 0, "serve.tokens": 9}, 100.0),
    ({"serve.pool_donated": 3, "serve.pool_copied": 1}, 75.0),
    ({"serve.pool_donated": 7}, 100.0),
    ({"serve.pool_copied": 5}, 0.0),                 # a true zero is a reading
    ({"serve.tokens": 9, "serve.decode_steps": 3}, None),   # a program without the counters
    ({}, None),
])
@pytest.mark.parametrize("name", sorted(RETIRED.values()))
def test_the_log_line_carries_what_the_retired_entry_read(name, counters, want, capsys):
    run = SimpleNamespace(counters=counters)
    assert pool.donated_pct(run) == want
    assert want is None or isinstance(pool.donated_pct(run), float)
    pool.log(run)
    (value, donated, copied), = LINE.findall(capsys.readouterr().out)
    assert value == repr(want)
    assert (int(donated), int(copied)) == (counters.get("serve.pool_donated", 0),
                                           counters.get("serve.pool_copied", 0))
    assert not any(m["name"] == name for m in MAN["per_layer"])


def check_listing(man):
    """No cell lists a donated share; what chat and long prompts list under `executors` (their XLA
    time) is each cell's own."""
    names = {m["name"] for m in man["per_layer"]}
    assert not names & set(RETIRED.values())
    for cell, prefix in PREFIX.items():
        mine = [m for m in manifest.listed(man, cell)[1] if m["layer"] == "executors"]
        assert mine
        for m in mine:
            assert m["name"].startswith(prefix + "_") and m["workloads"] == [cell], m["name"]


@pytest.mark.parametrize("cell", PREFIX)
def test_the_manifest_lists_each_for_its_own_cell_under_executors(cell):
    check_listing(MAN)
    assert not {m["name"] for m in manifest.listed(MAN, cell)[1]} & set(RETIRED.values())


@pytest.mark.parametrize("cell", PREFIX)
def test_a_rehearsal_logs_the_share_of_its_cell(cell, copy):
    # in a copy of the benchmark: test_rehearsal.py and test_phases.py trace the same cells
    # into the checkout's .bench_out, and the files of one directory run side by side
    proc = run_cell(["--workload", cell, "--seed", "2147483777", "--seconds", "3",
                     "--trace", "1", "--rehearse"], root=str(copy))
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0
    (value, donated, copied), = LINE.findall(proc.stdout)
    assert value == "100.0" and int(donated) > 0 and int(copied) == 0
    assert line["rehearsal"]["compiles_in_window"] == 0
