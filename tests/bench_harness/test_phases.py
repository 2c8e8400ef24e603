"""The chip's idle time put down to the phases of the serving loop (benchmark/lib/phases.py) on
a hand-built trace of two passes of the loop (benchmark/fixtures/trace_engine_loop.json), the
queue wait from hand-built bus records, and the readers of both end to end in a rehearsal.

The fixture's idle gaps and what each is put down to (ns; the window is 0 .. 10000):

    0 .. 1000      nothing 100, iteration alone 20 + 20 + 20, admit 80, upload 280, dispatch 480
    2000 .. 2200   inside the decode program's run: in_program 200, though the host is in fetch
    4000 .. 4100   between the decode program and the sampler: fetch 100
    4200 .. 6000   fetch 200, commit 300, admit 50, prefill 1000, iteration alone 50 + 50 + 50,
                   nothing 100 (between the two passes)
    7000 .. 7600   prefill 50, admit 50, iteration alone 50, upload 150, dispatch 300
    9000 .. 9100   in_program 100
    9800 .. 10000  fetch 100, commit 40, iteration alone 10, nothing 50
"""
import os
from types import SimpleNamespace

import pytest

from benchmark.lib import manifest, phases, xplane
from test_rehearsal import SERVE, last_line, run_cell

MAN = manifest.load_manifest()
FIXTURE = os.path.join(manifest.ROOT, "benchmark", "fixtures", "trace_engine_loop.json")
WANT = {"admit": 180, "prefill": 1050, "upload": 430, "dispatch": 780, "fetch": 400,
        "commit": 340, "unattributed": 520, "in_program": 300, "wait": 0}
NEW = {prefix: {f"{prefix}_idle_{share}_ms_per_iter" for share in phases.SHARES} | queue
       for prefix, queue in (("chat", {"chat_queue_wait_p50_ms", "chat_queue_wait_p95_ms"}),
                             ("longprompt", {"longprompt_queue_wait_p50_ms"}))}


@pytest.fixture(scope="module")
def planes():
    return xplane.load_json(FIXTURE)


@pytest.fixture(scope="module")
def red(planes):
    return xplane.reduce_trace(planes)


def modules(planes) -> list:
    return xplane.device_planes(planes)[0].line(xplane.MODULES_LINE)


def admitted(queued_ms: float) -> dict:
    return {"kind": "event", "name": "trace", "ts_ms": 0.0,
            "attrs": {"phase": "admitted", "request": 0, "queued_ms": queued_ms}}


def test_gaps_are_split_by_overlap_and_the_shares_add_up_to_the_idle_time(planes, red):
    chip = red.devices[0]
    assert chip.gaps == [(0, 1000), (2000, 2200), (4000, 4100), (4200, 6000), (7000, 7600),
                         (9000, 9100), (9800, 10000)]
    split = phases.split_idle(chip.gaps, modules(planes), red.host)
    assert split == WANT
    assert sum(split.values()) == chip.window_ns - chip.busy_ns == 4000


def test_one_gap_goes_to_every_phase_it_overlaps():
    ev = [xplane.Event("engine:iteration", 0, 100), xplane.Event("engine:upload", 10, 30),
          xplane.Event("engine:dispatch", 40, 50)]
    split = phases.split_idle([(20, 70)], [], ev)
    assert (split["upload"], split["dispatch"], split["unattributed"]) == (20, 30, 0)
    # a gap's middle would have given all fifty to dispatch


def test_innermost_takes_nested_events_out_of_their_parents(red):
    own = phases.innermost([e for e in red.host if e.name.startswith("engine:")])
    assert own["engine:prefill"] == [(5000, 7050)]
    assert own["engine:admit"] == [(120, 200), (4950, 5000), (7050, 7100)]
    assert xplane.total(own["engine:iteration"]) == 20 * 3 + 50 * 2 + 50 + 50 + 10
    # the runtime's worker pools are no host thread of the program: their line is left out
    assert all(end <= 9950 for parts in own.values() for _, end in parts)


def test_a_phase_with_nothing_under_it_reads_zero_and_wait_is_kept_apart(planes, red):
    chip = red.devices[0]
    without_commit = [e for e in red.host if e.name != "engine:commit"]
    split = phases.split_idle(chip.gaps, modules(planes), without_commit)
    assert split["commit"] == 0.0 and split["unattributed"] == WANT["unattributed"] + 340
    waiting = red.host + [xplane.Event("engine:wait", 4800, 100)]
    split = phases.split_idle(chip.gaps, modules(planes), waiting)
    assert split["wait"] == 100 and split["unattributed"] == WANT["unattributed"] - 100
    assert sum(split.values()) == 4000


def test_a_gap_inside_a_program_is_in_program_whatever_the_host_does(planes, red):
    split = phases.split_idle([(2000, 2200)], modules(planes), red.host)
    assert split["in_program"] == 200 and split["fetch"] == 0
    assert phases.split_idle([(2000, 2200)], [], red.host)["fetch"] == 200


def test_longest_gaps_name_phases_with_no_edit_to_the_reduction(red):
    assert [label for label, _ in red.longest_gaps(3)] == ["engine:prefill", "engine:iteration",
                                                           "engine:dispatch"]
    assert red.host_count("serve_decode") == 2 == red.host_count("engine:dispatch")


@pytest.fixture
def fake_run(planes, red, monkeypatch):
    """A run as the readers see it, with the fixture for its trace."""
    monkeypatch.setattr(phases, "traced_planes", lambda run: planes)
    cell = SimpleNamespace(root=manifest.ROOT, name="fixture")
    bus = [admitted(ms) for ms in (1.0, 2.0, 3.0, 4.0, 105.0)]
    bus += [{"kind": "event", "name": "trace", "attrs": {"phase": "retired", "request": 0}},
            {"kind": "span", "name": "serve_decode", "dur_ms": 9.0, "attrs": {}}]
    return SimpleNamespace(cell=cell, trace=red, traced={}, bus=bus)


def test_every_new_reader_reads_its_share_per_iteration(fake_run):
    assert phases.iterations(fake_run) == 2
    read = {name: manifest.load_module(manifest.ROOT, "layer_metrics", name).read(fake_run)
            for names in NEW.values() for name in names}
    for prefix in NEW:
        for share in phases.SHARES:
            assert read[f"{prefix}_idle_{share}_ms_per_iter"] == WANT[share] / 1e6 / 2
        assert read[f"{prefix}_queue_wait_p50_ms"] == 3.0
        assert sum(read[f"{prefix}_idle_{share}_ms_per_iter"] for share in phases.SHARES) \
            == pytest.approx(4000 / 1e6 / 2)
    assert read["chat_queue_wait_p95_ms"] == pytest.approx(4.0 + 0.8 * 101.0)
    assert all(isinstance(v, float) for v in read.values())


def test_a_program_without_the_phases_reports_none_and_does_not_raise(fake_run):
    old = SimpleNamespace(devices=fake_run.trace.devices, host=[], host_count=lambda name: 0)
    fake_run.trace, fake_run.bus = old, []
    for names in NEW.values():
        for name in names:
            assert manifest.load_module(manifest.ROOT, "layer_metrics", name).read(fake_run) is None
    fake_run.trace = None
    assert phases.idle_ms_per_iter(fake_run, "admit") is None


@pytest.mark.parametrize("cell", SERVE)
def test_the_manifest_lists_the_new_metrics_for_their_cell_only(cell):
    prefix = "chat" if cell.endswith("serve-chat") else "longprompt"
    listed = {m["name"]: m for m in manifest.resolve(MAN, cell).per_layer}
    assert NEW[prefix] <= set(listed)
    assert not any(name in listed for other in NEW if other != prefix for name in NEW[other])
    for name in NEW[prefix]:
        m = listed[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == ("ms", "lower", "program_span",
                                                                     "entry")
        assert m["workloads"] == [cell]


@pytest.mark.parametrize("cell", SERVE)
def test_a_rehearsal_reads_every_new_metric_of_its_cell(cell):
    line = last_line(run_cell(["--workload", cell, "--seed", "2147483659", "--seconds", "3",
                               "--trace", "1", "--rehearse"]))
    prefix = "chat" if cell.endswith("serve-chat") else "longprompt"
    assert line["correct"] is True
    assert NEW[prefix] <= set(line["rehearsal"]["metrics_read"])
    assert line["rehearsal"]["breakdown_read"] == ["device_ops", "idle_gaps"]
