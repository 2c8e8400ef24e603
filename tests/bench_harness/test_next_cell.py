"""A configuration and a cell more append cleanly: on a copy of the benchmark with one more
configuration, one more serve cell and twenty per-layer entries listed for that cell
(`conftest.py: _add_next_cell`), every per-cell listing check of this directory and the limits of
`test_manifest.py` hold as they hold on the manifest itself.

A listing check is a module's `check_listing(man)`: it holds its own cells' entries by name
(present, with their fields and cells, absent from other cells) and never a position or a total of
the manifest. A test file a later PR adds is found here by that name, with no edit."""
import importlib
import os

import pytest

from benchmark.lib import manifest
from test_manifest import check_limits

HERE = os.path.dirname(os.path.abspath(__file__))
MAN = manifest.load_manifest()


def modules_with_a_listing_check() -> list:
    out = []
    for fn in sorted(os.listdir(HERE)):
        if fn.startswith("test_") and fn.endswith(".py"):
            with open(os.path.join(HERE, fn)) as f:
                if "\ndef check_listing(man" in f.read():
                    out.append(fn[:-3])
    return out


CHECKS = modules_with_a_listing_check()


@pytest.fixture
def longer(copy, add_next_cell):
    cell = add_next_cell(copy)
    return cell, manifest.load_manifest(str(copy)), copy


def test_the_copy_has_one_more_configuration_cell_and_twenty_entries(longer):
    cell, man, _ = longer
    assert {c["name"] for c in man["configs"]} - {c["name"] for c in MAN["configs"]} == {"next-l4"}
    assert {w["name"] for w in man["workloads"]} - {w["name"] for w in MAN["workloads"]} == {cell}
    added = [m for m in man["per_layer"] if m["name"] not in {e["name"] for e in MAN["per_layer"]}]
    assert len(added) == 20 and all(m["workloads"] == [cell] for m in added)


def test_the_limits_hold_on_the_longer_manifest(longer):
    cell, man, root = longer
    check_limits(man, root)
    listed = {m["name"] for m in manifest.resolve(man, cell, root=str(root)).per_layer}
    assert len(listed) == 21 and {n for n in listed if not n.startswith("next_part_")} == {"recompiles_in_window"}


def test_every_test_file_that_lists_entries_has_a_listing_check():
    assert {"test_phases", "test_pool_donated", "test_overlap", "test_scopes", "test_serve_added",
            "test_serve_rollouts", "test_serve_trajectories", "test_serve_denoise"} <= set(CHECKS)


@pytest.mark.parametrize("module", CHECKS)
def test_a_listing_check_holds_on_the_longer_manifest(module, longer):
    _, man, _ = longer
    importlib.import_module(module).check_listing(man)
