"""A configuration that is no dense rope GPT comes as files and entries only: a served-only
dummy with a builder, a reference and a kernel-class file of its own (`conftest.py:
_add_served_only_cell`) resolves, rehearses to a `correct` line with no file that was there
edited, and is held to the kernel claims its own builder states; and the kernel classes are
found by file, the ones that were there first."""
import hashlib
import json
import os
from collections import Counter
from types import SimpleNamespace

import pytest

from benchmark.lib import harness, manifest, readers, xplane
from test_rehearsal import last_line, run_cell

ROOT = manifest.ROOT
FIXTURES = ("trace_two_chips.json", "trace_engine_loop.json")


def tree(root) -> dict:
    """Every file under ``root`` (compiled Python and run outputs aside) -> its digest."""
    out = {}
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d not in ("__pycache__", ".bench_out", ".tt_cache")]
        for fn in filenames:
            path = os.path.join(dirpath, fn)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_a_served_only_configuration_is_files_and_entries_only(copy, add_served_only_cell):
    before = tree(copy)
    cell = add_served_only_cell(copy)
    man = manifest.load_manifest(str(copy))
    c = manifest.resolve(man, cell, root=str(copy))
    assert c.traffic["driver"] == "serve" and c.config["builder"] == "dummy_hybrid"
    assert "rope_theta" not in c.config and c.config["attention_layers"] != c.config["num_hidden_layers"]
    assert callable(c.builder.build_serving_model) and not hasattr(c.builder, "build_loss_model")
    assert callable(c.reference.forward) and callable(c.reference.control)
    assert [m["name"] for m in c.per_layer] == ["recompiles_in_window", "dummy_decode_steps"]
    # it rehearses to a correct line, traced, reading its own per-layer metric
    line = last_line(run_cell(["--workload", cell, "--seed", "2147483999", "--seconds", "2",
                               "--trace", "1", "--rehearse"], root=str(copy)))
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    assert "dummy_decode_steps" in line["rehearsal"]["metrics_read"]
    assert list(line)[-1] == "compared" and line["compared"]["sample_margin"]["rule"] == "<="
    # and nothing that was there was edited: byte for byte, BENCHMARK.json aside (entries added)
    after = tree(copy)
    assert {k: v for k, v in after.items() if k in before and k != "BENCHMARK.json"} \
        == {k: v for k, v in before.items() if k != "BENCHMARK.json"}
    added = sorted(set(after) - set(before))
    assert added == ["benchmark/builders/dummy_hybrid.py", "benchmark/configs/dummy-hybrid.json",
                     "benchmark/kernels/dummy_hybrid.json",
                     "benchmark/layer_metrics/dummy_decode_steps.py",
                     "benchmark/reference/dummy_hybrid.py", "benchmark/traffic/serve-dummy.json"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        was = json.load(f)
    assert man["configs"][:-1] == was["configs"] and man["workloads"][:-1] == was["workloads"]
    assert man["per_layer"][:-1] == was["per_layer"]


def resolved(copy, cell, rehearse=False):
    return manifest.resolve(manifest.load_manifest(str(copy)), cell, root=str(copy), rehearse=rehearse)


def test_the_serve_driver_holds_a_run_to_the_builders_own_claims(copy, add_served_only_cell):
    """Off the TPU `check_kernels` has nothing to judge, so it is driven with claims as
    `program_claims` would have recorded them on the chip."""
    c = resolved(copy, add_served_only_cell(copy))
    serve = c.driver
    n, attn = c.config["num_hidden_layers"], c.config["attention_layers"]
    as_built = {"decode_cfn": {"thunder.paged_attention": attn, "dummy.state_update": n - attn},
                "chunk_cfn": {"thunder.paged_chunk_attention": attn, "dummy.scan": n - attn}}
    assert c.builder.kernel_claims(c.config) == as_built

    def judged(claims):
        notes, compared = [], {}
        serve.check_kernels(c, claims, notes, compared)
        run = harness.Run(cell=c, device_kind="TPU v5 lite", chips=1, window_s=1.0, attempted=1,
                          failed=0, end_to_end={}, notes=notes, compared=compared)
        return run.correct, notes, compared

    ok, notes, compared = judged(as_built)
    assert ok and compared["decode_cfn.dummy.state_update"] == (n - attn, "==", n - attn)
    # the dense GPT's count, one attention kernel in every layer, is not this model's
    ok, notes, _ = judged({"decode_cfn": {"thunder.paged_attention": n},
                           "chunk_cfn": {"thunder.paged_chunk_attention": n}})
    assert not ok and len(notes) == 4
    assert f"thunder.paged_attention claimed by pallas {n} times in decode_cfn, not {attn}" in notes
    assert f"dummy.state_update claimed by pallas 0 times in decode_cfn, not {n - attn}" in notes
    # a program the traffic never ran is not judged; the decode program has to have run
    assert judged({"decode_cfn": as_built["decode_cfn"]})[0]
    ok, notes, _ = judged({"chunk_cfn": as_built["chunk_cfn"]})
    assert not ok and notes == ["the decode program never ran"]
    # off the TPU: nothing to judge, nothing recorded
    assert judged(None) == (True, [], {})


def test_a_wrong_kernel_claims_makes_the_run_not_correct(copy, add_served_only_cell):
    c = resolved(copy, add_served_only_cell(copy))
    path = copy / "benchmark" / "builders" / "dummy_hybrid.py"
    path.write_text(path.read_text().replace('"thunder.paged_attention": attn',
                                             '"thunder.paged_attention": attn + state'))
    recorded = {"decode_cfn": {"thunder.paged_attention": 3, "dummy.state_update": 3}}
    notes = []
    c.driver.check_kernels(c, recorded, notes, {})
    assert notes == ["thunder.paged_attention claimed by pallas 3 times in decode_cfn, not 6"]


def test_a_builder_without_kernel_claims_is_an_error_on_any_platform(copy, add_served_only_cell):
    c = resolved(copy, add_served_only_cell(copy))
    path = copy / "benchmark" / "builders" / "dummy_hybrid.py"
    path.write_text(path.read_text().replace("def kernel_claims(", "def no_kernel_claims("))
    with pytest.raises(AttributeError, match="exports no kernel_claims"):
        c.driver.check_kernels(c, None, [], {})
    # and one that leaves out a program its driver judges
    path.write_text(path.read_text().replace("def no_kernel_claims(", "def kernel_claims(")
                    .replace('"chunk_cfn"', '"prefill_cfn"'))
    with pytest.raises(KeyError, match="names no .'chunk_cfn'."):
        c.driver.check_kernels(c, None, [], {})


def test_the_litgpt_builder_states_the_counts_of_pr_22():
    """One attention kernel a layer in each program, the plain and the rope-fused flash kernel
    summed, and at least twice the depth in Mosaic calls: what the drivers held before the
    claims moved into the builder."""
    man = manifest.load_manifest()
    for w in man["workloads"]:
        c = manifest.resolve(man, w["name"])
        if c.config["builder"] != "litgpt":
            continue
        n = c.config["num_hidden_layers"]
        assert c.builder.kernel_claims(c.config) == {
            "decode_cfn": {"thunder.paged_attention": n},
            "chunk_cfn": {"thunder.paged_chunk_attention": n},
            "forward": {"pallas.rope_flash_fwd+pallas.flash_attention_fwd": n},
            "backward": {"pallas.rope_flash_bwd+pallas.flash_attention_bwd": n}}


@pytest.mark.parametrize("fwd,bwd,mosaic,faults", [
    ({"pallas.rope_flash_fwd": 8}, {"pallas.rope_flash_bwd": 8}, 16, []),
    # the plain and the rope-fused kernel are summed, and more than the model needs is fine
    ({"pallas.rope_flash_fwd": 3, "pallas.flash_attention_fwd": 5, "pallas.rms_norm": 17},
     {"pallas.flash_attention_bwd": 9}, 40, []),
    ({"pallas.rope_flash_fwd": 7}, {"pallas.rope_flash_bwd": 8}, 16, ["forward"]),
    ({"pallas.rope_flash_fwd": 8}, {}, 16, ["backward"]),
    ({"pallas.rope_flash_fwd": 8}, {"pallas.rope_flash_bwd": 8}, 15, ["Mosaic"]),
])
def test_the_train_driver_holds_a_step_to_at_least_the_builders_claims(fwd, bwd, mosaic, faults):
    man = manifest.load_manifest()
    c = manifest.resolve(man, "mistral-7b-v0.3-l8.train-fsdp4-b4-t4096")
    notes, compared = [], {}
    out = c.driver.check_kernels(c, {"forward": fwd, "backward": bwd, "mosaic_calls": mosaic},
                                 notes, compared)
    assert out["mosaic_calls"] == mosaic and compared["mosaic_calls"] == (mosaic, ">=", 16)
    assert len(notes) == len(faults) and all(f in note for f, note in zip(faults, notes)), notes
    # a step the artifact store served has no trace in this process: the executable alone
    notes = []
    c.driver.check_kernels(c, {"mosaic_calls": 16}, notes, {})
    assert notes == []
    c.driver.check_kernels(c, {}, notes, {})
    assert notes == ["no trace and no executable to prove the kernels from"]


def test_flops_per_trained_token_come_from_the_builder():
    from benchmark.lib import costs

    man = manifest.load_manifest()
    for cell, T in (("pythia-410m.train-b4-t2048", 2048),
                    ("mistral-7b-v0.3-l8.train-fsdp4-b4-t4096", 4096)):
        c = manifest.resolve(man, cell)
        assert c.traffic["step"]["seq_len"] == T
        assert c.builder.train_flops_per_token(c.config, T) == \
            costs.train_flops_per_token(seq_len=T, **c.builder.dims(c.config))


# -- kernel classes, found by file --------------------------------------------------------------

def op_names() -> list:
    return sorted({e.name for fx in FIXTURES
                   for p in xplane.load_json(os.path.join(ROOT, "benchmark", "fixtures", fx))
                   for ln in p.lines for e in ln.events})


def test_every_fixture_op_keeps_its_class_beside_an_extra_class_file(copy, add_served_only_cell):
    names = op_names()
    before = {n: readers.pallas_class(ROOT, n) for n in names}
    assert Counter(before.values()) >= Counter({"flash_fwd": 1, "flash_bwd": 1, "paged_decode": 1,
                                                "paged_chunk": 1})
    add_served_only_cell(copy)
    classes = readers.kernel_classes(str(copy))
    assert [c for c, _ in classes["classes"]] == [c for c, _ in readers.kernel_classes(ROOT)["classes"]] \
        + ["dummy_state_update", "dummy_anything"]
    assert {n: readers.pallas_class(str(copy), n) for n in names} == before
    # the new classes are found, after the ones that were there: the extra file's greedy
    # pattern takes only what no earlier class did
    mosaic = 'custom_call_target="tpu_custom_call"'
    state = f"%c.1 = f32[8,16,64]{{2,1,0}} custom-call(f32[8,16,64]{{2,1,0}} %s, bf16[8,1,64]{{2,1,0}} %x), {mosaic}"
    other = f"%c.2 = bf16[8,64]{{1,0}} custom-call(bf16[8,64]{{1,0}} %x), {mosaic}"
    assert readers.pallas_class(str(copy), state) == "dummy_state_update"
    assert readers.pallas_class(str(copy), other) == "dummy_anything"
    assert readers.pallas_class(ROOT, other) == "unclassified"
    label = readers.breakdown_label(str(copy))
    assert label(SimpleNamespace(name=state)) == "pallas dummy_state_update f32[8,16,64]"


@pytest.mark.parametrize("doc,complaint", [
    ({"classes": [{"class": "flash_fwd", "pattern": "x"}]},
     "'flash_fwd' is defined twice: in benchmark/kernels/classes.json and in benchmark/kernels/more.json"),
    ({"mosaic": "custom-call", "classes": []}, "more.json may not redefine `mosaic`"),
], ids=["class-defined-twice", "mosaic-redefined"])
def test_a_class_file_may_not_take_over_what_is_there(copy, doc, complaint):
    (copy / "benchmark" / "kernels" / "more.json").write_text(json.dumps(doc))
    with pytest.raises(ValueError, match=complaint):
        readers.kernel_classes(str(copy))
