"""The reduction from a profiler trace to numbers, on a small hand-built trace
(benchmark/fixtures/trace_two_chips.json, names as the v5e writes them), and the yardstick
beside it: operations and bytes from shapes, and the table of peaks."""
import os
from types import SimpleNamespace

import pytest

from benchmark.lib import costs, manifest, peaks, readers, xplane

FIXTURE = os.path.join(manifest.ROOT, "benchmark", "fixtures", "trace_two_chips.json")
V5E = peaks.peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def red():
    return xplane.reduce_trace(xplane.load_json(FIXTURE))


# -- interval arithmetic ------------------------------------------------------------------

def test_union_merges_nested_overlapping_and_touching():
    assert xplane.union([(5, 6), (0, 2), (1, 3), (3, 4), (10, 10), (5.5, 5.8)]) == [(0, 4), (5, 6)]
    assert xplane.total(xplane.union([(0, 10), (2, 3), (8, 12)])) == 12


def test_subtract_and_clip():
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert xplane.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert xplane.subtract([(0, 4)], [(0, 4)]) == []
    assert xplane.clip([(0, 5), (8, 12), (20, 30)], 3, 10) == [(3, 5), (8, 10)]


def test_self_time_is_duration_minus_what_is_enclosed():
    ev = [xplane.Event("outer", 0, 100), xplane.Event("a", 10, 20), xplane.Event("b", 40, 30),
          xplane.Event("b.inner", 45, 5), xplane.Event("after", 100, 10)]
    selfs = {e.name: s for e, s in xplane.self_times(ev)}
    assert selfs == {"outer": 50, "a": 20, "b": 25, "b.inner": 5, "after": 10}
    assert sum(selfs.values()) == xplane.total(xplane.union((e.start, e.end) for e in ev))


@pytest.mark.parametrize("name,want", [
    ("%fusion.20 = (f32[8,4]{1,0:T(8,128)}, f32[8,4]{1,0:T(8,128)}) fusion(f32[8,4]{1,0} %a), kind=kLoop, calls=%fc",
     ("fusion.20", "fusion", "(f32[8,4], f32[8,4])")),
    ("%copy.109 = bf16[1537,8,64,128]{3,2,1,0:T(8,128)(2,1)} copy(bf16[1537,8,64,128]{3,2,1,0} %args_6_.1)",
     ("copy.109", "copy", "bf16[1537,8,64,128]")),
    ("%all-gather.5 = bf16[16,128]{1,0} all-gather(bf16[4,128]{1,0} %x), channel_id=1",
     ("all-gather.5", "all-gather", "bf16[16,128]")),
    ("dot_general.1", ("dot_general.1", "dot_general", "")),
])
def test_parse_hlo(name, want):
    assert xplane.parse_hlo(name) == want


def test_op_label_groups_layers():
    a = "%fusion.20 = (f32[8,4]{1,0}, f32[8,4]{1,0}, f32[8,4]{1,0}) fusion(f32[8,4]{1,0} %a), kind=kLoop, calls=%fc.1"
    b = a.replace("fusion.20", "fusion.21").replace("%fc.1", "%fc.2")
    assert xplane.op_label(a) == xplane.op_label(b) == "fusion kLoop (f32[8,4] x3)"


@pytest.mark.parametrize("name,want", [
    ("%all-gather.5 = bf16[16,128]{1,0} all-gather(bf16[4,128]{1,0} %x)", ("all-gather", "")),
    ("%all-reduce-start.6 = f32[128]{0} all-reduce-start(f32[128]{0} %g)", ("all-reduce", "-start")),
    ("%collective-permute-done.2 = bf16[8]{0} collective-permute-done(bf16[8]{0} %s)",
     ("collective-permute", "-done")),
    ("%reduce-scatter.1 = f32[4]{0} reduce-scatter(f32[16]{0} %g)", ("reduce-scatter", "")),
    ("%fusion.3 = f32[4]{0} fusion(f32[4]{0} %all-gather.5), kind=kLoop", None),
    ("%copy-start.1 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(f32[4]{0} %x)", None),
])
def test_collective_kind(name, want):
    assert xplane.collective_kind(name) == want


# -- the fixture, worked by hand -------------------------------------------------------------
#
# window: the harness's bench:window span, 1000 .. 11000 ns (10000 ns)
# chip 0, XLA Ops inside the window:
#   fusion.1 1000-2000 | while 2500-4500 enclosing fusion.2 2600-3100 and flash-fwd 3200-4200 |
#   fusion.3 5000-6000 overlapping copy.4 5500-6500 | flash-bwd 7000-8000 | all-gather 8200-8800 |
#   all-reduce-start 9000-9050, fusion.7 9100-9500, all-reduce-done 9700-9800 |
#   fusion.8 10800-11400 (200 ns inside); fusion.9 200-800 lies before the window
#   busy = 1000 + 2000 + 1500 + 1000 + 600 + (50 + 400 + 100) + 200 = 6850
# chip 1: fusion.1 1000-3000 | all-gather 3000-4000 | permute-start 4000-4100,
#   fusion.3 4100-4900, permute-done 4900-5000 | paged decode 6000-7500 | paged chunk 7500-8000
#   busy = 2000 + 1000 + 1000 + 2000 = 6000

def test_busy_union_and_idle_share(red):
    d0, d1 = red.devices
    assert (d0.plane, d1.plane) == ("/device:TPU:0", "/device:TPU:1")
    assert d0.window == d1.window == (1000.0, 11000.0)
    assert d0.busy_ns == 6850 and d1.busy_ns == 6000
    assert d0.idle_share == pytest.approx(0.315) and d1.idle_share == pytest.approx(0.4)
    assert red.worst_idle_share == pytest.approx(0.4)
    assert red.window_s == pytest.approx(10e-6)
    assert red.busy_s == pytest.approx((6850 + 6000) / 2 * 1e-9)   # averaged over the chips
    assert d0.gaps == [(2000, 2500), (4500, 5000), (6500, 7000), (8000, 8200), (8800, 9000),
                       (9050, 9100), (9500, 9700), (9800, 10800)]
    assert xplane.total(d0.gaps) + d0.busy_ns == d0.window_ns


def test_self_times_add_up_to_busy(red):
    for d in red.devices:
        assert sum(s for _, s in d.ops) == pytest.approx(d.busy_ns)
    selfs = {xplane.parse_hlo(e.name)[0]: s for e, s in red.devices[0].ops}
    assert selfs["while.1"] == 500          # 2000 - 500 - 1000
    assert selfs["fusion.3"] == 500 and selfs["copy.4"] == 1000   # the overlap counts once
    assert selfs["fusion.8"] == 200         # only its part inside the window
    assert "fusion.9" not in selfs


def test_collective_total_and_exposed_time(red):
    d0, d1 = red.devices
    # chip 0: all-gather 600 (synchronous, nothing beside it) + all-reduce in flight 9000-9800
    # (from the Async XLA Ops line) = 1400; fusion.7 hides 400 of the latter
    assert d0.collective_ns == 1400 and d0.collective_exposed_ns == 1000
    # chip 1: all-gather 1000 + permute 4000-5000 matched start-to-done by its number = 2000;
    # fusion.3 hides 800
    assert d1.collective_ns == 2000 and d1.collective_exposed_ns == 1200


def test_collective_reader_reports_the_worst_chip(red):
    run = SimpleNamespace(trace=red, chips=2, traced={"steps": 2})
    cell = manifest.resolve(manifest.load_manifest(), "mistral-7b-v0.3-l8.train-fsdp4-b4-t4096")
    assert cell.reader("collective_ms_per_step").read(run) == pytest.approx(2000 / 2 * 1e-6)
    assert cell.reader("collective_exposed_ms_per_step").read(run) == pytest.approx(1200 / 2 * 1e-6)
    run.chips = 1
    assert cell.reader("collective_ms_per_step").read(run) is None


@pytest.mark.parametrize("in_flight,exposed,want", [(500, 0, 0.0), (500, 100, 100 / 2 * 1e-6),
                                                    (0, 0, None)])
def test_exposed_collective_time_all_hidden_reads_zero(in_flight, exposed, want):
    # the check takes a traced line only with every metric listed for the cell on it: a step
    # that hides all its collectives must still report the metric, as 0
    chip = SimpleNamespace(collective_ns=in_flight, collective_exposed_ns=exposed)
    run = SimpleNamespace(trace=SimpleNamespace(devices=[chip, chip]), chips=2,
                          traced={"steps": 2})
    cell = manifest.resolve(manifest.load_manifest(), "mistral-7b-v0.3-l8.train-fsdp4-b4-t4096")
    got = cell.reader("collective_exposed_ms_per_step").read(run)
    assert got == (want if want is None else pytest.approx(want))


def test_programs_and_host_events(red):
    assert red.devices[0].modules == {"jit_tt_train_step": (3, 3500 + 4800 + 200)}
    assert red.module_seconds(r"^jit_tt_train_step$") == pytest.approx(8500e-9)
    # host events that began inside the window, from the process's own threads only
    assert red.host_events == {"bench:step": (1, 600.0), "serve_decode": (1, 2900.0),
                               "bench:wait": (1, 2800.0)}
    assert red.host_count("serve_decode") == 1 and red.host_count("tpu::System::Execute") == 0


def test_top_operations(red):
    top = red.top_ops(3)
    # fusion kLoop: chip 0 fusion.1 1000 + fusion.3 500 + fusion.8 200, chip 1 fusion.1 2000 +
    # fusion.3 800 = 4500 over two chips; all-gather 600 + 1000; paged decode 1500 on chip 1
    assert top == [["fusion kLoop bf16[8,128] x3", pytest.approx(2250e-9)],
                   ["all-gather bf16[16,128] x1", pytest.approx(800e-9)],
                   ["custom-call bf16[48,8,4,128] x1", pytest.approx(750e-9)]]
    named = red.top_ops(20, readers.breakdown_label(manifest.ROOT))
    labels = [row[0] for row in named]
    assert "pallas paged_decode bf16[48,8,4,128] x1" in labels
    assert "pallas paged_chunk bf16[1,8,2048,128] x1" in labels
    assert "pallas flash_fwd (bf16[1,4,128,64], f32[1,4,128,1]) x1" in labels
    assert "pallas flash_bwd (bf16[1,4,1,128,64], bf16[1,4,128,64], bf16[1,4,128,64]) x1" in labels
    assert len(red.top_ops(10)) == 10


def test_longest_gaps_name_what_the_host_was_doing(red):
    # the chip that idled most is chip 1: 8000-11000 (middle 9500: bench:wait inside
    # serve_decode, the innermost wins) and 5000-6000 (middle 5500: bench:step; the
    # allocator's event on a runtime thread is not the process's own)
    assert red.longest_gaps(5) == [["bench:wait", pytest.approx(3000e-9)],
                                   ["bench:step", pytest.approx(1000e-9)]]


def test_without_a_window_span_the_ops_set_the_window():
    planes = [p for p in xplane.load_json(FIXTURE) if p.name != "/host:CPU"]
    r = xplane.reduce_trace(planes)
    assert r.devices[0].window == (200.0, 11400.0)
    assert r.longest_gaps(1)[0][0] == "no host event"


def test_cpu_rehearsal_stands_host_ops_in_for_a_device():
    host = xplane.Plane("/host:CPU", [xplane.Line("tf_XLAPjRtCpuClient/1", [
        xplane.Event("dot_general.1", 0, 10, {"hlo_op": "dot_general.1"}),
        xplane.Event("ThreadpoolListener::Record", 3, 0, {})])])
    assert xplane.device_planes([host]) == []
    (dev,) = xplane.device_planes([host], host_ops_as_device=True)
    assert [e.name for e in dev.line(xplane.OPS_LINE)] == ["dot_general.1"]


# -- kernel classes and per-class time ----------------------------------------------------------

def fake_run(red, **kw):
    cell = SimpleNamespace(root=manifest.ROOT)
    return SimpleNamespace(trace=red, cell=cell, device_kind="TPU v5 lite", **kw)


@pytest.mark.parametrize("cls,ns,calls", [("flash_fwd", 1000, 1), ("flash_bwd", 1000, 1),
                                          ("paged_decode", 0, 0), ("rms_norm", 0, 0)])
def test_per_class_kernel_time_first_chip(red, cls, ns, calls):
    seconds, n = readers.class_time(fake_run(red), cls)
    assert n == calls and seconds == pytest.approx(ns * 1e-9)


def test_xla_time_is_what_is_neither_pallas_nor_collective(red):
    # chip 0: busy 6850 - flash 2000 - all-gather 600 - start/done 150 = 4100
    # chip 1: busy 6000 - paged 2000 - all-gather 1000 - start/done 200 = 2800
    assert readers.xla_seconds(fake_run(red)) == pytest.approx((4100 + 2800) / 2 * 1e-9)


def test_roofline_share(red):
    # the fixture's flash forward: B=1, H=Hkv=4, T=128, D=64, 1000 ns measured
    cost = costs.flash_fwd(1, 4, 4, 128, 128, 64)
    least, bound = costs.roofline_seconds(cost, V5E)
    assert bound == "bandwidth"
    assert readers.roofline_pct(fake_run(red), "flash_fwd", cost) == pytest.approx(100 * least / 1000e-9)
    assert readers.roofline_pct(fake_run(red), "paged_decode", cost) is None   # no such call on chip 0


# -- operations and bytes, by hand ------------------------------------------------------------------

def test_matmul_cost():
    c = costs.matmul(512, 14336, 4096)
    assert c.flops == 2 * 512 * 14336 * 4096 == 60_129_542_144
    assert c.bytes == (512 * 4096 + 4096 * 14336) * 2 + 512 * 14336 * 2 == 136_314_880
    assert costs.roofline_seconds(c, V5E) == (pytest.approx(60_129_542_144 / 197e12), "compute")


def test_flash_cost():
    # pythia-410m's call: B=4, 16 heads of 64, T=2048, causal: T(T+1)/2 = 2 098 176 pairs
    assert costs.causal_pairs(2048, 2048) == 2_098_176
    fwd = costs.flash_fwd(4, 16, 16, 2048, 2048, 64)
    assert fwd.flops == 4 * 4 * 16 * 64 * 2_098_176 == 34_376_515_584
    one = 4 * 16 * 2048 * 64 * 2            # one (B, H, T, D) bf16 tensor: 16 MiB
    assert fwd.bytes == 4 * one + 4 * 16 * 2048 * 4 == 67_633_152
    bwd = costs.flash_bwd(4, 16, 16, 2048, 2048, 64)
    assert bwd.flops == 2.5 * fwd.flops and bwd.bytes == 8 * one + 4 * 16 * 2048 * 4
    # GQA reads fewer keys and values
    gqa = costs.flash_fwd(1, 32, 8, 4096, 4096, 128)
    assert gqa.bytes == 2 * 32 * 4096 * 128 * 2 + 2 * 8 * 4096 * 128 * 2 + 32 * 4096 * 4
    # the last 512 queries of a 1024-long context keep 512 * 512 + 512 * 513 / 2 pairs
    assert costs.causal_pairs(512, 1024) == 262_144 + 131_328


def test_paged_decode_cost():
    # 24 sequences holding 10 000 cached tokens between them, Mistral's heads (32 / 8 x 128)
    c = costs.paged_decode(10_000, 24, 32, 8, 128)
    assert c.flops == 4 * 32 * 128 * 10_000 == 163_840_000
    assert c.bytes == 2 * 8 * 128 * 10_000 * 2 + 2 * 24 * 32 * 128 * 2 == 41_353_216
    assert costs.roofline_seconds(c, V5E) == (pytest.approx(41_353_216 / 819e9), "bandwidth")


def test_paged_chunk_cost():
    # a 512-token chunk at position 2048: each query sees 2048 earlier keys plus the causal
    # part of its own chunk
    c = costs.paged_chunk(512, 2048, 32, 8, 128)
    assert c.flops == 4 * 32 * 128 * (512 * 2048 + 512 * 513 // 2) == 19_331_547_136
    assert c.bytes == 2 * 32 * 512 * 128 * 2 + 2 * 8 * 2560 * 128 * 2 == 18_874_368
    assert costs.roofline_seconds(c, V5E)[1] == "compute"


@pytest.mark.parametrize("config,seq_len,want", [
    # 24 x (1024 x 3072 + 1024 x 1024 + 2 x 1024 x 4096) + 50304 x 1024 weights, and attention
    ("pythia-410m", 2048, 6 * (24 * 12_582_912 + 51_511_296) + 6 * 24 * 1024 * 2048),
    # 8 x (4096 x 6144 + 4096 x 4096 + 3 x 4096 x 14336) + 32768 x 4096
    ("mistral-7b-v0.3-l8", 4096, 6 * (8 * 218_103_808 + 134_217_728) + 6 * 8 * 4096 * 4096),
])
def test_train_flops_per_token(config, seq_len, want):
    man = manifest.load_manifest()
    cell = next(w["name"] for w in man["workloads"] if w["config"] == config)
    c = manifest.resolve(man, cell)
    assert costs.train_flops_per_token(seq_len=seq_len, **c.builder.dims(c.config)) == want


def test_peaks_table_has_the_v5e_and_refuses_the_unknown():
    assert V5E.bf16_flops == 197e12 and V5E.hbm_bytes_per_s == 819e9 and V5E.hbm_bytes == 16e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
