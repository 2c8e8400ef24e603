"""Device-time attribution + FLOPs accounting (ISSUE 8 tentpole):
the region registry round-trip, the per-symbol cost model (cross-checked
against XLA's cost_analysis), trace-event attribution, and the tier-1-safe
CPU smoke test that runs one profiled step end to end (capture → parse →
report) so the profiler path can't rot between TPU runs.
"""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import observability
from thunder_tpu.observability import flops as obs_flops
from thunder_tpu.observability import profiler as obs_profiler
from thunder_tpu.ops import ltorch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_obs_summary():
    spec = importlib.util.spec_from_file_location(
        "obs_summary", os.path.join(REPO, "tools", "obs_summary.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fusion_bsyms(cfn):
    """Fusion-executor regions of the compiled function's execution trace."""
    ex_trc = tt.last_traces(cfn)[-1]
    return [b for b in ex_trc.bound_symbols
            if getattr(b.sym, "executor", None) is not None
            and b.sym.executor.is_fusion_executor()]


# ---------------------------------------------------------------------------
# region registry: named_scope name <-> BoundSymbol ids round-trip
# ---------------------------------------------------------------------------


class TestRegionRegistry:
    def test_every_fusion_region_resolves_to_its_bsym_ids(self):
        def f(x, w):
            h = ltorch.tanh(ltorch.matmul(x, w))
            return ltorch.sum(ltorch.mul(h, h))

        cfn = tt.jit(f)
        x = jnp.ones((16, 16))
        cfn(x, x)
        fusions = _fusion_bsyms(cfn)
        assert fusions, "no fusion regions formed"
        for b in fusions:
            resolved = observability.resolve(b.sym.name)
            assert resolved == [s.sym.name for s in b.subsymbols], (
                f"region {b.sym.name} did not round-trip: {resolved}")
            info = observability.region_info(b.sym.name)
            assert info["executor"] == "xla"
            assert info["flops"] > 0

    def test_jitted_region_callable_named_after_region(self):
        # the hlo_module join (profiler.py) relies on jit_<region name>
        def f(x, w):
            return ltorch.sum(ltorch.tanh(ltorch.matmul(x, w)))

        cfn = tt.jit(f)
        x = jnp.ones((8, 8))
        cfn(x, x)
        (b,) = _fusion_bsyms(cfn)
        assert b.impl.jitted.__name__ == b.sym.name

    def test_unknown_region_resolves_empty(self):
        assert observability.resolve("no_such_region_xyz") == []


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------


class TestCostModel:
    def test_lone_matmul_flops_match_analytic(self):
        M = K = N = 32

        def f(x, w):
            return ltorch.matmul(x, w)

        cfn = tt.jit(f)
        x = jnp.ones((M, K), jnp.float32)
        w = jnp.ones((K, N), jnp.float32)
        cfn(x, w)
        (b,) = _fusion_bsyms(cfn)
        cost = b.cost()
        assert cost["flops"] == 2.0 * M * N * K
        # interface bytes: two f32 inputs + one f32 output
        assert cost["bytes"] == 4 * (M * K + K * N + M * N)
        # and the registry carries the same annotation
        assert observability.region_info(b.sym.name)["flops"] == cost["flops"]

    def test_matmul_flops_cross_check_xla_cost_analysis(self):
        def f(x, w):
            return ltorch.matmul(x, w)

        cfn = tt.jit(f)
        x = jnp.ones((64, 64), jnp.float32)
        cfn(x, x)
        (b,) = _fusion_bsyms(cfn)
        xla = obs_flops.xla_cost(b.impl.jitted.lower(x, x).compile())
        if xla is None:
            pytest.skip("backend does not expose cost_analysis")
        model = b.cost()["flops"]
        # XLA counts the same 2*M*N*K MACs; allow a few % for epsilon ops
        assert model == pytest.approx(xla["flops"], rel=0.05)

    def test_elementwise_and_reduction_costs(self):
        from thunder_tpu.core.proxies import TensorProxy
        from thunder_tpu.core import dtypes
        from thunder_tpu.core.prims import PrimIDs, get_prim
        from thunder_tpu.core.symbol import BoundSymbol

        t = TensorProxy(name="t0", shape=(8, 8), dtype=dtypes.float32, device="cpu")
        out = TensorProxy(name="t1", shape=(8, 8), dtype=dtypes.float32, device="cpu")
        b = BoundSymbol(get_prim(PrimIDs.EXP), (t,), {}, out)
        c = obs_flops.bsym_cost(b)
        assert c["flops"] == 64
        assert c["bytes"] == 2 * 64 * 4
        red_out = TensorProxy(name="t2", shape=(), dtype=dtypes.float32, device="cpu")
        r = BoundSymbol(get_prim(PrimIDs.SUM), (t,), {}, red_out)
        assert obs_flops.bsym_cost(r)["flops"] == 64

    def test_cost_fn_annotation_overrides_model(self):
        from thunder_tpu.core.proxies import TensorProxy
        from thunder_tpu.core import dtypes
        from thunder_tpu.core.symbol import BoundSymbol, Symbol

        sym = Symbol("custom_kernel", None, is_prim=True,
                     cost_fn=lambda bsym: {"flops": 123.0, "bytes": 456})
        t = TensorProxy(name="t0", shape=(4,), dtype=dtypes.float32, device="cpu")
        b = BoundSymbol(sym, (t,), {}, t)
        assert obs_flops.bsym_cost(b) == {"flops": 123.0, "bytes": 456}

    def test_peaks_table_has_no_default(self):
        """One table keyed by device_kind; a device that is not in it is an
        error, not a v5e."""
        assert obs_flops.device_peaks("TPU v5 lite") == (197.0, 819.0)
        assert obs_flops.device_peaks() == obs_flops.DEVICE_PEAKS[
            jax.devices()[0].device_kind]
        with pytest.raises(KeyError, match="TPU v9"):
            obs_flops.device_peaks("TPU v9")

    def test_roofline_tags(self):
        peaks = (100.0, 100.0)  # ridge = 1000 flops/byte
        assert obs_flops.roofline_tag(1e9, 10, peaks=peaks) == "compute-bound"
        assert obs_flops.roofline_tag(10, 1e9, peaks=peaks) == "memory-bound"
        assert obs_flops.roofline_tag(1e9, 10, category="collective",
                                      peaks=peaks) == "comms-bound"
        assert obs_flops.roofline_tag(0, 0, category="transfer") == "comms-bound"

    def test_structural_ops_are_free(self):
        from thunder_tpu.core import prims

        ret = prims.python_return.bind((), output=None)
        assert obs_flops.bsym_cost(ret) == {"flops": 0.0, "bytes": 0}


class TestCollectiveBytes:
    """Ring-model collective pricing (ISSUE 18 satellite): an N-way
    two-pass collective moves 2(N-1)/N of the buffer per participant,
    one-pass collectives (N-1)/N — not one flat buffer width."""

    @pytest.fixture(autouse=True)
    def _clear_axis_sizes(self):
        obs_flops.set_axis_sizes(None)
        yield
        obs_flops.set_axis_sizes(None)

    @staticmethod
    def _t(name, shape):
        from thunder_tpu.core import dtypes
        from thunder_tpu.core.proxies import TensorProxy

        return TensorProxy(name=name, shape=shape, dtype=dtypes.float32,
                           device="cpu")

    def test_all_reduce_prices_ring_two_pass(self):
        from thunder_tpu.core.symbol import BoundSymbol
        from thunder_tpu.parallel import prims as dist

        t = self._t("t0", (8, 8))  # S = 256 bytes
        b = BoundSymbol(dist.all_reduce, (t, "dp"), {}, self._t("t1", (8, 8)))
        obs_flops.set_axis_sizes({"dp": 8})
        assert obs_flops.collective_bytes(b) == int(2 * 7 / 8 * 256)
        # mesh registration is what carries N: unknown axis falls back to
        # N=2, which reproduces the old one-buffer-width price
        obs_flops.set_axis_sizes(None)
        assert obs_flops.collective_bytes(b) == 256

    def test_all_gather_prices_one_pass_on_full_buffer(self):
        from thunder_tpu.core.symbol import BoundSymbol
        from thunder_tpu.parallel import prims as dist

        # S is the FULL post-gather buffer (the output), not the shard
        shard = self._t("t0", (8, 8))      # 256 B
        full = self._t("t1", (32, 8))      # 1024 B
        b = BoundSymbol(dist.all_gather, (shard, "fsdp"),
                        {"world_size": 4}, full)
        assert obs_flops.collective_bytes(b) == int(3 / 4 * 1024)

    def test_synchronize_barrier_prices_one_buffer(self):
        from thunder_tpu.core.symbol import BoundSymbol
        from thunder_tpu.parallel import prims as dist

        t = self._t("t0", (16,))  # 64 B
        b = BoundSymbol(dist.synchronize, (t, "dp"), {}, self._t("t1", (16,)))
        obs_flops.set_axis_sizes({"dp": 8})
        assert obs_flops.collective_bytes(b) == 64

    def test_bsym_cost_routes_collectives_through_ring_model(self):
        from thunder_tpu.core.symbol import BoundSymbol
        from thunder_tpu.parallel import prims as dist

        t = self._t("t0", (8, 8))
        b = BoundSymbol(dist.all_reduce, (t, "dp"), {}, self._t("t1", (8, 8)))
        obs_flops.set_axis_sizes({"dp": 4})
        cost = obs_flops.bsym_cost(b)
        assert cost["bytes"] == int(2 * 3 / 4 * 256)
        assert cost["flops"] == 64.0  # one combine per output element

    def test_make_mesh_registers_axis_sizes(self):
        import jax

        from thunder_tpu.parallel import make_mesh

        n = min(4, len(jax.devices()))
        if n < 2:
            pytest.skip("single-device environment")
        make_mesh({"dp": n}, devices=jax.devices()[:n])
        t = self._t("t0", (8, 8))
        from thunder_tpu.core.symbol import BoundSymbol
        from thunder_tpu.parallel import prims as dist

        b = BoundSymbol(dist.all_reduce, (t, "dp"), {}, self._t("t1", (8, 8)))
        assert obs_flops.collective_bytes(b) == int(2 * (n - 1) / n * 256)


# ---------------------------------------------------------------------------
# attribution over a synthetic trace-event stream (no live profiler)
# ---------------------------------------------------------------------------


def _synthetic_events():
    return [
        {"ph": "M", "pid": 1, "name": "process_name", "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 1, "tid": 9, "name": "thread_name",
         "args": {"name": "tf_XLATfrtCpuClient/123"}},
        # joined by hlo_module
        {"ph": "X", "pid": 1, "tid": 9, "ts": 10.0, "dur": 100.0, "name": "dot.3",
         "args": {"hlo_module": "jit_xla_fusion_7", "hlo_op": "dot.3"}},
        {"ph": "X", "pid": 1, "tid": 9, "ts": 120.0, "dur": 40.0, "name": "tanh.1",
         "args": {"hlo_module": "jit_xla_fusion_7", "hlo_op": "tanh.1"}},
        # joined through the op map: the instruction's scope path in its executable's HLO
        {"ph": "X", "pid": 1, "tid": 9, "ts": 170.0, "dur": 30.0,
         "name": "fusion.9", "args": {"hlo_module": "jit_tt_train_step", "hlo_op": "fusion.9"}},
        # a collective and a transfer
        {"ph": "X", "pid": 1, "tid": 9, "ts": 210.0, "dur": 25.0,
         "name": "all-reduce.2", "args": {"hlo_module": "jit_xla_fusion_7"}},
        {"ph": "X", "pid": 1, "tid": 9, "ts": 240.0, "dur": 15.0,
         "name": "MemcpyH2D", "args": {"hlo_op": "copy-start.1"}},
        # unattributed device work
        {"ph": "X", "pid": 1, "tid": 9, "ts": 260.0, "dur": 5.0,
         "name": "reduce.8", "args": {"hlo_module": "jit_something_else"}},
        # host-side python event: ignored entirely
        {"ph": "X", "pid": 1, "tid": 2, "ts": 0.0, "dur": 500.0, "name": "PjitFunction(f)"},
    ]


_STEP_HLO = """HloModule jit_tt_train_step, is_scheduled=true

%fused_computation.9 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%p0, %p0), metadata={op_name="jit(tt_train_step)/tt_optimizer/add"}
}

ENTRY %main.1 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %dot.4 = f32[8]{0} dot(%a, %a), metadata={op_name="jit(tt_train_step)/tt_fwd_bwd/jit(xla_fusion_12)/xla_fusion_12/bwd/mlp/dot_general"}
  ROOT %fusion.9 = f32[8]{0} fusion(%dot.4), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(tt_train_step)/tt_optimizer/add"}
}
"""


def _step_op_map():
    ops = obs_profiler.parse_hlo_text(_STEP_HLO)
    return {ops.module: ops}


class TestAttribution:
    def test_synthetic_breakdown(self):
        regions = {
            "xla_fusion_7": {"bsym_ids": ["matmul", "tanh"], "flops": 1000.0,
                             "bytes": 100, "executor": "xla", "kind": "compute"},
            "tt_optimizer": {"bsym_ids": [], "flops": 0.0, "bytes": 0,
                             "executor": "trainstep", "kind": "compute"},
        }
        prof = obs_profiler.attribute(_synthetic_events(), region_map=regions, n_steps=1,
                                      op_map=_step_op_map())
        assert prof.total_device_us == pytest.approx(215.0)  # host event excluded
        assert prof.regions["xla_fusion_7"].us == pytest.approx(165.0)
        assert prof.regions["tt_optimizer"].us == pytest.approx(30.0)
        assert prof.unattributed_us == pytest.approx(20.0)  # memcpy + alien module
        assert prof.categories["collective"] == pytest.approx(25.0)
        assert prof.categories["transfer"] == pytest.approx(15.0)
        assert prof.attributed_frac == pytest.approx(195.0 / 215.0)
        # every attributed region carries a roofline tag
        assert all(r.roofline for r in prof.regions.values())
        # the report renders
        assert "xla_fusion_7" in prof.table()
        # the collective (210-235) and memcpy (240-255) sit in compute gaps:
        # all comms time is exposed, none hidden
        assert prof.overlapped_comms_us == pytest.approx(0.0)
        assert prof.exposed_comms_us == pytest.approx(40.0)
        assert prof.overlap_frac == pytest.approx(0.0)

    @pytest.mark.parametrize("hlo_op, wanted", [
        ("dot.4", "xla_fusion_12"),     # exact segment: never the prefix xla_fusion_1
        ("fusion.9", "tt_optimizer"),   # no fusion region on the path: the phase
        ("copy.7", "tt_train_step"),    # not in the map: the module's own name
    ])
    def test_finest_registered_region_on_the_path_wins(self, hlo_op, wanted):
        regions = {
            "xla_fusion_1": {"bsym_ids": [], "flops": 0.0, "bytes": 0},
            "xla_fusion_12": {"bsym_ids": [], "flops": 0.0, "bytes": 0},
            "tt_fwd_bwd": {"bsym_ids": [], "flops": 0.0, "bytes": 0, "level": 1},
            "tt_optimizer": {"bsym_ids": [], "flops": 0.0, "bytes": 0, "level": 1},
            "tt_train_step": {"bsym_ids": [], "flops": 0.0, "bytes": 0, "level": 2},
        }
        evs = [{"ph": "X", "pid": 1, "tid": 9, "ts": 0.0, "dur": 10.0, "name": hlo_op,
                "args": {"hlo_module": "jit_tt_train_step(42)", "hlo_op": hlo_op}}]
        prof = obs_profiler.attribute(evs, region_map=regions, op_map=_step_op_map())
        assert set(prof.regions) == {wanted}

    def test_a_name_in_an_events_text_is_no_join(self):
        # the substring join is gone: a region's name inside an event's name or metadata
        # puts nothing on it
        regions = {"xla_fusion_12": {"bsym_ids": [], "flops": 0.0, "bytes": 0}}
        evs = [{"ph": "X", "pid": 1, "tid": 9, "ts": 0.0, "dur": 10.0,
                "name": "fusion", "args": {"tf_op": "step/xla_fusion_12/dot", "hlo_op": "fusion"}}]
        prof = obs_profiler.attribute(evs, region_map=regions, op_map={})
        assert not prof.regions and prof.unattributed_us == pytest.approx(10.0)

    def test_a_store_served_region_goes_by_the_name_it_was_registered_under(self):
        # the publishing process called it xla_fusion_3; here it runs as xla_fusion_7
        text = _STEP_HLO.replace("jit_tt_train_step", "jit_xla_fusion_3").replace(
            "tt_train_step", "xla_fusion_3")
        ops = obs_profiler.parse_hlo_text(text, region="xla_fusion_7")
        regions = {"xla_fusion_7": {"bsym_ids": [], "flops": 0.0, "bytes": 0}}
        evs = [{"ph": "X", "pid": 1, "tid": 9, "ts": 0.0, "dur": 10.0, "name": "dot.4",
                "args": {"hlo_module": "jit_xla_fusion_3", "hlo_op": "dot.4"}}]
        prof = obs_profiler.attribute(evs, region_map=regions, op_map={ops.module: ops})
        assert set(prof.regions) == {"xla_fusion_7"}


# ---------------------------------------------------------------------------
# communication-overlap attribution (ISSUE 18 tentpole): the concurrency
# sweep splitting each comms slice into overlapped vs exposed time
# ---------------------------------------------------------------------------


def _ev(name, ts, dur, pid=1, **args):
    return {"ph": "X", "pid": pid, "tid": 9, "ts": ts, "dur": dur,
            "name": name, "args": args}


class TestOverlapAttribution:
    REGIONS = {
        "xla_fusion_7": {"bsym_ids": [], "flops": 1000.0, "bytes": 100},
        "grad_sync": {"bsym_ids": [], "flops": 0.0, "bytes": 0},
    }

    def test_fully_overlapped_collective(self):
        # collective [20,50] lives entirely inside compute [0,100]
        prof = obs_profiler.attribute([
            _ev("fusion.5", 0.0, 100.0, hlo_module="jit_xla_fusion_7"),
            _ev("all-reduce.2", 20.0, 30.0, hlo_module="jit_grad_sync"),
        ], region_map=self.REGIONS)
        assert prof.overlapped_comms_us == pytest.approx(30.0)
        assert prof.exposed_comms_us == pytest.approx(0.0)
        assert prof.overlap_frac == pytest.approx(1.0)
        rt = prof.regions["grad_sync"]
        assert rt.overlapped_us == pytest.approx(30.0)
        assert rt.exposed_us == pytest.approx(0.0)
        assert rt.overlap_frac == pytest.approx(1.0)

    def test_fully_exposed_collective(self):
        # collective [150,180] starts after all compute ended
        prof = obs_profiler.attribute([
            _ev("fusion.5", 0.0, 100.0, hlo_module="jit_xla_fusion_7"),
            _ev("all-reduce.2", 150.0, 30.0, hlo_module="jit_grad_sync"),
        ], region_map=self.REGIONS)
        assert prof.overlapped_comms_us == pytest.approx(0.0)
        assert prof.exposed_comms_us == pytest.approx(30.0)
        assert prof.overlap_frac == pytest.approx(0.0)
        assert prof.regions["grad_sync"].overlap_frac == pytest.approx(0.0)

    def test_partial_overlap_exact_fractions(self):
        # collective [80,140] against compute [0,100]: 20 us hidden,
        # 40 us exposed -> overlap_frac exactly 1/3
        prof = obs_profiler.attribute([
            _ev("fusion.5", 0.0, 100.0, hlo_module="jit_xla_fusion_7"),
            _ev("all-reduce.2", 80.0, 60.0, hlo_module="jit_grad_sync"),
        ], region_map=self.REGIONS)
        assert prof.overlapped_comms_us == pytest.approx(20.0)
        assert prof.exposed_comms_us == pytest.approx(40.0)
        assert prof.overlap_frac == pytest.approx(1.0 / 3.0)
        rt = prof.regions["grad_sync"]
        assert rt.overlapped_us == pytest.approx(20.0)
        assert rt.exposed_us == pytest.approx(40.0)
        assert rt.overlap_frac == pytest.approx(1.0 / 3.0)
        # the split rides as_dict/summary_dict into the bus payload
        d = rt.as_dict()
        assert d["overlapped_us"] == pytest.approx(20.0)
        assert d["exposed_us"] == pytest.approx(40.0)
        assert d["overlap_frac"] == pytest.approx(1.0 / 3.0, abs=1e-4)
        s = prof.summary_dict()
        assert s["exposed_comms_us"] == pytest.approx(40.0)
        assert s["overlap_frac"] == pytest.approx(1.0 / 3.0, abs=1e-4)
        # and the table grows the comms-overlap footer
        assert "comms overlap" in prof.table()

    def test_compute_on_another_device_does_not_hide_comms(self):
        # compute on pid 1, collective on pid 2 at the same wall time:
        # per-device unions must NOT count that as overlap
        prof = obs_profiler.attribute([
            _ev("fusion.5", 0.0, 100.0, pid=1, hlo_module="jit_xla_fusion_7"),
            _ev("all-reduce.2", 20.0, 30.0, pid=2, hlo_module="jit_grad_sync"),
        ], region_map=self.REGIONS)
        assert prof.overlapped_comms_us == pytest.approx(0.0)
        assert prof.exposed_comms_us == pytest.approx(30.0)

    def test_unattributed_comms_still_counts_as_exposed(self):
        # a memcpy matching no region must still show up in the
        # profile-level exposure (the comms tax exists even unattributed)
        prof = obs_profiler.attribute([
            _ev("fusion.5", 0.0, 100.0, hlo_module="jit_xla_fusion_7"),
            _ev("MemcpyD2H", 110.0, 15.0, hlo_op="copy-start.1"),
        ], region_map=self.REGIONS)
        assert prof.exposed_comms_us == pytest.approx(15.0)
        assert prof.unattributed_us == pytest.approx(15.0)

    def test_abutting_compute_slices_merge_into_one_interval(self):
        # [0,50] + [50,100] must merge; collective [40,60] fully hidden
        prof = obs_profiler.attribute([
            _ev("fusion.5", 0.0, 50.0, hlo_module="jit_xla_fusion_7"),
            _ev("fusion.6", 50.0, 50.0, hlo_module="jit_xla_fusion_7"),
            _ev("all-reduce.2", 40.0, 20.0, hlo_module="jit_grad_sync"),
        ], region_map=self.REGIONS)
        assert prof.overlapped_comms_us == pytest.approx(20.0)
        assert prof.exposed_comms_us == pytest.approx(0.0)

    def test_no_comms_leaves_overlap_frac_none(self):
        prof = obs_profiler.attribute([
            _ev("fusion.5", 0.0, 100.0, hlo_module="jit_xla_fusion_7"),
        ], region_map=self.REGIONS)
        assert prof.overlap_frac is None
        assert "comms overlap" not in prof.table()

# ---------------------------------------------------------------------------
# CPU smoke: one profiled step end to end (capture -> parse -> report)
# ---------------------------------------------------------------------------


class TestProfiledStepSmoke:
    def test_profile_steps_end_to_end(self, tmp_path):
        def f(x, w):
            return ltorch.sum(ltorch.tanh(ltorch.matmul(x, w)))

        cfn = tt.jit(f)
        x = jnp.ones((64, 64), jnp.float32)
        cfn(x, x)  # compile outside the capture window

        observability.reset()
        observability.enable()
        try:
            prof = observability.profile_steps(lambda: cfn(x, x), n=2, warmup=1)
            if prof is None:
                pytest.skip("jax profiler capture unavailable in this environment")
            assert prof.n_steps == 2
            assert prof.total_device_us > 0
            # the fusion region's device time was found and attributed
            region_names = set(prof.regions)
            assert any(n.startswith("xla_fusion_") for n in region_names), region_names
            assert prof.attributed_frac > 0.5
            # every region carries a roofline tag and the table renders
            assert all(r.roofline for r in prof.regions.values())
            table = prof.table()
            assert "device time:" in table and "roofline" in table
            # measured MFU is computable from the cost-model flops
            assert prof.mfu_measured() is not None
            # the overlap keys exist end to end (exact values are pinned by
            # the synthetic fixtures; a compute-only window may be all-zero)
            s = prof.summary_dict()
            assert "overlap_frac" in s and "exposed_comms_us" in s

            # the breakdown landed on the bus -> JSONL -> `perf` CLI view
            shard = str(tmp_path / "t.jsonl")
            observability.dump(shard)
            mod = _load_obs_summary()
            recs = mod.load_many([shard])
            out = mod.render_perf(recs)
            assert "device-time breakdown" in out
            assert "xla_fusion_" in out
        finally:
            observability.disable()
            observability.reset()


# ---------------------------------------------------------------------------
# obs_summary perf subcommand plumbing
# ---------------------------------------------------------------------------


class TestPerfReportCLI:
    def test_perf_subcommand_renders_recorded_profile(self, tmp_path, capsys):
        mod = _load_obs_summary()
        shard = tmp_path / "p.jsonl"
        profile = {
            "n_steps": 3, "total_device_us": 1000.0, "compute_us": 900.0,
            "collective_us": 50.0, "transfer_us": 25.0, "unattributed_us": 25.0,
            "attributed_frac": 0.975, "mfu_measured": 0.41,
            "regions": {"xla_fusion_0": {
                "us": 900.0, "count": 3, "category": "compute",
                "flops": 1e9, "bytes": 1e6, "intensity": 1000.0,
                "roofline": "compute-bound", "mfu": 0.41, "bsym_ids": ["matmul"]}},
        }
        shard.write_text(json.dumps(
            {"kind": "event", "name": "device_profile", "ts_ms": 1.0,
             "pid": 7, "attrs": {"profile": profile}}) + "\n")
        rc = mod.main(["perf", str(shard)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "mfu_measured=0.410" in out
        assert "compute-bound" in out
        assert "xla_fusion_0" in out
