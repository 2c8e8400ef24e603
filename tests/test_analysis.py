"""Static-analysis framework: pass-interposed verification, adversarial
corruption fixtures, the unified memory-budget API, and re-inference.

The contract under test (ISSUE 12 acceptance):
  - with checking enabled, every transform and executor pass in the
    train-step and paged-serving pipelines verifies with ZERO violations;
  - each deliberately-broken invariant (use-after-DEL, reordered effect,
    metadata drift, donation read-back, oversized region) fails with a
    diagnostic naming the offending pass and bsym index;
  - the budget API reproduces the pallas VMEM-decline decisions and the
    live-range estimator prices traces sanely.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import analysis, nn, optim
from thunder_tpu.analysis import TraceCheckError, budget
from thunder_tpu.analysis import manager as an_manager
from thunder_tpu.core import dtypes as dt
from thunder_tpu.core import prims
from thunder_tpu.core.proxies import TensorProxy
from thunder_tpu.core.symbol import BoundSymbol
from thunder_tpu.core.trace import TraceCtx, from_trace
from thunder_tpu.core.transform_common import Transform
from thunder_tpu.observability import events as obs_events
from thunder_tpu.ops import ltorch
from thunder_tpu.training import TrainStep

pytestmark = pytest.mark.analysis


@pytest.fixture(autouse=True)
def _clean_analysis_state():
    an_manager.clear_last_failure()
    budget.set_region_budget(None)
    yield
    an_manager.clear_last_failure()
    budget.set_region_budget(None)


class _Net(nn.Module):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(8, 16, seed=1)
        self.fc2 = nn.Linear(16, 4, seed=2)

    def forward(self, x, y):
        return ltorch.mse_loss(self.fc2(ltorch.gelu(self.fc1(x))), y)


def _batch():
    rng = np.random.RandomState(7)
    return (jnp.asarray(rng.randn(4, 8), jnp.float32), jnp.zeros((4, 4), jnp.float32))


# ---------------------------------------------------------------------------
# acceptance smoke: today's pipelines verify clean under TT_CHECK_TRACES=1
# ---------------------------------------------------------------------------


class TestCheckedPipelinesSmoke:
    def test_train_step_zero_violations(self):
        obs_events.reset()
        obs_events.enable()
        try:
            with analysis.override(1):
                step = TrainStep(tt.jit(_Net()), optim.AdamW(lr=1e-2))
                x, y = _batch()
                float(step(x, y))
            counters = obs_events.counters()
            assert counters.get("analysis.checks", 0) > 0
            assert counters.get("analysis.violations", 0) == 0
        finally:
            obs_events.disable()
            obs_events.reset()

    def test_transform_stack_zero_violations(self):
        from thunder_tpu.transforms.autocast import AutocastTransform
        from thunder_tpu.transforms.quantization import QuantizeInt8Transform
        from thunder_tpu.transforms.remat import RematTransform

        with analysis.override(1), analysis.session() as sess:
            tfs = [AutocastTransform(), RematTransform(), QuantizeInt8Transform()]
            step = TrainStep(tt.jit(_Net(), transforms=tfs), optim.AdamW(lr=1e-2))
            x, y = _batch()
            float(step(x, y))
        assert sess.checks > 0
        assert sess.violations == 0
        # the autodiff split and every transform/executor pass were verified
        passes = {r["pass"] for r in sess.rows}
        assert "autodiff:augmented-forward" in passes
        assert "executor:claim" in passes
        assert any(p.startswith("transform:") for p in passes)

    @pytest.mark.serve
    def test_serving_drain_zero_violations(self):
        from thunder_tpu.models.litgpt import Config, GPT
        from thunder_tpu.serving import ServingEngine

        cfg = Config.from_name("tiny-llama2", block_size=64)
        gpt = GPT(cfg, dtype=jnp.float32)
        with analysis.override(1), analysis.session() as sess:
            eng = ServingEngine(gpt, max_batch=4, page_size=8, max_seq=64,
                                dtype=jnp.float32)
            try:
                f1 = eng.submit([1, 2, 3], max_new_tokens=6, seed=1)
                f2 = eng.submit([4, 5], max_new_tokens=4, seed=2)
                eng.drain()
                assert len(f1.result().tokens) and len(f2.result().tokens)
            finally:
                eng.stop()
        assert sess.checks > 0
        assert sess.violations == 0

    def test_debug_options_force_without_env(self):
        from thunder_tpu.core.options import DebugOptions

        with analysis.override(0), analysis.session() as sess:
            cf = tt.jit(lambda x: ltorch.sum(ltorch.relu(x)),
                        debug_options=DebugOptions(check_traces=True))
            cf(jnp.ones((3, 3)))
        assert sess.checks > 0  # option forced checking with the env off

    def test_disabled_is_zero_work(self):
        with analysis.override(0), analysis.session() as sess:
            cf = tt.jit(lambda x: ltorch.sum(ltorch.relu(x)))
            cf(jnp.ones((3, 3)))
        assert sess.checks == 0 and sess.violations == 0

    def test_debug_options_force_covers_train_step(self):
        from thunder_tpu.core.options import DebugOptions

        with analysis.override(0), analysis.session() as sess:
            step = TrainStep(
                tt.jit(_Net(), debug_options=DebugOptions(check_traces=True)),
                optim.AdamW(lr=1e-2))
            x, y = _batch()
            float(step(x, y))
        assert sess.checks > 0, "option not threaded through the vag pipeline"
        assert sess.violations == 0
        passes = {r["pass"] for r in sess.rows}
        assert "autodiff:augmented-forward" in passes
        assert "executor:claim" in passes

    def test_env_levels_clamp_up(self, monkeypatch):
        with analysis.override(None):
            for val, want in (("0", 0), ("1", 1), ("2", 2), ("3", 2),
                              ("on", 1), ("", 0), ("junk", 0)):
                monkeypatch.setenv("TT_CHECK_TRACES", val)
                assert an_manager.enabled() == want, val

    def test_train_step_trace_carries_donation(self):
        # TrainStep(donate=True) annotates the params as donated on the
        # traced program, so the alias analysis guards the real pipeline
        with analysis.override(1):
            step = TrainStep(tt.jit(_Net()), optim.AdamW(lr=1e-2))
            x, y = _batch()
            float(step(x, y))
        fwd_claimed = step.compile_stats.last_traces[-1]
        donated = getattr(fwd_claimed, "donated", set())
        assert donated, "donated annotation lost on the claimed forward"
        arg_names = {p.name for p in fwd_claimed.args}
        assert donated <= arg_names


# ---------------------------------------------------------------------------
# adversarial corruption: each broken invariant names the pass + bsym index
# ---------------------------------------------------------------------------


class _CorruptUseAfterDel(Transform):
    """Moves a DEL before a use: the classic freed-too-early transform bug."""

    def transform_trace_post_optimization(self, trc, *, compile_data=None):
        out = from_trace(trc)
        bsyms = list(trc.bound_symbols)
        for i, b in enumerate(bsyms):
            args = [p for p in b.flat_proxy_args()]
            if args and b.sym.id not in (prims.PrimIDs.DEL, prims.PrimIDs.RETURN):
                bsyms.insert(i, prims.python_del.bind(args[0], output=None))
                break
        out.bound_symbols = bsyms
        return out


class _CorruptMetadataDrift(Transform):
    """Rewrites a consumer's input proxy to a different dtype under the SAME
    name — the inconsistent-rewrite class of transform bug."""

    def transform_trace_post_optimization(self, trc, *, compile_data=None):
        out = from_trace(trc)
        bsyms = list(trc.bound_symbols)
        for i, b in enumerate(bsyms):
            outs = [o for o in b.flat_proxy_outs() if isinstance(o, TensorProxy)]
            if not outs:
                continue
            victim = outs[0]
            clone = TensorProxy(victim.name, shape=victim.shape, dtype=dt.int32,
                                device=victim.device)
            for j in range(i + 1, len(bsyms)):
                if any(p.name == victim.name for p in bsyms[j].flat_proxy_args()):
                    new_args = tuple(
                        clone if (isinstance(a, TensorProxy) and a.name == victim.name)
                        else a for a in bsyms[j].args)
                    bsyms[j] = bsyms[j].replace(args=new_args)
                    out.bound_symbols = bsyms
                    return out
        out.bound_symbols = bsyms
        return out


class _CorruptDonationReadBack(Transform):
    """Marks the first trace arg donated, consumes its buffer with a write,
    then reads the stale arg — exactly what a broken donation-aware rewrite
    would emit."""

    def transform_trace_post_optimization(self, trc, *, compile_data=None):
        out = from_trace(trc)
        bsyms = list(trc.bound_symbols)
        arg = next(p for p in trc.args if isinstance(p, TensorProxy))
        written = TensorProxy(shape=arg.shape, dtype=arg.dtype, device=arg.device)
        stale = TensorProxy(shape=arg.shape, dtype=arg.dtype, device=arg.device)
        write = prims.copy_with_setitem.bind(arg, 0, 1.0, output=written)
        read = prims.neg.bind(arg, output=stale)  # stale read of the donated buffer
        ret = bsyms.index(next(b for b in bsyms if b.sym.id == prims.PrimIDs.RETURN))
        bsyms[ret:ret] = [write, read]
        out.bound_symbols = bsyms
        out.donated = {arg.name}
        return out


def _run_corrupted(transform):
    cf = tt.jit(lambda x: ltorch.sum(ltorch.relu(x) * 2.0),
                transforms=[transform], disable_fusion=True)
    cf(jnp.ones((3, 3)))


class TestAdversarialCorruption:
    def _expect(self, transform, kind, pass_prefix="transform_post:"):
        with analysis.override(1):
            with pytest.raises(TraceCheckError) as ei:
                _run_corrupted(transform)
        e = ei.value
        assert e.kind == kind
        assert e.pass_name == f"{pass_prefix}{type(transform).__name__}"
        assert e.bsym_index is not None and e.bsym_index >= 0
        assert e.excerpt and "-->" in e.excerpt
        return e

    def test_use_after_del_blamed(self):
        e = self._expect(_CorruptUseAfterDel(), "use-after-del")
        assert "deleted" in e.message or "use-after-free" in e.message

    def test_metadata_drift_blamed(self):
        e = self._expect(_CorruptMetadataDrift(), "meta-drift")
        assert "metadata" in e.message

    def test_donation_read_back_blamed(self):
        e = self._expect(_CorruptDonationReadBack(), "donation-read")
        assert "donat" in e.message

    def test_view_of_post_write_value_is_legal(self):
        # p2 = write(p); v = reshape(p2); neg(v) — v derives from the
        # POST-write value, so reading it is fine even with p donated and
        # strict alias checking on
        trc = TraceCtx(None)
        p = TensorProxy("p", shape=(4,), dtype=dt.float32, device=None)
        p2 = TensorProxy("p2", shape=(4,), dtype=dt.float32, device=None)
        v = TensorProxy("v", shape=(2, 2), dtype=dt.float32, device=None)
        t = TensorProxy("tt", shape=(2, 2), dtype=dt.float32, device=None)
        trc.args = (p,)
        trc.donated = {"p"}
        trc.bound_symbols = [
            prims.copy_with_setitem.bind(p, 0, 1.0, output=p2),
            prims.reshape.bind(p2, (2, 2), output=v),
            prims.neg.bind(v, output=t),
            prims.python_return.bind((t,), output=None),
        ]
        analysis.alias.check_alias_safety(trc, strict=True)  # must not raise
        # but a view of the PRE-write value is still a violation
        bad = from_trace(trc)
        stale_v = TensorProxy("sv", shape=(2, 2), dtype=dt.float32, device=None)
        st = TensorProxy("st", shape=(2, 2), dtype=dt.float32, device=None)
        bad.bound_symbols = [
            prims.copy_with_setitem.bind(p, 0, 1.0, output=p2),
            prims.reshape.bind(p, (2, 2), output=stale_v),
            prims.neg.bind(stale_v, output=st),
            prims.python_return.bind((st,), output=None),
        ]
        with pytest.raises(TraceCheckError, match="donat"):
            analysis.alias.check_alias_safety(bad)

    def test_reordered_effect_blamed(self):
        # two buffer writes to two DIFFERENT buffers (fp8-amax-update shape):
        # the "pass" swaps their program order without breaking dataflow, so
        # only the cross-pass effect-order check can catch it
        trc = TraceCtx(None)
        x = TensorProxy("x", shape=(4,), dtype=dt.float32, device=None)
        y = TensorProxy("y", shape=(4,), dtype=dt.float32, device=None)
        x2 = TensorProxy("x2", shape=(4,), dtype=dt.float32, device=None)
        y2 = TensorProxy("y2", shape=(4,), dtype=dt.float32, device=None)
        trc.args = (x, y)
        w1 = prims.copy_with_setitem.bind(x, 0, 1.0, output=x2)
        w2 = prims.copy_with_setitem.bind(y, 1, 2.0, output=y2)
        ret = prims.python_return.bind((x2, y2), output=None)
        trc.bound_symbols = [w1, w2, ret]

        reordered = from_trace(trc)
        reordered.bound_symbols = [w2, w1, ret]

        with analysis.override(1):
            with pytest.raises(TraceCheckError) as ei:
                analysis.checkpoint("transform:ReorderingPass", reordered, before=trc)
        e = ei.value
        assert e.kind == "effect-reorder"
        assert e.pass_name == "transform:ReorderingPass"
        assert "order" in e.message

    def test_corrupted_prologue_blamed(self):
        # a transform that rewrites the PROLOGUE inconsistently is caught at
        # its own checkpoint, not as a baffling guard failure at dispatch
        class _CorruptPrologue(Transform):
            def transform_traces_pre_autodiff(self, prologue_trc, computation_trc,
                                              *, compile_data=None):
                out = from_trace(prologue_trc)
                ghost = TensorProxy("ghost_t", shape=(2,), dtype=dt.float32,
                                    device=None)
                stale = TensorProxy(shape=(2,), dtype=dt.float32, device=None)
                bsyms = list(prologue_trc.bound_symbols)
                bsyms.insert(0, prims.neg.bind(ghost, output=stale))
                out.bound_symbols = bsyms
                return out, computation_trc

        with analysis.override(1):
            with pytest.raises(TraceCheckError) as ei:
                _run_corrupted(_CorruptPrologue())
        e = ei.value
        assert e.kind == "undef-use"
        assert e.pass_name == "transform:_CorruptPrologue:prologue"

    def test_pruned_prologue_verifies_clean(self):
        from thunder_tpu.transforms.prune_prologue_checks import PrunePrologueChecks

        with analysis.override(1), analysis.session() as sess:
            cf = tt.jit(lambda x: ltorch.sum(x * 2.0),
                        transforms=[PrunePrologueChecks()])
            cf(jnp.ones((3, 3)))
        assert sess.violations == 0
        assert any(r["pass"].endswith(":prologue") for r in sess.rows)

    def test_oversized_region_blamed(self):
        budget.set_region_budget(1)  # nothing fits one byte
        with analysis.override(1):
            with pytest.raises(TraceCheckError) as ei:
                cf = tt.jit(lambda x: ltorch.sum(ltorch.relu(x) * 2.0 + 1.0))
                cf(jnp.ones((64, 64)))
        e = ei.value
        assert e.kind == "region-budget"
        assert e.pass_name.startswith("executor:fusion:")
        assert e.bsym_index is not None
        assert "budget" in e.message

    def test_trace_check_failed_event_emitted(self):
        obs_events.reset()
        obs_events.enable()
        try:
            with analysis.override(1):
                with pytest.raises(TraceCheckError):
                    _run_corrupted(_CorruptMetadataDrift())
            counters = obs_events.counters()
            assert counters.get("analysis.violations", 0) >= 1
            evs = [r for r in obs_events.records()
                   if r.get("kind") == "event" and r.get("name") == "trace_check_failed"]
            assert evs, "trace_check_failed event missing"
            attrs = evs[-1]["attrs"]
            assert attrs["kind"] == "meta-drift"
            assert attrs["pass_name"].endswith("_CorruptMetadataDrift")
            assert isinstance(attrs["bsym_index"], int)
        finally:
            obs_events.disable()
            obs_events.reset()


# ---------------------------------------------------------------------------
# structured error + repro bundle attachment
# ---------------------------------------------------------------------------


class TestStructuredError:
    def test_fields_and_render(self):
        with analysis.override(1):
            with pytest.raises(TraceCheckError) as ei:
                _run_corrupted(_CorruptMetadataDrift())
        e = ei.value
        assert isinstance(e, AssertionError)  # legacy except-clauses keep working
        assert e.trace is not None and e.trace_name
        r = e.render()
        for needle in ("introduced by pass", "bsym index", "trace excerpt",
                       "minimized repro"):
            assert needle in r
        # the repro is a printable backward slice
        assert e.repro.startswith("def repro(")

    def test_repro_bundle_attaches_failing_trace(self, tmp_path):
        from thunder_tpu.utils.report import save_reproducer

        with analysis.override(1):
            with pytest.raises(TraceCheckError):
                _run_corrupted(_CorruptMetadataDrift())
        assert an_manager.last_failure() is not None
        cf = tt.jit(lambda x: ltorch.sum(x * 2.0), disable_fusion=True)
        cf(jnp.ones((3, 3)))
        path = str(tmp_path / "repro.py")
        save_reproducer(cf, path)
        attached = path + ".trace_check.txt"
        import os

        assert os.path.exists(attached)
        text = open(attached).read()
        assert "meta-drift" in text and "failing trace" in text
        # consumed on attach: a later, unrelated bundle must NOT carry the
        # stale failure
        path2 = str(tmp_path / "repro2.py")
        save_reproducer(cf, path2)
        assert not os.path.exists(path2 + ".trace_check.txt")


# ---------------------------------------------------------------------------
# unified budget API: pallas decision parity + live-range estimator
# ---------------------------------------------------------------------------


class _P:
    """A tensor proxy as a checker sees one: shape, rank, dtype name."""

    def __init__(self, shape, dtype="bfloat16"):
        self.shape, self.ndim, self.dtype = shape, len(shape), dtype


class TestBudgetAPI:
    def test_paged_vmem_parity_with_pallas_checker(self):
        """The decode kernel's block of pages and its working set come from the
        budget API: blocks of whole pages (every KV head), double-buffered."""
        from thunder_tpu.executors import pallasex

        for ps, D, Dv, Hkv, g, kvi, qi in ((64, 128, 128, 8, 4, 2, 2), (64, 128, 256, 10, 4, 2, 2),
                                           (512, 512, 512, 8, 8, 4, 4)):
            kv = "bfloat16" if kvi == 2 else "float32"
            pps, hb = pallasex._paged_decode_blocks(Hkv * g, D, qi, _P((9, Hkv, ps, D), kv),
                                                    _P((9, Hkv, ps, Dv), kv))
            assert pps == budget.paged_pages_per_step(ps, D, g, kvi, qi, Dv=Dv, n_kv_heads=Hkv)
            assert hb == budget.paged_head_block(Hkv, g)
            fits = budget.paged_decode_vmem_bytes(ps, D, g, kvi, qi, Dv=Dv, n_kv_heads=Hkv,
                                                  pages_per_step=max(pps, 1))
            assert budget.within_vmem(fits, budget.paged_vmem_limit()) == (pps > 0)
            if 0 < pps < budget.PAGED_MAX_PAGES_PER_STEP:  # one page more would not fit
                assert not budget.within_vmem(
                    budget.paged_decode_vmem_bytes(ps, D, g, kvi, qi, Dv=Dv, n_kv_heads=Hkv,
                                                   pages_per_step=pps + 1), budget.paged_vmem_limit())
        # two buffers of a step's pages of K and of V are the bulk of it
        est = budget.paged_decode_vmem_bytes(64, 128, 4, 2, 2, n_kv_heads=8, pages_per_step=8)
        assert 2 * 8 * (8 * 64 * 256 * 2) <= est < 2 * 8 * (8 * 64 * 256 * 2) + 2**20
        # the decline decision: a page of which not even one fits the budget
        assert budget.paged_pages_per_step(2048, 512, 64, 4, 4, n_kv_heads=8) == 0
        assert budget.paged_pages_per_step(16, 128, 4, 2, 2, n_kv_heads=2) == 8
        # the chunk kernel's working set (PR 32: a tile of the queries against whole pages): two
        # buffers of a step's pages of K and of V for the program's heads, the q, output and position
        # blocks twice, the f32 accumulator with its two lane-padded columns, four of a step's scores
        assert budget.paged_chunk_vmem_bytes(16, 64, 4, 1, 2, 2, heads=1, pages_per_step=1) == (
            2 * 16 * (64 + 64) * 2 + 2 * 4 * (64 + 64) * 2 + 2 * 4 * 128 * 4 + 4 * (64 + 256) * 4 + 4 * 4 * 16 * 4)
        # checker parity: the tile, the heads a program and the pages a step are the budget's
        for ps, D, Dv, Hkv, g, T in ((64, 128, 128, 8, 4, 512), (64, 128, 256, 10, 4, 512),
                                     (64, 128, 128, 8, 4, 5), (4096, 512, 512, 8, 8, 512)):
            blocks = pallasex._paged_chunk_blocks(Hkv * g, T, D, 2, _P((9, Hkv, ps, D)), _P((9, Hkv, ps, Dv)))
            assert blocks == budget.paged_chunk_blocks(ps, D, g, T, 2, 2, Dv=Dv, n_kv_heads=Hkv)
            q_tile, heads, pps = blocks
            if pps:
                assert T % q_tile == 0 and Hkv % heads == 0
                assert budget.within_vmem(budget.paged_chunk_vmem_bytes(
                    ps, D, g, q_tile, 2, 2, heads=heads, pages_per_step=pps, Dv=Dv), budget.paged_vmem_limit())
        assert blocks == (0, 0, 0)  # a page of which not one fits beside a tile of the queries

    def test_paged_decode_declines_a_page_that_does_not_fit(self, monkeypatch, pallas_claims):
        """`pallas.decline.paged_attention.vmem`: not even one page a step fits."""
        from thunder_tpu import observability
        from thunder_tpu.executors import pallasex

        q, table, lens = _P((2, 32, 128)), _P((2, 4), "int32"), _P((2,), "int32")
        observability.enable()
        observability.reset()
        try:
            assert pallasex.paged_attention_supported(q, _P((8, 8, 64, 128)), _P((8, 8, 64, 128)), table, lens)
            assert "pallas.decline.paged_attention.vmem" not in observability.counters()
            huge = _P((8, 8, 8192, 128))
            assert not pallasex.paged_attention_supported(q, huge, huge, table, lens)
            assert observability.counters()["pallas.decline.paged_attention.vmem"] == 1
            # a smaller budget takes fewer pages a step before it declines
            monkeypatch.setattr(budget, "paged_vmem_limit", lambda: 2**20)
            assert budget.paged_pages_per_step(64, 128, 4, 2, 2, n_kv_heads=8) == 1
            monkeypatch.setattr(budget, "paged_vmem_limit", lambda: 2**19)
            assert not pallasex.paged_attention_supported(q, _P((8, 8, 64, 128)), _P((8, 8, 64, 128)), table, lens)
        finally:
            observability.disable()

    def test_flash_block_cap_parity(self):
        # bf16 keeps the swept blocks; 4-byte operands cap at 256 with gcd
        assert budget.flash_block_cap(2, 512, 1024, 2048, 2048) == (512, 1024)
        assert budget.flash_block_cap(4, 512, 1024, 2048, 2048) == (256, 256)
        import math

        assert budget.flash_block_cap(4, 512, 1024, 192, 192) == (
            math.gcd(256, 192), math.gcd(256, 192))

    def test_peak_bytes_hand_built(self):
        # a (4,) f32 chain: the un-DEL'd arg is held to the end (XLA keeps
        # non-donated inputs), so the peak is a+b+c at bsym 1
        trc = TraceCtx(None)
        a = TensorProxy("a", shape=(4,), dtype=dt.float32, device=None)
        b = TensorProxy("b", shape=(4,), dtype=dt.float32, device=None)
        c = TensorProxy("c", shape=(4,), dtype=dt.float32, device=None)
        trc.args = (a,)
        trc.bound_symbols = [
            prims.neg.bind(a, output=b),
            prims.neg.bind(b, output=c),
            prims.python_return.bind((c,), output=None),
        ]
        rep = budget.peak_bytes(trc)
        assert rep.peak_bytes == 48
        assert rep.args_bytes == 16
        # intermediates-only pricing (what estimate_step_peak uses so
        # params/batch are never double-counted against resident state)
        assert budget.peak_bytes(trc, count_args=False).peak_bytes == 32
        # the seed-compatible walker agrees
        from thunder_tpu.utils import get_alloc_memory

        peak, timeline = get_alloc_memory(trc)
        assert peak == 48 and timeline[1] == 48

    def test_del_ends_live_range(self):
        trc = TraceCtx(None)
        a = TensorProxy("a", shape=(1024,), dtype=dt.float32, device=None)
        b = TensorProxy("b", shape=(1024,), dtype=dt.float32, device=None)
        c = TensorProxy("c", shape=(1024,), dtype=dt.float32, device=None)
        trc.args = (a,)
        trc.bound_symbols = [
            prims.neg.bind(a, output=b),
            prims.python_del.bind(a, output=None),
            prims.neg.bind(b, output=c),
            prims.python_return.bind((c,), output=None),
        ]
        ranges = budget.live_ranges(trc.bound_symbols, trc.args)
        assert ranges["a"][1] == 1  # range ends at the DEL, not trace end
        rep = budget.peak_bytes(trc)
        assert rep.peak_bytes == 2 * 1024 * 4  # a+b, never three at once

    def test_region_peaks_and_step_estimate(self):
        with analysis.override(0):
            step = TrainStep(tt.jit(_Net()), optim.AdamW(lr=1e-2))
            x, y = _batch()
            float(step(x, y))
        est = budget.estimate_step_peak(step)
        assert est is not None
        assert est["peak_bytes"] >= est["state_bytes"] > 0
        assert est["peak_gb"] == round(est["peak_bytes"] / 2**30, 4)
        regions = budget.region_peaks(step.compile_stats.last_traces[-1])
        assert regions, "fused train-step trace should contain xla regions"
        for r in regions:
            assert r["peak_bytes"] >= 0 and r["interface_bytes"] > 0


# ---------------------------------------------------------------------------
# re-inference
# ---------------------------------------------------------------------------


class TestReinference:
    def _trace_ab(self):
        trc = TraceCtx(None)
        a = TensorProxy("a", shape=(4, 4), dtype=dt.float32, device=None)
        b = TensorProxy("b", shape=(4, 4), dtype=dt.float32, device=None)
        trc.args = (a, b)
        return trc, a, b

    def test_rule_catches_corrupted_dtype(self):
        trc, a, b = self._trace_ab()
        bad_out = TensorProxy("c", shape=(4, 4), dtype=dt.int32, device=None)
        trc.bound_symbols = [
            prims.add.bind(a, b, output=bad_out),
            prims.python_return.bind((bad_out,), output=None),
        ]
        with pytest.raises(TraceCheckError, match="re-infers"):
            analysis.reinfer.reinfer_trace(trc)

    def test_rule_catches_corrupted_shape(self):
        trc, a, b = self._trace_ab()
        bad_out = TensorProxy("c", shape=(7, 7), dtype=dt.float32, device=None)
        trc.bound_symbols = [
            prims.matmul.bind(a, b, output=bad_out),
            prims.python_return.bind((bad_out,), output=None),
        ]
        with pytest.raises(TraceCheckError, match="re-infers"):
            analysis.reinfer.reinfer_trace(trc)

    def test_deep_reinfer_catches_div_class_lowering_bug(self):
        # the impl returns FLOAT where the trace records INT — the exact
        # shape of the int-DIV true_divide bug fixed in PR 10
        import jax.numpy as jnp_

        trc, _, _ = self._trace_ab()
        ai = TensorProxy("ai", shape=(4,), dtype=dt.int32, device=None)
        bi = TensorProxy("bi", shape=(4,), dtype=dt.int32, device=None)
        trc.args = (ai, bi)
        out = TensorProxy("q", shape=(4,), dtype=dt.int32, device=None)
        bad = prims.div.bind(ai, bi, output=out)
        bad = bad.with_impl(lambda x, y: jnp_.true_divide(x, y))  # f32 result
        trc.bound_symbols = [bad, prims.python_return.bind((out,), output=None)]
        with pytest.raises(TraceCheckError, match="lowering disagrees"):
            analysis.reinfer.reinfer_executed(trc)

    def test_deep_reinfer_accepts_correct_lowering(self):
        import jax.numpy as jnp_

        trc, _, _ = self._trace_ab()
        ai = TensorProxy("ai", shape=(4,), dtype=dt.int32, device=None)
        bi = TensorProxy("bi", shape=(4,), dtype=dt.int32, device=None)
        trc.args = (ai, bi)
        out = TensorProxy("q", shape=(4,), dtype=dt.int32, device=None)
        good = prims.div.bind(ai, bi, output=out).with_impl(
            lambda x, y: jnp_.floor_divide(x, y))
        trc.bound_symbols = [good, prims.python_return.bind((out,), output=None)]
        rep = analysis.reinfer.reinfer_executed(trc)
        assert rep["checked"] >= 1


# ---------------------------------------------------------------------------
# verifier extensions: fusion-region interfaces
# ---------------------------------------------------------------------------


class TestRegionInterfaces:
    def test_claimed_trace_regions_verify(self):
        cf = tt.jit(lambda x: ltorch.sum(ltorch.relu(x) * 2.0 + 1.0))
        cf(jnp.ones((8, 8)))
        trc = tt.last_traces(cf)[-1]
        analysis.verify_trace(trc)  # regions recurse clean

    def test_broken_region_interface_detected(self):
        cf = tt.jit(lambda x: ltorch.sum(ltorch.relu(x) * 2.0 + 1.0))
        cf(jnp.ones((8, 8)))
        trc = tt.last_traces(cf)[-1]
        bad = from_trace(trc)
        bsyms = list(trc.bound_symbols)
        for i, b in enumerate(bsyms):
            if b.subsymbols and b.sym.executor is not None:
                # drop a region input: members now consume an undeclared proxy
                args = tuple(b.args[1:])
                bsyms[i] = BoundSymbol(b.sym, args, b.kwargs, b.output,
                                       subsymbols=b.subsymbols, impl=b.impl)
                break
        else:
            pytest.skip("no fusion region formed")
        bad.bound_symbols = bsyms
        with pytest.raises(TraceCheckError, match="region interface"):
            analysis.verify_trace(bad)
