"""Live telemetry (ISSUE 11): streaming percentiles, observability.snapshot(),
the Prometheus exporter and SLO monitors with reason-coded breaches.

Acceptance pins: online p50/p90/p99 from the streaming histograms agree with
tools/obs_summary.py's offline percentiles on the SAME run within estimator
tolerance; a deterministic CPU serving run driven past a configured SLO
emits a reason-coded slo.breach event with a goodput gauge < 1.0.
"""
import importlib.util
import os
import re
import time
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import nn, observability, optim
from thunder_tpu.models.litgpt import Config, GPT
from thunder_tpu.observability import telemetry as tel
from thunder_tpu.observability.slo import SLOMonitor, SLOPolicy
from thunder_tpu.ops import ltorch
from thunder_tpu.serving import ServingEngine
from thunder_tpu.training import TrainStep

pytestmark = pytest.mark.telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def obs_mem():
    from thunder_tpu.observability import flight_recorder as fr

    observability.reset()
    fr.reset()  # spikes from earlier suites would skew the derived gauge
    observability.enable()
    yield
    observability.disable()
    observability.reset()
    fr.reset()


@pytest.fixture(scope="module")
def gpt():
    cfg = Config.from_name("tiny-llama2", block_size=64)
    return GPT(cfg, dtype=jnp.float32)


def _engine(gpt, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq", 64)
    kw.setdefault("dtype", jnp.float32)
    return ServingEngine(gpt, **kw)


class _Net(nn.Module):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(8, 4, seed=0)

    def forward(self, x, y):
        return ltorch.mse_loss(self.fc(x), y)


def _train_step(rng, **kw):
    step = TrainStep(tt.jit(_Net()), optim.AdamW(lr=0.05), **kw)
    x = jnp.asarray(rng.rand(4, 8).astype(np.float32))
    y = jnp.asarray(rng.rand(4, 4).astype(np.float32))
    return step, x, y


# ---------------------------------------------------------------------------
# StreamingHistogram: accuracy + bounded memory
# ---------------------------------------------------------------------------


class TestStreamingHistogram:
    def test_relative_accuracy_guarantee(self):
        """Every quantile lands within alpha of the exact nearest-rank
        sample (the DDSketch guarantee), on a skewed distribution."""
        rng = np.random.RandomState(7)
        xs = np.exp(rng.randn(5000) * 1.5 + 2.0)  # long-tailed latencies
        h = tel.StreamingHistogram(alpha=0.01)
        for x in xs:
            h.observe(float(x))
        srt = np.sort(xs)
        for q in (0.5, 0.9, 0.99, 0.999):
            exact = srt[min(len(srt) - 1, int(round(q * (len(srt) - 1))))]
            est = h.quantile(q)
            assert abs(est - exact) <= 0.0201 * exact + 1e-9, (q, est, exact)

    def test_bounded_memory_under_wide_range(self):
        """12 decades of distinct values stay within max_buckets (the two
        lowest buckets collapse; the tail keeps full accuracy)."""
        h = tel.StreamingHistogram(alpha=0.01, max_buckets=64)
        rng = np.random.RandomState(3)
        for _ in range(20_000):
            h.observe(float(10 ** rng.uniform(-6, 6)))
        assert h.n_buckets() <= 65
        assert h.count == 20_000
        # tail accuracy survives collapsing: the max is exact by clamping
        assert h.quantile(1.0) == h.max

    def test_zero_and_negative_values(self):
        h = tel.StreamingHistogram()
        for v in (0.0, -1.0, 5.0, 5.0):
            h.observe(v)
        assert h.count == 4
        assert h.quantile(0.0) == 0.0  # clamped to max(0, min)
        assert abs(h.quantile(0.99) - 5.0) <= 0.0201 * 5.0
        snap = h.snapshot()
        assert snap["count"] == 4 and snap["min"] == -1.0 and snap["max"] == 5.0

    def test_empty_histogram(self):
        h = tel.StreamingHistogram()
        assert h.quantile(0.5) is None
        assert h.snapshot() == {"count": 0}

    def test_prometheus_buckets_cumulative(self):
        h = tel.StreamingHistogram()
        for v in (0.0, 1.0, 10.0, 100.0):
            h.observe(v)
        bks = h.buckets()
        assert bks[0] == (0.0, 1)
        cums = [c for _, c in bks]
        assert cums == sorted(cums) and cums[-1] == 4
        les = [le for le, _ in bks]
        assert les == sorted(les)


# ---------------------------------------------------------------------------
# registry, snapshot(), summary() merge
# ---------------------------------------------------------------------------


class TestSnapshot:
    def test_observe_and_snapshot(self, obs_mem):
        for v in (1.0, 2.0, 3.0):
            observability.observe("t.ms", v)
        observability.set_gauge("t.gauge", 0.5)
        observability.inc("t.count", 2)
        snap = observability.snapshot()
        assert snap["enabled"] is True
        assert snap["counters"]["t.count"] == 2
        assert snap["gauges"]["t.gauge"] == 0.5
        assert snap["histograms"]["t.ms"]["count"] == 3

    def test_derived_cache_hit_rate_gauge(self, obs_mem):
        from thunder_tpu.observability import metrics as m

        m.record_cache("trace", "hit")
        m.record_cache("trace", "hit")
        m.record_cache("trace", "miss")
        g = observability.snapshot()["gauges"]
        assert g["trace.hit_rate"] == pytest.approx(2 / 3, abs=1e-3)
        assert g["flight.spikes"] == 0.0

    def test_summary_merges_serving_and_histograms(self, gpt, obs_mem, rng):
        """Satellite: one summary() call reports training AND serving state
        — serve.* counters plus the streaming-histogram snapshots."""
        engine = _engine(gpt)
        fut = engine.submit(rng.randint(0, gpt.cfg.vocab_size, (6,)).astype(np.int32), 3)
        engine.drain()
        fut.result()
        s = observability.summary()
        assert s["serving"].get("serve.retired") == 1
        assert all(k.startswith("serve.") for k in s["serving"])
        assert s["histograms"]["serve.ttft_ms"]["count"] == 1
        assert s["histograms"]["serve.tbot_ms"]["count"] == 1
        assert "serve.pool_utilization" in s["gauges"]

    def test_reset_clears_telemetry(self, obs_mem):
        observability.observe("t.ms", 1.0)
        observability.set_gauge("t.g", 1.0)
        observability.reset()
        snap = observability.snapshot()
        assert snap["histograms"] == {}
        assert "t.g" not in snap["gauges"]


# ---------------------------------------------------------------------------
# acceptance: online percentiles agree with the offline CLI on the same run
# ---------------------------------------------------------------------------


@pytest.mark.serve
class TestOnlineOfflineAgreement:
    def test_snapshot_matches_obs_summary(self, gpt, obs_mem, rng, tmp_path):
        """Drive the serving engine, then compare observability.snapshot()'s
        streaming p50/p99 for TTFT/TBOT against tools/obs_summary.py's
        offline percentiles over the SAME JSONL timeline. The histogram's
        relative-accuracy guarantee (alpha=1%) bounds the disagreement."""
        engine = _engine(gpt)
        futs = []
        for L, n in [(5, 4), (12, 6), (9, 3), (20, 5), (3, 6), (11, 4),
                     (7, 5), (15, 3), (6, 6), (10, 4), (4, 3), (18, 5)]:
            p = rng.randint(0, gpt.cfg.vocab_size, (L,)).astype(np.int32)
            futs.append(engine.submit(p, max_new_tokens=n))
        engine.drain()
        for f in futs:
            f.result()

        shard = str(tmp_path / "run.jsonl")
        observability.dump(shard)
        mod = _load_tool("obs_summary")
        recs = mod.load_many([shard])
        lines = "\n".join(mod.serving_lines(recs, mod.final_counters(recs)))
        offline = {}
        for series in ("ttft_ms", "tbot_ms"):
            m = re.search(rf"{series}\s+p50=([\d.]+)\s+p99=([\d.]+)", lines)
            assert m, f"no offline {series} percentiles in:\n{lines}"
            offline[series] = (float(m.group(1)), float(m.group(2)))

        hists = observability.snapshot()["histograms"]
        assert hists["serve.ttft_ms"]["count"] == 12
        assert hists["serve.tbot_ms"]["count"] == 12  # every request has n_new > 1
        for series, key in (("ttft_ms", "serve.ttft_ms"), ("tbot_ms", "serve.tbot_ms")):
            off_p50, off_p99 = offline[series]
            assert hists[key]["p50"] == pytest.approx(off_p50, rel=0.05, abs=0.02)
            assert hists[key]["p99"] == pytest.approx(off_p99, rel=0.05, abs=0.02)
        # decode-iteration series covers every packed step
        assert hists["serve.decode_ms"]["count"] == engine.decode_steps

    def test_serving_section_splits_lanes(self):
        """serve_retired events carrying lane= render a per-lane latency
        breakdown — and single-lane traffic stays aggregate-only."""
        mod = _load_tool("obs_summary")

        def retired(lane, ttft, tbot):
            return {"kind": "event", "name": "serve_retired",
                    "attrs": {"lane": lane, "ttft_ms": ttft, "tbot_ms": tbot,
                              "n_new": 4}}

        recs = [retired("interactive", 5.0, 1.0), retired("batch", 50.0, 2.0),
                retired("interactive", 7.0, 1.5)]
        out = "\n".join(mod.serving_lines(recs, {"serve.retired": 3}))
        assert re.search(r"lane interactive\s+n=2\s+ttft p50=5\.00", out)
        assert re.search(r"lane batch\s+n=1\s+ttft p50=50\.00", out)
        solo = "\n".join(mod.serving_lines(
            [retired("interactive", 5.0, 1.0)], {"serve.retired": 1}))
        assert "lane " not in solo

    def test_train_step_histogram_counts_every_step(self, obs_mem, rng):
        step, x, y = _train_step(rng)
        for _ in range(6):
            float(step(x, y))
        h = observability.snapshot()["histograms"]["train.step_ms"]
        assert h["count"] == 6
        assert h["p99"] >= h["p50"] > 0


# ---------------------------------------------------------------------------
# acceptance: SLO breach on the serving engine + goodput gauge
# ---------------------------------------------------------------------------


@pytest.mark.serve
class TestServingSLO:
    def test_breach_event_and_goodput_gauge(self, gpt, obs_mem, rng):
        """Drive the engine past an impossible TBOT target: a reason-coded
        slo.breach event fires, the goodput gauge drops below 1.0, and
        every result carries slo_met=False."""
        policy = SLOPolicy(p99_tbot_ms=1e-4, min_goodput=0.9,
                           window=32, min_samples=2)
        engine = _engine(gpt, slo=policy)
        futs = []
        for L in (5, 9, 12, 7):
            p = rng.randint(0, gpt.cfg.vocab_size, (L,)).astype(np.int32)
            futs.append(engine.submit(p, max_new_tokens=4))
        engine.drain()
        results = [f.result() for f in futs]
        assert all(r.slo_met is False for r in results)

        evs = [r for r in observability.records()
               if r["kind"] == "event" and r["name"] == "slo.breach"]
        reasons = {e["attrs"]["reason"] for e in evs}
        assert "p99-tbot" in reasons and "goodput" in reasons
        for e in evs:
            assert e["attrs"]["source"] == "serving"
            assert e["attrs"]["burn_rate"] >= 1.0
        counters = observability.counters()
        assert counters.get("slo.breach.p99-tbot", 0) >= 1
        assert counters.get("slo.breach.goodput", 0) >= 1
        assert tel.gauge("serve.goodput") is not None
        assert tel.gauge("serve.goodput") < 1.0
        st = engine.stats()
        assert st["goodput"] == 0.0 and st["requests_slo_met"] == 0
        assert st["slo"]["targets"]["p99-tbot"]["breached"] is True
        assert engine.goodput() == 0.0

    def test_met_slo_keeps_goodput_at_one(self, gpt, obs_mem, rng):
        policy = SLOPolicy(p99_ttft_ms=1e9, p99_tbot_ms=1e9,
                           window=32, min_samples=2)
        engine = _engine(gpt, slo=policy)
        fut = engine.submit(rng.randint(0, gpt.cfg.vocab_size, (6,)).astype(np.int32), 4)
        engine.drain()
        assert fut.result().slo_met is True
        assert engine.goodput() == 1.0
        assert not [r for r in observability.records()
                    if r["kind"] == "event" and r["name"] == "slo.breach"]

    def test_breach_emits_once_then_recovers(self, obs_mem):
        """A sustained breach emits ONE transition event, not one per
        sample; recovery emits slo.recovered."""
        mon = SLOMonitor(SLOPolicy(p99_ttft_ms=10.0, window=4, min_samples=2),
                         source="t")
        for _ in range(6):
            mon.observe_request(ttft_ms=100.0, tbot_ms=None, met=False)
        breaches = [r for r in observability.records()
                    if r["kind"] == "event" and r["name"] == "slo.breach"]
        assert len(breaches) == 1
        for _ in range(6):  # window (4) flushes clean
            mon.observe_request(ttft_ms=1.0, tbot_ms=None, met=True)
        recovered = [r for r in observability.records()
                     if r["kind"] == "event" and r["name"] == "slo.recovered"]
        assert len(recovered) == 1
        assert mon.status()["breached"] == []

    def test_policy_validation(self):
        with pytest.raises(ValueError, match="at least one target"):
            SLOPolicy()
        with pytest.raises(ValueError, match="objective"):
            SLOPolicy(p99_ttft_ms=1.0, objective=1.5)
        with pytest.raises(ValueError, match="min_goodput"):
            SLOPolicy(min_goodput=1.5)

    def test_reset_slo_accounting(self, gpt, obs_mem, rng):
        """The engine owns the warmup-exclusion reset: counters zero, the
        monitor restarts with the same policy, later traffic counts."""
        policy = SLOPolicy(p99_ttft_ms=1e9, window=32, min_samples=2)
        engine = _engine(gpt, slo=policy)
        p = rng.randint(0, gpt.cfg.vocab_size, (6,)).astype(np.int32)
        engine.submit(p, max_new_tokens=3)
        engine.drain()
        assert engine.requests_retired == 1
        engine.reset_slo_accounting()
        assert engine.requests_retired == 0 and engine.goodput() is None
        assert engine.slo_monitor.policy is policy
        assert engine.slo_monitor.goodput() is None  # window cleared too
        engine.submit(p, max_new_tokens=3)
        engine.drain()
        assert engine.goodput() == 1.0

    def test_throughput_target_respects_min_samples(self, obs_mem):
        """The tokens-per-s target honors the same cold-window gate as the
        latency targets: one inter-step gap never fires a breach."""
        mon = SLOMonitor(SLOPolicy(min_tokens_per_s=1e15, window=32,
                                   min_samples=8, tokens_per_step=1024),
                         source="training")
        for _ in range(4):  # below min_samples: no evaluation yet
            mon.observe_step(1.0)
        assert "tokens-per-s" not in mon.status()["targets"]
        for _ in range(8):
            mon.observe_step(1.0)
        assert mon.status()["targets"]["tokens-per-s"]["breached"] is True

    def test_cancelled_requests_excluded_from_goodput(self, gpt, obs_mem, rng):
        policy = SLOPolicy(p99_ttft_ms=1e9, window=32, min_samples=2)
        engine = _engine(gpt, slo=policy)
        p = rng.randint(0, gpt.cfg.vocab_size, (6,)).astype(np.int32)
        f = engine.submit(p, max_new_tokens=30)
        engine._step_once()
        assert f.cancel()
        ok = engine.submit(p, max_new_tokens=3)
        engine.drain()
        assert ok.result().slo_met is True
        assert engine.stats()["requests_retired"] == 1  # cancel not counted


class TestTrainingSLO:
    def test_step_time_and_throughput_breach(self, obs_mem, rng):
        """TrainStep(..., slo=...) monitors step wall time and tokens/s;
        impossible targets breach with reason codes."""
        policy = SLOPolicy(p99_step_ms=1e-6, min_tokens_per_s=1e15,
                           window=16, min_samples=2, tokens_per_step=1024)
        step, x, y = _train_step(rng, slo=policy)
        for _ in range(5):
            float(step(x, y))
        reasons = {r["attrs"]["reason"] for r in observability.records()
                   if r["kind"] == "event" and r["name"] == "slo.breach"}
        assert "p99-step-time" in reasons
        assert "tokens-per-s" in reasons
        st = step.slo_monitor.status()
        assert st["source"] == "training"
        assert st["targets"]["p99-step-time"]["breached"] is True

    def test_throughput_target_without_tokens_per_step_rejected(self, rng):
        """min_tokens_per_s on a TrainStep without tokens_per_step would
        silently never be evaluated — reject it at attachment."""
        with pytest.raises(ValueError, match="tokens_per_step"):
            _train_step(rng, slo=SLOPolicy(min_tokens_per_s=40_000))

    def test_monitor_without_bus_emits_nothing(self, rng):
        """An attached monitor keeps measuring (the operator asked), but a
        disabled bus records no events/counters."""
        assert not observability.enabled()
        policy = SLOPolicy(p99_step_ms=1e-6, window=16, min_samples=2)
        step, x, y = _train_step(rng, slo=policy)
        for _ in range(4):
            float(step(x, y))
        assert step.slo_monitor.status()["targets"]["p99-step-time"]["breached"]
        assert observability.records() == []
        assert observability.counters() == {}


# ---------------------------------------------------------------------------
# sampling interaction: histograms stay unsampled
# ---------------------------------------------------------------------------


@pytest.mark.serve
class TestSamplingInteraction:
    def test_sampled_serve_spans_do_not_thin_histograms(self, gpt, obs_mem, rng):
        """TT_OBS_SAMPLE thins the serve spans but the streaming histograms
        see EVERY retirement — sampled-out records must not skew the online
        percentiles."""
        from thunder_tpu.observability import runtime as rt

        engine = _engine(gpt)
        engine.warmup([4, 10], max_new_tokens=2)
        observability.reset()
        rt.set_sample_rate(0.5)
        try:
            futs = []
            for L in (3, 5, 8, 12, 6, 9):
                p = rng.randint(0, gpt.cfg.vocab_size, (L,)).astype(np.int32)
                futs.append(engine.submit(p, max_new_tokens=3))
            engine.drain()
            for f in futs:
                f.result()
            spans = [r for r in observability.records()
                     if r["kind"] == "span" and r["name"] == "serve_prefill"]
            assert len(spans) == 3  # deterministic counter modulo: every 2nd
            hists = observability.snapshot()["histograms"]
            assert hists["serve.ttft_ms"]["count"] == 6
            assert hists["serve.tbot_ms"]["count"] == 6
            retires = [r for r in observability.records()
                       if r["kind"] == "event" and r["name"] == "serve_retired"]
            assert len(retires) == 6  # lifecycle events are never sampled
        finally:
            rt.set_sample_rate(1.0)

    def test_sampled_train_steps_keep_full_histogram(self, obs_mem, rng):
        from thunder_tpu.observability import runtime as rt

        step, x, y = _train_step(rng)
        float(step(x, y))  # build outside the sampled window
        observability.reset()
        rt.set_sample_rate(0.25)
        try:
            for _ in range(8):
                float(step(x, y))
            spans = [r for r in observability.records()
                     if r["kind"] == "span" and r["name"] == "train_step"]
            assert len(spans) == 2
            assert observability.snapshot()["histograms"]["train.step_ms"]["count"] == 8
        finally:
            rt.set_sample_rate(1.0)


# ---------------------------------------------------------------------------
# zero-work disabled paths (counter-asserted, test_dispatch_fastpath style)
# ---------------------------------------------------------------------------


@pytest.mark.serve
class TestDisabledZeroWork:
    def test_disabled_serving_never_touches_telemetry(self, gpt, rng, monkeypatch):
        assert not observability.enabled()

        def boom(*a, **k):
            raise AssertionError("telemetry touched with the bus disabled")

        from thunder_tpu.serving import scheduler as sched

        monkeypatch.setattr(sched._obs_tel, "observe", boom)
        monkeypatch.setattr(sched._obs_tel, "set_gauge", boom)
        engine = _engine(gpt)
        fut = engine.submit(rng.randint(0, gpt.cfg.vocab_size, (6,)).astype(np.int32), 3)
        engine.drain()
        assert fut.result().n_new_tokens == 3
        assert fut.result().slo_met is None  # no policy attached

    def test_disabled_train_step_never_touches_telemetry(self, rng, monkeypatch):
        assert not observability.enabled()

        def boom(*a, **k):
            raise AssertionError("telemetry touched with the bus disabled")

        from thunder_tpu import training as T

        step, x, y = _train_step(rng)
        float(step(x, y))
        monkeypatch.setattr(T._obs_tel, "observe", boom)
        monkeypatch.setattr(T._obs_tel, "set_gauge", boom)
        float(step(x, y))

    def test_no_exporter_by_default(self):
        assert tel.exporter() is None

    def test_observe_disabled_is_one_attribute_read(self):
        assert not observability.enabled()
        tel.observe("never.ms", 1.0)
        tel.set_gauge("never.g", 1.0)
        assert tel.histogram("never.ms") is None
        assert tel.gauge("never.g") is None


# ---------------------------------------------------------------------------
# exporter: HTTP and file targets, Prometheus text format
# ---------------------------------------------------------------------------


class TestExporter:
    def test_http_exporter_serves_metrics(self, obs_mem):
        observability.inc("exp.count", 3)
        observability.observe("exp.ms", 2.0)
        observability.observe("exp.ms", 8.0)
        observability.set_gauge("exp.gauge", 0.25)
        exp = tel.start_exporter("0")  # ephemeral port
        try:
            assert exp.port and exp.port > 0
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{exp.port}/metrics", timeout=10).read().decode()
        finally:
            tel.stop_exporter()
        assert "# TYPE tt_exp_count counter" in body
        assert "tt_exp_count 3" in body
        assert "# TYPE tt_exp_gauge gauge" in body
        assert "tt_exp_gauge 0.25" in body
        assert "# TYPE tt_exp_ms histogram" in body
        assert 'tt_exp_ms_bucket{le="+Inf"} 2' in body
        assert "tt_exp_ms_count 2" in body
        # every exposition line is `name[{labels}] value` or a comment
        for line in body.strip().splitlines():
            assert line.startswith("#") or len(line.rsplit(" ", 1)) == 2

    def test_file_exporter_writes_snapshots(self, obs_mem, tmp_path):
        path = str(tmp_path / "metrics.prom")
        observability.inc("exp.file", 1)
        exp = tel.start_exporter(path, interval=0.05)
        try:
            assert exp.path == path
            observability.inc("exp.file", 1)
            deadline = time.time() + 5
            while time.time() < deadline:
                if os.path.exists(path) and "tt_exp_file 2" in open(path).read():
                    break
                time.sleep(0.02)
        finally:
            tel.stop_exporter()
        assert "tt_exp_file 2" in open(path).read()

    def test_start_exporter_enables_bus(self, tmp_path):
        assert not observability.enabled()
        try:
            tel.start_exporter(str(tmp_path / "m.prom"), interval=60)
            assert observability.enabled()
        finally:
            tel.stop_exporter()
            observability.disable()
            observability.reset()

    def test_name_sanitization(self):
        assert tel._prom_name("serve.ttft_ms") == "tt_serve_ttft_ms"
        assert tel._prom_name("slo.breach.p99-tbot") == "tt_slo_breach_p99_tbot"
        assert tel._prom_name("9lives") == "tt__9lives"

    def test_counter_gauge_name_collision_emits_one_family(self, obs_mem):
        """The `flight.spikes` bus counter and the derived gauge share a
        name; the exposition must emit ONE metric family (a second TYPE
        line would invalidate the whole scrape)."""
        observability.inc("flight.spikes")
        body = tel.render_prometheus()
        assert body.count("# TYPE tt_flight_spikes") == 1
        assert "# TYPE tt_flight_spikes counter" in body

    def test_bad_env_port_does_not_crash_import(self):
        """TT_OBS_EXPORT with an out-of-range port (OverflowError, not
        OSError) must warn and continue — telemetry never takes the
        importing process down."""
        import subprocess
        import sys as _sys

        code = ("import sys; sys.path.insert(0, %r); "
                "import thunder_tpu.observability as o; "
                "print('imported', o.telemetry.exporter())" % REPO)
        p = subprocess.run([_sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=300,
                           env={**os.environ, "TT_OBS_EXPORT": "99999",
                                "JAX_PLATFORMS": "cpu"})
        assert p.returncode == 0, p.stderr
        assert "imported None" in p.stdout
        assert "exporter failed to start" in p.stderr


# ---------------------------------------------------------------------------
# CLI: slo.breach events render in obs_summary
# ---------------------------------------------------------------------------


class TestCLISloSection:
    def test_breaches_render(self, obs_mem, tmp_path):
        mon = SLOMonitor(SLOPolicy(p99_ttft_ms=1.0, window=4, min_samples=2),
                         source="serving")
        for _ in range(3):
            mon.observe_request(ttft_ms=50.0, tbot_ms=None, met=False)
        shard = str(tmp_path / "t.jsonl")
        observability.dump(shard)
        mod = _load_tool("obs_summary")
        out = mod.render(mod.load_many([shard]))
        assert "== slo ==" in out
        assert "p99-ttft" in out
        assert "BREACH" in out
        assert "burn=" in out
