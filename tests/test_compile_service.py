"""Compile service: content-addressed artifact store, parallel region
compilation, bucketed lowering (thunder_tpu/compile_service/).

Covers the store's concurrency contract (racing publishes converge, corrupt
artifacts are skipped with an event, GC never deletes fresh publishes), the
sha-verified aot_cache shim (no unvalidated pickle.load), region prewarming
through both jit frontends, and the shared BucketLadder driving zero
steady-state recompiles across a TrainStep shape sweep.
"""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import observability
from thunder_tpu.compile_service import (
    ArtifactStore,
    BucketLadder,
    artifact_key,
    pad_to_bucket,
)
from thunder_tpu.ops import ltorch

pytestmark = pytest.mark.compile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- BucketLadder ------------------------------------------------------------

class TestBucketLadder:
    def test_rungs_and_rounding(self):
        l = BucketLadder(8, 64, page_size=8)
        assert l.rungs == (8, 16, 32, 64)
        assert l.bucket_for(1) == 8
        assert l.bucket_for(8) == 8
        assert l.bucket_for(9) == 16
        assert l.bucket_for(33) == 64
        assert l.bucket_for(200) == 64  # capped at max
        assert l.bucket_id(9) == 1 and l.bucket_id(10) == l.bucket_id(15)

    def test_cap_rung_not_power_of_two(self):
        l = BucketLadder(8, 24, page_size=8)
        assert l.rungs == (8, 16, 24)
        assert l.bucket_for(20) == 24

    def test_page_alignment_rejected(self):
        with pytest.raises(ValueError, match="min_bucket"):
            BucketLadder(20, 64, page_size=8)
        with pytest.raises(ValueError, match="max_len"):
            BucketLadder(8, 60, page_size=8)
        with pytest.raises(ValueError, match="min_len"):
            BucketLadder(16, 8)

    def test_touch_mru_and_hits(self):
        l = BucketLadder(8, 64)
        assert l.touch(9) == 16
        assert l.touch(3) == 8
        assert l.touch(12) == 16
        assert l.mru() == [16, 8]
        assert l.hits() == {16: 2, 8: 1}

    def test_key_fields_stable(self):
        a = BucketLadder(8, 64, page_size=8)
        b = BucketLadder(8, 64, page_size=8)
        assert a.key_fields() == b.key_fields()
        assert a.key_fields() != BucketLadder(16, 64, page_size=16).key_fields()

    def test_pad_to_bucket(self):
        l = BucketLadder(8, 64)
        idx = np.ones((2, 10), np.int32)
        tgt = np.ones((2, 10), np.int32)
        (pi, pt), kw = pad_to_bucket((idx, tgt), {}, l, axis=1,
                                     pad_values={0: 0, 1: -100})
        assert pi.shape == (2, 16) and pt.shape == (2, 16)
        assert (pi[:, 10:] == 0).all() and (pt[:, 10:] == -100).all()
        # on-rung lengths pass through untouched (no copy)
        on = np.ones((2, 16), np.int32)
        (same,), _ = pad_to_bucket((on,), {}, l, axis=1)
        assert same is on
        # scalars / low-rank leaves pass through
        (s,), _ = pad_to_bucket((3,), {}, l, axis=1)
        assert s == 3


# -- ArtifactStore -----------------------------------------------------------

class TestArtifactStore:
    def test_a_key_holds_the_version_of_the_code_that_lowered_the_trace(self, monkeypatch):
        """A trace names a program's symbols, not the kernel a symbol lowers to: an executable
        another version of this package built is not found under the same trace (PR 28: the
        rewritten paged decode kernel ran the parent's stored executables until the key held it)."""
        from thunder_tpu.compile_service import store

        assert store.environment_fingerprint()["code"] == store.code_fingerprint()
        assert len(store.code_fingerprint()) == 64
        key = artifact_key(kind="t", trace="def f(): ...")
        assert key == artifact_key(kind="t", trace="def f(): ...")
        monkeypatch.setattr(store, "code_fingerprint", lambda: "another version")
        assert artifact_key(kind="t", trace="def f(): ...") != key

    def test_roundtrip_and_counters(self, tmp_path):
        st = ArtifactStore(str(tmp_path))
        key = artifact_key(kind="t", x=1)
        assert st.get_bytes(key) is None
        assert st.put_bytes(key, b"payload", kind="t", meta={"x": "1"})
        got = st.get_bytes(key)
        assert got is not None and got[0] == b"payload"
        assert got[1]["kind"] == "t" and got[1]["meta"] == {"x": "1"}
        s = st.stats()
        assert s["hits"] == 1 and s["misses"] == 1 and s["publishes"] == 1

    def test_corrupt_payload_skipped_with_event(self, tmp_path):
        """A truncated/tampered artifact.bin is digest-rejected BEFORE any
        deserialization, evicted with a stale-key event, and read as a
        miss — never an exception (the unvalidated-pickle fix)."""
        st = ArtifactStore(str(tmp_path))
        key = artifact_key(kind="t", x=2)
        st.put_bytes(key, b"real-bytes", kind="t")
        with open(os.path.join(st._entry_dir(key), "artifact.bin"), "wb") as f:
            f.write(b"tampered!!")
        observability.enable()
        try:
            observability.reset()
            assert st.get_bytes(key) is None
            assert not st.contains(key), "corrupt entry not evicted"
            c = observability.counters()
            assert c.get("artifact.evict") == 1
            evs = [r for r in observability.records()
                   if r.get("kind") == "event"
                   and r["name"] == "compile_artifact_evict"]
            assert evs and evs[0]["attrs"]["why"] == "stale-key"
        finally:
            observability.disable()
            observability.reset()

    def test_torn_manifest_evicted(self, tmp_path):
        st = ArtifactStore(str(tmp_path))
        key = artifact_key(kind="t", x=3)
        st.put_bytes(key, b"bytes", kind="t")
        os.unlink(os.path.join(st._entry_dir(key), "manifest.json"))
        assert st.get_bytes(key) is None
        assert not os.path.isdir(st._entry_dir(key))

    def test_threaded_publish_race_converges(self, tmp_path):
        st = ArtifactStore(str(tmp_path))
        key = artifact_key(kind="t", x=4)
        errs = []

        def publish():
            try:
                for _ in range(10):
                    assert st.put_bytes(key, b"identical-payload", kind="t")
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=publish) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        ok, problems = st.validate(key)
        assert ok, problems
        assert st.get_bytes(key)[0] == b"identical-payload"
        assert len(st.entries()) == 1

    def test_gc_keep_last_k(self, tmp_path):
        st = ArtifactStore(str(tmp_path))
        keys = [artifact_key(kind="t", i=i) for i in range(6)]
        for i, k in enumerate(keys):
            st.put_bytes(k, f"p{i}".encode(), kind="t")
            # distinct mtimes order the retention scan deterministically
            os.utime(st._manifest_path(k), (1000 + i, 1000 + i))
        removed = st.gc(keep=2, _scan_start=float("inf"))
        assert removed == 4
        kept = {m["key"] for m in st.entries()}
        assert kept == set(keys[-2:])

    def test_gc_spares_artifacts_published_after_scan_start(self, tmp_path):
        """The GC race guard: entries created after the scan began are
        off-limits even when the retention budget says delete."""
        st = ArtifactStore(str(tmp_path))
        for i in range(4):
            st.put_bytes(artifact_key(kind="t", i=i), b"x", kind="t")
        # a scan that started before every publish must delete nothing
        assert st.gc(keep=0, _scan_start=0.0) == 0
        assert len(st.entries()) == 4
        # a scan starting now (after the publishes) may collect them
        assert st.gc(keep=1, _scan_start=float("inf")) == 3

    @pytest.mark.slow
    def test_cross_process_publish_race_converges(self, tmp_path):
        """Two processes racing publish of the same keys end with one valid
        artifact per key and no torn reads (satellite: concurrent store
        access; the threaded race above runs in tier-1 — this subprocess
        variant is the cross-process proof, kept out of the tier-1 budget)."""
        snippet = """
import sys
sys.path.insert(0, {repo!r})
from thunder_tpu.compile_service.store import ArtifactStore, artifact_key
st = ArtifactStore({root!r})
for i in range(8):
    k = artifact_key(kind="race", i=i)
    assert st.put_bytes(k, ("payload-%d" % i).encode() * 64, kind="race",
                        meta={{"i": str(i)}})
    got = st.get_bytes(k)
    assert got is not None and got[0].startswith(b"payload-")
print("ok")
""".format(repo=REPO, root=str(tmp_path))
        env = {**os.environ, "PYTHONPATH": REPO}
        procs = [subprocess.Popen([sys.executable, "-c", snippet], env=env,
                                  stdout=subprocess.PIPE,
                                  stderr=subprocess.PIPE, text=True)
                 for _ in range(2)]
        for p in procs:
            out, err = p.communicate(timeout=300)
            assert p.returncode == 0, err[-2000:]
            assert out.strip().endswith("ok")
        st = ArtifactStore(str(tmp_path))
        ents = [m for m in st.entries() if not m.get("_invalid")]
        assert len(ents) == 8
        for m in ents:
            ok, problems = st.validate(m["key"])
            assert ok, problems


# -- aot_cache shim (sha-verified executables) -------------------------------

class TestAotShim:
    @pytest.fixture
    def compiled_id(self):
        import jax

        spec = jax.ShapeDtypeStruct((4,), np.float32)
        return jax.jit(lambda x: x + 1).lower(spec).compile()

    def test_save_load_roundtrip_verified(self, tmp_path, monkeypatch, compiled_id):
        import jax.numpy as jnp

        from thunder_tpu.utils import aot_cache

        monkeypatch.setenv("TT_ARTIFACT_DIR", str(tmp_path))
        assert aot_cache.enabled()
        assert aot_cache.save_keyed("base0" * 12, "d" * 64, compiled_id)
        loaded, outcome = aot_cache.load_keyed("base0" * 12, "d" * 64)
        assert outcome == "hit" and loaded is not None
        np.testing.assert_allclose(
            np.asarray(loaded(jnp.zeros(4, jnp.float32))), np.ones(4))

    def test_corrupt_entry_evicted_not_unpickled(self, tmp_path, monkeypatch,
                                                 compiled_id):
        """Satellite: the publish-time sha256 is verified BEFORE pickle
        deserialization; a mismatch evicts instead of raising (the old
        format pickle.load'd unvalidated bytes)."""
        from thunder_tpu.compile_service.store import get_store
        from thunder_tpu.utils import aot_cache

        monkeypatch.setenv("TT_ARTIFACT_DIR", str(tmp_path))
        assert aot_cache.save_keyed("base1" * 12, "d" * 64, compiled_id)
        st = get_store(str(tmp_path))
        [m] = list(st.find(kind="step", base_key="base1" * 12))
        # tamper: a malicious/torn payload must never reach pickle.loads
        with open(os.path.join(st._entry_dir(m["key"]), "artifact.bin"),
                  "r+b") as f:
            f.write(b"cPickle-bomb")
        loaded, outcome = aot_cache.load_keyed("base1" * 12, "d" * 64)
        assert loaded is None and outcome == "corrupt"
        assert not st.contains(m["key"]), "corrupt entry not evicted"

    def test_stale_digest_evicted(self, tmp_path, monkeypatch, compiled_id):
        from thunder_tpu.utils import aot_cache

        monkeypatch.setenv("TT_ARTIFACT_DIR", str(tmp_path))
        assert aot_cache.save_keyed("base2" * 12, "a" * 64, compiled_id)
        loaded, outcome = aot_cache.load_keyed("base2" * 12, "b" * 64)
        assert loaded is None and outcome == "stale"
        # the stale entry is gone; the next probe is a clean miss
        loaded, outcome = aot_cache.load_keyed("base2" * 12, "b" * 64)
        assert outcome == "miss"


# -- parallel region compilation --------------------------------------------

def _matmul_chain(a, b):
    c = ltorch.matmul(a, b)
    d = ltorch.matmul(c, b)
    return ltorch.sum(d + c)


class TestParallelCompile:
    def test_prewarm_regions_and_store_hit(self, tmp_path, monkeypatch):
        """With the service enabled, fusion regions compile at transform
        time (compile_region spans), dispatch uses the prewarmed
        executable, and a second compile of the same program is served
        from the artifact store."""
        import jax.numpy as jnp

        from thunder_tpu.compile_service import parallel_compile as pc
        from thunder_tpu.compile_service.store import get_store

        monkeypatch.setenv("TT_ARTIFACT_DIR", str(tmp_path))
        monkeypatch.setenv("TT_PARALLEL_COMPILE", "1")
        assert pc.parallel_compile_enabled()
        a = jnp.ones((8, 8), jnp.float32)
        b = jnp.eye(8, dtype=jnp.float32)
        observability.enable()
        try:
            observability.reset()
            f1 = tt.jit(_matmul_chain)
            assert f1.prewarm(a, b) is True   # compile, no execution
            assert f1.prewarm(a, b) is False  # already specialized
            want = float(f1(a, b))
            ex_trc = tt.last_traces(f1)[-1]
            regions = pc.fusion_regions(ex_trc)
            assert regions, "no fusion regions formed"
            assert all(r.impl._prewarmed is not None for r in regions)
            recs = observability.records()
            spans = [r for r in recs if r.get("kind") == "span"
                     and r["name"] == "compile_region"]
            assert spans and spans[0]["attrs"]["outcome"] == "compiled"
            # no lazy first-dispatch compile happened
            assert not [r for r in recs if r.get("kind") == "span"
                        and r["name"] == "xla_compile"]
            # a second identical program is served from the store
            st = get_store(str(tmp_path))
            hits0 = st.stats()["hits"]
            f2 = tt.jit(_matmul_chain)
            assert abs(float(f2(a, b)) - want) < 1e-5
            assert st.stats()["hits"] > hits0
            c = observability.counters()
            assert c.get("compile.regions_prewarmed", 0) >= 2
            assert c.get("compile.region_store_hits", 0) >= 1
            assert c.get("artifact.hit", 0) >= 1
        finally:
            observability.disable()
            observability.reset()

    def test_disabled_by_default_on_cpu(self, monkeypatch):
        from thunder_tpu.compile_service import parallel_compile as pc

        monkeypatch.delenv("TT_PARALLEL_COMPILE", raising=False)
        monkeypatch.delenv("TT_ARTIFACT_DIR", raising=False)
        monkeypatch.delenv("TT_AOT_CACHE_DIR", raising=False)
        assert not pc.parallel_compile_enabled()
        monkeypatch.setenv("TT_PARALLEL_COMPILE", "0")
        monkeypatch.setenv("TT_ARTIFACT_DIR", "/tmp/x")
        assert not pc.parallel_compile_enabled()  # explicit off wins

    def test_interpreted_prewarm_symbolic_numbers(self):
        """prewarm passes the runtime numbers symbolic-values prologues
        expect — a second prewarm with a different (unobserved) scalar must
        match the existing entry, not compile a duplicate."""
        import jax.numpy as jnp

        if sys.version_info[:2] not in ((3, 12), (3, 13)):
            pytest.skip("symbolic values rides the bytecode-interpreter "
                        "frontend (CPython 3.12/3.13 only)")

        f = tt.jit(lambda x, s: ltorch.mul(x, s), cache="symbolic values")
        a = jnp.ones((4,), jnp.float32)
        assert f.prewarm(a, 2.0) is True
        assert f.prewarm(a, 3.0) is False, "symbolic entry not reused"
        assert len(f._entries) == 1
        np.testing.assert_allclose(np.asarray(f(a, 5.0)), 5.0 * np.ones(4))

    def test_prewarm_matches_lazy_numerics(self, monkeypatch):
        import jax.numpy as jnp

        monkeypatch.setenv("TT_PARALLEL_COMPILE", "1")
        monkeypatch.setenv("TT_NO_ARTIFACT_STORE", "1")  # pool only, no disk
        a = jnp.arange(16.0, dtype=jnp.float32).reshape(4, 4)
        b = jnp.ones((4, 4), jnp.float32)
        warm = float(tt.jit(_matmul_chain)(a, b))
        monkeypatch.setenv("TT_PARALLEL_COMPILE", "0")
        lazy = float(tt.jit(_matmul_chain)(a, b))
        assert abs(warm - lazy) < 1e-5


# -- bucketed TrainStep (shared ladder) --------------------------------------

class TestBucketedTraining:
    def test_shape_sweep_zero_recompiles(self):
        """Acceptance: one compiled (and storable) artifact serves >=3
        distinct sequence lengths with steady-state recompiles pinned at
        zero — the trainer-side collapse onto the shared BucketLadder."""
        import jax.numpy as jnp

        from thunder_tpu import optim
        from thunder_tpu.models.litgpt import Config, GPTForCausalLM
        from thunder_tpu.training import TrainStep

        cfg = Config.from_name("tiny")
        ladder = BucketLadder(32, 128)
        step = TrainStep(GPTForCausalLM(cfg), optim.AdamW(lr=1e-3),
                         buckets=ladder, bucket_pad={1: -100})
        rng = np.random.RandomState(0)

        def batch(T):
            idx = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, T)), jnp.int32)
            tgt = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, T)), jnp.int32)
            return idx, tgt

        losses = [float(step(*batch(T))) for T in (20, 32, 27)]  # bucket 32
        assert all(np.isfinite(l) for l in losses)
        jitted_after_first_bucket = step._jitted
        observability.enable()
        try:
            observability.reset()
            for T in (17, 25, 31):  # still bucket 32: zero recompiles
                assert np.isfinite(float(step(*batch(T))))
            assert step._jitted is jitted_after_first_bucket
            c = observability.counters()
            assert not any(k.startswith("recompile.") for k in c), c
        finally:
            observability.disable()
            observability.reset()
        assert ladder.mru()[0] == 32
        assert sum(ladder.hits().values()) == 6

    @pytest.mark.slow
    def test_pad_masked_out_of_loss(self):
        """Padding with ignore_index must not change the loss: the padded
        program is the SAME computation on a bucket-shaped batch. (A second
        tiny-GPT TrainStep compile — kept out of the tier-1 budget; run
        with -m compile.)"""
        import jax.numpy as jnp

        from thunder_tpu import optim
        from thunder_tpu.models.litgpt import Config, GPTForCausalLM
        from thunder_tpu.training import TrainStep

        cfg = Config.from_name("tiny")
        model = GPTForCausalLM(cfg)
        rng = np.random.RandomState(1)
        idx = rng.randint(0, cfg.vocab_size, (2, 24)).astype(np.int32)
        tgt = rng.randint(0, cfg.vocab_size, (2, 24)).astype(np.int32)
        # same params for both steps: bucketed vs exact-length
        bucketed = TrainStep(model, optim.SGD(lr=0.0),
                             buckets=BucketLadder(32, 64),
                             bucket_pad={1: -100})
        l_b = float(bucketed(jnp.asarray(idx), jnp.asarray(tgt)))
        exact = TrainStep(model, optim.SGD(lr=0.0))
        l_e = float(exact(jnp.asarray(idx), jnp.asarray(tgt)))
        np.testing.assert_allclose(l_b, l_e, rtol=2e-3)

    def test_serving_routes_through_shared_ladder(self):
        """No separate ShapeKeyedMRU keying path: the scheduler's bucket
        traffic is the ladder's, and the rounding rule is shared with
        bucket_len (the compat shim)."""
        from thunder_tpu.serving.runner import bucket_len
        from thunder_tpu.serving.scheduler import ServingEngine

        assert not hasattr(ServingEngine, "_touch_bucket")
        l = BucketLadder(16, 256, page_size=16)
        for n in (1, 16, 17, 100, 250, 300):
            assert bucket_len(n, minimum=16, maximum=256) == l.bucket_for(n)


# -- tools -------------------------------------------------------------------

class TestCacheInspect:
    def _store_with_entries(self, tmp_path, n=3):
        st = ArtifactStore(str(tmp_path))
        keys = []
        for i in range(n):
            k = artifact_key(kind="t", i=i)
            st.put_bytes(k, b"payload" * (i + 1), kind="region" if i else "step",
                         meta={"fn": f"f{i}"})
            keys.append(k)
        return st, keys

    def test_list_validate_exit_codes(self, tmp_path, capsys):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import cache_inspect

        st, keys = self._store_with_entries(tmp_path)
        assert cache_inspect.main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "ok" in out and "key fields" in out
        # corrupt one entry -> exit 1 with the problem named
        with open(os.path.join(st._entry_dir(keys[0]), "artifact.bin"), "wb") as f:
            f.write(b"bad")
        assert cache_inspect.main([str(tmp_path)]) == 1
        assert "INVALID" in capsys.readouterr().out
        # empty dir -> exit 2
        empty = tmp_path / "empty"
        empty.mkdir()
        assert cache_inspect.main([str(empty)]) == 2

    def test_gc_and_json(self, tmp_path, capsys):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import cache_inspect

        self._store_with_entries(tmp_path, n=4)
        assert cache_inspect.main([str(tmp_path), "--json"]) == 0
        rows = json.loads(capsys.readouterr().out)
        assert len(rows) == 4 and all(r["valid"] for r in rows)

    def test_obs_summary_compile_section(self, tmp_path, capsys):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        import obs_summary

        recs = [
            {"kind": "counter", "name": "artifact.hit", "value": 2, "ts_ms": 1.0},
            {"kind": "counter", "name": "compile.regions_prewarmed", "value": 3,
             "ts_ms": 1.5},
            {"kind": "event", "name": "compile_artifact_hit", "ts_ms": 2.0,
             "attrs": {"key": "abc", "kind": "step"}},
            {"kind": "span", "name": "compile_region", "ts_ms": 3.0,
             "dur_ms": 12.5, "span": 1,
             "attrs": {"region": "xla_fusion_0", "outcome": "compiled"}},
        ]
        lines = obs_summary.compile_lines(recs, obs_summary.final_counters(recs))
        text = "\n".join(lines)
        assert "artifact.hit" in text and "regions_prewarmed" in text
        assert "xla_fusion_0" in text and "hit" in text
        out = obs_summary.render(recs)
        assert "== compile ==" in out

