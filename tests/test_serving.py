"""Serving engine: continuous batching + paged KV cache correctness.

The engine contract under test: every request decoded under continuous
batching produces EXACTLY the token stream it would produce running solo
through the dense GPTInference engine — whatever mix of lengths, slots, and
admission waits it experienced — and a finished request's pages return to
the pool immediately. Runs entirely under JAX_PLATFORMS=cpu (conftest);
the pallas paged kernel path is covered in interpret mode by
tests/test_inference.py's equivalence tests.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu.inference import GPTInference
from thunder_tpu.models.litgpt import Config, GPT
from thunder_tpu.serving import (OutOfPages, PageAllocator, PagedKVCache,
                                 PrefixCache, ServingEngine)
from thunder_tpu.serving.runner import bucket_len

pytestmark = pytest.mark.serve


@pytest.fixture(scope="module")
def gpt():
    cfg = Config.from_name("tiny-llama2", block_size=64)
    return GPT(cfg, dtype=jnp.float32)


@pytest.fixture(scope="module")
def dense(gpt):
    return GPTInference(gpt, dtype=jnp.float32)


def _engine(gpt, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq", 64)
    kw.setdefault("dtype", jnp.float32)
    return ServingEngine(gpt, **kw)


# ---------------------------------------------------------------------------
# allocator / page-pool unit behavior
# ---------------------------------------------------------------------------


def test_allocator_freelist_roundtrip():
    a = PageAllocator(8)  # 7 usable + null
    assert a.n_free == 7
    got = a.alloc(5)
    assert len(set(got)) == 5 and 0 not in got
    assert a.n_used == 5
    with pytest.raises(OutOfPages):
        a.alloc(3)
    a.free(got[:2])
    assert a.n_free == 4
    with pytest.raises(ValueError):
        a.free([got[0]])  # double free
    with pytest.raises(ValueError):
        a.free([0])  # the null page is never allocatable


def test_page_table_row_pads_with_null():
    cache = PagedKVCache(1, 8, 4, 2, 8, jnp.float32)
    row = cache.page_table_row([3, 5], 4)
    assert row.tolist() == [3, 5, 0, 0]


def test_bucket_len_powers_of_two():
    assert bucket_len(1, minimum=8, maximum=64) == 8
    assert bucket_len(8, minimum=8, maximum=64) == 8
    assert bucket_len(9, minimum=8, maximum=64) == 16
    assert bucket_len(33, minimum=8, maximum=64) == 64
    assert bucket_len(200, minimum=8, maximum=64) == 64  # capped


# ---------------------------------------------------------------------------
# engine correctness vs the dense solo engine
# ---------------------------------------------------------------------------


def test_single_request_matches_dense(gpt, dense, rng):
    engine = _engine(gpt)
    prompt = rng.randint(0, gpt.cfg.vocab_size, (9,)).astype(np.int32)
    fut = engine.submit(prompt, max_new_tokens=6)
    engine.drain()
    res = fut.result()
    out, _ = dense.generate(jnp.asarray(prompt[None, :]), 6, scan_decode=False)
    np.testing.assert_array_equal(res.new_tokens, np.asarray(out)[0, 9:])
    assert res.tokens.shape == (15,)
    assert res.finish_reason == "length"
    assert res.ttft_s > 0 and res.tbot_s > 0


def test_concurrent_mixed_lengths_match_dense(gpt, dense, rng):
    """More requests than decode slots, mixed prompt/output lengths: every
    stream must equal its solo dense decode (slot reuse + admission waits
    must not perturb any sequence)."""
    engine = _engine(gpt)
    shapes = [(5, 7), (13, 4), (9, 10), (20, 3), (3, 8), (11, 5)]
    reqs = []
    for L, n in shapes:
        p = rng.randint(0, gpt.cfg.vocab_size, (L,)).astype(np.int32)
        reqs.append((p, n, engine.submit(p, max_new_tokens=n)))
    engine.drain()
    for p, n, fut in reqs:
        res = fut.result()
        out, _ = dense.generate(jnp.asarray(p[None, :]), n, scan_decode=False)
        np.testing.assert_array_equal(res.new_tokens, np.asarray(out)[0, len(p):])
    # all pages returned at retirement
    assert engine.cache.allocator.n_used == 0
    assert engine.stats()["page_pool_utilization"] == 0.0


def test_temperature_stream_matches_dense_seeded(gpt, dense, rng):
    """Position-keyed sampling: the same (seed, temperature) request draws
    the identical stream solo or continuously batched."""
    engine = _engine(gpt)
    p = rng.randint(0, gpt.cfg.vocab_size, (7,)).astype(np.int32)
    fut = engine.submit(p, max_new_tokens=8, temperature=0.9, seed=42)
    # a concurrent greedy request keeps the batch genuinely mixed
    other = engine.submit(rng.randint(0, gpt.cfg.vocab_size, (12,)).astype(np.int32),
                          max_new_tokens=5)
    engine.drain()
    res = fut.result()
    other.result()
    out, _ = dense.generate(jnp.asarray(p[None, :]), 8, temperature=0.9,
                            seed=42, scan_decode=False)
    np.testing.assert_array_equal(res.new_tokens, np.asarray(out)[0, 7:])


def test_eos_retires_early_and_frees_pages(gpt, rng):
    engine = _engine(gpt)
    p = rng.randint(0, gpt.cfg.vocab_size, (6,)).astype(np.int32)
    # find the greedy continuation's second token, then use it as eos
    probe = engine.submit(p, max_new_tokens=3)
    engine.drain()
    tok2 = int(probe.result().new_tokens[1])
    fut = engine.submit(p, max_new_tokens=30, eos_id=tok2)
    engine.drain()
    res = fut.result()
    assert res.finish_reason == "eos"
    assert res.n_new_tokens == 2  # stopped at eos, 28 tokens early
    assert engine.cache.allocator.n_used == 0


def test_admission_waits_for_pages_then_completes(gpt, dense, rng):
    """A pool sized for ~one sequence forces head-of-line waiting; both
    requests must still complete correctly (pages return at retirement)."""
    # 9 usable pages: one (L=9, n=7) request needs bucket 16/8=2 prefill
    # pages and ceil(16/8)=2 worst-case -> 2; three requests need 6; size
    # the pool so only one fits at a time
    engine = _engine(gpt, n_pages=4)
    reqs = []
    for _ in range(3):
        p = rng.randint(0, gpt.cfg.vocab_size, (9,)).astype(np.int32)
        reqs.append((p, engine.submit(p, max_new_tokens=7)))
    engine.drain()
    for p, fut in reqs:
        res = fut.result()
        out, _ = dense.generate(jnp.asarray(p[None, :]), 7, scan_decode=False)
        np.testing.assert_array_equal(res.new_tokens, np.asarray(out)[0, 9:])
    assert engine.cache.allocator.n_used == 0


def test_inadmissible_requests_fail_fast(gpt, rng):
    engine = _engine(gpt)
    p = rng.randint(0, gpt.cfg.vocab_size, (60,)).astype(np.int32)
    with pytest.raises(ValueError, match="max_seq"):
        engine.submit(p, max_new_tokens=10).result()  # 60 + 10 > 64
    small = _engine(gpt, n_pages=3)  # 2 usable pages
    big = rng.randint(0, gpt.cfg.vocab_size, (40,)).astype(np.int32)
    with pytest.raises(ValueError, match="pages"):
        small.submit(big, max_new_tokens=8).result()


def test_background_thread_driver(gpt, dense, rng):
    """submit() from the caller thread while the loop runs in background."""
    engine = _engine(gpt)
    engine.start()
    try:
        p = rng.randint(0, gpt.cfg.vocab_size, (8,)).astype(np.int32)
        res = engine.submit(p, max_new_tokens=5).result(timeout=120)
        out, _ = dense.generate(jnp.asarray(p[None, :]), 5, scan_decode=False)
        np.testing.assert_array_equal(res.new_tokens, np.asarray(out)[0, 8:])
    finally:
        engine.stop()


# ---------------------------------------------------------------------------
# steady-state compile behavior + observability
# ---------------------------------------------------------------------------


def test_zero_steady_state_recompiles(gpt, rng):
    """After warming the decode step and each prompt bucket, a fresh wave of
    mixed-length requests must trigger ZERO reason-coded recompile events —
    the acceptance bar for shape-bucketed continuous batching."""
    from thunder_tpu import observability

    engine = _engine(gpt)
    engine.warmup([3, 9, 17], max_new_tokens=2)  # buckets 8, 16, 32
    observability.enable()
    observability.reset()
    try:
        reqs = []
        for L, n in [(4, 5), (10, 3), (18, 6), (7, 4), (15, 7)]:
            p = rng.randint(0, gpt.cfg.vocab_size, (L,)).astype(np.int32)
            reqs.append(engine.submit(p, max_new_tokens=n))
        engine.drain()
        for fut in reqs:
            fut.result()
        counters = observability.counters()
        recompiles = {k: v for k, v in counters.items() if k.startswith("recompile.")}
        assert not recompiles, f"steady state recompiled: {recompiles}"
        assert counters.get("serve.requests", 0) == 5
        assert counters.get("serve.retired", 0) == 5
        assert counters.get("serve.decode_steps", 0) > 0
        assert counters.get("serve.tokens", 0) == sum(n - 1 for _, n in
                                                      [(4, 5), (10, 3), (18, 6), (7, 4), (15, 7)])
    finally:
        observability.disable()
        observability.reset()


def test_request_spans_and_retire_events(gpt, rng):
    """Per-request observability: request-id-tagged prefill spans and
    serve_retired events with TTFT/TBOT land on the bus."""
    from thunder_tpu import observability

    engine = _engine(gpt)
    observability.enable()
    observability.reset()
    try:
        p = rng.randint(0, gpt.cfg.vocab_size, (6,)).astype(np.int32)
        rid = None
        fut = engine.submit(p, max_new_tokens=4)
        engine.drain()
        rid = fut.result().request_id
        recs = observability.records()
        prefills = [r for r in recs if r["kind"] == "span" and r["name"] == "serve_prefill"]
        assert any(r["attrs"].get("request") == rid for r in prefills)
        retires = [r for r in recs if r["kind"] == "event" and r["name"] == "serve_retired"]
        assert len(retires) == 1
        attrs = retires[0]["attrs"]
        assert attrs["request"] == rid and attrs["n_new"] == 4
        assert attrs["ttft_ms"] > 0 and attrs["tbot_ms"] > 0
        decodes = [r for r in recs if r["kind"] == "span" and r["name"] == "serve_decode"]
        assert decodes and all(r["attrs"]["active"] >= 1 for r in decodes)
    finally:
        observability.disable()
        observability.reset()


def test_prefill_bucket_mru_promotes(gpt, rng):
    """The serving engine rides the interpreter frontend's ShapeKeyedMRU:
    the bucket that just served probes first."""
    engine = _engine(gpt)
    for L in (3, 20):  # buckets 8, 32
        engine.submit(rng.randint(0, gpt.cfg.vocab_size, (L,)).astype(np.int32), 2)
    engine.drain()
    assert engine.stats()["prefill_buckets"] == [32, 8]
    engine.submit(rng.randint(0, gpt.cfg.vocab_size, (4,)).astype(np.int32), 2)
    engine.drain()
    assert engine.stats()["prefill_buckets"] == [8, 32]


def test_prefill_failure_contained(gpt, dense, rng):
    """A request whose compiled step raises must fail ITS Future, return its
    pages, and leave the engine serving later requests — not kill the loop
    and hang every waiter."""
    engine = _engine(gpt)
    orig = engine.runner.prefill_cfn

    def boom(*a, **kw):
        raise RuntimeError("injected prefill failure")

    engine.runner.prefill_cfn = boom
    p = rng.randint(0, gpt.cfg.vocab_size, (6,)).astype(np.int32)
    fut = engine.submit(p, max_new_tokens=4)
    engine.drain()
    with pytest.raises(RuntimeError, match="injected"):
        fut.result(timeout=5)
    assert engine.cache.allocator.n_used == 0  # pages returned
    engine.runner.prefill_cfn = orig
    ok = engine.submit(p, max_new_tokens=4)
    engine.drain()
    out, _ = dense.generate(jnp.asarray(p[None, :]), 4, scan_decode=False)
    np.testing.assert_array_equal(ok.result().new_tokens, np.asarray(out)[0, 6:])


def test_decode_failure_fails_active_batch(gpt, rng):
    """A failing packed decode step fails every implicated Future and frees
    their pages; the engine stays usable."""
    engine = _engine(gpt)
    p1 = rng.randint(0, gpt.cfg.vocab_size, (6,)).astype(np.int32)
    p2 = rng.randint(0, gpt.cfg.vocab_size, (10,)).astype(np.int32)
    f1 = engine.submit(p1, max_new_tokens=8)
    f2 = engine.submit(p2, max_new_tokens=8)
    orig = engine.runner.decode_cfn
    engine.runner.decode_cfn = lambda *a, **kw: (_ for _ in ()).throw(
        RuntimeError("injected decode failure"))
    engine.drain()
    for f in (f1, f2):
        with pytest.raises(RuntimeError, match="injected"):
            f.result(timeout=5)
    assert engine.cache.allocator.n_used == 0
    engine.runner.decode_cfn = orig
    ok = engine.submit(p1, max_new_tokens=3)
    engine.drain()
    assert ok.result().n_new_tokens == 3


def test_seed_canonicalized_mod_2_32(gpt, dense, rng):
    """Seeds outside [0, 2^32) draw the same stream as seed % 2^32 in BOTH
    engines (the packed sampler array is uint32)."""
    p = rng.randint(0, gpt.cfg.vocab_size, (6,)).astype(np.int32)
    engine = _engine(gpt)
    f_big = engine.submit(p, 6, temperature=1.0, seed=(1 << 32) + 5)
    f_small = engine.submit(p, 6, temperature=1.0, seed=5)
    engine.drain()
    np.testing.assert_array_equal(f_big.result().new_tokens,
                                  f_small.result().new_tokens)
    out_big, _ = dense.generate(jnp.asarray(p[None, :]), 6, temperature=1.0,
                                seed=(1 << 32) + 5, scan_decode=False)
    out_small, _ = dense.generate(jnp.asarray(p[None, :]), 6, temperature=1.0,
                                  seed=5, scan_decode=False)
    np.testing.assert_array_equal(np.asarray(out_big), np.asarray(out_small))
    np.testing.assert_array_equal(f_big.result().new_tokens,
                                  np.asarray(out_big)[0, 6:])


def test_cancelled_future_does_not_wedge_engine(gpt, dense, rng):
    """fut.cancel() must not blow up retirement or leave a slot stuck:
    queued cancellations are dropped before allocation, in-flight ones
    retire at the next step with pages freed, and later requests serve."""
    engine = _engine(gpt)
    p = rng.randint(0, gpt.cfg.vocab_size, (6,)).astype(np.int32)
    queued = engine.submit(p, max_new_tokens=4)
    assert queued.cancel()  # still pending -> cancellable
    live = engine.submit(p, max_new_tokens=4)
    engine.drain()
    assert queued.cancelled()
    out, _ = dense.generate(jnp.asarray(p[None, :]), 4, scan_decode=False)
    np.testing.assert_array_equal(live.result().new_tokens, np.asarray(out)[0, 6:])
    # in-flight cancel: admit, then cancel mid-decode via inline stepping
    f = engine.submit(p, max_new_tokens=30)
    engine._step_once()  # admits + first decode step
    assert f.cancel()  # engine futures are never set_running
    engine.drain()
    assert engine.cache.allocator.n_used == 0  # pages freed either way
    again = engine.submit(p, max_new_tokens=3)
    engine.drain()
    assert again.result().n_new_tokens == 3


def test_misaligned_min_bucket_rejected(gpt):
    with pytest.raises(ValueError, match="min_bucket"):
        _engine(gpt, min_bucket=20)  # not a multiple of page_size=8


def test_intra_call_duplicate_free_rejected():
    a = PageAllocator(8)
    got = a.alloc(2)
    with pytest.raises(ValueError, match="double free"):
        a.free([got[0], got[0]])
    a.free(got)  # the failed call must not have mutated anything
    assert a.n_free == 7


def test_stop_fails_outstanding_futures(gpt, rng):
    """stop() must not strand waiters: whatever is still queued or
    in-flight fails with a clear error and its pages come back."""
    engine = _engine(gpt)
    p = rng.randint(0, gpt.cfg.vocab_size, (6,)).astype(np.int32)
    inflight = engine.submit(p, max_new_tokens=30)
    engine._step_once()  # admit + one decode step
    queued = engine.submit(p, max_new_tokens=4)
    engine.stop()
    for f in (inflight, queued):
        with pytest.raises(RuntimeError, match="stopped"):
            f.result(timeout=5)
    assert engine.cache.allocator.n_used == 0


def test_submit_after_stop_fails_fast(gpt, rng):
    engine = _engine(gpt)
    engine.start()
    engine.stop()
    p = rng.randint(0, gpt.cfg.vocab_size, (6,)).astype(np.int32)
    with pytest.raises(RuntimeError, match="stopped"):
        engine.submit(p, max_new_tokens=3).result(timeout=5)
    engine.start()  # restartable
    try:
        assert engine.submit(p, max_new_tokens=3).result(timeout=120).n_new_tokens == 3
    finally:
        engine.stop()


def test_drain_with_running_thread_only_waits(gpt, dense, rng):
    """drain() alongside the background thread must wait, not step inline
    (inline stepping would race the thread over slots/pool state)."""
    engine = _engine(gpt)
    engine.start()
    try:
        p = rng.randint(0, gpt.cfg.vocab_size, (7,)).astype(np.int32)
        fut = engine.submit(p, max_new_tokens=5)
        engine.drain()
        assert fut.done()
        out, _ = dense.generate(jnp.asarray(p[None, :]), 5, scan_decode=False)
        np.testing.assert_array_equal(fut.result().new_tokens, np.asarray(out)[0, 7:])
    finally:
        engine.stop()


def test_index_put_negative_indices_normalized(rng):
    """The multi-index linearization canonicalizes numpy-style negative
    indices per-dim (a raw -1 would address the previous row's last slot)."""
    from thunder_tpu.ops import ltorch

    a = jnp.zeros((4, 8, 3), jnp.float32)
    vals = jnp.asarray(rng.randn(2, 3), jnp.float32)
    f = tt.jit(lambda a, i0, i1, v: ltorch.index_put(a, (i0, i1), v))
    out = f(a, jnp.asarray([1, 2], jnp.int32), jnp.asarray([-1, 0], jnp.int32), vals)
    ref = np.zeros((4, 8, 3), np.float32)
    ref[1, -1] = np.asarray(vals)[0]
    ref[2, 0] = np.asarray(vals)[1]
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_moe_serving_matches_dense(rng):
    """The engine drives the MoE decoder too (block plumbing parity with
    inference._forward_cached)."""
    from thunder_tpu.models.moe import MoEConfig, MoEGPT

    cfg = Config.from_name("tiny-llama2", block_size=64)
    moe_cfg = MoEConfig(n_embd=cfg.n_embd, intermediate_size=160,
                        n_expert=4, n_expert_per_token=2)
    gpt = MoEGPT(cfg, moe_cfg, dtype=jnp.float32)
    engine = _engine(gpt)
    dense = GPTInference(gpt, dtype=jnp.float32)
    p = rng.randint(0, cfg.vocab_size, (8,)).astype(np.int32)
    fut = engine.submit(p, max_new_tokens=5)
    engine.drain()
    out, _ = dense.generate(jnp.asarray(p[None, :]), 5, scan_decode=False)
    np.testing.assert_array_equal(fut.result().new_tokens, np.asarray(out)[0, 8:])

# ---------------------------------------------------------------------------
# fleet serving: refcounts / CoW, prefix sharing, chunked prefill,
# speculative decoding, lanes + preemption
# ---------------------------------------------------------------------------


def test_allocator_refcounts():
    a = PageAllocator(8)
    (p,) = a.alloc(1)
    assert a.refcount(p) == 1
    a.incref(p)
    assert a.refcount(p) == 2
    a.free([p])            # decref: the page must NOT return to the free list
    assert a.refcount(p) == 1
    assert a.n_free == 6
    a.free([p])            # last owner lets go -> released
    assert a.refcount(p) == 0
    assert a.n_free == 7
    with pytest.raises(ValueError, match="double free"):
        a.free([p])
    with pytest.raises(ValueError, match="incref"):
        a.incref(p)        # incref of a free page is a use-after-free


def test_shared_page_free_does_not_reissue():
    """A shared page freed by ONE owner must never be handed to a new
    allocation while other owners hold it (the double-free-under-sharing
    hazard the refcount exists to kill)."""
    a = PageAllocator(4)   # 3 usable
    pages = a.alloc(3)
    a.incref(pages[0])     # second owner
    a.free([pages[0]])     # first owner retires
    with pytest.raises(OutOfPages):
        a.alloc(1)         # nothing is actually free
    a.free(pages)          # remaining owners let go of everything
    assert a.n_free == 3 and a.n_used == 0


def test_cow_fork():
    a = PageAllocator(8)
    (p,) = a.alloc(1)
    assert a.fork(p) == p  # sole owner: write-in-place, no copy
    a.incref(p)
    q = a.fork(p)          # shared: detach into a fresh page
    assert q != p
    assert a.refcount(p) == 1 and a.refcount(q) == 1
    with pytest.raises(ValueError, match="fork"):
        a.fork(7)          # never-allocated page


def test_prefix_cache_match_insert_evict():
    a = PageAllocator(16)
    c = PrefixCache(a, 4)
    prompt = np.arange(10, dtype=np.int32)   # 2 full pages + 2-token tail
    pages = a.alloc(3)
    assert c.insert(prompt, pages) == 2      # only FULL prompt pages register
    assert a.refcount(pages[0]) == 2 and a.refcount(pages[2]) == 1
    shared, covered = c.match(prompt[:8])
    assert covered == 8 and shared == pages[:2]
    assert a.refcount(pages[0]) == 3         # match increfs for the caller
    a.free(shared)
    # partial tail: a 6-token prompt whose tail is the LEADING tokens of a
    # cached page is fully covered by sharing that page
    shared, covered = c.match(prompt[:6])
    assert covered == 6 and shared == pages[:2]
    a.free(shared)
    a.free(pages)                            # original owner retires
    assert len(c) == 2 and a.n_used == 2     # cache refs keep 2 pages alive
    assert c.evict_until(15)                 # pool pressure: evict LRU leaves
    assert len(c) == 0 and a.n_used == 0 and a.n_free == 15


def test_prefix_sharing_suffix_prefill_matches_dense(gpt, dense, rng):
    """Requests sharing a system prompt map the donor's pages and prefill
    only the unshared suffix; every stream still equals its solo decode."""
    engine = _engine(gpt, prefix_sharing=True)
    sys_p = rng.randint(0, gpt.cfg.vocab_size, (16,)).astype(np.int32)  # 2 pages
    reqs = []
    for i in range(3):
        tail = rng.randint(0, gpt.cfg.vocab_size, (3,)).astype(np.int32)
        p = np.concatenate([sys_p, tail])
        reqs.append((p, engine.submit(p, max_new_tokens=5, temperature=0.7,
                                      seed=100 + i)))
    engine.drain()
    for p, fut in reqs:
        out, _ = dense.generate(jnp.asarray(p[None, :]), 5, temperature=0.7,
                                seed=int(fut.result().request_id) + 100,
                                scan_decode=False)
        np.testing.assert_array_equal(fut.result().new_tokens,
                                      np.asarray(out)[0, len(p):])
    assert engine.prefix_hits == 2                 # requests 2 and 3
    assert engine.prefix_tokens_saved == 2 * 16


def test_prefix_full_hit_skips_prefill(gpt, dense, rng):
    """Full coverage (including a partial-tail hit) admits with NO prefill:
    one re-decoded prompt token recovers the first-token logits."""
    engine = _engine(gpt, prefix_sharing=True)
    donor = rng.randint(0, gpt.cfg.vocab_size, (16,)).astype(np.int32)
    f1 = engine.submit(donor, max_new_tokens=4, seed=7)
    engine.drain()
    # exact repeat: both full pages hit
    f2 = engine.submit(donor, max_new_tokens=4, seed=7)
    engine.drain()
    np.testing.assert_array_equal(f1.result().new_tokens, f2.result().new_tokens)
    assert engine.prefix_hits == 1
    assert engine.prefix_tokens_saved == 15        # L - 1
    # partial-tail: an 11-token prefix of the donor is covered by page 2
    sub = donor[:11]
    f3 = engine.submit(sub, max_new_tokens=4, temperature=0.5, seed=9)
    engine.drain()
    out, _ = dense.generate(jnp.asarray(sub[None, :]), 4, temperature=0.5,
                            seed=9, scan_decode=False)
    np.testing.assert_array_equal(f3.result().new_tokens,
                                  np.asarray(out)[0, 11:])
    assert engine.prefix_hits == 2
    # donor pages stay intact (copy-on-write protected them from f2/f3 writes)
    f4 = engine.submit(donor, max_new_tokens=4, seed=7)
    engine.drain()
    np.testing.assert_array_equal(f4.result().new_tokens, f1.result().new_tokens)


def test_chunked_prefill_matches_dense(gpt, dense, rng):
    """Long prompts split into page-aligned chunks interleaved under the
    token budget produce streams identical to whole-prompt prefill."""
    engine = _engine(gpt, chunk_tokens=16, prefill_budget=16)
    shapes = [(40, 5), (23, 4)]   # 16+16+final rung, 16+final (mid-page end)
    reqs = []
    for L, n in shapes:
        p = rng.randint(0, gpt.cfg.vocab_size, (L,)).astype(np.int32)
        reqs.append((p, n, engine.submit(p, max_new_tokens=n)))
    engine.drain()
    for p, n, fut in reqs:
        out, _ = dense.generate(jnp.asarray(p[None, :]), n, scan_decode=False)
        np.testing.assert_array_equal(fut.result().new_tokens,
                                      np.asarray(out)[0, len(p):])
    assert engine.cache.allocator.n_used == 0      # no sharing -> all returned


def test_speculative_random_draft_matches_plain(gpt, dense, rng):
    """A draft with different weights proposes wrong tokens sometimes; the
    accept/rollback rule still commits exactly the plain-decode stream."""
    draft = GPT(Config.from_name("tiny-llama2", block_size=64), dtype=jnp.float32)
    engine = _engine(gpt, draft_gpt=draft, spec_k=2)
    p = rng.randint(0, gpt.cfg.vocab_size, (9,)).astype(np.int32)
    fut = engine.submit(p, max_new_tokens=6)
    engine.drain()
    out, _ = dense.generate(jnp.asarray(p[None, :]), 6, scan_decode=False)
    np.testing.assert_array_equal(fut.result().new_tokens,
                                  np.asarray(out)[0, 9:])
    assert engine.spec_proposed > 0
    assert engine.cache.allocator.n_used == 0


def test_all_stages_composed_match_dense(gpt, dense, rng):
    """Sharing + chunking + speculation all enabled at once: every request
    still decodes its exact solo stream (the tentpole equivalence bar).
    The draft IS the target, so this also pins the self-draft ceiling:
    every proposal must verify."""
    engine = _engine(gpt, prefix_sharing=True, chunk_tokens=16,
                     draft_gpt=gpt, spec_k=3)
    sys_p = rng.randint(0, gpt.cfg.vocab_size, (24,)).astype(np.int32)
    shapes = [(0, 6, 0.0, 11), (5, 7, 0.8, 12), (9, 4, 0.0, 13), (2, 5, 0.5, 14)]
    reqs = []
    for tail_len, n, temp, seed in shapes:
        tail = rng.randint(0, gpt.cfg.vocab_size, (tail_len,)).astype(np.int32)
        p = np.concatenate([sys_p, tail]) if tail_len else sys_p.copy()
        reqs.append((p, n, temp, seed,
                     engine.submit(p, max_new_tokens=n, temperature=temp,
                                   seed=seed)))
        if tail_len == 0:
            engine.drain()  # warm the prefix cache before the sharers arrive
    engine.drain()
    for p, n, temp, seed, fut in reqs:
        out, _ = dense.generate(jnp.asarray(p[None, :]), n, temperature=temp,
                                seed=seed, scan_decode=False)
        np.testing.assert_array_equal(fut.result().new_tokens,
                                      np.asarray(out)[0, len(p):])
    assert engine.prefix_hits > 0
    assert engine.spec_proposed > 0
    assert engine.spec_accepted == engine.spec_proposed  # perfect draft


def test_preemption_spill_resume_identity(gpt, dense, rng):
    """A batch-lane victim spilled for an interactive admission resumes and
    finishes with EXACTLY the stream it would have produced unpreempted."""
    engine = _engine(gpt, n_pages=9)               # 8 usable
    victim_p = rng.randint(0, gpt.cfg.vocab_size, (9,)).astype(np.int32)
    victim = engine.submit(victim_p, max_new_tokens=20, lane="batch")
    engine._step_once()                            # admit + a few tokens
    engine._step_once()
    # an interactive request needing the whole pool forces the spill
    inter_p = rng.randint(0, gpt.cfg.vocab_size, (33,)).astype(np.int32)
    inter = engine.submit(inter_p, max_new_tokens=5)
    engine.drain()
    assert engine.preempted == 1 and engine.resumed == 1
    out_v, _ = dense.generate(jnp.asarray(victim_p[None, :]), 20,
                              scan_decode=False)
    np.testing.assert_array_equal(victim.result().new_tokens,
                                  np.asarray(out_v)[0, 9:])
    out_i, _ = dense.generate(jnp.asarray(inter_p[None, :]), 5,
                              scan_decode=False)
    np.testing.assert_array_equal(inter.result().new_tokens,
                                  np.asarray(out_i)[0, 33:])
    assert engine.cache.allocator.n_used == 0


def test_no_leak_with_sharing_under_faults(gpt, rng):
    """Fault injection with sharing live: a failed suffix prefill must
    decref (not double-free) its shared pages, and after retirement only
    the prefix cache's own references remain."""
    engine = _engine(gpt, prefix_sharing=True)
    p_shared = rng.randint(0, gpt.cfg.vocab_size, (16,)).astype(np.int32)
    f1 = engine.submit(p_shared, max_new_tokens=4)
    engine.drain()
    f1.result()
    p2 = np.concatenate([p_shared,
                         rng.randint(0, gpt.cfg.vocab_size, (5,)).astype(np.int32)])
    orig = engine.runner.chunk_cfn
    engine.runner.chunk_cfn = lambda *a, **kw: (_ for _ in ()).throw(
        RuntimeError("injected chunk failure"))
    f2 = engine.submit(p2, max_new_tokens=4)
    engine.drain()
    with pytest.raises(RuntimeError, match="injected"):
        f2.result(timeout=5)
    engine.runner.chunk_cfn = orig
    # the shared pages survived the failure (cache refs intact): retry hits
    f3 = engine.submit(p2, max_new_tokens=4)
    engine.drain()
    f3.result()
    assert engine.prefix_hits == 2                 # f2 and f3 both matched
    # only cache-held references remain; eviction returns the pool to empty
    assert engine.cache.allocator.n_used == len(engine.prefix)
    engine.prefix.clear()
    assert engine.cache.allocator.n_used == 0
    pages = engine.cache.allocator.alloc(engine.cache.n_pages - 1)
    engine.cache.allocator.free(pages)             # free-list fully consistent


def test_lane_validation_and_batch_fifo(gpt, rng):
    engine = _engine(gpt)
    p = rng.randint(0, gpt.cfg.vocab_size, (6,)).astype(np.int32)
    with pytest.raises(ValueError, match="lane"):
        engine.submit(p, max_new_tokens=2, lane="bulk").result(timeout=5)
    fut = engine.submit(p, max_new_tokens=3, lane="batch")
    engine.drain()
    assert fut.result().n_new_tokens == 3


def test_paged_pages_counters_follow_the_lengths(gpt, rng):
    """`serve.paged.pages_live`: the pages the decode kernel walks, every slot of every decode
    step (an idle slot reads one); `serve.paged.pages_spanned`: slots x table width, what a grid
    of one program a table entry stepped over."""
    from thunder_tpu import observability

    engine = _engine(gpt)  # 4 slots, pages of 8, a table 8 wide
    prompt = rng.randint(0, gpt.cfg.vocab_size, (9,)).astype(np.int32)
    observability.enable()
    observability.reset()
    try:
        fut = engine.submit(prompt, max_new_tokens=20)
        engine.drain()
        counters = observability.counters()
    finally:
        observability.disable()
    assert fut.result().n_new_tokens == 20
    steps = counters["serve.decode_steps"]
    assert steps == 19  # the first token is the prefill's
    # the step that writes position p reads p + 1 keys: positions 9 .. 27, beside three idle slots
    live = sum(-(-(p + 1) // 8) + 3 for p in range(9, 9 + steps))
    assert counters["serve.paged.pages_live"] == live
    assert counters["serve.paged.pages_spanned"] == steps * 4 * 8
    assert sorted(n for n in counters if n.startswith("serve.paged.")) == [
        "serve.paged.pages_live", "serve.paged.pages_spanned"]


def _paged_counters(run):
    """The `serve.paged.*` counters of what ``run`` serves with the bus on."""
    from thunder_tpu import observability

    observability.enable()
    observability.reset()
    try:
        run()
        counters = observability.counters()
    finally:
        observability.disable()
    return {n[len("serve.paged."):]: v for n, v in counters.items() if n.startswith("serve.paged.")}, counters


def test_chunk_pages_counters_follow_the_chunks(gpt, rng):
    """`serve.paged.chunk_pages_live`: the pages a chunk's queries can see, once a chunk dispatch;
    `serve.paged.chunk_pages_spanned`: the table's width, what a grid of one program a table entry
    stepped over. Beside the decode step's pair, which the chunks leave alone."""
    engine = _engine(gpt, chunk_tokens=16, prefill_budget=16)  # pages of 8, a table 8 wide

    def run():
        for L in (48, 23):  # chunks at 0, 16, 32; then at 0 and a final one of 7 tokens on a rung of 8
            fut = engine.submit(rng.randint(0, gpt.cfg.vocab_size, (L,)).astype(np.int32), max_new_tokens=3)
            engine.drain()
            assert fut.result().n_new_tokens == 3

    paged, counters = _paged_counters(run)
    assert counters["serve.prefill_tokens"] == 48 + 23
    ends = [-(-(start + cb) // 8) for start, cb in ((0, 16), (16, 16), (32, 16), (0, 16), (16, 8))]
    assert paged["chunk_pages_live"] == sum(ends) == 17
    assert paged["chunk_pages_spanned"] == len(ends) * 8
    steps = counters["serve.decode_steps"]
    assert steps == 4 and paged["pages_spanned"] == steps * 4 * 8
    assert sorted(paged) == ["chunk_pages_live", "chunk_pages_spanned", "pages_live", "pages_spanned"]


def test_chunk_pages_counters_count_every_slot_of_a_verify_step(gpt, rng):
    """A verify dispatch is the packed program: every slot's k + 1 queries, an idle slot's on the
    null page."""
    engine = _engine(gpt, draft_gpt=gpt, spec_k=2)

    def run():
        fut = engine.submit(rng.randint(0, gpt.cfg.vocab_size, (9,)).astype(np.int32), max_new_tokens=7)
        engine.drain()
        assert fut.result().n_new_tokens == 7

    paged, counters = _paged_counters(run)
    steps = counters["serve.decode_steps"]
    assert steps == 3 and engine.spec_accepted == engine.spec_proposed == 6
    # a perfect draft: the sequence's three queries start at 9, 11, 13; three idle slots at 0
    assert paged["chunk_pages_live"] == sum(-(-(pos + 3) // 8) + 3 for pos in (9, 11, 13)) == 15
    assert paged["chunk_pages_spanned"] == steps * 4 * 8


# ---------------------------------------------------------------------------
# heads narrower than the lanes: cached several a row
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_kv_heads,head_size,expected", [
    (16, 64, 2), (8, 64, 2), (4, 32, 4), (2, 32, 1), (3, 64, 1), (8, 128, 1), (10, 128, 1),
    (8, 96, 1), (8, 256, 1), (8, 16, 8)])
def test_heads_a_row_fills_the_lanes_where_the_head_count_divides(n_kv_heads, head_size, expected):
    from thunder_tpu.serving.runner import heads_a_row

    assert heads_a_row(n_kv_heads, head_size) == expected


@pytest.mark.parametrize("kernel", ["decomposition", "kernel"])
@pytest.mark.parametrize("n_head,n_query_groups,pack", [(4, 4, 4), (8, 4, 4), (4, 2, 2)],
                         ids=["mha-4-a-row", "gqa-4-a-row", "gqa-2-a-row"])
def test_narrow_heads_are_cached_packed_and_match_dense(n_head, n_query_groups, pack, kernel,
                                                        rng, request):
    """Heads narrower than the 128 lanes are cached `pack` a row (a pool of n_query_groups / pack
    heads, 128 wide), which is the shape the decode kernel takes on the chip. Prefill, chunked
    prefill with a shared prefix, decode and speculative verify all write and read such rows, and
    every request still decodes its exact solo stream: through the gather decomposition and
    through both paged kernels (interpret mode)."""
    if kernel == "kernel":
        request.getfixturevalue("pallas_claims")
    head_size = 128 // pack
    cfg = Config.from_name("tiny-llama2", block_size=64, n_head=n_head, n_embd=n_head * head_size,
                           n_query_groups=n_query_groups, head_size=head_size)
    gpt = GPT(cfg, dtype=jnp.float32)
    dense = GPTInference(gpt, dtype=jnp.float32)
    engine = _engine(gpt, prefix_sharing=True, chunk_tokens=16, draft_gpt=gpt, spec_k=2)
    for cache in (engine.cache, engine.draft_cache):
        assert all(k.shape == (engine.cache.n_pages, n_query_groups // pack, 8, 128)
                   for k in cache.k_pages + cache.v_pages)
    sys_p = rng.randint(0, cfg.vocab_size, (24,)).astype(np.int32)
    reqs = []
    for tail_len, n, temp, seed in [(0, 5, 0.0, 11), (5, 6, 0.8, 12), (19, 4, 0.0, 13)]:
        p = np.concatenate([sys_p, rng.randint(0, cfg.vocab_size, (tail_len,)).astype(np.int32)])
        reqs.append((p, n, temp, seed,
                     engine.submit(p, max_new_tokens=n, temperature=temp, seed=seed)))
        if tail_len == 0:
            engine.drain()  # warm the prefix cache before the sharers arrive
    engine.drain()
    for p, n, temp, seed, fut in reqs:
        out, _ = dense.generate(jnp.asarray(p[None, :]), n, temperature=temp, seed=seed,
                                scan_decode=False)
        np.testing.assert_array_equal(fut.result().new_tokens, np.asarray(out)[0, len(p):])
    assert engine.prefix_hits > 0 and engine.spec_accepted == engine.spec_proposed > 0


def test_plain_decode_of_packed_heads_matches_dense(rng):
    """Without chunks, sharing or a draft: bucketed prefill writes the packed rows, batched decode
    reads them (llama-350m's shape in small: MHA, two heads of 64 a row)."""
    cfg = Config.from_name("tiny-llama2", block_size=64, n_head=4, n_embd=256, n_query_groups=4,
                           head_size=64)
    gpt = GPT(cfg, dtype=jnp.float32)
    dense = GPTInference(gpt, dtype=jnp.float32)
    engine = _engine(gpt)
    assert engine.cache.k_pages[0].shape[1:] == (2, 8, 128)
    reqs = []
    for L, n in [(3, 6), (17, 5), (30, 4)]:
        p = rng.randint(0, cfg.vocab_size, (L,)).astype(np.int32)
        reqs.append((p, n, engine.submit(p, max_new_tokens=n)))
    engine.drain()
    for p, n, fut in reqs:
        out, _ = dense.generate(jnp.asarray(p[None, :]), n, scan_decode=False)
        np.testing.assert_array_equal(fut.result().new_tokens, np.asarray(out)[0, len(p):])


# ---------------------------------------------------------------------------
# one decode step in flight: the next step is dispatched before the last
# one's tokens are fetched
# ---------------------------------------------------------------------------

# (prompt length, tokens asked for, temperature, seed): mixed lengths, greedy and sampled
_MIXED = [(5, 12, 0.0, 0), (13, 7, 0.9, 42), (9, 16, 0.0, 0), (20, 5, 0.7, 7), (3, 10, 0.0, 0)]


def _mixed_requests(rng, gpt):
    return [(rng.randint(0, gpt.cfg.vocab_size, (L,)).astype(np.int32), n, temp, seed)
            for L, n, temp, seed in _MIXED]


def _submit(engine, req, **kw):
    p, n, temp, seed = req
    return engine.submit(p, max_new_tokens=n, temperature=temp, seed=seed, **kw)


def _alone_on(engine, reqs):
    """What each request gives served alone on ``engine``, one request at a time."""
    out = []
    for req in reqs:
        fut = _submit(engine, req)
        engine.drain()
        out.append(fut.result().new_tokens)
    return out


def _alone(gpt, reqs):
    return _alone_on(_engine(gpt), reqs)


def _bus_counters(run):
    from thunder_tpu import observability

    observability.enable()
    observability.reset()
    try:
        out = run()
        return out, observability.counters()
    finally:
        observability.disable()
        observability.reset()


@pytest.mark.parametrize("end", ["length", "eos", "cancel", "preempt"])
def test_requests_admitted_while_others_decode_give_what_each_gives_alone(gpt, rng, end):
    """Admissions join the step in flight: the next step is fed their first tokens on the device,
    beside the sampler's output for every other sequence. Token for token nothing may show: ends by
    length, by an `eos_id` the seeded run is known to produce (the step dispatched before the
    end was seen is thrown away), by a cancelled Future, and across a preemption."""
    reqs = _mixed_requests(rng, gpt)
    want = _alone(gpt, reqs)
    engine = _engine(gpt, max_batch=3)   # five requests on three slots: slots are reused
    eos = {}
    if end == "eos":
        # request 2 ends early at its 6th token (an id it does not produce before)
        k = next(j for j in range(4, 16) if want[2][j] not in want[2][:j])
        eos[2] = int(want[2][k])
        want[2] = want[2][:k + 1]
    lanes = {0: "batch"} if end == "preempt" else {}

    def submit(i):
        p, n, temp, seed = reqs[i]
        return engine.submit(p, max_new_tokens=n, temperature=temp, seed=seed, eos_id=eos.get(i),
                             lane=lanes.get(i, "interactive"))

    def run():
        futs = {i: submit(i) for i in (0, 1)}
        for _ in range(3):
            engine._step_once()
        futs[2] = submit(2)                  # admitted while 0 and 1 decode
        for _ in range(3):
            engine._step_once()
        if end == "cancel":
            assert futs[0].cancel()          # mid-decode, a token of its in flight
        if end == "preempt":
            assert engine._inflight is not None and engine._preempt_one()
            assert engine._inflight is None  # the victim kept the token it had in flight
        futs[3], futs[4] = submit(3), submit(4)
        engine.drain()
        return futs

    futs, counters = _bus_counters(run)
    for i, fut in futs.items():
        if end == "cancel" and i == 0:
            assert fut.cancelled()
            continue
        res = fut.result(timeout=5)
        np.testing.assert_array_equal(res.new_tokens, want[i])
        assert res.finish_reason == ("eos" if i in eos else "length")
    assert engine._inflight is None and engine.cache.allocator.n_used == 0
    assert all(s is None for s in engine._slots)
    # a token is thrown away only where the host learned of an end at a commit with a step in
    # flight: nothing lands that step any more, so each such end costs exactly one
    assert counters.get("serve.decode_discarded", 0) == (end in ("eos", "cancel"))
    if end != "cancel":
        # committed tokens only: each request's first token is its prefill's
        assert counters["serve.tokens"] == sum(len(w) - 1 for w in want)
    if end == "preempt":
        assert engine.preempted == 1 and engine.resumed == 1


def test_an_end_the_host_learns_at_commit_costs_one_step_whose_token_is_thrown_away(gpt, rng):
    """`eos_id` is seen when the token is committed, one dispatch later: the step in flight
    then is wasted, its token counted in `serve.decode_discarded` and not in `serve.tokens`,
    and the pages it wrote to are the request's own, freed at that commit."""
    engine = _engine(gpt)
    p = rng.randint(0, gpt.cfg.vocab_size, (6,)).astype(np.int32)
    probe = engine.submit(p, max_new_tokens=8)
    engine.drain()
    stream = probe.result().new_tokens
    k = next(j for j in range(2, 8) if stream[j] not in stream[:j])
    steps0 = engine.decode_steps

    def run():
        fut = engine.submit(p, max_new_tokens=30, eos_id=int(stream[k]))
        engine.drain()
        return fut.result(timeout=5)

    res, counters = _bus_counters(run)
    assert res.finish_reason == "eos"
    np.testing.assert_array_equal(res.new_tokens, stream[:k + 1])
    # k steps gave the tokens after the prefill's; one more was in flight when the end was seen
    assert engine.decode_steps - steps0 == k + 1 == counters["serve.decode_steps"]
    assert counters["serve.tokens"] == k and counters["serve.decode_discarded"] == 1
    assert engine._inflight is None and engine.cache.allocator.n_used == 0


@pytest.mark.parametrize("n", [1, 2, 3, 9])
def test_a_request_alone_runs_the_decode_steps_it_needs_and_no_more(gpt, dense, rng, n):
    """The host knows a request's last token by count before it dispatches: n tokens are the
    prefill's and n - 1 decode steps, as in a loop that fetches before it dispatches, and a
    step with no live sequence is never made."""
    engine = _engine(gpt)
    p = rng.randint(0, gpt.cfg.vocab_size, (6,)).astype(np.int32)
    fut = engine.submit(p, max_new_tokens=n)
    engine.drain()
    out, _ = dense.generate(jnp.asarray(p[None, :]), n, scan_decode=False)
    np.testing.assert_array_equal(fut.result().new_tokens, np.asarray(out)[0, 6:])
    assert engine.decode_steps == n - 1 and engine._inflight is None
    engine._step_once()  # nothing outstanding: nothing is dispatched
    assert engine.decode_steps == n - 1


def test_most_decode_steps_are_dispatched_with_the_step_before_unfetched(gpt, rng):
    """`serve.decode_overlapped` over `serve.decode_steps`: every step but the first. Four
    requests admitted in one pass, before any step, and one admitted later, which joins the step
    in flight and lands nothing. Without `eos_id` or a cancel no token is thrown away."""
    engine = _engine(gpt)

    def run():
        futs = [engine.submit(rng.randint(0, gpt.cfg.vocab_size, (L,)).astype(np.int32),
                              max_new_tokens=n) for L, n in [(5, 24), (9, 20), (12, 16), (7, 12)]]
        for _ in range(6):
            engine._step_once()
        futs.append(engine.submit(rng.randint(0, gpt.cfg.vocab_size, (4,)).astype(np.int32),
                                  max_new_tokens=8))
        engine.drain()
        return [f.result(timeout=5).n_new_tokens for f in futs]

    got, counters = _bus_counters(run)
    assert got == [24, 20, 16, 12, 8]
    steps = counters["serve.decode_steps"]
    assert steps == engine.decode_steps == 23
    assert counters["serve.decode_overlapped"] == steps - 1
    assert counters["serve.activations"] == 5 and counters["serve.activations_joined"] == 1
    assert "serve.decode_discarded" not in counters
    assert counters["serve.tokens"] == sum(got) - len(got)


def test_the_speculative_path_records_no_overlapped_step(gpt, rng):
    """Verify needs the accepted count on the host: its path fetches before it dispatches, so an
    engine with a draft model lands at every activation and none of them joins a step."""
    engine = _engine(gpt, draft_gpt=gpt, spec_k=2)

    def run():
        fut = engine.submit(rng.randint(0, gpt.cfg.vocab_size, (6,)).astype(np.int32),
                            max_new_tokens=9)
        engine.drain()
        return fut.result(timeout=5)

    res, counters = _bus_counters(run)
    assert res.n_new_tokens == 9 and counters["serve.decode_steps"] > 0
    assert "serve.decode_overlapped" not in counters and engine._inflight is None
    assert counters["serve.activations"] == 1 and "serve.activations_joined" not in counters
    assert not engine._firsts


# ---------------------------------------------------------------------------
# an activation joins the pipeline: a first token stays on the device for the next step
# ---------------------------------------------------------------------------

# (prompt length, tokens asked for, temperature, seed): request 0 outlives the others, so some
# sequence is live in every pass; with chunks of 16 the prompts of 23 and 40 go through chunks
_JOINING = [(6, 30, 0.0, 0), (23, 5, 0.9, 42), (9, 7, 0.0, 0), (40, 4, 0.7, 7), (12, 6, 1.1, 3)]


def _joining_engine(gpt, kind: str, **kw):
    """The engines this file builds that keep a step in flight: whole-prompt prefills, chunks
    whose program carries the decode step, and chunks beside a decode program."""
    if kind == "whole-prompt":
        return _engine(gpt, **kw)
    return _chunking_engine(gpt, kind == "chunks-mixed", **kw)


_KINDS = ["whole-prompt", "chunks-mixed", "chunks-two-programs"]


def _joining_requests(rng, gpt):
    return [(rng.randint(0, gpt.cfg.vocab_size, (L,)).astype(np.int32), n, temp, seed)
            for L, n, temp, seed in _JOINING]


@pytest.mark.parametrize("kind", _KINDS)
def test_an_activation_joins_the_step_in_flight_and_nothing_lands(gpt, rng, kind):
    """Request 0 decodes and the others arrive two passes apart. Each prompt's program goes
    behind the step in flight, its first token is merged into the next step's tokens on the
    device and read after that step's dispatch: every step but the first finds the step before
    it unfetched, every activation but the first finds a step in flight, no token is thrown
    away, and every request has the tokens the engine gives it served alone."""
    reqs = _joining_requests(rng, gpt)
    want = _alone_on(_joining_engine(gpt, kind), reqs)
    engine = _joining_engine(gpt, kind, max_batch=3)

    def run():
        futs = []
        for req in reqs:
            futs.append(_submit(engine, req))
            for _ in range(2):
                engine._step_once()
                assert engine._inflight is not None   # nothing drained the pipeline
        engine.drain()
        return futs

    futs, counters = _bus_counters(run)
    for fut, w in zip(futs, want):
        res = fut.result(timeout=5)
        np.testing.assert_array_equal(res.new_tokens, w)
        assert res.ttft_s > 0 and res.finish_reason == "length"
    steps = counters["serve.decode_steps"]
    assert steps == engine.decode_steps and counters["serve.decode_overlapped"] == steps - 1
    assert counters["serve.activations"] == len(reqs)
    assert counters["serve.activations_joined"] == len(reqs) - 1
    assert "serve.decode_discarded" not in counters
    assert counters["serve.tokens"] == sum(len(w) - 1 for w in want)
    assert engine._inflight is None and not engine._firsts and not engine._feeds
    assert engine.cache.allocator.n_used == 0 and all(s is None for s in engine._slots)


@pytest.mark.parametrize("beside", [False, True], ids=["alone", "beside-a-decoding-request"])
@pytest.mark.parametrize("kind", _KINDS)
def test_a_first_token_that_ends_its_request_costs_one_step_whose_token_is_thrown_away(
        gpt, rng, kind, beside):
    """`eos_id` equal to the first token: the host learns of it after the slot's first step was
    dispatched. The request retires with one token and its pages are free at once, that step's
    token for the slot is the one `serve.decode_discarded` counts, and the request served next
    in the slot gives what it gives alone."""
    reqs = _joining_requests(rng, gpt)
    want = _alone_on(_joining_engine(gpt, kind), reqs)
    engine = _joining_engine(gpt, kind, max_batch=2)

    def submit(i, **kw):
        return _submit(engine, reqs[i], **kw)

    def run():
        futs = {}
        if beside:
            futs[0] = submit(0)
            for _ in range(3):
                engine._step_once()
        used = engine.cache.allocator.n_used
        futs[1] = submit(1, eos_id=int(want[1][0]))
        while not futs[1].done():
            engine._step_once()
        assert engine.cache.allocator.n_used == used   # its pages are back as it retires
        futs[2] = submit(2)                             # takes the slot the ended request left
        engine.drain()
        return futs

    futs, counters = _bus_counters(run)
    ended = futs.pop(1).result(timeout=5)
    assert ended.finish_reason == "eos" and ended.n_new_tokens == 1
    np.testing.assert_array_equal(ended.new_tokens, want[1][:1])
    for i, fut in futs.items():
        np.testing.assert_array_equal(fut.result(timeout=5).new_tokens, want[i])
    assert counters["serve.decode_discarded"] == 1
    assert counters["serve.tokens"] == sum(len(want[i]) - 1 for i in futs)
    assert engine._inflight is None and engine.cache.allocator.n_used == 0


@pytest.mark.parametrize("kind", _KINDS)
def test_a_request_that_wants_one_token_takes_no_slot(gpt, rng, kind):
    """The host knows at admission that the first token is the last: the request is never
    activated, no step holds it, and its token is read behind the next dispatch like any other
    first token. The request decoding beside it is not disturbed."""
    reqs = _joining_requests(rng, gpt)
    want = _alone_on(_joining_engine(gpt, kind), reqs)
    engine = _joining_engine(gpt, kind, max_batch=2)

    def run():
        first = _submit(engine, reqs[0])
        for _ in range(3):
            engine._step_once()
        ones = [_submit(engine, (p, 1, temp, seed)) for p, _, temp, seed in reqs[1:]]
        engine.drain()
        return first, ones

    (first, ones), counters = _bus_counters(run)
    np.testing.assert_array_equal(first.result(timeout=5).new_tokens, want[0])
    for fut, w in zip(ones, want[1:]):
        res = fut.result(timeout=5)
        assert res.n_new_tokens == 1 and res.finish_reason == "length" and res.ttft_s > 0
        np.testing.assert_array_equal(res.new_tokens, w[:1])
    steps = counters["serve.decode_steps"]
    assert steps == len(want[0]) - 1 and counters["serve.decode_overlapped"] == steps - 1
    assert counters["serve.activations"] == 1 and "serve.decode_discarded" not in counters
    assert counters["serve.retired"] == len(reqs) and engine.cache.allocator.n_used == 0


def test_a_future_cancelled_before_its_first_token_is_read_retires_at_that_read(gpt, rng):
    """The caller gives up between the dispatch of the prompt's program and the read of its first
    token: the read finds the Future cancelled and retires the request, the step that was
    dispatched with its slot throws that slot's token away, and the others go on."""
    reqs = _joining_requests(rng, gpt)
    want = _alone(gpt, reqs)
    engine = _engine(gpt, max_batch=2)
    dispatch = engine._dispatch

    def run():
        first = _submit(engine, reqs[0])
        for _ in range(3):
            engine._step_once()
        gone = _submit(engine, reqs[2])

        def cancel_then_dispatch(*args):
            assert engine._firsts and gone.cancel()   # its prompt's program is dispatched, no more
            engine._dispatch = dispatch
            return dispatch(*args)

        engine._dispatch = cancel_then_dispatch
        engine._step_once()
        assert engine._slots.count(None) == 1 and not engine._firsts   # retired at the read
        last = _submit(engine, reqs[4])
        engine.drain()
        return first, gone, last

    (first, gone, last), counters = _bus_counters(run)
    assert gone.cancelled() and counters["serve.cancelled"] == 1
    np.testing.assert_array_equal(first.result(timeout=5).new_tokens, want[0])
    np.testing.assert_array_equal(last.result(timeout=5).new_tokens, want[4])
    assert counters["serve.decode_discarded"] == 1
    assert counters["serve.decode_overlapped"] == counters["serve.decode_steps"] - 1
    assert engine._inflight is None and engine.cache.allocator.n_used == 0


@pytest.mark.parametrize("kind", _KINDS)
def test_a_resumed_request_joins_the_step_in_flight_with_the_token_it_kept(gpt, rng, kind):
    """A preemption lands the step in flight (the victim keeps its token); the resume does not:
    the victim's prompt and tokens are prefilled behind the step in flight, its last token goes
    up from the host and is merged into the next step's tokens, and its stream goes on bit for
    bit."""
    reqs = _joining_requests(rng, gpt)
    want = _alone_on(_joining_engine(gpt, kind), reqs)
    engine = _joining_engine(gpt, kind, max_batch=2)

    def submit(i, lane="interactive"):
        return _submit(engine, reqs[i], lane=lane)

    futs = {0: submit(0), 4: submit(4, lane="batch")}
    for _ in range(4):
        engine._step_once()
    assert engine._preempt_one() and engine._inflight is None and engine.preempted == 1
    futs[2] = submit(2)   # takes the victim's slot ahead of it: the victim waits for a slot

    def run():
        engine.drain()

    _, counters = _bus_counters(run)
    for i, fut in futs.items():
        np.testing.assert_array_equal(fut.result(timeout=5).new_tokens, want[i])
    assert engine.resumed == 1 and counters["serve.resumed"] == 1
    # request 2 found no step in flight (the preemption had landed it); the victim found one
    assert counters["serve.activations"] == 2 and counters["serve.activations_joined"] == 1
    assert counters["serve.decode_overlapped"] == counters["serve.decode_steps"] - 1
    assert "serve.decode_discarded" not in counters and engine.cache.allocator.n_used == 0


def test_a_request_that_waits_for_pages_with_no_victim_to_spill_lands_nothing(gpt, rng):
    """The head of the line cannot reserve its pages and no batch-lane sequence can be spilled
    for it: the pipeline goes on as it was, pass after pass, until a retirement frees pages."""
    reqs = _joining_requests(rng, gpt)
    want = _alone(gpt, reqs)
    engine = _engine(gpt, max_batch=3, n_pages=1 + 5 + 8)   # request 0 holds 5 pages, request 3 eight

    def run():
        futs = [_submit(engine, reqs[0])]
        for _ in range(3):
            engine._step_once()
        futs += [_submit(engine, reqs[3]), _submit(engine, reqs[1])]   # request 1 wants 4: none is free
        for _ in range(3):
            engine._step_once()
            assert engine._inflight is not None and len(engine._pending) == 1
        engine.drain()
        return futs

    futs, counters = _bus_counters(run)
    for i, fut in zip((0, 3, 1), futs):
        np.testing.assert_array_equal(fut.result(timeout=5).new_tokens, want[i])
    assert engine.preempted == 0
    assert counters["serve.decode_overlapped"] == counters["serve.decode_steps"] - 1
    assert counters["serve.activations_joined"] == 2


@pytest.mark.parametrize("kind,name,i", [("whole-prompt", "prefill_cfn", 3), ("chunks-mixed", "prefill_cfn", 4),
                                         ("chunks-two-programs", "chunk_cfn", 3)])
def test_a_prompt_program_that_raises_with_a_step_in_flight_fails_that_request_only(gpt, rng, kind,
                                                                                      name, i):
    """The failure's clean-up lands the step in flight: the sequences in it keep their tokens and
    go on. (A chunk's program that carries the decode step is that step: its failure is the
    step's, `test_decode_failure_fails_active_batch`.)"""
    reqs = _joining_requests(rng, gpt)
    want = _alone_on(_joining_engine(gpt, kind), reqs)
    engine = _joining_engine(gpt, kind, max_batch=3)
    program = getattr(engine.runner, name)

    def submit(i):
        return _submit(engine, reqs[i])

    def boom(*a, **kw):
        raise RuntimeError("injected prompt failure")

    first, second = submit(0), submit(2)
    for _ in range(3):
        engine._step_once()
    assert engine._inflight is not None
    used = engine.cache.allocator.n_used
    setattr(engine.runner, name, boom)
    failed = submit(i)
    engine._step_once()
    with pytest.raises(RuntimeError, match="injected"):
        failed.result(timeout=5)
    assert engine.cache.allocator.n_used == used
    setattr(engine.runner, name, program)
    again = submit(i)
    engine.drain()
    for j, fut in ((0, first), (2, second), (i, again)):
        np.testing.assert_array_equal(fut.result(timeout=5).new_tokens, want[j])
    assert engine._inflight is None and engine.cache.allocator.n_used == 0


# ---------------------------------------------------------------------------
# a prompt chunk and the live decode rows in one program
# ---------------------------------------------------------------------------

# (prompt length, tokens asked for, temperature, seed): with chunks of 16, the prompts over 16
# tokens go through chunks (40: two whole ones and a final rung of 8; 23: one and a final of 7)
_BESIDE = [(9, 20, 0.0, 0), (40, 6, 0.9, 42), (23, 9, 0.0, 0), (52, 4, 0.7, 7), (33, 5, 0.0, 0)]


def _chunking_engine(gpt, mixes: bool, **kw):
    """Chunks of 16 on three slots. ``mixes`` False: the engine as it was before a chunk's
    program took the decode step's rows (a chunk program and a decode program a pass)."""
    kw.setdefault("max_batch", 3)
    engine = _engine(gpt, chunk_tokens=16, prefill_budget=16, **kw)
    assert engine._mixes and engine.runner.mixes
    engine._mixes = mixes
    return engine


def _serve_beside(gpt, reqs, mixes: bool, end: str):
    """Request 0 decodes while the long prompts of 1 and 2 arrive and are chunked beside it, then
    3 and 4 take the slots that free. Returns ({i: tokens or None}, counters, engine)."""
    engine = _chunking_engine(gpt, mixes, prefix_sharing=(end == "prefix"))
    eos = {}
    if end == "eos":
        probe = _engine(gpt)
        p, n, temp, seed = reqs[0]
        fut = probe.submit(p, max_new_tokens=n, temperature=temp, seed=seed)
        probe.drain()
        stream = fut.result().new_tokens
        k = next(j for j in range(5, 20) if stream[j] not in stream[:j])
        eos[0] = int(stream[k])  # request 0 ends at its (k + 1)th token, chunks beside it
    lanes = {0: "batch"} if end == "preempt" else {}

    def submit(i):
        p, n, temp, seed = reqs[i]
        return engine.submit(p, max_new_tokens=n, temperature=temp, seed=seed, eos_id=eos.get(i),
                             lane=lanes.get(i, "interactive"))

    def run():
        futs = {0: submit(0)}
        for _ in range(3):
            engine._step_once()
        futs[1], futs[2] = submit(1), submit(2)   # chunked while 0 decodes
        for _ in range(2):
            engine._step_once()
        assert engine._chunking and engine._inflight is not None
        if end == "cancel":
            assert futs[0].cancel()                # a token of its in flight, in a chunk's program
        if end == "preempt":
            assert engine._preempt_one()           # 0 comes back as chunks of prompt + tokens
            assert engine._inflight is None
        futs[3], futs[4] = submit(3), submit(4)
        engine.drain()
        return futs

    futs, counters = _bus_counters(run)
    out = {i: (None if f.cancelled() else f.result(timeout=5).new_tokens) for i, f in futs.items()}
    assert engine._inflight is None and engine.cache.allocator.n_used == (
        len(engine.prefix) if end == "prefix" else 0)
    assert all(s is None for s in engine._slots) and not engine._chunking
    return out, counters, engine


@pytest.mark.parametrize("end", ["length", "eos", "cancel", "preempt", "prefix"])
def test_decode_rows_that_ride_in_a_chunks_program_give_what_the_two_programs_give(gpt, rng, end):
    """In a pass with a chunk due the decode step's rows go through the chunk's program. Token
    for token nothing may show against a chunk program and a decode program a pass: long prompts
    arriving while another decodes, a final chunk's first token, an end by `eos_id` and a cancel
    while a step is in flight in a chunk's program, a preempted request's resumed chunks, prefix
    sharing (request 4's prompt starts with request 1's)."""
    reqs = [(rng.randint(0, gpt.cfg.vocab_size, (L,)).astype(np.int32), n, temp, seed)
            for L, n, temp, seed in _BESIDE]
    if end == "prefix":
        reqs[4] = (np.concatenate([reqs[1][0][:32], reqs[4][0][:1]]),) + reqs[4][1:]
    want, two, _ = _serve_beside(gpt, reqs, False, end)
    got, one, engine = _serve_beside(gpt, reqs, True, end)
    for i in want:
        if want[i] is None:
            assert got[i] is None and end == "cancel" and i == 0
        else:
            np.testing.assert_array_equal(got[i], want[i])
    assert "serve.decode_mixed" not in two and one["serve.decode_mixed"] >= 4
    # a step a pass either way, and the same tokens committed
    assert one["serve.tokens"] == two["serve.tokens"]
    assert one["serve.decode_mixed"] <= one["serve.decode_steps"] == engine.decode_steps
    if end == "preempt":
        assert engine.preempted == 1 and engine.resumed == 1
    if end == "prefix":
        assert engine.prefix_hits == 1 and one["serve.prefix_tokens_saved"] == 32


def test_a_mixed_step_is_counted_as_the_two_dispatches_were(gpt, rng):
    """One request decodes, a prompt of 40 arrives: its chunks at 0, 16 and 32 (a rung of 8) each
    carry a decode step (`serve.decode_mixed` 3 of 11), the step after its activation is fed
    its first token on the device, and every counter of the decode step and of the chunks reads what it reads
    with a chunk program and a decode program a pass."""
    def run(mixes):
        engine = _chunking_engine(gpt, mixes, max_batch=4)
        rs = np.random.RandomState(5)

        def serve():
            a = engine.submit(rs.randint(0, gpt.cfg.vocab_size, (5,)).astype(np.int32), max_new_tokens=12)
            for _ in range(2):
                engine._step_once()
            b = engine.submit(rs.randint(0, gpt.cfg.vocab_size, (40,)).astype(np.int32), max_new_tokens=4)
            engine.drain()
            return a.result(timeout=5).new_tokens, b.result(timeout=5).new_tokens

        toks, counters = _bus_counters(serve)
        return toks, counters, engine

    (a2, b2), two, _ = run(False)
    (a1, b1), one, engine = run(True)
    np.testing.assert_array_equal(a1, a2)
    np.testing.assert_array_equal(b1, b2)
    assert one["serve.decode_mixed"] == 3 and "serve.decode_mixed" not in two
    same = ["serve.decode_steps", "serve.tokens", "serve.decode_overlapped", "serve.prefill_tokens",
            "serve.paged.chunk_pages_live", "serve.paged.chunk_pages_spanned", "serve.paged.pages_spanned",
            "serve.prefills"]
    assert {k: one[k] for k in same} == {k: two[k] for k in same}
    assert one["serve.decode_steps"] == engine.decode_steps == 11
    assert one["serve.tokens"] == (12 - 1) + (4 - 1)
    assert one["serve.decode_overlapped"] == 11 - 1   # the first step
    assert one["serve.activations"] == 2 and one["serve.activations_joined"] == 1
    assert one["serve.paged.chunk_pages_spanned"] == 3 * 8 and one["serve.paged.chunk_pages_live"] == 2 + 4 + 5
    assert one["serve.paged.pages_spanned"] == 11 * 4 * 8
    # the sequence that was chunked joins the decode step one pass later than it did: its rows
    # are read a step later, the first one's as before
    assert one["serve.paged.pages_live"] <= two["serve.paged.pages_live"]
    assert "serve.decode_discarded" not in one and "serve.pool_copied" not in one


def test_nothing_is_built_when_a_chunk_first_meets_live_rows(gpt, rng):
    """`warmup` runs every chunk rung with idle decode rows only. The same executable then
    serves a chunk with live rows beside it, fed from the host or from the sampler: no trace, no
    executable is built after warm-up."""
    import jax.monitoring
    from thunder_tpu import observability

    engine = _chunking_engine(gpt, True)
    engine.warmup([5, 17, 25, 33])  # the decode step, a bucket, chunk rungs 8 and 16 behind a chunk of 16
    chunk, decode = engine.runner.chunk_cfn._cfn, engine.runner.decode_cfn._cfn
    traced = (chunk.cache_misses, decode.cache_misses)
    built = []

    def on_build(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            built.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_build)
    observability.enable()
    observability.reset()
    try:
        futs = [engine.submit(rng.randint(0, gpt.cfg.vocab_size, (L,)).astype(np.int32), max_new_tokens=n)
                for L, n in [(6, 14), (40, 5), (23, 6)]]
        engine.drain()
        counters = observability.counters()
    finally:
        observability.disable()
        observability.reset()
        jax.monitoring.unregister_event_duration_listener(on_build)
    assert [f.result().n_new_tokens for f in futs] == [14, 5, 6]
    assert counters["serve.decode_mixed"] >= 4
    assert not {k: v for k, v in counters.items() if k.startswith("recompile.")}
    assert (chunk.cache_misses, decode.cache_misses) == traced and not built


def test_the_chunk_program_takes_each_kind_of_row_through_its_own_attention(gpt, rng):
    """One `paged_chunk_attention` and one `paged_attention` a layer in the chunk program's
    trace (never a second chunk call for the decode rows), the decode program's as it was; an
    engine with a draft model keeps the chunk program without decode rows and never mixes."""
    def attends(cfn):
        from collections import Counter

        return Counter(b.sym.name for b in tt.last_traces(cfn._cfn)[0].bound_symbols
                       if "paged" in b.sym.name)

    def serve(engine):
        futs = [engine.submit(rng.randint(0, gpt.cfg.vocab_size, (L,)).astype(np.int32), max_new_tokens=6)
                for L in (7, 40)]
        engine.drain()
        return [f.result().new_tokens for f in futs]

    n = gpt.cfg.n_layer
    engine = _engine(gpt, chunk_tokens=16)
    got, counters = _bus_counters(lambda: serve(engine))
    assert counters["serve.decode_mixed"] == 3
    assert attends(engine.runner.chunk_cfn) == {"paged_chunk_attention": n, "paged_attention": n}
    assert attends(engine.runner.decode_cfn) == {"paged_attention": n}
    drafted = _engine(gpt, chunk_tokens=16, draft_gpt=gpt, spec_k=2)
    assert not drafted._mixes and drafted._idle_rows is None
    want, counters = _bus_counters(lambda: serve(drafted))
    assert "serve.decode_mixed" not in counters
    assert attends(drafted.runner.chunk_cfn) == {"paged_chunk_attention": n}
    assert attends(drafted.draft_runner.chunk_cfn) == {"paged_chunk_attention": n}


def test_a_capacity_bound_expert_block_does_not_offer_the_mixed_program(rng):
    """An expert's capacity follows from the rows a program has, so decode rows beside a chunk's
    would be dropped where alone they are not: such a block offers no `mixed`, and the engine
    keeps the two programs. A drop-free expert block mixes like a dense one."""
    from thunder_tpu.models.moe import MoEConfig, MoEGPT

    cfg = Config.from_name("tiny-llama2", block_size=64, n_layer=1)
    def moe(capacity_factor):
        return MoEGPT(cfg, MoEConfig(n_embd=cfg.n_embd, intermediate_size=64, n_expert=4,
                                     n_expert_per_token=2, capacity_factor=capacity_factor),
                      dtype=jnp.float32)

    assert _engine(moe(None), chunk_tokens=16)._mixes
    bound = _engine(moe(1.0), chunk_tokens=16)
    assert not bound.runner.mixes and not bound._mixes
    futs = [bound.submit(rng.randint(0, cfg.vocab_size, (L,)).astype(np.int32), max_new_tokens=4)
            for L in (6, 30)]
    bound.drain()
    assert [f.result().n_new_tokens for f in futs] == [4, 4]


def test_programs_a_decode_row_may_run_in_either_of_round_at_every_op(gpt, rng):
    """A sequence's decode rows run in `decode_cfn` alone and in the chunk program beside a chunk.
    XLA by default keeps the intermediates of a fused chain in float32, and which chains it fuses
    follows from the shapes: on the chip a row read other bits in one program than in the other
    (PERF.md, PR 35). So both programs' XLA regions are compiled with
    `xla_allow_excess_precision` off (`tt.jit(round_every_op=True)` -> `trace.round_every_op` ->
    `jax.jit(compiler_options=)`), the option is part of a region's artifact key, and the
    programs that run at one shape only keep the default."""
    from thunder_tpu.compile_service import parallel_compile

    engine = _engine(gpt, chunk_tokens=16)
    futs = [engine.submit(rng.randint(0, gpt.cfg.vocab_size, (L,)).astype(np.int32), max_new_tokens=4)
            for L in (7, 40)]
    engine.drain()
    assert [f.result().n_new_tokens for f in futs] == [4, 4]

    def options(cfn):
        regions = parallel_compile.fusion_regions(tt.last_traces(cfn._cfn)[-1])
        assert regions
        return {repr(b.impl.compiler_options) for b in regions}

    strict = {repr({"xla_allow_excess_precision": False})}
    assert options(engine.runner.decode_cfn) == options(engine.runner.chunk_cfn) == strict
    assert options(engine.runner.prefill_cfn) == {"None"}
    region = parallel_compile.fusion_regions(tt.last_traces(engine.runner.decode_cfn._cfn)[-1])[0]
    avals = parallel_compile._region_avals(region)
    key = parallel_compile.region_key(region, avals)
    region.impl.compiler_options = None
    assert parallel_compile.region_key(region, avals) != key
