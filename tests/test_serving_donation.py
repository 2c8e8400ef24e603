"""The KV page pool is donated to the serving programs.

`tt.jit(donated_argnums=...)` puts the names of the given-up arguments on the acquired trace,
`executors/xlaex.py` hands them to the region's `jax.jit` as `donate_argnums`, and the artifact
store keys and serves such a region as what it is. `PagedGPTRunner` declares the pools donated in
its four programs and `PagedKVCache.copy_page` in its own: after a dispatch the arrays passed in
are gone and the rebound ones live, no pool is copied, and a step that fails after its program
consumed the pools leaves the engine with fresh ones and no page held. Everything here runs on the
CPU backend, which donates too.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import analysis, observability
from thunder_tpu.analysis import TraceCheckError
from thunder_tpu.compile_service import parallel_compile as pc
from thunder_tpu.compile_service.store import ArtifactStore
from thunder_tpu.core import prims
from thunder_tpu.models.litgpt import Config, GPT
from thunder_tpu.ops import ltorch
from thunder_tpu.serving import ServingEngine

pytestmark = pytest.mark.serve

PROGRAMS = ("prefill", "decode", "chunk", "verify")


@pytest.fixture(scope="module")
def gpt():
    return GPT(Config.from_name("tiny-llama2", block_size=64), dtype=jnp.float32)


def _engine(gpt, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq", 64)
    kw.setdefault("dtype", jnp.float32)
    return ServingEngine(gpt, **kw)


def _dispatch(engine, program: str):
    """One direct call of a compiled program on the engine's cached state, with the shapes the
    scheduler gives it. Returns (new state,), what `rebind` takes."""
    r, c, B, npm = engine.runner, engine.cache, len(engine._slots), engine.n_pages_max
    i32 = lambda *a: jnp.asarray(*a, dtype=jnp.int32)  # noqa: E731
    table = i32(np.tile(np.arange(1, npm + 1), (B, 1)))
    if program == "prefill":
        out = r.prefill_cfn(engine.params, i32(np.ones((1, 16))), (i32([1, 2]),), c.state, i32(10),
                            i32(0))
    elif program == "decode":
        out = r.decode_cfn(engine.params, i32(np.ones((B, 1))), c.state, (table,), i32(np.arange(B)))
    elif program == "chunk":
        out = r.chunk_cfn(engine.params, i32(np.ones((1, 16))), (table[:1],), c.state, i32(8),
                          i32(15), i32(0))
    else:
        out = r.verify_cfn(engine.params, i32(np.ones((B, 3))), c.state, (table,), i32(np.arange(B)))
    return (out[1],)


def _fusion_impls(cfn) -> list:
    return [b.impl for b in pc.fusion_regions(tt.last_traces(getattr(cfn, "_cfn", cfn))[-1])]


def _aliases(impl) -> int:
    """Entries of the compiled region's `input_output_alias`."""
    avals = pc._region_avals(impl.subtrace)  # reads `.args`, which a subtrace has too
    return len(re.findall(r"(?:may|must)-alias", impl.jitted.lower(*avals).compile().as_text()))


# ---------------------------------------------------------------------------
# the four programs and copy_page consume the pools they are given
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("program", PROGRAMS + ("copy_page",))
def test_dispatch_consumes_the_pools_and_the_rebound_ones_live(gpt, program):
    engine = _engine(gpt)
    cache = engine.cache
    given = cache.k_pages + cache.v_pages
    if program == "copy_page":
        cache.copy_page(1, 2)
    else:
        cache.rebind(*_dispatch(engine, program))
    assert all(a.is_deleted() for a in given)
    assert len(cache.k_pages) == len(cache.v_pages) == gpt.cfg.n_layer
    assert not any(a.is_deleted() for a in cache.k_pages + cache.v_pages)
    assert not cache.pools_deleted()
    assert not any(p.is_deleted() for p in engine.params.values())  # weights are not given up


def test_copy_page_copies_the_page_in_every_layer(gpt):
    engine = _engine(gpt)
    cache = engine.cache
    cache.rebind(*_dispatch(engine, "prefill"))  # writes pages 1 and 2
    want = [np.asarray(a[1]) for a in cache.k_pages + cache.v_pages]
    cache.copy_page(1, 5)
    for a, w in zip(cache.k_pages + cache.v_pages, want):
        np.testing.assert_array_equal(np.asarray(a[5]), w)
        np.testing.assert_array_equal(np.asarray(a[1]), w)
    assert any(w.any() for w in want)


def test_decode_region_aliases_every_pool(gpt):
    engine = _engine(gpt)
    engine.cache.rebind(*_dispatch(engine, "decode"))
    (impl,) = _fusion_impls(engine.runner.decode_cfn)
    assert len(impl.donate_argnums) == 2 * gpt.cfg.n_layer
    assert _aliases(impl) == 2 * gpt.cfg.n_layer
    donated = tt.last_traces(engine.runner.decode_cfn._cfn)[0].donated
    assert len(donated) == 2 * gpt.cfg.n_layer
    for trace in tt.last_traces(engine.runner.decode_cfn._cfn):
        assert trace.donated == donated          # carried through every pass
        analysis.alias.check_alias_safety(trace)  # and nothing reads a pool after its write


# ---------------------------------------------------------------------------
# tt.jit: the declaration, and what happens without it
# ---------------------------------------------------------------------------


def _write(pool, idx, val, w):
    new = ltorch.index_put(pool, (idx,), val)
    return new, ltorch.sum(new) * w


def _write_args():
    return jnp.zeros((8, 4)), jnp.asarray([1, 2], jnp.int32), jnp.ones((2, 4)), jnp.asarray(2.0)


def test_without_the_argument_nothing_is_aliased_and_the_inputs_live():
    """The train cells' guard: a function that declares nothing lowers as it always did."""
    cf = tt.jit(_write)
    args = _write_args()
    cf(*args)
    assert not any(a.is_deleted() for a in args)
    (impl,) = _fusion_impls(cf)
    assert impl.donate_argnums == () and _aliases(impl) == 0
    assert not getattr(tt.last_traces(cf)[0], "donated", None)


@pytest.mark.parametrize("argnums", [0, (0,), [0, 3]])
def test_declared_arguments_are_consumed_and_no_other(argnums):
    cf = tt.jit(_write, donated_argnums=argnums)
    args = _write_args()
    new, total = cf(*args)
    want = {0, 3} if argnums == [0, 3] else {0}  # the scalar becomes the sum
    assert {i for i, a in enumerate(args) if a.is_deleted()} == want
    assert float(total) == 16.0 and np.asarray(new)[1:3].all()
    (impl,) = _fusion_impls(cf)
    assert impl.donate_argnums == tuple(sorted(want)) and _aliases(impl) == len(want)


def test_an_argument_returned_as_it_came_or_read_by_a_later_region_is_kept():
    def back(pool, x):
        return pool, x * 2

    pool = jnp.zeros((8, 4))
    tt.jit(back, donated_argnums=(0,))(pool, jnp.ones((2,)))
    assert not pool.is_deleted()

    def two_regions(pool, idx, val):
        new = ltorch.index_put(pool, (idx,), val)
        n = ltorch.sum(new).item()      # not fusible: ends the first region
        return new, pool * n            # the second region still reads the old pool

    cf = tt.jit(two_regions, donated_argnums=(0,))
    pool, idx, val, _ = _write_args()
    new, scaled = cf(pool, idx, val)
    first, second = _fusion_impls(cf)
    assert first.donate_argnums == () and len(second.donate_argnums) == 1
    assert float(jnp.sum(new)) == 8.0 and not np.asarray(scaled).any()


def test_under_an_outer_jit_the_region_is_inlined_and_donates_nothing():
    cf = tt.jit(_write, donated_argnums=(0,))
    args = _write_args()
    _, total = jax.jit(lambda *a: cf(*a))(*args)
    assert float(total) == 16.0 and not args[0].is_deleted()
    (impl,) = _fusion_impls(cf)
    assert impl.donate_argnums == ()


def test_a_read_after_the_consuming_write_is_refused():
    def stale(pool, val):
        new = prims.copy_with_setitem(pool, 0, val)
        return new, ltorch.sum(pool)

    def fresh(pool, val):
        new = prims.copy_with_setitem(pool, 0, val)
        return new, ltorch.sum(new)

    with analysis.override(1):
        with pytest.raises(TraceCheckError) as ei:
            tt.jit(stale, donated_argnums=(0,))(jnp.zeros((8, 4)), jnp.ones((4,)))
        assert ei.value.kind == "donation-read"
        tt.jit(stale)(jnp.zeros((8, 4)), jnp.ones((4,)))  # nothing given up: nothing to refuse
        _, total = tt.jit(fresh, donated_argnums=(0,))(jnp.zeros((8, 4)), jnp.ones((4,)))
    assert float(total) == 4.0


@pytest.mark.parametrize("front_end", ["interpreter", "symbolic", "module"])
def test_a_front_end_that_cannot_donate_refuses_the_argument(gpt, front_end):
    fn, kw = {"interpreter": (_write, {"interpretation": "python interpreter"}),
              "symbolic": (_write, {"cache": "symbolic values"}),
              "module": (gpt, {})}[front_end]
    with pytest.raises(ValueError, match="cannot donate"):
        tt.jit(fn, donated_argnums=(0,), **kw)


# ---------------------------------------------------------------------------
# the artifact store
# ---------------------------------------------------------------------------


def _compiled_write(**kw):
    cf = tt.jit(_write, **kw)
    cf.prewarm(*_write_args())
    trace = tt.last_traces(cf)[-1]
    (region,) = pc.fusion_regions(trace)
    return cf, trace, region


def test_region_key_knows_the_donated_positions():
    _, _, plain = _compiled_write()
    _, _, donating = _compiled_write(donated_argnums=(0,))
    _, _, again = _compiled_write(donated_argnums=(0,))
    assert plain.impl.subtrace.python().partition("\n")[2] \
        == donating.impl.subtrace.python().partition("\n")[2]  # the same text
    avals = pc._region_avals(plain)
    assert pc.region_key(plain, avals) != pc.region_key(donating, avals)
    assert pc.region_key(donating, avals) == pc.region_key(again, avals)


@pytest.mark.parametrize("donating", [True, False])
def test_an_executable_served_from_the_store_consumes_its_inputs_like_a_compiled_one(
        tmp_path, donating):
    kw = {"donated_argnums": (0,)} if donating else {}
    store = ArtifactStore(str(tmp_path))
    _, trace, _ = _compiled_write(**kw)
    assert pc.prewarm_regions(trace, store=store)["compiled"] == 1
    # the other kind of region finds nothing under its own key
    _, other, _ = _compiled_write(**({} if donating else {"donated_argnums": (0,)}))
    assert pc.prewarm_regions(other, store=ArtifactStore(str(tmp_path)))["store_hits"] == 0

    cf, trace, region = _compiled_write(**kw)
    assert pc.prewarm_regions(trace, store=ArtifactStore(str(tmp_path))) == {
        "regions": 1, "prewarmed": 1, "store_hits": 1, "compiled": 0}
    served = region.impl._prewarmed
    args = _write_args()
    new, total = cf(*args)
    assert region.impl._prewarmed is served      # it served the call, no fallback
    assert args[0].is_deleted() == donating and not new.is_deleted()
    assert float(total) == 16.0


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


def _prompts(rng, gpt):
    """A long prompt (chunked where the engine chunks), a short one, and the long one's first
    pages again (a prefix hit where the engine shares)."""
    long = rng.randint(0, gpt.cfg.vocab_size, (40,)).astype(np.int32)
    short = rng.randint(0, gpt.cfg.vocab_size, (9,)).astype(np.int32)
    return [long, short, long[:16].copy()]


def _fail_after(engine, program: str):
    """Make the next dispatch of `program` raise after the real program ran and consumed the
    pools. Returns the function that undoes it."""
    runner = engine.runner
    name = f"{program}_cfn"
    real = getattr(runner, name)

    def consumed_then_failed(*a, **kw):
        real(*a, **kw)
        raise RuntimeError("injected failure after the pools were consumed")

    setattr(runner, name, consumed_then_failed)
    return lambda: setattr(runner, name, real)


@pytest.mark.parametrize("program", PROGRAMS)
def test_a_step_that_fails_after_consuming_the_pools_leaves_a_serving_engine(gpt, rng, program):
    kw = dict(prefix_sharing=True, chunk_tokens=16, prefill_budget=16)
    if program == "verify":
        kw.update(draft_gpt=gpt, spec_k=2)
    prompts = _prompts(rng, gpt)
    fresh = _engine(gpt, **kw)
    want = fresh.submit(prompts[1], max_new_tokens=5)
    fresh.drain()

    engine = _engine(gpt, **kw)
    first = engine.submit(prompts[1], max_new_tokens=3)   # fills the prefix cache
    engine.drain()
    first.result(timeout=5)
    assert len(engine.prefix) > 0
    undo = _fail_after(engine, program)
    futs = [engine.submit(p, max_new_tokens=6) for p in prompts]
    engine.drain()
    undo()
    failed = 0
    for f in futs:
        try:
            f.result(timeout=5)
        except RuntimeError as e:
            assert "injected" in str(e)
            failed += 1
    assert failed >= 1
    # whoever held pages lost them: sequences failed, prefix nodes dropped, fresh pools
    assert engine.cache.allocator.n_used == 0 and len(engine.prefix) == 0
    assert not engine.cache.pools_deleted()
    assert engine.draft_cache is None or not engine.draft_cache.pools_deleted()
    assert all(s is None for s in engine._slots) and not engine._chunking
    got = engine.submit(prompts[1], max_new_tokens=5)
    engine.drain()
    np.testing.assert_array_equal(got.result(timeout=5).new_tokens,
                                  want.result(timeout=5).new_tokens)


def test_a_step_that_fails_before_execution_keeps_the_pools_and_the_prefix_cache(gpt, rng):
    engine = _engine(gpt, prefix_sharing=True)
    prompts = _prompts(rng, gpt)
    engine.submit(prompts[0], max_new_tokens=3)
    engine.drain()
    nodes, page = len(engine.prefix), next(iter(engine.prefix._lru)).page
    held = np.asarray(engine.cache.k_pages[0][page])
    assert nodes > 0 and held.any()
    real = engine.runner.decode_cfn
    engine.runner.decode_cfn = lambda *a, **kw: (_ for _ in ()).throw(
        RuntimeError("injected failure before execution"))
    fut = engine.submit(prompts[1], max_new_tokens=4)
    engine.drain()
    engine.runner.decode_cfn = real
    with pytest.raises(RuntimeError, match="injected"):
        fut.result(timeout=5)
    assert len(engine.prefix) >= nodes
    np.testing.assert_array_equal(np.asarray(engine.cache.k_pages[0][page]), held)


def test_a_failed_copy_on_write_fork_fails_its_request_only(gpt, rng):
    engine = _engine(gpt, prefix_sharing=True)
    donor = _prompts(rng, gpt)[0][:16]
    engine.submit(donor, max_new_tokens=3)
    engine.drain()
    real = engine.cache.copy_page

    def consumed_then_failed(src, dst):
        real(src, dst)
        engine.cache.k_pages[0].delete()
        raise RuntimeError("injected fork failure")

    engine.cache.copy_page = consumed_then_failed
    fut = engine.submit(donor, max_new_tokens=3)  # a full hit: forks the last shared page
    engine.drain()
    engine.cache.copy_page = real
    with pytest.raises(RuntimeError, match="injected"):
        fut.result(timeout=5)
    assert engine.cache.allocator.n_used == 0 and not engine.cache.pools_deleted()
    ok = engine.submit(donor, max_new_tokens=3)
    engine.drain()
    assert ok.result(timeout=5).n_new_tokens == 3


@pytest.mark.parametrize("stages", ["plain", "all"])
def test_every_dispatch_counts_as_donated_and_none_as_copied(gpt, rng, stages):
    kw = {} if stages == "plain" else dict(prefix_sharing=True, chunk_tokens=16,
                                           prefill_budget=16, draft_gpt=gpt, spec_k=2)
    engine = _engine(gpt, **kw)
    calls = []

    def counted(fn, name):
        def call(*a, **k):
            calls.append(name)
            return fn(*a, **k)
        return call

    caches = [c for c in (engine.cache, engine.draft_cache) if c is not None]
    for runner in {engine.runner, engine.draft_runner} - {None}:
        for program in PROGRAMS:
            setattr(runner, f"{program}_cfn", counted(getattr(runner, f"{program}_cfn"), program))
    for c in caches:
        c.copy_page = counted(c.copy_page, "copy_page")
    observability.enable()
    observability.reset()
    try:
        prompts = _prompts(rng, gpt)
        for p in prompts + [prompts[2]]:   # the repeat is a full hit: a copy-on-write fork
            engine.submit(p, max_new_tokens=5)
            engine.drain()
        counters = observability.counters()
    finally:
        observability.disable()
        observability.reset()
    assert counters.get("serve.pool_copied", 0) == 0
    assert counters["serve.pool_donated"] == len(calls) > 0
    want = {"prefill", "decode"} if stages == "plain" else set(PROGRAMS) | {"copy_page"}
    assert set(calls) == want
