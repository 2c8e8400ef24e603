"""The serving loop's phases: ``engine:*`` spans on the bus and on the
profiler's clock, and the queue wait every request carries.

Each case serves a few requests on a tiny model with the bus on and a
``jax.profiler`` trace running, once per admission mode (whole-prompt
prefill, chunked prefill, prefix hit) and decode mode (plain, speculative);
the tests then read the bus records and the trace's host plane.
"""
import time
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from thunder_tpu import observability
from thunder_tpu.models.litgpt import Config, GPT
from thunder_tpu.observability import runtime as obs_runtime
from thunder_tpu.serving import ServingEngine

pytestmark = pytest.mark.serve

PHASES = {"engine:admit", "engine:prefill", "engine:upload", "engine:dispatch",
          "engine:fetch", "engine:commit"}
MODES = ("prefill", "chunk", "hit")
SPEC_K = 2


@pytest.fixture(scope="module")
def models():
    cfg = Config.from_name("tiny-llama2", block_size=64)
    return GPT(cfg, dtype=jnp.float32), GPT(cfg, dtype=jnp.float32)


def build(models, mode: str, spec: bool) -> ServingEngine:
    gpt, draft = models
    kw = dict(max_batch=4, page_size=8, max_seq=64, dtype=jnp.float32)
    if mode == "chunk":
        kw.update(chunk_tokens=16, prefill_budget=16)
    if mode == "hit":
        kw.update(prefix_sharing=True)
    if spec:
        kw.update(draft_gpt=draft, spec_k=SPEC_K)
    return ServingEngine(gpt, **kw)


def serve(engine: ServingEngine, mode: str) -> list:
    """Requests that take the admission road ``mode``; the last result is
    the one admitted that way (a prefix hit needs a donor before it)."""
    rng = np.random.RandomState(7)
    vocab = engine.cfg.vocab_size
    lengths = {"prefill": [9, 14], "chunk": [40, 23], "hit": [16]}[mode]
    prompts = [rng.randint(0, vocab, (L,)).astype(np.int32) for L in lengths]
    futs = [engine.submit(p, max_new_tokens=5) for p in prompts]
    engine.drain()
    if mode == "hit":
        futs.append(engine.submit(prompts[0], max_new_tokens=5))
        engine.drain()
    return [f.result(timeout=60) for f in futs]


def host_annotations(trace_dir) -> Counter:
    """Names of the events on the host plane's lines of a profiler trace."""
    from jax.profiler import ProfileData

    path = next(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    return Counter(e.name for p in ProfileData.from_file(str(path)).planes
                   if p.name == "/host:CPU" for ln in p.lines for e in ln.events)


@pytest.fixture(scope="module", params=[(m, s) for m in MODES for s in (False, True)],
                ids=lambda p: f"{p[0]}-{'spec' if p[1] else 'plain'}")
def served(request, models, tmp_path_factory):
    """One traced run: bus records, results, the engine and the trace's
    host annotations."""
    mode, spec = request.param
    engine = build(models, mode, spec)
    trace_dir = tmp_path_factory.mktemp("trace")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    observability.enable()
    observability.reset()
    try:
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        try:
            results = serve(engine, mode)
        finally:
            jax.profiler.stop_trace()
        records = observability.records()
        counters = observability.counters()
    finally:
        observability.disable()
        observability.reset()
    spans = [r for r in records if r["kind"] == "span"]
    return dict(mode=mode, spec=spec, engine=engine, results=results, records=records,
                spans=spans, names=Counter(r["name"] for r in spans),
                # decode steps whose rows rode in a chunk's program (the plain path, chunks due)
                mixed=counters.get("serve.decode_mixed", 0),
                annotations=host_annotations(trace_dir))


def end(span) -> float:
    return span["ts_ms"] + span["dur_ms"]


def test_phases_nest_under_an_iteration_and_siblings_do_not_overlap(served):
    by_id = {r["span"]: r for r in served["spans"]}

    def ancestors(r):
        while r["parent"] in by_id:
            r = by_id[r["parent"]]
            yield r

    phases = [r for r in served["spans"] if r["name"].startswith("engine:")]
    assert {r["name"] for r in phases} <= PHASES | {"engine:iteration"}
    iterations = [r for r in phases if r["name"] == "engine:iteration"]
    assert iterations and all(r["parent"] is None for r in iterations)
    assert all(set(r["attrs"]) == {"step", "active", "chunking", "pending"} for r in iterations)
    children = {}
    for r in phases:
        if r["name"] == "engine:iteration":
            continue
        up = list(ancestors(r))
        # under one iteration; between the two only other phases or the old serve_decode span,
        # and the chunk's serve_prefill span where the step rides in the chunk's program
        assert up and up[-1]["name"] == "engine:iteration", r
        assert all(a["name"] in PHASES | {"serve_decode", "serve_prefill"} for a in up[:-1]), r
        if "serve_prefill" in {a["name"] for a in up[:-1]}:
            assert served["mixed"] and r["name"] != "engine:prefill"
        assert r["ts_ms"] >= up[0]["ts_ms"] - 0.002 and end(r) <= end(up[0]) + 0.002
        children.setdefault(r["parent"], []).append(r)
    for sibs in children.values():
        sibs.sort(key=lambda r: r["ts_ms"])
        for a, b in zip(sibs, sibs[1:]):
            assert end(a) <= b["ts_ms"] + 0.002, (a, b)
    # the prefill that admission starts sits inside engine:admit; chunks do not
    inside_admit = {by_id[r["parent"]]["name"] for r in phases if r["name"] == "engine:prefill"}
    assert inside_admit == {"prefill": {"engine:admit"}, "chunk": {"engine:iteration"},
                            "hit": {"engine:admit"}}[served["mode"]]


def test_phase_counts_follow_the_decode_steps(served):
    names, steps = served["names"], served["engine"].decode_steps
    per_step = SPEC_K + 1 if served["spec"] else 1  # draft rounds + the verify step
    # a first token sampled behind its prompt's program is read under a fetch and committed under
    # a commit of its own, after the pass's dispatch: one each a request that was prefilled
    firsts = {"prefill": 2, "chunk": 2, "hit": 1}[served["mode"]]
    assert steps > 0 and names["serve_decode"] == steps == names["engine:commit"] - firsts
    assert names["engine:dispatch"] == names["engine:fetch"] - firsts == per_step * steps
    assert names["engine:upload"] == (per_step + 1) * steps
    assert names["engine:admit"] == names["engine:iteration"] >= steps
    # one phase round each prefill program's bus span, whole prompt or chunk
    assert names["engine:prefill"] == names["serve_prefill"] > 0


def test_prefill_phases_carry_the_request_and_its_trace_id(served):
    trace_ids = {r["attrs"]["request"]: r["attrs"]["trace_id"] for r in served["records"]
                 if r["kind"] == "event" and r["name"] == "trace"
                 and r["attrs"].get("phase") == "submitted"}
    prefills = [r for r in served["spans"] if r["name"] == "engine:prefill"]
    assert prefills
    for r in prefills:
        assert trace_ids[r["attrs"]["request"]] == r["attrs"]["trace_id"]
    # one whole-prompt prefill per request, or one phase per chunk
    by_request = Counter(r["attrs"]["request"] for r in prefills)
    want = {"prefill": [1, 1], "chunk": [3, 2], "hit": [1]}[served["mode"]]
    assert [by_request[i] for i in range(len(want))] == want


def test_the_profiler_sees_the_same_phases_and_one_serve_decode_a_dispatch(served):
    ann, names = served["annotations"], served["names"]
    for name in PHASES | {"engine:iteration"}:
        assert ann[name] == names[name], name
    steps, mixed = served["engine"].decode_steps, served["mixed"]
    # the old names are not reused: one annotation for each dispatch of a compiled program; a
    # step that rode in a chunk's program was dispatched as that chunk's `serve_chunk_prefill`
    # the second prompt's two chunks run beside the first request's decode steps
    assert mixed == (2 if (served["mode"], served["spec"]) == ("chunk", False) else 0)
    assert ann["serve_decode"] == (SPEC_K if served["spec"] else 1) * steps - mixed
    assert ann["serve_verify"] == (steps if served["spec"] else 0)
    assert ann["serve_decode"] + ann["serve_verify"] + mixed == ann["engine:dispatch"]
    if served["mode"] == "chunk":  # chunks at 0, 16, 32 of 40 tokens and at 0, 16 of 23
        assert ann["serve_chunk_prefill"] == (2 if served["spec"] else 1) * 5 >= mixed


def test_queue_wait_is_stamped_at_admission(served):
    admitted = {r["attrs"]["request"]: r["attrs"] for r in served["records"]
                if r["kind"] == "event" and r["name"] == "trace"
                and r["attrs"].get("phase") == "admitted"}
    for res in served["results"]:
        assert 0.0 <= res.queue_s <= res.ttft_s
        assert admitted[res.request_id]["queued_ms"] == round(res.queue_s * 1e3, 3)
    assert admitted[served["results"][-1].request_id]["mode"] == served["mode"]


@pytest.mark.parametrize("mode", MODES)
def test_with_the_bus_off_nothing_is_recorded_and_queue_wait_still_is(models, mode):
    assert not observability.enabled()
    assert obs_runtime.phase("engine:admit") is obs_runtime._NULL
    observability.reset()
    results = serve(build(models, mode, spec=False), mode)
    assert observability.records() == []
    assert all(0.0 < r.queue_s <= r.ttft_s for r in results)


def test_a_resumed_request_keeps_its_first_admission(models):
    engine = ServingEngine(models[0], max_batch=4, page_size=8, max_seq=64, n_pages=9,
                           dtype=jnp.float32)
    rng = np.random.RandomState(3)
    vocab = engine.cfg.vocab_size
    observability.enable()
    observability.reset()
    try:
        victim = engine.submit(rng.randint(0, vocab, (9,)).astype(np.int32), 20, lane="batch")
        engine._step_once()
        engine._step_once()
        # an interactive request that needs the whole pool spills the batch one
        engine.submit(rng.randint(0, vocab, (33,)).astype(np.int32), 5)
        engine.drain()
        records = observability.records()
    finally:
        observability.disable()
        observability.reset()
    assert engine.preempted == 1 and engine.resumed == 1
    res = victim.result(timeout=60)
    waits = [r["attrs"]["queued_ms"] for r in records
             if r["kind"] == "event" and r["name"] == "trace"
             and r["attrs"].get("phase") == "admitted"
             and r["attrs"]["request"] == res.request_id]
    assert len(waits) == 2 and waits[0] == waits[1] == round(res.queue_s * 1e3, 3)
    assert res.queue_s <= res.ttft_s


def test_an_idle_loop_opens_one_wait_phase_per_idle_stretch(models):
    engine = build(models, "prefill", spec=False)
    observability.enable()
    observability.reset()
    try:
        engine.start()
        try:
            time.sleep(0.05)  # fifty sleeps of the loop, one phase
            engine.submit(np.arange(1, 8, dtype=np.int32), 3).result(timeout=60)
        finally:
            engine.stop()
        spans = [r for r in observability.records() if r["kind"] == "span"]
    finally:
        observability.disable()
        observability.reset()
    waits = [r for r in spans if r["name"] == "engine:wait"]
    assert 1 <= len(waits) <= 3 and waits[0]["dur_ms"] >= 40.0
    assert all(r["parent"] is None for r in waits)
    loop_thread = {r["thread"] for r in spans if r["name"] == "engine:iteration"}
    assert {r["thread"] for r in waits} == loop_thread and len(loop_thread) == 1
