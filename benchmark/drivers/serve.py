"""Driver ``serve``: requests through ``ServingEngine`` (paged KV, continuous
batching, chunked prefill), greedy, no stop token, so that a request always
yields the tokens it asked for.

A traffic file for this driver states the engine (any keyword of
``ServingEngine``), the loop (open with a fixed ``rate_rps``, or closed with a
fixed number of ``clients``), the length distributions and the correctness
sample. Set-up, in order: the model in its served type with weights from the
seed; one request per compiled shape the traffic can produce; the correctness
sample (served alone, served together, judged against the plain reference);
then a pre-roll of the cell's own traffic, so that the window opens on a
running system and not on an empty one.
"""
from __future__ import annotations

import math
import threading
import time

import numpy as np

from benchmark.lib import harness, loadgen

RESULT_TIMEOUT_S = 600.0


def build_engine(cell, seed: int):
    import jax.numpy as jnp

    from thunder_tpu.serving import ServingEngine

    spec = dict(cell.traffic["engine"])
    dtype = getattr(jnp, spec.pop("dtype", "bfloat16"))
    gpt = cell.builder.build_serving_model(cell.config, cell.config_name, dtype)
    cell.builder.reseed(dict(gpt.named_parameters()), seed, cell.config)
    return ServingEngine(gpt, dtype=dtype, **spec)


def warmup_lengths(engine, lo: int, hi: int) -> list:
    """One prompt length for every compiled prefill shape that prompts of
    ``lo .. hi`` tokens can reach: whole-prompt buckets up to ``chunk_tokens``,
    and beyond it a full chunk followed by each rung of the final chunk."""
    first = {}
    C = engine.chunk_tokens
    for L in range(lo, hi + 1):
        if L <= C:
            key = ("prefill", engine.ladder.bucket_for(L))
        else:
            key = ("chunk", engine.chunk_ladder.bucket_for(L - ((L - 1) // C) * C))
        first.setdefault(key, L)
    return sorted(first.values())


def serve_all(engine, prompts: list, n_new: list) -> list:
    futures = [engine.submit(p, max_new_tokens=n) for p, n in zip(prompts, n_new)]
    return [f.result(timeout=RESULT_TIMEOUT_S) for f in futures]


def check_sample(cell, engine, seed: int, notes: list) -> dict:
    """The correctness sample: each request served alone and then all at once
    must give identical tokens, and the reference's full forward over prompt
    plus output must put every chosen token within ``margin`` of its own
    largest logit at that position."""
    import jax

    spec = cell.traffic["correctness"]
    vocab = cell.config["vocab_size"]
    reqs = [(int(p), int(n)) for p, n in spec["requests"]]
    prompts = [loadgen.prompt_tokens(seed, 1_000_000 + i, p, vocab) for i, (p, _) in enumerate(reqs)]
    alone = [serve_all(engine, [p], [n])[0] for p, (_, n) in zip(prompts, reqs)]
    together = serve_all(engine, prompts, [n for _, n in reqs])
    differ = 0
    for i, (a, b) in enumerate(zip(alone, together)):
        if a.n_new_tokens != reqs[i][1] or not np.array_equal(a.new_tokens, b.new_tokens):
            notes.append(f"sample request {i} {reqs[i]}: alone and batched outputs differ")
            differ += 1

    ref = cell.reference
    params = engine.params
    t_max = max(p + n for p, n in reqs)
    n_max = max(n for _, n in reqs)
    fn = jax.jit(lambda prm, toks, rows: ref.forward(cell.config, prm, toks, rows=rows))
    worst = 0.0
    for (p, n), res in zip(reqs, alone):
        toks = np.zeros((t_max,), np.int32)
        toks[:p + n] = res.tokens
        # the logits that chose output token j are those at position p + j - 1
        rows = np.minimum(np.arange(n_max) + p - 1, p + n - 2).astype(np.int32)
        logits = np.asarray(fn(params, toks, rows))[:n]
        gap = logits.max(axis=-1) - logits[np.arange(n), res.new_tokens]
        worst = max(worst, float(gap.max()))
    margin = float(spec["margin"])
    harness.say(f"correctness sample: {len(reqs)} requests alone == batched; largest distance "
                f"of a chosen token from the reference's top logit {worst:.4f} (margin {margin})")
    if not worst <= margin:
        notes.append(f"a chosen token is {worst} below the reference's top logit (margin {margin})")
    return {"sample_margin": worst, "sample_differ": differ}


# the compiled programs of ``engine.runner`` whose kernels the builder's ``kernel_claims`` states
PROGRAMS = ("decode_cfn", "chunk_cfn")


def program_claims(engine) -> dict:
    """What Pallas claimed in each of ``PROGRAMS``, read off the program's last
    executed trace; a program this cell's traffic never ran is left out."""
    import thunder_tpu as tt

    out = {}
    for name in PROGRAMS:
        traces = tt.last_traces(getattr(engine.runner, name)._cfn)
        if traces:
            out[name] = dict(harness.pallas_claims(traces[-1]))
    return out


def check_kernels(cell, claims, notes: list, compared: dict) -> dict:
    """``claims`` (``program_claims`` of the engine) against what the builder
    says the model needs: each symbol claimed exactly as often as stated, in
    every program that ran. ``claims`` is ``None`` off the TPU, where the Pallas
    executor declines and XLA runs the decomposition: nothing to judge, but the
    builder is still asked, so that one without ``kernel_claims`` fails here."""
    want = harness.wanted_claims(cell, PROGRAMS)
    if claims is None:
        return {}
    # a program that is not in ``claims`` is one this cell's traffic never ran
    for name, symbols, got, count in harness.unheld_claims(want, claims, "==", compared):
        notes.append(f"{symbols} claimed by pallas {got} times in {name}, not {count}")
    if "decode_cfn" not in claims:
        notes.append("the decode program never ran")
    return claims


def decode_regions(engine) -> list:
    """Names of the XLA regions the decode program executes (each runs as the
    executable ``jit_<name>``), read off its executed trace."""
    import thunder_tpu as tt
    from thunder_tpu.executors import xlaex

    traces = tt.last_traces(engine.runner.decode_cfn._cfn)
    if not traces:
        return []
    return sorted({b.sym.name for b in traces[-1].bound_symbols if b.sym.executor is xlaex.ex})


def set_up(cell, seed: int, notes: list):
    """The started engine, every shape of the cell's traffic compiled, and the
    correctness sample judged. Returns ``(engine, stats)``."""
    engine = build_engine(cell, seed)
    engine.start()
    try:
        lengths = cell.traffic["prompt_len"]
        warm = warmup_lengths(engine, lengths["min"], lengths["max"])
        serve_all(engine, [np.zeros((L,), np.int32) for L in warm], [2] * len(warm))
        stats = check_sample(cell, engine, seed, notes)
    except BaseException:
        engine.stop()
        raise
    stats["warmup_lengths"] = warm
    return engine, stats


def make_loop(traffic: dict, engine, seconds: float, seed: int, vocab: int):
    def submit(req):
        return engine.submit(loadgen.prompt_tokens(seed, req.index, req.prompt_len, vocab),
                             max_new_tokens=req.output_len)

    def read(res):
        return res.ttft_s, res.tbot_s, res.n_new_tokens

    kind = traffic["loop"]["kind"]
    if kind == "open":
        return loadgen.OpenLoop(submit, read, loadgen.open_loop_schedule(traffic, seconds, seed))
    if kind == "closed":
        return loadgen.ClosedLoop(submit, read, loadgen.LengthStream(traffic, seed),
                                  int(traffic["loop"]["clients"]))
    raise ValueError(f"unknown loop kind {kind!r}")


def offer(traffic: dict, engine, seconds: float, seed: int, vocab: int, watch, profiler=None) -> dict:
    """Pre-roll, then one measured window of ``traffic`` on a warm engine.
    Returns the records (times relative to the window's start) and what was
    counted inside the window. The engine is left running."""
    from thunder_tpu import observability

    loop = make_loop(traffic, engine, seconds, seed, vocab)
    tracer = None
    t_win = time.perf_counter() + float(traffic["loop"].get("preroll_s", 0.0)) + 0.05
    loop.start(t_win)
    try:
        time.sleep(max(0.0, t_win - time.perf_counter()))
        observability.reset()
        compiles0 = watch.snapshot()
        steps0 = engine.stats()["decode_steps"]
        if profiler is not None:
            def trace_part():
                time.sleep(harness.TRACE_AT * seconds)
                profiler.start()
                time.sleep(min(harness.TRACE_SECONDS, (1 - harness.TRACE_AT) * seconds))
                profiler.stop()

            tracer = threading.Thread(target=trace_part, name="bench-tracer")
            tracer.start()
        time.sleep(max(0.0, t_win + seconds - time.perf_counter()))
        window_s = time.perf_counter() - t_win
    finally:
        loop.stop()
        if tracer is not None:
            tracer.join()
    engine_stats = engine.stats()
    return {"records": loop.records, "t_win": t_win, "window_s": window_s,
            "engine": engine_stats, "decode_steps": engine_stats["decode_steps"] - steps0,
            "compiles": harness.CompileWatch.delta(watch.snapshot(), compiles0),
            "counters": observability.counters(), "bus": observability.records()}


def run(cell, opts, env) -> harness.Run:
    from thunder_tpu import observability
    from thunder_tpu.executors import pallasex

    traffic = cell.traffic
    vocab = cell.config["vocab_size"]
    on_tpu = env.devices[0].platform == "tpu"
    notes: list = []
    if on_tpu and pallasex._interpret():
        notes.append("pallas kernels would run in interpret mode")
    if opts.trace:
        observability.enable()  # in memory: the counters and spans the readers use

    engine, stats = set_up(cell, opts.seed, notes)
    compared: dict = {}
    harness.held(compared, "sample_alone_vs_batched_differ", stats["sample_differ"], "==", 0)
    harness.held(compared, "sample_margin", stats["sample_margin"], "<=",
                 float(traffic["correctness"]["margin"]))
    profiler = env.profiler() if opts.trace else None
    try:
        w = offer(traffic, engine, opts.seconds, opts.seed, vocab, env.watch, profiler)
    finally:
        engine.stop()
    setup_s = w["t_win"] - env.t_start
    records, compiles, counters = w["records"], w["compiles"], w["counters"]

    in_window = [r for r in records if 0.0 <= r.due < opts.seconds]
    measured = loadgen.measured(records, opts.seconds)
    failed = [r for r in measured if not r.ok]
    for r in failed[:3]:
        notes.append(f"request {r.index} ({r.prompt_len} + {r.output_len}) failed: {r.error}")
    good = [r for r in measured if r.ok]
    harness.held(compared, "requests_failed", len(failed), "==", 0)
    if not harness.held(compared, "requests_completed", len(good), ">=", 1):
        notes.append("no request completed inside the window")
    if not harness.held(compared, "requests_with_other_token_count",
                        sum(r.n_new != r.output_len for r in good), "==", 0):
        notes.append("a request came back with another number of tokens than it asked for")
    if not harness.held(compared, "executables_built_in_window", compiles["builds"], "==", 0):
        notes.append(f"{compiles['builds']} executables were built inside the window")
    faults = harness.steady_state_faults(counters)
    if not harness.held(compared, "program_recompiles_or_fallbacks", sum(faults.values()), "==", 0):
        notes.append(f"the program counted recompiles or fallbacks in the window: {faults}")
    stats["kernels"] = check_kernels(cell, program_claims(engine) if on_tpu else None, notes,
                                     compared)
    stats["decode_regions"] = decode_regions(engine)
    stats["engine"] = w["engine"]
    stats["decode_steps"] = w["decode_steps"]
    stats["max_batch"] = engine.max_batch
    stats["in_flight_at_end"] = sum(1 for r in records if math.isnan(r.done) or r.done > opts.seconds)
    stats["due_in_window"] = len(in_window)

    end_to_end = {"setup_s": setup_s}
    if good:
        # time per output token as the client sees it: from the due instant to
        # the last token, over the tokens received — the harness's own clock
        tpot = [(r.done - r.due) / r.n_new * 1e3 if r.ok else opts.seconds * 1e3
                for r in measured]
        end_to_end["serve_tpot_p50_ms"] = loadgen.percentile(tpot, 50)
        end_to_end["serve_total_tokens_per_s"] = loadgen.token_rate(
            records, opts.seconds, lambda r: r.prompt_len + r.n_new,
            -float(traffic["loop"].get("preroll_s", 0.0)))
    harness.say(f"{len(in_window)} requests due in the window, {len(good)} completed in it, "
                f"{len(failed)} failed, {stats['in_flight_at_end']} still in flight at its end; "
                f"{stats['decode_steps']} decode steps")
    run = harness.Run(cell=cell, device_kind=env.devices[0].device_kind, chips=cell.chips,
                      window_s=w["window_s"], attempted=len(measured),
                      failed=len(failed), end_to_end=end_to_end, spans=dict(env.spans.durations),
                      records=records, stats=stats, counters=counters, bus=w["bus"],
                      compiles=compiles, notes=notes, compared=compared)
    if profiler is not None:
        run.trace = profiler.reduce(host_ops_as_device=not on_tpu)
    return run
