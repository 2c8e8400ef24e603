"""Serving load benchmark: open- or closed-loop streams against the
continuous-batching engine (thunder_tpu/serving/), reporting aggregate
tokens/sec, TTFT/TBOT p50/p99, page-pool utilization, the steady-state
recompile count, and — with an SLO configured — goodput.

Two load modes:

* ``--mode open`` (default; Orca/vLLM evaluation style): request arrival
  times are drawn up front from an exponential inter-arrival process and
  requests are submitted on that schedule whatever the engine's backlog —
  so queueing delay shows up in TTFT instead of being hidden by a closed
  loop.
* ``--mode closed``: ``--concurrency`` requests stay in flight; each
  completion immediately submits the next until ``--streams`` total have
  run. With ``--slo_ttft_ms``/``--slo_tbot_ms`` set, the engine stamps a
  per-request SLO-met flag at retirement and the row reports **goodput**
  (the fraction meeting the SLO) and **requests/s meeting the SLO** — the
  ROADMAP #2 acceptance metric.

Requests that produced <= 1 token have no between-token interval; they are
excluded from the TBOT percentiles but still counted in aggregate tokens/s,
so the row reports ``n_truncated`` explicitly to keep goodput and latency
denominators honest.

Workloads:

* ``--workload uniform`` (default): every prompt drawn iid from
  ``[prompt_len_min, prompt_len_max]`` — the original BENCH_SERVE row.
* ``--workload mixed``: fleet traffic through every serving stage at once
  (docs/serving.md). A ``--shared_frac`` fraction of requests reuse one
  system prompt (``--shared_prefix_len`` tokens) plus a short tail —
  admitted through the copy-on-write prefix cache; a ``--long_frac``
  fraction carry long prompts on the ``batch`` lane, prefilled in
  ``--chunk_tokens`` chunks interleaved with decode; the rest are the
  uniform interactive background. ``--self_draft`` runs the target model
  as its own speculative draft (every proposal verifies, so the row's
  ``spec_accept_rate`` is the plumbing ceiling, not a model-quality
  number). The row adds ``prefix_hit_rate`` (serve.prefix_hits /
  serve.requests) and ``spec_accept_rate`` (serve.spec_accepted /
  serve.spec_proposed) from post-warmup counters; both gate
  higher-is-better in tools/perf_gate.py.

Every row also reports ``obs_overhead_us`` — the measured disabled-path
cost of per-request tracing (tracing.disabled_overhead_us(); gated
lower-is-better) — plus the ``trace_counters`` family and a ``fleet``
block (per-host step stats + straggler flags from
observability.fleet_snapshot(), single-host degenerate here but the same
merge path a multi-host run aggregates through).

Usage:
    python -m thunder_tpu.benchmarks.benchmark_serving --model_name tiny-llama2 \
        --streams 8 --page_size 16 --arrival_rate 16
    python -m thunder_tpu.benchmarks.benchmark_serving --mode closed \
        --concurrency 4 --slo_ttft_ms 50 --slo_tbot_ms 15
    BENCH_SERVE=1 python -m thunder_tpu.benchmarks.benchmark_serving ...
        # additionally writes the BENCH_SERVE.json artifact row
        # (gate fresh runs against it with tools/perf_gate.py)
    BENCH_SERVE=1 python -m thunder_tpu.benchmarks.benchmark_serving \
        --mode closed --workload mixed --self_draft --spec_k 2 \
        --streams 160 --concurrency 10 --precision f32 --n_pages 256 \
        --slo_ttft_ms 750 --slo_tbot_ms 100 --new_tokens_min 2 \
        --new_tokens_max 4 --long_frac 0.06 --artifact BENCH_SERVE_FLEET.json
        # regenerates the committed fleet baseline row
"""
from __future__ import annotations

import argparse
import json
import os
import time
from concurrent.futures import FIRST_COMPLETED, wait

import jax.numpy as jnp
import numpy as np


from thunder_tpu.observability.telemetry import percentile as _pct


def _submit(engine, rng, cfg, spec, temperature):
    prompt, n, lane = spec
    return engine.submit(prompt, max_new_tokens=n, temperature=temperature,
                         seed=int(rng.randint(1 << 30)), lane=lane)


def _mixed_specs(args, cfg, rng) -> list:
    """(prompt, max_new_tokens, lane) per stream: shared-prefix requests
    (interactive), long chunked prompts (batch lane), uniform background."""
    shared = rng.randint(0, cfg.vocab_size,
                         (args.shared_prefix_len,)).astype(np.int32)
    long_max = args.max_seq - args.new_tokens_max - 1
    specs = []
    for _ in range(args.streams):
        n = int(rng.randint(args.new_tokens_min, args.new_tokens_max + 1))
        u = rng.random_sample()
        if u < args.shared_frac:
            tail = rng.randint(0, cfg.vocab_size,
                               (int(rng.randint(1, 9)),)).astype(np.int32)
            specs.append((np.concatenate([shared, tail]), n, "interactive"))
        elif u < args.shared_frac + args.long_frac:
            L = int(rng.randint(max(args.chunk_tokens + 1, long_max // 2),
                                long_max + 1))
            specs.append((rng.randint(0, cfg.vocab_size, (L,)).astype(np.int32),
                          n, "batch"))
        else:
            L = int(rng.randint(args.prompt_len_min, args.prompt_len_max + 1))
            specs.append((rng.randint(0, cfg.vocab_size, (L,)).astype(np.int32),
                          n, "interactive"))
    return specs


def _uniform_specs(args, cfg, rng) -> list:
    return [(rng.randint(0, cfg.vocab_size,
                         (int(rng.randint(args.prompt_len_min,
                                          args.prompt_len_max + 1)),)
                         ).astype(np.int32),
             int(rng.randint(args.new_tokens_min, args.new_tokens_max + 1)),
             "interactive")
            for _ in range(args.streams)]


def run(args) -> dict:
    from thunder_tpu import observability
    from thunder_tpu.models.litgpt import Config, GPT
    from thunder_tpu.observability.slo import SLOPolicy
    from thunder_tpu.serving import ServingEngine

    slo = None
    if args.slo_ttft_ms or args.slo_tbot_ms:
        slo = SLOPolicy(p99_ttft_ms=args.slo_ttft_ms or None,
                        p99_tbot_ms=args.slo_tbot_ms or None,
                        min_samples=min(8, max(2, args.streams // 4)))

    dtype = jnp.bfloat16 if args.precision == "bf16" else jnp.float32
    cfg = Config.from_name(args.model_name, block_size=max(args.max_seq, 128))
    gpt = GPT(cfg, dtype=dtype)
    fleet_kw = {}
    if args.workload == "mixed":
        fleet_kw = dict(prefix_sharing=True, chunk_tokens=args.chunk_tokens,
                        draft_gpt=gpt if args.self_draft else None,
                        spec_k=args.spec_k if args.self_draft else None)
    engine = ServingEngine(gpt, max_batch=args.max_batch, page_size=args.page_size,
                           max_seq=args.max_seq, dtype=dtype, slo=slo,
                           n_pages=args.n_pages or None,
                           quantize=None if args.quantize == "none" else args.quantize,
                           **fleet_kw)

    rng = np.random.RandomState(args.seed)
    if args.workload == "mixed":
        specs = _mixed_specs(args, cfg, rng)
    else:
        specs = _uniform_specs(args, cfg, rng)

    observability.enable()
    # warm every program the workload will touch plus the decode step, then
    # clear the counters: any recompile recorded after this point is a
    # steady-state failure
    if args.workload == "mixed":
        # replay the full spec list once so every prefill bucket, chunk
        # rung, and the verify program compile — AND the prefix cache ends
        # warm, which is the steady state the measured phase models
        for spec in specs:
            engine.submit(spec[0], 3, lane=spec[2])  # as warmup(): both ways a step is fed
        engine.drain()
    else:
        engine.warmup(sorted({len(p) for p, _, _ in specs}))
    observability.reset()
    engine.reset_slo_accounting()  # warmup must not pollute goodput/windows

    engine.start()
    t0 = time.perf_counter()
    futs = []
    try:
        if args.mode == "open":
            # exponential inter-arrivals -> open-loop schedule (s from t0)
            gaps = rng.exponential(1.0 / args.arrival_rate, size=args.streams)
            arrivals = np.cumsum(gaps) - gaps[0]
            for spec, at in zip(specs, arrivals):
                dt = t0 + float(at) - time.perf_counter()
                if dt > 0:
                    time.sleep(dt)
                futs.append(_submit(engine, rng, cfg, spec, args.temperature))
            results = [f.result(timeout=600) for f in futs]
        else:
            # closed loop: a fixed number of in-flight requests; every
            # completion immediately feeds the next submission
            todo = list(specs)
            inflight = set()
            while todo and len(inflight) < max(1, args.concurrency):
                inflight.add(_submit(engine, rng, cfg, todo.pop(0),
                                     args.temperature))
            futs = list(inflight)
            while inflight:
                done, inflight = wait(inflight, timeout=600,
                                      return_when=FIRST_COMPLETED)
                if not done:
                    raise TimeoutError("closed-loop benchmark stalled")
                for _ in done:
                    if todo:
                        f = _submit(engine, rng, cfg, todo.pop(0),
                                    args.temperature)
                        inflight.add(f)
                        futs.append(f)
            results = [f.result(timeout=600) for f in futs]
    finally:
        engine.stop()
    wall = time.perf_counter() - t0

    counters = observability.counters()
    # fleet view over this (single-host) run: merged counters + per-host step
    # stats from the same snapshot/merge path a multi-host aggregation uses
    fleet_snap = observability.fleet_snapshot()
    observability.disable()
    # disabled-path cost of request tracing, measured with the bus OFF (the
    # state the key gates): min-of-repeats microbench, see perf_gate.py
    from thunder_tpu.observability import tracing as _tracing
    obs_overhead_us = _tracing.disabled_overhead_us()
    recompiles = sum(v for k, v in counters.items() if k.startswith("recompile."))

    import jax

    total_new = sum(r.n_new_tokens for r in results)
    ttfts = [r.ttft_s * 1e3 for r in results]
    # <= 1 generated token -> no between-token interval: excluded from the
    # TBOT percentiles (but still in aggregate tokens/s); n_truncated below
    # reports the exclusion explicitly
    tbots = [r.tbot_s * 1e3 for r in results if r.n_new_tokens > 1]
    n_truncated = sum(1 for r in results if r.n_new_tokens <= 1)
    stats = engine.stats()
    workload_tag = "" if args.workload == "uniform" else f"{args.workload} workload, "
    if args.quantize != "none":
        workload_tag += f"{args.quantize} weight-quantized decode, "
    row = {
        "platform": jax.devices()[0].platform,
        "metric": (f"{args.model_name} serving aggregate new tokens/sec "
                   f"({args.streams} {args.mode}-loop streams, {workload_tag}"
                   f"max_batch={args.max_batch}, "
                   f"page_size={args.page_size}, "
                   f"prompts {args.prompt_len_min}-{args.prompt_len_max}, "
                   f"outputs {args.new_tokens_min}-{args.new_tokens_max})"),
        "value": round(total_new / wall, 2),
        "unit": "tokens/s",
        "mode": args.mode,
        "n_requests": len(results),
        "n_truncated": n_truncated,
        "total_new_tokens": total_new,
        "wall_s": round(wall, 3),
        "requests_per_s": round(len(results) / wall, 2),
        "ttft_ms_p50": round(_pct(ttfts, 0.50), 2),
        "ttft_ms_p99": round(_pct(ttfts, 0.99), 2),
        "tbot_ms_p50": round(_pct(tbots, 0.50), 2),
        "tbot_ms_p99": round(_pct(tbots, 0.99), 2),
        "decode_steps": stats["decode_steps"],
        "peak_page_pool_utilization": stats["peak_page_pool_utilization"],
        "recompiles_steady_state": int(recompiles),
        "obs_overhead_us": round(obs_overhead_us, 3),
        "serve_counters": {k: v for k, v in counters.items() if k.startswith("serve.")},
        # request-tracing traffic only: the specialization cache is ALSO
        # named "trace", so exclude its hit/miss/evict outcome counters
        "trace_counters": {k: v for k, v in counters.items()
                           if k.startswith("trace.")
                           and k.partition(".")[2] not in ("hit", "miss", "evict")},
        "fleet": {
            "n_hosts": fleet_snap.get("n_hosts"),
            "hosts": {str(h): info.get("steps")
                      for h, info in fleet_snap.get("hosts", {}).items()},
            "stragglers": fleet_snap.get("stragglers", []),
        },
    }
    if args.workload == "mixed":
        n_req = counters.get("serve.requests", 0)
        proposed = counters.get("serve.spec_proposed", 0)
        row["workload"] = {"shared_frac": args.shared_frac,
                           "long_frac": args.long_frac,
                           "shared_prefix_len": args.shared_prefix_len,
                           "chunk_tokens": args.chunk_tokens,
                           "self_draft": bool(args.self_draft),
                           "spec_k": args.spec_k if args.self_draft else 0}
        row["prefix_hit_rate"] = (round(counters.get("serve.prefix_hits", 0)
                                        / n_req, 4) if n_req else None)
        row["prefix_tokens_saved"] = counters.get("serve.prefix_tokens_saved", 0)
        row["spec_accept_rate"] = (round(counters.get("serve.spec_accepted", 0)
                                         / proposed, 4) if proposed else None)
        row["preempted"] = stats["preempted"]
        row["resumed"] = stats["resumed"]
    if slo is not None:
        n_met = sum(1 for r in results if r.slo_met)
        row["slo"] = {"ttft_ms": args.slo_ttft_ms or None,
                      "tbot_ms": args.slo_tbot_ms or None}
        row["goodput"] = round(n_met / len(results), 4) if results else None
        row["requests_per_s_slo_met"] = round(n_met / wall, 2)
        row["slo_breaches"] = {k: v for k, v in counters.items()
                               if k.startswith("slo.breach.")}
    print(json.dumps(row, indent=1))
    if os.environ.get("BENCH_SERVE") == "1":
        # merge-by-metric so variant runs (e.g. --quantize int8 next to the
        # bf16 baseline) accumulate into one multi-row artifact instead of
        # clobbering each other; perf_gate.load_rows handles both shapes
        rows = []
        if os.path.exists(args.artifact):
            try:
                with open(args.artifact) as f:
                    old = json.load(f)
                rows = old if isinstance(old, list) else [old]
            except Exception:
                rows = []
        rows = [r for r in rows if r.get("metric") != row["metric"]] + [row]
        with open(args.artifact, "w") as f:
            json.dump(rows if len(rows) > 1 else row, f, indent=1)
        print(f"wrote {args.artifact} ({len(rows)} row(s))")
    return row


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model_name", default="tiny-llama2")
    p.add_argument("--mode", default="open", choices=["open", "closed"])
    p.add_argument("--streams", type=int, default=8)
    p.add_argument("--concurrency", type=int, default=4,
                   help="closed-loop in-flight request target")
    p.add_argument("--max_batch", type=int, default=8)
    p.add_argument("--page_size", type=int, default=16)
    p.add_argument("--max_seq", type=int, default=256)
    p.add_argument("--prompt_len_min", type=int, default=8)
    p.add_argument("--prompt_len_max", type=int, default=48)
    p.add_argument("--new_tokens_min", type=int, default=8)
    p.add_argument("--new_tokens_max", type=int, default=32)
    p.add_argument("--arrival_rate", type=float, default=8.0,
                   help="open-loop arrivals per second")
    p.add_argument("--slo_ttft_ms", type=float, default=0.0,
                   help="per-request TTFT target; enables goodput reporting")
    p.add_argument("--slo_tbot_ms", type=float, default=0.0,
                   help="per-request TBOT target; enables goodput reporting")
    p.add_argument("--workload", default="uniform", choices=["uniform", "mixed"])
    p.add_argument("--shared_frac", type=float, default=0.6,
                   help="mixed: fraction of requests sharing the system prompt")
    p.add_argument("--long_frac", type=float, default=0.15,
                   help="mixed: fraction with long (chunk-prefilled) prompts")
    p.add_argument("--shared_prefix_len", type=int, default=64,
                   help="mixed: shared system-prompt length (page-aligned)")
    p.add_argument("--chunk_tokens", type=int, default=64,
                   help="mixed: chunked-prefill chunk size")
    p.add_argument("--self_draft", action="store_true",
                   help="mixed: speculative decoding with the target as its "
                        "own draft (plumbing-ceiling accept rate)")
    p.add_argument("--spec_k", type=int, default=3)
    p.add_argument("--n_pages", type=int, default=0,
                   help="page-pool override (0 = engine default)")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--quantize", default="none", choices=["none", "int8"],
                   help="weight-only quantization for the serving model "
                        "(int8: dequant-in-kernel decode compute)")
    p.add_argument("--precision", default="bf16", choices=["bf16", "f32"])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--artifact", default="BENCH_SERVE.json")
    run(p.parse_args())


if __name__ == "__main__":
    main()
