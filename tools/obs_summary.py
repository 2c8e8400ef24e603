"""Pretty-print thunder_tpu observability JSONL timelines.

Reads one or more event-bus exports (TT_OBS_FILE=..., observability.dump(),
or per-process shards) and renders the views an operator actually wants: the compile-phase span tree with
durations, cache traffic and recompile reasons, step-latency statistics,
a per-host fleet breakdown (step latency + straggler flags per shard),
a memory section (watermarks, pressure crossings, estimate drift, OOM
bundles, live-array census), the ``perf`` subcommand's device-time/FLOPs
view (with per-region comms-overlap columns), and the ``trace``
subcommand's end-to-end request timeline (submitted -> ... -> retired,
optionally exported as Chrome trace-event JSON for chrome://tracing).

Usage:
    python tools/obs_summary.py TIMELINE.jsonl [more.jsonl ...] [--top N]
    python tools/obs_summary.py perf TIMELINE.jsonl [more.jsonl ...]
    python tools/obs_summary.py trace REQUEST_ID TIMELINE.jsonl [more.jsonl ...]
                                [--chrome out.json]

Multiple shards are merged: records from shard i get the composite process
key ``s<i>:<pid>`` (two hosts can share a pid) and the merged stream is
sorted by monotonic time within each process. Exits non-zero with a clear
message when the merged timeline holds no parseable records. This tool is
deliberately stdlib-only (no thunder_tpu/jax import) so it runs anywhere a
shard lands — the trace/fleet views re-derive their structure from the raw
JSONL schema documented in docs/observability.md.
"""
from __future__ import annotations

import argparse
import json
import sys

_STEP_SPANS = ("step", "train_step", "micro_step", "infer_step",
               "infer_prefill", "infer_decode")


def load(path: str) -> list[dict]:
    recs = []
    with open(path) as f:
        for ln, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                print(f"# {path}: skipping malformed line {ln}", file=sys.stderr)
                continue
            if isinstance(rec, dict):
                recs.append(rec)
    return recs


def load_many(paths: list[str]) -> list[dict]:
    """Load + merge shards. With several shards, pids are namespaced per
    shard (``s0:4242``) so span trees and counter totals from different
    hosts never collide, then the stream is sorted by ``ts_ms`` within each
    process (ts_ms is monotonic per process, meaningless across them)."""
    if len(paths) == 1:
        shards = [load(paths[0])]
    else:
        shards = []
        for i, p in enumerate(paths):
            recs = load(p)
            for r in recs:
                r["pid"] = f"s{i}:{r.get('pid', 0)}"
            shards.append(recs)
    merged = [r for recs in shards for r in recs]
    merged.sort(key=lambda r: (str(r.get("pid", 0)), r.get("ts_ms", 0.0)))
    return merged


def _sid(r: dict, key: str = "span"):
    """Span identity: (pid, span id). Span ids restart at 1 in every
    process, and a timeline may concatenate several processes' records —
    pid keeps their trees from colliding (absent pid → one shared bucket)."""
    return (r.get("pid", 0), r.get(key))


def span_tree(recs: list[dict]) -> list[str]:
    """Indented span forest, in start order, with durations and tags."""
    spans = [r for r in recs if r.get("kind") == "span"]
    by_id = {_sid(r): r for r in spans}
    children: dict = {}
    roots = []
    for r in spans:
        if r.get("parent") is not None and _sid(r, "parent") in by_id:
            children.setdefault(_sid(r, "parent"), []).append(r)
        else:
            roots.append(r)
    lines = []

    def tag_str(r: dict) -> str:
        attrs = r.get("attrs") or {}
        shown = {k: v for k, v in attrs.items() if k != "executors"}
        return ("  [" + " ".join(f"{k}={v}" for k, v in shown.items()) + "]") if shown else ""

    def walk(r: dict, depth: int):
        lines.append(f"{'  ' * depth}{r['name']:<{max(1, 28 - 2 * depth)}} "
                     f"{r['dur_ms']:>10.2f} ms{tag_str(r)}")
        for c in sorted(children.get(_sid(r), []), key=lambda x: x["ts_ms"]):
            walk(c, depth + 1)

    for r in sorted(roots, key=lambda x: (x.get("pid", 0), x["ts_ms"])):
        walk(r, 0)
    return lines


def final_counters(recs: list[dict]) -> dict[str, int]:
    """Counter totals summed across processes: within one pid the running
    ``value`` (or its final snapshot) is authoritative; a multi-process
    artifact (bench cold + warm phases) sums the per-pid finals."""
    per_pid: dict = {}
    for r in recs:
        pid = r.get("pid", 0)
        if r.get("kind") == "counter":
            per_pid.setdefault(pid, {})[r["name"]] = r.get(
                "value", per_pid.get(pid, {}).get(r["name"], 0))
        elif r.get("kind") == "snapshot":
            per_pid.setdefault(pid, {}).update(r.get("counters", {}))
    out: dict[str, int] = {}
    for finals in per_pid.values():
        for name, v in finals.items():
            out[name] = out.get(name, 0) + v
    return out


def cache_table(counters: dict[str, int]) -> list[str]:
    caches: dict[str, dict[str, int]] = {}
    for name, v in counters.items():
        cache, _, outcome = name.partition(".")
        if outcome in ("hit", "miss", "evict"):
            caches.setdefault(cache, {})[outcome] = v
    lines = []
    for cache, stats in sorted(caches.items()):
        hit, miss = stats.get("hit", 0), stats.get("miss", 0)
        rate = f"{hit / (hit + miss):.0%}" if hit + miss else "-"
        lines.append(f"  {cache:<8} hit={hit:<6} miss={miss:<6} "
                     f"evict={stats.get('evict', 0):<4} hit-rate={rate}")
    return lines


def recompile_lines(recs: list[dict], counters: dict[str, int]) -> list[str]:
    lines = []
    for name, v in sorted(counters.items()):
        if name.startswith("recompile."):
            lines.append(f"  {name.removeprefix('recompile.'):<30} x{v}")
    events = [r for r in recs if r.get("kind") == "event" and r.get("name") == "recompile"]
    for r in events[-8:]:
        attrs = r.get("attrs", {})
        detail = " ".join(f"{k}={v}" for k, v in attrs.items() if k != "reason")
        lines.append(f"    @{r['ts_ms']:.0f}ms  {attrs.get('reason', '?')}  {detail}")
    return lines


def host_overhead_stats(recs: list[dict]) -> list[str]:
    """Per-dispatch host overhead (the opt-in ``host_overhead`` event emitted
    by TrainStep and InterpretedFunction cache hits): how much Python runs
    between step entry and the compiled-program handoff."""
    by_fn: dict[str, list[float]] = {}
    for r in recs:
        if r.get("kind") == "event" and r.get("name") == "host_overhead":
            attrs = r.get("attrs") or {}
            if "us" in attrs:
                by_fn.setdefault(attrs.get("fn", "?"), []).append(attrs["us"])
    lines = []
    for fn, durs in sorted(by_fn.items()):
        durs.sort()
        n = len(durs)
        lines.append(f"  {fn:<20} dispatches={n}  mean={sum(durs) / n:.1f}us  "
                     f"p50={durs[n // 2]:.1f}us  "
                     f"p95={durs[min(n - 1, int(n * 0.95))]:.1f}us  max={durs[-1]:.1f}us")
    return lines


def step_stats(recs: list[dict]) -> list[str]:
    durs = sorted(r["dur_ms"] for r in recs
                  if r.get("kind") == "span" and r.get("name") in _STEP_SPANS)
    if not durs:
        return []
    n = len(durs)
    return [f"  steps={n}  mean={sum(durs) / n:.3f}ms  p50={durs[n // 2]:.3f}ms  "
            f"p95={durs[min(n - 1, int(n * 0.95))]:.3f}ms  max={durs[-1]:.3f}ms"]


def spike_lines(recs: list[dict]) -> list[str]:
    """Flight-recorder straggler/spike events with their triaged cause."""
    spikes = [r for r in recs if r.get("kind") == "event" and r.get("name") == "step_spike"]
    lines = []
    for r in spikes[-10:]:
        a = r.get("attrs", {})
        ratio = a.get("ratio")
        detail = ""
        if a.get("reason"):
            detail = f" reason={a['reason']}"
        elif a.get("cause") == "checkpoint-save":
            # name the overlapping save so checkpoint stalls stop reading as
            # anonymous spikes (save_ms is absent while the write is in flight)
            detail = f" ckpt_step={a.get('ckpt_step')}"
            if a.get("save_ms") is not None:
                detail += f" save_ms={a['save_ms']}"
        lines.append(
            f"  step {a.get('step', '?'):>6}  {a.get('wall_ms', '?')}ms "
            f"({ratio}x median {a.get('median_ms', '?')}ms)  "
            f"cause={a.get('cause', 'unknown')}" + detail)
    return lines


def compile_lines(recs: list[dict], counters: dict[str, int]) -> list[str]:
    """Compile-service section: artifact-store traffic (artifact.* counters
    + recent compile_artifact_* events) and per-region compile latency from
    ``compile_region`` spans (parallel region compilation) plus the lazy
    ``xla_compile`` first-dispatch spans (thunder_tpu/compile_service/)."""
    art_counters = {k: v for k, v in counters.items() if k.startswith("artifact.")}
    region_spans = [r for r in recs if r.get("kind") == "span"
                    and r.get("name") == "compile_region"]
    lazy_spans = [r for r in recs if r.get("kind") == "span"
                  and r.get("name") == "xla_compile"]
    prewarmed = {k: v for k, v in counters.items() if k.startswith("compile.")}
    if not art_counters and not region_spans and not lazy_spans and not prewarmed:
        return []
    lines = []
    for k, v in sorted({**art_counters, **prewarmed}.items()):
        lines.append(f"  {k:<28} {v}")
    evs = [r for r in recs if r.get("kind") == "event"
           and str(r.get("name", "")).startswith("compile_artifact_")]
    for r in evs[-6:]:
        a = r.get("attrs", {})
        detail = " ".join(f"{k}={v}" for k, v in sorted(a.items()))
        kind = r["name"].removeprefix("compile_artifact_")
        lines.append(f"    @{r['ts_ms']:.0f}ms  {kind:<8} {detail}")
    by_region: dict[str, list] = {}
    for r in region_spans:
        by_region.setdefault(r.get("attrs", {}).get("region", "?"), []).append(r)
    for name, spans in sorted(by_region.items()):
        durs = sorted(s["dur_ms"] for s in spans)
        outcomes = sorted({s.get("attrs", {}).get("outcome", "?") for s in spans})
        lines.append(f"  region {name:<20} n={len(durs)}  "
                     f"mean={sum(durs) / len(durs):.1f}ms  max={durs[-1]:.1f}ms  "
                     f"[{','.join(outcomes)}]")
    if lazy_spans:
        durs = sorted(s["dur_ms"] for s in lazy_spans)
        lines.append(f"  lazy xla_compile         n={len(durs)}  "
                     f"mean={sum(durs) / len(durs):.1f}ms  max={durs[-1]:.1f}ms")
    return lines


def serving_lines(recs: list[dict], counters: dict[str, int]) -> list[str]:
    """Serving-engine section: serve.* traffic counters plus TTFT/TBOT
    percentiles from serve_retired events and prefill/decode span latency
    (thunder_tpu/serving/; docs/serving.md)."""
    serve_counters = {k: v for k, v in counters.items() if k.startswith("serve.")}
    retires = [r.get("attrs", {}) for r in recs
               if r.get("kind") == "event" and r.get("name") == "serve_retired"]
    if not serve_counters and not retires:
        return []
    lines = []
    for k, v in sorted(serve_counters.items()):
        lines.append(f"  {k.removeprefix('serve.'):<24} {v}")

    def pct(xs, q):
        return xs[min(len(xs) - 1, int(round(q * (len(xs) - 1))))]

    ttfts = sorted(a["ttft_ms"] for a in retires if "ttft_ms" in a)
    # one-token requests have NO between-token interval (the engine records
    # a 0.0 placeholder) — exclude them from the tbot population by n_new,
    # not by truthiness, so a real 0.0ms sample would still count
    tbots = sorted(a["tbot_ms"] for a in retires
                   if "tbot_ms" in a and a.get("n_new", 0) > 1)
    if ttfts:
        lines.append(f"  ttft_ms                  p50={pct(ttfts, 0.5):.2f}  "
                     f"p99={pct(ttfts, 0.99):.2f}  max={ttfts[-1]:.2f}")
    if tbots:
        lines.append(f"  tbot_ms                  p50={pct(tbots, 0.5):.2f}  "
                     f"p99={pct(tbots, 0.99):.2f}  max={tbots[-1]:.2f}")
    # per-lane breakdown (serve_retired carries lane= since the SLO-aware
    # scheduler): only shown when traffic actually spans more than one lane,
    # so single-lane runs keep the compact aggregate-only section
    lanes = sorted({a.get("lane") for a in retires if a.get("lane")})
    if len(lanes) > 1:
        for lane in lanes:
            sub = [a for a in retires if a.get("lane") == lane]
            lt = sorted(a["ttft_ms"] for a in sub if "ttft_ms" in a)
            lb = sorted(a["tbot_ms"] for a in sub
                        if "tbot_ms" in a and a.get("n_new", 0) > 1)
            parts = [f"n={len(sub)}"]
            if lt:
                parts.append(f"ttft p50={pct(lt, 0.5):.2f} p99={pct(lt, 0.99):.2f}")
            if lb:
                parts.append(f"tbot p50={pct(lb, 0.5):.2f} p99={pct(lb, 0.99):.2f}")
            lines.append(f"  lane {lane:<19} {'  '.join(parts)}")
    utils = [a["pool_utilization"] for a in retires + [
        r.get("attrs", {}) for r in recs
        if r.get("kind") == "event" and r.get("name") == "serve_prefills"]
        if "pool_utilization" in a]
    if utils:
        lines.append(f"  page_pool_utilization    peak={max(utils):.2%}")
    for name in ("serve_prefill", "serve_decode"):
        durs = sorted(r["dur_ms"] for r in recs
                      if r.get("kind") == "span" and r.get("name") == name)
        if durs:
            lines.append(f"  {name:<24} n={len(durs)}  p50={pct(durs, 0.5):.2f}ms  "
                         f"p95={pct(durs, 0.95):.2f}ms")
    return lines


def moe_lines(recs: list[dict], counters: dict[str, int]) -> list[str]:
    """MoE routing-health section: moe.* counters (steps, cumulative
    dropped tokens) plus the latest ``moe_stats`` event's per-expert load
    vector and router entropy (observability/metrics.py record_moe)."""
    moe_counters = {k: v for k, v in counters.items() if k.startswith("moe.")}
    stats = [r for r in recs
             if r.get("kind") == "event" and r.get("name") == "moe_stats"]
    if not moe_counters and not stats:
        return []
    lines = []
    for k, v in sorted(moe_counters.items()):
        lines.append(f"  {k.removeprefix('moe.'):<24} {v}")
    if stats:
        a = stats[-1].get("attrs") or {}
        load = a.get("expert_load") or []
        if load:
            peak = max(load)
            lines.append(f"  expert_load              "
                         f"[{' '.join(f'{v:.3f}' for v in load)}]  "
                         f"(max={peak:.3f}, balanced={1 / len(load):.3f})")
        if a.get("router_entropy") is not None:
            lines.append(f"  router_entropy           "
                         f"{a['router_entropy']:.3f} nats")
        if a.get("dropped_tokens") is not None:
            lines.append(f"  dropped_tokens (last)    {a['dropped_tokens']}")
    return lines


def checkpoint_lines(recs: list[dict], counters: dict[str, int]) -> list[str]:
    """Checkpoint/robustness section: save/restore traffic, per-host shard
    counts+bytes (distributed sharded saves), save latency, and the
    desync / guard-agreement events the distributed fault-tolerance layer
    emits (docs/robustness.md)."""
    ckpt_counters = {k: v for k, v in counters.items()
                     if k.startswith("checkpoint.") or k.startswith("desync.")}
    dist_guard = {k: v for k, v in counters.items()
                  if k.startswith("guard.dist_")}
    shard_evs = [r.get("attrs", {}) for r in recs
                 if r.get("kind") == "event" and r.get("name") == "checkpoint_shard"]
    desync_evs = [r for r in recs
                  if r.get("kind") == "event" and r.get("name") == "desync"]
    save_ms = sorted(r["attrs"]["ms"] for r in recs
                     if r.get("kind") == "event"
                     and r.get("name") == "checkpoint_save"
                     and (r.get("attrs") or {}).get("phase") == "done"
                     and "ms" in (r.get("attrs") or {}))
    if not ckpt_counters and not dist_guard and not shard_evs and not desync_evs:
        return []
    lines = []
    for k, v in sorted({**ckpt_counters, **dist_guard}.items()):
        lines.append(f"  {k:<28} {v}")
    if save_ms:
        n = len(save_ms)
        # nearest-rank lower median: [5, 500] must report p50=5, not 500 —
        # an operator triaging save blocking time reads this as "typical"
        lines.append(f"  ckpt_save_ms                 n={n}  "
                     f"p50={save_ms[(n - 1) // 2]:.1f}ms  max={save_ms[-1]:.1f}ms")
    by_host: dict = {}
    for a in shard_evs:
        h = a.get("host", "?")
        cnt, byts, blocks = by_host.get(h, (0, 0, 0))
        by_host[h] = (cnt + 1, byts + a.get("bytes", 0), blocks + a.get("blocks", 0))
    for h, (cnt, byts, blocks) in sorted(by_host.items(), key=lambda kv: str(kv[0])):
        lines.append(f"  host {h!s:<6} shards={cnt:<4} blocks={blocks:<5} "
                     f"bytes={byts}")
    for r in desync_evs[-6:]:
        a = r.get("attrs", {})
        detail = " ".join(f"{k}={v}" for k, v in sorted(a.items()) if k != "kind")
        lines.append(f"    @{r['ts_ms']:.0f}ms  DESYNC {a.get('kind', '?'):<12} {detail}")
    return lines


def slo_lines(recs: list[dict], counters: dict[str, int]) -> list[str]:
    """SLO section: breach counters plus the most recent reason-coded
    slo.breach / slo.recovered events (observability/slo.py)."""
    breach_counters = {k: v for k, v in counters.items()
                       if k.startswith("slo.breach.")}
    evs = [r for r in recs if r.get("kind") == "event"
           and r.get("name") in ("slo.breach", "slo.recovered")]
    if not breach_counters and not evs:
        return []
    lines = []
    for k, v in sorted(breach_counters.items()):
        lines.append(f"  {k.removeprefix('slo.breach.'):<24} x{v}")
    for r in evs[-8:]:
        a = r.get("attrs", {})
        kind = "BREACH" if r["name"] == "slo.breach" else "recovered"
        burn = f" burn={a['burn_rate']}x" if a.get("burn_rate") is not None else ""
        lines.append(f"    @{r['ts_ms']:.0f}ms  {kind:<10} {a.get('reason', '?'):<14} "
                     f"value={a.get('value')} target={a.get('target')}{burn} "
                     f"[{a.get('source', '?')}]")
    return lines


def fleet_lines(recs: list[dict], counters: dict[str, int]) -> list[str]:
    """Per-host fleet breakdown: step latency per process shard, straggler
    onset/recovery events (observability/fleet.py), and the fleet.* /
    trace.* counter families. Only rendered when the timeline carries
    multi-host signal (several pids, straggler events, or fleet counters)."""
    # trace.* here means request tracing (trace.requests / trace.spans) —
    # the specialization cache is ALSO named "trace", and its hit/miss/evict
    # outcomes already render in the cache table
    fleet_counters = {k: v for k, v in counters.items()
                      if (k.startswith("fleet.") or k.startswith("trace."))
                      and k.partition(".")[2] not in ("hit", "miss", "evict")}
    strag_evs = [r for r in recs if r.get("kind") == "event"
                 and r.get("name") in ("straggler", "straggler.recovered")]
    by_pid: dict = {}
    spikes_by_pid: dict = {}
    for r in recs:
        if r.get("kind") == "span" and r.get("name") in _STEP_SPANS:
            by_pid.setdefault(r.get("pid", 0), []).append(r["dur_ms"])
        elif r.get("kind") == "event" and r.get("name") == "step_spike":
            pid = r.get("pid", 0)
            spikes_by_pid[pid] = spikes_by_pid.get(pid, 0) + 1
    multi_host = len(by_pid) > 1
    if not fleet_counters and not strag_evs and not multi_host:
        return []
    lines = []
    for k, v in sorted(fleet_counters.items()):
        lines.append(f"  {k:<28} {v}")
    if multi_host:
        lines.append(f"  {'host':<12} {'steps':>6} {'p50':>9} {'p95':>9} "
                     f"{'max':>9} {'spikes':>7}")
        for pid, durs in sorted(by_pid.items(), key=lambda kv: str(kv[0])):
            durs.sort()
            n = len(durs)
            lines.append(
                f"  {pid!s:<12} {n:>6} {durs[n // 2]:>7.2f}ms "
                f"{durs[min(n - 1, int(n * 0.95))]:>7.2f}ms {durs[-1]:>7.2f}ms "
                f"{spikes_by_pid.get(pid, 0):>7}")
    for r in strag_evs[-8:]:
        a = r.get("attrs", {})
        kind = "STRAGGLER" if r["name"] == "straggler" else "recovered"
        ratio = f" ({a['ratio']}x fleet)" if a.get("ratio") is not None else ""
        lines.append(f"    @{r['ts_ms']:.0f}ms  {kind:<10} host={a.get('host', '?')}  "
                     f"median={a.get('median_ms', '?')}ms"
                     f"{ratio}  cause={a.get('cause', '-')}")
    return lines


# canonical request-lifecycle phase order (mirrors observability/tracing.py
# PHASES) — used to stabilize sorting when several trace events share one
# timestamp (e.g. admitted + prefill landing in the same millisecond)
_TRACE_PHASES = ("submitted", "prefix_lookup", "admitted", "prefill",
                 "prefill_chunk", "decode", "spec_verify", "preempted",
                 "resumed", "retired", "failed")


def trace_entries(recs: list[dict], request_id: str) -> tuple[str, list[dict]]:
    """Resolve `request_id` to its trace id, then collect that request's
    trace events — both its own and the shared per-step events (decode /
    spec_verify batches carry ``trace_ids=[...]`` for every participant).
    Returns (trace_id, entries sorted by time then phase order)."""
    trace_id = None
    for r in recs:
        if r.get("kind") == "event" and r.get("name") == "trace":
            a = r.get("attrs") or {}
            if str(a.get("request")) == str(request_id) and a.get("trace_id"):
                trace_id = a["trace_id"]
                break
    if trace_id is None:
        return "", []
    entries = []
    for r in recs:
        if r.get("kind") != "event" or r.get("name") != "trace":
            continue
        a = r.get("attrs") or {}
        if a.get("trace_id") == trace_id or trace_id in (a.get("trace_ids") or ()):
            entries.append(r)

    def order(r):
        phase = (r.get("attrs") or {}).get("phase", "")
        rank = _TRACE_PHASES.index(phase) if phase in _TRACE_PHASES else len(_TRACE_PHASES)
        return (r.get("ts_ms", 0.0), rank)

    entries.sort(key=order)
    return trace_id, entries


def render_trace(recs: list[dict], request_id: str) -> str:
    trace_id, entries = trace_entries(recs, request_id)
    if not entries:
        return (f"(no trace events for request {request_id!r} — was the "
                f"request submitted with observability enabled?)")
    t0 = entries[0].get("ts_ms", 0.0)
    out = [f"== trace {trace_id} (request {request_id}) =="]
    for r in entries:
        a = dict(r.get("attrs") or {})
        phase = a.pop("phase", "?")
        for k in ("trace_id", "trace_ids", "request"):
            a.pop(k, None)
        dur = a.pop("dur_ms", None)
        dur_s = f" {dur:>8.2f}ms" if isinstance(dur, (int, float)) else " " * 11
        detail = " ".join(f"{k}={v}" for k, v in a.items())
        out.append(f"  +{r.get('ts_ms', 0.0) - t0:>10.1f}ms  {phase:<14}"
                   f"{dur_s}  {detail}".rstrip())
    span_ms = entries[-1].get("ts_ms", 0.0) - t0
    phases = [(r.get("attrs") or {}).get("phase") for r in entries]
    out.append(f"  {len(entries)} event(s), {phases[0]} -> {phases[-1]}, "
               f"{span_ms:.1f}ms end to end")
    return "\n".join(out)


def chrome_trace_json(recs: list[dict], request_id: str) -> dict:
    """Chrome trace-event JSON (chrome://tracing / Perfetto) for one
    request: duration phases become complete ("X") events positioned at
    ``ts - dur`` (the emitter stamps events at phase END); instantaneous
    phases become thread-scoped instants ("i")."""
    trace_id, entries = trace_entries(recs, request_id)
    pids = {}
    evs = []
    for r in entries:
        a = dict(r.get("attrs") or {})
        phase = a.pop("phase", "?")
        for k in ("trace_id", "trace_ids", "request"):
            a.pop(k, None)
        dur = a.pop("dur_ms", None)
        pid = pids.setdefault(str(r.get("pid", 0)), len(pids))
        base = {"name": phase, "cat": "serving", "pid": pid,
                "tid": trace_id or str(request_id), "args": a}
        ts_us = r.get("ts_ms", 0.0) * 1e3
        if isinstance(dur, (int, float)) and dur > 0:
            evs.append({**base, "ph": "X", "ts": ts_us - dur * 1e3,
                        "dur": dur * 1e3})
        else:
            evs.append({**base, "ph": "i", "ts": ts_us, "s": "t"})
    return {"traceEvents": evs, "displayTimeUnit": "ms"}


def device_profiles(recs: list[dict]) -> list[dict]:
    return [r["attrs"]["profile"] for r in recs
            if r.get("kind") == "event" and r.get("name") == "device_profile"
            and isinstance(r.get("attrs", {}).get("profile"), dict)]


def render_perf(recs: list[dict]) -> str:
    """The `perf report` view: per-region device time, FLOPs, arithmetic
    intensity and roofline tags from recorded device_profile events, plus
    step/spike statistics."""
    profs = device_profiles(recs)
    out = []
    for p in profs:
        tot = p.get("total_device_us") or 0.0
        out.append(f"== device-time breakdown ({p.get('n_steps', '?')} step(s), "
                   f"{tot / 1e3:.3f} ms device) ==")
        frac = p.get("attributed_frac")
        head = (f"  compute={p.get('compute_us', 0) / 1e3:.3f}ms  "
                f"collective={p.get('collective_us', 0) / 1e3:.3f}ms  "
                f"transfer={p.get('transfer_us', 0) / 1e3:.3f}ms  "
                f"unattributed={p.get('unattributed_us', 0) / 1e3:.3f}ms")
        if frac is not None:
            head += f"  attributed={frac:.0%}"
        if p.get("mfu_measured") is not None:
            head += f"  mfu_measured={p['mfu_measured']:.3f}"
        out.append(head)
        if p.get("overlap_frac") is not None:
            out.append(
                f"  comms overlap: {p['overlap_frac']:.0%} hidden behind "
                f"compute  (overlapped={p.get('overlapped_comms_us', 0) / 1e3:.3f}ms"
                f"  exposed={p.get('exposed_comms_us', 0) / 1e3:.3f}ms)")
        out.append(f"  {'region':<28} {'time':>10} {'%':>6} {'calls':>6} "
                   f"{'category':<10} {'GFLOP':>8} {'AI':>7} {'roofline':<13} "
                   f"{'mfu':>6} {'overlap':>8}")
        regions = p.get("regions") or {}
        for name, r in sorted(regions.items(), key=lambda kv: -(kv[1].get("us") or 0)):
            us = r.get("us") or 0.0
            ai = r.get("intensity")
            mfu = r.get("mfu")
            ovf = r.get("overlap_frac")
            out.append(
                f"  {name:<28} {us / 1e3:>8.3f}ms "
                f"{100 * us / tot if tot else 0:>5.1f}% {r.get('count', 0):>6} "
                f"{r.get('category', ''):<10} {(r.get('flops') or 0) / 1e9:>8.2f} "
                f"{'-' if ai is None else f'{ai:.1f}':>7} {r.get('roofline', ''):<13} "
                f"{'-' if mfu is None else f'{mfu:.3f}':>6} "
                f"{'-' if ovf is None else f'{ovf:.0%}':>8}")
        out.append("")
    steps = step_stats(recs)
    if steps:
        out += ["== step latency (host-side) ==", *steps]
    spikes = spike_lines(recs)
    if spikes:
        out += ["", "== step spikes (flight recorder) ==", *spikes]
    if not out:
        return ("(no device_profile records — capture one with "
                "observability.profile_steps(...))")
    return "\n".join(out)


def _gb(n) -> str:
    return f"{(n or 0) / 2**30:.3f} GiB"


def mem_lines(recs: list[dict], counters: dict) -> list[str]:
    """The memory section: watermark high-water from ``mem_sample`` events,
    pressure transitions, estimate-vs-measured drift, OOM post-mortems
    (with their bundle paths), and the latest deep live-array census."""
    samples, pressure, drifts, ooms, census = [], [], [], [], []
    for r in recs:
        if r.get("kind") != "event":
            continue
        name = r.get("name")
        if name == "mem_sample":
            samples.append(r)
        elif name == "mem_pressure":
            pressure.append(r)
        elif name == "mem.estimate_drift":
            drifts.append(r)
        elif name == "oom":
            ooms.append(r)
        elif name == "mem_census":
            census.append(r)
    out = []
    if samples:
        last = samples[-1]["attrs"]
        peak = max((s["attrs"].get("peak_bytes_in_use") or 0) for s in samples)
        out.append(f"  peak bytes_in_use {_gb(peak)}  "
                   f"(last sample {_gb(last.get('bytes_in_use'))} at step "
                   f"{last.get('step')}, source={last.get('mem_source', '?')}, "
                   f"{len(samples)} watermark sample(s))")
    n_pressure = counters.get("mem.pressure", len(pressure))
    if n_pressure:
        a = pressure[-1]["attrs"] if pressure else {}
        util = a.get("utilization")
        out.append(f"  memory pressure transitions {n_pressure}"
                   + (f"  (last at {util:.0%} of bytes_limit, step "
                      f"{a.get('step')})" if util is not None else ""))
    for d in drifts[-3:]:
        a = d.get("attrs") or {}
        out.append(f"  estimate drift: measured "
                   f"{_gb(a.get('measured_peak_bytes'))} vs estimated "
                   f"{_gb(a.get('estimated_peak_bytes'))} "
                   f"(x{a.get('ratio', '?')}, {a.get('context') or a.get('source', '?')})")
    for o in ooms:
        a = o.get("attrs") or {}
        out.append(f"  OOM at step {a.get('step')} ({a.get('source', '?')}): "
                   f"{(a.get('error') or '')[:80]}")
        if a.get("bundle"):
            out.append(f"    forensic bundle: {a['bundle']}")
    if census:
        groups = (census[-1].get("attrs") or {}).get("groups") or []
        if groups:
            out.append("  live arrays (top by bytes, latest census):")
            for g in groups[:6]:
                out.append(f"    {str(g.get('shape')):<24} {g.get('dtype', ''):<10} "
                           f"x{g.get('count', 0):<5} {_gb(g.get('bytes'))}")
    return out


def render(recs: list[dict], top: int = 0) -> str:
    out = []
    tree = span_tree(recs)
    if top:
        tree = tree[:top]
    if tree:
        out += ["== pipeline spans ==", *tree]
    counters = final_counters(recs)
    caches = cache_table(counters)
    if caches:
        out += ["", "== cache traffic ==", *caches]
    rec = recompile_lines(recs, counters)
    if rec:
        out += ["", "== recompiles ==", *rec]
    comp = compile_lines(recs, counters)
    if comp:
        out += ["", "== compile ==", *comp]
    steps = step_stats(recs)
    if steps:
        out += ["", "== step latency (host-side) ==", *steps]
    spikes = spike_lines(recs)
    if spikes:
        out += ["", "== step spikes (flight recorder) ==", *spikes]
    host = host_overhead_stats(recs)
    if host:
        out += ["", "== host dispatch overhead ==", *host]
    serving = serving_lines(recs, counters)
    if serving:
        out += ["", "== serving ==", *serving]
    slo = slo_lines(recs, counters)
    if slo:
        out += ["", "== slo ==", *slo]
    moe = moe_lines(recs, counters)
    if moe:
        out += ["", "== moe ==", *moe]
    ckpt = checkpoint_lines(recs, counters)
    if ckpt:
        out += ["", "== checkpoint / robustness ==", *ckpt]
    fleet = fleet_lines(recs, counters)
    if fleet:
        out += ["", "== fleet ==", *fleet]
    mem = mem_lines(recs, counters)
    if mem:
        out += ["", "== memory ==", *mem]
    other = {k: v for k, v in counters.items()
             if not k.startswith("recompile.") and not k.startswith("serve.")
             and not k.startswith("slo.breach.") and not k.startswith("artifact.")
             and not k.startswith("compile.") and not k.startswith("checkpoint.")
             and not k.startswith("desync.") and not k.startswith("guard.dist_")
             and not k.startswith("fleet.") and not k.startswith("trace.")
             and not k.startswith("mem.") and not k.startswith("moe.")
             and k.partition(".")[2] not in ("hit", "miss", "evict")}
    if other:
        out += ["", "== counters =="]
        out += [f"  {k:<30} {v}" for k, v in sorted(other.items())]
    return "\n".join(out) if out else "(empty timeline)"


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    sub = argv[0] if argv and argv[0] in ("perf", "trace") else None
    if sub:
        argv = argv[1:]
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    if sub == "trace":
        ap.add_argument("request_id",
                        help="request id passed to ServingEngine.submit()")
        ap.add_argument("--chrome", metavar="OUT.json", default=None,
                        help="also write Chrome trace-event JSON "
                             "(load in chrome://tracing or Perfetto)")
    ap.add_argument("timeline", nargs="+",
                    help="JSONL shard(s) written by TT_OBS_FILE / observability.dump(); "
                         "several shards are merged by process")
    ap.add_argument("--top", type=int, default=0, help="show at most N span-tree lines")
    ns = ap.parse_args(argv)
    try:
        recs = load_many(ns.timeline)
    except OSError as e:
        print(f"error: cannot read timeline: {e}", file=sys.stderr)
        return 2
    if not recs:
        print(f"error: no parseable records in {', '.join(ns.timeline)} "
              f"(empty or entirely malformed timeline)", file=sys.stderr)
        return 2
    if sub == "trace":
        text = render_trace(recs, ns.request_id)
        print(text)
        if text.startswith("(no trace events"):
            return 1
        if ns.chrome:
            with open(ns.chrome, "w") as f:
                json.dump(chrome_trace_json(recs, ns.request_id), f)
            print(f"# wrote {ns.chrome}")
        return 0
    print(render_perf(recs) if sub == "perf" else render(recs, top=ns.top))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
