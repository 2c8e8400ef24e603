"""Pipeline metrics over the event bus: cache traffic, recompiles, fusions.

Recompile *reason codes* are the machine-readable vocabulary shared by the
jit drivers (thunder_tpu/__init__.py, frontend/compiled.py), the AOT step
cache (training.py, utils/aot_cache.py) and the CLI (tools/obs_summary.py):

  cache-miss                    first compile of this function/key
  shape-change                  entries exist but none matches the call's
                                input metadata (shape/dtype/mode flip)
  fallback-after-runtime-error  an AOT-deserialized executable raised at
                                run time; the retrace path took over
  stale-key                     an AOT entry exists for these inputs but
                                its model-code digest no longer matches

Counter naming: ``<cache>.<hit|miss|evict>`` for cache traffic (caches:
``trace`` — the per-function specialization cache, ``aot`` — the serialized
whole-step executable cache), ``recompile.<reason>`` for recompiles,
``fusion.regions`` / ``fusion.ops`` for fusion formation.

Thread-safety: the bus counters (``events.inc``) read-modify-write under
the bus lock, so every path through this module is already atomic under
concurrent inference threads. The per-function CompileStats counters
(common.py) were NOT — plain-int ``+=`` loses updates — and now use
``AtomicCounter`` below (tests/test_observability.py TestAtomicCounters).
"""
from __future__ import annotations

import threading

from . import events


class AtomicCounter:
    """An int-like counter whose ``+= n`` is atomic under concurrent
    threads.

    The bus's own counters (``events.inc``) already mutate under the bus
    lock, but the per-function CompileStats counters (cache_hits/misses/
    calls in common.py) were plain ints — ``cs.cache_hits += 1`` is a
    read-modify-write that loses updates when concurrent inference threads
    share one compiled function. This type keeps those call sites
    unchanged: ``+=`` routes through ``__iadd__``, which mutates in place
    under a lock and returns self (the attribute re-assignment rebinds the
    same object). Reads compare/convert like an int."""

    __slots__ = ("_value", "_lock")

    def __init__(self, value: int = 0):
        self._value = int(value)
        self._lock = threading.Lock()

    def __iadd__(self, other: int) -> "AtomicCounter":
        with self._lock:
            self._value += int(other)
        return self

    @property
    def value(self) -> int:
        return self._value

    def __int__(self) -> int:
        return self._value

    def __index__(self) -> int:
        return self._value

    def __bool__(self) -> bool:
        return bool(self._value)

    def __add__(self, other):
        return self._value + int(other)

    __radd__ = __add__

    def __sub__(self, other):
        return self._value - int(other)

    def __rsub__(self, other):
        return int(other) - self._value

    def __eq__(self, other):
        return self._value == int(other) if isinstance(other, (int, AtomicCounter)) else NotImplemented

    def __ne__(self, other):
        eq = self.__eq__(other)
        return NotImplemented if eq is NotImplemented else not eq

    def __lt__(self, other):
        return self._value < int(other)

    def __le__(self, other):
        return self._value <= int(other)

    def __gt__(self, other):
        return self._value > int(other)

    def __ge__(self, other):
        return self._value >= int(other)

    def __hash__(self):
        return hash(self._value)

    def __repr__(self) -> str:
        return repr(self._value)

REASON_CACHE_MISS = "cache-miss"
REASON_SHAPE_CHANGE = "shape-change"
REASON_FALLBACK = "fallback-after-runtime-error"
REASON_STALE_KEY = "stale-key"

REASON_CODES = (REASON_CACHE_MISS, REASON_SHAPE_CHANGE, REASON_FALLBACK, REASON_STALE_KEY)

# Robustness-layer intervention reason codes (the guard/checkpoint analog of
# the recompile vocabulary above; consumed by the flight recorder's spike
# triage and tools/obs_summary.py):
#
#   nonfinite-skip       a NaN/Inf step's update was gated off in-program
#   nonfinite-raise      the guard policy (or an exhausted skip budget) raised
#   rollback             N consecutive bad steps restored the last checkpoint
#   transient-retry      a transient runtime error was retried with backoff
#   transient-exhausted  the retry budget ran out; the error propagated
#   preempt              SIGTERM drained into a final checkpoint
#   preempt-escalated    a SECOND SIGTERM during the drain window forced an
#                        immediate blocking save (no courtesy waits)
#
# Distributed runs double-book interventions under guard.dist_<reason>
# (record_dist_verdict) — the lockstep-agreement counters — and add the
# desync.<kind> family (record_desync) for cross-host divergence caught
# before a hung collective.
INTERVENTION_CODES = ("nonfinite-skip", "nonfinite-raise", "rollback",
                      "transient-retry", "transient-exhausted", "preempt",
                      "preempt-escalated")


def record_cache(cache: str, outcome: str, **attrs) -> None:
    """One cache lookup outcome: outcome in {"hit", "miss", "evict"}."""
    if not events.enabled():
        return
    events.inc(f"{cache}.{outcome}", **attrs)


def record_artifact(outcome: str, **attrs) -> None:
    """Compile-artifact-store traffic (thunder_tpu/compile_service/store.py):
    bumps ``artifact.<outcome>`` (outcome in {"hit", "miss", "evict",
    "publish"}) and records a ``compile_artifact_<outcome>`` timeline event.
    ``compile_artifact_hit`` is the counter-asserted signal that a fresh
    process served its first step from the store with zero trace/lowering
    work (docs/compilation.md)."""
    if not events.enabled():
        return
    events.inc(f"artifact.{outcome}")
    events.event(f"compile_artifact_{outcome}", **attrs)


def record_recompile(reason: str, **attrs) -> None:
    """A compile that a cache could not serve, tagged with why."""
    if not events.enabled():
        return
    events.inc(f"recompile.{reason}")
    events.event("recompile", reason=reason, **attrs)


def record_intervention(reason: str, **attrs) -> None:
    """A robustness-layer intervention (guard skip/raise/rollback, transient
    retry, preemption drain), reason-coded like recompiles so spike triage
    and the CLI can name it."""
    if not events.enabled():
        return
    events.inc(f"guard.{reason}")
    events.event("guard", reason=reason, **attrs)


def record_dist_verdict(reason: str, **attrs) -> None:
    """An intervention taken on a psum'd ALL-HOST guard verdict. Emits the
    regular ``guard.<reason>`` vocabulary (every host acts, so every host
    counts) PLUS ``guard.dist_<reason>``: diffing per-host counter dumps on
    the dist_* keys is the lockstep-agreement assertion the multi-process
    harness pins (a host missing a dist_ count diverged from the fleet)."""
    if not events.enabled():
        return
    events.inc(f"guard.{reason}")
    events.inc(f"guard.dist_{reason}")
    events.event("guard", reason=reason, distributed=True, **attrs)


def record_desync(kind: str, **attrs) -> None:
    """A cross-host desynchronization detected (step counter or program key
    disagreement, or an unresponsive peer) BEFORE it could hang a
    collective. Counter ``desync.<kind>`` + one ``desync`` timeline event
    carrying the per-host values; ``robustness/distributed.py`` raises
    ``DesyncError`` right after recording this."""
    if not events.enabled():
        return
    events.inc(f"desync.{kind}")
    events.event("desync", kind=kind, **attrs)


def record_ckpt_shard(host: int, n_blocks: int, nbytes: int, **attrs) -> None:
    """One host's checkpoint shard written (distributed sharded save).
    Counters ``checkpoint.shard_written`` / ``checkpoint.shard_bytes`` plus
    a per-shard ``checkpoint_shard`` event — tools/obs_summary.py renders
    these as the per-host shard table."""
    if not events.enabled():
        return
    events.inc("checkpoint.shard_written")
    events.inc("checkpoint.shard_bytes", int(nbytes))
    events.event("checkpoint_shard", host=int(host), blocks=int(n_blocks),
                 bytes=int(nbytes), **attrs)


def record_slo_breach(reason: str, **attrs) -> None:
    """An SLO target crossed into violation (slo.py; reason in
    slo.BREACH_CODES). Counter ``slo.breach.<reason>`` + one reason-coded
    ``slo.breach`` timeline event carrying value/target/burn_rate."""
    if not events.enabled():
        return
    events.inc(f"slo.breach.{reason}")
    events.event("slo.breach", reason=reason, **attrs)


def record_straggler(host: int, cause: str, **attrs) -> None:
    """A host crossed the fleet straggler threshold (fleet.py; transition-
    deduped by the detector, so one onset = one event). Counter
    ``fleet.straggler`` + a reason-coded ``straggler`` timeline event
    carrying host/median_ms/fleet_median_ms/ratio — the cause code comes
    from that host's flight-recorder triage vocabulary (recompile /
    data-stall / checkpoint-save / host-overhead / guard-intervention /
    unknown)."""
    if not events.enabled():
        return
    events.inc("fleet.straggler")
    events.event("straggler", host=int(host), cause=cause, **attrs)


def record_serve(outcome: str, delta: int = 1, event: bool = False, **attrs) -> None:
    """Serving-engine traffic: bumps ``serve.<outcome>`` and, for the
    low-rate lifecycle outcomes (admission/retirement), records a
    ``serve_<outcome>`` timeline event carrying the request tags
    (request id, ttft_ms/tbot_ms, pool_utilization). High-rate outcomes
    (decode_steps, tokens) stay counter-only so a long-running engine
    doesn't flood the ring buffer.

    Fleet-serving vocabulary (docs/serving.md; all zero-work when
    observability is disabled, like every outcome here): ``prefix_hits`` /
    ``prefix_tokens_saved`` (copy-on-write prefix cache), ``spec_proposed``
    / ``spec_accepted`` (speculative draft tokens offered / verified —
    their ratio is the accept rate), and the
    lane-scheduling events ``preempted`` / ``resumed``. ``serve_retired``
    events carry ``lane=`` so obs_summary.py can split latency percentiles
    per lane."""
    if not events.enabled():
        return
    events.inc(f"serve.{outcome}", delta)
    if event:
        events.event(f"serve_{outcome}", **attrs)


def record_moe(expert_load, dropped_tokens, router_entropy, **attrs) -> None:
    """Routing health for one MoE step (models/moe.py buffers or the
    EP stats dict from parallel/expert_parallel.py): counter ``moe.steps``
    plus ``moe.dropped_tokens`` (cumulative drops — the counter-asserted
    signal that capacity routing is shedding load), per-expert last-value
    gauges ``moe.expert_load.e<i>`` with the max under
    ``moe.expert_load_max`` (1/E = perfectly balanced), and gauge
    ``moe.router_entropy`` (nats; ln E = uniform router). One ``moe_stats``
    timeline event carries the full load vector. Zero-work disabled."""
    if not events.enabled():
        return
    from . import telemetry

    load = [float(v) for v in expert_load]
    dropped = int(dropped_tokens)
    entropy = float(router_entropy)
    events.inc("moe.steps")
    if dropped:
        events.inc("moe.dropped_tokens", dropped)
    for i, v in enumerate(load):
        telemetry.set_gauge(f"moe.expert_load.e{i}", v)
    telemetry.set_gauge("moe.expert_load_max", max(load) if load else 0.0)
    telemetry.set_gauge("moe.router_entropy", entropy)
    events.event("moe_stats", expert_load=load, dropped_tokens=dropped,
                 router_entropy=entropy, **attrs)


def record_fusion(executor: str, n_regions: int, n_ops: int, **attrs) -> None:
    """Fusion-pass outcome for one executor over one trace."""
    if not events.enabled():
        return
    events.inc("fusion.regions", n_regions, executor=executor)
    events.inc("fusion.ops", n_ops, executor=executor)
    events.event("fusion_pass", executor=executor, regions=n_regions, ops=n_ops, **attrs)


def record_executable_size(cache: str, nbytes: int, **attrs) -> None:
    """Serialized-executable byte size (AOT save / load)."""
    if not events.enabled():
        return
    events.event("executable_bytes", cache=cache, bytes=int(nbytes), **attrs)


def cache_stats() -> dict[str, dict[str, int]]:
    """{"trace": {"hit": 3, "miss": 1}, "aot": {...}} from the live counters."""
    out: dict[str, dict[str, int]] = {}
    for name, v in events.counters().items():
        cache, _, outcome = name.partition(".")
        if outcome in ("hit", "miss", "evict"):
            out.setdefault(cache, {})[outcome] = v
    return out
