"""Whole-step training compilation: fwd + bwd + optimizer in ONE XLA program.

The reference composes thunder-compiled fwd/bwd with torch autograd and a
separate optimizer step, then optionally wraps regions in CUDA graphs
(thunder/transforms/cudagraph.py:229) to kill dispatch overhead. On TPU the
idiomatic equivalent is stronger: the generated forward and backward callables
are pure-jax, so the full step — prologue-validated forward, backward,
optimizer update — is traced into a single ``jax.jit`` program with buffer
donation on params/optimizer state. XLA then schedules the whole step with
one dispatch and no host round-trips."""
from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from .nn.module import Module, ThunderModule, structure_epoch
from .observability import events as _obs
from .observability import flight_recorder as _obs_flight
from .observability import memory_watch as _obs_mem
from .observability import metrics as _obs_metrics
from .observability import runtime as _obs_runtime
from .observability import telemetry as _obs_tel
from .optim import global_norm as _global_norm
from .robustness import faults as _rb_faults


def _stable_val(v, depth: int = 0) -> str:
    """Deterministic string for a config value: simple types repr directly,
    containers recurse, other objects render as type + their own stable
    attrs (NEVER the default repr — it embeds addresses and would make
    cache keys miss every process; silently dropping attrs is worse: two
    semantically different configs would collide on the same key)."""
    if isinstance(v, (int, float, bool, str, bytes, type(None))):
        return repr(v)
    if isinstance(v, (tuple, list)):
        return "[" + ",".join(_stable_val(e, depth + 1) for e in v) + "]"
    if isinstance(v, dict):
        return "{" + ",".join(f"{k!r}:{_stable_val(val, depth + 1)}"
                              for k, val in sorted(v.items(), key=lambda kv: str(kv[0]))) + "}"
    if depth >= 3:
        return f"<{type(v).__name__}>"
    try:
        attrs = vars(v)
    except TypeError:
        # dtype-like singletons print stably (e.g. "dtypes.bfloat16")
        return f"{type(v).__name__}:{v!s}"
    return (f"{type(v).__name__}(" +
            ",".join(f"{k}={_stable_val(val, depth + 1)}"
                     for k, val in sorted(attrs.items())) + ")")


def _safe_repr(obj) -> str:
    """Deterministic config repr for cache keys (see _stable_val)."""
    return _stable_val(obj)


# What a stale or mismatched AOT-deserialized executable raises: argument-spec
# mismatches surface as TypeError/ValueError from the jax Compiled call layer,
# ABI/runtime mismatches as JaxRuntimeError. Anything else (a genuine bug)
# must propagate, not silently retrace.
_AOT_FALLBACK_ERRORS = (TypeError, ValueError, jax.errors.JaxRuntimeError)

# shared reusable no-op span for disabled-observability hot paths
_NULL_SPAN = contextlib.nullcontext()


class _CompiledWithFallback:
    """A serialized-executable step that transparently falls back to the
    retrace path (the jax.jit fn) if inputs stop matching the compiled
    shapes — AOT warm starts must never change semantics. The fallback is
    never silent: it warns and emits a reason-coded recompile event, since
    a persistently-failing executable would otherwise mask every runtime
    error as a recompile."""

    def __init__(self, compiled, jit_fn_factory):
        self._compiled = compiled
        self._factory = jit_fn_factory
        self._jit_fn = None

    def __call__(self, *args):
        if self._compiled is not None:
            try:
                return self._compiled(*args)
            except _AOT_FALLBACK_ERRORS as e:
                import warnings

                self._compiled = None
                warnings.warn(
                    f"AOT-cached executable failed at run time "
                    f"({type(e).__name__}: {e}); falling back to the retrace "
                    f"path. Delete the artifact store entry if this "
                    f"persists.", stacklevel=2)
                _obs_metrics.record_recompile(
                    _obs_metrics.REASON_FALLBACK,
                    error=f"{type(e).__name__}: {e}"[:300])
        if self._jit_fn is None:
            self._jit_fn = self._factory()
        return self._jit_fn(*args)


class TrainStep:
    """step(*batch) -> loss; updates module parameters in place.

    loss_module: a Module whose forward(*batch) returns a scalar loss.
    """

    def __init__(self, loss_module, optimizer, *, donate: bool = True, mesh_plan=None,
                 guard=None, slo=None, buckets=None, bucket_pad=None,
                 bucket_axis: int = 1):
        from . import jit as _jit

        if isinstance(loss_module, Module):
            loss_module = _jit(loss_module)
        if not isinstance(loss_module, ThunderModule):
            raise TypeError("TrainStep expects a Module or ThunderModule computing a scalar loss")
        self.tmodule = loss_module
        self.optimizer = optimizer
        self.donate = donate
        self.mesh_plan = mesh_plan  # set by parallel transforms for sharded steps
        # robustness layer: a StepGuard changes the traced program (finite
        # gate + grad-norm metric), so it is fixed at construction; the
        # CheckpointManager attaches itself via manager.attach(step)
        self._guard = guard
        # live telemetry: an SLOPolicy (observability/slo.py) gets a
        # sliding-window monitor over step wall time and tokens/s (via
        # policy.tokens_per_step); breaches land on the bus reason-coded.
        # Without one the per-step cost is a single `is None` test.
        self.slo_monitor = None
        if slo is not None:
            from .observability.slo import SLOMonitor

            if slo.min_tokens_per_s is not None and not slo.tokens_per_step:
                # a training step has no per-request token count; without
                # tokens_per_step the throughput target would silently never
                # be evaluated — the operator would believe it enforced
                raise ValueError(
                    "SLOPolicy(min_tokens_per_s=...) on a TrainStep needs "
                    "tokens_per_step=<batch tokens per step> to compute "
                    "throughput")
            self.slo_monitor = SLOMonitor(slo, source="training")
        # bucketed lowering (compile_service/buckets.py): with a BucketLadder
        # attached, batch args pad along `bucket_axis` to the next rung
        # before dispatch, so every length in a bucket shares ONE compiled
        # (and one stored) artifact — the trainer-side collapse of the
        # serving engine's prompt buckets. bucket_pad maps positional index
        # (or kwarg name) -> fill value; causal-LM targets use -100 so
        # ltorch.cross_entropy masks padded positions out of loss AND grads.
        self.buckets = buckets
        self.bucket_pad = dict(bucket_pad or {})
        self.bucket_axis = bucket_axis
        self._jitted: Optional[Callable] = None
        self.opt_state = None
        self._step_count = 0
        # steady-state dispatch fast path: the param split (an O(model) tree
        # walk + requires_grad filter) is cached under the module structure
        # epoch; _split_walks counts full walks for regression tests
        self._split_cache = None
        self._split_walks = 0
        self._mode_epoch = None
        # built programs are mode-specific (train/eval flips change the traced
        # program — BatchNorm/Dropout branches — without changing any input
        # metadata); key the whole compiled-program set on the module-mode
        # tuple so a flip selects/rebuilds instead of silently running stale
        self._mode_cache: dict = {}
        self._active_mode = self._mode_key()
        # the whole-step executable is one observability.op_scopes() reads (a weak
        # reference: nothing is asked of the step until a profile is read)
        from .observability import profiler as _obs_profiler

        _obs_profiler.register_executable(self, TrainStep.compiled)

    # every compiled artifact + trace-derived metadata that depends on the
    # module's train/eval mode (the FSDP param gather is shape-only and is
    # deliberately NOT mode-keyed)
    _MODE_STATE_ATTRS = (
        "_jitted", "_vag", "_effect_keys", "_micro_jitted", "_jitted_with_acc_fn",
        "_vag_nosync", "_micro_dist_jitted", "_fold_dist_jitted", "_vag_full",
        "_micro_fsdp_jitted", "_fold_fsdp_jitted",
    )

    def _mode_key(self):
        extra = getattr(self.tmodule._cfn._cd.fn, "__cache_extra__", None)
        return extra() if extra is not None else None

    def _sync_mode(self):
        # train()/eval() (and any structural mutation) bump the module
        # structure epoch, so an unchanged epoch proves the mode tuple is
        # unchanged — steady state skips the O(model) mode-tuple walk
        epoch = structure_epoch()
        if epoch == self._mode_epoch:
            return
        key = self._mode_key()
        if key == self._active_mode:
            self._mode_epoch = epoch
            return
        # consume the epoch only AFTER the swap succeeds: if the error below
        # raises, the next call must re-check and raise again rather than
        # early-return and silently run the stale-mode program
        if self._grad_acc is not None:
            raise RuntimeError(
                "module train/eval mode changed in the middle of a no_sync "
                "gradient-accumulation window; finish the window (a syncing "
                "step) before flipping the mode")
        self._mode_cache[self._active_mode] = {
            a: getattr(self, a, None) for a in self._MODE_STATE_ATTRS}
        stash = self._mode_cache.get(key) or {a: None for a in self._MODE_STATE_ATTRS}
        for a, v in stash.items():
            setattr(self, a, v)
        self._active_mode = key
        self._mode_epoch = epoch

    def _make_vag(self, *, sync_loss: bool = True):
        """Build a ThunderValueAndGrad over the (optionally distributed)
        traced step. sync_loss=False skips the cross-replica loss all-reduce,
        so gradients stay per-replica partial — the no_sync program variant."""
        from .transforms.autodiff import ThunderValueAndGrad

        plan = getattr(self.tmodule, "_dist_plan", None)
        inner = self.tmodule._cfn._cd.fn

        if plan is None:
            traced = inner
        else:
            from .ops import ltorch
            from .parallel import prims as dist_prims
            from .parallel.transforms import apply_param_collectives

            def traced(params: dict, args: tuple, kwargs: dict):
                import contextlib

                from .parallel.context_parallel import seq_parallel_tracing

                seq_axes = tuple(getattr(plan, "seq_axes", ()))
                cp_ctx = (
                    seq_parallel_tracing(seq_axes[0], plan.world_size(seq_axes[0]))
                    if seq_axes else contextlib.nullcontext()
                )
                full_params = apply_param_collectives(params, plan)
                with cp_ctx:
                    local_loss = inner(full_params, args, kwargs)
                if sync_loss and plan.loss_axes:
                    s = dist_prims.all_reduce(local_loss, plan.loss_axes)
                    return ltorch.div(s, float(plan.loss_world_size))
                return local_loss

            traced.__name__ = f"dist_{getattr(inner, '__name__', 'step')}"

        # Frozen (requires_grad=False) params ride as a separate non-donated,
        # non-differentiated arg so LoRA/quantized base weights stay untouched.
        def traced_split(tparams: dict, frozen: dict, args: tuple, kwargs: dict):
            return traced({**frozen, **tparams}, args, kwargs)

        traced_split.__name__ = getattr(traced, "__name__", "step")

        # argnums=0: the trainable params dict is arg 0 of the traced wrapper;
        # inside the jitted step params are raw arrays, so positional marking
        # is required. donated_argnums mirrors the jax.jit donation of the
        # whole step (params donated when self.donate) so the trace carries
        # the annotation the alias analysis verifies under TT_CHECK_TRACES
        vag = ThunderValueAndGrad(traced_split, argnums=0,
                                  transforms=self.tmodule._cfn._transforms,
                                  donated_argnums=(0,) if self.donate else None,
                                  check_traces=getattr(self.tmodule._cfn,
                                                       "_check_traces", False))
        vag._effects_consumer_attached = True  # TrainStep consumes pending effects
        return vag

    def _build(self, batch_args, batch_kwargs):
        plan = getattr(self.tmodule, "_dist_plan", None)
        optimizer = self.optimizer
        guard = self._guard
        if guard is not None and plan is not None:
            # host-side policy decisions must come from an ALL-HOST verdict
            # (see the psum in raw_step below); mark the guard so after_step
            # records the distributed agreement counters
            guard.mark_distributed()
        check_gnorm = guard is not None and guard.policy.check_grad_norm
        vag = self._make_vag(sync_loss=True)
        self._vag = vag

        train_step = self

        def raw_step(tparam_arrays: dict, frozen_arrays: dict, opt_state, args, kwargs):
            # named phases: HLO traced under these scopes carries the phase
            # name in its op metadata, so device profiles of the ONE fused
            # step program can still attribute time to fwd+bwd vs the
            # optimizer (the registered fusion regions nest inside tt_fwd_bwd)
            with _obs_runtime.fusion_scope("tt_fwd_bwd"):
                loss, grads = vag(tparam_arrays, frozen_arrays, args, kwargs)
            param_grads = grads[0][0]
            with _obs_runtime.fusion_scope("tt_optimizer"):
                new_params, new_state = optimizer.update(tparam_arrays, param_grads, opt_state)
            gmetrics = None
            if guard is not None:
                # in-program health gate: a non-finite loss/grad-norm step
                # must leave params AND optimizer state untouched. This has
                # to happen inside the program — under buffer donation the
                # old arrays no longer exist anywhere the host could reach
                # by the time it observes the loss.
                if check_gnorm:
                    gnorm = (_dist_global_norm(param_grads, plan)
                             if plan is not None else _global_norm(param_grads))
                else:
                    gnorm = jnp.zeros((), jnp.float32)
                finite = jnp.isfinite(loss)
                if check_gnorm:
                    finite = jnp.logical_and(finite, jnp.isfinite(gnorm))
                if plan is not None:
                    # distributed verdict — "one psum away" (ROADMAP #1):
                    # a NaN in ANY shard (one host's batch, one param shard's
                    # grads) must gate the update on EVERY device, or the
                    # replicas diverge and every later step is garbage. One
                    # psum of the local badness over ALL mesh axes turns the
                    # local flag into the all-host agreement.
                    axes = tuple(plan.mesh.axis_names)
                    axes = axes if len(axes) > 1 else axes[0]
                    bad = jax.lax.psum(
                        jnp.where(finite, 0, 1).astype(jnp.int32), axes)
                    finite = bad == 0
                new_params = {k: jnp.where(finite, v, tparam_arrays[k])
                              for k, v in new_params.items()}
                new_state = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(finite, n, o), new_state, opt_state)
                gmetrics = (finite, gnorm)
            pending = vag.consume_pending_effects()
            if pending is not None:
                # epilogue values (buffer mutations) ride out as jit outputs;
                # __call__ replays them onto the module after the step
                train_step._effect_keys = pending[0]
                effects = pending[1]
            else:
                train_step._effect_keys = None
                effects = ()
            if guard is not None:
                return loss, new_params, new_state, effects, gmetrics
            return loss, new_params, new_state, effects

        # attribution hierarchy for device profiles: the whole-step program
        # is named (its HLO module becomes jit_tt_train_step — the join
        # that works on backends whose per-op events drop scope metadata),
        # and the phase scopes above are registered one level finer so
        # optimizer/collective time that no fusion region claims still has
        # a bucket. Fusion regions themselves register at level 0.
        from .observability import profiler as _obs_profiler

        raw_step.__name__ = "tt_train_step"
        _obs_profiler.register_region("tt_fwd_bwd", executor="trainstep", level=1)
        _obs_profiler.register_region("tt_optimizer", executor="trainstep", level=1)
        _obs_profiler.register_region("tt_train_step", executor="trainstep", level=2)

        donate = (0, 2) if self.donate else ()
        if plan is None:
            self._jitted = jax.jit(raw_step, donate_argnums=donate)
        else:
            def raw_step_dist(*a, **kw):
                out = raw_step(*a, **kw)
                if out[3]:
                    raise NotImplementedError(
                        "buffer mutations (e.g. BatchNorm running stats) inside a "
                        "distributed TrainStep are not supported yet — stats would "
                        "need a cross-replica mean; freeze the buffers (module.eval()) "
                        "or train without a mesh plan")
                return out

            self._jitted = _shard_mapped_step(raw_step_dist, plan, self.tmodule, self.opt_state,
                                              batch_args, batch_kwargs, donate,
                                              guarded=guard is not None)

    # -- AOT executable cache (utils/aot_cache.py): warm process start
    # deserializes the compiled whole-step program — no trace, no lowering,
    # no XLA compile. Single-chip effect-free steps only (distributed plans
    # go through shard_map; buffer-mutating steps carry module references).

    def _aot_key(self, tparam_arrays, frozen_arrays, args, kwargs) -> str:
        from .utils import aot_cache

        extra = "|".join([
            _safe_repr(self.optimizer),
            repr(self._active_mode),
            repr(self.donate),
            # a guard changes the traced program (finite gate + metric
            # outputs): a guarded and an unguarded step must never share an
            # AOT entry
            self._guard.program_key() if self._guard is not None else "noguard",
            # a bucketed step's artifact serves a LENGTH RANGE: the ladder
            # identity keys it so a different ladder (different rungs, so
            # different padded shapes could coincide) never shares an entry
            self.buckets.key_fields() if self.buckets is not None else "nobuckets",
            # overlap compiler options (parallel/overlap.py) change the
            # compiled executable without changing any input metadata: a
            # config flip must MISS the cache, never reuse a non-overlapped
            # program under an overlap-requested step (or vice versa)
            getattr(self, "_overlap_key", "nooverlap"),
            "|".join(_safe_repr(t) for t in getattr(self.tmodule._cfn, "_transforms", ())),
        ])
        inputs = (tparam_arrays, frozen_arrays, self.opt_state, args, kwargs)
        return aot_cache.step_key(inputs=inputs, extra=extra)

    def _model_digest(self) -> str:
        """Digest of the model's computation (module tree + forward sources):
        editing a forward must invalidate AOT warm starts even though the
        input shape/dtype spec — the base key — is unchanged."""
        from .utils import aot_cache

        if self._model_digest_cached is None:
            self._model_digest_cached = aot_cache.module_digest(self.tmodule.module)
        return self._model_digest_cached

    _model_digest_cached = None

    def _try_aot(self, tparam_arrays, frozen_arrays, args, kwargs) -> bool:
        from .utils import aot_cache

        if not aot_cache.enabled() or getattr(self.tmodule, "_dist_plan", None) is not None:
            return False
        base = self._aot_key(tparam_arrays, frozen_arrays, args, kwargs)
        loaded, outcome = aot_cache.load_keyed(base, self._model_digest())
        if outcome == "stale":
            # an executable for these exact inputs exists but the model code
            # changed underneath it: the cold trace that follows is forced
            _obs_metrics.record_recompile(_obs_metrics.REASON_STALE_KEY,
                                          key=base[:12])
        if loaded is None:
            return False
        train_step = self

        def rebuild():
            train_step._jitted = None
            train_step._build(args, kwargs)
            return train_step._jitted

        self._effect_keys = None
        self._jitted = _CompiledWithFallback(loaded, rebuild)
        return True

    def _maybe_save_aot(self, tparam_arrays, frozen_arrays, args, kwargs) -> None:
        from .utils import aot_cache

        if not aot_cache.enabled() or getattr(self.tmodule, "_dist_plan", None) is not None:
            return
        jit_fn = self._jitted
        lowered = jit_fn.lower(tparam_arrays, frozen_arrays, self.opt_state, args, kwargs)
        if getattr(self, "_effect_keys", None) is not None:
            return  # buffer-mutation epilogues carry module refs: not cacheable
        # this is the step's one compile: the compiled program is used
        # directly (the AOT lower/compile does not populate jax.jit's dispatch
        # cache, so going back to jit_fn would compile the whole step again),
        # whether or not the store then takes it
        compiled = lowered.compile()
        self._jitted = _CompiledWithFallback(compiled, lambda: jit_fn)
        if not aot_cache.save_keyed(self._aot_key(tparam_arrays, frozen_arrays, args, kwargs),
                                    self._model_digest(), compiled):
            import warnings

            _obs_metrics.record_cache("aot", "save_failed")
            warnings.warn("the whole-step executable was not published to the "
                          "artifact store (serialization or the store's "
                          "directory failed); the next process compiles it again",
                          stacklevel=3)

    def _bucketize(self, args, kwargs):
        """Pad batch leaves to the attached BucketLadder's next rung (no-op
        without a ladder, zero copies when lengths already sit on a rung).
        Every length in a bucket then dispatches through the SAME cache key
        — steady-state recompiles across a (batch, seq) sweep stay at zero,
        and the stored whole-step artifact serves the whole range."""
        if self.buckets is None:
            return args, kwargs
        from .compile_service.buckets import pad_to_bucket

        for a in args:
            shape = getattr(a, "shape", None)
            if shape is not None and len(shape) > self.bucket_axis:
                # ladder traffic stats (MRU order, per-rung hits) — the
                # same bookkeeping the serving engine records per prefill
                self.buckets.touch(int(shape[self.bucket_axis]))
                break
        args, kwargs = pad_to_bucket(args, kwargs, self.buckets,
                                     axis=self.bucket_axis,
                                     pad_values=self.bucket_pad)
        return args, kwargs

    def _split_params(self):
        self._split_walks += 1
        params = self.tmodule.get_parameters()
        trainable = {k: p for k, p in params.items() if getattr(p, "requires_grad", True)}
        frozen = {k: p for k, p in params.items() if k not in trainable}
        # buffers (running stats etc.) ride as frozen inputs so they are not
        # baked into the program as constants
        getb = getattr(self.tmodule, "get_buffers", None)
        if callable(getb):
            frozen.update(getb())
        return trainable, frozen

    def _split_arrays(self):
        """(tparam_arrays, frozen_arrays, trainable_pairs) with the split
        STRUCTURE cached under the module structure epoch. Steady-state steps
        do no module-tree walk and no requires_grad filtering — only direct
        ``.data`` reads off cached Parameter references (params/buffer values
        may change between steps; the key sets and grad partition cannot
        without bumping the epoch). trainable_pairs is the write-back list
        for ``new_params``."""
        epoch = structure_epoch()
        cache = self._split_cache
        if cache is None or cache[0] != epoch:
            params = self.tmodule.get_parameters()
            self._split_walks += 1
            t_pairs = tuple((k, p) for k, p in params.items()
                            if getattr(p, "requires_grad", True))
            tset = {k for k, _ in t_pairs}
            f_pairs = tuple((k, p) for k, p in params.items() if k not in tset)
            # buffers are re-read from their owning module each step: effect
            # replay rebinds _buffers[name] to a NEW array, so caching the
            # value (rather than the owner+name slot) would serve stale stats
            b_triples = ()
            if callable(getattr(self.tmodule, "get_buffers", None)):
                b_triples = tuple(self.tmodule.module.named_buffer_slots())
            cache = self._split_cache = (epoch, t_pairs, f_pairs, b_triples)
        _, t_pairs, f_pairs, b_triples = cache
        tparam_arrays = {k: p.data for k, p in t_pairs}
        frozen_arrays = {k: getattr(p, "data", p) for k, p in f_pairs}
        for k, m, bn in b_triples:
            frozen_arrays[k] = m._buffers[bn]
        return tparam_arrays, frozen_arrays, t_pairs

    # set by CheckpointManager.attach(); None keeps the per-step cost at one
    # attribute read (same discipline as the disabled observability bus)
    _ckpt_manager = None

    @property
    def step_count(self) -> int:
        """Completed optimizer steps; checkpoint/restore round-trips it."""
        return self._step_count

    def _dispatch(self, *jit_args):
        """Invoke the compiled step, with bounded retry-with-backoff for
        transient runtime errors when the guard asks for it (generalizing
        the one-shot rebuild in _CompiledWithFallback, which stays the
        first line of defense for stale AOT executables)."""
        g = self._guard
        step_idx = self._step_count
        if g is None or g.policy.retry_transient <= 0:
            if _rb_faults.active():
                # `die` kills the process mid-step (host-death injection) —
                # deliberately OUTSIDE any retry loop: a dead host does not
                # retry, its peers discover it through the runtime; `oom`
                # likewise — an exhausted allocator does not recover on the
                # next attempt, the post-mortem path owns it
                _rb_faults.maybe_die(step_idx)
                _rb_faults.maybe_oom(step_idx)
                _rb_faults.maybe_raise("transient", step_idx)
            return self._jitted(*jit_args)

        def attempt():
            # the injection point sits INSIDE the retry loop so an armed
            # `transient@N*k` fault fails the first k attempts of step N
            if _rb_faults.active():
                _rb_faults.maybe_raise("transient", step_idx)
            return self._jitted(*jit_args)

        if _rb_faults.active():
            _rb_faults.maybe_die(step_idx)
            _rb_faults.maybe_oom(step_idx)

        return g.run_with_retry(attempt, step=step_idx)

    def __call__(self, *args, **kwargs):
        # one enabled() read gates ALL per-step observability: disabled mode
        # (the default) must do zero event-bus work on the dispatch path.
        # `sampled` additionally applies TT_OBS_SAMPLE to the per-step
        # records (span + host_overhead) — the flight recorder stays
        # unsampled so its p99/spike detection keeps every step.
        obs_on = _obs.enabled()
        slo_mon = self.slo_monitor
        t_host = time.perf_counter_ns() if (obs_on or slo_mon is not None) else 0
        sampled = obs_on and _obs_runtime.step_sampled("train_step")
        self._sync_mode()
        if getattr(self.tmodule, "_no_sync_active", False):
            return self.micro_step(*args, **kwargs)
        args, kwargs = self._bucketize(args, kwargs)
        # fault-injection seam (TT_FAULT): with no plan armed this is one
        # module-global read — the same zero-work contract as the bus
        step_idx = self._step_count
        if _rb_faults.active():
            # `slow` stalls the host at the step boundary (straggler
            # injection for the fleet detector) before any device work
            _rb_faults.maybe_sleep(step_idx)
            args, kwargs = _rb_faults.maybe_poison(args, kwargs, step_idx)
        tparam_arrays, frozen_arrays, t_pairs = self._split_arrays()
        if self.opt_state is None:
            self.opt_state = self.optimizer.init(tparam_arrays)
        was_built = self._jitted is not None
        if not was_built:
            if obs_on and self._step_count > 0:
                # a mid-run (re)build is a compile no cache served: record it
                # so the flight recorder's spike triage can name the cause
                _obs_metrics.record_recompile(_obs_metrics.REASON_CACHE_MISS,
                                              fn="train_step", step=self._step_count)
            if not self._try_aot(tparam_arrays, frozen_arrays, args, kwargs):
                self._build(args, kwargs)
                self._maybe_save_aot(tparam_arrays, frozen_arrays, args, kwargs)
        self.last_batch = (args, kwargs)  # for memory_analysis/harnesses
        if sampled and was_built:
            # host dispatch overhead of a steady-state step: everything
            # between call entry and handing off to the jitted program
            # (mode check, cached split, array-dict build). Opt-in: with the
            # bus disabled this whole block is one boolean test.
            _obs.event("host_overhead", fn="train_step", step=self._step_count,
                       us=round((time.perf_counter_ns() - t_host) / 1e3, 2))
        gmetrics = None
        if self._grad_acc is not None:
            # final (syncing) step of a no_sync accumulation window: fold the
            # accumulated local grads in before the optimizer update
            plan = getattr(self.tmodule, "_dist_plan", None)
            if plan is not None:
                loss, new_params, self.opt_state = self._fold_dist(
                    plan, tparam_arrays, frozen_arrays, self.opt_state, self._grad_acc, args, kwargs)
            else:
                loss, new_params, self.opt_state = self._jitted_with_acc(
                    tparam_arrays, frozen_arrays, self.opt_state, self._grad_acc, args, kwargs)
            self._grad_acc = None
        else:
            # host-side step latency (opt-in; dispatch is async so this is
            # submission latency unless the caller reads the loss value).
            # Gated on the obs_on read from call entry: the disabled-mode
            # steady-state path must not call into the observability layer
            try:
                with _obs.span("train_step") if sampled else _NULL_SPAN:
                    out = self._dispatch(
                        tparam_arrays, frozen_arrays, self.opt_state, args, kwargs)
            except BaseException as e:
                # RESOURCE_EXHAUSTED through dispatch: dump the forensic
                # bundle (live-array census, watermark ring, budget
                # estimate) BEFORE re-raising — the step is already dead,
                # the only question is whether the crash is legible
                _obs_mem.maybe_post_mortem(e, step=step_idx, source="train")
                # a step that dies while the FLEET is draining (a preempted
                # peer stopped stepping, so this host's collective had no
                # counterparty) is the drain arriving, not a crash: finalize
                # the preemption from the last completed step instead of
                # surfacing a dead-collective error. Zero cost on healthy
                # failures without a manager; with one, the KV read happens
                # only on this (already exceptional) path.
                mgr = self._ckpt_manager
                if mgr is not None and (mgr.preempted or mgr._peer_preempted()):
                    mgr._finalize_preempt(self)  # raises Preempted
                raise
            if self._guard is not None:
                loss, new_params, self.opt_state, effects, gmetrics = out
            else:
                loss, new_params, self.opt_state, effects = out
                gmetrics = None
            if effects and getattr(self, "_effect_keys", None):
                # epilogue: replay traced buffer mutations (running stats).
                # Under a guard, a non-finite step must not replay either:
                # the effect values were computed from the NaN forward, and
                # poisoned running stats / amax histories would corrupt
                # every later step the param gate just protected. The
                # bool() sync is one the guard's after_step pays anyway.
                if gmetrics is None or bool(gmetrics[0]):
                    for (owner, name), v in zip(self._effect_keys, effects):
                        owner._buffers[name] = v
        for k, p in t_pairs:
            p.data = new_params[k]
        self._step_count += 1
        if obs_on or slo_mon is not None:
            wall_ms = (time.perf_counter_ns() - t_host) / 1e6
            if obs_on:
                # flight recorder: every step's wall time (submission latency
                # + any synchronous compile) feeds the bounded ring; spikes
                # cross-reference the bus's recent recompile/stall events.
                # The streaming histogram is equally unsampled: online
                # step-time percentiles must cover every step.
                _obs_flight.record_step(wall_ms, step=self._step_count,
                                        fn="train_step")
                _obs_tel.observe("train.step_ms", wall_ms)
                # HBM watermark sample at the step boundary (mem.* gauges +
                # watermark ring); gated on the same obs_on read
                _obs_mem.on_step(self._step_count, source="train")
            if slo_mon is not None:
                slo_mon.observe_step(wall_ms)
        if gmetrics is not None:
            # host half of the guard: one device sync, then policy
            # (raise / skip-with-budget / rollback via the manager)
            self._guard.after_step(self, loss, gmetrics)
        if _rb_faults.active():
            _rb_faults.maybe_preempt(step_idx)
        mgr = self._ckpt_manager
        if mgr is not None:
            # periodic save / preemption drain; idle cost is an Event read
            # plus an int modulo (see CheckpointManager.on_step)
            mgr.on_step(self)
        return loss

    # -- gradient accumulation (reference ThunderModule.no_sync,
    # thunder/core/module.py:341 + skip_data_parallel_grad_sync) --
    _grad_acc = None
    _micro_jitted = None
    _jitted_with_acc_fn = None

    def micro_step(self, *args, **kwargs):
        """Accumulate local gradients without the cross-replica sync or the
        optimizer update; a following regular step folds them in.

        Under a distributed plan (pure-DDP/replicate) the per-replica partial
        gradients ride in a device-axis-sharded accumulator, so a K-step
        window costs ONE all-reduce instead of K (reference no_sync +
        _sync_grads, thunder/distributed/__init__.py:36,118)."""
        if self._guard is not None:
            # the window's fold step applies the optimizer update through a
            # separate program with no finite gate — silently un-guarding
            # the only updating step of a window would fake NaN protection
            raise NotImplementedError(
                "step guards are not supported inside no_sync gradient-"
                "accumulation windows yet; step without no_sync, or drop "
                "the guard")
        self._sync_mode()
        args, kwargs = self._bucketize(args, kwargs)
        plan = getattr(self.tmodule, "_dist_plan", None)
        if plan is not None:
            return self._micro_step_dist(plan, args, kwargs)
        tparam_arrays, frozen_arrays, _ = self._split_arrays()
        if self._jitted is None:
            if self.opt_state is None:
                self.opt_state = self.optimizer.init(tparam_arrays)
            self._build(args, kwargs)
        if self._micro_jitted is None:
            vag = self._vag

            def micro(tparam_arrays, frozen_arrays, acc, args, kwargs):
                loss, grads = vag(tparam_arrays, frozen_arrays, args, kwargs)
                if vag.consume_pending_effects():
                    raise NotImplementedError(
                        "buffer mutations are not supported inside no_sync "
                        "accumulation windows yet; freeze the buffers (eval()) "
                        "or step without no_sync")
                g = grads[0][0]
                new_acc = g if acc is None else {k: acc[k] + g[k] for k in g}
                return loss, new_acc

            self._micro_jitted = jax.jit(micro, donate_argnums=(2,) if self.donate else ())
        with _obs_runtime.step_span("micro_step") if _obs.enabled() else _NULL_SPAN:
            loss, self._grad_acc = self._micro_jitted(tparam_arrays, frozen_arrays, self._grad_acc, args, kwargs)
        return loss

    # -- distributed no_sync (pure-DDP and DDP/FSDP plans) --
    _vag_nosync = None
    _micro_dist_jitted = None
    _fold_dist_jitted = None
    _acc_mode = None  # 'ddp' (partial grads) | 'fsdp' (full grads, cached gather)
    _vag_full = None
    _gather_jitted = None
    _full_cache = None
    _micro_fsdp_jitted = None
    _fold_fsdp_jitted = None

    @staticmethod
    def _nosync_mode(plan) -> str:
        kinds = {st.kind for sts in plan.param_strategies.values() for st in sts}
        if kinds <= {"replicate"}:
            return "ddp"
        if kinds <= {"replicate", "shard0"} and not getattr(plan, "seq_axes", ()):
            return "fsdp"
        raise NotImplementedError(
            "no_sync supports DDP (replicate) and FSDP (shard0) plans; "
            "TP/CP gradients synchronize per micro-batch inherently")

    def _dist_specs(self, plan, trainable, frozen, batch_args, batch_kwargs):
        from jax.sharding import PartitionSpec as P

        param_specs, frozen_specs, args_specs, kwargs_specs = _dist_in_specs(
            plan, trainable, frozen, batch_args, batch_kwargs)
        acc_specs = {k: P(plan.loss_axis_name, *([None] * v.ndim)) for k, v in trainable.items()}
        return param_specs, frozen_specs, acc_specs, args_specs, kwargs_specs

    def _micro_step_dist(self, plan, args, kwargs):
        self._acc_mode = self._nosync_mode(plan)
        if self._acc_mode == "fsdp":
            return self._micro_step_fsdp(plan, args, kwargs)
        # epoch-cached split: K micro-steps per window must not pay K walks
        tparam_arrays, frozen_arrays, _ = self._split_arrays()
        if self._jitted is None:
            if self.opt_state is None:
                self.opt_state = self.optimizer.init(tparam_arrays)
            self._build(args, kwargs)
        if self._vag_nosync is None:
            self._vag_nosync = self._make_vag(sync_loss=False)
        if self._grad_acc is None:
            # allocate the accumulator already sharded over the device axis
            # (a plain jnp.zeros would materialize world_size x params on one
            # device before resharding — an OOM hazard at scale)
            from jax.sharding import NamedSharding, PartitionSpec as P

            def _sharded_zeros(shape, dtype):
                sh = NamedSharding(plan.mesh, P(plan.loss_axis_name, *([None] * (len(shape) - 1))))
                return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sh)()

            self._grad_acc = {k: _sharded_zeros((plan.loss_world_size,) + tuple(v.shape), v.dtype)
                              for k, v in tparam_arrays.items()}
        if self._micro_dist_jitted is None:
            from jax.sharding import PartitionSpec as P

            vagn = self._vag_nosync
            ndev = plan.loss_world_size
            axes = plan.loss_axis_name

            def micro_raw(tparams, frozen_a, acc, a, kw):
                loss_local, grads = vagn(tparams, frozen_a, a, kw)
                if vagn.consume_pending_effects():
                    raise NotImplementedError(
                        "buffer mutations are not supported in distributed "
                        "no_sync windows; freeze the buffers (eval())")
                g = grads[0][0]
                new_acc = {k: acc[k] + g[k][None] for k in g}
                loss = jax.lax.psum(loss_local, axes) / ndev
                return loss, new_acc

            pspec, fspec, aspec, args_specs, kwargs_specs = self._dist_specs(
                plan, tparam_arrays, frozen_arrays, args, kwargs)
            sm = _shard_map_compat(micro_raw, plan.mesh,
                                   (pspec, fspec, aspec, args_specs, kwargs_specs),
                                   (P(), aspec))
            self._micro_dist_jitted = jax.jit(sm, donate_argnums=(2,) if self.donate else ())
        loss, self._grad_acc = self._micro_dist_jitted(
            tparam_arrays, frozen_arrays, self._grad_acc, args, kwargs)
        return loss

    # -- FSDP no_sync: gather params ONCE per accumulation window, run
    # micro-steps with zero communication on cached full params, fold with a
    # single reduce-scatter (reference FSDP no_sync stashes unsharded grads,
    # thunder/distributed/__init__.py:36 + STASH_GRAD_FOR_FSDP) --

    def _make_vag_full(self):
        """ValueAndGrad over the raw model with FULL params (no collectives)."""
        from .transforms.autodiff import ThunderValueAndGrad

        inner = self.tmodule._cfn._cd.fn

        def traced_full(tfull: dict, frozen_full: dict, args: tuple, kwargs: dict):
            return inner({**frozen_full, **tfull}, args, kwargs)

        traced_full.__name__ = f"nosync_{getattr(inner, '__name__', 'step')}"
        vag = ThunderValueAndGrad(traced_full, argnums=0,
                                  transforms=self.tmodule._cfn._transforms,
                                  check_traces=getattr(self.tmodule._cfn,
                                                       "_check_traces", False))
        vag._effects_consumer_attached = True
        return vag

    def _gather_full(self, plan, tparam_arrays, frozen_arrays):
        """One jitted gather of every sharded param to full (unpadded) form."""
        if self._gather_jitted is None:
            from jax.sharding import PartitionSpec as P

            strategies = plan.param_strategies

            def gather_raw(tparams, frozen_a):
                def full(k, v):
                    for st in strategies.get(k, ()):
                        if st.kind == "shard0":
                            v = jax.lax.all_gather(v, st.axis, tiled=True)
                            if st.orig_dim0 is not None:
                                v = v[: st.orig_dim0]
                    return v

                return ({k: full(k, v) for k, v in tparams.items()},
                        {k: full(k, v) for k, v in frozen_a.items()})

            pspec = {k: plan.param_spec(k, v.ndim) for k, v in tparam_arrays.items()}
            fspec = {k: plan.param_spec(k, v.ndim) for k, v in frozen_arrays.items()}
            out_t = {k: P() for k in tparam_arrays}
            out_f = {k: P() for k in frozen_arrays}
            sm = _shard_map_compat(gather_raw, plan.mesh, (pspec, fspec), (out_t, out_f))
            self._gather_jitted = jax.jit(sm)
        return self._gather_jitted(tparam_arrays, frozen_arrays)

    def _micro_step_fsdp(self, plan, args, kwargs):
        tparam_arrays, frozen_arrays, _ = self._split_arrays()
        if self._jitted is None:
            if self.opt_state is None:
                self.opt_state = self.optimizer.init(tparam_arrays)
            self._build(args, kwargs)
        if self._vag_full is None:
            self._vag_full = self._make_vag_full()
        if self._full_cache is None:
            self._full_cache = self._gather_full(plan, tparam_arrays, frozen_arrays)
        full_t, full_f = self._full_cache
        if self._grad_acc is None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            def _sharded_zeros(shape, dtype):
                sh = NamedSharding(plan.mesh, P(plan.loss_axis_name, *([None] * (len(shape) - 1))))
                return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=sh)()

            self._grad_acc = {k: _sharded_zeros((plan.loss_world_size,) + tuple(v.shape), v.dtype)
                              for k, v in full_t.items()}
        if self._micro_fsdp_jitted is None:
            from jax.sharding import PartitionSpec as P

            vagf = self._vag_full
            ndev = plan.loss_world_size
            axes = plan.loss_axis_name

            def micro_raw(tfull, ffull, acc, a, kw):
                loss_local, grads = vagf(tfull, ffull, a, kw)
                if vagf.consume_pending_effects():
                    raise NotImplementedError(
                        "buffer mutations are not supported in FSDP no_sync "
                        "windows; freeze the buffers (eval())")
                g = grads[0][0]
                new_acc = {k: acc[k] + g[k][None] for k in g}
                loss = jax.lax.psum(loss_local, axes) / ndev
                return loss, new_acc

            tspec = {k: P() for k in full_t}
            fspec = {k: P() for k in full_f}
            aspec = {k: P(plan.loss_axis_name, *([None] * v.ndim)) for k, v in full_t.items()}
            args_specs = jax.tree_util.tree_map(lambda l: _batch_pspec(plan, l), args)
            kwargs_specs = jax.tree_util.tree_map(lambda l: _batch_pspec(plan, l), kwargs)
            sm = _shard_map_compat(micro_raw, plan.mesh,
                                   (tspec, fspec, aspec, args_specs, kwargs_specs),
                                   (P(), aspec))
            self._micro_fsdp_jitted = jax.jit(sm, donate_argnums=(2,) if self.donate else ())
        loss, self._grad_acc = self._micro_fsdp_jitted(full_t, full_f, self._grad_acc, args, kwargs)
        return loss

    def _fold_fsdp(self, plan, tparam_arrays, frozen_arrays, opt_state, acc, args, kwargs):
        """Final step of an FSDP no_sync window: fresh local full grads + the
        accumulator, ONE reduce-scatter per sharded param, optimizer on
        shards; the cached full params are then invalidated."""
        full_t, full_f = self._full_cache
        if self._fold_fsdp_jitted is None:
            from jax.sharding import PartitionSpec as P

            vagf = self._vag_full
            optimizer = self.optimizer
            ndev = plan.loss_world_size
            axes = plan.loss_axis_name
            strategies = plan.param_strategies

            def shard_grad(k, g, shard_like):
                # full chain: psum over every loss axis the param is NOT
                # sharded on (dp replicas see different batches), then one
                # reduce-scatter over its shard axis
                shard_st = next((st for st in strategies.get(k, ()) if st.kind == "shard0"), None)
                if shard_st is None:
                    return jax.lax.psum(g, axes) / ndev
                other = tuple(a for a in plan.loss_axes if a != shard_st.axis)
                if other:
                    g = jax.lax.psum(g, other if len(other) > 1 else other[0])
                if shard_st.orig_dim0 is not None:
                    pad = shard_like.shape[0] * plan.world_size(shard_st.axis) - shard_st.orig_dim0
                    g = jnp.pad(g, [(0, pad)] + [(0, 0)] * (g.ndim - 1))
                return jax.lax.psum_scatter(g, shard_st.axis, scatter_dimension=0, tiled=True) / ndev

            def fold_raw(tshards, opt_st, tfull, ffull, acc, a, kw):
                loss_local, grads = vagf(tfull, ffull, a, kw)
                vagf.consume_pending_effects()
                g = grads[0][0]
                total = {k: g[k] + acc[k][0] for k in g}
                gshards = {k: shard_grad(k, total[k], tshards[k]) for k in total}
                new_params, new_state = optimizer.update(tshards, gshards, opt_st)
                loss = jax.lax.psum(loss_local, axes) / ndev
                return loss, new_params, new_state

            pspec = {k: plan.param_spec(k, v.ndim) for k, v in tparam_arrays.items()}
            opt_specs = _opt_state_specs(opt_state, pspec)
            tspec = {k: P() for k in full_t}
            fspec = {k: P() for k in full_f}
            aspec = {k: P(plan.loss_axis_name, *([None] * v.ndim)) for k, v in full_t.items()}
            args_specs = jax.tree_util.tree_map(lambda l: _batch_pspec(plan, l), args)
            kwargs_specs = jax.tree_util.tree_map(lambda l: _batch_pspec(plan, l), kwargs)
            sm = _shard_map_compat(fold_raw, plan.mesh,
                                   (pspec, opt_specs, tspec, fspec, aspec, args_specs, kwargs_specs),
                                   (P(), pspec, opt_specs))
            self._fold_fsdp_jitted = jax.jit(sm, donate_argnums=(0, 1, 4) if self.donate else ())
        out = self._fold_fsdp_jitted(tparam_arrays, opt_state, full_t, full_f, acc, args, kwargs)
        self._full_cache = None  # params change: next window re-gathers
        return out

    def _fold_dist(self, plan, tparam_arrays, frozen_arrays, opt_state, acc, args, kwargs):
        """Final step of a distributed no_sync window: ONE all-reduce over
        (fresh local grads + accumulated partials), then the optimizer."""
        if self._acc_mode == "fsdp":
            return self._fold_fsdp(plan, tparam_arrays, frozen_arrays, opt_state, acc, args, kwargs)
        if self._fold_dist_jitted is None:
            from jax.sharding import PartitionSpec as P

            vagn = self._vag_nosync or self._make_vag(sync_loss=False)
            self._vag_nosync = vagn
            optimizer = self.optimizer
            ndev = plan.loss_world_size
            axes = plan.loss_axis_name

            def fold_raw(tparams, frozen_a, opt_st, acc, a, kw):
                loss_local, grads = vagn(tparams, frozen_a, a, kw)
                vagn.consume_pending_effects()
                g = grads[0][0]
                total = {k: jax.lax.psum(g[k] + acc[k][0], axes) / ndev for k in g}
                new_params, new_state = optimizer.update(tparams, total, opt_st)
                loss = jax.lax.psum(loss_local, axes) / ndev
                return loss, new_params, new_state

            pspec, fspec, aspec, args_specs, kwargs_specs = self._dist_specs(
                plan, tparam_arrays, frozen_arrays, args, kwargs)
            opt_specs = _opt_state_specs(opt_state, pspec)
            sm = _shard_map_compat(fold_raw, plan.mesh,
                                   (pspec, fspec, opt_specs, aspec, args_specs, kwargs_specs),
                                   (P(), pspec, opt_specs))
            self._fold_dist_jitted = jax.jit(sm, donate_argnums=(0, 2, 3) if self.donate else ())
        return self._fold_dist_jitted(tparam_arrays, frozen_arrays, opt_state, acc, args, kwargs)

    def _jitted_with_acc(self, tparam_arrays, frozen_arrays, opt_state, acc, args, kwargs):
        if self._jitted_with_acc_fn is None:
            vag = self._vag
            optimizer = self.optimizer

            def step_acc(tparam_arrays, frozen_arrays, opt_state, acc, args, kwargs):
                loss, grads = vag(tparam_arrays, frozen_arrays, args, kwargs)
                vag.consume_pending_effects()  # window already rejected effects in micro
                g = grads[0][0]
                total = {k: g[k] + acc[k] for k in g}
                new_params, new_state = optimizer.update(tparam_arrays, total, opt_state)
                return loss, new_params, new_state

            self._jitted_with_acc_fn = jax.jit(step_acc, donate_argnums=(0, 2, 3) if self.donate else ())
        return self._jitted_with_acc_fn(tparam_arrays, frozen_arrays, opt_state, acc, args, kwargs)

    @property
    def compile_stats(self):
        return getattr(self, "_vag", None) and self._vag._cs

    def compiled(self):
        """The executable of the last-built step: the AOT one where the step holds it,
        else (the distributed road, or a step with no artifact store) the jitted step
        lowered again for the last batch, which finds the executable the last step ran
        in JAX's own caches. None before the first step. What ``memory_analysis``
        and ``observability.op_scopes`` read."""
        if self._jitted is None or getattr(self, "last_batch", None) is None:
            return None
        if isinstance(self._jitted, _CompiledWithFallback):
            compiled = self._jitted._compiled
            if compiled is not None:
                return compiled
            jitted = self._jitted._jit_fn
            if jitted is None:
                return None
        else:
            jitted = self._jitted
        again = getattr(self, "_lowered_again", None)
        if again is None or again[0] is not jitted:
            trainable, frozen = self._split_params()
            tparams = {k: p.data for k, p in trainable.items()}
            fparams = {k: getattr(p, "data", p) for k, p in frozen.items()}
            args, kwargs = self.last_batch
            again = self._lowered_again = (
                jitted, jitted.lower(tparams, fparams, self.opt_state, args, kwargs).compile())
        return again[1]

    def memory_analysis(self):
        """Compiled-program memory analysis of the last-built step."""
        compiled = self.compiled()
        return None if compiled is None else compiled.memory_analysis()


def _dist_global_norm(param_grads: dict, plan):
    """TRUE global gradient norm inside a shard_map'd step: per param, the
    local sum-of-squares is psum'd over exactly the axes that param's grad
    is SHARDED on (shard0/column/row) and counted once over the axes it is
    replicated on — a blanket psum would overcount replicated grads by the
    world size, a bare local norm would understate sharded ones by √shards.
    The result is identical on every device (replicated components are
    equal, psum'd components are collective outputs), so it rides the P()
    out-spec unchanged."""
    strategies = plan.param_strategies
    total = jnp.zeros((), jnp.float32)
    for k, g in param_grads.items():
        ss = jnp.sum(jnp.square(g.astype(jnp.float32)))
        shard_axes = tuple(st.axis for st in strategies.get(k, ())
                           if st.kind in ("shard0", "column", "row"))
        if shard_axes:
            ss = jax.lax.psum(ss, shard_axes if len(shard_axes) > 1
                              else shard_axes[0])
        total = total + ss
    return jnp.sqrt(total)


def _batch_pspec(plan, leaf):
    from jax.sharding import PartitionSpec as P

    ndim = getattr(leaf, "ndim", 0)
    seq_axes = tuple(getattr(plan, "seq_axes", ()))
    if ndim == 0 or (not plan.data_axes and not seq_axes):
        return P()
    first = None
    if plan.data_axes:
        first = plan.data_axes[0] if len(plan.data_axes) == 1 else tuple(plan.data_axes)
    parts = [first]
    if seq_axes and ndim >= 2:
        parts.append(seq_axes[0] if len(seq_axes) == 1 else tuple(seq_axes))
    while len(parts) < ndim:
        parts.append(None)
    return P(*parts)


def _opt_state_specs(opt_state, param_specs: dict):
    from jax.sharding import PartitionSpec as P

    def rec(node):
        if isinstance(node, dict):
            if set(node.keys()) == set(param_specs.keys()):
                return dict(param_specs)
            return {k: rec(v) for k, v in node.items()}
        return P()

    return rec(opt_state)


def _shard_map_compat(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)


def _dist_in_specs(plan, trainable, frozen, batch_args, batch_kwargs):
    """PartitionSpecs for (params, frozen, args, kwargs) — the single source
    of sharding rules shared by the synced step and the no_sync variants."""
    param_specs = {k: plan.param_spec(k, v.ndim) for k, v in trainable.items()}
    frozen_specs = {k: plan.param_spec(k, v.ndim) for k, v in frozen.items()}
    args_specs = jax.tree_util.tree_map(lambda l: _batch_pspec(plan, l), batch_args)
    kwargs_specs = jax.tree_util.tree_map(lambda l: _batch_pspec(plan, l), batch_kwargs)
    return param_specs, frozen_specs, args_specs, kwargs_specs


def _shard_mapped_step(raw_step, plan, tmodule, opt_state, batch_args, batch_kwargs, donate,
                       *, guarded: bool = False):
    """Wrap the step in shard_map over the plan's mesh: params/opt-state use
    per-param specs, batch leaves shard dim 0 over the data axes, loss comes
    back replicated. XLA lowers the recorded collective prims to ICI
    collectives and overlaps them with compute. A guarded step returns two
    extra outputs — the psum'd finite verdict and the pmax'd grad norm —
    both replicated, so every host's after_step reads the same decision."""
    from jax.sharding import PartitionSpec as P

    all_params = dict(tmodule.get_parameters())
    trainable = {k: p.data for k, p in all_params.items() if getattr(p, "requires_grad", True)}
    getb = getattr(tmodule, "get_buffers", None)
    if callable(getb):
        all_params.update(getb())
    frozen = {k: getattr(p, "data", p) for k, p in all_params.items() if k not in trainable}
    if opt_state is None:
        raise RuntimeError("opt_state must be initialized before building the distributed step")
    if plan.data_axes:
        # loud divisibility check: shard_map's own failure on an uneven
        # batch is an anonymous AssertionError deep in spec matching
        dp_world = 1
        for a in plan.data_axes:
            dp_world *= plan.world_size(a)
        for leaf in jax.tree_util.tree_leaves((batch_args, batch_kwargs)):
            shape = getattr(leaf, "shape", None)
            if shape and shape[0] % dp_world:
                raise ValueError(
                    f"batch dim 0 ({shape[0]}) is not divisible by the "
                    f"data-parallel world size {dp_world} (axes "
                    f"{plan.data_axes}); pad or resize the batch")
    param_specs, frozen_specs, args_specs, kwargs_specs = _dist_in_specs(
        plan, trainable, frozen, batch_args, batch_kwargs)
    opt_specs = _opt_state_specs(opt_state, param_specs)
    out_specs = (P(), param_specs, opt_specs, ())
    if guarded:
        out_specs = out_specs + ((P(), P()),)
    smapped = _shard_map_compat(raw_step, plan.mesh,
                                (param_specs, frozen_specs, opt_specs, args_specs, kwargs_specs),
                                out_specs)
    return jax.jit(smapped, donate_argnums=donate)
