"""Step guards: NaN/Inf detection, skip/rollback policies, bounded retry.

A 7B run that hits one NaN loss at step 90k must not silently optimize into
garbage — and must not necessarily die either. The guard machinery has two
halves:

* **in-program** (built by ``TrainStep._build`` when a guard is attached):
  the step program computes ``finite = isfinite(loss) [& isfinite(gnorm)]``
  and gates the parameter/optimizer-state update with ``where(finite, new,
  old)``. This is what makes the *skip* policy safe under buffer donation —
  by the time the host could react, donated input buffers are gone, so the
  only place the old params still exist is inside the program itself.
* **host-side** (``StepGuard.after_step``): reads the finite flag (one host
  sync — guards are opt-in precisely because of this), counts consecutive
  bad steps, and applies the policy: ``raise`` / ``skip`` (with escalation
  after ``max_consecutive``) / ``rollback`` to the attached
  ``CheckpointManager``'s last checkpoint.

Transient runtime errors get bounded retry-with-backoff
(``StepGuard.run_with_retry``), generalizing the one-shot rebuild in
``training._CompiledWithFallback``: a JaxRuntimeError (or an injected
``faults.InjectedTransientError``) is retried up to ``retry_transient``
times with exponential backoff. Every intervention is a reason-coded bus
event (``guard`` events + ``guard.<action>`` counters) so the flight
recorder's spike triage can name it.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass
from typing import Optional

from ..observability import metrics as _obs_metrics

ON_NONFINITE = ("raise", "skip", "rollback")


class NonFiniteLossError(RuntimeError):
    """Loss or gradient norm went NaN/Inf and the policy said raise."""


_TRANSIENT_ERRORS: Optional[tuple] = None


def transient_errors() -> tuple:
    """Exception types treated as transient/retryable runtime failures.
    Memoized: this sits on the guarded dispatch path, which must not pay
    try-imports per step."""
    global _TRANSIENT_ERRORS
    if _TRANSIENT_ERRORS is not None:
        return _TRANSIENT_ERRORS
    from .faults import InjectedTransientError

    import jax

    _TRANSIENT_ERRORS = (InjectedTransientError, jax.errors.JaxRuntimeError)
    return _TRANSIENT_ERRORS


@dataclass
class GuardPolicy:
    """What to do when a step goes bad.

    on_nonfinite:     "raise" | "skip" | "rollback"
                      skip: the in-program gate already kept params/opt-state
                      unchanged; training continues on the next batch.
                      rollback: after ``max_consecutive`` bad steps, restore
                      the attached CheckpointManager's last checkpoint.
    max_consecutive:  bad-step budget before skip/rollback escalates
                      (skip escalates to raise; rollback restores, and raises
                      if a second budget is exhausted after restoring).
    check_grad_norm:  also compute/check the global gradient norm in-program.
    retry_transient:  bounded retries for transient runtime errors (0 = off).
    retry_backoff_s:  initial backoff, doubled per retry.
    """

    on_nonfinite: str = "raise"
    max_consecutive: int = 3
    check_grad_norm: bool = True
    retry_transient: int = 0
    retry_backoff_s: float = 0.05

    def __post_init__(self):
        if self.on_nonfinite not in ON_NONFINITE:
            raise ValueError(
                f"on_nonfinite must be one of {ON_NONFINITE}, got {self.on_nonfinite!r}")
        if self.max_consecutive < 1:
            raise ValueError("max_consecutive must be >= 1")


class StepGuard:
    """Host-side half of the guard; attach via ``TrainStep(..., guard=...)``."""

    def __init__(self, policy: Optional[GuardPolicy] = None):
        self.policy = policy or GuardPolicy()
        self.consecutive_bad = 0
        self.skipped = 0
        self.rollbacks = 0
        self.retries = 0
        # rollbacks since the last finite step: a persistent NaN source
        # (corrupt data replayed from the same restored cursor) must raise
        # on the second exhausted budget, not livelock restoring forever
        self._rollbacks_since_good = 0
        # set by TrainStep._build under a mesh plan: the finite flag is then
        # a psum'd ALL-HOST verdict, and every intervention below is also
        # recorded as a guard.dist_* agreement counter so cross-host counter
        # dumps can be diffed for lockstep (tests/test_multiprocess.py)
        self.distributed = False

    def mark_distributed(self) -> None:
        self.distributed = True

    def program_key(self) -> str:
        """The part of the guard config that changes the traced program
        (folded into the AOT step cache key)."""
        return f"guard(gnorm={self.policy.check_grad_norm})"

    def _record(self, reason: str, **attrs) -> None:
        """Reason-coded intervention event/counter; under a distributed
        verdict the same reason is additionally bumped as guard.dist_<reason>
        so per-host counter dumps can be diffed for lockstep agreement."""
        if self.distributed:
            _obs_metrics.record_dist_verdict(reason, **attrs)
        else:
            _obs_metrics.record_intervention(reason, **attrs)

    # -- nonfinite policy ---------------------------------------------------

    def after_step(self, train_step, loss, metrics) -> None:
        """Called by TrainStep.__call__ after the jitted step returns.
        ``metrics`` is the (finite, grad_norm) pair the program computed."""
        finite, gnorm = metrics
        rec = self._record
        if bool(finite):  # host sync: the price of guarding
            self.consecutive_bad = 0
            self._rollbacks_since_good = 0
            return
        self.consecutive_bad += 1
        pol = self.policy
        step = train_step._step_count
        gnorm_f = float(gnorm) if pol.check_grad_norm else None
        if pol.on_nonfinite == "raise":
            rec("nonfinite-raise", step=step, grad_norm=gnorm_f)
            raise NonFiniteLossError(
                f"non-finite loss/grad at step {step} "
                f"(loss={float(loss)!r}, grad_norm={gnorm_f!r})")
        if pol.on_nonfinite == "skip":
            self.skipped += 1
            rec("nonfinite-skip", step=step, consecutive=self.consecutive_bad,
                grad_norm=gnorm_f)
            if self.consecutive_bad >= pol.max_consecutive:
                rec("nonfinite-raise", step=step, after_skips=self.consecutive_bad)
                raise NonFiniteLossError(
                    f"{self.consecutive_bad} consecutive non-finite steps "
                    f"(budget {pol.max_consecutive}); last at step {step}")
            return
        # rollback
        self.skipped += 1
        rec("nonfinite-skip", step=step, consecutive=self.consecutive_bad,
            grad_norm=gnorm_f)
        if self.consecutive_bad < pol.max_consecutive:
            return
        mgr = getattr(train_step, "_ckpt_manager", None)
        if mgr is None:
            rec("nonfinite-raise", step=step, rollback="no-manager")
            raise NonFiniteLossError(
                f"{self.consecutive_bad} consecutive non-finite steps and no "
                f"CheckpointManager attached to roll back to (step {step})")
        if self._rollbacks_since_good >= 1:
            rec("nonfinite-raise", step=step, rollback="budget-exhausted")
            raise NonFiniteLossError(
                f"non-finite steps persisted through a rollback (step {step}); "
                f"the fault is deterministic (bad data/model), not transient — "
                f"refusing to livelock restoring the same checkpoint")
        restored = mgr.restore(train_step)
        self.rollbacks += 1
        self._rollbacks_since_good += 1
        self.consecutive_bad = 0
        rec("rollback", step=step, restored_step=restored.get("step"))
        warnings.warn(
            f"rolled back to checkpoint step {restored.get('step')} after "
            f"{self.policy.max_consecutive} consecutive non-finite steps",
            stacklevel=2)

    # -- transient retry ----------------------------------------------------

    def run_with_retry(self, attempt, *, step: int):
        """Run ``attempt()`` with bounded retry-with-backoff on transient
        runtime errors. The retry budget is per-call (per step), the backoff
        doubles per retry. Non-transient errors propagate immediately.

        Caveat (documented in docs/robustness.md): a retry re-dispatches with
        the same host-side argument references. On CPU (donation is a no-op)
        this is always safe; on TPU a *genuinely started* step may have
        consumed donated buffers, in which case the retry surfaces the
        donation error and the rollback policy is the right recovery."""
        errs = transient_errors()
        retries = self.policy.retry_transient
        backoff = self.policy.retry_backoff_s
        for i in range(retries + 1):
            try:
                return attempt()
            except errs as e:
                if i >= retries:
                    _obs_metrics.record_intervention(
                        "transient-exhausted", step=step, attempts=i + 1,
                        error=f"{type(e).__name__}: {e}"[:200])
                    raise
                self.retries += 1
                _obs_metrics.record_intervention(
                    "transient-retry", step=step, attempt=i + 1,
                    backoff_s=round(backoff, 4),
                    error=f"{type(e).__name__}: {e}"[:200])
                if backoff > 0:
                    time.sleep(backoff)
                backoff *= 2
