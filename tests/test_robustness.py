"""Fault-tolerant training (ISSUE 9): preemption-safe checkpoint/resume,
step guards, and the TT_FAULT injection harness.

The acceptance scenarios live here: kill-and-resume bit-identity (train,
inject SIGTERM, restore in a fresh TrainStep/loader, identical trajectory),
all four fault classes with their policies' observable outcomes + bus
events, and the counter-asserted zero-work-when-idle contract.
"""
import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import thunder_tpu as tt
from thunder_tpu import nn, observability, optim
from thunder_tpu.data import TokenLoader, write_token_file
from thunder_tpu.observability import flight_recorder as fr
from thunder_tpu.ops import ltorch
from thunder_tpu.robustness import (
    CheckpointError,
    CheckpointManager,
    GuardPolicy,
    NonFiniteLossError,
    Preempted,
    StepGuard,
    faults,
    list_steps,
    validate_step,
)
from thunder_tpu.robustness.faults import (
    InjectedCheckpointError,
    InjectedTransientError,
)
from thunder_tpu.training import TrainStep


@pytest.fixture(autouse=True)
def _disarm_faults():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture
def obs_mem():
    observability.reset()
    fr.reset()
    observability.enable()
    yield
    observability.disable()
    observability.reset()
    fr.reset()


class _Net(nn.Module):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(8, 16, seed=1)
        self.fc2 = nn.Linear(16, 4, seed=2)

    def forward(self, x, y):
        return ltorch.mse_loss(self.fc2(ltorch.gelu(self.fc1(x))), y)


def _make_step(guard=None, lr=1e-2):
    net = _Net()
    step = TrainStep(tt.jit(net), optim.AdamW(lr=lr), guard=guard)
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(4, 8), jnp.float32)
    y = jnp.zeros((4, 4), jnp.float32)
    return step, x, y


def _params(step):
    return {k: np.asarray(p.data).copy()
            for k, p in step.tmodule.get_parameters().items()}


def _events(name):
    return [r for r in observability.records()
            if r.get("kind") == "event" and r.get("name") == name]


# ---------------------------------------------------------------------------
# fault plan parsing
# ---------------------------------------------------------------------------

class TestFaultPlan:
    def test_parse_spec(self):
        plan = faults.FaultPlan.parse("nan_loss@5, transient@7*2,preempt@9")
        kinds = [(f.kind, f.step, f.count) for f in plan.faults]
        assert kinds == [("nan_loss", 5, 1), ("transient", 7, 2), ("preempt", 9, 1)]

    def test_parse_rejects_garbage(self):
        with pytest.raises(ValueError, match="expected"):
            faults.FaultPlan.parse("nan_loss5")
        with pytest.raises(ValueError, match="unknown fault kind"):
            faults.FaultPlan.parse("frobnicate@3")

    def test_should_fire_consumes(self):
        plan = faults.FaultPlan.parse("transient@3*2")
        assert not plan.should_fire("transient", 2)
        assert plan.should_fire("transient", 3)
        assert plan.should_fire("transient", 3)
        assert not plan.should_fire("transient", 4)
        assert not plan.pending()

    def test_inactive_is_zero_work(self):
        faults.clear()
        assert not faults.active()
        assert not faults.should_fire("nan_loss", 0)


# ---------------------------------------------------------------------------
# checkpoint manager
# ---------------------------------------------------------------------------

class TestCheckpointManager:
    def test_periodic_save_and_keep_k(self, tmp_path):
        step, x, y = _make_step()
        mgr = CheckpointManager(str(tmp_path), every_n_steps=2, keep=2,
                                async_save=False, preemption=False).attach(step)
        for _ in range(7):
            step(x, y)
        steps = [s for s, _ in list_steps(str(tmp_path))]
        assert steps == [4, 6]  # keep-last-2 pruned step 2
        assert mgr.saves == 3

    def test_restore_round_trips_bit_identical(self, tmp_path):
        step, x, y = _make_step()
        mgr = CheckpointManager(str(tmp_path), async_save=False,
                                preemption=False).attach(step)
        for _ in range(3):
            step(x, y)
        want = _params(step)
        want_loss = float(step.tmodule(x, y))
        mgr.save(step, block=True)
        for _ in range(2):
            step(x, y)  # drift
        meta = mgr.restore(step)
        assert meta["step"] == 3 and step.step_count == 3
        got = _params(step)
        for k in want:
            np.testing.assert_array_equal(want[k], got[k], err_msg=k)
        assert float(step.tmodule(x, y)) == want_loss  # bit-identical forward
        # optimizer state restored too: continuing matches a never-restored run
        step(x, y)
        assert step.step_count == 4

    def test_async_save_does_not_lose_state(self, tmp_path):
        step, x, y = _make_step()
        mgr = CheckpointManager(str(tmp_path), async_save=True,
                                preemption=False).attach(step)
        step(x, y)
        want = _params(step)
        mgr.save(step)     # background write
        step(x, y)         # mutate while in flight (host snapshot protects us)
        mgr.wait()
        mgr.restore(step)
        got = _params(step)
        for k in want:
            np.testing.assert_array_equal(want[k], got[k], err_msg=k)

    def test_idle_steps_are_zero_work(self, tmp_path, monkeypatch):
        """Acceptance: checkpointing enabled but idle must not touch the
        state-capture path at all (same counter-asserted discipline as the
        disabled observability bus)."""
        step, x, y = _make_step()
        mgr = CheckpointManager(str(tmp_path), every_n_steps=5,
                                async_save=False, preemption=False).attach(step)
        calls = {"collect": 0, "snapshot": 0}
        orig_collect = mgr._collect
        monkeypatch.setattr(mgr, "_collect",
                            lambda ts: (calls.__setitem__("collect", calls["collect"] + 1),
                                        orig_collect(ts))[1])
        orig_snap = CheckpointManager._snapshot
        monkeypatch.setattr(CheckpointManager, "_snapshot",
                            staticmethod(lambda s: (calls.__setitem__("snapshot", calls["snapshot"] + 1),
                                                    orig_snap(s))[1]))
        for _ in range(4):
            step(x, y)
        assert calls == {"collect": 0, "snapshot": 0}  # idle: int modulo only
        step(x, y)  # step 5: the interval fires
        assert calls == {"collect": 1, "snapshot": 1}

    def test_corrupt_latest_falls_back_to_older(self, tmp_path):
        step, x, y = _make_step()
        mgr = CheckpointManager(str(tmp_path), async_save=False,
                                preemption=False).attach(step)
        step(x, y)
        mgr.save(step, block=True)
        good = _params(step)
        step(x, y)
        mgr.save(step, block=True)
        # tamper with the newest checkpoint's payload
        newest = list_steps(str(tmp_path))[-1][1]
        payload = os.path.join(newest, "state", "state.npz")
        if not os.path.exists(payload):  # orbax layout: tamper any payload file
            for dirpath, _, fns in os.walk(os.path.join(newest, "state")):
                for fn in fns:
                    payload = os.path.join(dirpath, fn)
                    break
        with open(payload, "ab") as f:
            f.write(b"corrupt")
        ok, problems = validate_step(newest)
        assert not ok and problems
        step(x, y)  # drift
        with pytest.warns(UserWarning, match="skipping invalid checkpoint"):
            meta = mgr.restore(step)
        assert meta["step"] == 1
        got = _params(step)
        for k in good:
            np.testing.assert_array_equal(good[k], got[k], err_msg=k)


# ---------------------------------------------------------------------------
# fault class 1: checkpoint-write failure
# ---------------------------------------------------------------------------

@pytest.mark.fault
class TestCheckpointWriteFaults:
    def test_save_failure_nonfatal_by_default(self, tmp_path, obs_mem):
        step, x, y = _make_step()
        mgr = CheckpointManager(str(tmp_path), every_n_steps=2,
                                async_save=False, preemption=False).attach(step)
        faults.configure("ckpt_fail@2")
        with pytest.warns(UserWarning, match="non-fatal"):
            for _ in range(4):
                step(x, y)  # save at step 2 fails, training continues
        assert step.step_count == 4
        assert mgr.failed_saves == 1
        assert mgr.saves == 1  # step-4 save succeeded
        assert _events("checkpoint.save_failed"), "no save_failed bus event"
        assert observability.counters().get("checkpoint.save_failed") == 1

    def test_save_failure_strict_raises(self, tmp_path):
        step, x, y = _make_step()
        CheckpointManager(str(tmp_path), every_n_steps=2, async_save=False,
                          strict=True, preemption=False).attach(step)
        faults.configure("ckpt_fail@2")
        step(x, y)
        with pytest.raises(CheckpointError):
            step(x, y)

    def test_async_save_failure_surfaces_in_strict_wait(self, tmp_path):
        step, x, y = _make_step()
        mgr = CheckpointManager(str(tmp_path), async_save=True, strict=True,
                                preemption=False).attach(step)
        step(x, y)
        faults.configure("ckpt_fail@1")
        mgr.save(step)
        with pytest.raises(CheckpointError):
            mgr.wait()


# ---------------------------------------------------------------------------
# fault class 2: NaN loss -> guard policies
# ---------------------------------------------------------------------------

@pytest.mark.fault
class TestNaNGuards:
    def test_policy_raise(self, obs_mem):
        guard = StepGuard(GuardPolicy(on_nonfinite="raise"))
        step, x, y = _make_step(guard=guard)
        step(x, y)
        faults.configure("nan_loss@1")
        with pytest.raises(NonFiniteLossError, match="non-finite"):
            step(x, y)
        evs = _events("guard")
        assert any(e["attrs"].get("reason") == "nonfinite-raise" for e in evs)

    def test_policy_skip_keeps_params_and_continues(self, obs_mem):
        guard = StepGuard(GuardPolicy(on_nonfinite="skip", max_consecutive=3))
        step, x, y = _make_step(guard=guard)
        clean_step, _, _ = _make_step()  # unguarded reference trajectory
        losses_ref = [float(clean_step(x, y)) for _ in range(3)]
        faults.configure("nan_loss@1")
        l0 = float(step(x, y))
        before = _params(step)
        l1 = float(step(x, y))  # poisoned: update gated off in-program
        assert np.isnan(l1)
        after = _params(step)
        for k in before:
            np.testing.assert_array_equal(before[k], after[k], err_msg=k)
        # the skipped step consumed a batch but not an update: the next step
        # re-walks the reference trajectory from the post-step-0 params
        l2 = float(step(x, y))
        assert l0 == losses_ref[0] and l2 == losses_ref[1]
        assert guard.skipped == 1 and guard.consecutive_bad == 0
        assert observability.counters().get("guard.nonfinite-skip") == 1

    def test_skip_budget_escalates_to_raise(self):
        guard = StepGuard(GuardPolicy(on_nonfinite="skip", max_consecutive=3))
        step, x, y = _make_step(guard=guard)
        faults.configure("nan_loss@1*5")
        step(x, y)
        step(x, y)  # bad 1 (skipped)
        step(x, y)  # bad 2 (skipped)
        with pytest.raises(NonFiniteLossError, match="consecutive"):
            step(x, y)  # bad 3: budget exhausted

    def test_policy_rollback_restores_checkpoint(self, tmp_path, obs_mem):
        guard = StepGuard(GuardPolicy(on_nonfinite="rollback", max_consecutive=2))
        step, x, y = _make_step(guard=guard)
        mgr = CheckpointManager(str(tmp_path), every_n_steps=2,
                                async_save=False, preemption=False).attach(step)
        for _ in range(2):
            step(x, y)
        ckpt_params = _params(step)  # saved at step 2
        faults.configure("nan_loss@2*2")
        with pytest.warns(UserWarning, match="rolled back"):
            step(x, y)  # bad 1
            step(x, y)  # bad 2 -> rollback to step-2 checkpoint
        assert guard.rollbacks == 1
        assert step.step_count == 2
        got = _params(step)
        for k in ckpt_params:
            np.testing.assert_array_equal(ckpt_params[k], got[k], err_msg=k)
        evs = _events("guard")
        assert any(e["attrs"].get("reason") == "rollback" for e in evs)
        # training continues from the restored state
        step(x, y)
        assert step.step_count == 3

    def test_rollback_budget_refuses_livelock(self, tmp_path):
        """A deterministic NaN source (same bad batches replayed from the
        restored cursor) must raise on the second exhausted budget instead
        of restoring the same checkpoint forever."""
        guard = StepGuard(GuardPolicy(on_nonfinite="rollback", max_consecutive=1))
        step, x, y = _make_step(guard=guard)
        mgr = CheckpointManager(str(tmp_path), every_n_steps=2,
                                async_save=False, preemption=False).attach(step)
        for _ in range(2):
            step(x, y)
        faults.configure("nan_loss@2*10")  # persists through the rollback
        with pytest.warns(UserWarning, match="rolled back"):
            step(x, y)  # bad -> rollback to step 2
        assert guard.rollbacks == 1 and step.step_count == 2
        with pytest.raises(NonFiniteLossError, match="persisted through a rollback"):
            step(x, y)  # still bad -> refuse to livelock
        assert guard.rollbacks == 1

    def test_guard_rejected_inside_no_sync_window(self):
        guard = StepGuard(GuardPolicy(on_nonfinite="skip"))
        step, x, y = _make_step(guard=guard)
        step.tmodule._no_sync_active = True
        try:
            with pytest.raises(NotImplementedError, match="no_sync"):
                step(x, y)
        finally:
            step.tmodule._no_sync_active = False

    def test_rollback_without_manager_raises(self):
        guard = StepGuard(GuardPolicy(on_nonfinite="rollback", max_consecutive=1))
        step, x, y = _make_step(guard=guard)
        step(x, y)
        faults.configure("nan_loss@1")
        with pytest.raises(NonFiniteLossError, match="no CheckpointManager"):
            step(x, y)

    def test_skip_also_gates_buffer_effects(self):
        """A skipped NaN step must not replay traced buffer mutations either:
        running stats / amax histories computed from the NaN forward would
        corrupt every later step the param gate just protected."""
        from thunder_tpu.models.resnet import BatchNorm2d

        class BNNet(nn.Module):
            def __init__(self):
                super().__init__()
                self.bn = BatchNorm2d(3)

            def forward(self, x, y):
                return ltorch.mse_loss(self.bn(x), y)

        guard = StepGuard(GuardPolicy(on_nonfinite="skip", max_consecutive=3))
        net = BNNet()
        step = TrainStep(tt.jit(net), optim.SGD(lr=0.01), guard=guard)
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(4, 3, 4, 4), jnp.float32)
        y = jnp.zeros((4, 3, 4, 4), jnp.float32)
        step(x, y)
        stats_before = {k: np.asarray(v).copy() for k, v in net.named_buffers()}
        assert "bn.running_mean" in stats_before  # the test must not be vacuous
        faults.configure("nan_loss@1")
        assert np.isnan(float(step(x, y)))
        for k, v in net.named_buffers():
            np.testing.assert_array_equal(stats_before[k], np.asarray(v),
                                          err_msg=f"buffer {k} replayed from NaN step")
        # a following clean step updates the stats again
        step(x, y)
        assert any(not np.array_equal(stats_before[k], np.asarray(v))
                   for k, v in net.named_buffers())

    def test_unguarded_step_unchanged_arity(self):
        # no guard: the program still returns the 4-tuple (no metric outputs)
        step, x, y = _make_step()
        assert float(step(x, y)) > 0


# ---------------------------------------------------------------------------
# fault class 3: transient runtime errors -> bounded retry
# ---------------------------------------------------------------------------

@pytest.mark.fault
class TestTransientRetry:
    def test_retry_recovers(self, obs_mem):
        guard = StepGuard(GuardPolicy(retry_transient=2, retry_backoff_s=0.0))
        step, x, y = _make_step(guard=guard)
        clean, _, _ = _make_step()
        ref = [float(clean(x, y)) for _ in range(3)]
        step(x, y)
        faults.configure("transient@1*2")
        losses = [float(step(x, y)), float(step(x, y))]
        assert losses == ref[1:]  # retries did not perturb the trajectory
        assert guard.retries == 2
        evs = _events("guard")
        assert sum(1 for e in evs if e["attrs"].get("reason") == "transient-retry") == 2

    def test_retry_budget_exhausted_raises(self, obs_mem):
        guard = StepGuard(GuardPolicy(retry_transient=1, retry_backoff_s=0.0))
        step, x, y = _make_step(guard=guard)
        step(x, y)
        faults.configure("transient@1*5")
        with pytest.raises(InjectedTransientError):
            step(x, y)
        evs = _events("guard")
        assert any(e["attrs"].get("reason") == "transient-exhausted" for e in evs)

    def test_no_guard_means_no_retry(self):
        step, x, y = _make_step()
        step(x, y)
        faults.configure("transient@1")
        with pytest.raises(InjectedTransientError):
            step(x, y)


# ---------------------------------------------------------------------------
# fault class 4: preemption -> drain + final checkpoint + bit-identical resume
# ---------------------------------------------------------------------------

def _token_setup(tmp_path, name="tok.bin"):
    path = str(tmp_path / name)
    toks = np.random.RandomState(99).randint(0, 1000, 5000)
    write_token_file(path, toks, token_bytes=2)
    return path


def _loader_batch(loader):
    xi, yi = loader.next_batch()
    # float views of the token batch: keeps the MSE net differentiable AND
    # the loader cursor on the resumable path
    return (jnp.asarray(xi[:, :8], jnp.float32) / 1000.0,
            jnp.zeros((xi.shape[0], 4), jnp.float32))


@pytest.mark.fault
class TestKillAndResume:
    N_STEPS = 10
    KILL_AT = 5  # 0-based step index; SIGTERM fires after it completes

    def _uninterrupted(self, token_path):
        loader = TokenLoader(token_path, batch_size=4, seq_len=32, seed=3,
                             native=False)
        step, _, _ = _make_step()
        losses = []
        for _ in range(self.N_STEPS):
            x, y = _loader_batch(loader)
            losses.append(float(step(x, y)))
        loader.close()
        return losses, _params(step)

    def test_kill_and_resume_is_bit_identical(self, tmp_path):
        """Acceptance: train N steps, SIGTERM mid-run (injected), restore in
        a FRESH TrainStep/loader, loss trajectory and final params identical
        to an uninterrupted run (numpy-fallback loader, CPU)."""
        token_path = _token_setup(tmp_path)
        ref_losses, ref_params = self._uninterrupted(token_path)

        ckdir = str(tmp_path / "ckpts")
        loader = TokenLoader(token_path, batch_size=4, seq_len=32, seed=3,
                             native=False)
        step, _, _ = _make_step()
        mgr = CheckpointManager(ckdir, every_n_steps=2, loader=loader).attach(step)
        faults.configure(f"preempt@{self.KILL_AT}")
        pre_losses = []
        try:
            for _ in range(self.N_STEPS):
                x, y = _loader_batch(loader)
                pre_losses.append(float(step(x, y)))
            pytest.fail("preemption fault never fired")
        except Preempted as e:
            assert e.step == self.KILL_AT + 1
            assert e.checkpoint_path and os.path.isdir(e.checkpoint_path)
        finally:
            mgr.close()
            loader.close()
        # steps 0..KILL_AT-1 returned their losses before the kill
        assert pre_losses == ref_losses[:self.KILL_AT]

        # fresh process equivalent: new module, TrainStep, loader, manager
        loader2 = TokenLoader(token_path, batch_size=4, seq_len=32, seed=3,
                              native=False)
        step2, _, _ = _make_step()
        mgr2 = CheckpointManager(ckdir, loader=loader2, preemption=False)
        meta = mgr2.restore(step2)
        assert meta["step"] == self.KILL_AT + 1
        assert step2.step_count == self.KILL_AT + 1
        post_losses = []
        for _ in range(self.N_STEPS - step2.step_count):
            x, y = _loader_batch(loader2)
            post_losses.append(float(step2(x, y)))
        loader2.close()
        assert post_losses == ref_losses[self.KILL_AT + 1:]
        got = _params(step2)
        for k in ref_params:
            np.testing.assert_array_equal(ref_params[k], got[k], err_msg=k)

    def test_preempted_reaches_excepthook_chain(self, tmp_path):
        """Preempted is a plain uncaught-able exception: the flight
        recorder's sys.excepthook crash dump still fires on it."""
        import sys

        step, x, y = _make_step()
        mgr = CheckpointManager(str(tmp_path), async_save=False).attach(step)
        fr.reset()
        fr.record_step(1.0)
        dump_path = str(tmp_path / "flight.json")
        os.environ["TT_FLIGHT_FILE"] = dump_path
        fr.install_crash_hook()
        try:
            faults.configure("preempt@0")
            with pytest.raises(Preempted):
                step(x, y)
            # simulate the interpreter's top-level uncaught dispatch
            try:
                raise Preempted("boom")
            except Preempted:
                sys.excepthook(*sys.exc_info())
            assert os.path.exists(dump_path)
            with open(dump_path) as f:
                assert json.load(f)["stats"]["count"] >= 1
        finally:
            fr.uninstall_crash_hook()
            os.environ.pop("TT_FLIGHT_FILE", None)
            mgr.close()
            fr.reset()


# ---------------------------------------------------------------------------
# flight recorder: checkpoint-save spike cause + obs_summary rendering
# ---------------------------------------------------------------------------

class TestCheckpointSpikeCause:
    def test_overlapping_save_names_the_spike(self, obs_mem):
        r = fr.FlightRecorder()
        for _ in range(20):
            r.record_step(2.0)
        observability.event("checkpoint_save", phase="start", step=20,
                           reason="interval")
        spike = r.record_step(40.0)
        assert spike is not None
        assert spike["cause"] == "checkpoint-save"
        assert spike["ckpt_step"] == 20

    def test_recompile_outranks_routine_save(self, obs_mem):
        from thunder_tpu.observability import metrics as obs_metrics

        r = fr.FlightRecorder()
        for _ in range(20):
            r.record_step(2.0)
        obs_metrics.record_recompile(obs_metrics.REASON_SHAPE_CHANGE, fn="f")
        observability.event("checkpoint_save", phase="done", step=20, ms=3.0)
        spike = r.record_step(40.0)
        assert spike["cause"] == "recompile"  # priority, not recency

    def test_cli_renders_ckpt_cause(self, obs_mem, tmp_path):
        import importlib.util

        r = fr.FlightRecorder()
        for _ in range(20):
            r.record_step(2.0)
        observability.event("checkpoint_save", phase="done", step=20, ms=12.5)
        r.record_step(40.0)
        shard = str(tmp_path / "t.jsonl")
        observability.dump(shard)
        spec = importlib.util.spec_from_file_location(
            "obs_summary", os.path.join(os.path.dirname(__file__), "..",
                                        "tools", "obs_summary.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out = mod.render_perf(mod.load_many([shard]))
        assert "cause=checkpoint-save" in out
        assert "save_ms=12.5" in out


# ---------------------------------------------------------------------------
# tools/ckpt_inspect.py
# ---------------------------------------------------------------------------

class TestCkptInspect:
    def _mod(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "ckpt_inspect", os.path.join(os.path.dirname(__file__), "..",
                                         "tools", "ckpt_inspect.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_valid_dir_exit_zero(self, tmp_path, capsys):
        step, x, y = _make_step()
        mgr = CheckpointManager(str(tmp_path), async_save=False,
                                preemption=False).attach(step)
        step(x, y)
        mgr.save(step, block=True)
        mod = self._mod()
        assert mod.main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "latest restorable step: 1" in out and "ok" in out

    def test_tampered_dir_exit_one(self, tmp_path, capsys):
        step, x, y = _make_step()
        mgr = CheckpointManager(str(tmp_path), async_save=False,
                                preemption=False).attach(step)
        step(x, y)
        mgr.save(step, block=True)
        stepdir = list_steps(str(tmp_path))[0][1]
        with open(os.path.join(stepdir, "meta.json"), "a") as f:
            f.write(" ")
        mod = self._mod()
        assert mod.main([str(tmp_path)]) == 1
        assert "INVALID" in capsys.readouterr().out

    def test_empty_dir_exit_two(self, tmp_path):
        mod = self._mod()
        assert mod.main([str(tmp_path)]) == 2


# ---------------------------------------------------------------------------
# distributed fault tolerance (ISSUE 14) — in-process virtual-mesh half;
# the real 2-process cluster scenarios live in tests/test_multiprocess.py
# ---------------------------------------------------------------------------

def _make_fsdp_step(guard=None, ndev=4):
    from thunder_tpu.parallel import fsdp, make_mesh

    net = _Net()
    mesh = make_mesh({"fsdp": ndev})
    tm = fsdp(tt.jit(net), mesh)
    step = TrainStep(tm, optim.AdamW(lr=1e-2), guard=guard)
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(8, 8), jnp.float32)
    y = jnp.zeros((8, 4), jnp.float32)
    return step, x, y


@pytest.mark.fault
class TestDistributedGuards:
    """The NotImplementedError at the old training.py:281 is gone: guards
    work under a mesh plan via a psum'd all-host verdict."""

    def test_fsdp_guard_skip_gates_update_in_lockstep(self, obs_mem):
        guard = StepGuard(GuardPolicy(on_nonfinite="skip", max_consecutive=3))
        step, x, y = _make_fsdp_step(guard=guard)
        l0 = float(step(x, y))
        assert not np.isnan(l0)
        assert guard.distributed  # marked by the distributed build
        before = _params(step)
        faults.configure("nan_loss@1")
        assert np.isnan(float(step(x, y)))
        after = _params(step)
        for k in before:
            np.testing.assert_array_equal(before[k], after[k], err_msg=k)
        assert guard.skipped == 1 and guard.consecutive_bad == 1
        # distributed verdicts double-book under the agreement counters
        assert observability.counters().get("guard.nonfinite-skip") == 1
        assert observability.counters().get("guard.dist_nonfinite-skip") == 1
        faults.clear()
        assert not np.isnan(float(step(x, y)))  # training continues
        assert guard.consecutive_bad == 0  # the clean step reset the budget

    def test_fsdp_guard_raise_policy(self):
        guard = StepGuard(GuardPolicy(on_nonfinite="raise"))
        step, x, y = _make_fsdp_step(guard=guard)
        step(x, y)
        faults.configure("nan_loss@1")
        with pytest.raises(NonFiniteLossError, match="non-finite"):
            step(x, y)

    def test_distributed_grad_norm_is_the_true_global_norm(self, monkeypatch):
        """The guard's reported grad norm under FSDP must equal the
        single-device global norm — per-param sum-of-squares psum'd over
        exactly the axes each param is sharded on (a blanket psum would
        overcount replicated grads; a bare local norm understates sharded
        ones by √shards)."""
        captured = {}
        orig = StepGuard.after_step

        def cap(self, ts, loss, m):
            captured[id(self)] = float(m[1])
            return orig(self, ts, loss, m)

        monkeypatch.setattr(StepGuard, "after_step", cap)
        g_ref = StepGuard(GuardPolicy(on_nonfinite="skip"))
        step_ref, x, y = _make_step(guard=g_ref)
        step_ref(x, y)
        g_dist = StepGuard(GuardPolicy(on_nonfinite="skip"))
        step_dist, xd, yd = _make_fsdp_step(guard=g_dist)
        step_dist(xd, yd)
        ref, dist = captured[id(g_ref)], captured[id(g_dist)]
        # note: the single-host net sees batch (4,8), the fsdp net (8,8) —
        # grads differ, so compare against a single-host run of the SAME
        # batch instead of cross-shape
        g_ref8 = StepGuard(GuardPolicy(on_nonfinite="skip"))
        step_ref8 = TrainStep(tt.jit(_Net()), optim.AdamW(lr=1e-2), guard=g_ref8)
        step_ref8(xd, yd)
        ref8 = captured[id(g_ref8)]
        np.testing.assert_allclose(dist, ref8, rtol=1e-5)

    def test_gspmd_guard_skip(self):
        """The compiler-partitioned road guards too: one global program, so
        the finite flag is inherently the all-host decision."""
        from thunder_tpu.parallel import make_mesh
        from thunder_tpu.parallel.gspmd import gspmd_step
        from thunder_tpu.parallel.transforms import DistPlan, ParamStrategy

        net = _Net()
        tm = tt.jit(net)
        mesh = make_mesh({"fsdp": 4})
        plan = DistPlan(mesh, data_axes=("fsdp",))
        for name, p in tm.get_parameters().items():
            plan.param_strategies[name] = [
                ParamStrategy("shard0" if p.data.shape[0] % 4 == 0 else "replicate",
                              "fsdp")]
        guard = StepGuard(GuardPolicy(on_nonfinite="skip", max_consecutive=3))
        step = gspmd_step(tm, optim.AdamW(lr=1e-2), plan, guard=guard)
        rng = np.random.RandomState(7)
        x = jnp.asarray(rng.randn(8, 8), jnp.float32)
        y = jnp.zeros((8, 4), jnp.float32)
        assert guard.distributed
        step(x, y)
        before = _params(step)
        faults.configure("nan_loss@1")
        assert np.isnan(float(step(x, y)))
        after = _params(step)
        for k in before:
            np.testing.assert_array_equal(before[k], after[k], err_msg=k)
        faults.clear()
        assert not np.isnan(float(step(x, y)))


class TestHostScopedFaults:
    def test_parse_host_scope(self):
        plan = faults.FaultPlan.parse("nan_loss@5:host=1, die@3*2:host=0")
        specs = [(f.kind, f.step, f.count, f.host) for f in plan.faults]
        assert specs == [("nan_loss", 5, 1, 1), ("die", 3, 2, 0)]

    def test_parse_rejects_bad_scope(self):
        with pytest.raises(ValueError, match="host"):
            faults.FaultPlan.parse("nan_loss@5:rank=1")
        with pytest.raises(ValueError, match="unknown fault kind"):
            faults.FaultPlan.parse("explode@5:host=1")

    def test_host_scoped_fault_fires_only_on_matching_host(self, monkeypatch):
        monkeypatch.setenv("TT_MP_PROC", "1")
        faults._reset_host_index()
        try:
            plan = faults.FaultPlan.parse("nan_loss@0:host=0,transient@0:host=1")
            assert not plan.should_fire("nan_loss", 0)   # scoped to host 0
            assert plan.should_fire("transient", 0)      # scoped to us
            # the foreign-host fault is never consumed here
            assert [f.kind for f in plan.pending()] == ["nan_loss"]
        finally:
            faults._reset_host_index()

    def test_idle_path_stays_one_global_read(self, monkeypatch):
        """PR 9's zero-work contract survives the host-scope extension: with
        no plan armed, stepping consults should_fire exactly zero times."""
        calls = {"n": 0}
        orig = faults.FaultPlan.should_fire

        def counting(self, kind, step):
            calls["n"] += 1
            return orig(self, kind, step)

        monkeypatch.setattr(faults.FaultPlan, "should_fire", counting)
        step, x, y = _make_step()
        for _ in range(3):
            step(x, y)
        assert calls["n"] == 0  # _PLAN is None: active() short-circuits all sites
        # armed but scoped to another host: sites consult the plan, nothing
        # fires, and the trajectory is untouched
        monkeypatch.setenv("TT_MP_PROC", "0")
        faults._reset_host_index()
        try:
            faults.configure("nan_loss@0*99:host=7,die@0*99:host=7")
            assert not np.isnan(float(step(x, y)))
            assert calls["n"] > 0
        finally:
            faults._reset_host_index()


@pytest.mark.fault
class TestPreemptionEscalation:
    def test_second_sigterm_escalates_without_rechaining(self, tmp_path, obs_mem):
        import signal as _signal

        chained = {"n": 0}

        def prev_handler(signum, frame):
            chained["n"] += 1

        old = _signal.signal(_signal.SIGTERM, prev_handler)
        step, x, y = _make_step()
        mgr = CheckpointManager(str(tmp_path), async_save=False).attach(step)
        try:
            _signal.raise_signal(_signal.SIGTERM)  # drain begins
            _signal.raise_signal(_signal.SIGTERM)  # impatient scheduler
            assert mgr._preempt.escalated.is_set()
            assert chained["n"] == 1  # second signal did NOT re-enter prev
            with pytest.raises(Preempted, match="escalated"):
                step(x, y)
            assert mgr.saves == 1  # immediate blocking save landed
            evs = _events("guard")
            assert any(e["attrs"].get("reason") == "preempt-escalated"
                       for e in evs)
            assert observability.counters().get("guard.preempt-escalated") == 1
        finally:
            mgr.close()
            _signal.signal(_signal.SIGTERM, old)

    def test_opt_in_sigint_drains_like_sigterm(self, tmp_path):
        import signal as _signal

        step, x, y = _make_step()
        mgr = CheckpointManager(str(tmp_path), async_save=False,
                                signals=(_signal.SIGTERM, _signal.SIGINT)
                                ).attach(step)
        old_int = _signal.getsignal(_signal.SIGINT)
        try:
            _signal.raise_signal(_signal.SIGINT)
            with pytest.raises(Preempted):
                step(x, y)
            assert mgr.saves == 1
        finally:
            mgr.close()
            _signal.signal(_signal.SIGINT, old_int)


class TestShardedCheckpointLayout:
    """Single-process coverage of the sharded layout machinery (the commit
    protocol degenerates to host 0 doing everything); the cross-host block
    paths are pinned by tests/test_multiprocess.py."""

    def _sharded_save(self, tmp_path):
        step, x, y = _make_step()
        mgr = CheckpointManager(str(tmp_path), async_save=False,
                                preemption=False, distributed=True).attach(step)
        step(x, y)
        step(x, y)
        want = _params(step)
        path = mgr.save(step, block=True)
        return step, mgr, x, y, want, path

    def test_sharded_layout_and_restore(self, tmp_path):
        step, mgr, x, y, want, path = self._sharded_save(tmp_path)
        assert path is not None
        names = sorted(os.listdir(path))
        assert "shard-0" in names and "manifest.json" in names
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        assert manifest["format"] == "checkpoint-v2-sharded"
        assert manifest["hosts"] == 1
        # every shard file is covered by the merged manifest
        assert any(rel.startswith("shard-0/") for rel in manifest["files"])
        ok, problems = validate_step(path)
        assert ok, problems
        step(x, y)  # drift
        meta = mgr.restore(step)
        assert meta["step"] == 2
        got = _params(step)
        for k in want:
            np.testing.assert_array_equal(want[k], got[k], err_msg=k)

    def test_async_sharded_save_round_trips(self, tmp_path):
        step, x, y = _make_step()
        mgr = CheckpointManager(str(tmp_path), async_save=True,
                                preemption=False, distributed=True).attach(step)
        step(x, y)
        want = _params(step)
        mgr.save(step)     # background writer runs the commit protocol
        step(x, y)         # mutate while in flight (host snapshot protects us)
        mgr.wait()
        mgr.restore(step)
        got = _params(step)
        for k in want:
            np.testing.assert_array_equal(want[k], got[k], err_msg=k)

    def test_missing_shard_refuses_restore(self, tmp_path):
        import shutil

        step, mgr, x, y, want, path = self._sharded_save(tmp_path)
        shutil.rmtree(os.path.join(path, "shard-0"))
        ok, problems = validate_step(path)
        assert not ok
        with pytest.raises(CheckpointError):
            mgr.restore(step)

    def test_ckpt_inspect_validates_and_merges_sharded(self, tmp_path, capsys):
        import importlib.util

        step, mgr, x, y, want, path = self._sharded_save(tmp_path)
        spec = importlib.util.spec_from_file_location(
            "ckpt_inspect", os.path.join(os.path.dirname(__file__), "..",
                                         "tools", "ckpt_inspect.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.main([str(tmp_path)]) == 0
        assert "shards=1/1" in capsys.readouterr().out
        # offline merge -> single-host layout that restores with stock paths
        merged = str(tmp_path / "merged")
        assert mod.main([str(tmp_path), "--merge", merged]) == 0
        step2, x2, y2 = _make_step()
        step2(x2, y2)
        mgr2 = CheckpointManager(merged, preemption=False, distributed=False)
        meta = mgr2.restore(step2)
        assert meta["step"] == 2
        got = _params(step2)
        for k in want:
            np.testing.assert_array_equal(want[k], got[k], err_msg=k)
        # a deleted host shard flips validate to exit 1 with a named host
        import shutil

        shutil.rmtree(os.path.join(path, "shard-0"))
        assert mod.main([str(tmp_path)]) == 1
        assert "missing host shard: shard-0" in capsys.readouterr().out

    def test_obs_summary_renders_shard_and_desync_sections(self, tmp_path, obs_mem):
        import importlib.util

        from thunder_tpu.observability import metrics as obs_metrics

        obs_metrics.record_ckpt_shard(0, 4, 1234, step=2)
        obs_metrics.record_ckpt_shard(1, 3, 999, step=2)
        obs_metrics.record_desync("mismatch", step=3,
                                  hosts={"0": "3:k", "1": "4:k"})
        obs_metrics.record_dist_verdict("nonfinite-skip", step=5)
        observability.event("checkpoint_save", phase="done", step=2, ms=7.5)
        shard = str(tmp_path / "t.jsonl")
        observability.dump(shard)
        spec = importlib.util.spec_from_file_location(
            "obs_summary", os.path.join(os.path.dirname(__file__), "..",
                                        "tools", "obs_summary.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        recs = mod.load_many([shard])
        out = mod.render(recs)
        assert "checkpoint / robustness" in out
        assert "host 0" in out and "host 1" in out
        assert "bytes=1234" in out
        assert "DESYNC mismatch" in out
        assert "guard.dist_nonfinite-skip" in out
        assert "ckpt_save_ms" in out
