"""Driver ``train``: the pre-training step through ``tt.jit`` + ``TrainStep``.

A traffic file for this driver states the step: batch, sequence length,
optimizer, recomputation, and optionally a mesh (``{"fsdp": 4}``) for the
explicit distribution road. Batches come fresh from ``--seed`` through the
program's own ``prefetch_to_device``; tokens are log-uniform over the
vocabulary (a Zipf-like unigram distribution, as text has), so the loss has
something to learn and must fall.

The window starts after warm-up and ends on a ``block_until_ready`` of the last
step's loss. At most two steps are in flight, so the host cannot run ahead of
the window's end.
"""
from __future__ import annotations

import math
import time
from collections import deque

import numpy as np

from benchmark.lib import harness
from benchmark.lib.peaks import peaks

WARMUP_STEPS = 3
IN_FLIGHT = 2
TRACE_MIN_STEPS = 3


class TracedPart:
    """The traced part of a ``--trace 1`` window: the profiler starts
    ``harness.TRACE_AT`` into it and stops ``harness.TRACE_SECONDS`` and at least
    ``TRACE_MIN_STEPS`` later, on whole steps (the queue is drained before
    each). Starting and stopping the profiler takes seconds in which no step
    runs; that time is ``paused_s`` and the window does not count it."""

    def __init__(self, profiler, seconds: float):
        self.profiler, self.seconds = profiler, seconds
        self.paused_s, self.steps = 0.0, 0
        self._from_step = self._t0 = None

    def _timed(self, fn) -> None:
        t = time.perf_counter()
        fn()
        self.paused_s += time.perf_counter() - t

    def tick(self, now: float, n_steps: int, drain) -> None:
        if self.profiler is None or self.steps:
            return
        if not self.profiler.active:
            if now >= harness.TRACE_AT * self.seconds:
                drain()
                self._timed(self.profiler.start)
                self._from_step, self._t0 = n_steps, time.perf_counter()
        elif time.perf_counter() - self._t0 >= harness.TRACE_SECONDS \
                and n_steps - self._from_step >= TRACE_MIN_STEPS:
            drain()
            self.finish(n_steps)

    def finish(self, n_steps: int) -> None:
        if self.profiler is not None and self.profiler.active:
            self._timed(self.profiler.stop)
            self.steps = n_steps - self._from_step


def batches(seed: int, batch: int, seq_len: int, vocab: int):
    """Endless ``(tokens, next tokens)`` pairs, ``(batch, seq_len)`` int32 each."""
    rng = np.random.default_rng([seed, 3])
    while True:
        ranks = np.floor(vocab ** rng.random((batch, seq_len + 1))).astype(np.int64) - 1
        toks = np.clip(ranks, 0, vocab - 1).astype(np.int32)
        yield toks[:, :-1], toks[:, 1:]


def build_step(cell, devices):
    import thunder_tpu as tt
    from thunder_tpu import optim
    from thunder_tpu.training import TrainStep
    from thunder_tpu.transforms.autocast import AutocastTransform

    spec = cell.traffic["step"]
    model = cell.builder.build_loss_model(
        cell.config, cell.config_name,
        activation_checkpoint=bool(spec.get("activation_checkpoint", False)))
    tm = tt.jit(model, transforms=[AutocastTransform()] if spec.get("autocast", True) else [])
    batch_sharding = None
    mesh_axes = spec.get("mesh")
    if mesh_axes:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from thunder_tpu.parallel import ddp, fsdp, make_mesh

        if math.prod(mesh_axes.values()) != cell.chips:
            raise ValueError(f"mesh {mesh_axes} does not cover the cell's {cell.chips} chips")
        mesh = make_mesh(dict(mesh_axes), devices=devices[:cell.chips])
        if "dp" in mesh_axes:
            ddp(tm, mesh)
        if "fsdp" in mesh_axes:
            fsdp(tm, mesh)
        batch_sharding = NamedSharding(mesh, P(tuple(tm._dist_plan.data_axes)))
    elif cell.chips != 1:
        raise ValueError("a train cell on several chips needs a mesh in its traffic file")
    opt = spec.get("optimizer", {"name": "AdamW", "lr": 1e-4})
    optimizer = getattr(optim, opt["name"])(**{k: v for k, v in opt.items() if k != "name"})
    return tm, TrainStep(tm, optimizer), batch_sharding


def shard_optimizer_state(tm, step) -> None:
    """Create the optimizer's state sharded like the parameters it belongs to.

    Left to itself ``TrainStep`` calls ``optimizer.init`` at the first step, and
    ``optim.AdamW.init`` makes every moment with ``jnp.zeros(shape)``: whole, on
    the first chip — 16 GB for this benchmark's sharded model, which is the
    out-of-memory failure of my first four-chip run (PERF.md, Findings, PR 22).
    ``TrainStep.opt_state`` is a public attribute, so the harness does what a
    user would: the same ``init`` under ``jit`` with the parameters' shardings
    as its output's. The repair belongs in the program (PERF.md, Open questions)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    params = {k: p.data for k, p in tm.get_parameters().items() if p.requires_grad}
    replicated = NamedSharding(tm._dist_plan.mesh, P())
    shapes = jax.eval_shape(step.optimizer.init, params)
    shardings = {name: ({k: params[k].sharding for k in part} if isinstance(part, dict)
                        else replicated)
                 for name, part in shapes.items()}
    step.opt_state = jax.jit(step.optimizer.init, out_shardings=shardings)(params)


def reference_loss(cell, tm, idx, tgt) -> float:
    """The plain reference's mean loss over the sequences of one batch, from
    the weights as they are now."""
    import jax

    ref = cell.reference
    params = {k: p.data for k, p in tm.get_parameters().items()}
    fn = jax.jit(lambda p, x, y: ref.loss(cell.config, p, x, y, prefix="gpt."))
    return float(np.mean([float(fn(params, idx[i], tgt[i])) for i in range(idx.shape[0])]))


# the step's traces whose kernels the builder's ``kernel_claims`` states
PROGRAMS = ("forward", "backward")


def step_proofs(step) -> dict:
    """What the step can be judged from: the Pallas claims of its forward and
    backward traces where this process traced it (``forward``, ``backward``),
    and the Mosaic calls of its executable where it kept one (``mosaic_calls``;
    also an executable the artifact store served)."""
    out = {}
    if hasattr(step, "_vag"):
        cs = step._vag._cs
        out["forward"] = dict(harness.pallas_claims(cs.last_traces[-1]))
        out["backward"] = dict(harness.pallas_claims(cs.last_backward_traces[-1]))
    compiled = getattr(step._jitted, "_compiled", None)
    if compiled is not None:
        out["mosaic_calls"] = harness.mosaic_calls(compiled)
    return out


def check_kernels(cell, proofs, notes: list, compared: dict) -> dict:
    """``proofs`` (``step_proofs``) against what the builder says the model
    needs: each symbol claimed by Pallas at least as often as stated, forward
    and backward, and at least as many Mosaic kernels in the executable as the
    two together. ``proofs`` is ``None`` off the TPU, where the Pallas executor
    declines and XLA runs the decomposition: nothing to judge, but the builder
    is still asked, so that one without ``kernel_claims`` fails here."""
    want = harness.wanted_claims(cell, PROGRAMS)
    if proofs is None:
        return {}
    for name, symbols, got, count in harness.unheld_claims(want, proofs, ">=", compared):
        notes.append(f"{symbols} claimed by pallas {got} times in the {name} trace, "
                     f"not the {count} the model needs: {proofs[name]}")
    if "mosaic_calls" in proofs:
        least = sum(int(c) for name in PROGRAMS for c in want[name].values())
        if not harness.held(compared, "mosaic_calls", proofs["mosaic_calls"], ">=", least):
            notes.append(f"{proofs['mosaic_calls']} Mosaic kernels in the step, "
                         f"expected at least {least}")
    if not proofs:
        notes.append("no trace and no executable to prove the kernels from")
    return dict(proofs)


def xla_step_bytes(step):
    """What XLA's memory analysis says the step executable needs on one chip:
    arguments + temporaries + outputs - aliased. ``TrainStep.memory_analysis``
    answers on both roads: from the AOT executable on one chip, and on the
    distributed road, which keeps none, by lowering the jitted step again,
    which finds the executable the last step ran in JAX's own cache (0.01 s and
    nothing built on four chips; my chip run, PR 22). Asked only in a traced run,
    after the window, and timed on a log line so that a road that does compile
    shows."""
    t0 = time.perf_counter()
    try:
        ma = step.memory_analysis()
    except Exception as e:  # the window is measured: keep the line, say what is missing
        harness.say(f"TrainStep.memory_analysis failed: {type(e).__name__}: {e}")
        return None
    if ma is None:
        return None
    nbytes = int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                 + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    harness.say(f"XLA sizes the step at {nbytes / 2**30:.3f} GiB a chip: arguments "
                f"{ma.argument_size_in_bytes / 2**30:.3f}, temporaries "
                f"{ma.temp_size_in_bytes / 2**30:.3f}, outputs {ma.output_size_in_bytes / 2**30:.3f}, "
                f"aliased {ma.alias_size_in_bytes / 2**30:.3f} (asked in "
                f"{time.perf_counter() - t0:.2f} s)")
    return nbytes


def run(cell, opts, env) -> harness.Run:
    import jax

    from thunder_tpu import observability
    from thunder_tpu.data.prefetch import prefetch_to_device
    from thunder_tpu.executors import pallasex

    spec = cell.traffic["step"]
    B, T = int(spec["batch"]), int(spec["seq_len"])
    vocab = cell.config["vocab_size"]
    on_tpu = env.devices[0].platform == "tpu"
    notes: list = []
    compared: dict = {}
    if on_tpu and pallasex._interpret():
        notes.append("pallas kernels would run in interpret mode")
    if opts.trace:
        observability.enable()  # in memory: the counters and spans the readers use

    tm, step, sharding = build_step(cell, env.devices)
    cell.builder.reseed(tm.get_parameters(), opts.seed, cell.config)
    if sharding is not None:
        shard_optimizer_state(tm, step)
    data = prefetch_to_device(batches(opts.seed, B, T, vocab), size=2, sharding=sharding)
    spans = env.spans
    try:
        # step 0 compiles (or loads) the program; its loss is also the one the
        # reference is asked about, from the same weights and the same batch
        x0, y0 = next(data)
        ref_loss = reference_loss(cell, tm, np.asarray(x0), np.asarray(y0))
        losses = [step(x0, y0)]
        first_loss = float(losses[0])
        tol = float(cell.traffic["correctness"]["loss_tolerance"])
        harness.say(f"step 0 loss {first_loss:.6f}, reference {ref_loss:.6f}, "
                    f"difference {abs(first_loss - ref_loss):.6f} (tolerance {tol})")
        if not harness.held(compared, "step0_loss_gap", abs(first_loss - ref_loss), "<=", tol):
            notes.append(f"step 0 loss {first_loss} against the reference's {ref_loss}")
        for _ in range(WARMUP_STEPS - 1):
            losses.append(step(*next(data)))
        jax.block_until_ready(losses)
        n_warm = len(losses)

        # -- the measured window
        observability.reset()
        compiles0 = env.watch.snapshot()
        part = TracedPart(env.profiler() if opts.trace else None, opts.seconds)
        pending: deque = deque()

        def drain():
            jax.block_until_ready(list(pending))
            pending.clear()

        t_win = time.perf_counter()
        setup_s = t_win - env.t_start
        while True:
            now = time.perf_counter() - t_win - part.paused_s
            if now >= opts.seconds:
                break
            part.tick(now, len(losses), drain)
            xb, yb = next(data)
            with spans.span("step"):
                loss = step(xb, yb)
            losses.append(loss)
            pending.append(loss)
            if len(pending) > IN_FLIGHT:
                with spans.span("wait"):
                    pending.popleft().block_until_ready()
        losses[-1].block_until_ready()
        part.finish(len(losses))
        window_s = time.perf_counter() - t_win - part.paused_s
        traced = {"steps": part.steps} if part.steps else {}
        compiles = harness.CompileWatch.delta(env.watch.snapshot(), compiles0)
        counters, bus = observability.counters(), observability.records()
    finally:
        data.close()

    values = [float(v) for v in losses]
    steps = len(values) - n_warm
    bad = [v for v in values[n_warm:] if not math.isfinite(v)]
    if not harness.held(compared, "losses_not_finite",
                        sum(not math.isfinite(v) for v in values), "==", 0):
        notes.append("a loss is not finite")
    if not harness.held(compared, "steps_in_window", steps, ">=", 1):
        notes.append("no step completed inside the window")
    elif not harness.held(compared, "loss_last_five_less_first",
                          float(np.mean(values[-5:])) - values[0], "<", 0.0):
        notes.append(f"the loss did not fall: first {values[0]}, last five {values[-5:]}")
    if not harness.held(compared, "executables_built_in_window", compiles["builds"], "==", 0):
        notes.append(f"{compiles['builds']} executables were built inside the window")
    faults = harness.steady_state_faults(counters)
    if not harness.held(compared, "program_recompiles_or_fallbacks", sum(faults.values()), "==", 0):
        notes.append(f"the program counted recompiles or fallbacks in the window: {faults}")
    stats = check_kernels(cell, step_proofs(step) if on_tpu else None, notes, compared)
    stats["bytes_in_use"] = harness.memory_in_use_bytes(env.devices[:cell.chips])
    if opts.trace:
        stats["xla_step_bytes"] = xla_step_bytes(step)
    stats.update(steps=steps, tokens_per_step=B * T, first_loss=values[0],
                 last_loss=values[-1], reference_loss=ref_loss)

    tokens_per_s_per_chip = steps * B * T / window_s / cell.chips
    flops_per_token = float(cell.builder.train_flops_per_token(cell.config, T))
    stats["flops_per_token"] = flops_per_token
    harness.say(f"{steps} steps of {B} x {T} tokens in {window_s:.3f} s; loss "
                f"{values[0]:.4f} -> {values[-1]:.4f}; {flops_per_token / 1e9:.3f} GFLOP a token")
    if on_tpu:
        mfu = tokens_per_s_per_chip * flops_per_token / peaks(env.devices[0].device_kind).bf16_flops
        stats["mfu"] = mfu
        harness.say(f"{tokens_per_s_per_chip:.1f} tokens/s/chip, model FLOP/s utilization "
                    f"{100 * mfu:.2f}% of {env.devices[0].device_kind}")
    run = harness.Run(cell=cell, device_kind=env.devices[0].device_kind, chips=cell.chips,
                      window_s=window_s, attempted=steps, failed=len(bad),
                      end_to_end={"train_tokens_per_s_per_chip": tokens_per_s_per_chip,
                                  "setup_s": setup_s},
                      spans=dict(spans.durations), stats=stats, counters=counters, bus=bus,
                      compiles=compiles, traced=traced, notes=notes, compared=compared)
    if traced:
        run.trace = part.profiler.reduce(host_ops_as_device=not on_tpu)
    return run
