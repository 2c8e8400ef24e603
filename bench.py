"""Benchmark: transformer pretraining step tokens/sec on one chip.

Runs TWO configs — llama-350m (B=4, T=2048; the Llama-2-class single-chip
shape, BASELINE.json north star) and nanogpt-124m (B=8, T=1024) — and prints
one JSON line per config, **llama-350m last** (the headline row the driver
captures).

Each row: {"metric", "value", "unit", "vs_baseline", "mfu", "tflops_per_sec",
"peak_hbm_gb", "baseline_tokens_per_sec", "compile_time_s"}.

vs_baseline compares the thunder_tpu whole-step program against the honest
competitor: the SAME model hand-written in plain jax.jit with the standard
mixed-precision recipe and fused AdamW (benchmarks/handwritten_jax.py) — the
TPU analog of the reference's "vs PyTorch eager" headline (README.md:23).
Both phases run the same precision policy (bf16 compute, f32 masters).
compile_time_s covers trace acquisition + transforms + XLA compile of the
whole fwd+bwd+optimizer program (BASELINE.json secondary metric).

Each phase runs in its own subprocess so one phase's device state is fully
released before the next.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

def _peak_tflops() -> float:
    from thunder_tpu.observability.flops import device_peaks

    return device_peaks()[0]


def _flops_per_token(cfg, T: int) -> float:
    """6*N matmul params + causal attention term (standard accounting,
    reference benchmark_litgpt.py measured-TFLOPs role)."""
    from thunder_tpu.benchmarks.litgpt_bench import model_flops_per_token

    return model_flops_per_token(cfg) + 6.0 * cfg.n_layer * cfg.n_embd * T / 2.0 * 2.0


def _mem_gb(step) -> float | None:
    try:
        ma = step.memory_analysis()
        if ma is None:
            return None
        tot = (getattr(ma, "argument_size_in_bytes", 0)
               + getattr(ma, "temp_size_in_bytes", 0)
               + getattr(ma, "output_size_in_bytes", 0)
               - getattr(ma, "alias_size_in_bytes", 0))
        return round(tot / 2**30, 3)
    except Exception:
        return None


def _device_peak_gb() -> float | None:
    import jax

    try:
        ms = jax.devices()[0].memory_stats() or {}
        peak = ms.get("peak_bytes_in_use")
        return round(peak / 2**30, 3) if peak else None
    except Exception:
        return None


def _bench_fused(model_name: str, B: int, T: int, iters: int, warmup: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import thunder_tpu as tt
    from thunder_tpu import optim
    from thunder_tpu.models.litgpt import Config, GPTForCausalLM
    from thunder_tpu.training import TrainStep

    obs_artifact = os.environ.get("BENCH_OBS_ARTIFACT")
    if obs_artifact:
        # one timeline per bench run, shared by the cold and warm phases
        # (append: each phase is a subprocess); BENCH_OBS=1 sets this up
        from thunder_tpu import observability

        observability.enable(obs_artifact, append=True)
        observability.event("bench_phase", model=model_name, B=B, T=T)

    ckpt = os.environ.get("BENCH_CKPT") == "1"
    cfg = Config.from_name(model_name, block_size=T, activation_checkpoint=ckpt)
    model = GPTForCausalLM(cfg)
    # bf16 mixed precision by default, matching the reference harness
    # (thunder/benchmarks/benchmark_litgpt.py precision default)
    transforms = []
    if os.environ.get("BENCH_PRECISION", "bf16") == "bf16":
        from thunder_tpu.transforms.autocast import AutocastTransform

        transforms.append(AutocastTransform())
    if os.environ.get("BENCH_FP8") == "1":
        # delayed-scaling fp8 linears (fwd+bwd) on top of the bf16 policy
        from thunder_tpu.transforms.fp8_training import FP8TrainingTransform

        transforms.append(FP8TrainingTransform())
    if os.environ.get("BENCH_ROAD") == "gspmd":
        # the compiler-partitioned road (parallel/gspmd.py) — on one chip
        # this measures pure road overhead vs the explicit TrainStep path
        from thunder_tpu.parallel import DistPlan, ParamStrategy, gspmd_step, make_mesh

        tm = tt.jit(model, transforms=transforms)
        # BENCH_DP>1 widens the dp axis over the visible devices (pair with
        # XLA_FLAGS=--xla_force_host_platform_device_count=N on CPU) so the
        # road runs REAL grad-sync collectives and the profiled window has
        # comms to attribute overlap on
        dp = max(1, int(os.environ.get("BENCH_DP", "1")))
        mesh = make_mesh({"dp": dp}, devices=jax.devices()[:dp])
        plan = DistPlan(mesh, {k: [ParamStrategy("replicate", "dp")]
                               for k in tm.get_parameters()}, ("dp",))
        step = gspmd_step(tm, optim.AdamW(lr=1e-4), plan)
    else:
        step = TrainStep(tt.jit(model, transforms=transforms), optim.AdamW(lr=1e-4))
    rng = np.random.RandomState(0)
    idx = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, T)), jnp.int32)
    tgt = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, T)), jnp.int32)

    # first call = trace + transforms + XLA compile (the BASELINE.json
    # secondary metric); the value read makes it a true end-to-end bound.
    # _bench_row empties the row's cache directory first, so this is an
    # honest cold number; the warm number comes from a second subprocess
    # that hits the AOT executable cache (utils/aot_cache.py) it now holds.
    t0 = time.perf_counter()
    float(step(idx, tgt))
    compile_time_s = time.perf_counter() - t0
    for _ in range(warmup - 1):
        jax.block_until_ready(step(idx, tgt))

    # BENCH_HOST=1: per-step host dispatch overhead (everything between step
    # entry and the jitted handoff) via the opt-in host_overhead event —
    # enabling the bus costs a few µs/step, so it's a separate mode
    bench_host = os.environ.get("BENCH_HOST") == "1"
    if bench_host:
        from thunder_tpu import observability

        if not observability.enabled():
            observability.enable()  # in-memory ring buffer only
        observability.reset()  # timed steps only

    # BENCH_PREFETCH=1: fresh host batches per step, device_put'd on the
    # prefetch thread (data/prefetch.py) so H2D overlaps the device step —
    # the input-pipeline-included number instead of the resident-batch one
    if os.environ.get("BENCH_PREFETCH") == "1":
        from thunder_tpu.data.prefetch import prefetch_to_device

        def _host_batches(n):
            for _ in range(n):
                yield (rng.randint(0, cfg.vocab_size, (B, T)).astype(np.int32),
                       rng.randint(0, cfg.vocab_size, (B, T)).astype(np.int32))

        stream = prefetch_to_device(_host_batches(iters), size=2)
        t0 = time.perf_counter()
        for xb, yb in stream:
            loss = step(xb, yb)
        loss_val = float(loss)
        dt = time.perf_counter() - t0
    else:
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step(idx, tgt)
        loss_val = float(loss)  # forces the whole 20-step chain
        dt = time.perf_counter() - t0
    tps = (B * T * iters) / dt

    host_overhead_us = None
    if bench_host:
        from thunder_tpu.observability import events as _obs_events

        durs = [r["attrs"]["us"] for r in _obs_events.records()
                if r.get("kind") == "event" and r.get("name") == "host_overhead"
                and r.get("attrs", {}).get("fn") == "train_step"]
        if durs:
            host_overhead_us = round(sum(durs) / len(durs), 1)

    # BENCH_OBS=1: capture a short profiled window after the timed loop and
    # attribute device time to fusion regions — `mfu_measured` is model
    # FLOPs over MEASURED device time (vs the analytic wall-clock `mfu`),
    # and the breakdown names where the non-peak fraction goes. Best-effort:
    # a profiler failure must never take the bench row down.
    mfu_measured = None
    device_breakdown = None
    if obs_artifact:
        try:
            from thunder_tpu import observability

            flops_per_step = _flops_per_token(cfg, T) * B * T
            prof = observability.profile_steps(
                lambda: float(step(idx, tgt)), n=3, warmup=1)
            if prof is not None and prof.total_device_us:
                mfu_measured = prof.mfu_measured(flops_per_step)
                s = prof.summary_dict(flops_per_step)
                device_breakdown = {k: s[k] for k in (
                    "compute_us", "collective_us", "transfer_us",
                    "unattributed_us", "attributed_frac",
                    "overlapped_comms_us", "exposed_comms_us",
                    "overlap_frac")}
                print(f"# device-time breakdown ({model_name}):", file=sys.stderr)
                print("\n".join("# " + ln for ln in prof.table(top=12).splitlines()),
                      file=sys.stderr)
        except Exception as e:
            print(f"# device profile failed ({model_name}): {e}", file=sys.stderr)

    # static live-range peak-HBM estimate (analysis/memory.py): the
    # trace-level prediction the measured device peak is judged against —
    # estimator regressions gate like perf regressions (tools/perf_gate.py).
    # Best-effort: an estimator failure must never take the bench row down.
    mem_peak_estimated = None
    est = None
    try:
        from thunder_tpu.analysis import budget as _budget

        est = _budget.estimate_step_peak(step)
        if est is not None:
            mem_peak_estimated = est["peak_gb"]
    except Exception as e:
        print(f"# mem_peak_estimated failed ({model_name}): {e}", file=sys.stderr)

    # measured peak next to the estimate (observability/memory_watch.py):
    # the device allocator's high-water mark where the backend reports one,
    # host RSS otherwise (CPU CI), tagged with its source — and the >2×
    # estimate-vs-measured reconciliation event when both are device truth
    mem_peak_measured = None
    mem_measured_source = None
    try:
        from thunder_tpu.observability import memory_watch as _mem_watch

        if est is not None:
            _mem_watch.note_estimate(est)
        m = _mem_watch.sample()
        if m is not None:
            mem_peak_measured = round(m["peak_bytes_in_use"] / 2**30, 3)
            mem_measured_source = m["source"]
            if est is not None and m["source"] == "device":
                _mem_watch.reconcile(m["peak_bytes_in_use"],
                                     est.get("peak_bytes"), context="bench")
    except Exception as e:
        print(f"# mem_peak_measured failed ({model_name}): {e}", file=sys.stderr)

    # compile-artifact-store traffic (compile_service/store.py keeps these
    # process-local counters unconditionally): the warm phase's hits are the
    # proof the cold phase's artifacts were actually served
    artifact_stats = None
    try:
        from thunder_tpu.compile_service import store as _cs_store

        if _cs_store.store_enabled():
            artifact_stats = _cs_store.get_store().stats()
    except Exception:
        pass

    return {
        "tps": tps,
        "loss": loss_val,
        "platform": jax.devices()[0].platform,
        "compile_time_s": round(compile_time_s, 1),
        "artifact_stats": artifact_stats,
        "flops_per_token": _flops_per_token(cfg, T),
        "peak_tflops": _peak_tflops(),
        "mem_gb": _mem_gb(step),
        "device_peak_gb": _device_peak_gb(),
        "mem_peak_estimated": mem_peak_estimated,
        "mem_peak_measured": mem_peak_measured,
        "mem_measured_source": mem_measured_source,
        "host_overhead_us": host_overhead_us,
        "mfu_measured": None if mfu_measured is None else round(mfu_measured, 4),
        "device_breakdown": device_breakdown,
    }


def _bench_handwritten(model_name: str, B: int, T: int, iters: int, warmup: int):
    """The honest baseline: same model/optimizer hand-written in plain jax."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from thunder_tpu.benchmarks import handwritten_jax as hw
    from thunder_tpu.models.litgpt import Config

    ckpt = os.environ.get("BENCH_CKPT") == "1"
    cfg = Config.from_name(model_name, block_size=T, activation_checkpoint=ckpt)
    compute = jnp.bfloat16 if os.environ.get("BENCH_PRECISION", "bf16") == "bf16" else jnp.float32
    params = hw.init_params(cfg)
    opt = hw.adamw_init(params)
    step = hw.make_train_step(cfg, compute_dtype=compute)
    rng = np.random.RandomState(0)
    idx = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, T)), jnp.int32)
    tgt = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, T)), jnp.int32)

    loss, params, opt = step(params, opt, idx, tgt)
    float(loss)
    for _ in range(warmup - 1):
        loss, params, opt = step(params, opt, idx, tgt)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss, params, opt = step(params, opt, idx, tgt)
    loss_val = float(loss)  # the chained steps end here
    dt = time.perf_counter() - t0
    return {"tps": (B * T * iters) / dt, "loss": loss_val}


def _run_phase(phase: str, model_name: str, B: int, T: int, iters: int,
               ckpt: bool = False, cache_root: str | None = None) -> dict:
    """Run one benchmark phase in a subprocess; returns its result JSON."""
    env = dict(os.environ)
    env["BENCH_PHASE"] = phase
    env["BENCH_MODEL"] = model_name
    env["BENCH_BATCH"] = str(B)
    env["BENCH_SEQLEN"] = str(T)
    env["BENCH_ITERS"] = str(iters)
    env["BENCH_CKPT"] = "1" if ckpt else "0"
    if cache_root is not None:
        # both compile caches placed in the row's own directory, through the
        # variables the program reads them from: run 1 is honestly cold
        # (_fresh_cache_root emptied it), run 2 is honestly warm (this run's
        # artifacts, not an operator's fleet store or an earlier round's)
        env["TT_ARTIFACT_DIR"] = os.path.join(cache_root, "artifacts")
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache_root, "xla")
    out = subprocess.run([sys.executable, os.path.abspath(__file__)], env=env,
                         capture_output=True, text=True, timeout=3000)
    if out.returncode != 0:
        raise RuntimeError(f"phase {phase} failed: {out.stderr[-500:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def _fresh_cache_root(row: str) -> str:
    """An emptied cache directory for one cold/warm ladder: a fixed path per
    row under the compile-cache root ($JAX_COMPILATION_CACHE_DIR, else the
    checkout's .tt_cache — the rule of thunder_tpu/utils/compile_cache.py,
    restated here so this parent stays off the package and off JAX)."""
    import shutil

    root = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".tt_cache")
    path = os.path.join(root, "bench", row)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def _bench_row(model_name: str, B: int, T: int, iters: int, ckpt: bool = False) -> dict:
    cache_root = _fresh_cache_root(f"{model_name}-B{B}-T{T}{'-ckpt' if ckpt else ''}")
    fused = _run_phase("fused", model_name, B, T, iters, ckpt, cache_root=cache_root)
    # warm start: a fresh process against the artifact store the cold
    # run just wrote (whole-step executable deserialization; no retrace,
    # no relowering) — artifact_hits_warm counts the served entries
    warm = _run_phase("fused", model_name, B, T, min(iters, 3), ckpt,
                      cache_root=cache_root)
    compile_time_warm_s = warm.get("compile_time_s")
    wstats = warm.get("artifact_stats") or {}
    artifact_hits_warm = wstats.get("hits")
    artifact_misses_warm = wstats.get("misses")
    fused_tps = fused["tps"]
    tflops = fused_tps * fused["flops_per_token"] / 1e12
    mfu = tflops / fused["peak_tflops"]

    baseline_tps = _run_phase("handwritten", model_name, B, T, iters, ckpt)["tps"]
    vs_baseline = fused_tps / baseline_tps

    peak_gb = fused.get("device_peak_gb") or fused.get("mem_gb")
    extra = "+ckpt" if ckpt else ""
    row = {
        "metric": f"{model_name} pretrain tokens/sec/chip (B={B}, T={T}, fwd+bwd+adamw{extra}, "
                  f"vs hand-written jax.jit of the same model)",
        "value": round(fused_tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(vs_baseline, 3),
        "baseline_tokens_per_sec": round(baseline_tps, 1),
        "tflops_per_sec": round(tflops, 1),
        "mfu": round(mfu, 3),
        "peak_hbm_gb": peak_gb,
        "compile_time_s": fused.get("compile_time_s"),
        # cold/warm ladder (compile_service): compile_time_cold_s is the
        # explicit alias of the cold first-call number so BENCH_COMPILE.json
        # and the perf gate name both ends of the ladder unambiguously
        "compile_time_cold_s": fused.get("compile_time_s"),
        "compile_time_warm_s": compile_time_warm_s,
    }
    if artifact_hits_warm is not None:
        row["artifact_hits_warm"] = artifact_hits_warm
        row["artifact_misses_warm"] = artifact_misses_warm
    # static peak-HBM estimate rides next to the measured figures so the
    # estimator's accuracy (vs peak_hbm_gb) is visible in every artifact
    if fused.get("mem_peak_estimated") is not None:
        row["mem_peak_estimated"] = fused["mem_peak_estimated"]
    if fused.get("mem_peak_measured") is not None:
        row["mem_peak_measured"] = fused["mem_peak_measured"]
        row["mem_measured_source"] = fused.get("mem_measured_source")
    # measured-MFU columns ride only when the profiled window ran (BENCH_OBS=1)
    if fused.get("mfu_measured") is not None:
        row["mfu_measured"] = fused["mfu_measured"]
    db = fused.get("device_breakdown")
    if db is not None:
        row["device_breakdown"] = db
        # the gated overlap scalars ride at TOP level: perf_gate compares
        # flat row keys, and lever #5a needs these two as its target
        if db.get("exposed_comms_us") is not None:
            row["exposed_comms_us"] = db["exposed_comms_us"]
        if db.get("overlap_frac") is not None:
            row["overlap_frac"] = db["overlap_frac"]
    return row


def _obs_row() -> dict:
    """Comms/memory observability row (BENCH_OBS_ROW=1, artifact
    BENCH_OBS.json): a profiled gspmd window with REAL grad-sync
    collectives on a dp=2 mesh, so the three ISSUE-18 gate keys —
    ``exposed_comms_us``, ``overlap_frac``, ``mem_peak_measured`` — exist
    on a committed row perf_gate can match. CPU-feasible: the dp axis runs
    on virtual host devices, and the measured peak falls back to host RSS
    (tagged ``mem_measured_source``). Knobs: BENCH_OBS_MODEL/BATCH/SEQLEN/
    ITERS (default tiny-llama2, B=2, T=128, 3 iters)."""
    import tempfile

    model_name = os.environ.get("BENCH_OBS_MODEL", "tiny-llama2")
    B = int(os.environ.get("BENCH_OBS_BATCH", "2"))
    T = int(os.environ.get("BENCH_OBS_SEQLEN", "128"))
    iters = int(os.environ.get("BENCH_OBS_ITERS", "3"))
    dp = max(2, int(os.environ.get("BENCH_DP", "2")))
    # the fused subprocess inherits this env: gspmd road over a dp-wide
    # virtual mesh, with the profiled window armed via a scratch timeline
    os.environ["BENCH_ROAD"] = "gspmd"
    os.environ["BENCH_DP"] = str(dp)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={dp}").strip()
    scratch = tempfile.NamedTemporaryFile(
        prefix="tt_bench_obs_", suffix=".jsonl", delete=False)
    scratch.close()
    os.environ.setdefault("BENCH_OBS_ARTIFACT", scratch.name)
    try:
        fused = _run_phase("fused", model_name, B, T, iters)
    finally:
        try:
            os.unlink(scratch.name)
        except OSError:
            pass
    row = {
        "metric": f"{model_name} comms/memory observability window (B={B}, "
                  f"T={T}, gspmd road, dp={dp}, profiled 3-step window)",
        "value": round(fused["tps"], 1),
        "unit": "tokens/s",
        "compile_time_s": fused.get("compile_time_s"),
    }
    if fused.get("mem_peak_estimated") is not None:
        row["mem_peak_estimated"] = fused["mem_peak_estimated"]
    if fused.get("mem_peak_measured") is not None:
        row["mem_peak_measured"] = fused["mem_peak_measured"]
        row["mem_measured_source"] = fused.get("mem_measured_source")
    db = fused.get("device_breakdown")
    if db is not None:
        row["device_breakdown"] = db
        if db.get("exposed_comms_us") is not None:
            row["exposed_comms_us"] = db["exposed_comms_us"]
        if db.get("overlap_frac") is not None:
            row["overlap_frac"] = db["overlap_frac"]
    of = row.get("overlap_frac")
    if of is not None and of < 0.85 and fused.get("platform") == "cpu":
        row["note"] = (
            "overlap_frac under the 0.85 target because this window ran on "
            "the CPU host backend: the per-backend probe in "
            "parallel/overlap.py drops all six latency-hiding/async-"
            "collective XLA options as unsupported there, so the measured "
            "fraction is the CPU backend's default schedule — the overlap "
            "levers (latency-hiding scheduler + async collectives) only "
            "engage on TPU, where the same gspmd step requests them.")
    return row


def _mfu_row(spec: str) -> dict:
    """One profiled training config for BENCH_MFU.json (BENCH_MFU=1): the
    measured-MFU row the ISSUE-19 gate holds a baseline against. Spec
    ``model:B:T[:gspmd]`` — the gspmd tag runs the GSPMD road on a dp-wide
    virtual mesh (BENCH_DP, default 2) with the collective-overlap compiler
    options armed (parallel/overlap.py), so ``overlap_frac`` /
    ``exposed_comms_us`` measure the latency-hiding scheduler's work.

    ``value`` is ``mfu_measured``: model FLOPs over MEASURED device time
    from the profiled window, against the platform peak
    (observability/flops.py DEVICE_PEAKS). When the row lands under the
    0.60 target, ``note`` states the blocking roofline bound explicitly."""
    import tempfile

    parts = spec.split(":")
    model_name, B, T = parts[0], int(parts[1]), int(parts[2])
    gspmd = "gspmd" in parts[3:]
    iters = int(os.environ.get("BENCH_MFU_ITERS", "3"))
    dp = max(2, int(os.environ.get("BENCH_DP", "2"))) if gspmd else 1
    if gspmd:
        os.environ["BENCH_ROAD"] = "gspmd"
        os.environ["BENCH_DP"] = str(dp)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={dp}").strip()
    else:
        # a prior gspmd spec in the same BENCH_MFU run must not leak its
        # road/mesh into this single-device subprocess
        os.environ.pop("BENCH_ROAD", None)
        os.environ.pop("BENCH_DP", None)
    scratch = tempfile.NamedTemporaryFile(
        prefix="tt_bench_mfu_", suffix=".jsonl", delete=False)
    scratch.close()
    os.environ["BENCH_OBS_ARTIFACT"] = scratch.name
    try:
        fused = _run_phase("fused", model_name, B, T, iters)
    finally:
        try:
            os.unlink(scratch.name)
        except OSError:
            pass
    road_tag = f"gspmd road, dp={dp}, overlap scheduling" if gspmd else "single-device"
    row = {
        "metric": f"{model_name} measured MFU (B={B}, T={T}, {road_tag}, "
                  f"fwd+bwd+adamw, profiled 3-step window)",
        "value": fused.get("mfu_measured"),
        "unit": "mfu",
        "platform": fused.get("platform"),
        "tokens_per_sec": round(fused["tps"], 1),
        "peak_tflops": fused.get("peak_tflops"),
    }
    if fused.get("mfu_measured") is not None:
        row["mfu_measured"] = fused["mfu_measured"]
    db = fused.get("device_breakdown")
    if db is not None:
        row["device_breakdown"] = db
        if db.get("exposed_comms_us") is not None:
            row["exposed_comms_us"] = db["exposed_comms_us"]
        if db.get("overlap_frac") is not None:
            row["overlap_frac"] = db["overlap_frac"]
    mfu = row.get("mfu_measured")
    if mfu is not None and mfu < 0.60 and fused.get("platform") == "cpu":
        # mfu_measured is judged against DEVICE_PEAKS["cpu"] = 1.0 TFLOP/s
        # (observability/flops.py), NOT bench's TPU-style peak_tflops column
        sustained = round(mfu * 1.0 * 1e3, 1)
        note = (
            f"Under the 0.60 target because the window ran on the CPU host "
            f"backend: single-core XLA sustained ~{sustained} GFLOP/s against "
            f"the nominal 1.0 TFLOP/s 'cpu' peak (observability/flops.py "
            f"DEVICE_PEAKS) — a host compute-roofline bound, not a "
            f"scheduling gap; the overlap/attribution columns are the "
            f"portable evidence. TPU-measured MFU for this compiler is "
            f"committed in BENCH_FP8.json (llama-350m fwd+bwd: 0.493 bf16 / "
            f"0.41 fp8 on v5e).")
        if gspmd:
            note += (
                " overlap_frac here is the CPU backend's default schedule: "
                "the probe in parallel/overlap.py drops all six latency-"
                "hiding/async-collective compiler options as unsupported on "
                "CPU, so the overlap levers only engage on TPU.")
        row["note"] = note
    return row


def _ensure_virtual_devices(n: int) -> None:
    """Arm an n-device virtual CPU mesh BEFORE the first jax import (the
    MoE/longctx modes run in-process, not via subprocess phases)."""
    if "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()


def _require_devices(n: int, mode: str) -> None:
    """The MoE and long-context modes are written for the n virtual host
    devices _ensure_virtual_devices arms; say so here, not from inside a
    mesh reshape, when the process found something else (a chip)."""
    import jax

    devs = jax.devices()
    if len(devs) < n:
        raise SystemExit(
            f"{mode} runs on {n} virtual host devices; this process has "
            f"{len(devs)} {devs[0].platform} device(s). Start it with "
            f"JAX_PLATFORMS=cpu.")


def _steady_recompiles(counters: dict) -> int:
    return sum(v for k, v in counters.items() if k.startswith("recompile."))


def _moe_rows() -> list[dict]:
    """BENCH_MOE=1 artifact rows (BENCH_MOE.json): the routed-MoE train step
    on the grouped-dispatch road vs the one-hot einsum road (same module
    weights, dispatch flag flipped) vs the handwritten-jax one-hot baseline,
    plus an EP×DP all_to_all dispatch row on one 2-D virtual mesh.

    Grouped-vs-onehot is an ALGORITHM comparison both on CPU and TPU: the
    grouped road multiplies E*cap = N*K*cf packed rows through the experts
    while the one-hot road multiplies all E*N rows, so the win scales with
    E/(K*cf). On TPU the Pallas grouped kernel additionally claims
    ltorch.grouped_mlp; on CPU the kernel's checker declines (interpret
    escape clause, named in the note) and the pure-jax decomposition of the
    same packed algorithm runs."""
    import math as _math

    import jax
    import jax.numpy as jnp
    import numpy as np

    _require_devices(8, "BENCH_MOE=1")

    from thunder_tpu import nn, observability, optim
    from thunder_tpu.analysis import budget
    from thunder_tpu.models.moe import MoEConfig, MoEMLP, publish_moe_stats
    from thunder_tpu.ops import ltorch
    from thunder_tpu.training import TrainStep

    E = int(os.environ.get("BENCH_MOE_EXPERTS", "8"))
    D = int(os.environ.get("BENCH_MOE_EMBD", "128"))
    H = int(os.environ.get("BENCH_MOE_HIDDEN", "256"))
    B, T, K, cf = 8, int(os.environ.get("BENCH_MOE_SEQLEN", "128")), 2, 1.0
    iters = int(os.environ.get("BENCH_MOE_ITERS", "10"))
    N = B * T
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, T, D).astype(np.float32))

    class MoELoss(nn.Module):
        def __init__(self, cfg):
            super().__init__()
            self.moe = MoEMLP(cfg)

        def forward(self, x):
            y = self.moe(x)
            return ltorch.sum(y * y) / (B * T)

    state = None
    roads = {}
    last_module = None
    for dispatch in ("grouped", "dense"):
        cfg = MoEConfig(n_embd=D, intermediate_size=H, n_expert=E,
                        n_expert_per_token=K, capacity_factor=cf,
                        dispatch=dispatch)
        m = MoELoss(cfg)
        if state is None:
            state = {k: np.asarray(v).copy() for k, v in m.state_dict().items()}
        else:
            m.load_state_dict(state)  # identical weights on both roads
        observability.enable()
        step = TrainStep(m, optim.AdamW(lr=1e-3))
        step(x)  # trace + compile (with the moe.* buffer refresh traced in)
        float(step(x))
        observability.reset()
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step(x)
        loss = float(loss)
        dt = (time.perf_counter() - t0) / iters
        counters = observability.counters()
        observability.disable()
        roads[dispatch] = {"s_per_step": dt,
                           "recompiles": _steady_recompiles(counters)}
        last_module = m

    # handwritten-jax baseline: the same one-hot-einsum MoE a competent jax
    # user writes directly (jax.jit value_and_grad + inline adamw)
    s = 1.0 / _math.sqrt(D)
    k0 = jax.random.PRNGKey(7)
    params = {
        "gate": jnp.asarray(rng.randn(D, E).astype(np.float32) * s),
        "w_gate": jax.random.uniform(k0, (E, D, H), jnp.float32, -s, s),
        "w_up": jax.random.uniform(jax.random.fold_in(k0, 1), (E, D, H), jnp.float32, -s, s),
        "w_down": jax.random.uniform(jax.random.fold_in(k0, 2), (E, H, D), jnp.float32, -s / 2, s / 2),
    }
    opt = {"m": jax.tree_util.tree_map(jnp.zeros_like, params),
           "v": jax.tree_util.tree_map(jnp.zeros_like, params),
           "t": jnp.zeros((), jnp.int32)}
    cap = min(N, (_math.ceil(cf * N * K / E) + 7) // 8 * 8)

    def hand_loss(p, x):
        xf = x.reshape(N, D)
        probs = jax.nn.softmax(xf @ p["gate"], -1)
        topk_probs, topk_idx = jax.lax.top_k(probs, K)
        topk_probs = topk_probs / jnp.sum(topk_probs, -1, keepdims=True)
        flat_e = topk_idx.reshape(-1)
        oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        rank = jnp.take_along_axis(jnp.cumsum(oh, 0), flat_e[:, None], 1)[:, 0] - 1
        w = topk_probs.reshape(-1) * (rank < cap)
        comb = (oh * w[:, None]).reshape(N, K, E).sum(1)  # (N, E)
        g = jnp.einsum("nd,edh->enh", xf, p["w_gate"])
        u = jnp.einsum("nd,edh->enh", xf, p["w_up"])
        y = jnp.einsum("enh,ehd->end", jax.nn.silu(g) * u, p["w_down"])
        out = jnp.einsum("end,ne->nd", y, comb)
        return jnp.sum(out * out) / (B * T)

    @jax.jit
    def hand_step(p, opt, x):
        loss, grads = jax.value_and_grad(hand_loss)(p, x)
        t = opt["t"] + 1
        b1, b2, lr, eps = 0.9, 0.999, 1e-3, 1e-8
        m_ = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, opt["m"], grads)
        v_ = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["v"], grads)
        tf = t.astype(jnp.float32)
        p = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * (m / (1 - b1 ** tf)) /
            (jnp.sqrt(v / (1 - b2 ** tf)) + eps), p, m_, v_)
        return p, {"m": m_, "v": v_, "t": t}, loss

    params, opt, _ = hand_step(params, opt, x)  # compile
    jax.block_until_ready(params["gate"])
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt, hloss = hand_step(params, opt, x)
    jax.block_until_ready(hloss)
    hand_dt = (time.perf_counter() - t0) / iters

    on_tpu = jax.devices()[0].platform == "tpu"
    block_c = _math.gcd(cap, 128)
    vmem_est = budget.grouped_mlp_vmem_bytes(block_c, D, H, 4, 4)
    observability.enable()
    publish_moe_stats(last_module)
    gauges = observability.gauges()
    moe_stats = {k: v for k, v in gauges.items() if k.startswith("moe.")}
    observability.disable()
    row = {
        "platform": jax.devices()[0].platform,
        "metric": (f"MoE train step, grouped vs one-hot dispatch (E={E}, K={K}, "
                   f"cf={cf}, d={D}, h={H}, B={B}, T={T}, fwd+bwd+adamw)"),
        "value": round(N / roads["grouped"]["s_per_step"], 1),
        "unit": "tokens/s",
        "grouped_vs_onehot": round(roads["dense"]["s_per_step"]
                                   / roads["grouped"]["s_per_step"], 3),
        "onehot_tokens_per_sec": round(N / roads["dense"]["s_per_step"], 1),
        "baseline_tokens_per_sec": round(N / hand_dt, 1),
        "vs_baseline": round(hand_dt / roads["grouped"]["s_per_step"], 3),
        "recompiles_steady_state": roads["grouped"]["recompiles"],
        "capacity": cap,
        "kernel_path": "pallas grouped_mlp" if on_tpu
                       else "pure-jax decomposition (kernel checker declines off-TPU)",
        "vmem_grouped_estimate_bytes": int(vmem_est),
        "vmem_within_budget": bool(budget.within_vmem(vmem_est)),
        "moe_gauges": moe_stats,
    }
    if not on_tpu:
        row["note"] = (
            "CPU escape clause: the Pallas grouped kernel's checker declines "
            "off-TPU (interpret mode is a correctness road, not a perf road "
            "— tests pin TT_GROUPED_KERNEL=1 interpret A/B bit-identity), so "
            "grouped_vs_onehot here measures the DISPATCH ALGORITHM: "
            f"E*cap={E * cap} packed rows vs E*N={E * N} one-hot rows "
            "through the same SwiGLU experts. The same packing drives the "
            "MXU kernel on TPU, where the gap widens with the kernel's "
            "per-expert grid.")

    # EP×DP: experts over ep, tokens batch-sharded over (dp, ep), ONE mesh
    from thunder_tpu.parallel.expert_parallel import moe_ep_forward
    from thunder_tpu.parallel.mesh import make_mesh

    n_dev = jax.device_count()
    ep = min(4, n_dev)
    dp = max(1, n_dev // ep)
    mesh = make_mesh({"dp": dp, "ep": ep})
    ep_params = {"gate_w": params["gate"], "w_gate": params["w_gate"],
                 "w_up": params["w_up"], "w_down": params["w_down"]}
    xf = jnp.asarray(rng.randn(N, D).astype(np.float32))
    ep_fn = jax.jit(lambda p, x: moe_ep_forward(
        p, x, mesh=mesh, axis="ep", dp_axis="dp", n_expert_per_token=K,
        return_stats=True))
    out, stats = ep_fn(ep_params, xf)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out, stats = ep_fn(ep_params, xf)
    jax.block_until_ready(out)
    ep_dt = (time.perf_counter() - t0) / iters
    ep_row = {
        "platform": jax.devices()[0].platform,
        "metric": (f"MoE EP×DP all_to_all dispatch forward (E={E} over "
                   f"ep={ep}, dp={dp}, N={N}, d={D}, h={H}, drop-free)"),
        "value": round(N / ep_dt, 1),
        "unit": "tokens/s",
        "expert_load_max": round(float(jnp.max(stats["expert_load"])), 4),
        "dropped_tokens": int(stats["dropped_tokens"]),
        "router_entropy": round(float(stats["router_entropy"]), 4),
    }
    return [row, ep_row]


def _longctx_rows() -> list[dict]:
    """BENCH_LONGCTX=1 artifact rows (BENCH_LONGCTX.json): (1) the
    32k-context train step through the product path — tt.jit +
    context_parallel ring attention over an sp=8 virtual mesh + TrainStep —
    with steady-state recompiles counted after warmup; (2) the GQA-native
    ring attention forward vs a handwritten-jax ring that replicates KV
    heads (the idiom this PR removed); (3) a 32k paged serve: chunked
    prefill + decode through the ServingEngine with the compile counters
    proving the bucket ladder admits 32k with zero steady-state recompiles."""
    import math as _math

    import jax
    import jax.numpy as jnp
    import numpy as np

    _require_devices(8, "BENCH_LONGCTX=1")

    import thunder_tpu as tt
    from thunder_tpu import observability, optim
    from thunder_tpu.analysis import budget
    from thunder_tpu.models.litgpt import Config, GPT, GPTForCausalLM
    from thunder_tpu.parallel import make_mesh
    from thunder_tpu.parallel.context_parallel import (
        _ring_attention_impl, context_parallel)
    from thunder_tpu.training import TrainStep, _shard_map_compat

    T = int(os.environ.get("BENCH_LONGCTX_T", "32768"))
    sp = min(8, jax.device_count())
    T_loc = T // sp
    iters = int(os.environ.get("BENCH_LONGCTX_ITERS", "1"))
    rng = np.random.RandomState(0)
    rows = []

    # --- row 1: 32k-context train step (context_parallel product path) ---
    cfg = Config.from_name("tiny", block_size=T, n_layer=1, n_head=2,
                           n_query_groups=1, n_embd=32, vocab_size=512)
    model = GPTForCausalLM(cfg)
    observability.enable()
    tm = tt.jit(model)
    context_parallel(tm, make_mesh({"sp": sp}))
    step = TrainStep(tm, optim.SGD(lr=1e-4))
    idx = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, T)), jnp.int32)
    tgt = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, T)), jnp.int32)
    t0 = time.perf_counter()
    loss = float(step(idx, tgt))
    compile_s = time.perf_counter() - t0
    observability.reset()
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(idx, tgt)
    loss = float(loss)
    dt = (time.perf_counter() - t0) / iters
    counters = observability.counters()
    observability.disable()
    D_head = cfg.n_embd // cfg.n_head
    block_q = min(512, T_loc)
    ring_est = budget.ring_flash_vmem_bytes(block_q, T_loc, D_head, 4, 4)
    on_tpu = jax.devices()[0].platform == "tpu"
    rows.append({
        "platform": jax.devices()[0].platform,
        "metric": (f"{T}-context train step, ring attention over sp={sp} "
                   f"(GQA {cfg.n_head}q/{cfg.n_query_groups}kv, n_embd="
                   f"{cfg.n_embd}, 1 layer, fwd+bwd+sgd)"),
        "value": round(T / dt, 1),
        "unit": "tokens/s",
        "s_per_step": round(dt, 2),
        "compile_time_s": round(compile_s, 1),
        "loss": round(loss, 4),
        "recompiles_steady_state": _steady_recompiles(counters),
        "vmem_ring_estimate_bytes": int(ring_est),
        "vmem_within_budget": bool(budget.within_vmem(ring_est)),
        "kernel_path": "pallas streaming ring-flash" if on_tpu
                       else "pure-jax GQA-native ring (kernel checker declines off-TPU)",
    })

    # --- row 2: GQA-native ring vs handwritten replicated-KV ring ---
    from jax.sharding import PartitionSpec as P

    B, Hq, Hkv, Dh = 1, 4, 2, 16
    mesh = make_mesh({"sp": sp})
    q = jnp.asarray(rng.randn(B, Hq, T, Dh).astype(np.float32))
    k = jnp.asarray(rng.randn(B, Hkv, T, Dh).astype(np.float32))
    v = jnp.asarray(rng.randn(B, Hkv, T, Dh).astype(np.float32))
    spec = P(None, None, "sp")
    ours = jax.jit(_shard_map_compat(
        lambda q, k, v: _ring_attention_impl(q, k, v, axis="sp", causal=True,
                                             world_size=sp),
        mesh, (spec, spec, spec), spec))

    def hand_ring(q, k, v):
        # the pre-GQA idiom: replicate KV heads to Hq, then ring with a
        # plain natural-exp online softmax
        g = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
        Bq, H, Tl, Dq = q.shape
        my = jax.lax.axis_index("sp")
        scale = 1.0 / _math.sqrt(Dq)
        q_pos = my * Tl + jnp.arange(Tl)
        perm = [(j, (j + 1) % sp) for j in range(sp)]

        def stp(carry, i):
            o, m, l, kb, vb = carry
            src = (my - i) % sp
            s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                           kb.astype(jnp.float32)) * scale
            k_pos = src * Tl + jnp.arange(Tl)
            s = jnp.where((k_pos[None, :] <= q_pos[:, None])[None, None],
                          s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, -1))
            safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.where(jnp.isfinite(s), jnp.exp(s - safe[..., None]), 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)
            l = l * corr + p.sum(-1)
            o = o * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, vb.astype(jnp.float32))
            return (o, m_new, l, jax.lax.ppermute(kb, "sp", perm),
                    jax.lax.ppermute(vb, "sp", perm)), None

        o0 = jnp.zeros((Bq, H, Tl, Dq), jnp.float32)
        m0 = jnp.full((Bq, H, Tl), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((Bq, H, Tl), jnp.float32)
        (o, m, l, _, _), _ = jax.lax.scan(stp, (o0, m0, l0, k, v),
                                          jnp.arange(sp))
        return (o / jnp.where(l == 0.0, 1.0, l)[..., None]).astype(q.dtype)

    hand = jax.jit(_shard_map_compat(hand_ring, mesh, (spec, spec, spec), spec))
    timings = {}
    for name, fn in (("ours", ours), ("hand", hand)):
        out = fn(q, k, v)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(q, k, v)
        jax.block_until_ready(out)
        timings[name] = (time.perf_counter() - t0) / iters
    rows.append({
        "platform": jax.devices()[0].platform,
        "metric": (f"ring attention forward at T={T}, GQA-native vs "
                   f"replicated-KV handwritten ring (B={B}, {Hq}q/{Hkv}kv "
                   f"heads, D={Dh}, sp={sp})"),
        "value": round(T / timings["ours"], 1),
        "unit": "tokens/s",
        "baseline_tokens_per_sec": round(T / timings["hand"], 1),
        "vs_baseline": round(timings["hand"] / timings["ours"], 3),
        "kv_bytes_on_ring_ours": int(2 * B * Hkv * T_loc * Dh * 4),
        "kv_bytes_on_ring_baseline": int(2 * B * Hq * T_loc * Dh * 4),
    })
    if not on_tpu:
        rows[-1]["note"] = (
            "GQA-native keeps Hkv heads on the ring (kv_bytes_on_ring halved "
            "vs the replicated-KV idiom). On the virtual-CPU mesh ppermute "
            "is a process-local memcpy, so the ICI-bandwidth saving cannot "
            "show in wall time — vs_baseline here isolates the compute-side "
            "cost of the grouped einsums; the byte columns carry the win "
            "that matters on a real ring.")

    # --- row 3: 32k paged serve (chunked prefill through the engine) ---
    from thunder_tpu.serving import ServingEngine

    chunk = 512
    prompt_len = T - 2 * chunk  # full chunks only; leaves decode headroom
    scfg = Config.from_name("tiny", block_size=T, n_layer=1, n_head=2,
                            n_query_groups=1, n_embd=32, vocab_size=512)
    gpt = GPT(scfg, dtype=jnp.float32)
    engine = ServingEngine(gpt, max_batch=2, page_size=16, max_seq=T,
                           dtype=jnp.float32, chunk_tokens=chunk)
    observability.enable()
    engine.start()
    warm_prompt = rng.randint(0, scfg.vocab_size, (2 * chunk,)).astype(np.int32)
    engine.submit(warm_prompt, max_new_tokens=4).result(timeout=600)
    observability.reset()
    prompt = rng.randint(0, scfg.vocab_size, (prompt_len,)).astype(np.int32)
    t0 = time.perf_counter()
    res = engine.submit(prompt, max_new_tokens=8).result(timeout=3600)
    wall = time.perf_counter() - t0
    counters = observability.counters()
    stats = engine.stats()
    observability.disable()
    engine.stop()
    g = scfg.n_head // scfg.n_query_groups
    head = scfg.n_embd // scfg.n_head
    q_tile, heads, pps = budget.paged_chunk_blocks(16, head, g, chunk, 4, 4,
                                                   n_kv_heads=scfg.n_query_groups)
    chunk_est = budget.paged_chunk_vmem_bytes(16, head, g, q_tile or chunk, 4, 4,
                                              heads=max(heads, 1), pages_per_step=max(pps, 1))
    rows.append({
        "platform": jax.devices()[0].platform,
        "metric": (f"{T}-context paged serve: {prompt_len}-token prompt, "
                   f"chunked prefill (chunk={chunk}) + 8 decode tokens, "
                   f"page_size=16"),
        "value": round(prompt_len / res.ttft_s, 1),
        "unit": "prefill tokens/s",
        "ttft_ms": round(res.ttft_s * 1e3, 1),
        "wall_s": round(wall, 2),
        "n_new_tokens": res.n_new_tokens,
        "recompiles_steady_state": _steady_recompiles(counters),
        "peak_page_pool_utilization": stats["peak_page_pool_utilization"],
        "pages_for_request": prompt_len // 16 + 1,
        "vmem_chunk_estimate_bytes": int(chunk_est),
        "vmem_within_budget": bool(budget.within_vmem(
            chunk_est, budget.paged_vmem_limit())),
    })
    return rows


def _compile_ladder_row(model_name: str, B: int, T: int, iters: int = 3) -> dict:
    """One cold→warm compile ladder measurement (BENCH_COMPILE=1): a cold
    process against an empty artifact store, then a fresh process against
    the store it wrote. No handwritten baseline — the metric is start-up
    latency, and `artifact_hits_warm` proves the store (not a residual
    in-process cache) served the warm start."""
    cache_root = _fresh_cache_root(f"ladder-{model_name}-B{B}-T{T}")
    cold = _run_phase("fused", model_name, B, T, iters, cache_root=cache_root)
    warm = _run_phase("fused", model_name, B, T, iters, cache_root=cache_root)
    cold_s = cold.get("compile_time_s")
    warm_s = warm.get("compile_time_s")
    wstats = warm.get("artifact_stats") or {}
    return {
        "metric": f"{model_name} compile ladder (B={B}, T={T}, cold store -> "
                  f"warm store, fresh process each)",
        "compile_time_cold_s": cold_s,
        "compile_time_warm_s": warm_s,
        "warm_over_cold": round(warm_s / cold_s, 3) if cold_s and warm_s is not None else None,
        "artifact_hits_warm": wstats.get("hits"),
        "artifact_misses_warm": wstats.get("misses"),
        "unit": "s",
    }


def main():
    iters = int(os.environ.get("BENCH_ITERS", "20"))
    phase = os.environ.get("BENCH_PHASE", "")

    if os.environ.get("BENCH_OBS") == "1" and "BENCH_OBS_ARTIFACT" not in os.environ:
        # observability timeline artifact next to BENCH_LATEST.jsonl; the
        # fused phases (subprocesses) append their spans/counters to it —
        # inspect with `python tools/obs_summary.py OBS_TIMELINE.jsonl`
        artifact = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "OBS_TIMELINE.jsonl")
        open(artifact, "w").close()  # fresh timeline per bench run
        os.environ["BENCH_OBS_ARTIFACT"] = artifact

    if phase:
        if phase not in ("fused", "handwritten"):
            raise SystemExit(f"unknown BENCH_PHASE {phase!r} (expected fused|handwritten)")
        model_name = os.environ.get("BENCH_MODEL", "llama-350m")
        B = int(os.environ.get("BENCH_BATCH", "4"))
        T = int(os.environ.get("BENCH_SEQLEN", "2048"))
        fn = _bench_fused if phase == "fused" else _bench_handwritten
        print(json.dumps(fn(model_name, B, T, iters=iters, warmup=3)))
        return

    if os.environ.get("BENCH_OBS_ROW") == "1":
        # comms/memory observability artifact (ISSUE 18): one row whose
        # exposed_comms_us / overlap_frac / mem_peak_measured keys the perf
        # gate can hold a baseline against — regenerate with
        #   BENCH_OBS_ROW=1 python bench.py
        row = _obs_row()
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_OBS.json")
        with open(out_path, "w") as f:
            json.dump([row], f, indent=1, sort_keys=True)
            f.write("\n")
        print(json.dumps(row), flush=True)
        print(f"# wrote {out_path}", file=sys.stderr)
        return

    if os.environ.get("BENCH_MFU") == "1":
        # measured-MFU artifact (ISSUE 19): profiled training configs with
        # the overlap levers armed; best config first so perf_gate's
        # higher-is-better mfu_measured baseline tracks the headline row.
        # Regenerate with BENCH_MFU=1 python bench.py
        # (BENCH_MFU_ROWS="model:B:T[:gspmd],..." overrides the configs).
        specs = os.environ.get(
            "BENCH_MFU_ROWS", "tiny-llama2:2:128:gspmd,tiny-llama2:4:128").split(",")
        rows = []
        for spec in specs:
            row = _mfu_row(spec)
            rows.append(row)
            print(json.dumps(row), flush=True)
        rows.sort(key=lambda r: r.get("mfu_measured") or 0.0, reverse=True)
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_MFU.json")
        with open(out_path, "w") as f:
            json.dump(rows, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote {out_path}", file=sys.stderr)
        return

    if os.environ.get("BENCH_MOE") == "1":
        # sparse-frontier artifact (ISSUE 20): the routed-MoE train step on
        # the grouped-dispatch road vs the one-hot einsum road vs a
        # handwritten-jax one-hot baseline, plus an EP×DP all_to_all row.
        # Regenerate with BENCH_MOE=1 python bench.py
        _ensure_virtual_devices(8)
        rows = _moe_rows()
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_MOE.json")
        with open(out_path, "w") as f:
            json.dump(rows, f, indent=1, sort_keys=True)
            f.write("\n")
        for row in rows:
            print(json.dumps(row), flush=True)
        print(f"# wrote {out_path}", file=sys.stderr)
        return

    if os.environ.get("BENCH_LONGCTX") == "1":
        # long-context artifact (ISSUE 20): 32k-context train step over the
        # ring, GQA-native ring vs replicated-KV handwritten ring, and a 32k
        # paged serve with chunked prefill. The 32k rows take minutes on the
        # virtual-CPU mesh; BENCH_LONGCTX_T shrinks T for smoke runs.
        # Regenerate with BENCH_LONGCTX=1 python bench.py
        _ensure_virtual_devices(8)
        rows = _longctx_rows()
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_LONGCTX.json")
        with open(out_path, "w") as f:
            json.dump(rows, f, indent=1, sort_keys=True)
            f.write("\n")
        for row in rows:
            print(json.dumps(row), flush=True)
        print(f"# wrote {out_path}", file=sys.stderr)
        return

    if os.environ.get("BENCH_COMPILE") == "1":
        # cold→warm compile ladder artifact (compile_service acceptance:
        # warm first-step wall time well under cold). Rows from
        # BENCH_COMPILE_ROWS ("model:B:T,..."); the default regenerates the
        # SAME rows as the committed BENCH_COMPILE.json so perf_gate can
        # match metric strings against the baseline.
        specs = os.environ.get("BENCH_COMPILE_ROWS",
                               "nanogpt-124m:1:256,tiny-llama2:2:256").split(",")
        rows = []
        for spec in specs:
            name, B, T = spec.split(":")[:3]
            row = _compile_ladder_row(name, int(B), int(T),
                                      iters=min(iters, 3))
            rows.append(row)
            print(json.dumps(row), flush=True)
        out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "BENCH_COMPILE.json")
        with open(out_path, "w") as f:
            json.dump(rows, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote {out_path}", file=sys.stderr)
        return

    # headline LAST: the driver records the final line. llama-350m is the
    # Llama-2-class single-chip shape (BASELINE.json north star).
    # BENCH_MODEL/BENCH_BATCH/BENCH_SEQLEN select a single custom row instead.
    if "BENCH_MODEL" in os.environ:
        rows = (f"{os.environ['BENCH_MODEL']}:{os.environ.get('BENCH_BATCH', '4')}"
                f":{os.environ.get('BENCH_SEQLEN', '2048')}")
        if os.environ.get("BENCH_CKPT") == "1":
            rows += ":ckpt"
    else:
        rows = os.environ.get(
            "BENCH_ROWS", "nanogpt-124m:8:1024,llama-1b:1:2048:ckpt,llama-350m:4:2048")
    failed = []
    for spec in rows.split(","):
        parts = spec.split(":")
        name, B, T = parts[0], parts[1], parts[2]
        ckpt = "ckpt" in parts[3:]
        try:
            print(json.dumps(_bench_row(name, int(B), int(T), iters, ckpt)), flush=True)
        except (RuntimeError, subprocess.TimeoutExpired) as e:
            # a failed phase (cold, warm or the baseline) fails its row and,
            # at the end, the run — but must not swallow the rows after it
            print(f"# bench row {name} failed: {e}", file=sys.stderr)
            failed.append(name)
    if failed:
        raise SystemExit(f"bench rows failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
