"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py

One process, one TPU, the entry points a user calls, at the full width and
depth of llama-350m with random weights from a seed:

* trainer — ``tt.jit(GPTForCausalLM, [AutocastTransform])`` + ``TrainStep``
  with AdamW at B=4, T=2048: a forward-only loss,
  then a handful of steps on one fixed batch;
* meshes — only where the host has four chips: the same step through
  ``fsdp`` on ``{"fsdp": 4}`` and ``ddp`` x ``fsdp`` on ``{"dp": 2, "fsdp": 2}``,
  judged against the one-chip phase of this very process;
* server — ``ServingEngine`` over the bf16 GPT with paged KV and chunked
  prefill: three requests alone, then eight of mixed lengths at once.

Every phase checks its results (finite falling loss, the forward-only loss
against the step's, every request's token count, alone == batched) and reads
the program's own records for proof that the chip's kernels ran: Pallas not in
interpret mode, the Pallas symbols claimed in the executed traces, and no
recompile or fallback counted after warm-up. Any failure ends the run with a
non-zero exit code and no result line. Without a TPU it refuses to run. The
last line of standard output is one JSON object naming the device as JAX
reports it.
"""
from __future__ import annotations

import gc
import json
import sys
import time
from collections import Counter
from importlib import metadata

MODEL = "llama-350m"
BATCH = 4
TRAIN_STEPS = 6
LR = 1e-4
# loss is ~ln(32000) = 10.4 in bf16 compute: two roads to the same forward
# (fused cross-entropy kernel vs the step's decomposition, one chip vs a
# mesh's reduction order) agree far inside this
LOSS_TOL = 0.05

# serving: pages and a context a deployment of this model would use
MAX_BATCH = 8
PAGE_SIZE = 64
MAX_SEQ = 2048
CHUNK_TOKENS = 512
# (prompt tokens, new tokens): whole-prompt prefill in the buckets 256 and
# 512, and prompts past CHUNK_TOKENS that go through chunked paged prefill
# (their last chunk rounds up to 512 too, so three programs prefill them all)
REQUESTS = [(130, 32), (250, 48), (400, 64), (512, 40),
            (800, 56), (900, 32), (1000, 64), (1024, 48)]
ALONE = (1, 3, 6)  # indexes into REQUESTS also served alone, one per program


def check(cond, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def pallas_claims(trace) -> Counter:
    """Symbols of an executed trace that the Pallas executor runs, by id,
    looking inside the XLA fusion regions. A composite the executor did not
    claim is not here at all: it was decomposed into its prims."""
    from thunder_tpu.executors import pallasex, xlaex

    out: Counter = Counter()

    def walk(bsyms):
        for b in bsyms:
            if b.sym.executor is xlaex.ex:
                walk(b.subsymbols)
            elif b.sym.executor is pallasex.ex or (
                    b.impl is not None and b.impl is pallasex.ex.get_impl(b.sym.id)):
                out[b.sym.id] += 1

    walk(trace.bound_symbols)
    return out


def mosaic_calls(compiled) -> int:
    """Mosaic (compiled Pallas) kernels in a jax executable: the
    tpu_custom_call custom-calls of its HLO. Also answers for an executable
    the artifact store served, which no trace in this process describes."""
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def steady_state_faults(counters: dict) -> dict:
    """Counters that must stay at zero once every program is compiled."""
    return {k: v for k, v in counters.items()
            if v and (k.startswith("recompile.") or k == "compile.prewarm_fallback"
                      or k == "aot.save_failed")}


def gib(nbytes: int) -> str:
    return f"{nbytes / 2**30:.2f} GiB"


def fixed_batch(cfg):
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(0)
    shape = (BATCH, cfg.block_size)
    return (jnp.asarray(rng.randint(0, cfg.vocab_size, shape), jnp.int32),
            jnp.asarray(rng.randint(0, cfg.vocab_size, shape), jnp.int32))


def run_steps(step, idx, tgt):
    """TRAIN_STEPS steps on one batch. Returns the losses; the counters of
    steps 1.. (after the compile in step 0) must show no recompile."""
    import jax
    import numpy as np

    from thunder_tpu import observability

    losses = [float(step(idx, tgt))]
    observability.reset()
    pending = [step(idx, tgt) for _ in range(TRAIN_STEPS - 1)]
    jax.block_until_ready(pending)
    losses += [float(x) for x in pending]
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    faults = steady_state_faults(observability.counters())
    check(not faults, f"recompiles or fallbacks after the first step: {faults}")
    return losses


def check_flash_claims(step, n_layer: int, label: str) -> None:
    """The step's executed forward and backward traces claim the flash
    kernels (rope-fused or plain) in every layer."""
    fwd = pallas_claims(step._vag._cs.last_traces[-1])
    bwd = pallas_claims(step._vag._cs.last_backward_traces[-1])
    say(f"{label}: pallas claims forward {dict(fwd)} backward {dict(bwd)}")
    check(fwd["pallas.rope_flash_fwd"] + fwd["pallas.flash_attention_fwd"] == n_layer,
          f"{label}: flash attention forward not claimed by pallas in every layer")
    check(bwd["pallas.rope_flash_bwd"] + bwd["pallas.flash_attention_bwd"] == n_layer,
          f"{label}: flash attention backward not claimed by pallas in every layer")


def train_phase(device):
    import jax.numpy as jnp
    import numpy as np

    import thunder_tpu as tt
    from thunder_tpu import optim
    from thunder_tpu.models.litgpt import Config, GPTForCausalLM
    from thunder_tpu.training import TrainStep, _CompiledWithFallback
    from thunder_tpu.transforms.autocast import AutocastTransform

    t0 = time.perf_counter()
    cfg = Config.from_name(MODEL)
    model = GPTForCausalLM(cfg)
    # the same initial weights for every later mesh phase
    init = {k: np.asarray(p.data) for k, p in model.named_parameters()}
    tm = tt.jit(model, transforms=[AutocastTransform()])
    step = TrainStep(tm, optim.AdamW(lr=LR))
    idx, tgt = fixed_batch(cfg)

    # forward-only loss (what an evaluation pass runs): the road on which the
    # fused cross-entropy and RMSNorm kernels are claimed
    eval_loss = float(tm(idx, tgt))
    fwd_only = pallas_claims(tt.last_traces(tm)[-1])
    say(f"train: forward-only loss {eval_loss:.4f}, pallas claims {dict(fwd_only)}")
    check(fwd_only["torch.nn.functional.cross_entropy"] == 1,
          "fused cross-entropy not claimed by pallas in the forward-only trace")
    check(fwd_only["torch.nn.functional.rms_norm"] == 2 * cfg.n_layer + 1,
          "RMSNorm not claimed by pallas in the forward-only trace")

    losses = run_steps(step, idx, tgt)
    say(f"train: {TRAIN_STEPS} steps at B={BATCH} T={cfg.block_size}, losses "
        f"{[round(x, 4) for x in losses]}")
    # the whole step runs as one AOT-compiled executable, compiled here on a
    # cold start or served by the artifact store on a warm one; if it is gone
    # the step fell back to the retrace path. One flash forward and one
    # single-pass flash backward per layer are in it as Mosaic kernels.
    exe = step._jitted
    check(isinstance(exe, _CompiledWithFallback) and exe._compiled is not None,
          "the step is not running its AOT-compiled executable (it fell back "
          "to the retrace path, or the artifact store is switched off)")
    traced = hasattr(step, "_vag")
    n_mosaic = mosaic_calls(exe._compiled)
    say(f"train: step executable {'compiled in this process' if traced else 'served by the artifact store'}, "
        f"{n_mosaic} Mosaic kernels in its HLO")
    check(n_mosaic == 2 * cfg.n_layer,
          f"{n_mosaic} Mosaic kernels in the step, expected {2 * cfg.n_layer}")
    if traced:
        check_flash_claims(step, cfg.n_layer, "train")
    check(abs(eval_loss - losses[0]) < LOSS_TOL,
          f"forward-only loss {eval_loss} and the first step's loss {losses[0]} disagree")
    mem = device.memory_stats()
    say(f"train: HBM in use {gib(mem['bytes_in_use'])}, peak "
        f"{gib(mem['peak_bytes_in_use'])} of {gib(mem['bytes_limit'])}; "
        f"{time.perf_counter() - t0:.0f} s wall, compiles included")
    return {"init": init, "loss0": losses[0], "bytes_in_use": mem["bytes_in_use"]}


def mesh_phase(axes: dict, devices, one_chip: dict):
    """The train step over four chips through fsdp / ddp x fsdp."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    import thunder_tpu as tt
    from thunder_tpu import optim
    from thunder_tpu.models.litgpt import Config, GPTForCausalLM
    from thunder_tpu.parallel import ddp, fsdp, make_mesh
    from thunder_tpu.training import TrainStep
    from thunder_tpu.transforms.autocast import AutocastTransform

    t0 = time.perf_counter()
    cfg = Config.from_name(MODEL)
    model = GPTForCausalLM(cfg)
    for k, p in model.named_parameters():
        p.data = jnp.asarray(one_chip["init"][k])
    tm = tt.jit(model, transforms=[AutocastTransform()])
    mesh = make_mesh(axes, devices=devices)
    if "dp" in axes:
        ddp(tm, mesh)
    fsdp(tm, mesh)
    step = TrainStep(tm, optim.AdamW(lr=LR))
    losses = run_steps(step, *fixed_batch(cfg))
    say(f"mesh {axes}: losses {[round(x, 4) for x in losses]}")
    check_flash_claims(step, cfg.n_layer, f"mesh {axes}")
    check(abs(losses[0] - one_chip["loss0"]) < LOSS_TOL,
          f"mesh {axes}: step-0 loss {losses[0]} against {one_chip['loss0']} on one chip")

    params = tm.get_parameters()
    spread = set()
    for name, p in params.items():
        sh = p.data.sharding
        check(isinstance(sh, NamedSharding), f"mesh {axes}: {name} is {type(sh).__name__}")
        spread |= sh.device_set
    check(spread == set(devices),
          f"mesh {axes}: parameters live on {len(spread)} of {len(devices)} devices")
    in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
    say(f"mesh {axes}: {len(params)} parameters sharded over {len(spread)} devices; "
        f"HBM in use per chip {[gib(b) for b in in_use]} against "
        f"{gib(one_chip['bytes_in_use'])} on one chip; "
        f"{time.perf_counter() - t0:.0f} s wall, compile included")
    check(max(in_use) < one_chip["bytes_in_use"],
          f"mesh {axes}: a chip holds no less than the one-chip run")
    check(max(in_use) - min(in_use) < 0.05 * max(in_use),
          f"mesh {axes}: uneven HBM use {in_use}")


def serve_phase(device):
    import jax.numpy as jnp
    import numpy as np

    import thunder_tpu as tt
    from thunder_tpu import observability
    from thunder_tpu.models.litgpt import GPT, Config
    from thunder_tpu.serving import ServingEngine

    t0 = time.perf_counter()
    cfg = Config.from_name(MODEL)
    gpt = GPT(cfg, dtype=jnp.bfloat16)
    engine = ServingEngine(gpt, max_batch=MAX_BATCH, page_size=PAGE_SIZE,
                           max_seq=MAX_SEQ, dtype=jnp.bfloat16,
                           chunk_tokens=CHUNK_TOKENS)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32) for n, _ in REQUESTS]
    engine.start()
    try:
        # alone: one request per compiled program (prefill buckets 256 and
        # 512, the chunk program, and decode) — the warm-up and the reference
        alone = {i: engine.submit(prompts[i], REQUESTS[i][1]).result(timeout=900)
                 for i in ALONE}
        observability.reset()
        futures = [engine.submit(p, n_new) for p, (_, n_new) in zip(prompts, REQUESTS)]
        results = [f.result(timeout=900) for f in futures]
        counters = observability.counters()
        stats = engine.stats()
    finally:
        engine.stop()

    for (n_prompt, n_new), r in zip(REQUESTS, results):
        check(r.n_new_tokens == n_new and r.new_tokens.shape == (n_new,),
              f"request {r.request_id}: {r.n_new_tokens} of {n_new} tokens ({r.finish_reason})")
        check(r.tokens.shape == (n_prompt + n_new,), f"request {r.request_id}: bad length")
        check(((r.new_tokens >= 0) & (r.new_tokens < cfg.padded_vocab_size)).all(),
              f"request {r.request_id}: token out of the vocabulary")
    for i, solo in alone.items():
        check(np.array_equal(solo.new_tokens, results[i].new_tokens),
              f"request of {REQUESTS[i]} decodes differently alone and in the batch")
    faults = steady_state_faults(counters)
    check(not faults, f"recompiles or fallbacks after warm-up: {faults}")
    check(counters.get("serve.retired") == len(REQUESTS),
          f"retired {counters.get('serve.retired')} of {len(REQUESTS)} requests")

    claims = {name: pallas_claims(tt.last_traces(getattr(engine.runner, name)._cfn)[-1])
              for name in ("prefill_cfn", "chunk_cfn", "decode_cfn")}
    say(f"serve: {len(ALONE)} requests alone + {len(REQUESTS)} at once, "
        f"{sum(n for _, n in REQUESTS)} tokens generated in {stats['decode_steps']} "
        f"decode steps, peak page pool use {stats['peak_page_pool_utilization']}")
    say(f"serve: pallas claims {({k: dict(v) for k, v in claims.items()})}")
    check(claims["decode_cfn"]["thunder.paged_attention"] == cfg.n_layer,
          "thunder.paged_attention not claimed by pallas in the decode step")
    check(claims["chunk_cfn"]["thunder.paged_chunk_attention"] == cfg.n_layer,
          "thunder.paged_chunk_attention not claimed by pallas in chunked prefill")
    for name, c in claims.items():
        check(c["torch.nn.functional.rms_norm"] == 2 * cfg.n_layer + 1,
              f"RMSNorm not claimed by pallas in {name}")
    mem = device.memory_stats()
    say(f"serve: HBM in use {gib(mem['bytes_in_use'])}, peak "
        f"{gib(mem['peak_bytes_in_use'])}; {time.perf_counter() - t0:.0f} s wall, "
        f"compiles included")


def main() -> int:
    import jax
    import jaxlib

    devices = jax.devices()
    first = devices[0]
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    say(f"platform: {first.platform}, device_kind: {first.device_kind}, "
        f"devices: {len(devices)}, python {sys.version.split()[0]}, "
        f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu {libtpu}")
    if first.platform != "tpu":
        say(f"refusing to run: jax found platform {first.platform!r}, not a TPU")
        return 2

    from thunder_tpu import observability
    from thunder_tpu.executors import pallasex
    from thunder_tpu.utils import compile_cache

    check(not pallasex._interpret(), "pallas kernels would run in interpret mode")
    observability.enable()  # in memory: the counters the phases read

    one_chip = train_phase(first)
    say(f"compile cache: {compile_cache.cache_dir()}")
    gc.collect()
    if len(devices) >= 4:
        for axes in ({"fsdp": 4}, {"dp": 2, "fsdp": 2}):
            mesh_phase(axes, devices[:4], one_chip)
            gc.collect()
    del one_chip
    gc.collect()
    serve_phase(first)

    peak = first.memory_stats()["peak_bytes_in_use"]
    say(f"ok: trained {TRAIN_STEPS} steps and served {len(ALONE) + len(REQUESTS)} "
        f"requests at {MODEL} width on {first.device_kind}; peak HBM {gib(peak)}")
    print(json.dumps({"ok": True, "device": {"platform": first.platform,
                                             "kind": first.device_kind,
                                             "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
