"""LitGPT pretraining benchmark harness.

Counterpart of reference thunder/benchmarks/benchmark_litgpt.py:475-871:
reports tokens/sec (per-chip and global), model TFLOP/s, MFU, average iter
time, peak memory, and saved-for-backward size. Distributed modes map to
mesh axes instead of torchrun process groups.

Usage:
    python -m thunder_tpu.benchmarks.litgpt_bench --model_name tiny-llama2 \
        --micro_batch_size 4 --seq_len 512 [--distributed_mode fsdp --n_devices 8]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def model_flops_per_token(cfg) -> float:
    """6 * N params approximation + attention term (standard accounting)."""
    n_params = (
        cfg.padded_vocab_size * cfg.n_embd * 2
        + cfg.n_layer * (
            # attention
            cfg.n_embd * (cfg.n_head + 2 * cfg.n_query_groups) * cfg.head_size
            + cfg.n_head * cfg.head_size * cfg.n_embd
            # mlp (LLaMA 3-matrix or GptNeox 2-matrix)
            + (3 if cfg.mlp_class_name == "LLaMAMLP" else 2) * cfg.n_embd * cfg.intermediate_size
        )
    )
    return 6.0 * n_params


def peak_tflops_per_chip() -> float:
    """bf16 MXU peak of the local device (observability/flops.py)."""
    from ..observability.flops import device_peaks

    return device_peaks()[0]


def step_memory_gb(step) -> float | None:
    """Compiled-program memory estimate (args+temps+outputs-aliased)."""
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


def saved_for_backward_mib(step) -> float | None:
    """Size of the residual tensors crossing the fwd/bwd split (reference
    benchmark_litgpt.py:867 saved-for-backward accounting)."""
    try:
        entry = next(iter(step._vag._cache.values()))
        ret = entry.fwd_trc.bound_symbols[-1]
        saved = ret.args[0][1]
        total = 0
        for p in saved:
            if hasattr(p, "shape") and hasattr(p, "dtype"):
                n = 1
                for d in p.shape:
                    n *= int(d)
                total += n * p.dtype.bytes
        return round(total / 2**20, 1)
    except Exception:
        return None


def run(args) -> dict:
    import thunder_tpu as tt
    from thunder_tpu import optim
    from thunder_tpu.models.litgpt import Config, GPTForCausalLM
    from thunder_tpu.training import TrainStep

    cfg = Config.from_name(args.model_name, block_size=args.seq_len,
                           activation_checkpoint=args.activation_checkpoint)
    transforms = []
    if args.autocast:
        # fp32 master weights + bf16 compute (the standard mixed recipe)
        from thunder_tpu.transforms.autocast import AutocastTransform

        transforms.append(AutocastTransform())
        dtype = jnp.float32
    else:
        dtype = jnp.bfloat16 if args.precision == "bf16" else jnp.float32
    if getattr(args, "fp8", False):
        # delayed-scaling fp8 linears (amax-history buffers, fwd+bwd);
        # reference benchmark_litgpt.py TE fp8 role
        from thunder_tpu.transforms.fp8_training import FP8TrainingTransform

        transforms.append(FP8TrainingTransform())
    model = GPTForCausalLM(cfg, dtype=dtype)
    tm = tt.jit(model, transforms=transforms)

    n_devices = 1
    if args.distributed_mode != "none":
        from thunder_tpu.parallel import ddp, fsdp, make_mesh

        n_devices = args.n_devices or len(jax.devices())
        if args.distributed_mode == "ddp":
            mesh = make_mesh({"dp": n_devices})
            ddp(tm, mesh)
        elif args.distributed_mode == "fsdp":
            mesh = make_mesh({"fsdp": n_devices})
            fsdp(tm, mesh)
        elif args.distributed_mode == "ddp_fsdp":
            mesh = make_mesh({"dp": 2, "fsdp": n_devices // 2})
            ddp(tm, mesh)
            fsdp(tm, mesh)
        else:
            raise ValueError(args.distributed_mode)

    step = TrainStep(tm, optim.AdamW(lr=args.lr))
    rng = np.random.RandomState(0)
    B = args.micro_batch_size * (n_devices if args.distributed_mode != "none" else 1)
    idx = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, args.seq_len)), jnp.int32)
    tgt = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, args.seq_len)), jnp.int32)

    t0 = time.perf_counter()
    loss = step(idx, tgt)
    float(loss)
    compile_time = time.perf_counter() - t0

    for _ in range(args.warmup_iters):
        jax.block_until_ready(step(idx, tgt))
    t0 = time.perf_counter()
    for _ in range(args.max_iters):
        loss = step(idx, tgt)
    jax.block_until_ready(loss)  # the chained steps end here
    dt = (time.perf_counter() - t0) / args.max_iters

    tokens_per_iter = B * args.seq_len
    tokens_per_sec = tokens_per_iter / dt
    flops = model_flops_per_token(cfg) * tokens_per_iter
    tflops = flops / dt / 1e12
    result = {
        "model": args.model_name,
        "distributed_mode": args.distributed_mode,
        "n_devices": n_devices,
        "iter_time_ms": dt * 1e3,
        "tokens_per_sec_global": tokens_per_sec,
        "tokens_per_sec_per_chip": tokens_per_sec / n_devices,
        "model_tflops": tflops,
        "mfu": tflops / (peak_tflops_per_chip() * n_devices),
        "peak_memory_gb": step_memory_gb(step),
        "saved_for_backward_mib": saved_for_backward_mib(step),
        "compile_time_s": compile_time,
        "final_loss": float(loss),
    }
    for k, v in result.items():
        print(f"{k:26s} {v}")
    return result


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model_name", default="tiny-llama2")
    p.add_argument("--micro_batch_size", type=int, default=4)
    p.add_argument("--seq_len", type=int, default=512)
    p.add_argument("--max_iters", type=int, default=20)
    p.add_argument("--warmup_iters", type=int, default=3)
    p.add_argument("--lr", type=float, default=1e-4)
    p.add_argument("--precision", default="bf16", choices=["bf16", "f32"])
    p.add_argument("--activation_checkpoint", action="store_true",
                   help="recompute each block in backward (remat.checkpoint)")
    p.add_argument("--fp8", action="store_true",
                   help="delayed-scaling fp8 linears (fwd+bwd)")
    p.add_argument("--autocast", action="store_true",
                   help="fp32 master weights + bf16 compute via AutocastTransform")
    p.add_argument("--distributed_mode", default="none",
                   choices=["none", "ddp", "fsdp", "ddp_fsdp"])
    p.add_argument("--n_devices", type=int, default=0)
    run(p.parse_args())


if __name__ == "__main__":
    main()
