"""BENCH_7B.json: the 7B-shape evidence set on one chip.

The north star (BASELINE.json; reference benchmark_litgpt.py:475-479) is
Llama-2-7B tokens/sec — the full 32-layer model's AdamW state cannot fit one
16 GB v5e chip (1.07 GB params x 12 bytes f32 master+moments alone is
~13 GB x 8 = impossible at 32 layers), so the honest single-chip evidence is:

1. the 7B-shape microbench targets (one full-dims attention layer, one MLP,
   QKV+RoPE at width 4096 / head_dim 128), and
2. a 4-block 7B-dims stack (``llama-7b-block4``: everything per-layer is
   EXACTLY Llama-2-7B's shape; only depth is truncated) trained end-to-end —
   fwd+bwd+AdamW with activation checkpointing at B=1, T=2048 — through the
   same bench.py machinery as every other row, with MFU and the
   hand-written-jax vs_baseline column.

Run on chip:  python -m thunder_tpu.benchmarks.bench_7b
Writes BENCH_7B.json at the repo root (or $BENCH_7B_OUT).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time


_TARGETS = ("llama2_7b_attention", "llama_mlp_7b", "litgpt_qkv_rope")


def _measure_targets() -> list[dict]:
    """Runs in the child started by run_targets: this is the process that
    initialises JAX and holds the chip while it measures."""
    import numpy as np

    from . import targets

    rows = []
    for name in _TARGETS:
        t0 = time.perf_counter()
        seconds = targets.BENCHMARKS[name](np.random.RandomState(0))
        rows.append({
            "target": name,
            "ms": round(seconds * 1e3, 2),
            "wall_s": round(time.perf_counter() - t0, 1),
        })
    return rows


def run_targets() -> list[dict]:
    """The microbench targets, in a child of their own: a chip belongs to one
    process at a time, and this parent goes on to start bench.py children
    that need it, so the parent itself never initialises JAX."""
    out = subprocess.run([sys.executable, "-m", "thunder_tpu.benchmarks.bench_7b", "targets"],
                         capture_output=True, text=True, timeout=3600)
    if out.returncode != 0:
        raise RuntimeError(f"7B-shape targets failed: {out.stderr[-800:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def run_block_stack(B: int = 1, T: int = 2048, iters: int = 10) -> dict:
    """The 4-block 7B-dims train step through bench.py's row machinery."""
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env = dict(os.environ)
    env.update({
        "BENCH_MODEL": "llama-7b-block4",
        "BENCH_BATCH": str(B),
        "BENCH_SEQLEN": str(T),
        "BENCH_CKPT": "1",
        "BENCH_ITERS": str(iters),
    })
    out = subprocess.run([sys.executable, os.path.join(repo, "bench.py")],
                         env=env, capture_output=True, text=True, timeout=3600)
    if out.returncode != 0:
        raise RuntimeError(f"block-stack bench failed: {out.stderr[-800:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> None:
    if sys.argv[1:] == ["targets"]:
        print(json.dumps(_measure_targets()))
        return
    result = {
        "comment": ("7B-shape single-chip evidence: per-layer dims are exactly "
                    "Llama-2-7B's (width 4096, head_dim 128, MLP 11008, vocab 32k); "
                    "the stack row is a 4-block depth truncation (the deepest whose "
                    "f32 AdamW state fits 16 GB), fwd+bwd+adamw+ckpt"),
        "targets_ms": run_targets(),
        "block_stack": run_block_stack(),
    }
    out_path = os.environ.get("BENCH_7B_OUT", "BENCH_7B.json")
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result["block_stack"]))


if __name__ == "__main__":
    main()
