"""Trace lint: run every static analysis over a model pipeline and report.

Compiles real pipelines (train step, serving engine, a transform stack) on
tiny CPU configs with pass-interposed verification forced on, then prints:

  - one row per verified pass checkpoint (pass name, pipeline, bsym count,
    live-range peak estimate, status)
  - a memory-budget section: per-fusion-region live-range peaks of the
    final claimed traces, the TrainStep peak-HBM estimate, and the pallas
    VMEM fit decisions for representative kernel shapes

Usage:
    python tools/trace_lint.py                       # all pipelines
    python tools/trace_lint.py --pipeline train      # train step only
    python tools/trace_lint.py --pipeline serve      # serving drain only
    python tools/trace_lint.py --pipeline transforms # autocast+remat+int8
    python tools/trace_lint.py --deep                # + eval_shape reinference
    python tools/trace_lint.py --json                # machine-readable report

Exit codes: 0 all checkpoints clean, 1 violation(s), 2 usage/setup error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _run_train(session) -> dict:
    import jax.numpy as jnp
    import numpy as np

    import thunder_tpu as tt
    from thunder_tpu import analysis, nn, optim
    from thunder_tpu.ops import ltorch
    from thunder_tpu.training import TrainStep

    class Net(nn.Module):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(16, 32, seed=1)
            self.fc2 = nn.Linear(32, 8, seed=2)

        def forward(self, x, y):
            return ltorch.mse_loss(self.fc2(ltorch.gelu(self.fc1(x))), y)

    step = TrainStep(tt.jit(Net()), optim.AdamW(lr=1e-3))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(8, 16), jnp.float32)
    y = jnp.zeros((8, 8), jnp.float32)
    float(step(x, y))
    out = {"regions": [], "step_peak": analysis.budget.estimate_step_peak(step)}
    cs = step.compile_stats
    if cs is not None and cs.last_traces:
        out["regions"] = analysis.budget.region_peaks(cs.last_traces[-1])
        if getattr(cs, "last_backward_traces", None):
            out["regions"] += analysis.budget.region_peaks(cs.last_backward_traces[-1])
    return out


def _run_serve(session) -> dict:
    import jax.numpy as jnp

    from thunder_tpu.models.litgpt import Config, GPT
    from thunder_tpu.serving import ServingEngine

    cfg = Config.from_name("tiny-llama2", block_size=64)
    gpt = GPT(cfg, dtype=jnp.float32)
    eng = ServingEngine(gpt, max_batch=4, page_size=8, max_seq=64, dtype=jnp.float32)
    try:
        f1 = eng.submit([1, 2, 3], max_new_tokens=6, seed=1)
        f2 = eng.submit([4, 5, 6, 7, 8, 9], max_new_tokens=4, seed=2)
        eng.drain()
        f1.result(), f2.result()
    finally:
        eng.stop()
    return {}


def _run_transforms(session) -> dict:
    import jax.numpy as jnp
    import numpy as np

    import thunder_tpu as tt
    from thunder_tpu import nn, optim
    from thunder_tpu.ops import ltorch
    from thunder_tpu.training import TrainStep
    from thunder_tpu.transforms.autocast import AutocastTransform
    from thunder_tpu.transforms.quantization import QuantizeInt8Transform
    from thunder_tpu.transforms.remat import RematTransform

    class Net(nn.Module):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(16, 32, seed=3)
            self.fc2 = nn.Linear(32, 8, seed=4)

        def forward(self, x, y):
            return ltorch.mse_loss(self.fc2(ltorch.gelu(self.fc1(x))), y)

    tfs = [AutocastTransform(), RematTransform(), QuantizeInt8Transform()]
    step = TrainStep(tt.jit(Net(), transforms=tfs), optim.AdamW(lr=1e-3))
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(8, 16), jnp.float32)
    y = jnp.zeros((8, 8), jnp.float32)
    float(step(x, y))
    return {}


def _budget_table() -> list[dict]:
    """Representative pallas VMEM fit decisions through the budget API."""
    from thunder_tpu.analysis import budget

    rows = []
    for ps, D, Hkv, g, item in ((64, 128, 8, 4, 2), (16, 128, 2, 8, 2), (4096, 512, 8, 4, 4)):
        pps = budget.paged_pages_per_step(ps, D, g, item, item, n_kv_heads=Hkv)
        nb = budget.paged_decode_vmem_bytes(ps, D, g, item, item, n_kv_heads=Hkv,
                                            pages_per_step=max(pps, 1))
        rows.append({"kernel": "paged_attention_decode",
                     "shape": (f"page_size={ps} D={D} kv_heads={Hkv} g={g} itemsize={item} "
                               f"pages_per_step={pps}"),
                     "est_bytes": nb,
                     "fits": budget.within_vmem(nb, budget.paged_vmem_limit())})
    for widest, bq, bk, T in ((2, 512, 1024, 2048), (4, 512, 1024, 2048)):
        cq, ck = budget.flash_block_cap(widest, bq, bk, T, T)
        rows.append({"kernel": "flash_attention",
                     "shape": f"itemsize={widest} T={T}",
                     "est_bytes": None,
                     "fits": f"blocks {bq}x{bk} -> {cq}x{ck}"})
    return rows


PIPELINES = {"train": _run_train, "serve": _run_serve, "transforms": _run_transforms}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--pipeline", choices=[*PIPELINES, "all"], default="all")
    ap.add_argument("--deep", action="store_true",
                    help="level-2 checks: strict alias reads + eval_shape "
                         "impl re-inference (slower)")
    ap.add_argument("--json", action="store_true", dest="as_json")
    ns = ap.parse_args(argv)

    from thunder_tpu import analysis

    names = list(PIPELINES) if ns.pipeline == "all" else [ns.pipeline]
    level = 2 if ns.deep else 1
    extras: dict = {}
    violations = 0
    rows: list[dict] = []
    with analysis.override(level):
        for name in names:
            with analysis.session(estimate_memory=True) as sess:
                try:
                    extras[name] = PIPELINES[name](sess)
                except analysis.TraceCheckError as e:
                    print(f"pipeline {name}: TRACE CHECK FAILED\n{e.render()}",
                          file=sys.stderr)
                except Exception as e:
                    print(f"error: pipeline {name} failed to run: "
                          f"{type(e).__name__}: {e}", file=sys.stderr)
                    return 2
                violations += sess.violations
                for r in sess.rows:
                    rows.append({"pipeline": name, **r})

    if ns.as_json:
        print(json.dumps({"level": level, "violations": violations,
                          "checkpoints": rows, "budget": _budget_table(),
                          "extras": {k: v for k, v in extras.items() if v}},
                         indent=2, default=str))
        return 1 if violations else 0

    print(f"trace lint — level {level} ({len(rows)} checkpoints over "
          f"{', '.join(names)})\n")
    print(f"{'pipeline':<11} {'pass':<40} {'bsyms':>6} {'peak MiB':>9}  status")
    for r in rows:
        peak = r.get("peak_bytes")
        peak_s = f"{peak / 2**20:9.3f}" if peak is not None else " " * 9
        print(f"{r['pipeline']:<11} {r['pass']:<40} {r['bsyms']:>6} "
              f"{peak_s}  {r['status']}")

    print("\nmemory budget")
    for row in _budget_table():
        est = f"{row['est_bytes']:>10}" if row["est_bytes"] is not None else " " * 10
        print(f"  {row['kernel']:<24} {row['shape']:<38} {est}  {row['fits']}")
    tr = extras.get("train") or {}
    if tr.get("step_peak"):
        sp = tr["step_peak"]
        print(f"  train-step peak-HBM estimate: {sp['peak_gb']} GB "
              f"(state {sp['state_bytes']}, fwd {sp['fwd_peak_bytes']}, "
              f"bwd {sp['bwd_peak_bytes']})")
    for r in (tr.get("regions") or [])[:12]:
        print(f"  region {r['region']:<22} ({r['executor']}) iface "
              f"{r['interface_bytes']:>9} peak {r['peak_bytes']:>9}")

    if violations:
        print(f"\ntrace lint: {violations} violation(s)", file=sys.stderr)
        return 1
    print("\ntrace lint: ok")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
