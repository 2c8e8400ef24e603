"""HF model coverage harness — the reference's jit-coverage job
(examples/coverage/jit_coverage_hf.py) rebuilt for the torch interop frontend.

Loads small randomly-initialized configs for N architectures, traces each
through ``interop.torch_frontend`` (forward AND backward), compares against
torch eager, and reports per-model status plus which torch ops fell back to
the host-eager path (the coverage signal: a fallback is correct but slow).

Usage:
    python -m thunder_tpu.interop.hf_coverage [--models gpt2,llama,...]
    # writes the report table to --out (default HF_COVERAGE.md: a generated
    # file, not committed)
"""
from __future__ import annotations

import argparse
import json
import re
import time
import traceback

import numpy as np


def _configs():
    from transformers import (
        BertConfig,
        GemmaConfig,
        GPT2Config,
        LlamaConfig,
        MistralConfig,
        Qwen2Config,
    )

    common = dict(vocab_size=256, max_position_embeddings=128)
    return {
        "gpt2": (GPT2Config(n_layer=2, n_head=2, n_embd=64, vocab_size=256,
                            n_positions=128, use_cache=False), "causal"),
        "llama": (LlamaConfig(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                              num_attention_heads=4, num_key_value_heads=2,
                              use_cache=False, **common), "causal"),
        "mistral": (MistralConfig(hidden_size=64, intermediate_size=128,
                                  num_hidden_layers=2, num_attention_heads=4,
                                  num_key_value_heads=2, sliding_window=None,
                                  use_cache=False, **common), "causal"),
        "qwen2": (Qwen2Config(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                              num_attention_heads=4, num_key_value_heads=2,
                              use_cache=False, **common), "causal"),
        "gemma": (GemmaConfig(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                              num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                              use_cache=False, **common), "causal"),
        # eager attention: transformers' sdpa path probes `0 in attention_mask`
        # (data-dependent host branch — untraceable by design)
        "bert": (BertConfig(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                            num_attention_heads=4, vocab_size=256,
                            max_position_embeddings=128,
                            attn_implementation="eager"), "masked"),
    }


def _scrape_fallbacks(warning_list) -> list[str]:
    """Torch ops that hit the host-eager path, from the frontend's warning."""
    return sorted({
        m.group(1) for wi in warning_list
        for m in [re.search(r"no mapping for ([\w.]+)", str(wi.message))] if m})


def run_model(name: str, cfg, kind: str, *, check_backward: bool = True) -> dict:
    import warnings

    import jax.numpy as jnp
    import torch
    from transformers import AutoModelForCausalLM, AutoModelForMaskedLM

    import thunder_tpu as tt
    from thunder_tpu.interop import torch_frontend as tf

    torch.manual_seed(0)
    cls = AutoModelForCausalLM if kind == "causal" else AutoModelForMaskedLM
    model = cls.from_config(cfg).eval()
    ids = torch.randint(0, cfg.vocab_size, (2, 16))
    # masked-LM models get an explicit all-ones mask: without one,
    # transformers probes `pad_token_id in input_ids` just to warn (a
    # data-dependent host branch). Causal models take the opposite choice:
    # an explicit mask routes them into the `0 in attention_mask` sdpa
    # pruning probe — equally untraceable — so they pass none.
    mask = torch.ones_like(ids) if kind == "masked" else None
    mask_kw = {"attention_mask": mask} if mask is not None else {}

    rec: dict = {"model": name, "status": "ok", "fallbacks": [], "max_abs_err": None,
                 "bwd_max_rel_err": None}
    t0 = time.time()
    try:
        with torch.no_grad():
            ref = model(input_ids=ids, **mask_kw).logits
        tf._eager_warned.clear()
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            ctm = tt.jit(model)
            out = ctm(input_ids=ids, **mask_kw)
        logits = out["logits"] if isinstance(out, dict) else getattr(out, "logits", out[0])
        err = float(np.max(np.abs(np.asarray(logits) - ref.numpy())))
        rec["max_abs_err"] = err
        rec["fallbacks"] = _scrape_fallbacks(w)
        if err > 1e-2:
            rec["status"] = f"numerics ({err:.2e})"

        if check_backward and rec["status"] == "ok":
            # fwd+bwd vs torch autograd: a torch wrapper computes the scalar
            # loss so the TorchModuleValueAndGrad path (grads per param name)
            # applies
            class LossWrap(torch.nn.Module):
                def __init__(self, inner):
                    super().__init__()
                    self.inner = inner

                def forward(self, input_ids, attention_mask=None):
                    kw = {"attention_mask": attention_mask} if attention_mask is not None else {}
                    return self.inner(input_ids=input_ids, **kw).logits.float().pow(2).mean()

            wrap = LossWrap(model)
            loss_t = wrap(ids, mask) if mask is not None else wrap(ids)
            loss_t.backward()
            named = {n: p for n, p in wrap.named_parameters() if p.grad is not None}
            tname, tparam = max(named.items(), key=lambda kv: float(kv[1].grad.abs().sum()))

            ctm_loss = tt.jit(wrap)
            vag_args = (ids, mask) if mask is not None else (ids,)
            tf._eager_warned.clear()  # fwd dedup must not hide bwd fallbacks
            with warnings.catch_warnings(record=True) as wb:
                warnings.simplefilter("always")
                lval, grads = tt.value_and_grad(ctm_loss)(*vag_args)
            rec["fallbacks"] = sorted(set(rec["fallbacks"]) | set(_scrape_fallbacks(wb)))
            g = grads.get(tname)
            if g is None:
                rec["status"] = f"bwd: no grad entry for {tname}"
            else:
                rel = float(np.max(np.abs(np.asarray(g) - tparam.grad.numpy()))
                            / (np.max(np.abs(tparam.grad.numpy())) + 1e-12))
                rec["bwd_max_rel_err"] = rel
                if not np.isclose(float(lval), float(loss_t), rtol=1e-3):
                    rec["status"] = f"bwd loss mismatch ({float(lval):.4f} vs {float(loss_t):.4f})"
                elif rel > 5e-2:
                    rec["status"] = f"bwd numerics ({rel:.2e})"
    except Exception as e:
        rec["status"] = f"error: {type(e).__name__}: {str(e)[:160]}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["seconds"] = round(time.time() - t0, 1)
    return rec


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--models", default=None, help="comma list; default all")
    p.add_argument("--out", default="HF_COVERAGE.md")
    p.add_argument("--no-backward", action="store_true")
    args = p.parse_args(argv)

    cfgs = _configs()
    names = args.models.split(",") if args.models else list(cfgs)
    rows = []
    for n in names:
        cfg, kind = cfgs[n]
        rec = run_model(n, cfg, kind, check_backward=not args.no_backward)
        print(json.dumps({k: v for k, v in rec.items() if k != "traceback"}))
        rows.append(rec)

    lines = [
        "# HF model coverage (torch interop frontend)",
        "",
        "Counterpart of the reference's jit-coverage job "
        "(`examples/coverage/jit_coverage_hf.py`): each architecture is traced "
        "fwd+bwd through `interop/torch_frontend.py` on randomly-initialized "
        "small configs and compared against torch eager. `fallbacks` lists "
        "torch ops that ran host-eager (correct but slow — lowering TODOs).",
        "",
        "| model | status | fwd max abs err | bwd max rel err | host-eager fallbacks |",
        "|---|---|---|---|---|",
    ]
    for r in rows:
        fb = ", ".join(r["fallbacks"]) if r["fallbacks"] else "none"
        lines.append(
            f"| {r['model']} | {r['status']} | "
            f"{r['max_abs_err'] if r['max_abs_err'] is not None else '—'} | "
            f"{r['bwd_max_rel_err'] if r['bwd_max_rel_err'] is not None else '—'} | {fb} |")
    # regenerate the table but carry over hand-measured sections appended
    # after it (e.g. the timed KV-cache generation artifact)
    extra = ""
    try:
        prev = open(args.out).read()
        cut = prev.find("\n## ")
        if cut != -1:
            extra = prev[cut:]
    except OSError:
        pass
    with open(args.out, "w") as f:
        f.write("\n".join(lines) + "\n" + extra)
    ok = sum(1 for r in rows if r["status"] == "ok")
    print(f"# {ok}/{len(rows)} architectures ok -> {args.out}")
    return rows


if __name__ == "__main__":
    main()
