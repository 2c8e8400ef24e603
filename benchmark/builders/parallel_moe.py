"""Builder ``parallel_moe``: a ``cohere2_moe`` configuration file (Cohere's Command A+: the keys
of the model's own public ``config.json``) -> the program's model,
``thunder_tpu.models.parallel_moe.ParallelMoE``, as one chip of an expert-parallel group holds it.

``num_experts`` in the file counts the experts HELD here (``experts_held`` names the range),
``reduced_from.num_experts`` is the router's published width, which the model keeps. Under
``assumed`` the file holds what the published file has no key for. This module maps the keys
onto ``parallel_moe.Config``, builds the model through the program's own constructor and
replaces its weights with ones made on the device from ``--seed`` through
``builders/latent_moe.py``'s ``seeded_params`` (one compiled function serves every layer of a
kind, so that what is alive beside the 9.47 GB of weights is one layer's temporaries).
``benchmark/reference/parallel_moe.py`` reads the same keys on its own, so a wrong mapping here
shows as a disagreement. The model is served only: there is no ``build_loss_model``.
"""
from __future__ import annotations

import os

from benchmark.lib import manifest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# weights from the seed as `builders/latent_moe.py` makes them (norm weights one, the embedding normal
# with `embedding_std`, the router's selection bias with `router_bias_std` (0: this family has none),
# every other matrix and panel with `initializer_range`, drawn on the device one layer at a time)
_latent_moe = manifest.load_module(_ROOT, "builders", "latent_moe")
reseed, seeded_params = _latent_moe.reseed, _latent_moe.seeded_params

KINDS = {"sliding_attention": "window", "full_attention": "full"}


def layer_types(config: dict) -> tuple:
    """The kind of each layer the file keeps: the first ``num_hidden_layers`` of the published
    ``layer_types``."""
    return tuple(KINDS[t] for t in config["layer_types"][:config["num_hidden_layers"]])


def n_routed(config: dict) -> int:
    return int(config.get("reduced_from", {}).get("num_experts", config["num_experts"]))


def model_keys(config: dict) -> dict:
    """Keyword arguments of ``parallel_moe.Config`` for a published configuration."""
    a = config["assumed"]
    if config["model_type"] != "cohere2_moe":
        raise ValueError(f"builder parallel_moe does not know model_type {config['model_type']!r}")
    if (config["attention_bias"] or not config["tie_word_embeddings"] or not config["use_parallel_block"]
            or config["use_qk_norm"] or config["first_k_dense_replace"] or a["layer_norm_bias"]
            or not config["use_gated_activation"] or config["hidden_act"] != "silu"):
        raise ValueError("builder parallel_moe maps a parallel block of gated SiLU experts in every "
                         "layer, no bias, no q/k norm and a tied head only")
    if (config["position_embedding_type"] != "rope_gptj" or config["rotary_pct"] != 1
            or a["full_layer_positions"] != "none" or config["expert_selection_fn"] != "sigmoid"
            or config["shared_expert_combination_strategy"] != "average" or config["logit_scale"] != 1):
        raise ValueError("builder parallel_moe maps the layer its model is written to only: GPT-J rope "
                         "over the whole head in the window layers, none in the full ones, a sigmoid "
                         "router, the shared experts averaged, logit_scale 1")
    held = tuple(int(e) for e in config["experts_held"])
    if held[1] - held[0] != config["num_experts"]:
        raise ValueError(f"experts_held {held} are not the {config['num_experts']} num_experts")
    return dict(
        block_size=min(config["max_position_embeddings"], a["rope_table_rows"]),
        vocab_size=config["vocab_size"], n_embd=config["hidden_size"],
        n_head=config["num_attention_heads"], n_query_groups=config["num_key_value_heads"],
        head_size=config["head_dim"], sliding_window=config["sliding_window"],
        layer_types=layer_types(config), rope_base=float(config["rope_theta"]),
        norm_eps=config["layer_norm_eps"], expert_width=config["intermediate_size"],
        n_routed_experts=n_routed(config), experts_held=held,
        n_expert_per_token=config["num_experts_per_tok"],
        n_shared_experts=config["num_shared_experts"], norm_topk_prob=config["norm_topk_prob"])


def dims(config: dict) -> dict:
    """The sizes the cost functions of ``benchmark/lib/costs_parallel_moe.py`` need."""
    kinds = layer_types(config)
    lo, hi = config["experts_held"]
    return dict(n_layer=config["num_hidden_layers"], d_model=config["hidden_size"],
                heads=config["num_attention_heads"], kv_heads=config["num_key_value_heads"],
                head_dim=config["head_dim"], window=config["sliding_window"],
                layers={k: kinds.count(k) for k in ("window", "full")},
                expert_width=config["intermediate_size"], experts_held=hi - lo,
                n_routed=n_routed(config), experts_per_token=config["num_experts_per_tok"],
                n_shared=config["num_shared_experts"], vocab=config["vocab_size"])


def kernel_claims(config: dict) -> dict:
    """What this model needs Pallas to have claimed, ``{program: {symbols: count}}``: every
    layer's routed rows go through the ragged expert kernel; in the decode program every layer's
    one query a sequence through the paged decode kernel, and in the chunk program every layer's
    chunk rows through the paged chunk kernel: in both, the three window layers' calls with their
    window and the full layer's without (``harness.pallas_claims`` counts by symbol; the device
    trace tells the two apart, ``window_decode`` and ``window_chunk`` against ``paged_decode`` and
    ``paged_chunk`` in ``benchmark/kernels/``)."""
    n = config["num_hidden_layers"]
    return {"decode_cfn": {"thunder.paged_attention": n, "thunder.ragged_mlp": n},
            "chunk_cfn": {"thunder.paged_chunk_attention": n, "thunder.ragged_mlp": n}}


def build_serving_model(config: dict, name: str, dtype):
    """The served model, weights in ``dtype``."""
    from thunder_tpu.models.parallel_moe import Config, ParallelMoE

    return ParallelMoE(Config(name=name, **model_keys(config)), dtype=dtype)
