"""Builder ``block_moe``: an ``sdar_moe`` configuration file (SDAR's mixture-of-experts chat
models: the keys of the model's own public ``config.json``, Qwen3-MoE's) -> the program's model,
``thunder_tpu.models.block_moe.BlockMoE``, every expert held.

The file holds the published keys and, under ``generation`` and ``assumed``, what the published
file has no key for (how a block is generated; the q and k head norms; the router's precision;
the initialisers). This module maps the keys onto ``block_moe.Config``, builds the model through
the program's own constructor and replaces its weights with ones made on the device from
``--seed`` through ``builders/latent_moe.py``'s ``seeded_params`` (one compiled function serves
every layer, so that what is alive beside the 8.72 GB of weights is one layer's temporaries).
``benchmark/reference/block_moe.py`` reads the same keys on its own, so a wrong mapping here
shows as a disagreement. ``block_diffusion(config)`` is the keyword ``ServingEngine`` takes for
the file's ``generation`` group. The model is served only: there is no ``build_loss_model``.
"""
from __future__ import annotations

import os

from benchmark.lib import manifest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# weights from the seed as `builders/latent_moe.py` makes them (norm gains one, the embedding normal with
# `embedding_std`, every other matrix and panel with `initializer_range`, drawn on the device one layer
# at a time); the router's selection bias, which this family lacks, at `router_bias_std` 0
_latent_moe = manifest.load_module(_ROOT, "builders", "latent_moe")
reseed, seeded_params = _latent_moe.reseed, _latent_moe.seeded_params


def model_keys(config: dict) -> dict:
    """Keyword arguments of ``block_moe.Config`` for a published configuration."""
    a = config["assumed"]
    if config["model_type"] != "sdar_moe":
        raise ValueError(f"builder block_moe does not know model_type {config['model_type']!r}")
    if config["attention_bias"] or config["tie_word_embeddings"] or config["mlp_only_layers"] \
            or config["decoder_sparse_step"] != 1 or config["use_sliding_window"] or config["rope_scaling"]:
        raise ValueError("builder block_moe maps expert layers throughout, no bias, no window, "
                         "plain rope and an untied head only")
    if not a["qk_norm"] or a["router_dtype"] != "float32" or a["rope_pairing"] != "rotate_half" \
            or a["logits_shift"] != 0:
        raise ValueError("builder block_moe maps the assumptions its model is written to only")
    return dict(
        block_size=min(config["max_position_embeddings"], a["rope_table_rows"]),
        vocab_size=config["vocab_size"], padded_vocab_size=config["vocab_size"],
        n_layer=config["num_hidden_layers"], n_embd=config["hidden_size"],
        n_head=config["num_attention_heads"], n_query_groups=config["num_key_value_heads"],
        head_size=config["head_dim"], norm_eps=config["rms_norm_eps"], rope_base=config["rope_theta"],
        norm_qk=True, n_expert=config["num_experts"], n_expert_per_token=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"], norm_topk_prob=config["norm_topk_prob"])


def block_diffusion(config: dict) -> dict:
    """``ServingEngine(block_diffusion=)`` for the file's ``generation`` group."""
    g = config["generation"]
    return {"block_length": g["block_length"], "denoising_steps": g["denoising_steps"],
            "strategy": g["remasking_strategy"], "threshold": g["confidence_threshold"],
            "mask_id": g["mask_token_id"]}


def dims(config: dict) -> dict:
    """The sizes the cost functions of ``benchmark/lib/costs_block_moe.py`` need."""
    return dict(n_layer=config["num_hidden_layers"], d_model=config["hidden_size"],
                heads=config["num_attention_heads"], kv_heads=config["num_key_value_heads"],
                head_dim=config["head_dim"], expert_width=config["moe_intermediate_size"],
                experts_held=config["num_experts"], n_routed=config["num_experts"],
                experts_per_token=config["num_experts_per_tok"], vocab=config["vocab_size"],
                block_length=config["generation"]["block_length"])


def kernel_claims(config: dict) -> dict:
    """What this model needs Pallas to have claimed, ``{program: {symbols: count}}``: in a pass
    over blocks every layer's routed rows go through the ragged expert kernel and its K rows a
    sequence through the paged chunk kernel (the block's last position as every row's coverage);
    a prompt chunk's rows likewise (block-causal coverage)."""
    n = config["num_hidden_layers"]
    both = {"thunder.ragged_mlp": n, "thunder.paged_chunk_attention": n}
    return {"block_cfn": dict(both), "chunk_cfn": dict(both)}


def build_serving_model(config: dict, name: str, dtype):
    """The served model, weights in ``dtype``."""
    from thunder_tpu.models.block_moe import BlockMoE, Config

    return BlockMoE(Config(name=name, **model_keys(config)), dtype=dtype)
