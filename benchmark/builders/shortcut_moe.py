"""Builder ``shortcut_moe``: a LongCat-Flash configuration file (the text decoder's keys of
LongCat-Flash-Omni's public ``config.json``) -> the program's model, as one chip of an
expert-parallel group holds it.

What a double-layer builder maps. The published ``num_layers`` counts DOUBLE layers: two
latent-attention blocks, two dense FFNs and one expert layer on a shortcut each. The program
serves each half as a layer of its own (``thunder_tpu.models.shortcut_moe``: one latent pool a
half, the experts' result carried from the first half to the second), so the engine, the page
tables and ``drivers/serve_rollouts.py`` see ``num_hidden_layers = 2 * num_layers`` layers with a
latent cache (the file carries that key beside the published one, as the transformers port of the
family does), and the parameters are named ``h.<2l>`` (with ``experts.*``) and ``h.<2l + 1>``.
``n_routed_experts`` in the file counts the experts HELD here (``experts_held`` names the
range); ``reduced_from.n_routed_experts`` is the published count, which the router keeps beside
its ``zero_expert_num`` identity outputs. ``kernel_claims`` therefore counts the ragged expert
kernel once a DOUBLE layer (``num_layers``: 4) and the latent decode kernel once a HALF
(``num_hidden_layers``: 8) in the decode program, and the ragged kernel once a double layer in
the chunk program, whose queries over the latent pools go through XLA.

Weights come from ``--seed`` through ``builders/latent_moe.py``'s ``seeded_params`` (one
compiled function for the first halves and one for the second, a half's number an argument).
``benchmark/reference/shortcut_moe.py`` reads the same keys on its own, so a wrong mapping here
shows as a disagreement. The model is served only: there is no ``build_loss_model``.
"""
from __future__ import annotations

import os

from benchmark.lib import manifest

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_latent_moe = manifest.load_module(_ROOT, "builders", "latent_moe")

reseed, seeded_params = _latent_moe.reseed, _latent_moe.seeded_params


def _published_routed(config: dict) -> int:
    return config.get("reduced_from", {}).get("n_routed_experts", config["n_routed_experts"])


def model_keys(config: dict) -> dict:
    """Keyword arguments of ``shortcut_moe.Config`` for a published configuration."""
    a = config["assumed"]
    if config["model_type"] != "longcat_flash":
        raise ValueError(f"builder shortcut_moe does not know model_type {config['model_type']!r}")
    if config["attention_method"] != "MLA" or config["zero_expert_type"] != "identity":
        raise ValueError("builder shortcut_moe maps latent attention and identity zero-compute experts only")
    if config["attention_bias"] or a["tie_word_embeddings"] or a["norm_topk_prob"]:
        raise ValueError("builder shortcut_moe maps layers without bias, an untied head and "
                         "router weights that are not normalised only")
    if config["num_hidden_layers"] != 2 * config["num_layers"]:
        raise ValueError("num_hidden_layers counts the attention blocks: two a double layer")
    if not (config["mla_scale_q_lora"] and config["mla_scale_kv_lora"]) or a["mla_scale"] != "sqrt_hidden_over_rank" \
            or not a["rope_interleave"] or a["router_columns"] != "routed_then_identity":
        raise ValueError("builder shortcut_moe maps both low-rank streams rescaled and the assumptions its model is written to only")
    held = tuple(int(e) for e in config["experts_held"])
    if held[1] - held[0] != config["n_routed_experts"]:
        raise ValueError(f"experts_held {held} are not the {config['n_routed_experts']} n_routed_experts")
    return dict(
        block_size=min(config["max_position_embeddings"], a["rope_table_rows"]),
        vocab_size=config["vocab_size"], n_layer=config["num_layers"],
        n_embd=config["hidden_size"], n_head=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"], qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], intermediate_size=config["ffn_hidden_size"],
        moe_intermediate_size=config["expert_ffn_hidden_size"],
        n_routed_experts=_published_routed(config), n_zero_experts=config["zero_expert_num"],
        experts_held=held, n_expert_per_token=config["moe_topk"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_eps=config["rms_norm_eps"], rope_theta=float(config["rope_theta"]))


def dims(config: dict) -> dict:
    """The sizes the cost functions of ``benchmark/lib/costs_shortcut_moe.py`` need. ``n_layer``
    counts the layers that cache (the halves), ``n_expert_layers`` the double layers."""
    lo, hi = config["experts_held"]
    width = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return dict(n_layer=config["num_hidden_layers"], n_expert_layers=config["num_layers"],
                d_model=config["hidden_size"], heads=config["num_attention_heads"],
                q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
                nope=config["qk_nope_head_dim"], rope=config["qk_rope_head_dim"],
                v_dim=config["v_head_dim"], latent_width=width, latent_row=-(-width // 128) * 128,
                ffn_width=config["ffn_hidden_size"], expert_width=config["expert_ffn_hidden_size"],
                experts_held=hi - lo, n_routed=_published_routed(config), n_zero=config["zero_expert_num"],
                experts_per_token=config["moe_topk"], n_shared=0, vocab=config["vocab_size"])


def kernel_claims(config: dict) -> dict:
    """What this model needs Pallas to have claimed, ``{program: {symbols: count}}``: the ragged
    expert kernel once a double layer, the latent decode kernel once a half (the module's
    docstring says why the two counts differ)."""
    layers, halves = config["num_layers"], config["num_hidden_layers"]
    return {"decode_cfn": {"thunder.ragged_mlp": layers, "thunder.paged_latent_attention": halves},
            "chunk_cfn": {"thunder.ragged_mlp": layers}}


def build_serving_model(config: dict, name: str, dtype):
    """The served model, weights in ``dtype``."""
    from thunder_tpu.models.shortcut_moe import Config, ShortcutMoE

    return ShortcutMoE(Config(name=name, **model_keys(config)), dtype=dtype)
