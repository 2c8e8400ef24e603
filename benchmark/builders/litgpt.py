"""Builder ``litgpt``: a configuration file's published keys -> the program's model.

The configuration files hold the keys of the model's own public
``config.json``. This module maps them onto ``thunder_tpu.models.litgpt.Config``
(one small table per ``model_type``), builds the model through the program's
own constructors, and replaces its weights with ones made on the device from
``--seed`` in one jitted call. ``benchmark/reference/litgpt.py`` reads the same
published keys on its own, so a wrong mapping here shows as a disagreement.
"""
from __future__ import annotations


def head_dim(config: dict) -> int:
    return int(config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"])


def model_keys(config: dict) -> dict:
    """Keyword arguments of ``litgpt.Config`` for a published configuration."""
    kind = config["model_type"]
    common = dict(
        block_size=config["max_position_embeddings"],
        vocab_size=config["vocab_size"],
        padded_vocab_size=config["vocab_size"],
        n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        n_embd=config["hidden_size"],
        head_size=head_dim(config),
        intermediate_size=config["intermediate_size"],
        shared_embedding=bool(config.get("tie_word_embeddings", False)),
        lm_head_bias=False,
    )
    if common["shared_embedding"]:
        raise ValueError("tied embeddings are not mapped yet")
    if kind == "gpt_neox":
        return dict(common, n_query_groups=config["num_attention_heads"],
                    rotary_percentage=config["rotary_pct"],
                    rope_base=int(config["rotary_emb_base"]),
                    parallel_residual=bool(config["use_parallel_residual"]), bias=True,
                    norm_class_name="LayerNorm", norm_eps=config["layer_norm_eps"],
                    mlp_class_name="GptNeoxMLP")
    if kind == "mistral":
        if config.get("sliding_window") is not None:
            raise ValueError("the program has no sliding-window attention")
        return dict(common, n_query_groups=config["num_key_value_heads"],
                    rotary_percentage=1.0, rope_base=int(config["rope_theta"]),
                    parallel_residual=False, bias=False,
                    norm_class_name="RMSNorm", norm_eps=config["rms_norm_eps"],
                    mlp_class_name="LLaMAMLP")
    raise ValueError(f"builder litgpt does not know model_type {kind!r}")


def dims(config: dict) -> dict:
    """The sizes ``benchmark/lib/costs.py`` needs, from the published keys."""
    return dict(
        n_layer=config["num_hidden_layers"], d_model=config["hidden_size"],
        heads=config["num_attention_heads"],
        kv_heads=config.get("num_key_value_heads", config["num_attention_heads"]),
        head_dim=head_dim(config), d_ff=config["intermediate_size"],
        mlp_matrices=3 if config["model_type"] == "mistral" else 2,
        vocab=config["vocab_size"])


def kernel_claims(config: dict) -> dict:
    """What this model needs Pallas to have claimed, ``{program: {symbols:
    count}}``: one attention kernel a layer in each serving program and in the
    step's forward and backward traces. Symbols joined by ``+`` are summed: the
    plain flash kernel (partial rope, decomposed beside it) or the rope-fused
    one, whichever the configuration takes."""
    n = int(config["num_hidden_layers"])
    return {"decode_cfn": {"thunder.paged_attention": n},
            "chunk_cfn": {"thunder.paged_chunk_attention": n},
            "forward": {"pallas.rope_flash_fwd+pallas.flash_attention_fwd": n},
            "backward": {"pallas.rope_flash_bwd+pallas.flash_attention_bwd": n}}


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Operations the forward and backward passes need per trained token: the
    dense-GPT count of ``benchmark/lib/costs.py`` at this configuration's sizes."""
    from benchmark.lib import costs

    return costs.train_flops_per_token(seq_len=seq_len, **dims(config))


def program_config(config: dict, name: str, **overrides):
    from thunder_tpu.models.litgpt import Config

    return Config(name=name, **dict(model_keys(config), **overrides))


def seeded_params(like: dict, seed: int, std: float) -> dict:
    """Weights for every entry of ``like`` (name -> array: shape, dtype and
    sharding are taken from it), made on the device in one jitted call. Norm
    gains are one; everything else, biases included, is normal with the
    published ``initializer_range``, so that a dropped bias or a misplaced
    weight changes the result. The seed is an argument of the compiled
    program, not a constant in it: every seed runs the same executable."""
    import jax
    import jax.numpy as jnp

    names = sorted(like)
    spec = {n: (tuple(like[n].shape), like[n].dtype) for n in names}

    def make(seed):
        key = jax.random.key(seed)
        out = {}
        for i, n in enumerate(names):
            shape, dtype = spec[n]
            if len(shape) == 1 and n.endswith(".weight"):
                out[n] = jnp.ones(shape, dtype)
            else:
                out[n] = (std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                                  jnp.float32)).astype(dtype)
        return out

    shardings = {n: like[n].sharding for n in names}
    return jax.jit(make, out_shardings=shardings)(jnp.asarray(seed, jnp.uint32))


def reseed(parameters: dict, seed: int, config: dict) -> None:
    """Replace the data of ``parameters`` (name -> ``nn.Parameter``) in place."""
    new = seeded_params({n: p.data for n, p in parameters.items()}, seed,
                        float(config.get("initializer_range", 0.02)))
    for n, p in parameters.items():
        p.data = new[n]


def build_loss_model(config: dict, name: str, *, activation_checkpoint: bool = False):
    """The pre-training target: GPT plus cross-entropy, f32 master weights."""
    from thunder_tpu.models.litgpt import GPTForCausalLM

    return GPTForCausalLM(program_config(config, name,
                                         activation_checkpoint=activation_checkpoint))


def build_serving_model(config: dict, name: str, dtype):
    """The served model, weights in ``dtype``."""
    from thunder_tpu.models.litgpt import GPT

    return GPT(program_config(config, name), dtype=dtype)
