"""Builder ``sambay``: a ``phi4flash`` configuration file -> the program's model.

The configuration file holds the keys of the model's own public ``config.json``
and, under ``assumed``, the sizes that file has no key for. This module maps
them onto ``thunder_tpu.models.sambay.Config``, builds the model through the
program's own constructor and replaces its weights with ones made on the device
from ``--seed`` in one jitted call. ``benchmark/reference/sambay.py`` reads the
same keys on its own, so a wrong mapping here shows as a disagreement. The model
is served only: there is no ``build_loss_model``.
"""
from __future__ import annotations

import math


def model_keys(config: dict) -> dict:
    """Keyword arguments of ``sambay.Config`` for a published configuration."""
    if config["model_type"] != "phi4flash":
        raise ValueError(f"builder sambay does not know model_type {config['model_type']!r}")
    if not config["tie_word_embeddings"] or config["mlp_bias"] or config["lm_head_bias"]:
        raise ValueError("builder sambay maps a tied head and an MLP without bias only")
    a = config["assumed"]
    return dict(block_size=config["max_position_embeddings"], vocab_size=config["vocab_size"],
                n_layer=config["num_hidden_layers"], n_head=config["num_attention_heads"],
                n_query_groups=config["num_key_value_heads"], n_embd=config["hidden_size"],
                intermediate_size=config["intermediate_size"],
                sliding_window=config["sliding_window"], mamba_every=config["mb_per_layer"],
                norm_eps=config["layer_norm_eps"], d_inner=a["mamba_d_inner"],
                d_state=a["mamba_d_state"], d_conv=a["mamba_d_conv"], dt_rank=a["mamba_dt_rank"])


def layer_counts(config: dict) -> dict:
    """How many layers of each kind the configuration has."""
    from thunder_tpu.models.sambay import Config

    cfg = Config(**model_keys(config))
    kinds = [cfg.layer_kind(i) for i in range(cfg.n_layer)]
    return {k: kinds.count(k) for k in ("mamba", "window_attn", "full_attn", "gmu", "cross_attn")}


def dims(config: dict) -> dict:
    """The sizes the cost functions of ``benchmark/lib/costs_sambay.py`` need."""
    a = config["assumed"]
    return dict(n_layer=config["num_hidden_layers"], d_model=config["hidden_size"],
                heads=config["num_attention_heads"], kv_heads=config["num_key_value_heads"],
                head_dim=config["hidden_size"] // config["num_attention_heads"],
                d_ff=config["intermediate_size"], vocab=config["vocab_size"],
                window=config["sliding_window"], d_inner=a["mamba_d_inner"],
                d_state=a["mamba_d_state"], d_conv=a["mamba_d_conv"], dt_rank=a["mamba_dt_rank"],
                layers=layer_counts(config))


def kernel_claims(config: dict) -> dict:
    """What this model needs Pallas to have claimed, ``{program: {symbols:
    count}}``. Differential attention is laid on the paged kernels as ONE call
    a layer (queries ordered by key head, V twice as wide as QK), so the decode
    and the chunk program each call their kernel once in every layer that
    attends: the window layers, the full layer and the cross-attention layers
    that read its pool. The scan and the conv go through XLA."""
    n = layer_counts(config)
    attends = n["window_attn"] + n["full_attn"] + n["cross_attn"]
    return {"decode_cfn": {"thunder.paged_attention": attends},
            "chunk_cfn": {"thunder.paged_chunk_attention": attends}}


def seeded_params(like: dict, seed: int, config: dict) -> dict:
    """Weights for every entry of ``like`` (name -> array, or anything with its
    ``shape``, ``dtype`` and ``sharding``), made on the device in one jitted call
    whose seed is an argument. Norm gains are one; matrices
    and biases normal with ``initializer_range``; the four lambda vectors normal
    with ``lambda_std``; the conv weight uniform in +-1/sqrt(d_conv); and by the
    Mamba-1 convention ``A_log = log(1 .. d_state)`` in every channel, ``D = 1``
    and the dt bias the inverse softplus of dt log-uniform in [1e-3, 1e-1]."""
    import jax
    import jax.numpy as jnp

    a = config["assumed"]
    std, lambda_std = float(a["initializer_range"]), float(a["lambda_std"])
    names = sorted(like)
    spec = {n: (tuple(like[n].shape), like[n].dtype) for n in names}

    def make(seed):
        key = jax.random.key(seed)
        out = {}
        for i, n in enumerate(names):
            shape, dtype = spec[n]
            k = jax.random.fold_in(key, i)
            leaf = n.rsplit(".", 1)[-1]
            if leaf == "A_log":
                v = jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)), shape)
            elif leaf == "D" or (len(shape) == 1 and leaf == "weight"):
                v = jnp.ones(shape, jnp.float32)
            elif n.endswith("dt_proj.bias"):
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                v = dt + jnp.log(-jnp.expm1(-dt))
            elif n.endswith("conv.weight"):
                bound = 1.0 / math.sqrt(shape[1])
                v = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
            elif leaf.startswith("lambda_"):
                v = lambda_std * jax.random.normal(k, shape, jnp.float32)
            else:
                v = std * jax.random.normal(k, shape, jnp.float32)
            out[n] = v.astype(dtype)
        return out

    shardings = {n: like[n].sharding for n in names}
    return jax.jit(make, out_shardings=shardings)(jnp.asarray(seed, jnp.uint32))


def reseed(parameters: dict, seed: int, config: dict) -> None:
    """Replace the data of ``parameters`` (name -> ``nn.Parameter``) in place.
    The arrays that were there are freed first: with both sets alive the whole
    model would take twice its 7.7 GB, all but the last half gigabyte of a chip."""
    import jax

    like = {}
    for n, p in parameters.items():
        like[n] = jax.ShapeDtypeStruct(p.data.shape, p.data.dtype, sharding=p.data.sharding)
        p.data.delete()
    new = seeded_params(like, seed, config)
    for n, p in parameters.items():
        p.data = new[n]


def build_serving_model(config: dict, name: str, dtype):
    """The served model, weights in ``dtype``."""
    from thunder_tpu.models.sambay import Config, SambaY

    return SambaY(Config(name=name, **model_keys(config)), dtype=dtype)
