"""Builder ``latent_moe``: a ``mistral4`` configuration file -> the program's model, as one
chip of an expert-parallel group holds it.

The configuration file holds the keys of the model's own public ``config.json`` and, under
``assumed``, what that file has no key for. ``n_routed_experts`` there counts the experts HELD
here (``experts_held`` names the range), ``reduced_from.n_routed_experts`` is the router's
published width, which the model keeps. This module maps the keys onto
``thunder_tpu.models.latent_moe.Config``, builds the model through the program's own
constructor and replaces its weights with ones made on the device from ``--seed``.
``benchmark/reference/latent_moe.py`` reads the same keys on its own, so a wrong mapping here
shows as a disagreement. The model is served only: there is no ``build_loss_model``.
"""
from __future__ import annotations


def model_keys(config: dict) -> dict:
    """Keyword arguments of ``latent_moe.Config`` for a published configuration."""
    if config["model_type"] != "mistral4":
        raise ValueError(f"builder latent_moe does not know model_type {config['model_type']!r}")
    if config["tie_word_embeddings"] or config["attention_bias"] or config["mlp_bias"]:
        raise ValueError("builder latent_moe maps an untied head and layers without bias only")
    if config["first_k_dense_replace"] or config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("builder latent_moe maps expert layers throughout and one routing group only")
    rp, a = config["rope_parameters"], config["assumed"]
    if a["scoring_func"] != "sigmoid":
        raise ValueError(f"builder latent_moe maps a sigmoid router only, not {a['scoring_func']!r}")
    held = tuple(int(e) for e in config["experts_held"])
    if held[1] - held[0] != config["n_routed_experts"]:
        raise ValueError(f"experts_held {held} are not the {config['n_routed_experts']} n_routed_experts")
    yarn = rp["rope_type"] == "yarn"
    return dict(
        block_size=min(config["max_position_embeddings"], a["rope_table_rows"]),
        vocab_size=config["vocab_size"], n_layer=config["num_hidden_layers"],
        n_embd=config["hidden_size"], n_head=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"], qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], moe_intermediate_size=config["moe_intermediate_size"],
        n_routed_experts=config.get("reduced_from", {}).get("n_routed_experts", config["n_routed_experts"]),
        experts_held=held, n_expert_per_token=config["num_experts_per_tok"],
        n_shared_experts=config["n_shared_experts"], norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        norm_eps=config["rms_norm_eps"], rope_theta=float(rp["rope_theta"]),
        rope_factor=float(rp["factor"]) if yarn else 1.0,
        rope_original=rp["original_max_position_embeddings"], beta_fast=float(rp["beta_fast"]),
        beta_slow=float(rp["beta_slow"]), mscale=float(rp["mscale"]),
        mscale_all_dim=(float(rp["mscale_all_dim"])
                        if a["softmax_scale"] == "yarn_mscale_all_dim_squared" else 0.0),
        query_scaling_beta=float(rp.get("llama_4_scaling_beta", 0.0)))


def dims(config: dict) -> dict:
    """The sizes the cost functions of ``benchmark/lib/costs_latent_moe.py`` need."""
    lo, hi = config["experts_held"]
    width = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return dict(n_layer=config["num_hidden_layers"], d_model=config["hidden_size"],
                heads=config["num_attention_heads"], q_rank=config["q_lora_rank"],
                kv_rank=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
                rope=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
                latent_width=width, latent_row=-(-width // 128) * 128,
                expert_width=config["moe_intermediate_size"], experts_held=hi - lo,
                n_routed=config.get("reduced_from", {}).get("n_routed_experts", config["n_routed_experts"]),
                experts_per_token=config["num_experts_per_tok"], n_shared=config["n_shared_experts"],
                vocab=config["vocab_size"])


def kernel_claims(config: dict) -> dict:
    """What this model needs Pallas to have claimed, ``{program: {symbols: count}}``: in the
    decode program every layer's routed rows go through the ragged expert kernel and its one
    query a sequence through the latent decode kernel; a chunk's rows go through the ragged
    kernel too, and its queries over the latent pool through XLA (the gather decomposition of
    ``ltorch.paged_latent_attention``: no chunk kernel over a latent pool yet, PERF.md)."""
    n = config["num_hidden_layers"]
    return {"decode_cfn": {"thunder.ragged_mlp": n, "thunder.paged_latent_attention": n},
            "chunk_cfn": {"thunder.ragged_mlp": n}}


def _make(names: list, spec: dict, config: dict):
    """A function ``(seed, salt) -> {name: array}`` for the entries of ``spec`` (name -> (shape,
    dtype)): norm gains one, the router's selection bias normal with ``router_bias_std``, the
    embedding normal with ``embedding_std``, every other matrix and panel normal with
    ``initializer_range``. Entry ``i`` draws from ``fold_in(fold_in(
    key(seed), salt), i)``, so one compiled function serves every layer under its own salt."""
    import jax
    import jax.numpy as jnp

    a = config["assumed"]
    std, bias_std = float(a["initializer_range"]), float(a["router_bias_std"])
    embedding_std = float(a["embedding_std"])

    def make(seed, salt):
        key = jax.random.fold_in(jax.random.key(seed), salt)
        out = {}
        for i, n in enumerate(names):
            shape, dtype = spec[n]
            k = jax.random.fold_in(key, i)
            if len(shape) == 1 and n.endswith("weight"):
                v = jnp.ones(shape, jnp.float32)
            elif n.endswith("e_score_correction_bias"):
                v = bias_std * jax.random.normal(k, shape, jnp.float32)
            elif n == "wte.weight":
                v = embedding_std * jax.random.normal(k, shape, dtype)
            else:
                # drawn in the parameter's own type: a 537 MB panel has no float32 twin
                v = std * jax.random.normal(k, shape, dtype)
            out[n] = v.astype(dtype)
        return out

    return make


def seeded_params(like: dict, seed: int, config: dict) -> dict:
    """Weights for every entry of ``like`` (name -> array, or anything with its ``shape`` and
    ``dtype``), made on the device from the seed: one jitted call for the embedding, the last
    norm and the head, and one a layer, all layers through ONE compiled function (a layer's
    number is an argument), so that what is alive beside the weights is one layer's
    temporaries and not the model's."""
    import jax
    import jax.numpy as jnp

    seed = jnp.asarray(int(seed) % 2**32, jnp.uint32)  # any whole number is a seed
    out, layers, top = {}, {}, []
    for n in sorted(like):
        parts = n.split(".")
        if parts[0] == "h":
            layers.setdefault(int(parts[1]), []).append(n)
        else:
            top.append(n)

    def spec_of(names, strip: int):
        return {n[strip:]: (tuple(like[n].shape), like[n].dtype) for n in names}

    if top:
        spec = spec_of(top, 0)
        out.update(jax.jit(_make(top, spec, config))(seed, jnp.asarray(2**20, jnp.uint32)))
    made = {}
    for i, names in sorted(layers.items()):
        spec = spec_of(names, len(f"h.{i}."))
        key = tuple(sorted((n, s, str(d)) for n, (s, d) in spec.items()))
        if key not in made:
            made[key] = jax.jit(_make(sorted(spec), spec, config))
        got = made[key](seed, jnp.asarray(i, jnp.uint32))
        out.update({f"h.{i}.{n}": v for n, v in got.items()})
    return out


def reseed(parameters: dict, seed: int, config: dict) -> None:
    """Replace the data of ``parameters`` (name -> ``nn.Parameter``) in place. The arrays that
    were there are freed first: with both sets alive the cut model would take twice its
    10.85 GB, more than a chip has."""
    import jax

    like = {}
    for n, p in parameters.items():
        like[n] = jax.ShapeDtypeStruct(p.data.shape, p.data.dtype)
        p.data.delete()
    new = seeded_params(like, seed, config)
    for n, p in parameters.items():
        p.data = new[n]


def build_serving_model(config: dict, name: str, dtype):
    """The served model, weights in ``dtype``."""
    from thunder_tpu.models.latent_moe import Config, LatentMoE

    return LatentMoE(Config(name=name, **model_keys(config)), dtype=dtype)
