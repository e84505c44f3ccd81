"""The benchmark's weights (``lib/hybrid_ssm_weights.py``) as
``models/hybrid_ssm.HybridSSMLM``'s flax tree, and the model's fields from
a ``config.json`` of the family.  Re-labelling only: no array is copied."""

from __future__ import annotations


def _dense(kernel) -> dict:
    return {"kernel": kernel}


def to_flax(weights: dict) -> dict:
    tree = {"token_embed": {"embedding": weights["embed"]},
            "lm_head": _dense(weights["head"]),
            "final_norm": {"scale": weights["final_norm"]}}
    for i, w in enumerate(weights["layers"]):
        tree[f"norm_{i}"] = {"scale": w["norm"]}
        if "in_proj" in w:
            mixer = {"in_proj": _dense(w["in_proj"]),
                     "conv_weight": w["conv_w"], "conv_bias": w["conv_b"],
                     "dt_bias": w["dt_bias"], "A_log": w["a_log"],
                     "D": w["d"], "norm": w["gate_norm"],
                     "out_proj": _dense(w["out_proj"])}
        elif "router" in w:
            mixer = {"router": w["router"], "choice_bias": w["choice_bias"],
                     "latent_down": _dense(w["latent_down"]),
                     "latent_up": _dense(w["latent_up"]),
                     "w_up": w["w_up"], "w_down": w["w_down"],
                     "shared": {"up": _dense(w["shared_up"]),
                                "down": _dense(w["shared_down"])}}
        else:
            mixer = {f"{k}_proj": _dense(w[k]) for k in "qkvo"}
        tree[f"mixer_{i}"] = mixer
    return tree


def model_kwargs(config: dict, max_len: int) -> dict:
    """``HybridSSMLM`` fields from the family's ``config.json`` keys.  The
    keys that fix what the program and the reference both assume are
    checked, not read past."""
    fixed = {"model_type": "nemotron_h", "attention_bias": False,
             "mamba_hidden_act": "silu", "mamba_proj_bias": False,
             "mlp_bias": False, "mlp_hidden_act": "relu2", "use_bias": False,
             "use_conv_bias": True, "n_group": 1, "topk_group": 1,
             "n_shared_experts": 1, "sliding_window": None,
             "tie_word_embeddings": False, "residual_in_fp32": False,
             "moe_shared_expert_overlap": False}
    for key, want in fixed.items():
        if config[key] != want:
            raise ValueError(f"{key}={config[key]!r}: only {want!r} is built")
    pattern = config["hybrid_override_pattern"]
    if set(pattern) - set("M*E"):
        raise ValueError(f"hybrid_override_pattern {pattern!r}: only the "
                         f"mixers M, * and E are built")
    if len(pattern) != int(config["num_hidden_layers"]):
        raise ValueError("hybrid_override_pattern names one mixer a layer")
    heads, head_dim = (int(config[k]) for k in ("mamba_num_heads",
                                                "mamba_head_dim"))
    if heads * head_dim != int(config["expand"]) * int(config["hidden_size"]):
        raise ValueError("mamba_num_heads x mamba_head_dim is not expand x "
                         "hidden_size")
    if float(config["norm_eps"]) != float(config["layer_norm_epsilon"]):
        raise ValueError("norm_eps and layer_norm_epsilon differ")
    if max_len > int(config["max_position_embeddings"]):
        raise ValueError(f"max_len {max_len} exceeds the model's positions")
    first, count = (int(v) for v in config["experts_held"])
    router = int(config["_published"]["n_routed_experts"])
    if count != int(config["n_routed_experts"]) or first + count > router:
        raise ValueError("experts_held is not n_routed_experts of the "
                         "router's published width")
    return {"vocab_size": int(config["vocab_size"]),
            "hidden": int(config["hidden_size"]), "pattern": pattern,
            "ssm_heads": heads, "ssm_head_dim": head_dim,
            "ssm_groups": int(config["n_groups"]),
            "ssm_state": int(config["ssm_state_size"]),
            "conv_kernel": int(config["conv_kernel"]),
            "chunk": int(config["chunk_size"]),
            "dt_limits": tuple(float(config[k]) for k in (
                "time_step_min", "time_step_max", "time_step_floor")),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]),
            "num_experts": router,
            "experts_per_token": int(config["num_experts_per_tok"]),
            "expert_ffn": int(config["moe_intermediate_size"]),
            "expert_latent": int(config["moe_latent_size"]),
            "shared_ffn": int(config["moe_shared_expert_intermediate_size"]),
            "routed_scale": float(config["routed_scaling_factor"]),
            "norm_topk": bool(config["norm_topk_prob"]),
            "experts_held": None if count == router else (first, count),
            "eps": float(config["norm_eps"]), "max_len": int(max_len)}
