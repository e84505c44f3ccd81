"""The benchmark's weights (``lib/window_moe_weights.py``) as
``models/window_moe.WindowMoELM``'s flax tree, and the model's fields from
a ``config.json`` of the family.  Re-labelling only: no array is copied
(the family has no choice bias: ``DroplessMoE``'s is handed zeros)."""

from __future__ import annotations

import jax.numpy as jnp


def _dense(kernel) -> dict:
    return {"kernel": kernel}


def to_flax(weights: dict) -> dict:
    tree = {"token_embed": {"embedding": weights["embed"]},
            "final_norm": {"scale": weights["final_norm"]}}
    for i, w in enumerate(weights["layers"]):
        tree[f"norm_{i}"] = {"scale": w["norm"]}
        tree[f"attn_{i}"] = {f"{k}_proj": _dense(w[k]) for k in "qkvo"}
        tree[f"ffn_{i}"] = {
            "router": w["router"],
            "choice_bias": jnp.zeros(w["router"].shape[1], jnp.float32),
            "w_gate": w["w_gate"], "w_up": w["w_up"],
            "w_down": w["w_down"],
            "shared": {k: _dense(w[f"shared_{k}"])
                       for k in ("gate", "up", "down")}}
    return tree


# what the program and the reference both assume: checked, not read past
FIXED = {"model_type": "cohere2_moe", "use_parallel_block": True,
         "use_qk_norm": False, "attention_bias": False,
         "use_gated_activation": True, "hidden_act": "silu",
         "expert_selection_fn": "sigmoid",
         "shared_expert_combination_strategy": "average",
         "position_embedding_type": "rope_gptj", "rotary_pct": 1,
         "tie_word_embeddings": True, "first_k_dense_replace": 0,
         "order_of_interleaved_layers": "local_attn_first"}
KINDS = {"sliding_attention": "W", "full_attention": "F"}


def model_kwargs(config: dict, max_len: int) -> dict:
    """``WindowMoELM`` fields from the family's ``config.json`` keys."""
    for key, want in FIXED.items():
        if config[key] != want:
            raise ValueError(f"{key}={config[key]!r}: only {want!r} is built")
    types = config["layer_types"]
    if set(types) - set(KINDS):
        raise ValueError(f"layer_types {sorted(set(types))}: only "
                         f"{sorted(KINDS)} are built")
    if len(types) != int(config["num_hidden_layers"]):
        raise ValueError("layer_types names one kind a layer")
    if float(config["rope_theta"]) != float(
            config["rope_parameters"]["rope_theta"]):
        raise ValueError("rope_theta and rope_parameters.rope_theta differ")
    if max_len > int(config["max_position_embeddings"]):
        raise ValueError(f"max_len {max_len} exceeds the model's positions")
    first, count = (int(v) for v in config["experts_held"])
    router = int(config["_published"]["num_experts"])
    if count != int(config["num_experts"]) or first + count > router:
        raise ValueError("experts_held is not num_experts of the router's "
                         "published width")
    return {"vocab_size": int(config["vocab_size"]),
            "hidden": int(config["hidden_size"]),
            "pattern": "".join(KINDS[t] for t in types),
            "window": int(config["sliding_window"]),
            "rope_theta": float(config["rope_theta"]),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]),
            "num_experts": router,
            "experts_per_token": int(config["num_experts_per_tok"]),
            "expert_ffn": int(config["intermediate_size"]),
            "shared_experts": int(config["num_shared_experts"]),
            "shared_ffn": int(config["intermediate_size"]),
            "norm_topk": bool(config["norm_topk_prob"]),
            "experts_held": None if count == router else (first, count),
            "logit_scale": float(config["logit_scale"]),
            "eps": float(config["layer_norm_eps"]), "max_len": int(max_len)}
