"""The benchmark's weights (``lib/jamba_weights.py``) as
``models/jamba.JambaLM``'s flax tree, and the model's fields from a
``config.json`` of the family.  Re-labelling only: no array is copied."""

from __future__ import annotations


def _dense(kernel) -> dict:
    return {"kernel": kernel}


def to_flax(weights: dict) -> dict:
    tree = {"token_embed": {"embedding": weights["embed"]},
            "final_norm": {"scale": weights["final_norm"]}}
    for i, w in enumerate(weights["layers"]):
        tree[f"norm_{i}"] = {"scale": w["norm"]}
        tree[f"ffn_norm_{i}"] = {"scale": w["ffn_norm"]}
        tree[f"ffn_{i}"] = {k: _dense(w[k]) for k in ("gate", "up", "down")}
        if "in_proj" in w:
            mixer = {"in_proj": w["in_proj"], "conv_weight": w["conv_w"],
                     "conv_bias": w["conv_b"], "x_proj": w["x_proj"],
                     "dt_norm": w["dt_norm"], "b_norm": w["b_norm"],
                     "c_norm": w["c_norm"], "dt_proj": w["dt_proj"],
                     "dt_bias": w["dt_bias"], "A_log": w["a_log"],
                     "D": w["d"], "out_proj": w["out_proj"]}
        else:
            mixer = {f"{k}_proj": _dense(w[k]) for k in "qkvo"}
        tree[f"mixer_{i}"] = mixer
    return tree


# what the program and the reference both assume: checked, not read past
FIXED = {"model_type": "jamba", "mamba_conv_bias": True,
         "mamba_proj_bias": False, "hidden_act": "silu",
         "tie_word_embeddings": True, "sliding_window": None,
         "num_experts": 1, "num_experts_per_tok": 1}


def model_kwargs(config: dict, max_len: int) -> dict:
    """``JambaLM`` fields from the family's ``config.json`` keys.  With one
    expert every feed-forward is the dense SwiGLU and ``expert_layer_*`` are
    read by nothing."""
    for key, want in FIXED.items():
        if config[key] != want:
            raise ValueError(f"{key}={config[key]!r}: only {want!r} is built")
    if max_len > int(config["max_position_embeddings"]):
        raise ValueError(f"max_len {max_len} exceeds the model's positions")
    hidden, heads = (int(config[k]) for k in ("hidden_size",
                                              "num_attention_heads"))
    return {"vocab_size": int(config["vocab_size"]), "hidden": hidden,
            "layers": int(config["num_hidden_layers"]),
            "attn_period": int(config["attn_layer_period"]),
            "attn_offset": int(config["attn_layer_offset"]),
            "ssm_state": int(config["mamba_d_state"]),
            "ssm_conv": int(config["mamba_d_conv"]),
            "ssm_expand": int(config["mamba_expand"]),
            "ssm_dt_rank": int(config["mamba_dt_rank"]),
            "heads": heads,
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config.get("head_dim") or hidden // heads),
            "ffn": int(config["intermediate_size"]),
            "eps": float(config["rms_norm_eps"]), "max_len": int(max_len)}
