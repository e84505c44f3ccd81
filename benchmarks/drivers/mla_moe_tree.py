"""The benchmark's weights (``lib/mla_moe_weights.py``) as
``models/mla_moe.LatentMoELM``'s flax tree, and the model's fields from a
``config.json`` of the family.  Re-labelling only: no array is copied."""

from __future__ import annotations


def _dense(kernel) -> dict:
    return {"kernel": kernel}


def to_flax(weights: dict) -> dict:
    tree = {"token_embed": {"embedding": weights["embed"]},
            "lm_head": _dense(weights["head"]),
            "final_norm": {"scale": weights["final_norm"]}}
    for i, w in enumerate(weights["layers"]):
        block = {"attn_norm": {"scale": w["attn_norm"]},
                 "ffn_norm": {"scale": w["ffn_norm"]},
                 "attn": {"q_proj": _dense(w["q"]),
                          "kv_a_proj": _dense(w["kv_a"]),
                          "kv_a_norm": {"scale": w["kv_a_norm"]},
                          "kv_b_proj": w["kv_b"], "o_proj": _dense(w["o"])}}
        if "router" in w:
            block["moe"] = {
                "router": w["router"], "choice_bias": w["choice_bias"],
                "w_gate": w["w_gate"], "w_up": w["w_up"],
                "w_down": w["w_down"],
                "shared": {k: _dense(w[f"shared_{k}"])
                           for k in ("gate", "up", "down")}}
        else:
            block["mlp"] = {k: _dense(w[k]) for k in ("gate", "up", "down")}
        tree[f"block_{i}"] = block
    return tree


def model_kwargs(config: dict, max_len: int) -> dict:
    """``LatentMoELM`` fields from the family's ``config.json`` keys.  The
    keys that fix what the program and the reference both assume are
    checked, not read past."""
    fixed = {"q_lora_rank": None, "n_group": 1, "topk_group": 1,
             "scoring_func": "sigmoid", "topk_method": "noaux_tc",
             "rope_scaling": None, "moe_layer_freq": 1,
             "hidden_act": "silu", "attention_bias": False,
             "tie_word_embeddings": False}
    for key, want in fixed.items():
        if config[key] != want:
            raise ValueError(f"{key}={config[key]!r}: only {want!r} is built")
    if max_len > int(config["max_position_embeddings"]):
        raise ValueError(f"max_len {max_len} exceeds the model's positions")
    return {"vocab_size": int(config["vocab_size"]),
            "hidden": int(config["hidden_size"]),
            "layers": int(config["num_hidden_layers"]),
            "heads": int(config["num_attention_heads"]),
            "qk_nope_dim": int(config["qk_nope_head_dim"]),
            "qk_rope_dim": int(config["qk_rope_head_dim"]),
            "v_dim": int(config["v_head_dim"]),
            "kv_rank": int(config["kv_lora_rank"]),
            "dense_ffn": int(config["intermediate_size"]),
            "first_dense": int(config["first_k_dense_replace"]),
            "num_experts": int(config["n_routed_experts"]),
            "experts_per_token": int(config["num_experts_per_tok"]),
            "expert_ffn": int(config["moe_intermediate_size"]),
            "shared_experts": int(config["n_shared_experts"]),
            "routed_scale": float(config["routed_scaling_factor"]),
            "norm_topk": bool(config["norm_topk_prob"]),
            "rope_theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]), "max_len": int(max_len)}
