"""The benchmark's weights (``lib/keye_weights.py``) as
``models/keye.KeyeLM``'s flax tree, and the model's fields from a
``config.json`` of the family.  Re-labelling only: no array is copied."""

from __future__ import annotations

_ATTN = {"q": "q_proj", "k": "k_proj", "v": "v_proj", "o": "o_proj",
         "q_norm": "q_norm", "k_norm": "k_norm", "index_q": "index_q",
         "index_k": "index_k", "index_k_norm": "index_k_norm",
         "index_k_bias": "index_k_bias", "index_weights": "index_weights"}


def to_flax(weights: dict) -> dict:
    tree = {"token_embed": {"embedding": weights["embed"]},
            "final_norm": {"scale": weights["final_norm"]},
            "lm_head": {"kernel": weights["head"]}}
    for i, w in enumerate(weights["layers"]):
        tree[f"norm_{i}"] = {"scale": w["norm"]}
        tree[f"ffn_norm_{i}"] = {"scale": w["ffn_norm"]}
        tree[f"attn_{i}"] = {name: w[key] for key, name in _ATTN.items()}
        tree[f"ffn_{i}"] = {"router": w["router"], "w_gate": w["w_gate"],
                            "w_up": w["w_up"], "w_down": w["w_down"]}
    return tree


# what the program and the reference both assume: checked, not read past
FIXED = {"hidden_act": "silu", "attention_bias": False,
         "tie_word_embeddings": False, "decoder_sparse_step": 1,
         "mlp_only_layers": [], "use_sliding_window": False,
         "norm_topk_prob": True}


def model_kwargs(config: dict, max_len: int) -> dict:
    """``KeyeLM`` fields from the family's ``config.json`` keys and the
    configuration's ``assumed.index_rope_dim``."""
    for key, want in FIXED.items():
        if config[key] != want:
            raise ValueError(f"{key}={config[key]!r}: only {want!r} is built")
    if max_len > int(config["max_position_embeddings"]):
        raise ValueError(f"max_len {max_len} exceeds the model's positions")
    sa = config["sa_config"]
    if int(sa["indexer_num_kv_heads"]) != 1:
        raise ValueError("one indexer key head is built")
    return {"vocab_size": int(config["vocab_size"]),
            "hidden": int(config["hidden_size"]),
            "layers": int(config["num_hidden_layers"]),
            "heads": int(config["num_attention_heads"]),
            "kv_heads": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]),
            "index_heads": int(sa["indexer_num_heads"]),
            "index_head_dim": int(sa["indexer_head_dim"]),
            "index_rope_dim": int(config["assumed"]["index_rope_dim"]),
            "index_topk": int(sa["topk"]),
            "num_experts": int(config["num_experts"]),
            "experts_per_token": int(config["num_experts_per_tok"]),
            "expert_ffn": int(config["moe_intermediate_size"]),
            "rope_theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]), "max_len": int(max_len)}
