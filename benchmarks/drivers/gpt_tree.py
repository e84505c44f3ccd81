"""The benchmark's canonical weights <-> ``models/gpt.GPTLM``'s flax tree."""

from __future__ import annotations

import jax.numpy as jnp

# canonical block leaf -> path inside a GPTBlock's params
_PATHS = {
    "ln1_g": ("LayerNorm_0", "scale"), "ln1_b": ("LayerNorm_0", "bias"),
    "wq": ("CausalSelfAttention_0", "query", "kernel"),
    "bq": ("CausalSelfAttention_0", "query", "bias"),
    "wk": ("CausalSelfAttention_0", "key", "kernel"),
    "bk": ("CausalSelfAttention_0", "key", "bias"),
    "wv": ("CausalSelfAttention_0", "value", "kernel"),
    "bv": ("CausalSelfAttention_0", "value", "bias"),
    "wo": ("CausalSelfAttention_0", "out", "kernel"),
    "bo": ("CausalSelfAttention_0", "out", "bias"),
    "ln2_g": ("LayerNorm_1", "scale"), "ln2_b": ("LayerNorm_1", "bias"),
    "w1": ("Dense_0", "kernel"), "b1": ("Dense_0", "bias"),
    "w2": ("Dense_1", "kernel"), "b2": ("Dense_1", "bias"),
}


def to_flax(weights: dict) -> dict:
    layers = next(iter(weights["blocks"].values())).shape[0]
    tree = {"token_embed": {"embedding": weights["wte"]},
            "pos_embed": {"embedding": weights["wpe"]},
            "LayerNorm_0": {"scale": weights["lnf_g"],
                            "bias": weights["lnf_b"]}}
    for i in range(layers):
        block: dict = {}
        for name, path in _PATHS.items():
            node = block
            for key in path[:-1]:
                node = node.setdefault(key, {})
            node[path[-1]] = weights["blocks"][name][i]
        tree[f"GPTBlock_{i}"] = block
    return tree


def from_flax(tree: dict) -> dict:
    layers = sum(1 for k in tree if k.startswith("GPTBlock_"))

    def leaf(i, path):
        node = tree[f"GPTBlock_{i}"]
        for key in path:
            node = node[key]
        return node

    return {"wte": tree["token_embed"]["embedding"],
            "wpe": tree["pos_embed"]["embedding"],
            "lnf_g": tree["LayerNorm_0"]["scale"],
            "lnf_b": tree["LayerNorm_0"]["bias"],
            "blocks": {name: jnp.stack([leaf(i, path) for i in range(layers)])
                       for name, path in _PATHS.items()}}


def model_kwargs(config: dict) -> dict:
    """``GPTLM`` fields from GPT-2's ``config.json`` keys."""
    h = int(config["n_embd"])
    return {"vocab_size": int(config["vocab_size"]), "hidden": h,
            "layers": int(config["n_layer"]), "heads": int(config["n_head"]),
            "ffn": int(config.get("n_inner") or 4 * h),
            "max_len": int(config["n_positions"]),
            "dropout_rate": float(config.get("resid_pdrop", 0.0))}
