"""The benchmark's own weights for the sparse-attention / softmax-expert
decoder: made from the seed on the device, held in bfloat16.

The family's checkpoints are published in bfloat16, so the weights ARE
bfloat16 numbers: the program is handed these very arrays (no second copy:
``drivers/keye_tree.py`` only re-labels them) and the reference raises them
to float32 where it uses them.  One list entry a layer, the experts of a
layer stacked on their first axis (the program's own layout), so that
handing them over moves nothing.

Normal(0, ``assumed.initializer_range``) for matrices, the embedding and
the head.  Unlike a checkpoint's ones and zeros, the gains are drawn as 1 +
normal(0, initializer_range) and the indexer key LayerNorm's bias as
normal(0, initializer_range), so that a path which drops one is seen."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HELD = jnp.bfloat16


def sizes(cfg: dict) -> dict:
    sa = cfg["sa_config"]
    heads, d = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    return dict(
        h=int(cfg["hidden_size"]), q=heads * d,
        kv=int(cfg["num_key_value_heads"]) * d, head_dim=d,
        index_heads=int(sa["indexer_num_heads"]),
        index_dim=int(sa["indexer_head_dim"]),
        experts=int(cfg["num_experts"]),
        per_token=int(cfg["num_experts_per_tok"]),
        ffn=int(cfg["moe_intermediate_size"]),
        vocab=int(cfg["vocab_size"]), layers=int(cfg["num_hidden_layers"]))


def layer_shapes(cfg: dict) -> dict:
    """``{name: shape}`` of one layer; a gain is a one-element tuple."""
    s = sizes(cfg)
    h, e, f = s["h"], s["experts"], s["ffn"]
    hi, di = s["index_heads"], s["index_dim"]
    return {"norm": (h,), "ffn_norm": (h,),
            "q": (h, s["q"]), "k": (h, s["kv"]), "v": (h, s["kv"]),
            "o": (s["q"], h), "q_norm": (s["head_dim"],),
            "k_norm": (s["head_dim"],),
            "index_q": (h, hi * di), "index_k": (h, di),
            "index_k_norm": (di,), "index_k_bias": (di,),
            "index_weights": (h, hi),
            "router": (h, e), "w_gate": (e, h, f), "w_up": (e, h, f),
            "w_down": (e, f, h)}


def shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    return {"embed": (s["vocab"], s["h"]), "final_norm": (s["h"],),
            "head": (s["h"], s["vocab"]),
            "layers": [layer_shapes(cfg) for _ in range(s["layers"])]}


def _count(tree) -> int:
    return sum(math.prod(shape) for shape in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))


def param_count(cfg: dict) -> int:
    return _count(shapes(cfg))


def layer_params(cfg: dict) -> int:
    return _count(layer_shapes(cfg))


def make(cfg: dict, seed: int) -> dict:
    """The weights of ``shapes(cfg)``, one jitted call a leaf so that the
    float32 draw of one leaf is all that is ever live beside what is
    held."""
    std = float(cfg["assumed"]["initializer_range"])

    @functools.partial(jax.jit, static_argnums=(1,))
    def normal(key, shape, offset):
        return (offset + std * jax.random.normal(key, shape, jnp.float32)
                ).astype(HELD)

    tree = shapes(cfg)
    leaves, treedef = jax.tree.flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.key(int(seed)), len(leaves))
    out = [normal(key, shape, 1.0 if path[-1].key.endswith("norm") else 0.0)
           for key, (path, shape) in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, out)
