"""The benchmark's own weights for the window-and-global-attention,
sparse-expert decoder: made from the seed on the device, held in bfloat16.

The program is handed these very arrays (no second copy:
``drivers/window_moe_tree.py`` only re-labels them) and the reference
raises them to float32 where it uses them.  One list entry a layer, nothing
stacked across layers, so that handing them over moves nothing.  Only the
experts HELD here are made (``experts_held``: 16 of the router's 128), and
the ``num_shared_experts`` shared experts lie side by side in one gate, one
up and one down matrix (expert ``j`` the columns, or rows, ``[j m, (j + 1)
m)``): the layout the program's one fused product reads and the reference
slices.

Normal(0, ``assumed.initializer_range``) for matrices and the embedding
(which is the head too).  Unlike a checkpoint's ones, the norm gains are
drawn around 1, so that a path which drops a gain is seen."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HELD = jnp.bfloat16


def sizes(cfg: dict) -> dict:
    d = int(cfg["head_dim"])
    return dict(
        h=int(cfg["hidden_size"]), q=int(cfg["num_attention_heads"]) * d,
        kv=int(cfg["num_key_value_heads"]) * d,
        router=int(cfg["_published"]["num_experts"]),
        held=int(cfg["num_experts"]), k=int(cfg["num_experts_per_tok"]),
        m=int(cfg["intermediate_size"]),
        shared=int(cfg["num_shared_experts"]), vocab=int(cfg["vocab_size"]),
        window=int(cfg["sliding_window"]),
        windowed=tuple(t == "sliding_attention" for t in cfg["layer_types"]))


def layer_shapes(cfg: dict) -> dict:
    """``{name: shape}`` of one layer (both kinds hold the same)."""
    s = sizes(cfg)
    h, wide = s["h"], s["shared"] * s["m"]
    return {"norm": (h,), "q": (h, s["q"]), "k": (h, s["kv"]),
            "v": (h, s["kv"]), "o": (s["q"], h), "router": (h, s["router"]),
            "w_gate": (s["held"], h, s["m"]), "w_up": (s["held"], h, s["m"]),
            "w_down": (s["held"], s["m"], h), "shared_gate": (h, wide),
            "shared_up": (h, wide), "shared_down": (wide, h)}


def shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    return {"embed": (s["vocab"], s["h"]), "final_norm": (s["h"],),
            "layers": [layer_shapes(cfg) for _ in s["windowed"]]}


def _count(tree) -> int:
    return sum(math.prod(shape) for shape in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))


def param_count(cfg: dict) -> int:
    return _count(shapes(cfg))


def layer_params(cfg: dict) -> int:
    return _count(layer_shapes(cfg))


def make(cfg: dict, seed: int) -> dict:
    """The weights of ``shapes(cfg)``, one jitted call a leaf so that the
    float32 draw of one leaf (a layer's 16 held gate matrices: 1.07 GB) is
    all that is ever live beside what is held."""
    std = float(cfg["assumed"]["initializer_range"])

    @functools.partial(jax.jit, static_argnums=(1,))
    def normal(key, shape, offset):
        return (offset + std * jax.random.normal(
            key, shape, jnp.float32)).astype(HELD)

    leaves, treedef = jax.tree.flatten_with_path(
        shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.key(int(seed)), len(leaves))
    return jax.tree.unflatten(treedef, [
        normal(key, shape, 1.0 if path[-1].key.endswith("norm") else 0.0)
        for key, (path, shape) in zip(keys, leaves)])
