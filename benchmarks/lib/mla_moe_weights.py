"""The benchmark's own weights for the latent-attention, sparse-expert
decoder: made from the seed on the device, held in bfloat16.

The family's checkpoints are published in bfloat16, so the weights ARE
bfloat16 numbers: the program is handed these very arrays (no second copy:
``drivers/mla_moe_tree.py`` only re-labels them) and the reference raises
them to float32 where it uses them.  One list entry a layer, nothing
stacked, so that handing them over moves nothing.

Normal(0, ``initializer_range``) for matrices and the embedding, as the
family initialises them.  Unlike a checkpoint's ones and trained values,
the norm gains are drawn around 1 and the choice bias around 0 at
``assumed.choice_bias_std``, wide against the spread of the scores, so that
a path which drops a gain or the bias is seen."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HELD = jnp.bfloat16


def shapes(cfg: dict) -> dict:
    """``{"embed": shape, ..., "layers": [{name: shape}]}``; a norm's gain
    is marked by a one-element shape tuple."""
    h, hn = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    dn, dr = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    dv, r = int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"])
    e, m = int(cfg["n_routed_experts"]), int(cfg["moe_intermediate_size"])
    f, s = int(cfg["intermediate_size"]), int(cfg["n_shared_experts"]) * m
    vocab = int(cfg["vocab_size"])
    layers = []
    for i in range(int(cfg["num_hidden_layers"])):
        w = {"attn_norm": (h,), "q": (h, hn * (dn + dr)),
             "kv_a": (h, r + dr), "kv_a_norm": (r,),
             "kv_b": (r, hn * (dn + dv)), "o": (hn * dv, h),
             "ffn_norm": (h,)}
        if i < int(cfg["first_k_dense_replace"]):
            w.update(gate=(h, f), up=(h, f), down=(f, h))
        else:
            w.update(router=(h, e), choice_bias=(e,), w_gate=(e, h, m),
                     w_up=(e, h, m), w_down=(e, m, h), shared_gate=(h, s),
                     shared_up=(h, s), shared_down=(s, h))
        layers.append(w)
    return {"embed": (vocab, h), "head": (h, vocab), "final_norm": (h,),
            "layers": layers}


def param_count(cfg: dict) -> int:
    total = 0
    for shape in jax.tree.leaves(shapes(cfg),
                                 is_leaf=lambda x: isinstance(x, tuple)):
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def make(cfg: dict, seed: int) -> dict:
    """The weights of ``shapes(cfg)``, one jitted call a leaf so that the
    float32 draw of one leaf (a layer's 128 experts: 0.8 GB) is all that is
    ever live beside what is held."""
    std = float(cfg["assumed"]["initializer_range"])
    bias_std = float(cfg["assumed"]["choice_bias_std"])

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def normal(key, shape, dtype, scale, offset):
        return (offset + scale * jax.random.normal(
            key, shape, jnp.float32)).astype(dtype)

    tree = shapes(cfg)
    is_shape = lambda x: isinstance(x, tuple)
    leaves, treedef = jax.tree.flatten_with_path(tree, is_leaf=is_shape)
    keys = jax.random.split(jax.random.key(int(seed)), len(leaves))
    out = []
    for key, (path, shape) in zip(keys, leaves):
        name = path[-1].key
        if name == "choice_bias":       # float32, as the family keeps it
            out.append(normal(key, shape, jnp.float32, bias_std, 0.0))
        else:
            out.append(normal(key, shape, HELD, std,
                              1.0 if name.endswith("norm") else 0.0))
    return jax.tree.unflatten(treedef, out)
