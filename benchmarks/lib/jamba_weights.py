"""The benchmark's own weights for the selective-scan / multi-query-attention
hybrid decoder: made from the seed on the device, held in bfloat16.

The family's checkpoints are published in bfloat16, so the weights ARE
bfloat16 numbers: the program is handed these very arrays (no second copy:
``drivers/jamba_tree.py`` only re-labels them) and the reference raises them
to float32 where it uses them.  One list entry a layer, nothing stacked, so
that handing them over moves nothing.

Normal(0, ``assumed.initializer_range``) for matrices and the embedding; the
convolution's taps normal(0, ``assumed.conv_std``) and its bias normal(0,
``assumed.conv_bias_std``).  A channel's ``dt_bias``, ``a_log`` and ``d`` are
float32, as the family keeps them: ``softplus(dt_bias)`` log-uniform in
``assumed.dt_range``, ``A[d, n] = -(n + 1)`` and ``D = 1``, as the family
initialises them.  Unlike a checkpoint's ones, the norm gains (the three
inner ones too) are drawn around 1 at ``initializer_range``, so that a path
which drops a gain is seen."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HELD = jnp.bfloat16


def sizes(cfg: dict) -> dict:
    h, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    head_dim = int(cfg.get("head_dim") or h // heads)
    period, offset = (int(cfg[k]) for k in ("attn_layer_period",
                                            "attn_layer_offset"))
    layers = int(cfg["num_hidden_layers"])
    return dict(
        h=h, di=int(cfg["mamba_expand"]) * h, state=int(cfg["mamba_d_state"]),
        rank=int(cfg["mamba_dt_rank"]), taps=int(cfg["mamba_d_conv"]),
        q=heads * head_dim, kv=int(cfg["num_key_value_heads"]) * head_dim,
        ffn=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
        # the family's rule for the order of the layer types
        pattern="".join("A" if i % period == offset else "M"
                        for i in range(layers)))


def layer_shapes(cfg: dict, kind: str) -> dict:
    """``{name: shape}`` of one layer; a gain is a one-element tuple."""
    s = sizes(cfg)
    h, di, n, r = s["h"], s["di"], s["state"], s["rank"]
    both = {"norm": (h,), "ffn_norm": (h,), "gate": (h, s["ffn"]),
            "up": (h, s["ffn"]), "down": (s["ffn"], h)}
    if kind == "M":
        return {**both, "in_proj": (h, 2 * di), "conv_w": (s["taps"], di),
                "conv_b": (di,), "x_proj": (di, r + 2 * n), "dt_norm": (r,),
                "b_norm": (n,), "c_norm": (n,), "dt_proj": (r, di),
                "dt_bias": (di,), "a_log": (di, n), "d": (di,),
                "out_proj": (di, h)}
    if kind == "A":
        return {**both, "q": (h, s["q"]), "k": (h, s["kv"]),
                "v": (h, s["kv"]), "o": (s["q"], h)}
    raise ValueError(f"layer kind {kind!r}: M or A")


def shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    return {"embed": (s["vocab"], s["h"]), "final_norm": (s["h"],),
            "layers": [layer_shapes(cfg, kind) for kind in s["pattern"]]}


def _count(tree) -> int:
    return sum(math.prod(shape) for shape in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, tuple)))


def param_count(cfg: dict) -> int:
    return _count(shapes(cfg))


def layer_params(cfg: dict, kind: str) -> int:
    return _count(layer_shapes(cfg, kind))


def make(cfg: dict, seed: int) -> dict:
    """The weights of ``shapes(cfg)``, one jitted call a leaf so that the
    float32 draw of one leaf is all that is ever live beside what is
    held."""
    a = cfg["assumed"]
    std, conv_std, bias_std = (float(a[k]) for k in (
        "initializer_range", "conv_std", "conv_bias_std"))
    dt_lo, dt_hi = (float(v) for v in a["dt_range"])

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def normal(key, shape, dtype, scale, offset):
        return (offset + scale * jax.random.normal(
            key, shape, jnp.float32)).astype(dtype)

    @functools.partial(jax.jit, static_argnums=(1,))
    def dt_bias(key, shape):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(dt_hi) - math.log(dt_lo))
                     + math.log(dt_lo))
        return dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1

    tree = shapes(cfg)
    leaves, treedef = jax.tree.flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.key(int(seed)), len(leaves))
    out = []
    for key, (path, shape) in zip(keys, leaves):
        name = path[-1].key
        if name == "dt_bias":
            out.append(dt_bias(key, shape))
        elif name == "a_log":
            out.append(jnp.broadcast_to(jnp.log(jnp.arange(
                1, shape[1] + 1, dtype=jnp.float32)), shape) + 0.0)
        elif name == "d":
            out.append(jnp.ones(shape, jnp.float32))
        elif name == "conv_w":
            out.append(normal(key, shape, HELD, conv_std, 0.0))
        elif name == "conv_b":
            out.append(normal(key, shape, HELD, bias_std, 0.0))
        else:
            out.append(normal(key, shape, HELD, std,
                              1.0 if name.endswith("norm") else 0.0))
    return jax.tree.unflatten(treedef, out)
