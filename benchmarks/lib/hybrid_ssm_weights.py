"""The benchmark's own weights for the hybrid state-space / attention /
latent-expert decoder: made from the seed on the device, held in bfloat16.

The family's checkpoints are published in bfloat16, so the weights ARE
bfloat16 numbers: the program is handed these very arrays (no second copy:
``drivers/hybrid_ssm_tree.py`` only re-labels them) and the reference
raises them to float32 where it uses them.  One list entry a layer, nothing
stacked, so that handing them over moves nothing.  Only the experts HELD
here are made (``experts_held``: 128 of the router's 512).

Normal(0, ``assumed.initializer_range``) for matrices and the embedding;
the convolution's taps normal(0, ``assumed.conv_std``).  A head's
``dt_bias``, ``a_log`` and ``d`` are float32, as the family keeps them:
``softplus(dt_bias)`` log-uniform in ``[time_step_min, time_step_max]``
(floored at ``time_step_floor``), ``A = -exp(a_log)`` uniform in [-16, -1],
as the family initialises them.  Unlike a checkpoint's ones and trained
values, the norm gains and ``d`` are drawn around 1 and the choice bias
around 0 at ``assumed.choice_bias_std``, wide against the spread of the
scores, so that a path which drops a gain or the bias is seen."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HELD = jnp.bfloat16
FLOAT32 = ("choice_bias", "dt_bias", "a_log", "d")


def sizes(cfg: dict) -> dict:
    hn, p = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    g, n = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    return dict(
        h=int(cfg["hidden_size"]), di=hn * p, ssm_heads=hn, ssm_head_dim=p,
        width=hn * p + 2 * g * n, state=n, taps=int(cfg["conv_kernel"]),
        q=int(cfg["num_attention_heads"]) * int(cfg["head_dim"]),
        kv=int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]),
        router=int(cfg["_published"]["n_routed_experts"]),
        held=int(cfg["n_routed_experts"]), k=int(cfg["num_experts_per_tok"]),
        latent=int(cfg["moe_latent_size"]),
        m=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["moe_shared_expert_intermediate_size"]),
        vocab=int(cfg["vocab_size"]), pattern=cfg["hybrid_override_pattern"])


def layer_shapes(cfg: dict, kind: str) -> dict:
    """``{name: shape}`` of one layer; a gain is a one-element tuple."""
    s = sizes(cfg)
    h = s["h"]
    if kind == "M":
        return {"norm": (h,), "in_proj": (h, s["di"] + s["width"]
                                          + s["ssm_heads"]),
                "conv_w": (s["taps"], s["width"]), "conv_b": (s["width"],),
                "dt_bias": (s["ssm_heads"],), "a_log": (s["ssm_heads"],),
                "d": (s["ssm_heads"],), "gate_norm": (s["di"],),
                "out_proj": (s["di"], h)}
    if kind == "*":
        return {"norm": (h,), "q": (h, s["q"]), "k": (h, s["kv"]),
                "v": (h, s["kv"]), "o": (s["q"], h)}
    if kind == "E":
        return {"norm": (h,), "router": (h, s["router"]),
                "choice_bias": (s["router"],),
                "latent_down": (h, s["latent"]), "latent_up": (s["latent"], h),
                "w_up": (s["held"], s["latent"], s["m"]),
                "w_down": (s["held"], s["m"], s["latent"]),
                "shared_up": (h, s["shared"]), "shared_down": (s["shared"], h)}
    raise ValueError(f"layer kind {kind!r}: M, * or E")


def shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    return {"embed": (s["vocab"], s["h"]), "head": (s["h"], s["vocab"]),
            "final_norm": (s["h"],),
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
    float32 draw of one leaf (a layer's 128 held experts: 1.4 GB) is all
    that is ever live beside what is held."""
    a = cfg["assumed"]
    std, bias_std, conv_std = (float(a[k]) for k in (
        "initializer_range", "choice_bias_std", "conv_std"))
    dt_lo, dt_hi, dt_floor = (float(cfg[k]) for k in (
        "time_step_min", "time_step_max", "time_step_floor"))

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def normal(key, shape, dtype, scale, offset):
        return (offset + scale * jax.random.normal(
            key, shape, jnp.float32)).astype(dtype)

    @functools.partial(jax.jit, static_argnums=(1,))
    def dt_bias(key, shape):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.maximum(jnp.exp(u * (math.log(dt_hi) - math.log(dt_lo))
                                 + math.log(dt_lo)), dt_floor)
        return dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1

    @functools.partial(jax.jit, static_argnums=(1,))
    def a_log(key, shape):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))

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
            out.append(a_log(key, shape))
        elif name == "choice_bias":
            out.append(normal(key, shape, jnp.float32, bias_std, 0.0))
        elif name == "d":
            out.append(normal(key, shape, jnp.float32, std, 1.0))
        elif name == "conv_w":
            out.append(normal(key, shape, HELD, conv_std, 0.0))
        else:
            out.append(normal(key, shape, HELD, std,
                              1.0 if name.endswith("norm") else 0.0))
    return jax.tree.unflatten(treedef, out)
