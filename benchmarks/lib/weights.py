"""The benchmark's own weights: one jitted call from the seed, on device.

The weights belong to the benchmark, not to the program: the program's
state is overwritten with them and the reference reads the same arrays, so
neither side takes anything the other has made.  The canonical form stacks
every block leaf over the layers (``blocks[name]`` has a leading ``L``),
which is what lets the reference scan over depth."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# block leaf -> (shape in terms of h/ffn, kind)
_BLOCK = {
    "ln1_g": ("h", "one"), "ln1_b": ("h", "bias"),
    "wq": ("hh", "w"), "bq": ("h", "bias"),
    "wk": ("hh", "w"), "bk": ("h", "bias"),
    "wv": ("hh", "w"), "bv": ("h", "bias"),
    "wo": ("hh", "w"), "bo": ("h", "bias"),
    "ln2_g": ("h", "one"), "ln2_b": ("h", "bias"),
    "w1": ("hf", "w"), "b1": ("f", "bias"),
    "w2": ("fh", "w"), "b2": ("h", "bias"),
}


def make(cfg: dict, seed: int) -> dict:
    """float32 weights in the canonical stacked form.  GPT-2's own
    initialisation (normal, ``initializer_range``) for matrices and
    embeddings; biases and layer-norm offsets get the same small normal
    instead of zero, so a path that drops one is seen."""
    h, layers = int(cfg["n_embd"]), int(cfg["n_layer"])
    ffn = int(cfg.get("n_inner") or 4 * h)
    std = float(cfg.get("initializer_range", 0.02))
    dims = {"h": (h,), "f": (ffn,), "hh": (h, h), "hf": (h, ffn),
            "fh": (ffn, h)}

    @jax.jit
    def build(key):
        keys = iter(jax.random.split(key, len(_BLOCK) + 4))

        def normal(shape):
            return std * jax.random.normal(next(keys), shape, jnp.float32)

        blocks = {}
        for name, (dim, kind) in _BLOCK.items():
            shape = (layers,) + dims[dim]
            blocks[name] = (1.0 + normal(shape)) if kind == "one" \
                else normal(shape)
        return {"wte": normal((int(cfg["vocab_size"]), h)),
                "wpe": normal((int(cfg["n_positions"]), h)),
                "lnf_g": 1.0 + normal((h,)), "lnf_b": normal((h,)),
                "blocks": blocks}

    return build(jax.random.key(int(seed)))


def leaf_norms(tree: dict) -> dict:
    """L2 norm of every leaf, a block leaf giving one norm per layer:
    ``{"wte": (), ..., "blocks": {"wq": (L,), ...}}``."""
    def norm(x, keep):
        x = x.astype(jnp.float32)
        axes = tuple(range(keep, x.ndim))
        return jnp.sqrt(jnp.sum(x * x, axis=axes))

    return {k: ({n: norm(v, 1) for n, v in tree[k].items()}
                if k == "blocks" else norm(tree[k], 0)) for k in tree}
