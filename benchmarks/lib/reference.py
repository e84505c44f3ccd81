"""Plain GPT-2: forward, loss, gradients and Adam in straightforward jnp.

float32 with ``highest`` matmul precision, no kernels, no cache, no
batching tricks; imports nothing of the program.  Depth is a ``lax.scan``
over the stacked block weights of ``lib/weights.py`` and training goes row
by row with each block rematerialised, so the published sizes fit beside
nothing else on one chip.

Architecture as the repository's decoder runs it (departures from OpenAI's
GPT-2 are the configuration file's ``layer_norm_epsilon``): pre-LN blocks,
learned positions, tanh GELU, biases everywhere, head tied to the token
embedding.

``mode`` is the precision of every matrix product: ``"f32"`` is the
reference; ``"fp8"`` rounds both operands to float8 (e4m3: three bits of mantissa) with one max-abs scale per
contracted vector, the nearest precision below bfloat16 and therefore the
control of ``correct`` (see PERF.md section 2).  Accumulation is float32
in both.  (8-bit integers with the same scales keep as many
significant bits as bfloat16 does and read like it; float8 is the step
that loses something.)"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0   # e4m3 max
    scale = jnp.where(scale == 0, 1.0, scale)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)        # straight-through


def _mm(a, b, mode: str, eq: str):
    """``einsum(eq, a, b)``; the contracted axis is a's last and is named
    ``k`` in ``eq`` for both operands."""
    if mode == "fp8":
        a = _fp8(a, -1)
        b = _fp8(b, eq.split(",")[1].split("->")[0].index("k"))
    elif mode != "f32":
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.einsum(eq, a, b, precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _block(x, w, heads: int, eps: float, mode: str):
    s, h = x.shape
    d = h // heads
    y = _ln(x, w["ln1_g"], w["ln1_b"], eps)
    q = (_mm(y, w["wq"], mode, "sk,kn->sn") + w["bq"]).reshape(s, heads, d)
    k = (_mm(y, w["wk"], mode, "sk,kn->sn") + w["bk"]).reshape(s, heads, d)
    v = (_mm(y, w["wv"], mode, "sk,kn->sn") + w["bv"]).reshape(s, heads, d)
    scores = _mm(q.transpose(1, 0, 2), k.transpose(1, 0, 2), mode,
                 "hqk,htk->hqt") / jnp.sqrt(jnp.float32(d))
    mask = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    a = _mm(p, v.transpose(1, 2, 0), mode, "hqk,hdk->qhd").reshape(s, h)
    x = x + _mm(a, w["wo"], mode, "sk,kn->sn") + w["bo"]
    y = _ln(x, w["ln2_g"], w["ln2_b"], eps)
    y = _gelu_tanh(_mm(y, w["w1"], mode, "sk,kn->sn") + w["b1"])
    return x + _mm(y, w["w2"], mode, "sk,kn->sn") + w["b2"]


def logits_fn(weights: dict, tokens, *, heads: int, eps: float,
              mode: str = "f32", remat: bool = False):
    """``(S,)`` token ids -> ``(S, V)`` float32 next-token logits."""
    s = tokens.shape[0]
    x = weights["wte"][tokens] + weights["wpe"][:s]
    block = functools.partial(_block, heads=heads, eps=eps, mode=mode)
    if remat:
        block = jax.checkpoint(block)
    x, _ = lax.scan(lambda c, w: (block(c, w), None), x, weights["blocks"])
    x = _ln(x, weights["lnf_g"], weights["lnf_b"], eps)
    return _mm(x, weights["wte"], mode, "sk,vk->sv")


def row_loss(weights, x, y, **kw):
    """Mean next-token cross-entropy of one row."""
    logits = logits_fn(weights, x, remat=True, **kw)
    lse = jax.nn.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(logits, y[:, None], 1)[:, 0])


def batch_loss_and_grad(weights, xs, ys, **kw):
    """Mean loss and its gradient over the rows of one batch, one row at a
    time (so activations of one row live at once)."""
    grad = jax.value_and_grad(functools.partial(row_loss, **kw))

    def body(acc, xy):
        loss, g = grad(weights, *xy)
        return jax.tree.map(jnp.add, acc, (loss, g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, weights))
    (loss, g), _ = lax.scan(body, zero, (xs, ys))
    n = xs.shape[0]
    return loss / n, jax.tree.map(lambda t: t / n, g)


FAULTS = ("half_batch", "state_unchanged", "first_batch_again",
          "stale_weights")


def adam_steps(weights, xs, ys, *, lr: float, b1: float = 0.9,
               b2: float = 0.999, adam_eps: float = 1e-8,
               fault: str | None = None, **kw):
    """Follow ``xs.shape[0]`` Adam steps (optax.adam's arithmetic) from
    ``weights``, as one scan over the steps.  Returns ``(losses,
    first_moment, final_weights)``; the first moment after k steps is the
    moving average ``(1 - b1) sum b1^(k-i) g_i`` of the k gradients as
    the optimizer got them.

    ``fault`` plants what the check must catch: the mean taken over half
    of each batch; a step that returns its state unchanged; a chunk that
    feeds its first batch to every step; a chunk whose steps all read the
    weights it started from (a cast hoisted out of the scan)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    half = xs.shape[1] // 2

    def step(carry, xy):
        w, m, v, t = carry
        x, y = (xs[0], ys[0]) if fault == "first_batch_again" else xy
        if fault == "half_batch":
            x, y = x[:half], y[:half]
        loss, g = batch_loss_and_grad(
            weights if fault == "stale_weights" else w, x, y, **kw)
        if fault == "state_unchanged":
            return (w, m, v, t + 1), loss
        m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
        v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        w = jax.tree.map(
            lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + adam_eps),
            w, m, v)
        return (w, m, v, t + 1), loss

    zeros = jax.tree.map(jnp.zeros_like, weights)
    (w, m, _, _), losses = lax.scan(
        step, (weights, zeros, zeros, jnp.ones((), jnp.float32)), (xs, ys))
    return losses, m, w
