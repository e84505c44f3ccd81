"""``lib/costs.py`` against hand counts, and ``lib/reference.py`` against
``models/gpt.GPTLM`` at a tiny size on the CPU."""

from __future__ import annotations

import json

import numpy as np
import pytest

from helpers import ROOT, load

CONFIGS = {name: json.loads(
    (ROOT / "benchmarks/configs" / f"{name}.json").read_text())
    for name in ("gpt2-medium", "gpt2-large")}


def test_hand_counts_of_both_configurations():
    from benchmarks.lib import costs

    med, large = CONFIGS["gpt2-medium"], CONFIGS["gpt2-large"]
    # per layer 2*1024*(4*1024 + 2*4096) = 25.17M, attention 4*1024*512 =
    # 2.10M, head 2*1024*50257 = 102.9M; times 3 for forward and backward
    per_token = 3 * (24 * (25_165_824 + 2_097_152) + 102_926_336)
    assert costs.train_flops_per_token(med, 1024) == per_token
    assert round(per_token / 1e9, 2) == 2.27
    assert costs.param_count(med) == 354_823_168
    assert costs.param_count(large) == 774_030_080
    # one decoded token at 100 keys: 36 * (2*1280*(5120+10240) + 4*1280*100)
    # + 2*1280*50257
    assert costs.forward_flops_per_token(large, 100) == \
        36 * (39_321_600 + 512_000) + 128_657_920
    # a request of 3 prompt and 2 new tokens feeds 4 tokens at 1..4 keys
    assert costs.serve_flops(large, 3, 2) == \
        36 * (4 * 39_321_600 + 4 * 1280 * 10) + 2 * 128_657_920


def test_flash_costs_and_roofline():
    from benchmarks.lib import costs, peaks

    fwd = costs.flash_fwd(batch=4, heads=16, seq=1024, head_dim=64)
    assert fwd["flops"] == 4 * 4 * 16 * 1024 * 1024 * 64 / 2
    assert fwd["bytes"] == 4 * (4 * 16 * 1024 * 64 * 2) + 4 * 16 * 1024 * 4
    bwd = costs.flash_bwd(batch=4, heads=16, seq=1024, head_dim=64)
    assert bwd["flops"] == 2.5 * fwd["flops"]
    least, limit = costs.roofline_seconds(fwd, peaks.lookup("TPU v5e"))
    assert limit == "compute" and least == fwd["flops"] / 197e12


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    from benchmarks.drivers import gpt_tree
    from benchmarks.lib import traffic, weights
    from distributed_tensorflow_tpu.models import create_model

    config = load("tiny-gpt2")
    w = weights.make(config, 2 ** 31 + 3)
    model = create_model("gpt", dtype="float32",
                         **gpt_tree.model_kwargs(config))
    rows = traffic.token_corpus(5, 3, 24, config["vocab_size"])
    kw = dict(heads=config["n_head"], eps=config["layer_norm_epsilon"])
    return (jax, jnp, config, w, model, gpt_tree.to_flax(w),
            jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:]), kw)


def test_weights_round_trip_through_the_flax_tree(tiny):
    from benchmarks.drivers import gpt_tree

    jax, jnp, config, w, model, params, x, y, kw = tiny
    init = model.init(jax.random.key(0), x[:1], train=False)["params"]
    assert jax.tree.structure(init) == jax.tree.structure(params)
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree.leaves(init), jax.tree.leaves(params)))
    back = gpt_tree.from_flax(params)
    assert all(bool(jnp.array_equal(a, b)) for a, b in zip(
        jax.tree.leaves(back), jax.tree.leaves(w)))


def test_reference_forward_and_loss_agree_with_gptlm(tiny):
    from benchmarks.lib import reference
    from distributed_tensorflow_tpu.engines.base import make_loss_fn

    jax, jnp, config, w, model, params, x, y, kw = tiny
    with jax.default_matmul_precision("highest"):
        got = model.apply({"params": params}, x, train=False)
        loss, _ = make_loss_fn(model.apply)(params, x, y, jax.random.key(0))
    ref = jnp.stack([reference.logits_fn(w, row, **kw) for row in x])
    np.testing.assert_allclose(got, ref, atol=2e-5)
    ref_loss, _ = reference.batch_loss_and_grad(w, x, y, **kw)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)


def test_reference_gradients_agree_with_gptlm(tiny):
    from benchmarks.drivers import gpt_tree
    from benchmarks.lib import reference
    from distributed_tensorflow_tpu.engines.base import make_loss_fn

    jax, jnp, config, w, model, params, x, y, kw = tiny
    loss_fn = make_loss_fn(model.apply)
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(lambda p: loss_fn(p, x, y, jax.random.key(0))[0])(
            params)
    _, ref = reference.batch_loss_and_grad(w, x, y, **kw)
    for a, b in zip(jax.tree.leaves(gpt_tree.from_flax(grads)),
                    jax.tree.leaves(ref)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)


def test_reference_adam_follows_optax(tiny):
    import optax

    from benchmarks.lib import reference

    jax, jnp, config, w, model, params, x, y, kw = tiny
    xs, ys = x[:, None], y[:, None]        # three steps of one row
    losses, moment, end = reference.adam_steps(w, xs, ys, lr=1e-3, **kw)
    tx = optax.adam(1e-3)
    p, s = w, tx.init(w)
    for t in range(3):
        _, g = reference.batch_loss_and_grad(p, xs[t], ys[t], **kw)
        u, s = tx.update(g, s, p)
        p = optax.apply_updates(p, u)
    for a, b in zip(jax.tree.leaves(end), jax.tree.leaves(p)):
        np.testing.assert_allclose(a, b, atol=2e-5)   # 2% of one step
    for a, b in zip(jax.tree.leaves(moment), jax.tree.leaves(s[0].mu)):
        np.testing.assert_allclose(a, b, atol=1e-7, rtol=1e-4)
    assert losses[2] < losses[0]
