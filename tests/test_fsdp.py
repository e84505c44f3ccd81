"""FSDP engine tests: math parity with sync DP, the 1/n memory claim, and
the CLI/harness wiring.

The reference has no FSDP (its optimizer simply lives whole on the server,
reference server.py:52-55); these tests pin the TPU-first contract instead:
identical training math to SyncEngine with ~1/n per-device state bytes.
"""

import jax
import numpy as np
import optax
import pytest

from distributed_tensorflow_tpu.data.loaders import (
    Dataset, synthetic_classification)
from distributed_tensorflow_tpu.engines import (
    FSDPEngine, SyncEngine, Trainer, create_engine)
from distributed_tensorflow_tpu.engines.fsdp import fsdp_spec
from distributed_tensorflow_tpu.models import create_model
from distributed_tensorflow_tpu.parallel import mesh as meshlib


def tiny_data(n=512, split="train"):
    x, y = synthetic_classification((8, 8), 4, n, seed=3, split=split)
    return Dataset(x=x, y=y, num_classes=4, name="tiny", synthetic=True)


def tiny_model(**kw):
    return create_model("mlp", num_classes=4, hidden=32, **kw)


@pytest.fixture(scope="module")
def data():
    return tiny_data(), tiny_data(128, "test")


def test_fsdp_spec_picks_largest_divisible_dim():
    assert fsdp_spec((64, 32), 8) == jax.sharding.PartitionSpec("data", None)
    assert fsdp_spec((8, 512), 8) == jax.sharding.PartitionSpec(None, "data")
    assert fsdp_spec((7, 9), 8) == jax.sharding.PartitionSpec()   # replicate
    assert fsdp_spec((), 8) == jax.sharding.PartitionSpec()       # scalar


def test_fsdp_matches_sync_math(data):
    """FSDP must be sync DP in different clothes: same global batch, same
    SGD updates (SGD is linear in the gradient, so a wrong grad scale or a
    dropped reduce-scatter fails loudly; Adam would mask scale bugs)."""
    train, _ = data
    x, y = train.x[:64], train.y[:64]

    results = {}
    for cls in (SyncEngine, FSDPEngine):
        mesh = meshlib.create_mesh(8)
        model = tiny_model(dropout_rate=0.0)
        eng = cls(model, optimizer=optax.sgd(0.5), mesh=mesh)
        state = eng.init_state(jax.random.key(0), x)
        for _ in range(3):
            xs, ys = eng.shard_batch(x, y)
            state, m = eng.step(state, xs, ys)
        results[cls.__name__] = (jax.device_get(eng.eval_params(state)),
                                 float(m["loss"]))

    for a, b in zip(jax.tree.leaves(results["SyncEngine"][0]),
                    jax.tree.leaves(results["FSDPEngine"][0])):
        np.testing.assert_allclose(a, b, atol=1e-5)
    assert results["SyncEngine"][1] == pytest.approx(
        results["FSDPEngine"][1], abs=1e-5)


def test_fsdp_state_is_sharded_one_nth(mesh8, data):
    """The FSDP memory claim: per-device param+opt bytes ≈ 1/n of the
    replicated total (adam: mu+nu mirror params, all sharded; the residue
    is odd-sized biases and scalar counts)."""
    train, _ = data
    eng = FSDPEngine(tiny_model(), optimizer=optax.adam(1e-3), mesh=mesh8)
    state = eng.init_state(jax.random.key(0), train.x[:8])
    per_dev, total = eng.state_bytes_per_device(state)
    n = eng.n_devices
    # the MLP's kernels ((64,32)/(32,4) at hidden=32... use real fractions):
    # everything with an 8-divisible dim shards; allow the small replicated
    # residue but require most bytes gone from each device
    assert per_dev < total / n * 2.0, (per_dev, total)
    assert per_dev < total * 0.3, (per_dev, total)

    # the update must PRESERVE the layout step over step (out_shardings pin)
    xs, ys = eng.shard_batch(train.x[:64], train.y[:64])
    new_state, _ = eng.step(state, xs, ys)
    expected = jax.tree.leaves(eng._state_shardings)
    actual = jax.tree.leaves(jax.tree.map(lambda l: l.sharding, new_state))
    for before, after in zip(expected, actual):
        assert before == after


def test_fsdp_converges_and_cli_selects(mesh8, data):
    """End-to-end: -m d -ds fsdp maps to the engine; training converges on
    the tiny task through the standard Trainer."""
    from distributed_tensorflow_tpu.cli import build_parser, select_engine

    args = build_parser().parse_args(["-m", "d", "-ds", "fsdp"])
    assert select_engine(args) == "fsdp"

    # the Pallas flash kernel cannot ride the engine's GSPMD program on
    # more than one device (Mosaic refuses automatic partitioning on the
    # chip): rejected by name before any data is loaded, on every platform
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    with pytest.raises(ValueError, match="fsdp engine cannot hold"):
        run(ExperimentConfig(engine="fsdp", model="gpt", dataset="lm_synth",
                             n_devices=8, attention_impl="flash"))

    train, test = data
    eng = create_engine("fsdp", tiny_model(), mesh=mesh8, learning_rate=5e-3)
    tr = Trainer(None, engine=eng, seed=0)
    tr.fit(train, epochs=6, batch_size=64, log_every=0)
    acc = tr.evaluate(test)["accuracy"]
    assert acc > 0.9, f"fsdp reached only {acc}"


def test_fsdp_works_with_annotated_model(mesh8):
    """A model carrying with_partitioning boxes (the TP MLP) must still
    init/step under FSDP — the boxes are unboxed and the shape rule wins."""
    from distributed_tensorflow_tpu.engines import TPMLP

    eng = FSDPEngine(TPMLP(num_classes=4, hidden=64), mesh=mesh8)
    x = np.random.default_rng(0).random((16, 8, 8, 1), np.float32)
    y = (np.arange(16) % 4).astype(np.int32)
    state = eng.init_state(jax.random.key(0), x)
    xs, ys = eng.shard_batch(x, y)
    state, m = eng.step(state, xs, ys)
    assert np.isfinite(float(m["loss"]))


# ----------------------------------------------------------- fsdp x tp


def _tp_bert(partition_model=True):
    return create_model("bert_tiny", num_classes=2, vocab_size=64, hidden=32,
                        layers=1, heads=2, ffn=64, max_len=16,
                        dropout_rate=0.0, partition_model=partition_model)


def _fsdp_tp_mesh():
    return meshlib.create_mesh(
        8, shape=(4, 2), axis_names=(meshlib.DATA_AXIS, meshlib.MODEL_AXIS))


def _bert_tokens(n=8, seed=5):
    rnd = np.random.default_rng(seed)
    return (rnd.integers(1, 64, (n, 16)).astype(np.int32),
            (np.arange(n) % 2).astype(np.int32))


@pytest.mark.slow
def test_fsdp_tp_matches_sync_math():
    """fsdp×tp on a ('data','model') mesh must train identically to plain
    sync DP of the same (unannotated) model: the Megatron annotations and
    the data-dim storage sharding change layout, never math (SGD, so any
    wrong grad scale or dropped collective fails loudly)."""
    x, y = _bert_tokens()

    sync = SyncEngine(_tp_bert(partition_model=False),
                      optimizer=optax.sgd(0.5), mesh=meshlib.create_mesh(8))
    fsdp = FSDPEngine(_tp_bert(partition_model=True),
                      optimizer=optax.sgd(0.5), mesh=_fsdp_tp_mesh())
    results = {}
    for name, eng in (("sync", sync), ("fsdp_tp", fsdp)):
        state = eng.init_state(jax.random.key(0), x)
        for _ in range(3):
            state, m = eng.step(state, *eng.shard_batch(x, y))
        results[name] = (jax.device_get(eng.eval_params(state)),
                         float(m["loss"]))
    assert abs(results["sync"][1] - results["fsdp_tp"][1]) < 1e-5
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4),
        results["sync"][0], results["fsdp_tp"][0])


@pytest.mark.slow
def test_fsdp_tp_state_sharded_over_both_axes():
    """Per-device state bytes under fsdp×tp must undercut even a perfect
    1/dp data-only sharding: the model dims shard too."""
    x, y = _bert_tokens()
    eng = FSDPEngine(_tp_bert(), mesh=_fsdp_tp_mesh())
    state = eng.init_state(jax.random.key(1), x)
    per_dev, total = eng.state_bytes_per_device(state)
    assert per_dev < total / 4, (per_dev, total)


@pytest.mark.slow
def test_fsdp_grad_accum_matches_k1():
    """K-microbatch accumulation under FSDP: identical SGD math to K=1."""
    x, y = _bert_tokens(n=16)
    outs = []
    for K in (1, 4):
        eng = FSDPEngine(_tp_bert(partition_model=False),
                         optimizer=optax.sgd(0.5),
                         mesh=meshlib.create_mesh(8), grad_accum=K)
        state = eng.init_state(jax.random.key(2), x)
        state, m = eng.step(state, *eng.shard_batch(x, y))
        outs.append((float(m["loss"]),
                     jax.device_get(eng.eval_params(state))))
    assert abs(outs[0][0] - outs[1][0]) < 1e-6
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4),
        outs[0][1], outs[1][1])


@pytest.mark.slow
def test_fsdp_tp_harness_run():
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    summary = run(ExperimentConfig(
        engine="fsdp", model="bert_tiny", dataset="glue_synth", n_devices=8,
        tensor_parallel=2, grad_accum=2, batch_size=4, epochs=1, log_every=0,
        model_args={"hidden": 32, "layers": 1, "heads": 2, "ffn": 64,
                    "vocab_size": 1024, "max_len": 128}))
    assert summary["engine"] == "fsdp_tp[fsdp*tp]"
    assert np.isfinite(summary["test_loss"])
