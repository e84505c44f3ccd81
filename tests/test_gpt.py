"""GPT decoder-LM tests: causal-attention parity across impls, LM training
under DP/TP/FSDP/seq-parallel/pipeline, and the harness/CLI path.

The reference has no language models (SURVEY.md §2.2); these tests hold the
new family to the same oracle discipline as BERT: every parallel rendering
must reproduce single-device dense-attention training step-for-step.
"""

import jax
import numpy as np
import pytest

from distributed_tensorflow_tpu.data.loaders import load_lm_dataset
from distributed_tensorflow_tpu.engines import (
    FSDPEngine, SeqParallelEngine, SyncEngine, Trainer)
from distributed_tensorflow_tpu.models import create_model
from distributed_tensorflow_tpu.parallel import mesh as meshlib


def tiny_gpt(attention_impl="dense", heads=2, partition_model=False,
             vocab_size=64, max_len=64):
    return create_model(
        "gpt", num_classes=vocab_size, hidden=32, layers=1, heads=heads,
        ffn=64, max_len=max_len, dropout_rate=0.0,
        attention_impl=attention_impl, partition_model=partition_model)


@pytest.fixture(scope="module")
def lm_data():
    tr = load_lm_dataset(seq_len=32, vocab_size=64, n_train=512, n_test=256)
    te = load_lm_dataset(seq_len=32, vocab_size=64, n_train=512, n_test=256,
                         split="test")
    return tr, te


# ---------------------------------------------------------------- dataset


def test_lm_synth_dataset(lm_data):
    tr, te = lm_data
    assert tr.x.shape == (512, 32) and tr.y.shape == (512, 32)
    assert tr.num_classes == 64
    # targets are the inputs shifted by one: x[t+1] == y[t]
    np.testing.assert_array_equal(tr.x[:, 1:], tr.y[:, :-1])
    # deterministic in (seed, split); splits disjoint draws of one chain
    tr2 = load_lm_dataset(seq_len=32, vocab_size=64, n_train=512, n_test=256)
    np.testing.assert_array_equal(tr.x, tr2.x)
    assert not np.array_equal(tr.x[:256], te.x)


# ------------------------------------------------- causal impl parity


@pytest.mark.slow
def test_flash_causal_matches_dense(lm_data):
    """Same params, same tokens: the Pallas flash path (interpret mode on
    CPU) must produce the dense-causal logits."""
    tr, _ = lm_data
    x = tr.x[:4]
    dense = tiny_gpt("dense")
    flash = dense.clone(attention_impl="flash")
    params = dense.init(jax.random.key(0), x, train=False)["params"]
    ld = dense.apply({"params": params}, x, train=False)
    lf = flash.apply({"params": params}, x, train=False)
    np.testing.assert_allclose(ld, lf, atol=2e-5, rtol=1e-4)


# ----------------------------------------------------------- DP training


def test_gpt_sync_trains(lm_data):
    tr, te = lm_data
    eng = SyncEngine(tiny_gpt(), mesh=meshlib.create_mesh(8),
                     learning_rate=3e-3)
    t = Trainer(None, engine=eng)
    t.fit(tr, epochs=4, batch_size=64, log_every=0)
    ev = t.evaluate(te, batch_size=64)
    # a learned Markov chain beats the 1/64 ≈ 0.016 uniform floor by a wide
    # margin (measured ~0.097 after 4 epochs of this tiny config; 0.06 keeps
    # seed headroom while still requiring ~4× above chance)
    assert ev["accuracy"] > 0.06, ev
    # eval counts TOKENS for LMs (token_weights broadcast): B × L of them
    assert ev["count"] == len(te) * te.x.shape[1]


def test_gpt_fsdp_step(lm_data):
    tr, _ = lm_data
    eng = FSDPEngine(tiny_gpt(), mesh=meshlib.create_mesh(8))
    state = eng.init_state(jax.random.key(0), tr.x[:8])
    xs, ys = eng.shard_batch(tr.x[:16], tr.y[:16])
    state, m = eng.step(state, xs, ys)
    assert np.isfinite(float(m["loss"]))
    per_dev, total = eng.state_bytes_per_device(state)
    assert per_dev < total


# ------------------------------------------------------- tensor parallel


def test_gpt_tensor_parallel_matches_single_device(lm_data):
    """Megatron-annotated GPT on (data=2, model=4) must reproduce
    single-device training (SGD so fp32 noise stays fp32 noise)."""
    import optax

    tr, _ = lm_data
    x, y = tr.x[:16], tr.y[:16]

    eng1 = SyncEngine(tiny_gpt(heads=4), optimizer=optax.sgd(0.1),
                      mesh=meshlib.create_mesh(1))
    s1 = eng1.init_state(jax.random.key(0), x)
    for _ in range(2):
        xs, ys = eng1.shard_batch(x, y)
        s1, m1 = eng1.step(s1, xs, ys)

    from distributed_tensorflow_tpu.engines.tensor_parallel import (
        TensorParallelEngine)

    tp_mesh = meshlib.create_mesh(8, shape=(2, 4),
                                  axis_names=("data", "model"))
    eng8 = TensorParallelEngine(
        tiny_gpt(heads=4, partition_model=True), optimizer=optax.sgd(0.1),
        mesh=tp_mesh)
    s8 = eng8.init_state(jax.random.key(0), x)
    for _ in range(2):
        xs, ys = eng8.shard_batch(x, y)
        s8, m8 = eng8.step(s8, xs, ys)

    for a, b in zip(jax.tree.leaves(jax.device_get(s1.params)),
                    jax.tree.leaves(jax.device_get(s8.params))):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3)
    assert float(m1["loss"]) == pytest.approx(float(m8["loss"]), abs=1e-4)


# ----------------------------------------------- sequence parallelism (LM)


@pytest.mark.slow
@pytest.mark.parametrize("impl", ["ring", "ring_flash", "ulysses",
                                  "ulysses_flash"])
def test_gpt_seq_parallel_matches_single_device(lm_data, impl):
    """Causal LM under (data=2, seq=4): per-token logits VARY over 'seq'
    (unlike BERT's [CLS] broadcast), exercising the engine's LM loss path —
    must still reproduce single-device dense training step-for-step."""
    import optax

    tr, _ = lm_data
    x, y = tr.x[:16], tr.y[:16]
    heads = 4 if impl.startswith("ulysses") else 2

    eng1 = SyncEngine(tiny_gpt("dense", heads=heads),
                      optimizer=optax.sgd(0.1), mesh=meshlib.create_mesh(1))
    s1 = eng1.init_state(jax.random.key(0), x)
    for _ in range(2):
        xs, ys = eng1.shard_batch(x, y)
        s1, m1 = eng1.step(s1, xs, ys)

    sp_mesh = meshlib.create_mesh(8, shape=(2, 4),
                                  axis_names=("data", "seq"))
    eng8 = SeqParallelEngine(tiny_gpt(impl, heads=heads),
                             optimizer=optax.sgd(0.1), mesh=sp_mesh)
    s8 = eng8.init_state(jax.random.key(0), x)
    for _ in range(2):
        xs, ys = eng8.shard_batch(x, y)
        s8, m8 = eng8.step(s8, xs, ys)

    for a, b in zip(jax.tree.leaves(jax.device_get(s1.params)),
                    jax.tree.leaves(jax.device_get(s8.params))):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3)
    assert float(m1["loss"]) == pytest.approx(float(m8["loss"]), abs=1e-4)


def test_gpt_seq_parallel_eval_counts_tokens(lm_data):
    _, te = lm_data
    sp_mesh = meshlib.create_mesh(8, shape=(2, 4),
                                  axis_names=("data", "seq"))
    eng = SeqParallelEngine(tiny_gpt("ring"), mesh=sp_mesh)
    state = eng.init_state(jax.random.key(0), te.x[:8])
    ev = eng.evaluate(state, te, batch_size=64)
    assert ev["count"] == len(te) * te.x.shape[1]


@pytest.mark.slow
def test_gpt_composite_tp_sp_matches_single_device(lm_data):
    """dp×tp×sp GPT: Megatron-sharded weights (GSPMD) + manual-seq causal
    ring, LM loss varying over 'seq' — must reproduce single-device dense
    training (the composite engine's LM path)."""
    import optax

    from distributed_tensorflow_tpu.engines.composite import CompositeEngine

    tr, _ = lm_data
    x, y = tr.x[:8], tr.y[:8]

    eng1 = SyncEngine(tiny_gpt("dense", heads=2),
                      optimizer=optax.sgd(0.1), mesh=meshlib.create_mesh(1))
    s1 = eng1.init_state(jax.random.key(0), x)
    for _ in range(2):
        xs, ys = eng1.shard_batch(x, y)
        s1, m1 = eng1.step(s1, xs, ys)

    c_mesh = meshlib.create_mesh(
        8, shape=(2, 2, 2), axis_names=("data", "model", "seq"))
    eng8 = CompositeEngine(
        tiny_gpt("ring", heads=2, partition_model=True),
        optimizer=optax.sgd(0.1), mesh=c_mesh)
    s8 = eng8.init_state(jax.random.key(0), x)
    for _ in range(2):
        xs, ys = eng8.shard_batch(x, y)
        s8, m8 = eng8.step(s8, xs, ys)

    for a, b in zip(jax.tree.leaves(jax.device_get(s1.params)),
                    jax.tree.leaves(jax.device_get(s8.params))):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3)
    assert float(m1["loss"]) == pytest.approx(float(m8["loss"]), abs=1e-4)


# ---------------------------------------------------------------- pipeline


@pytest.mark.slow
def test_gpt_pipeline_trains(lm_data):
    """GPT decoder over the pipe axis (embed → blocks → untied head)."""
    from distributed_tensorflow_tpu.engines.pipeline import PipelineEngine
    from distributed_tensorflow_tpu.models.gpt import gpt_pipeline_stages

    tr, _ = lm_data
    pp_mesh = meshlib.create_mesh(8, shape=(2, 4),
                                  axis_names=("data", "pipe"))
    eng = PipelineEngine(
        microbatches=2, mesh=pp_mesh, learning_rate=3e-3,
        stages=gpt_pipeline_stages(vocab_size=64, hidden=32, heads=2,
                                   ffn=64, max_len=32))
    state = eng.init_state(jax.random.key(0), tr.x[:8])
    losses = []
    for i in range(6):
        lo = (i * 16) % 256
        xs, ys = eng.shard_batch(tr.x[lo:lo + 16], tr.y[lo:lo + 16])
        state, m = eng.step(state, xs, ys)
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


# --------------------------------------------------------------- generate


@pytest.mark.slow
def test_generate_greedy_matches_full_forward(lm_data):
    """KV-cache decode oracle: greedy generation must reproduce the
    teacher-forced rollout that re-runs the FULL forward each step — any
    cache/cursor/position bug shows up as a divergent token."""
    from distributed_tensorflow_tpu.models.gpt import generate

    tr, _ = lm_data
    model = tiny_gpt()
    x = tr.x[:2, :8]
    params = model.init(jax.random.key(0), x, train=False)["params"]

    out = np.asarray(generate(model, params, x, max_new_tokens=6,
                              greedy=True))

    cur = np.asarray(x)
    for _ in range(6):
        logits = model.apply({"params": params}, cur, train=False)
        nxt = np.asarray(logits[:, -1].argmax(-1)).astype(cur.dtype)
        cur = np.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, cur[:, 8:])


def test_generate_sampling_shapes_and_bounds(lm_data):
    from distributed_tensorflow_tpu.models.gpt import generate

    tr, _ = lm_data
    model = tiny_gpt()
    x = tr.x[:3, :5]
    params = model.init(jax.random.key(0), x, train=False)["params"]
    out = np.asarray(generate(model, params, x, max_new_tokens=4,
                              temperature=0.8, rng=jax.random.key(7)))
    assert out.shape == (3, 4)
    assert out.dtype == x.dtype
    assert (out >= 0).all() and (out < 64).all()
    # capacity guard: prompt (32) + 40 new > max_len (64)
    with pytest.raises(ValueError, match="max_len"):
        generate(model, params, tr.x[:1], max_new_tokens=40)


# ------------------------------------------------------------ harness/CLI


def _lm_dataset_fn(batch_size, type="train", **kw):
    return load_lm_dataset(seq_len=32, vocab_size=64, n_train=256, n_test=64,
                           split=type)


@pytest.mark.slow
def test_gpt_harness_dp(lm_data):
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    summary = run(ExperimentConfig(
        engine="sync", model="gpt", dataset="lm_synth", n_devices=8,
        batch_size=4, epochs=1, log_every=0, dataset_fn=_lm_dataset_fn))
    assert summary["model"] == "gpt"
    assert np.isfinite(summary["test_loss"])


@pytest.mark.slow
def test_gpt_harness_seq_parallel():
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    summary = run(ExperimentConfig(
        engine="sync", model="gpt", dataset="lm_synth", n_devices=8,
        seq_parallel=4, attention_impl="ring", batch_size=4, epochs=1,
        log_every=0, dataset_fn=_lm_dataset_fn))
    assert summary["engine"] == "seq_parallel[ring]"
    assert np.isfinite(summary["test_loss"])


def test_gpt_rejects_non_token_dataset():
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    with pytest.raises(ValueError, match="lm_synth"):
        run(ExperimentConfig(engine="sync", model="gpt", dataset="mnist",
                             n_devices=8))


# -------------------------------------------------------------------- RoPE


@pytest.mark.slow
def test_rope_gpt_trains_and_beats_chance(lm_data):
    tr, te = lm_data
    model = create_model("gpt", num_classes=64, hidden=32, layers=1,
                         heads=2, ffn=64, max_len=64, dropout_rate=0.0,
                         positional="rope")
    # no learned position table in the param tree
    params = model.init(jax.random.key(0), tr.x[:2], train=False)["params"]
    assert "pos_embed" not in params
    eng = SyncEngine(model, mesh=meshlib.create_mesh(8), learning_rate=3e-3)
    t = Trainer(None, engine=eng)
    t.fit(tr, epochs=3, batch_size=64, log_every=0)
    assert t.evaluate(te, batch_size=64)["accuracy"] > 0.05


@pytest.mark.slow
def test_rope_seq_parallel_matches_single_device(lm_data):
    """RoPE under (data=2, seq=4) ring attention: each seq device must
    rotate its block at GLOBAL positions (offset = block index × local
    length) — an un-offset implementation diverges immediately."""
    import optax

    tr, _ = lm_data
    x, y = tr.x[:16], tr.y[:16]

    def rope_gpt(impl):
        return create_model("gpt", num_classes=64, hidden=32, layers=1,
                            heads=2, ffn=64, max_len=64, dropout_rate=0.0,
                            positional="rope", attention_impl=impl)

    eng1 = SyncEngine(rope_gpt("dense"), optimizer=optax.sgd(0.1),
                      mesh=meshlib.create_mesh(1))
    s1 = eng1.init_state(jax.random.key(0), x)
    for _ in range(2):
        xs, ys = eng1.shard_batch(x, y)
        s1, m1 = eng1.step(s1, xs, ys)

    sp_mesh = meshlib.create_mesh(8, shape=(2, 4),
                                  axis_names=("data", "seq"))
    eng8 = SeqParallelEngine(rope_gpt("ring"), optimizer=optax.sgd(0.1),
                             mesh=sp_mesh)
    s8 = eng8.init_state(jax.random.key(0), x)
    for _ in range(2):
        xs, ys = eng8.shard_batch(x, y)
        s8, m8 = eng8.step(s8, xs, ys)

    for a, b in zip(jax.tree.leaves(jax.device_get(s1.params)),
                    jax.tree.leaves(jax.device_get(s8.params))):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3)
    assert float(m1["loss"]) == pytest.approx(float(m8["loss"]), abs=1e-4)


@pytest.mark.slow
def test_rope_generate_matches_full_forward(lm_data):
    """KV-cache decode with RoPE: cached keys carry their own rotation;
    the cursor position rotates each new q — greedy generation must still
    equal the teacher-forced rollout."""
    from distributed_tensorflow_tpu.models.gpt import generate

    tr, _ = lm_data
    model = create_model("gpt", num_classes=64, hidden=32, layers=1,
                         heads=2, ffn=64, max_len=64, dropout_rate=0.0,
                         positional="rope")
    x = tr.x[:2, :8]
    params = model.init(jax.random.key(0), x, train=False)["params"]
    out = np.asarray(generate(model, params, x, max_new_tokens=5,
                              greedy=True))
    cur = np.asarray(x)
    for _ in range(5):
        logits = model.apply({"params": params}, cur, train=False)
        nxt = np.asarray(logits[:, -1].argmax(-1)).astype(cur.dtype)
        cur = np.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, cur[:, 8:])


@pytest.mark.slow
def test_rope_pipeline_trains(lm_data):
    """RoPE threads through the pipeline stages (no position table in any
    stage's params; blocks rotate at arange(L))."""
    from distributed_tensorflow_tpu.engines.pipeline import PipelineEngine
    from distributed_tensorflow_tpu.models.gpt import gpt_pipeline_stages

    tr, _ = lm_data
    pp_mesh = meshlib.create_mesh(8, shape=(2, 4),
                                  axis_names=("data", "pipe"))
    eng = PipelineEngine(
        microbatches=2, mesh=pp_mesh, learning_rate=3e-3,
        stages=gpt_pipeline_stages(vocab_size=64, hidden=32, heads=2,
                                   ffn=64, max_len=32, positional="rope"))
    state = eng.init_state(jax.random.key(0), tr.x[:8])
    flat = jax.tree_util.tree_flatten_with_path(state.params)[0]
    names = {"/".join(str(getattr(k, "key", k)) for k in p)
             for p, _ in flat}
    assert not any("Embed_1" in n for n in names), names  # no pos table
    losses = []
    for i in range(4):
        lo = (i * 16) % 256
        xs, ys = eng.shard_batch(tr.x[lo:lo + 16], tr.y[lo:lo + 16])
        state, m = eng.step(state, xs, ys)
        losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()


# --------------------------------------------------------------------- GQA


def test_gqa_param_shapes_and_training(lm_data):
    """kv_heads=2 under heads=4: K/V kernels emit half the heads, and the
    model still trains."""
    tr, _ = lm_data
    model = create_model("gpt", num_classes=64, hidden=32, layers=1,
                         heads=4, kv_heads=2, ffn=64, max_len=64,
                         dropout_rate=0.0)
    params = model.init(jax.random.key(0), tr.x[:2], train=False)["params"]
    attn = params["GPTBlock_0"]["CausalSelfAttention_0"]
    assert attn["query"]["kernel"].shape == (32, 32)
    assert attn["key"]["kernel"].shape == (32, 16)   # 2 kv heads × 8
    assert attn["value"]["kernel"].shape == (32, 16)
    eng = SyncEngine(model, mesh=meshlib.create_mesh(8), learning_rate=3e-3)
    s = eng.init_state(jax.random.key(0), tr.x[:8])
    xs, ys = eng.shard_batch(tr.x[:32], tr.y[:32])
    s, first = eng.step(s, xs, ys)
    for _ in range(20):
        s, m = eng.step(s, xs, ys)
    assert float(m["loss"]) < float(first["loss"])


@pytest.mark.parametrize("kvh", [1, 2])
@pytest.mark.slow
def test_gqa_generate_matches_full_forward(lm_data, kvh):
    """MQA/GQA decode: the cache holds kv_heads only; greedy generation
    must still equal the teacher-forced full-forward rollout."""
    from distributed_tensorflow_tpu.models.gpt import generate

    tr, _ = lm_data
    model = create_model("gpt", num_classes=64, hidden=32, layers=1,
                         heads=4, kv_heads=kvh, ffn=64, max_len=64,
                         dropout_rate=0.0)
    x = tr.x[:2, :8]
    params = model.init(jax.random.key(0), x, train=False)["params"]
    out = np.asarray(generate(model, params, x, max_new_tokens=5,
                              greedy=True))
    cur = np.asarray(x)
    for _ in range(5):
        logits = model.apply({"params": params}, cur, train=False)
        nxt = np.asarray(logits[:, -1].argmax(-1)).astype(cur.dtype)
        cur = np.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, cur[:, 8:])


def test_gqa_invalid_heads_rejected(lm_data):
    tr, _ = lm_data
    model = create_model("gpt", num_classes=64, hidden=32, layers=1,
                         heads=4, kv_heads=3, ffn=64, max_len=64)
    with pytest.raises(ValueError, match="kv_heads"):
        model.init(jax.random.key(0), tr.x[:2], train=False)


# ---------------------------------------------------------- checkpointing


@pytest.mark.slow
def test_gpt_checkpoint_roundtrip_and_generate(tmp_path, lm_data):
    """Orbax save → restore of a trained LM state, then generation parity:
    the restored params must produce byte-identical greedy continuations."""
    from distributed_tensorflow_tpu.models.gpt import generate
    from distributed_tensorflow_tpu.utils.checkpoint import CheckpointManager

    tr, _ = lm_data
    model = tiny_gpt()
    eng = SyncEngine(model, mesh=meshlib.create_mesh(8), learning_rate=3e-3)
    state = eng.init_state(jax.random.key(0), tr.x[:8])
    for i in range(3):
        xs, ys = eng.shard_batch(tr.x[i * 32:(i + 1) * 32],
                                 tr.y[i * 32:(i + 1) * 32])
        state, _ = eng.step(state, xs, ys)

    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    jax.block_until_ready(state)
    mgr.save(state)

    template = eng.init_state(jax.random.key(1), tr.x[:8])
    restored = mgr.restore(template)
    assert int(jax.device_get(restored.step)) == int(
        jax.device_get(state.step))

    p0 = jax.device_get(eng.eval_params(state))
    p1 = jax.device_get(eng.eval_params(restored))
    out0 = np.asarray(generate(model, p0, tr.x[:2, :8], max_new_tokens=6,
                               greedy=True))
    out1 = np.asarray(generate(model, p1, tr.x[:2, :8], max_new_tokens=6,
                               greedy=True))
    np.testing.assert_array_equal(out0, out1)


@pytest.mark.slow
def test_lm_summary_reports_perplexity():
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    summary = run(ExperimentConfig(
        engine="sync", model="gpt", dataset="lm_synth", n_devices=8,
        batch_size=4, epochs=1, log_every=0, dataset_fn=_lm_dataset_fn))
    assert summary["test_perplexity"] == pytest.approx(
        np.exp(summary["test_loss"]), rel=1e-6)


# ------------------------------------------------- engine-matrix breadth


@pytest.mark.slow
def test_gpt_bf16_trains_finite(lm_data):
    """Mixed precision (bf16 activations, f32 params) on the LM: loss
    stays finite and decreases."""
    import jax.numpy as jnp

    tr, _ = lm_data
    model = create_model("gpt", num_classes=64, hidden=32, layers=1,
                         heads=2, ffn=64, max_len=64, dropout_rate=0.0,
                         dtype=jnp.bfloat16)
    p = model.init(jax.random.key(0), tr.x[:2], train=False)["params"]
    assert jax.tree.leaves(p)[0].dtype == jnp.float32  # params stay f32
    eng = SyncEngine(model, mesh=meshlib.create_mesh(8), learning_rate=3e-3)
    s = eng.init_state(jax.random.key(0), tr.x[:8])
    xs, ys = eng.shard_batch(tr.x[:32], tr.y[:32])
    s, first = eng.step(s, xs, ys)
    for _ in range(20):
        s, m = eng.step(s, xs, ys)
    assert np.isfinite(float(m["loss"]))
    assert float(m["loss"]) < float(first["loss"])


@pytest.mark.parametrize("engine_name", ["async", "gossip"])
@pytest.mark.slow
def test_gpt_under_async_and_gossip(lm_data, engine_name):
    """The LM trains under the reference-parity DP engines too (local-SGD
    async, ppermute gossip) — (B, L) labels need no engine special-casing."""
    from distributed_tensorflow_tpu.engines import create_engine

    tr, te = lm_data
    kw = {"sync_every": 4} if engine_name == "async" else {"degree": 1}
    eng = create_engine(engine_name, tiny_gpt(),
                        mesh=meshlib.create_mesh(8), learning_rate=3e-3,
                        **kw)
    t = Trainer(None, engine=eng)
    t.fit(tr, epochs=2, batch_size=64, log_every=0)
    ev = t.evaluate(te, batch_size=64)
    assert np.isfinite(ev["loss"])
    assert ev["accuracy"] > 0.03  # above the 1/64 floor


@pytest.mark.slow
def test_decode_cache_overflow_flag():
    """Direct decode-API use past max_len cannot raise (the cursor is
    traced) but must not stay silent: the sticky cache['overflow'] flag
    flips once a token would land past capacity (ADVICE r3)."""
    import jax.numpy as jnp

    def overflowed(cache):
        leaves = [leaf for path, leaf
                  in jax.tree_util.tree_flatten_with_path(cache)[0]
                  if "overflow" in jax.tree_util.keystr(path)]
        assert leaves, "decode cache carries no overflow flag"
        return any(bool(x) for x in leaves)

    model = tiny_gpt(max_len=4).clone(decode=True)
    tok = np.zeros((1, 1), np.int32)
    variables = model.init(jax.random.key(0), jnp.asarray(tok), train=False)
    params, cache = variables["params"], variables["cache"]

    flags = []
    for _ in range(6):
        _, upd = model.apply({"params": params, "cache": cache},
                             jnp.asarray(tok), train=False,
                             mutable=["cache"])
        cache = upd["cache"]
        flags.append(overflowed(cache))
    # within capacity: clean; past it: sticky True
    assert flags == [False, False, False, False, True, True]


def test_remat_grad_parity_dp(lm_data):
    """Model-level remat (nn.remat per block) is a scheduling change only:
    identical loss and SGD step on the sync DP path."""
    import optax

    tr, _ = lm_data
    x, y = tr.x[:16], tr.y[:16]
    out = {}
    for remat in (False, True):
        model = create_model("gpt", num_classes=64, hidden=32, layers=2,
                             heads=2, ffn=64, max_len=64, dropout_rate=0.0,
                             remat=remat)
        eng = SyncEngine(model, optimizer=optax.sgd(0.1),
                         mesh=meshlib.create_mesh(8))
        st = eng.init_state(jax.random.key(0), x)
        st, m = eng.step(st, *eng.shard_batch(x, y))
        out[remat] = (float(m["loss"]), jax.device_get(st.params))
    assert out[False][0] == pytest.approx(out[True][0], abs=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5),
        out[False][1], out[True][1])


@pytest.mark.slow
def test_remat_composes_with_ring_seq_parallel(lm_data):
    """--remat under dp×sp: nn.remat'd blocks containing ring ppermutes
    replay symmetrically across seq devices; parity vs the non-remat run."""
    import optax

    tr, _ = lm_data
    x, y = tr.x[:8], tr.y[:8]
    mesh = meshlib.create_mesh(
        8, shape=(2, 4), axis_names=(meshlib.DATA_AXIS, meshlib.SEQ_AXIS))
    out = {}
    for remat in (False, True):
        model = create_model("gpt", num_classes=64, hidden=32, layers=2,
                             heads=2, ffn=64, max_len=64, dropout_rate=0.0,
                             attention_impl="ring", remat=remat)
        eng = SeqParallelEngine(model, optimizer=optax.sgd(0.1), mesh=mesh)
        st = eng.init_state(jax.random.key(0), x)
        st, m = eng.step(st, *eng.shard_batch(x, y))
        out[remat] = (float(m["loss"]), jax.device_get(st.params))
    assert out[False][0] == pytest.approx(out[True][0], abs=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5),
        out[False][1], out[True][1])


def test_remat_cli_rejects_non_transformer():
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    with pytest.raises(ValueError, match="remat"):
        run(ExperimentConfig(engine="sync", model="mlp", dataset="synthetic",
                             n_devices=8, remat=True))


# -------------------------------------------------- multi-device generate


def test_generate_batch_parallel_matches_single_device(lm_data):
    """generate(mesh=...) shards the prompt batch over 'data': tokens must
    be identical to the single-device sampler (same params, same rng)."""
    from distributed_tensorflow_tpu.models.gpt import generate

    tr, _ = lm_data
    model = tiny_gpt()
    x = tr.x[:8, :8]
    params = model.init(jax.random.key(0), x, train=False)["params"]

    ref = np.asarray(generate(model, params, x, max_new_tokens=5,
                              greedy=True))
    mesh = meshlib.create_mesh(8)
    out = np.asarray(generate(model, params, x, max_new_tokens=5,
                              greedy=True, mesh=mesh))
    np.testing.assert_array_equal(ref, out)


@pytest.mark.slow
def test_generate_tp_decode_matches_single_device(lm_data):
    """TP decode: a partition_model GPT generates under a ('data','model')
    mesh with params kept Megatron-sharded — tokens must match the
    single-device sampler on the same (replicated) params."""
    from distributed_tensorflow_tpu.models.gpt import generate

    tr, _ = lm_data
    tp_model = tiny_gpt(partition_model=True)
    plain = tiny_gpt(partition_model=False)
    x = tr.x[:4, :8]
    # init unsharded (annotations only box metadata at init under jit);
    # reference tokens from the plain clone on identical param values
    params = jax.tree.map(
        lambda l: getattr(l, "value", l),
        tp_model.init(jax.random.key(1), x, train=False)["params"])
    ref = np.asarray(generate(plain, params, x, max_new_tokens=5,
                              greedy=True))

    mesh = meshlib.create_mesh(
        8, shape=(2, 4),
        axis_names=(meshlib.DATA_AXIS, meshlib.MODEL_AXIS))
    out = np.asarray(generate(tp_model, params, x, max_new_tokens=5,
                              greedy=True, mesh=mesh))
    np.testing.assert_array_equal(ref, out)


def test_gpt_seq_parallel_grad_accum_parity(lm_data):
    """grad_accum under dp×sp with an LM: loss/acc vary over BOTH manual
    axes (per-token blocks), exercising the varying-carry scan path."""
    import optax

    tr, _ = lm_data
    x, y = tr.x[:8], tr.y[:8]
    mesh = meshlib.create_mesh(
        8, shape=(2, 4), axis_names=(meshlib.DATA_AXIS, meshlib.SEQ_AXIS))
    out = {}
    for K in (1, 2):
        model = tiny_gpt("ring")
        eng = SeqParallelEngine(model, optimizer=optax.sgd(0.1), mesh=mesh,
                                grad_accum=K)
        st = eng.init_state(jax.random.key(0), x)
        st, m = eng.step(st, *eng.shard_batch(x, y))
        out[K] = (float(m["loss"]), jax.device_get(st.params))
    assert out[1][0] == pytest.approx(out[2][0], abs=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5),
        out[1][1], out[2][1])


# ---------------------------------------------------------------- --sample


def test_harness_sample_after_training():
    """--sample N: the summary carries greedy continuations decoded from
    the trained params (deterministic per seed), shaped (data_shards, N),
    with token ids inside the vocab."""
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    out = run(ExperimentConfig(
        model="gpt", dataset="lm_synth", engine="sync", n_devices=8,
        batch_size=4, epochs=1, log_every=0, sample_tokens=6,
        sample_prompt_len=4,
        model_args={"hidden": 32, "layers": 1, "heads": 2, "ffn": 64}))
    samples = np.asarray(out["samples"])
    prompts = np.asarray(out["sample_prompts"])
    assert samples.shape == (8, 6) and prompts.shape == (8, 4)
    # lm_synth's default vocab is 128 (data/loaders.py load_lm_dataset)
    assert (samples >= 0).all() and (samples < 128).all()


def test_harness_sample_validation():
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    # --sample under --pipeline-parallel works since round 5 (sequential-
    # forward decode over pipe-stacked GPT stages, engines/pipeline.py
    # generate; oracle-tested in tests/test_pipeline.py) — the rejection
    # that remains is a pipeline whose stages END IN A CLASSIFIER
    with pytest.raises(ValueError, match="causal LM"):
        run(ExperimentConfig(model="bert_tiny", dataset="glue_synth",
                             pipeline_parallel=4, sample_tokens=4,
                             n_devices=8))
    with pytest.raises(ValueError, match="causal LM"):
        run(ExperimentConfig(model="mlp", dataset="synthetic",
                             sample_tokens=4, n_devices=8))
    # deterministically-knowable failures raise BEFORE training: a
    # post-train raise would waste the run (and loop under --max-restarts)
    base = dict(model="gpt", dataset="lm_synth", engine="sync", n_devices=8,
                model_args={"hidden": 32, "layers": 1, "heads": 2,
                            "ffn": 64})
    with pytest.raises(ValueError, match="positive"):
        run(ExperimentConfig(sample_tokens=-4, **base))
    with pytest.raises(ValueError, match="sample-prompt-len"):
        run(ExperimentConfig(sample_tokens=4, sample_prompt_len=500, **base))
    with pytest.raises(ValueError, match="capacity"):
        run(ExperimentConfig(sample_tokens=4, sample_prompt_len=128,
                             **{**base, "model_args": {
                                 "hidden": 32, "layers": 1, "heads": 2,
                                 "ffn": 64, "max_len": 128}}))


# ------------------------------------------- slot prefill from position 0

@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("kv_quant", [False, True], ids=["float", "int8"])
def test_slot_prefill_block_is_the_training_forward_at_one_row(remat,
                                                               kv_quant):
    """``prompt_len`` in slot-decode mode (the block prefill of
    serving/kv_cache.py ``insert``): ONE call over a padded prompt from
    position 0 returns logits for the last real position alone, equal to
    the training-mode forward's row there (pad tokens sit after it), and
    leaves every layer's K/V of the whole block in the table's rows
    ``[0, L)`` — the rows the one-token step writes one call a token —
    with the rows past the block as they were."""
    import jax.numpy as jnp

    lpad, lp, max_len = 8, 5, 16
    model = create_model("gpt", num_classes=64, hidden=32, layers=2, heads=2,
                         ffn=64, max_len=max_len, dropout_rate=0.0,
                         remat=remat)
    toks = jnp.asarray(np.random.default_rng(30).integers(0, 64, (1, lpad)),
                       jnp.int32)
    params = model.init(jax.random.key(0), toks, train=False)["params"]
    dm = model.slot_decode_clone(kv_quant=kv_quant)
    pos = jnp.arange(lpad, dtype=jnp.int32)[None, :]
    shapes = jax.eval_shape(
        lambda: dm.init(jax.random.key(0), toks[:, :1], train=False,
                        positions=pos[:, :1]))["cache"]
    before = jax.tree.map(lambda s: jnp.full(s.shape, 3, s.dtype), shapes)

    logits, upd = dm.apply({"params": params, "cache": before}, toks,
                           train=False, positions=pos,
                           prompt_len=jnp.asarray([lp], jnp.int32),
                           mutable=["cache"])
    assert logits.shape == (1, 1, 64)
    full = model.apply({"params": params}, toks, train=False)
    np.testing.assert_allclose(logits[0, 0], full[0, lp - 1],
                               atol=1e-5, rtol=1e-5)

    # the one-token step over the same tokens, a call a position
    cache = before
    for t in range(lpad):
        _, step = dm.apply({"params": params, "cache": cache},
                           toks[:, t:t + 1], train=False,
                           positions=pos[:, t:t + 1], mutable=["cache"])
        cache = step["cache"]
    for b, got, want in zip(*map(jax.tree.leaves,
                                 (before, upd["cache"], cache))):
        assert got.dtype == b.dtype and got.shape == b.shape
        got, want = np.asarray(got), np.asarray(want)
        if got.dtype == np.int8:
            # past the first layer the step attended to DEQUANTIZED keys
            # where the block sees its own unquantized ones: a few codes
            # of 127, not rounding
            assert np.abs(got[:, :lpad].astype(int)
                          - want[:, :lpad].astype(int)).max() <= 4
        else:
            tol = 0.03 if kv_quant else 1e-5        # int8: the scales
            np.testing.assert_allclose(got[:, :lpad], want[:, :lpad],
                                       atol=tol, rtol=tol)
        np.testing.assert_array_equal(got[:, lpad:], np.asarray(b)[:, lpad:])


@pytest.mark.parametrize("clone, match", [
    (dict(), "decode_slots"),
    (dict(decode=True), "decode_slots"),
], ids=["training", "cursor_decode"])
def test_prompt_len_is_refused_outside_the_monolithic_slot_mode(clone, match):
    import jax.numpy as jnp

    model = tiny_gpt(max_len=16).clone(**clone)
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(lambda: model.init(
            jax.random.key(0), toks, train=False,
            positions=toks if model.decode_slots else None,
            prompt_len=jnp.asarray([3], jnp.int32)))
