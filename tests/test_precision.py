"""End-to-end mixed precision (ISSUE 8): the --precision policy layer.

Covers the policy/wrapper math, the GSPMD engines (FSDP is pure jit), the
Trainer/report/harness plumbing, the checkpoint adoption path and the
sync-engine variants (explicit shard_map collectives).

The two acceptance claims pinned here:

* ``--precision f32`` (the default) is a strict no-op — the fsdp
  trajectory is BITWISE equal to an engine built without the argument,
  at k=1 and through the k=8 scanned drain;
* bf16-f32master halves param bytes per device while training to the
  same accuracy bar (same-method comparison, BASELINE.md tolerance), and
  a seeded non-finite injection under fp16-f32master triggers loss-scale
  backoff + a structured anomaly event instead of a silent NaN
  trajectory (or a fatal nan-guard abort).
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from distributed_tensorflow_tpu.data.loaders import (
    Dataset, load_dataset, synthetic_classification)
from distributed_tensorflow_tpu.engines import Trainer
from distributed_tensorflow_tpu.engines.fsdp import FSDPEngine
from distributed_tensorflow_tpu.models import create_model
from distributed_tensorflow_tpu.observability import (
    Tracer, build_run_report, health as hl)
from distributed_tensorflow_tpu.parallel import precision as pl
from distributed_tensorflow_tpu.utils.checkpoint import CheckpointManager

def _tiny_ds(n=512, split="train"):
    x, y = synthetic_classification((8, 8), 4, n, seed=3, split=split)
    return Dataset(x=x, y=y, num_classes=4, name="tiny", synthetic=True)


def _engine(mesh, precision="f32", dtype=None, lr=5e-3, **kw):
    model_kw = {} if dtype is None else {"dtype": dtype}
    return FSDPEngine(create_model("mlp", num_classes=4, hidden=32,
                                   **model_kw),
                      mesh=mesh, learning_rate=lr, precision=precision,
                      **kw)


def _run_steps(eng, ds, n_steps=4, k=1):
    state = eng.init_state(jax.random.key(0), ds.x[:8])
    batches = [eng.shard_batch(ds.x[i * 32:(i + 1) * 32],
                               ds.y[i * 32:(i + 1) * 32])
               for i in range(n_steps)]
    if k == 1:
        losses = []
        for bx, by in batches:
            state, m = eng.step(state, bx, by)
            losses.append(np.asarray(m["loss"]))
        return np.asarray(losses), state, m
    state, m = eng.many_step(state, [b[0] for b in batches],
                             [b[1] for b in batches])
    return np.asarray(m["loss"]), state, m


# ------------------------------------------------------------- policy unit

def test_make_policy_resolution():
    assert pl.make_policy(None).name == "f32"
    assert not pl.make_policy("f32").active
    b = pl.make_policy("bf16")
    assert b.param_dtype == jnp.bfloat16 and b.master_dtype is None
    m = pl.make_policy("bf16-f32master")
    assert m.master_dtype == jnp.float32 and not m.loss_scaling
    f = pl.make_policy("fp16-f32master")
    assert f.loss_scaling and f.param_dtype == jnp.float16
    assert pl.make_policy(m) is m
    with pytest.raises(ValueError, match="known:"):
        pl.make_policy("bf8")


def test_master_weights_update_is_exact_downcast():
    """The emitted f32 delta lands params EXACTLY on cast(master'): the
    apply_updates invariant the whole design rests on."""
    policy = pl.make_policy("bf16-f32master")
    tx = policy.wrap_optimizer(optax.sgd(0.1))
    params = {"w": jnp.asarray([1.0, -0.5, 0.25], jnp.bfloat16)}
    st = tx.init(params)
    grads = {"w": jnp.asarray([0.01, 0.02, -0.01], jnp.bfloat16)}
    u, st2 = tx.update(grads, st, params)
    new_params = optax.apply_updates(params, u)
    master = pl._find_master(st2)[0].master
    np.testing.assert_array_equal(
        np.asarray(new_params["w"]),
        np.asarray(master["w"].astype(jnp.bfloat16)))
    # and the master moved by the true f32 sgd step
    np.testing.assert_allclose(np.asarray(master["w"], np.float32),
                               np.asarray(params["w"], np.float32)
                               - 0.1 * np.asarray(grads["w"], np.float32),
                               rtol=1e-6)


def test_fp16_scaler_skips_and_backs_off_then_grows():
    """Wrapper-level grow/backoff: a non-finite grad skips the update
    (master unchanged, emitted delta exactly zero), halves the scale and
    counts the skip; growth_interval finite steps double it back."""
    policy = pl.PrecisionPolicy(
        name="fp16-f32master", param_dtype=jnp.float16,
        compute_dtype=jnp.float16, master_dtype=jnp.float32,
        loss_scaling=True, init_scale=8.0, growth_interval=2)
    tx = policy.wrap_optimizer(optax.sgd(0.1))
    params = {"w": jnp.asarray([1.0, 2.0], jnp.float16)}
    st = tx.init(params)
    bad = {"w": jnp.asarray([np.inf, 1.0], jnp.float16)}
    u, st = tx.update(bad, st, params)
    np.testing.assert_array_equal(np.asarray(u["w"]), 0.0)
    m = pl._find_master(st)[0]
    assert float(m.loss_scale) == 4.0 and int(m.skipped) == 1
    assert bool(m.last_skipped)
    good = {"w": jnp.asarray([8.0, 8.0], jnp.float16)}  # scaled grads
    for _ in range(2):
        u, st = tx.update(good, st, params)
        params = optax.apply_updates(params, u)
    m = pl._find_master(st)[0]
    assert float(m.loss_scale) == 8.0  # grew after growth_interval
    assert not bool(m.last_skipped)


def test_fp16_rejected_without_engine_support(mesh8):
    """Engines that do not thread the loss scale into their loss reject
    the scaling policy by name (base Engine.supports_loss_scaling) —
    silently training unscaled loss while the wrapper unscales would
    divide the effective LR by the scale.  bf16 policies (no scaling)
    stay accepted everywhere."""
    from distributed_tensorflow_tpu.engines.base import Engine

    model = create_model("mlp", num_classes=4, hidden=32)
    with pytest.raises(ValueError, match="loss scaling"):
        Engine(model, mesh=mesh8, precision="fp16-f32master")
    eng = Engine(model, mesh=mesh8, precision="bf16-f32master")
    assert eng.precision.name == "bf16-f32master"


# -------------------------------------------------- f32 bitwise no-op (fsdp)

def test_f32_policy_bitwise_noop_at_k1_and_k8(mesh8):
    """Acceptance: --precision f32 compiles the byte-identical pre-policy
    step — bitwise-equal trajectory AND final params vs an engine built
    without the argument, through both drain shapes."""
    ds = _tiny_ds()
    for k, n in ((1, 4), (8, 8)):
        base_l, base_st, _ = _run_steps(
            FSDPEngine(create_model("mlp", num_classes=4, hidden=32),
                       mesh=mesh8, learning_rate=5e-3), ds, n_steps=n, k=k)
        f32_l, f32_st, _ = _run_steps(_engine(mesh8, "f32"), ds,
                                      n_steps=n, k=k)
        np.testing.assert_array_equal(base_l, f32_l)
        for a, b in zip(jax.tree.leaves(base_st.params),
                        jax.tree.leaves(f32_st.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------------------- bf16 master policies

def test_bf16_master_layout_and_bytes(mesh8):
    """bf16-f32master: params stored bfloat16 (half the per-device param
    bytes of f32), an f32 master inside opt_state, and the params ==
    cast(master) invariant after training steps."""
    ds = _tiny_ds()
    _, st32, _ = _run_steps(_engine(mesh8, "f32"), ds)
    eng = _engine(mesh8, "bf16-f32master", dtype="bfloat16")
    _, st, _ = _run_steps(eng, ds)
    assert {p.dtype for p in jax.tree.leaves(st.params)} == \
        {jnp.dtype(jnp.bfloat16)}
    master = pl._find_master(st.opt_state)[0].master
    assert {m.dtype for m in jax.tree.leaves(master)} == \
        {jnp.dtype(jnp.float32)}
    for p, m in zip(jax.tree.leaves(st.params), jax.tree.leaves(master)):
        np.testing.assert_array_equal(np.asarray(p),
                                      np.asarray(m.astype(p.dtype)))
    eng32 = _engine(mesh8, "f32")
    assert eng.param_bytes_per_device(st) * 2 == \
        eng32.param_bytes_per_device(st32)
    # the master policy GROWS optimizer bytes (the f32 copy lives there)
    assert eng.opt_state_bytes_per_device(st) > \
        eng32.opt_state_bytes_per_device(st32)


def test_pure_bf16_halves_optimizer_state_too(mesh8):
    ds = _tiny_ds()
    eng32, engb = _engine(mesh8, "f32"), _engine(mesh8, "bf16",
                                                 dtype="bfloat16")
    _, st32, _ = _run_steps(eng32, ds)
    _, stb, _ = _run_steps(engb, ds)
    assert engb.param_bytes_per_device(stb) * 2 == \
        eng32.param_bytes_per_device(st32)
    # adam moments inherit the bf16 param dtype; the i32 count leaf keeps
    # the ratio from being exactly half
    assert engb.opt_state_bytes_per_device(stb) < \
        0.6 * eng32.opt_state_bytes_per_device(st32)


def test_bf16_drain_parity_k1_vs_k8_on_disk(mesh8, tmp_path):
    """Acceptance: the bf16 policy rides the scanned drain unchanged —
    the ON-DISK per-step metrics stream of a k=8 fit equals k=1's
    (the steady-state zero-downshift contract, policy edition)."""
    from distributed_tensorflow_tpu.utils.metrics import MetricsLogger

    streams = {}
    for k in (1, 8):
        path = tmp_path / f"m{k}.jsonl"
        eng = _engine(mesh8, "bf16-f32master", dtype="bfloat16")
        tr = Trainer(None, engine=eng, seed=0)
        ml = MetricsLogger(str(path), log_every=1)
        tr.fit(_tiny_ds(), epochs=1, batch_size=64, log_every=0,
               steps_per_call=k, metrics_logger=ml, max_steps=8)
        ml.close()
        streams[k] = [json.loads(line) for line in path.read_text()
                      .splitlines() if line.strip()]
    assert len(streams[1]) == len(streams[8]) == 8
    for a, b in zip(streams[1], streams[8]):
        # the async sink stamps host wall-clock arrival time — everything
        # the training produced must match exactly
        assert {k: v for k, v in a.items() if k != "time"} == \
            {k: v for k, v in b.items() if k != "time"}


def test_bf16_grad_reduce_composes_with_codecs_no_double_cast(mesh8):
    """bf16 param storage makes the gradient exchange 2 bytes/param with
    NO codec — and the PR 3 bf16 codec composes without double-casting
    (≤2-byte floats pass through at their own width, so wire == raw)."""
    ds = _tiny_ds(64)
    eng_plain = _engine(mesh8, "bf16-f32master", dtype="bfloat16")
    eng_codec = FSDPEngine(
        create_model("mlp", num_classes=4, hidden=32, dtype="bfloat16"),
        mesh=mesh8, learning_rate=5e-3, precision="bf16-f32master",
        grad_compression="bf16")
    st_p = eng_plain.init_state(jax.random.key(0), ds.x[:8])
    st_c = eng_codec.init_state(jax.random.key(0), ds.x[:8])
    raw = eng_plain.grad_collective_bytes_raw(st_p)
    eng32 = _engine(mesh8, "f32")
    st32 = eng32.init_state(jax.random.key(0), ds.x[:8])
    assert raw * 2 == eng32.grad_collective_bytes_raw(st32)
    # the codec adds nothing on already-bf16 grads: wire == raw
    assert eng_codec.grad_collective_bytes(st_c) == raw
    assert eng_plain.grad_collective_bytes(st_p) == raw


# ------------------------------------------------------ convergence (MNIST)

# round 20 fast-lane repair: convergence e2e (~10s) rides the slow
# lane; the bitwise/layout precision pins stay fast
@pytest.mark.slow
def test_mnist_mlp_bf16_vs_f32_same_method_accuracy(mesh8):
    """BASELINE.md same-method rule: the bf16-f32master MNIST MLP reaches
    the f32 run's accuracy within tolerance at the same step budget —
    fsdp (pure jit) so every container runs it; the sync variant below
    is shard_map-guarded."""
    train = load_dataset("mnist", split="train")
    test = load_dataset("mnist", split="test")
    accs = {}
    for name in ("f32", "bf16-f32master"):
        dtype = "bfloat16" if name != "f32" else None
        kw = {} if dtype is None else {"dtype": dtype}
        eng = FSDPEngine(
            create_model("mlp", num_classes=train.num_classes, **kw),
            mesh=mesh8, learning_rate=1e-3, precision=name)
        tr = Trainer(None, engine=eng, seed=0)
        tr.fit(train, epochs=1, batch_size=256, log_every=0, max_steps=80)
        accs[name] = tr.evaluate(test, batch_size=500)["accuracy"]
    assert accs["f32"] > 0.8            # the task trains at all
    assert abs(accs["bf16-f32master"] - accs["f32"]) < 0.05


def test_sync_mnist_mlp_bf16_policy_converges(mesh8):
    """The sync-engine rendering of the same-method claim (explicit
    shard_map collectives; the grad psum itself moves bf16)."""
    from distributed_tensorflow_tpu.engines import SyncEngine

    train = load_dataset("mnist", split="train")
    test = load_dataset("mnist", split="test")
    accs = {}
    for name in ("f32", "bf16-f32master"):
        kw = {} if name == "f32" else {"dtype": "bfloat16"}
        eng = SyncEngine(
            create_model("mlp", num_classes=train.num_classes, **kw),
            mesh=mesh8, precision=name)
        tr = Trainer(None, engine=eng, seed=0)
        tr.fit(train, epochs=1, batch_size=256, log_every=0, max_steps=80)
        accs[name] = tr.evaluate(test, batch_size=500)["accuracy"]
    assert accs["f32"] > 0.8
    assert abs(accs["bf16-f32master"] - accs["f32"]) < 0.05


# ------------------------------------------------- fp16 + health guard rail

def test_fp16_injection_backoff_and_anomaly_event(mesh8, tmp_path):
    """Acceptance: a seeded non-finite injection (HealthConfig
    inject_nan_at) under fp16-f32master triggers loss-scale backoff + a
    structured anomaly event instead of a silent NaN trajectory — AND
    instead of the nan-guard's fatal abort: the scaler handled the step,
    so training continues finite."""
    ds = _tiny_ds()
    eng = _engine(mesh8, "fp16-f32master", dtype="float16")
    eng.enable_health(hl.HealthConfig(inject_nan_at=3))
    tr = Trainer(None, engine=eng, seed=0)
    tracer = Tracer(path=str(tmp_path / "trace.jsonl"))
    fit = tr.fit(ds, epochs=1, batch_size=64, log_every=0,
                 steps_per_call=1, max_steps=6, tracer=tracer,
                 on_anomaly="warn")  # default nan_guard stays ON
    tracer.close()
    ls = fit["loss_scale"]
    assert ls["skipped_steps"] == 1 and ls["skipped_step_list"] == [3]
    assert ls["final_scale"] == pl.make_policy("fp16-f32master").init_scale \
        * 0.5  # one backoff, no growth inside 6 steps
    assert fit["precision"] == "fp16-f32master"
    recs = [json.loads(line)
            for line in (tmp_path / "trace.jsonl").read_text().splitlines()]
    events = [r for r in recs if r.get("event") == "event"]
    assert any(r["name"] == "loss_scale"
               and r.get("action") == "backoff_skip" and r.get("step") == 3
               for r in events)
    assert any(r["name"] == "anomaly" and r.get("step") == 3
               for r in events)
    # trajectory stays finite: the skipped step left params untouched
    for leaf in jax.tree.leaves(tr.state.params):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()
    assert fit["steps"] == 6  # trained to completion, no abort


def test_fp16_skip_does_not_halt_under_on_anomaly_halt(mesh8):
    """on_anomaly='halt' must not kill an fp16 run at a scaler-handled
    overflow — the skip IS the remediation; halting would defeat the
    policy's whole point (unhandled anomalies still halt)."""
    ds = _tiny_ds()
    eng = _engine(mesh8, "fp16-f32master", dtype="float16")
    eng.enable_health(hl.HealthConfig(inject_nan_at=2))
    tr = Trainer(None, engine=eng, seed=0)
    fit = tr.fit(ds, epochs=1, batch_size=64, log_every=0,
                 steps_per_call=1, max_steps=4, on_anomaly="halt")
    assert fit["steps"] == 4
    assert fit["loss_scale"]["skipped_steps"] == 1


# round 20 fast-lane repair: k-invariance is also pinned by the
# cheaper test_f32_policy_bitwise_noop_at_k1_and_k8
@pytest.mark.slow
def test_fp16_scale_metrics_ride_the_scan_k_invariantly(mesh8):
    """loss_scale / ls_skipped stack through build_many_step like any
    metric: k=8 reproduces k=1's per-step scale trajectory exactly."""
    ds = _tiny_ds()
    runs = {}
    for k in (1, 8):
        eng = _engine(mesh8, "fp16-f32master", dtype="float16")
        eng.enable_health(hl.HealthConfig(inject_nan_at=4))
        losses, _, m = _run_steps(eng, ds, n_steps=8, k=k)
        runs[k] = (losses if k == 8 else losses,
                   np.asarray(m["loss_scale"]) if k == 8 else None)
    # rebuild the k=1 scale trajectory by stepping
    eng1 = _engine(mesh8, "fp16-f32master", dtype="float16")
    eng1.enable_health(hl.HealthConfig(inject_nan_at=4))
    st = eng1.init_state(jax.random.key(0), ds.x[:8])
    scales = []
    for i in range(8):
        st, m = eng1.step(st, *eng1.shard_batch(
            ds.x[i * 32:(i + 1) * 32], ds.y[i * 32:(i + 1) * 32]))
        scales.append(float(m["loss_scale"]))
    np.testing.assert_array_equal(np.asarray(scales), runs[8][1])
    np.testing.assert_array_equal(runs[1][0], runs[8][0])


# ------------------------------------------------------ checkpoint crossing

def test_checkpoint_roundtrip_same_policy(mesh8, tmp_path):
    """A bf16-f32master checkpoint (master + scale state in the optimizer
    tree) round-trips bitwise through the on-disk format."""
    ds = _tiny_ds()
    eng = _engine(mesh8, "bf16-f32master", dtype="bfloat16")
    _, st, _ = _run_steps(eng, ds, n_steps=2)
    mgr = CheckpointManager(tmp_path / "ckpt")
    mgr.save(st, step=2)
    eng2 = _engine(mesh8, "bf16-f32master", dtype="bfloat16")
    template = eng2.init_state(jax.random.key(7), ds.x[:8])
    restored = pl.restore_into_policy(mgr, template, eng2.precision)
    for a, b in zip(jax.tree.leaves((st.params, st.opt_state)),
                    jax.tree.leaves((restored.params,
                                     restored.opt_state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_f32_checkpoint_adopts_into_bf16_policy(mesh8, tmp_path):
    """Acceptance: an f32-era checkpoint restores into a bf16 policy —
    the restored f32 params become the MASTER exactly, the stored params
    their downcast, and training continues."""
    ds = _tiny_ds()
    engf = _engine(mesh8, "f32")
    _, stf, _ = _run_steps(engf, ds, n_steps=2)
    mgr = CheckpointManager(tmp_path / "ckpt")
    mgr.save(stf, step=2)
    engb = _engine(mesh8, "bf16-f32master", dtype="bfloat16")
    template = engb.init_state(jax.random.key(7), ds.x[:8])
    restored = pl.restore_into_policy(mgr, template, engb.precision)
    master = pl._find_master(restored.opt_state)[0].master
    for a, b in zip(jax.tree.leaves(stf.params), jax.tree.leaves(master)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for p, m in zip(jax.tree.leaves(restored.params),
                    jax.tree.leaves(master)):
        np.testing.assert_array_equal(np.asarray(p),
                                      np.asarray(m.astype(p.dtype)))
    assert int(jax.device_get(restored.step)) == 2
    restored, m = engb.step(restored, *engb.shard_batch(ds.x[:32],
                                                        ds.y[:32]))
    assert np.isfinite(float(m["loss"]))


# -------------------------------------------------- fit result / run report

def test_precision_in_fit_result_and_run_report(mesh8):
    ds = _tiny_ds()
    eng = _engine(mesh8, "bf16-f32master", dtype="bfloat16")
    tr = Trainer(None, engine=eng, seed=0)
    fit = tr.fit(ds, epochs=1, batch_size=64, log_every=0, max_steps=4)
    assert fit["precision"] == "bf16-f32master"
    assert fit["param_bytes_per_device"] > 0
    assert fit["opt_state_bytes_per_device"] > fit["param_bytes_per_device"]
    assert "loss_scale" not in fit  # no dynamic scaling on bf16
    rep = build_run_report(fit)
    assert rep["precision"] == "bf16-f32master"
    assert rep["param_bytes_per_device"] == fit["param_bytes_per_device"]
    assert rep["opt_state_bytes_per_device"] == \
        fit["opt_state_bytes_per_device"]
    assert rep["loss_scale"] is None


def test_analyze_diff_gates_bytes_and_skips(tmp_path):
    """The new lower-is-better keys enter the diff table: a doubled
    param-bytes figure (or more scaler skips) reads as a regression."""
    from distributed_tensorflow_tpu.observability.analyze import (
        diff_reports, load_report)

    base = {"param_bytes_per_device": 100, "opt_state_bytes_per_device": 300,
            "loss_scale": {"skipped_steps": 0, "final_scale": 32768.0}}
    worse = {"param_bytes_per_device": 200,
             "opt_state_bytes_per_device": 300,
             "loss_scale": {"skipped_steps": 5, "final_scale": 1024.0}}
    (tmp_path / "a.json").write_text(json.dumps(base))
    (tmp_path / "b.json").write_text(json.dumps(worse))
    d = diff_reports(load_report(tmp_path / "a.json"),
                     load_report(tmp_path / "b.json"))
    bad = {r["metric"] for r in d["regressions"]}
    assert {"param_bytes_per_device", "loss_scale_skipped_steps"} <= bad


# ------------------------------------------------------------- harness/CLI

def test_harness_precision_dtype_resolution():
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, _resolve_precision)

    # non-f32 policy owns the model dtype
    cfg = _resolve_precision(ExperimentConfig(precision="bf16-f32master"))
    assert cfg.dtype == "bfloat16"
    # explicit agreeing --dtype is fine
    cfg = _resolve_precision(ExperimentConfig(precision="bf16",
                                              dtype="bf16"))
    assert cfg.dtype == "bfloat16"
    # conflicting --dtype rejected
    with pytest.raises(ValueError, match="conflicts"):
        _resolve_precision(ExperimentConfig(precision="fp16-f32master",
                                            dtype="bfloat16"))
    # f32 policy: --dtype keeps its activation-only meaning, untouched
    cfg = _resolve_precision(ExperimentConfig(dtype="bfloat16"))
    assert cfg.dtype == "bfloat16" and cfg.precision == "f32"
    # pipeline modes reject non-f32 policies by name
    with pytest.raises(ValueError, match="pipeline"):
        _resolve_precision(ExperimentConfig(precision="bf16",
                                            pipeline_parallel=2))
    # typos fail with the menu
    with pytest.raises(ValueError, match="known:"):
        _resolve_precision(ExperimentConfig(precision="int4"))


# round 20 fast-lane repair: heaviest precision e2e (~19s: two full
# harness runs + checkpoint adoption) rides the slow lane;
# test_f32_checkpoint_adopts_into_bf16_policy keeps the fast pin
@pytest.mark.slow
def test_harness_e2e_f32_checkpoint_resumes_into_bf16(tmp_path):
    """run()-level crossing: train f32 with checkpoints, resume the same
    directory under --precision bf16-f32master — the policy-aware restore
    adopts the f32 state and the resumed run continues the numbering."""
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    common = dict(engine="fsdp", model="mlp", dataset="synthetic",
                  n_devices=1, batch_size=32, log_every=0,
                  checkpoint_dir=str(tmp_path / "ckpt"))
    first = run(ExperimentConfig(**common))
    resumed = run(ExperimentConfig(**common, resume=True,
                                   precision="bf16-f32master"))
    assert resumed["precision"] == "bf16-f32master"
    assert resumed["run_report"]["param_bytes_per_device"] * 2 == \
        first["run_report"]["param_bytes_per_device"]
    assert np.isfinite(resumed["test_loss"])


def test_cli_precision_flag_parses():
    from distributed_tensorflow_tpu.cli import build_parser

    args = build_parser().parse_args(
        ["--precision", "bf16-f32master", "--serve-kv-dtype", "bfloat16"])
    assert args.precision == "bf16-f32master"
    assert args.serve_kv_dtype == "bfloat16"
    with pytest.raises(SystemExit):
        build_parser().parse_args(["--precision", "int4"])
