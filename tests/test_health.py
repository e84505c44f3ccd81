"""Numeric-health layer + offline trace analyzer (ISSUE 4).

Covers the acceptance contracts: health stats computed on device inside
the jitted scan and stacked like metrics (k=8 on-disk stream bitwise equal
to k=1), ``--health off`` leaving the program untouched (health ON must
not perturb the trajectory either — the captures are pass-through), the
seeded-NaN injection caught AT its step by ``on_anomaly='halt'`` with a
structured ``anomaly`` event naming the offending stat (where the loss-only
nan_guard catches it a log-cadence later), and the analyzer round-trip:
trace JSONL → Chrome-trace JSON with one complete event per span, plus the
run-vs-run diff exiting nonzero iff a metric regresses beyond threshold.

Engine-layer machinery runs through the pure-jit ``JitEngine`` (any
container); the shard_map engines get a health smoke wherever the engine
layer itself runs.
"""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from test_steady_state import JitEngine, _tiny_ds  # noqa: E402

from distributed_tensorflow_tpu.engines.allreduce import Trainer  # noqa: E402
from distributed_tensorflow_tpu.observability import analyze  # noqa: E402
from distributed_tensorflow_tpu.observability import Tracer, build_run_report  # noqa: E402
from distributed_tensorflow_tpu.observability import health as hl  # noqa: E402
from distributed_tensorflow_tpu.utils.failure import (  # noqa: E402
    AnomalyDetected, TrainingDiverged)
from distributed_tensorflow_tpu.utils.metrics import MetricsLogger  # noqa: E402

# ------------------------------------------------------------ capture units

def test_global_norm_and_nonfinite_count():
    tree = {"a": jnp.full((2, 3), 2.0), "b": jnp.ones((4,))}
    assert float(hl.global_norm(tree)) == pytest.approx(math.sqrt(4 * 6 + 4))
    assert float(hl.nonfinite_leaf_count(tree)) == 0
    bad = {"a": jnp.array([1.0, jnp.nan]), "b": jnp.array([jnp.inf]),
           "c": jnp.array([1, 2], jnp.int32)}  # int leaves can't be nonfinite
    assert float(hl.nonfinite_leaf_count(bad)) == 2
    assert float(hl.global_norm({})) == 0.0


def test_wrapped_optimizer_captures_stats():
    """The optax capture chain records grad/param/update norms and the
    ratio WITHOUT changing the updates (pass-through)."""
    params = {"w": jnp.ones((4, 3)), "b": jnp.zeros((3,))}
    grads = {"w": jnp.full((4, 3), 2.0), "b": jnp.ones((3,))}
    plain = optax.sgd(0.1)
    wrapped = hl.wrap_optimizer(optax.sgd(0.1), hl.HealthConfig())
    u0, _ = plain.update(grads, plain.init(params), params)
    u1, st = wrapped.update(grads, wrapped.init(params), params)
    for a, b in zip(jax.tree.leaves(u0), jax.tree.leaves(u1)):
        np.testing.assert_array_equal(a, b)  # captures observe, not perturb
    stats = {k: float(v) for k, v in hl.from_opt_state(st).items()}
    gn = math.sqrt(4 * 4 * 3 + 3)
    pn = math.sqrt(12)
    assert stats["grad_norm"] == pytest.approx(gn, rel=1e-6)
    assert stats["param_norm"] == pytest.approx(pn, rel=1e-6)
    assert stats["update_norm"] == pytest.approx(0.1 * gn, rel=1e-6)
    assert stats["update_ratio"] == pytest.approx(0.1 * gn / pn, rel=1e-6)
    assert stats["nonfinite_count"] == 0


def test_injection_hook_poisons_exactly_one_step():
    params = {"w": jnp.ones((2,))}
    tx = hl.wrap_optimizer(optax.sgd(0.1),
                           hl.HealthConfig(inject_nan_at=2))
    st = tx.init(params)
    grads = {"w": jnp.full((2,), 3.0)}
    _, st = tx.update(grads, st, params)
    s1 = hl.from_opt_state(st)
    assert math.isfinite(float(s1["grad_norm"]))
    _, st = tx.update(grads, st, params)
    s2 = hl.from_opt_state(st)
    assert not math.isfinite(float(s2["grad_norm"]))  # the poisoned step
    assert float(s2["nonfinite_count"]) > 0


def test_from_opt_state_without_captures_is_loud():
    tx = optax.sgd(0.1)
    with pytest.raises(ValueError, match="enable_health"):
        hl.from_opt_state(tx.init({"w": jnp.ones((2,))}))


def test_detect_anomalies_policy():
    cfg = hl.HealthConfig()
    assert hl.detect_anomalies(
        {"loss": 1.0, "grad_norm": 2.0, "update_ratio": 0.1,
         "loss_spike": 1.1, "nonfinite_count": 0.0}, cfg) == []
    stats = [a["stat"] for a in hl.detect_anomalies(
        {"loss": float("nan"), "nonfinite_count": 3.0,
         "update_ratio": 2.0, "loss_spike": 99.0}, cfg)]
    assert stats == ["nonfinite_count", "loss", "update_ratio", "loss_spike"]
    # threshold checks only fire on finite values (NaN comparisons are
    # silently False); the non-finite check is what reports them
    assert [a["stat"] for a in hl.detect_anomalies(
        {"grad_norm": float("inf")}, cfg)] == ["grad_norm"]
    ceil = hl.HealthConfig(max_grad_norm=10.0)
    assert [a["stat"] for a in hl.detect_anomalies(
        {"grad_norm": 11.0}, ceil)] == ["grad_norm"]


# --------------------------------------------------- engine hook (pure jit)

def test_engine_step_metrics_carry_health_and_real_grad_norm():
    """The base hook merges the health stats into the step metrics, and
    grad_norm is the TRUE global gradient norm (cross-checked against a
    hand computation of the same loss)."""
    from distributed_tensorflow_tpu.engines.base import cross_entropy

    eng = JitEngine()
    eng.enable_health()
    ds = _tiny_ds()
    state = eng.init_state(jax.random.key(0), ds.x[:8])
    params0 = jax.device_get(state.params)
    xs, ys = eng.shard_batch(ds.x[:16], ds.y[:16])
    state, m = eng.step(state, xs, ys)
    assert set(hl.HEALTH_KEYS) <= set(m.keys())
    assert float(m["loss_spike"]) == 1.0  # first step scores 1 by definition

    def loss_fn(p):
        logits = eng.model.apply({"params": p}, jnp.asarray(ds.x[:16]))
        return cross_entropy(logits, jnp.asarray(ds.y[:16])).mean()

    grads = jax.grad(loss_fn)(params0)
    assert float(m["grad_norm"]) == pytest.approx(
        float(hl.global_norm(grads)), rel=1e-5)
    # SGD: ‖Δp‖ = lr·‖g‖ (JitEngine uses optax.sgd(0.1))
    assert float(m["update_norm"]) == pytest.approx(
        0.1 * float(m["grad_norm"]), rel=1e-5)
    state, m2 = eng.step(state, xs, ys)
    assert math.isfinite(float(m2["loss_spike"]))


def test_enable_health_after_step_build_is_rejected():
    eng = JitEngine()
    ds = _tiny_ds()
    state = eng.init_state(jax.random.key(0), ds.x[:8])
    xs, ys = eng.shard_batch(ds.x[:16], ds.y[:16])
    eng.step(state, xs, ys)
    with pytest.raises(RuntimeError, match="before"):
        eng.enable_health()


def test_enable_health_after_init_state_fails_actionably():
    """The replicated engines' init_state sets none of the fields the
    enable-time guard can see — a state initialized pre-enable must fail
    at the first step with the actionable message, not an opaque optax
    tree mismatch inside the jit."""
    eng = JitEngine()
    ds = _tiny_ds()
    state = eng.init_state(jax.random.key(0), ds.x[:8])  # pre-enable
    eng.enable_health()
    xs, ys = eng.shard_batch(ds.x[:16], ds.y[:16])
    with pytest.raises(ValueError, match="enable_health"):
        eng.step(state, xs, ys)
    with pytest.raises(ValueError, match="enable_health"):
        eng.many_step(state, [xs], [ys])


def _run_fit(k, health=True, inject=None, on_anomaly="warn", path=None,
             tracer=None, **fit_kw):
    eng = JitEngine()
    if health:
        eng.enable_health(hl.HealthConfig(inject_nan_at=inject))
    tr = Trainer(None, engine=eng, seed=0)
    ml = MetricsLogger(path, log_every=1)
    r = tr.fit(_tiny_ds(), epochs=2, batch_size=16, log_every=0,
               steps_per_call=k, metrics_logger=ml, max_steps=13,
               on_anomaly=on_anomaly, tracer=tracer, **fit_kw)
    ml.close()
    return r, ml.records, jax.device_get(tr.state.params)


def test_health_on_does_not_perturb_trajectory():
    """Health ON must observe, not perturb: identical per-step loss and
    bitwise-identical final params vs health OFF on the same seed (the
    capture transforms are pass-through; `--health off` trivially keeps
    the pre-health program — nothing is wrapped)."""
    r_on, recs_on, p_on = _run_fit(8, health=True)
    r_off, recs_off, p_off = _run_fit(8, health=False)
    assert [m["loss"] for m in recs_on] == [m["loss"] for m in recs_off]
    for a, b in zip(jax.tree.leaves(p_on), jax.tree.leaves(p_off)):
        np.testing.assert_array_equal(a, b)
    assert "health" in r_on and "health" not in r_off
    assert r_on["health"]["anomalies"] == 0
    assert r_on["health"]["first_anomaly_step"] is None
    assert r_on["health"]["max_update_ratio"] > 0


def test_health_stream_parity_k8_vs_k1_on_disk(tmp_path):
    """Acceptance: with health ON, the k=8 on-disk health stream equals
    k=1's — every per-step health stat, bitwise, same discipline as the
    PR 2 metrics parity."""
    r1, _, p1 = _run_fit(1, path=tmp_path / "k1.jsonl")
    r8, _, p8 = _run_fit(8, path=tmp_path / "k8.jsonl")
    assert r8["steps_per_call"] == 8  # health never downshifts
    load = lambda p: [json.loads(l)  # noqa: E731
                      for l in p.read_text().splitlines()]
    recs1, recs8 = load(tmp_path / "k1.jsonl"), load(tmp_path / "k8.jsonl")
    assert len(recs1) == len(recs8) == 13
    keys = ("step", "loss", "accuracy") + hl.HEALTH_KEYS
    traj = lambda recs: [tuple(m[kk] for kk in keys)  # noqa: E731
                         for m in recs]
    assert traj(recs1) == traj(recs8)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p8)):
        np.testing.assert_array_equal(a, b)


def test_anomaly_halt_catches_injection_at_its_step(tmp_path):
    """Acceptance: grads scaled by inf at step 5 → on_anomaly='halt'
    raises AT step 5 with a structured `anomaly` trace event naming the
    offending stat, and the step's metrics record reached the sink first."""
    trace = tmp_path / "t.jsonl"
    with Tracer(path=trace, run_id="r-halt") as tracer:
        with pytest.raises(AnomalyDetected, match="step 5"):
            _run_fit(8, inject=5, on_anomaly="halt",
                     path=tmp_path / "m.jsonl", tracer=tracer)
    events = [json.loads(l) for l in trace.read_text().splitlines()]
    anomalies = [e for e in events if e.get("name") == "anomaly"]
    assert anomalies and anomalies[0]["step"] == 5
    assert anomalies[0]["stat"] in hl.HEALTH_KEYS + ("loss",)
    assert anomalies[0]["policy"] == "halt"
    recs = [json.loads(l)
            for l in (tmp_path / "m.jsonl").read_text().splitlines()]
    assert recs[-1]["step"] == 5  # the diverging step's record is on disk
    assert not math.isfinite(recs[-1]["grad_norm"])


def test_old_nan_guard_catches_a_cadence_later():
    """The contrast the tentpole exists for: the same blow-up under the
    loss-only nan_guard (health off) is invisible until a logging cadence
    materializes the loss — here the END of the 13-step run, 8 steps after
    the fault; the health policy (previous test) halts at step 5."""
    class BlowsUpAtStep5(JitEngine):
        """Grads scale by inf once state.step reaches 4 (0-based), i.e.
        the 5th optimizer update — a health-off rendering of the
        inject_nan_at hook."""

        def _build_step(self):
            import optax as _optax

            tx, apply_fn = self.tx, self.model.apply

            def train_step(state, x, y):
                from distributed_tensorflow_tpu.engines.base import (
                    cross_entropy)

                def loss_fn(p):
                    logits = apply_fn({"params": p}, x)
                    loss = cross_entropy(logits, y).mean()
                    return loss, (logits.argmax(-1) == y).mean()

                (loss, acc), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(state.params)
                scale = jnp.where(state.step == 4, jnp.inf, 1.0)
                grads = jax.tree.map(lambda g: g * scale, grads)
                updates, opt_state = tx.update(grads, state.opt_state,
                                               state.params)
                params = _optax.apply_updates(state.params, updates)
                return state.replace(step=state.step + 1, params=params,
                                     opt_state=opt_state), \
                    {"loss": loss, "accuracy": acc}

            return jax.jit(train_step, donate_argnums=0)

    eng = BlowsUpAtStep5()
    tr = Trainer(None, engine=eng, seed=0)
    # log_every=0 and no metrics logger: the only nan_guard check left is
    # the final-metrics one — the divergence surfaces at step 13, not 5
    with pytest.raises(TrainingDiverged, match="step 13"):
        tr.fit(_tiny_ds(), epochs=2, batch_size=16, log_every=0,
               steps_per_call=8, max_steps=13)


def test_warn_keeps_divergence_fatal_under_nan_guard_default():
    """Adding --health must never silently downgrade a NaN'd run from
    abort to train-to-completion: under on_anomaly='warn' with the
    nan_guard default, a 'nonfinite' anomaly is still fatal — and now
    step-exact, where the legacy guard waited for a log cadence."""
    with pytest.raises(AnomalyDetected, match="step 5"):
        _run_fit(8, inject=5, on_anomaly="warn")  # nan_guard defaults True


def test_anomaly_warn_completes_and_reports(tmp_path):
    """Observe-only mode (warn + nan_guard off): the run completes and
    the health summary records every anomalous step."""
    trace = tmp_path / "t.jsonl"
    with Tracer(path=trace) as tracer:
        r, recs, _ = _run_fit(8, inject=5, on_anomaly="warn",
                              nan_guard=False, tracer=tracer)
    h = r["health"]
    assert r["steps"] == 13  # observe-only records, never stops
    assert h["first_anomaly_step"] == 5
    assert h["anomaly_steps"][0] == 5 and 13 in h["anomaly_steps"]
    assert h["anomalies"] >= len(h["anomaly_steps"])
    events = [json.loads(l) for l in trace.read_text().splitlines()]
    assert any(e.get("name") == "anomaly" and e.get("step") == 5
               for e in events)


def test_fit_rejects_unknown_anomaly_policy():
    eng = JitEngine()
    eng.enable_health()
    tr = Trainer(None, engine=eng, seed=0)
    with pytest.raises(ValueError, match="on_anomaly"):
        tr.fit(_tiny_ds(), epochs=1, batch_size=16, on_anomaly="explode")


def test_run_report_carries_health_section():
    r, _, _ = _run_fit(8)
    report = build_run_report(r)
    assert report["health"] == r["health"]
    assert build_run_report({"elapsed": 1.0, "steps": 1})["health"] is None


# ---------------------------------------------- shard_map engine smoke

def test_sync_engine_health_smoke(mesh8):
    """The shared base hook covers the real engine layer: one SyncEngine
    step on the 8-device mesh carries finite health stats."""
    from distributed_tensorflow_tpu.data.loaders import load_dataset
    from distributed_tensorflow_tpu.engines import SyncEngine
    from distributed_tensorflow_tpu.models import create_model

    ds = load_dataset("mnist", split="train")
    eng = SyncEngine(create_model("mlp", num_classes=ds.num_classes),
                     mesh=mesh8)
    eng.enable_health()
    state = eng.init_state(jax.random.key(0), ds.x[:8])
    xs, ys = eng.shard_batch(ds.x[:64], ds.y[:64])
    state, m = eng.step(state, xs, ys)
    floats = {k: float(v) for k, v in m.items()}
    assert set(hl.HEALTH_KEYS) <= set(floats)
    assert floats["nonfinite_count"] == 0
    assert floats["grad_norm"] > 0 and floats["update_ratio"] > 0
    assert hl.detect_anomalies(floats, eng.health) == []


# ------------------------------------------------------- analyzer (offline)

def _instrumented_run(tmp_path):
    trace = tmp_path / "trace.jsonl"
    metrics = tmp_path / "metrics.jsonl"
    with Tracer(path=trace, run_id="r-an") as tracer:
        r, _, _ = _run_fit(8, path=metrics, tracer=tracer)
        report = build_run_report(r, tracer=tracer)
    return trace, metrics, r, report


def test_chrome_export_round_trip(tmp_path):
    """Acceptance: a real run's trace JSONL exports to Chrome-trace JSON
    that json.loads with one complete ('X') event per span record."""
    trace, _, _, _ = _instrumented_run(tmp_path)
    out = tmp_path / "chrome.json"
    assert analyze.main(["export", str(trace), "-o", str(out)]) == 0
    ct = json.load(open(out))
    assert "traceEvents" in ct and ct["traceEvents"]
    recs = analyze.read_jsonl(trace)
    n_spans = sum(1 for r in recs if r.get("event") == "span")
    xs = [e for e in ct["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == n_spans > 0
    for e in xs:
        assert {"name", "ts", "dur", "pid", "tid"} <= set(e)
    # instants + counters made it too, and the timeline is ts-ordered
    assert any(e["ph"] == "C" for e in ct["traceEvents"])
    ts = [e["ts"] for e in ct["traceEvents"] if e["ph"] != "M"]
    assert ts == sorted(ts)


def test_trace_summary_spans_and_stalls(tmp_path):
    trace, _, _, _ = _instrumented_run(tmp_path)
    summ = analyze.trace_summary(analyze.read_jsonl(trace))
    assert summ["spans"]["compile"]["count"] == 1
    assert summ["spans"]["materialize"]["count"] >= 1
    assert summ["wall_s"] > 0
    assert summ["stalls"]["anomaly_events"] == 0
    assert summ["stalls"]["gauges"] >= 1


def test_health_timeline_from_metrics(tmp_path):
    _, metrics, r, _ = _instrumented_run(tmp_path)
    ht = analyze.health_timeline(analyze.read_jsonl(metrics))
    assert ht["steps"] == 13
    assert ht["first_anomaly_step"] is None
    assert ht["max_update_ratio"] == pytest.approx(
        r["health"]["max_update_ratio"])
    # and with a poisoned run the first anomaly step is recoverable
    bad = tmp_path / "bad.jsonl"
    _run_fit(8, inject=5, on_anomaly="warn", nan_guard=False, path=bad)
    ht2 = analyze.health_timeline(analyze.read_jsonl(bad))
    assert ht2["first_anomaly_step"] == 5
    assert ht2["nonfinite_steps"] >= 1


def test_diff_exits_nonzero_iff_regression(tmp_path):
    """Acceptance: self-diff reports zero regressions (exit 0); a metric
    past the threshold exits nonzero; within-threshold drift does not."""
    _, _, _, report = _instrumented_run(tmp_path)
    a = tmp_path / "a.json"
    a.write_text(json.dumps(report))
    assert analyze.main(["diff", str(a), str(a)]) == 0
    worse = dict(report)
    worse["step_time_p50_s"] = (report["step_time_p50_s"] or 0.01) * 2
    b = tmp_path / "b.json"
    b.write_text(json.dumps(worse))
    assert analyze.main(["diff", str(a), str(b)]) == 1
    assert analyze.main(["diff", str(b), str(a)]) == 0  # improvement
    drift = dict(report)
    drift["step_time_p50_s"] = (report["step_time_p50_s"] or 0.01) * 1.05
    c = tmp_path / "c.json"
    c.write_text(json.dumps(drift))
    assert analyze.main(["diff", str(a), str(c), "--threshold", "0.1"]) == 0
    assert analyze.main(["diff", str(a), str(c), "--threshold", "0.01"]) == 1


def test_diff_bench_lines_and_higher_better(tmp_path):
    base = {"metric": "mnist", "value": 100.0, "step_time_p50": 0.01,
            "prefetch_starvation": 0}
    slow = {"metric": "mnist", "value": 70.0, "step_time_p50": 0.01,
            "prefetch_starvation": 0}
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(base))
    b.write_text(json.dumps(slow))
    res = analyze.diff_reports(analyze.load_report(a),
                               analyze.load_report(b))
    assert [r["metric"] for r in res["regressions"]] == ["value"]
    assert analyze.main(["diff", str(a), str(b)]) == 1


def test_diff_value_direction_and_metric_mismatch(tmp_path):
    """A time-valued bench metric's headline `value` is lower-is-better
    (a 2x attention-step-ms increase is a regression, not an
    improvement), and diffing two DIFFERENT bench metrics compares
    nothing and exits 2 — never a silent 'no regression'."""
    fast = {"metric": "attention_fwd_bwd_step_ms", "value": 10.0}
    slow = {"metric": "attention_fwd_bwd_step_ms", "value": 20.0}
    res = analyze.diff_reports(fast, slow)
    assert [r["metric"] for r in res["regressions"]] == ["value"]
    assert analyze.diff_reports(slow, fast)["regressions"] == []
    # rate-valued metrics keep higher-is-better
    res2 = analyze.diff_reports({"metric": "x_examples_per_sec",
                                 "unit": "examples/sec", "value": 100.0},
                                {"metric": "x_examples_per_sec",
                                 "unit": "examples/sec", "value": 50.0})
    assert [r["metric"] for r in res2["regressions"]] == ["value"]
    mism = analyze.diff_reports({"metric": "a", "value": 1.0},
                                {"metric": "b", "value": 99.0})
    assert mism["compared"] == 0 and mism["metric_mismatch"]
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps({"metric": "a", "value": 1.0}))
    b.write_text(json.dumps({"metric": "b", "value": 99.0}))
    assert analyze.main(["diff", str(a), str(b)]) == 2


def test_health_timeline_counts_threshold_crossings():
    """Threshold anomalies (finite values past the ceilings) must not
    vanish from the offline timeline — first_anomaly_step covers them,
    not only non-finites."""
    recs = [{"step": 1, "update_ratio": 0.1, "loss_spike": 1.0,
             "nonfinite_count": 0},
            {"step": 2, "update_ratio": 3.0, "loss_spike": 1.0,
             "nonfinite_count": 0},
            {"step": 3, "update_ratio": 0.1, "loss_spike": 50.0,
             "nonfinite_count": 0}]
    ht = analyze.health_timeline(recs)
    assert ht["first_anomaly_step"] == 2
    assert ht["threshold_steps"] == 2
    assert ht["nonfinite_steps"] == 0
    # custom ceilings mirror a customized HealthConfig
    loose = analyze.health_timeline(recs, max_update_ratio=5.0,
                                    loss_spike_factor=100.0)
    assert loose["first_anomaly_step"] is None


def test_chrome_export_keeps_event_value_arg():
    recs = [{"event": "event", "name": "anomaly", "t": 1.0, "step": 5,
             "stat": "update_ratio", "value": 12.3, "limit": 1.0,
             "process": 0, "pid": 42, "run": "r", "host": "h"},
            {"event": "gauge", "name": "prefetch_depth", "t": 2.0,
             "value": 2, "process": 0, "pid": 42}]
    ct = analyze.to_chrome_trace(recs)
    instant = next(e for e in ct["traceEvents"] if e["ph"] == "i")
    assert instant["args"]["value"] == 12.3  # the offending stat value
    counter = next(e for e in ct["traceEvents"] if e["ph"] == "C")
    assert counter["args"] == {"prefetch_depth": 2}


def test_chrome_export_of_anomalous_run_is_strict_json():
    """The traces most worth opening carry inf/NaN anomaly values —
    json.dumps would render bare Infinity tokens that Perfetto's strict
    JSON.parse rejects, so they must export as strings."""
    recs = [{"event": "event", "name": "anomaly", "t": 1.0, "step": 5,
             "stat": "grad_norm", "value": float("inf"), "limit": None,
             "process": 0, "pid": 1},
            {"event": "span", "name": "chunk_dispatch", "t": 2.0,
             "dur_s": 0.1, "bad": float("nan"), "process": 0, "pid": 1}]
    text = json.dumps(analyze.to_chrome_trace(recs))
    parsed = json.loads(text, parse_constant=lambda s: pytest.fail(
        f"non-strict JSON token {s!r} in Chrome export"))
    instant = next(e for e in parsed["traceEvents"] if e["ph"] == "i")
    assert instant["args"]["value"] == "inf"


def test_diff_nothing_compared_exits_2(tmp_path):
    """Diffing artifacts that share no known metric keys (e.g. two trace
    files by mistake) must not exit 0 — nothing was checked."""
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps({"event": "span", "name": "eval", "t": 1.0}))
    b.write_text(json.dumps({"event": "span", "name": "eval", "t": 2.0}))
    assert analyze.main(["diff", str(a), str(b)]) == 2


def test_health_timeline_ignores_trace_records():
    """Trace spans carry a 'step' attr (checkpoint/eval) but are not
    health steps — only metric records (no 'event' envelope) count."""
    recs = [{"step": 1, "loss": 1.0, "nonfinite_count": 0},
            {"event": "span", "name": "checkpoint", "t": 1.0, "step": 400},
            {"event": "gauge", "name": "prefetch_depth", "t": 2.0,
             "value": 2}]
    assert analyze.health_timeline(recs)["steps"] == 1


def test_diff_summary_with_nested_run_report(tmp_path):
    summary = {"engine": "sync", "examples_per_sec": 1000.0,
               "run_report": {"step_time_p50_s": 0.01,
                              "health": {"anomalies": 0}}}
    worse = {"engine": "sync", "examples_per_sec": 1000.0,
             "run_report": {"step_time_p50_s": 0.05,
                            "health": {"anomalies": 3}}}
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    a.write_text(json.dumps(summary))
    b.write_text(json.dumps(worse))
    res = analyze.diff_reports(analyze.load_report(a),
                               analyze.load_report(b))
    assert {r["metric"] for r in res["regressions"]} == {
        "step_time_p50_s", "health_anomalies"}


def test_load_report_takes_last_jsonl_object(tmp_path):
    p = tmp_path / "results.jsonl"
    p.write_text('{"event": "start"}\n{"value": 5.0, "metric": "m"}\n')
    assert analyze.load_report(p)["value"] == 5.0
    torn = tmp_path / "torn.jsonl"
    torn.write_text("not json at all\n")
    with pytest.raises(ValueError, match="no parsable"):
        analyze.load_report(torn)


def test_read_jsonl_rejects_torn_lines(tmp_path):
    p = tmp_path / "t.jsonl"
    p.write_text('{"a": 1}\n{"b": ')
    with pytest.raises(ValueError, match="unparsable"):
        analyze.read_jsonl(p)
