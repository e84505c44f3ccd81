"""Observability layer: async JSONL sink, trace spans, run report.

Covers the ISSUE 2 contracts: crash-durable metric sinks (whole JSON
lines even after SIGKILL, schema_version on every record), the bounded
queue's drop counter, the span timeline's envelope (monotonic clock,
run/host/process ids), the end-of-run report's fields, and the harness
wiring that emits the report through the CLI with telemetry enabled at
``steps_per_call > 1``.
"""

import contextlib
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from distributed_tensorflow_tpu.observability import (
    NULL_TRACER, SCHEMA_VERSION, AsyncJsonlSink, Tracer, build_run_report)
from distributed_tensorflow_tpu.utils.metrics import MetricsLogger, StepTimer

# ------------------------------------------------------------ AsyncJsonlSink


def test_sink_writes_whole_schema_stamped_lines(tmp_path):
    path = tmp_path / "sink.jsonl"
    with AsyncJsonlSink(path) as sink:
        for i in range(50):
            assert sink.write({"step": i, "loss": 0.1 * i})
        sink.flush()
    lines = [json.loads(line) for line in path.read_text().splitlines()]
    assert [rec["step"] for rec in lines] == list(range(50))  # order kept
    assert all(rec["schema_version"] == SCHEMA_VERSION for rec in lines)
    assert sink.stats() == {"written": 50, "dropped": 0}


def test_sink_bounded_queue_drops_and_counts(tmp_path):
    # start=False keeps the writer thread off so the queue fills
    # deterministically; close() then drains synchronously
    sink = AsyncJsonlSink(tmp_path / "s.jsonl", maxsize=4, start=False)
    results = [sink.write({"i": i}) for i in range(10)]
    assert results == [True] * 4 + [False] * 6
    assert sink.dropped == 6
    sink.close()
    lines = (tmp_path / "s.jsonl").read_text().splitlines()
    assert len(lines) == 4  # the accepted records survive, in order
    assert [json.loads(line)["i"] for line in lines] == [0, 1, 2, 3]
    assert sink.write({"i": 99}) is False  # closed sink drops, not crashes


def test_sink_close_is_idempotent(tmp_path):
    sink = AsyncJsonlSink(tmp_path / "s.jsonl")
    sink.write({"a": 1})
    sink.close()
    sink.close()
    assert json.loads((tmp_path / "s.jsonl").read_text())["a"] == 1


_KILLED_WRITER = """
import sys, time
from distributed_tensorflow_tpu.utils.metrics import MetricsLogger
ml = MetricsLogger(sys.argv[1], log_every=1)
step = 0
while True:  # parent SIGKILLs us mid-stream
    step += 1
    ml.log(step, loss=1.0 / step, accuracy=0.5)
    if step == 5:
        print("GOING", flush=True)  # parent waits for real records first
"""


def test_killed_run_leaves_only_whole_json_lines(tmp_path):
    """Satellite: crash durability — a SIGKILLed run's metrics file holds
    only complete JSON lines (each with schema_version), never a torn
    record."""
    path = tmp_path / "metrics.jsonl"
    proc = subprocess.Popen(
        [sys.executable, "-c", _KILLED_WRITER, str(path)],
        stdout=subprocess.PIPE, text=True,
        cwd=str(Path(__file__).resolve().parents[1]))
    try:
        assert proc.stdout.readline().strip() == "GOING"
        # let the writer thread put real bytes on disk mid-write-storm
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if path.exists() and path.stat().st_size > 2000:
                break
            time.sleep(0.02)
        os.kill(proc.pid, signal.SIGKILL)
    finally:
        proc.wait(timeout=10)
    data = path.read_text()
    assert len(data) > 0
    recs = [json.loads(line) for line in data.splitlines()]  # ALL parse
    assert len(recs) >= 5
    assert all(rec["schema_version"] == SCHEMA_VERSION for rec in recs)
    assert [rec["step"] for rec in recs] == list(range(1, len(recs) + 1))


# ------------------------------------------------------------------- tracer


def test_tracer_span_timeline_envelope(tmp_path):
    path = tmp_path / "trace.jsonl"
    with Tracer(path=path, run_id="r-test", process_index=3) as tracer:
        with tracer.span("compile", steps=8):
            time.sleep(0.01)
        with tracer.span("chunk_dispatch", steps=8):
            pass
        tracer.gauge("prefetch_depth", 2, starvation=0)
        tracer.counter("drops", 4)
        tracer.event("collective_profile", grad_allreduce_bytes=123)
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    assert all(rec["run"] == "r-test" and rec["process"] == 3
               and rec["host"] and rec["pid"] for rec in recs)
    spans = [rec for rec in recs if rec["event"] == "span"]
    assert [s["name"] for s in spans] == ["compile", "chunk_dispatch"]
    assert spans[0]["dur_s"] >= 0.01 and spans[0]["steps"] == 8
    # monotonic clock: the timeline orders within the run
    ts = [rec["t"] for rec in recs]
    assert ts == sorted(ts)
    gauge = next(rec for rec in recs if rec["event"] == "gauge")
    assert gauge["name"] == "prefetch_depth" and gauge["value"] == 2
    counter = next(rec for rec in recs if rec["event"] == "counter")
    assert counter["total"] == 4


def test_tracer_aggregates_without_file_sink():
    tracer = Tracer(path=None)
    for _ in range(3):
        with tracer.span("materialize"):
            pass
    summary = tracer.span_summary()
    assert summary["materialize"]["count"] == 3
    assert summary["materialize"]["total_s"] >= \
        summary["materialize"]["max_s"] > 0
    assert tracer.overhead_s >= 0
    tracer.close()


def test_null_tracer_is_inert():
    with NULL_TRACER.span("anything", x=1) as attrs:
        attrs["late"] = 2           # the dict is there to write to
    NULL_TRACER.gauge("g", 1)
    NULL_TRACER.event("e")
    NULL_TRACER.counter("c")
    handle = NULL_TRACER.begin("request", rid=1)
    handle.attrs.update(tokens=3)
    NULL_TRACER.end(handle)
    assert NULL_TRACER.records() == [] == NULL_TRACER.records(root="x")
    assert NULL_TRACER.span_summary() == {}
    assert not NULL_TRACER.enabled


class _FakeAnnotation:
    """Stands where ``jax.profiler.TraceAnnotation`` is: keeps what the
    tracer mirrors into a profile."""

    def __init__(self, seen):
        self.seen = seen

    def __call__(self, name, **kwargs):
        self.seen.append((name, kwargs))
        return contextlib.nullcontext()


def test_span_records_carry_identity_and_nesting(tmp_path):
    """One record per finished span: both ends on one clock, an id, the
    enclosing lexical span as parent, the rid, the attributes as they were
    at exit.  A detached span is recorded like any other, is nobody's
    parent and is not mirrored into the profile."""
    tracer = Tracer(path=tmp_path / "t.jsonl")
    seen = []
    tracer._annotation = _FakeAnnotation(seen)
    t_before = time.perf_counter()
    with tracer.span("serve_run", offered=2):
        req = tracer.begin("request", rid=7, prompt_len=5)
        with tracer.span("prefill", rid=7, prompt_len=5) as attrs:
            attrs["padded_len"] = 8
        with tracer.span("decode_step", active=1, slots=4):
            with tracer.span("inner"):
                time.sleep(0.002)
        req.attrs.update(tokens=3)
        tracer.end(req)
    t_after = time.perf_counter()
    tracer.close()
    recs = {r["name"]: r for r in tracer.records()}
    assert list(recs) == ["prefill", "inner", "decode_step", "request",
                          "serve_run"]                  # in finishing order
    assert set(recs["prefill"]) == {"name", "start", "end", "id", "parent",
                                    "rid", "attrs"}
    ids = [r["id"] for r in recs.values()]
    assert len(set(ids)) == len(ids)
    root = recs["serve_run"]
    assert root["parent"] is None and root["rid"] is None
    assert t_before <= root["start"] <= root["end"] <= t_after
    for name in ("prefill", "decode_step", "request"):
        assert recs[name]["parent"] == root["id"]
        assert root["start"] <= recs[name]["start"] <= recs[name]["end"] \
            <= root["end"]
    # opened while `request` was open, yet the detached span is not its
    # parent: the lexical root is
    assert recs["inner"]["parent"] == recs["decode_step"]["id"]
    assert recs["inner"]["end"] - recs["inner"]["start"] >= 0.002
    assert recs["prefill"]["rid"] == 7 == recs["request"]["rid"]
    assert recs["prefill"]["attrs"]["padded_len"] == 8
    assert recs["request"]["attrs"] == {"rid": 7, "prompt_len": 5,
                                        "tokens": 3}
    # the profile gets the lexical spans under their names, with the id
    assert seen == [(name, {"id": recs[name]["id"]}) for name in
                    ("serve_run", "prefill", "decode_step", "inner")]
    # the file carries the same identity
    lines = [json.loads(line) for line in
             (tmp_path / "t.jsonl").read_text().splitlines()]
    spans = {rec["name"]: rec for rec in lines if rec["event"] == "span"}
    assert spans["inner"]["parent"] == recs["decode_step"]["id"]
    assert spans["prefill"]["t"] == recs["prefill"]["start"]
    assert spans["prefill"]["dur_s"] == pytest.approx(
        recs["prefill"]["end"] - recs["prefill"]["start"])
    assert tracer.stats()["records"] == 5
    assert tracer.stats()["records_dropped"] == 0


def test_record_ring_is_bounded_and_counts_what_it_drops(monkeypatch):
    from distributed_tensorflow_tpu.observability import trace

    monkeypatch.setattr(trace, "RING_CAPACITY", 8)
    tracer = Tracer()
    for i in range(11):
        with tracer.span("step", i=i):
            pass
    recs = tracer.records()
    assert [r["attrs"]["i"] for r in recs] == list(range(3, 11))
    assert tracer.dropped == 3
    assert tracer.span_summary()["step"]["count"] == 11   # aggregates stay


def test_records_of_a_root_are_the_last_roots_interval_only():
    tracer = Tracer()
    assert tracer.records(root="serve_run") == []
    with tracer.span("warmup"):
        pass
    for window in range(2):
        with tracer.span("serve_run", window=window):
            with tracer.span("decode_step", window=window):
                pass
            open_across = tracer.begin("request", window=window)
        tracer.end(open_across)      # ends after the root: not inside it
    got = tracer.records(root="serve_run")
    assert [r["name"] for r in got] == ["serve_run", "decode_step"]
    assert all(r["attrs"]["window"] == 1 for r in got)
    assert tracer.records(root="no_such_span") == []
    assert len(tracer.records()) == 7


@pytest.mark.parametrize("threads", [1, 4])
def test_records_under_a_shared_tracer(threads):
    """The fleet shares ONE tracer across its replica threads: no record
    and no count is lost, ids stay unique, and a span's parent is the span
    open on ITS thread."""
    import sys
    import threading

    tracer = Tracer()
    rounds = 300
    errors = []

    def work(k):
        try:
            for i in range(rounds):
                with tracer.span("outer", thread=k, i=i):
                    handle = tracer.begin("life", thread=k)
                    with tracer.span("inner", thread=k, i=i):
                        pass
                    tracer.end(handle)
        except BaseException as e:      # surfaced below, in the test's thread
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        pool = [threading.Thread(target=work, args=(k,))
                for k in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(old)
    assert not errors
    recs = tracer.records()
    assert len(recs) == 3 * rounds * threads and tracer.dropped == 0
    assert len({r["id"] for r in recs}) == len(recs)
    summary = tracer.span_summary()
    assert all(summary[n]["count"] == rounds * threads
               for n in ("outer", "inner", "life"))
    outer = {r["id"]: r for r in recs if r["name"] == "outer"}
    for r in recs:
        if r["name"] == "outer":
            assert r["parent"] is None
        else:
            assert outer[r["parent"]]["attrs"]["thread"] \
                == r["attrs"]["thread"]
    assert tracer.overhead_s > 0


def test_recorder_is_one_tracer_per_process():
    from distributed_tensorflow_tpu.observability import recorder

    tracer = recorder()
    assert tracer is recorder() and tracer.enabled
    assert tracer.stats().get("written") is None      # no file behind it


# round 20 fast-lane repair: xprof-window e2e rides the slow lane
@pytest.mark.slow
def test_profile_wraps_xprof_window_in_span(tmp_path):
    from distributed_tensorflow_tpu.utils.metrics import profile

    tracer = Tracer(path=None)
    try:
        with profile(tmp_path / "xprof", tracer=tracer):
            jax.block_until_ready(jax.numpy.ones((4,)) * 2)
    except Exception:
        pytest.skip("jax profiler unavailable on this backend")
    assert tracer.span_summary()["xprof"]["count"] == 1


# --------------------------------------------------------------- run report


def _fit_result():
    st = StepTimer()
    st.compile_steps = 8
    st.times = [0.5] * 8 + [0.01] * 24
    return {
        "elapsed": 4.3, "steps": 32, "steps_per_call": 8,
        "chunk_sizes": [8], "prefetch_depth": 2,
        "prefetch_starvation": 1, "prefetch_fill_wait_s": 0.2,
        "step_time": st.summary(),
    }


def test_run_report_fields(tmp_path):
    from distributed_tensorflow_tpu.utils.failure import Watchdog

    ml = MetricsLogger(tmp_path / "m.jsonl", log_every=1)
    for i in range(1, 33):
        ml.log(i, loss=1.0 / i)
    ml.close()
    tracer = Tracer(path=None)
    with tracer.span("chunk_dispatch", steps=8):
        pass
    wd = Watchdog(timeout=1.0, poll_interval=0.01)
    wd.rescale(8)
    wd.beat()
    report = build_run_report(_fit_result(), watchdog=wd,
                              metrics_logger=ml, tracer=tracer)
    wd.close()
    assert report["schema_version"] == SCHEMA_VERSION
    # steady-state percentiles split from the compile-smeared first chunk
    assert report["compile_s"] == pytest.approx(4.0)
    assert report["step_time_p50_s"] == pytest.approx(0.01)
    assert report["step_time_p95_s"] == pytest.approx(0.01)
    assert report["chunk_sizes"] == [8]
    assert report["watchdog"] == {"beats": 1, "stall_episodes": 0,
                                  "timeout_s": 8.0}
    assert report["prefetch"] == {"depth": 2, "starvation": 1,
                                  "fill_wait_s": 0.2}
    assert report["metrics_sink"]["records"] == 32
    assert report["metrics_sink"]["dropped"] == 0
    assert report["spans"]["chunk_dispatch"]["count"] == 1
    # the telemetry budget is measured and self-reported
    assert report["telemetry_overhead_s"] >= 0
    assert 0 <= report["telemetry_overhead_frac"] < 0.05


def test_run_report_none_for_absent_subsystems():
    report = build_run_report({"elapsed": 0.0, "steps": 0})
    assert report["watchdog"] is None
    assert report["metrics_sink"] is None
    assert report["prefetch"] is None
    assert report["spans"] is None
    assert report["trace"] is None
    assert report["health"] is None
    assert report["telemetry_overhead_frac"] is None


def test_run_report_zero_elapsed_is_not_none():
    """Satellite: a measured 0.0-elapsed run is a real observation — the
    old `elapsed or None` collapsed it into 'never reported'."""
    report = build_run_report({"elapsed": 0.0, "steps": 0})
    assert report["elapsed_s"] == 0.0
    assert build_run_report({"steps": 0})["elapsed_s"] is None


def test_run_report_enabled_idle_tracer_is_not_none(tmp_path):
    """Satellite: an ENABLED tracer always reports a trace dict —
    file-backed-but-idle shows integer zeros-or-counts, aggregate-only
    shows None written/dropped; only a DISABLED tracer reports None."""
    agg = Tracer(path=None)
    report = build_run_report(_fit_result(), tracer=agg)
    assert report["trace"] == {"written": None, "dropped": None}
    agg.close()
    with Tracer(path=tmp_path / "t.jsonl") as filed:
        filed._sink.flush()
        report = build_run_report(_fit_result(), tracer=filed)
    assert isinstance(report["trace"]["written"], int)
    assert report["trace"]["dropped"] == 0


def test_run_report_single_chunk_run_has_no_steady_percentiles():
    """Satellite: a run that never left its compile-smeared first chunk
    has NO steady state — percentiles report None, compile_s the whole
    prefix — rather than smearing compile into 'steady' numbers."""
    st = StepTimer()
    st.compile_steps = 8
    st.times = [0.5] * 8  # one chunk, all compile-smeared
    report = build_run_report(
        {"elapsed": 4.0, "steps": 8, "step_time": st.summary()})
    assert report["compile_s"] == pytest.approx(4.0)
    assert report["step_time_p50_s"] is None
    assert report["step_time_p95_s"] is None
    assert report["step_time_mean_s"] is None


def test_run_report_without_step_time():
    report = build_run_report({"elapsed": 1.0, "steps": 0})
    assert report["compile_s"] is None
    assert report["step_time_p50_s"] is None


# --------------------------------------------------- harness / CLI end-to-end


def test_cli_run_report_with_telemetry_at_k8(tmp_path):
    """End-to-end through the harness: metrics + trace enabled, explicit
    steps_per_call=8 — the run keeps its chunking, the summary carries the
    run report, and both JSONL artifacts land on disk.

    Subprocess (like the other CLI tests): the harness initializes a jax
    backend, which must not leak into this process's fake 8-CPU mesh."""
    repo = Path(__file__).resolve().parents[1]
    metrics = tmp_path / "metrics.jsonl"
    trace = tmp_path / "trace.jsonl"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "distributed_tensorflow_tpu.cli",
         "--dataset", "synthetic", "--model", "mlp", "-n", "1",
         "-b", "32", "--log-every", "4", "--steps-per-call", "8",
         "--watchdog-timeout", "30", "--health", "on",
         "--metrics-path", str(metrics), "--trace", str(trace)],
        capture_output=True, text=True, timeout=300, env=env, cwd=str(repo))
    if proc.returncode != 0 and "shard_map" in (proc.stderr or ""):
        pytest.skip("engine layer needs jax.shard_map")
    assert proc.returncode == 0, proc.stderr[-2000:]
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["steps_per_call"] == 8  # telemetry did not downshift
    report = summary["run_report"]
    assert report["steps"] == summary["steps"]
    assert report["metrics_sink"]["dropped"] == 0
    assert report["watchdog"]["beats"] >= 1
    assert report["watchdog"]["timeout_s"] == pytest.approx(240.0)
    assert report["telemetry_overhead_s"] >= 0
    # --health on: the report carries the health section and the metric
    # records carry the on-device health trajectory (ISSUE 4)
    assert report["health"]["anomalies"] == 0
    assert report["health"]["max_update_ratio"] > 0
    # both artifacts are whole-line JSONL with the schema stamp
    recs = [json.loads(line) for line in metrics.read_text().splitlines()]
    assert recs and all(r["schema_version"] == SCHEMA_VERSION for r in recs)
    assert all("grad_norm" in r and "update_ratio" in r for r in recs)
    spans = [json.loads(line) for line in trace.read_text().splitlines()]
    assert any(s.get("name") == "compile" for s in spans)
    assert any(s.get("name") == "eval" for s in spans)
    # the run_report event also reached the sink-readable timeline
    assert summary["run_report"]["spans"]


def test_overhead_bounded_jit_engine():
    """Telemetry-on vs telemetry-off through the pure-jit engine: the
    measured overhead the report carries must be a small fraction of the
    run, and the two configurations must produce identical trajectories
    (telemetry must observe, not perturb)."""
    sys.path.insert(0, os.path.dirname(__file__))
    from test_steady_state import JitEngine, _tiny_ds

    from distributed_tensorflow_tpu.engines.allreduce import Trainer

    def run(telemetry, tmpdir=None):
        eng = JitEngine()
        tr = Trainer(None, engine=eng, seed=0)
        kw = {}
        ml = tracer = None
        if telemetry:
            ml = MetricsLogger(None, log_every=1)
            tracer = Tracer(path=None)
            kw = dict(metrics_logger=ml, tracer=tracer)
        r = tr.fit(_tiny_ds(), epochs=2, batch_size=16, log_every=0,
                   steps_per_call=8, max_steps=13, **kw)
        report = build_run_report(r, metrics_logger=ml, tracer=tracer)
        return r, report, jax.device_get(tr.state.params)

    r_off, rep_off, p_off = run(False)
    r_on, rep_on, p_on = run(True)
    for a, b in zip(jax.tree.leaves(p_off), jax.tree.leaves(p_on)):
        np.testing.assert_array_equal(a, b)  # observed ≠ perturbed
    assert rep_on["telemetry_overhead_s"] < max(0.05 * r_on["elapsed"], 0.05)
    assert rep_off["telemetry_overhead_s"] == 0.0
