"""SLO-aware serving observability (ISSUE 13): log-bucketed histogram
exactness/merge semantics, SLOMonitor goodput accounting, per-request
phase attribution, bounded-admission overload mode (shed-with-429,
conservation), lease drain of a serving window, the `analyze serve`
waterfall, and the new `analyze diff` gates.  Everything here runs on
this container — the histogram/SLO layer is stdlib host code and the
batcher tests ride the same GSPMD jit paths as tests/test_serving.py.
"""

import json
import math
import os
import signal
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.gpt import GPTLM
from distributed_tensorflow_tpu.observability import Tracer
from distributed_tensorflow_tpu.observability.metrics import (
    LogHistogram, MetricsRegistry, exact_percentile)
from distributed_tensorflow_tpu.observability.slo import SLOMonitor
from distributed_tensorflow_tpu.serving import (
    ContinuousBatcher, Request, RequestQueue, SlotKVCache, VirtualClock)


def tiny_gpt(**kw):
    kw.setdefault("vocab_size", 64)
    kw.setdefault("hidden", 32)
    kw.setdefault("layers", 2)
    kw.setdefault("heads", 2)
    kw.setdefault("ffn", 64)
    kw.setdefault("max_len", 32)
    kw.setdefault("dropout_rate", 0.0)
    return GPTLM(**kw)


@pytest.fixture(scope="module")
def model_params():
    model = tiny_gpt()
    x = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 8)),
                    jnp.int32)
    params = model.init(jax.random.key(0), x, train=False)["params"]
    return model, params


def _requests(n, seed=0, rate=None, max_new=4, lo=3, hi=9):
    rng = np.random.default_rng(seed)
    arrivals = (rng.exponential(1.0 / rate, n).cumsum()
                if rate else np.zeros(n))
    return [Request(rid=i,
                    prompt=rng.integers(0, 64, int(rng.integers(lo, hi)))
                    .astype(np.int32),
                    max_new_tokens=max_new,
                    arrival_s=float(arrivals[i]))
            for i in range(n)]


# ------------------------------------------------------- histogram exactness

@pytest.mark.parametrize("dist", ["uniform", "lognormal", "point_mass"])
def test_histogram_quantiles_within_one_bucket_width(dist):
    """THE exactness contract: every histogram quantile is within one
    bucket's relative width (growth − 1) of the exact stored-sample
    percentile, across distribution shapes — uniform (flat), lognormal
    (the latency shape), point-mass (ties)."""
    rng = np.random.default_rng(0)
    n = 5000
    if dist == "uniform":
        vals = rng.uniform(1e-4, 1.0, n)
    elif dist == "lognormal":
        vals = rng.lognormal(mean=-3.0, sigma=1.0, size=n)
    else:
        vals = np.full(n, 0.0421)
    h = LogHistogram()
    for v in vals:
        h.record(float(v))
    g = h.growth
    for q in (0.5, 0.9, 0.95, 0.99):
        exact = exact_percentile(vals.tolist(), q)
        approx = h.quantile(q)
        assert approx is not None
        # one bucket width each way (tiny epsilon for the interpolated
        # reference straddling a bucket edge)
        assert exact / g * 0.999 <= approx <= exact * g * 1.001, (
            dist, q, exact, approx)


def test_histogram_point_mass_is_exact():
    h = LogHistogram()
    for _ in range(100):
        h.record(0.25)
    # quantiles clamp into the tracked exact [min, max] — a point mass
    # reports its exact value, not a bucket edge
    assert h.quantile(0.5) == 0.25
    assert h.quantile(0.99) == 0.25
    assert h.vmin == h.vmax == 0.25


def test_histogram_underflow_overflow_and_extremes():
    h = LogHistogram(min_value=1e-3, max_value=10.0)
    for v in (1e-6, 5e-4, 0.5, 123.0):
        h.record(v)
    assert h.underflow == 2 and h.overflow == 1
    assert h.count == 4
    assert h.quantile(0.0) == pytest.approx(1e-6)   # underflow → exact min
    assert h.quantile(1.0) == pytest.approx(123.0)  # overflow → exact max


def test_histogram_merge_equals_record_all():
    rng = np.random.default_rng(1)
    a_vals = rng.lognormal(-2.0, 0.7, 400)
    b_vals = rng.uniform(1e-5, 2.0, 300)
    a, b, ref = LogHistogram(), LogHistogram(), LogHistogram()
    for v in a_vals:
        a.record(float(v))
        ref.record(float(v))
    for v in b_vals:
        b.record(float(v))
        ref.record(float(v))
    a.merge(b)
    # merged quantiles are EXACTLY record-all's (same fixed ladder)
    assert a.counts == ref.counts
    assert a.count == ref.count and a.underflow == ref.underflow
    assert a.sum == pytest.approx(ref.sum)
    for q in (0.5, 0.95, 0.99):
        assert a.quantile(q) == ref.quantile(q)


def test_histogram_merge_rejects_different_ladder():
    with pytest.raises(ValueError, match="ladder"):
        LogHistogram(growth=1.05).merge(LogHistogram(growth=1.1))


def test_histogram_serialization_roundtrip():
    h = LogHistogram()
    for v in (0.001, 0.01, 0.1, 1.0, 0.1):
        h.record(v)
    h2 = LogHistogram.from_dict(json.loads(json.dumps(h.to_dict())))
    assert h2.counts == h.counts
    assert h2.summary() == h.summary()


def test_registry_record_snapshot_merge():
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    for v in (0.01, 0.02, 0.03):
        r1.record("ttft", v)
    r2.record("ttft", 0.04)
    r2.record("itl", 0.005)
    r1.merge(r2)
    snap = r1.snapshot()
    assert snap["ttft"]["count"] == 4
    assert snap["itl"]["count"] == 1
    assert r1.names() == ["itl", "ttft"]
    # merge left r2 untouched
    assert r2.snapshot()["ttft"]["count"] == 1


# ----------------------------------------------------------------- SLOMonitor

def test_slo_monitor_observe_and_misses():
    m = SLOMonitor(ttft_s=0.1, itl_s=0.01, quantile=0.99)
    assert m.observe(0.05, [0.005, 0.008]) is True
    assert m.observe(0.2, [0.005]) is False            # TTFT miss
    assert m.observe(0.05, [0.005, 0.5]) is False      # ITL p99 miss
    assert m.observe(0.05, []) is True                 # no gaps → ITL ok
    s = m.summary(elapsed_s=2.0)
    assert s["requests"] == 4 and s["good_requests"] == 2
    assert s["ttft_misses"] == 1 and s["itl_misses"] == 1
    assert s["slo_attainment"] == pytest.approx(0.5)
    assert s["goodput_requests_per_sec"] == pytest.approx(1.0)


def test_slo_monitor_zero_requests_window():
    m = SLOMonitor(ttft_s=1.0, itl_s=1.0)
    s = m.summary(elapsed_s=1.0)
    assert s["requests"] == 0
    assert s["slo_attainment"] is None     # no claim, not a perfect score
    assert s["goodput_requests_per_sec"] == 0.0
    assert m.summary(elapsed_s=None)["goodput_requests_per_sec"] is None


def test_slo_monitor_all_shed_window():
    m = SLOMonitor(ttft_s=1.0, itl_s=1.0)
    m.shed(5)
    s = m.summary(elapsed_s=2.0)
    assert s["shed_requests"] == 5
    assert s["good_requests"] == 0
    assert s["goodput_requests_per_sec"] == 0.0   # shed is never goodput
    assert s["slo_attainment"] is None


def test_slo_monitor_validates():
    with pytest.raises(ValueError, match="positive"):
        SLOMonitor(ttft_s=0, itl_s=1.0)
    with pytest.raises(ValueError, match="quantile"):
        SLOMonitor(ttft_s=1.0, itl_s=1.0, quantile=1.5)


# ------------------------------------------------------------- request queue

def test_request_queue_depth_and_high_watermark():
    q = RequestQueue(_requests(5, rate=1.0))
    assert q.depth() == 5                  # all queued
    d1 = q.depth(now=q.next_arrival())     # only the first has arrived
    assert d1 >= 1
    assert q.depth(now=1e9) == 5
    assert q.depth_high_watermark == 5


def test_request_queue_shed_ready_keeps_fifo_prefix():
    reqs = _requests(6)                    # all arrive at t=0
    q = RequestQueue(reqs)
    shed = q.shed_ready(now=0.0, keep=2)
    assert [r.rid for r in shed] == [2, 3, 4, 5]   # newest shed
    assert len(q) == 2
    assert q.pop_ready(0.0).rid == 0               # FIFO survivors
    assert q.shed_ready(now=0.0, keep=5) == []     # under the cap: no-op


# ------------------------------------------ batcher: attribution + overload

def test_batcher_phase_attribution_and_histograms(model_params):
    """Per-request phase attribution: queue_wait + prefill == TTFT per
    request, the summary carries p99 + queue-wait percentiles from the
    stored-sample path, and the histogram copies agree within one bucket
    width (the online-percentile contract end-to-end)."""
    model, params = model_params
    kv = SlotKVCache(model, params, 2)
    reqs = _requests(6, rate=0.5, max_new=3)
    clock = VirtualClock(tick=1.0, prefill_token_tick=0.1)
    b = ContinuousBatcher(kv, clock=clock,
                          slo=SLOMonitor(ttft_s=1e9, itl_s=1e9))
    s = b.run(reqs)
    assert s["completed"] == 6
    for r in s["results"]:
        assert r.queue_wait_s >= 0
        assert r.prefill_s >= 0
        assert r.queue_wait_s + r.prefill_s == pytest.approx(r.ttft_s)
        assert r.slo_met is True
    # stored-sample percentile keys (p50 ≤ p95 ≤ p99, same stdlib path)
    assert (s["serve_ttft_p50_s"] <= s["serve_ttft_p95_s"]
            <= s["serve_ttft_p99_s"])
    assert (s["serve_queue_wait_p50_s"] <= s["serve_queue_wait_p95_s"]
            <= s["serve_queue_wait_p99_s"])
    assert s["serve_itl_p99_s"] >= s["serve_itl_p95_s"] >= 0
    # histogram copies within one bucket's relative width of exact
    hist = s["histograms"]
    for name, exact in (("ttft", s["serve_ttft_p99_s"]),
                        ("queue_wait", s["serve_queue_wait_p99_s"]),
                        ("itl", s["serve_itl_p99_s"])):
        hq = hist[name]["p99"]
        g = 1.0 + hist[name]["relative_width"]
        if exact and exact > 0:
            assert exact / g * 0.999 <= hq <= exact * g * 1.001, (
                name, exact, hq)
    assert hist["ttft"]["count"] == 6
    # goodput under an unmissable SLO == throughput
    assert s["serve_goodput_under_slo"] == pytest.approx(
        s["serve_requests_per_sec"])
    assert s["slo"]["slo_attainment"] == 1.0
    # queue-pressure keys exist
    assert s["queue_depth_p95"] is not None
    assert s["queue_depth_high_watermark"] >= 1
    # device-phase split observed some host time in both programs
    assert s["device_phase_s"]["prefill_s"] > 0
    assert s["device_phase_s"]["decode_s"] > 0


def test_batcher_external_registry_merges_across_windows(model_params):
    model, params = model_params
    kv = SlotKVCache(model, params, 2)
    reg = MetricsRegistry()
    b = ContinuousBatcher(kv, metrics=reg)
    b.run(_requests(3, max_new=2))
    b.run(_requests(3, seed=1, max_new=2))
    # the external registry accumulated BOTH windows (merge semantics)
    assert reg.snapshot()["ttft"]["count"] == 6


def test_batcher_shed_accounting_conservation(model_params):
    """Exact conservation under the queue cap: admitted + shed +
    unserved == offered, every shed gets an overload event + counter,
    and the SLO monitor counts shed as offered-not-goodput."""
    model, params = model_params
    kv = SlotKVCache(model, params, 2)
    slo = SLOMonitor(ttft_s=1e9, itl_s=1e9)
    b = ContinuousBatcher(kv, clock=VirtualClock(), queue_cap=2, slo=slo)
    s = b.run(_requests(10, max_new=2))    # all arrive at t=0
    assert s["shed_requests"] > 0
    assert (s["admitted"] + s["shed_requests"] + s["unserved_requests"]
            == s["offered"] == 10)
    assert s["serve_shed_rate"] == pytest.approx(s["shed_requests"] / 10)
    assert s["slo"]["shed_requests"] == s["shed_requests"]
    assert len(s["shed_rids"]) == s["shed_requests"]
    # shed rids and completed rids partition the offered set
    done = {r.rid for r in s["results"]}
    assert done.isdisjoint(s["shed_rids"])
    assert len(done) + len(s["shed_rids"]) == 10


# round 20 fast-lane repair: overload acceptance race (~9s) rides the
# slow lane; the bounded-admission conservation pins stay fast
@pytest.mark.slow
def test_overload_bounded_queue_wait_acceptance(model_params):
    """THE overload acceptance (ISSUE 13): on the same seeded trace,
    deterministic in decode-iteration time (VirtualClock), the uncapped
    batcher's queue wait GROWS with offered load, while the queue-capped
    batcher at ~2× the knee keeps queue-wait p99 bounded (≤ 3× the
    at-knee value) and sheds the excess with exact accounting."""
    model, params = model_params

    def run(rate, cap):
        kv = SlotKVCache(model, params, 2)
        b = ContinuousBatcher(kv, clock=VirtualClock(tick=1.0),
                              queue_cap=cap,
                              slo=SLOMonitor(ttft_s=1e9, itl_s=1e9))
        return b.run(_requests(24, seed=3, rate=rate, max_new=4))

    # service capacity ≈ slots/(max_new iterations) = 0.5 req/tick: the
    # knee.  2× and 4× the knee are increasingly overloaded.
    knee, over, collapse = 0.5, 1.0, 2.0
    s_knee = run(knee, cap=0)
    s_over = run(over, cap=0)
    s_coll = run(collapse, cap=0)
    # uncapped: queue wait grows monotonically with offered load
    assert (s_knee["serve_queue_wait_p99_s"]
            < s_over["serve_queue_wait_p99_s"]
            < s_coll["serve_queue_wait_p99_s"])
    assert s_over["shed_requests"] == 0
    # capped at 2× the knee: bounded queue wait + exact shed accounting
    s_cap = run(over, cap=2)
    assert s_cap["shed_requests"] > 0
    assert (s_cap["admitted"] + s_cap["shed_requests"]
            + s_cap["unserved_requests"] == s_cap["offered"] == 24)
    assert (s_cap["serve_queue_wait_p99_s"]
            <= 3.0 * s_knee["serve_queue_wait_p99_s"])
    assert (s_cap["serve_queue_wait_p99_s"]
            < s_over["serve_queue_wait_p99_s"])
    # and the cap bounds the observed backlog itself
    assert s_cap["queue_depth_p95"] <= 2.0


def test_observability_off_parity_with_pr10(model_params):
    """Parity discipline: with SLO/overload observability OFF (and even
    ON, uncapped — it is all host-side), the compiled program set and
    the greedy tokens are byte-identical to the PR 10 batcher."""
    model, params = model_params
    reqs = lambda: _requests(5, seed=7, rate=1.0, max_new=3)  # noqa: E731

    kv_plain = SlotKVCache(model, params, 2)
    plain = ContinuousBatcher(kv_plain, clock=VirtualClock()).run(reqs())

    kv_obs = SlotKVCache(model, params, 2)
    obs = ContinuousBatcher(
        kv_obs, clock=VirtualClock(), metrics=MetricsRegistry(),
        slo=SLOMonitor(ttft_s=0.001, itl_s=0.001),
        queue_cap=0).run(reqs())

    assert [r.tokens for r in plain["results"]] == \
        [r.tokens for r in obs["results"]]
    # the compiled-programs pin, extended: observability adds NO programs
    assert kv_obs.compiled_programs() == kv_plain.compiled_programs()
    assert kv_plain.compiled_programs()["prefill_chunk_buckets"] == 0
    assert kv_plain.compiled_programs()["prefix_block_ops"] == 0


# ------------------------------------------------------------- lease drain

def test_batcher_should_stop_drains_gracefully(model_params, tmp_path):
    """The serving lease drain: should_stop firing mid-run stops
    admission, finishes in-flight requests, accounts the unserved tail,
    and closes every opened span — the partial summary is consistent."""
    model, params = model_params
    kv = SlotKVCache(model, params, 2)
    trace = tmp_path / "drain.jsonl"
    tracer = Tracer(path=trace)
    fired = {"n": 0}

    def stop(_iters):
        fired["n"] += 1
        return "signal:SIGTERM" if fired["n"] > 4 else None

    b = ContinuousBatcher(kv, clock=VirtualClock(), tracer=tracer,
                          should_stop=stop,
                          slo=SLOMonitor(ttft_s=1e9, itl_s=1e9))
    s = b.run(_requests(12, rate=0.2, max_new=4))   # slow arrivals
    tracer.close()
    assert s["preempted"] == "signal:SIGTERM"
    assert 0 < s["completed"] < 12
    assert s["unserved_requests"] == 12 - s["completed"]
    assert (s["admitted"] + s["shed_requests"] + s["unserved_requests"]
            == s["offered"])
    # every opened request span closed (count == completed) + the
    # structured drain event is in the trace
    recs = [json.loads(line) for line in trace.read_text().splitlines()]
    req_spans = [r for r in recs if r.get("event") == "span"
                 and r.get("name") == "request"]
    assert len(req_spans) == s["completed"]
    drains = [r for r in recs if r.get("event") == "event"
              and r.get("name") == "serve_preempted"]
    assert drains and drains[0]["reason"] == "signal:SIGTERM"
    # the table is clean: a later run on the same kv serves normally
    s2 = ContinuousBatcher(kv, clock=VirtualClock()).run(
        _requests(3, max_new=2))
    assert s2["completed"] == 3


# round 20 fast-lane repair: subprocess sigterm e2e rides the slow lane
@pytest.mark.slow
def test_harness_sigterm_with_serve_flushes_serve_section(tmp_path):
    """Satellite (PR 9 integration): the in-process SIGTERM harness from
    tests/test_elastic.py, now with --serve — a preempted run must still
    flush its serve section (drained, with exact accounting) into the
    summary AND run report before exit."""
    from distributed_tensorflow_tpu.data.loaders import load_lm_dataset
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    def lm_fn(batch_size, type="train", **kw):
        return load_lm_dataset(seq_len=16, vocab_size=64, n_train=64,
                               n_test=32, split=type)

    cfg = ExperimentConfig(
        engine="fsdp", model="gpt", dataset="lm_synth", dataset_fn=lm_fn,
        n_devices=8, batch_size=4, epochs=800, log_every=0,
        steps_per_call=4,
        model_args={"hidden": 32, "layers": 1, "heads": 2, "ffn": 64,
                    "max_len": 32},
        checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=4,
        serve_requests=5, serve_slots=2, serve_max_new=4,
        serve_prompt_len=4)
    timer = threading.Timer(2.0, os.kill,
                            args=(os.getpid(), signal.SIGTERM))
    timer.daemon = True
    timer.start()
    try:
        s = run(cfg)
    finally:
        timer.cancel()
    assert s["preempted"] == "signal:SIGTERM"
    sec = s["serve"]
    assert sec is not None
    assert sec == s["run_report"]["serve"]
    # the drained window's accounting is exact whether it served
    # nothing (signal before serve) or part of the queue (signal mid-
    # serve): admitted + shed + unserved == offered == 5
    assert (sec["admitted"] + sec["shed_requests"]
            + sec["unserved_requests"] == sec["offered"] == 5)
    assert sec["preempted"] == "signal:SIGTERM" or sec["completed"] == 5
    assert sec["serve_goodput_under_slo"] is not None \
        or sec["completed"] == 0


def test_should_stop_interrupts_idle_wait(model_params):
    """A preemption notice landing in a long idle gap drains within one
    poll slice — not after the next arrival (regression: the hook was
    only consulted at the loop top, so a wall-clock batcher idling 30s
    to the next arrival ignored SIGTERM for the whole gap)."""
    import time as timelib

    model, params = model_params
    kv = SlotKVCache(model, params, 2)
    flag = {"stop": False}

    def on_token(rid, tok):
        flag["stop"] = True    # preempt once the first request streams

    b = ContinuousBatcher(
        kv, should_stop=lambda _i: ("signal:SIGTERM" if flag["stop"]
                                    else None))
    reqs = [Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=2, arrival_s=0.0),
            Request(rid=1, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=2, arrival_s=30.0)]   # far future
    t0 = timelib.monotonic()
    s = b.run(reqs, on_token=on_token)
    elapsed = timelib.monotonic() - t0
    assert s["preempted"] == "signal:SIGTERM"
    assert s["completed"] == 1 and s["unserved_requests"] == 1
    assert elapsed < 5.0     # drained within poll slices, not after 30s


# ------------------------------------------------------- analyze: waterfall

def test_analyze_serve_waterfall_from_trace(model_params, tmp_path):
    from distributed_tensorflow_tpu.observability.analyze import (
        read_jsonl, render_waterfall_text, serve_waterfall,
        trace_summary)

    model, params = model_params
    kv = SlotKVCache(model, params, 2)
    trace = tmp_path / "serve.jsonl"
    tracer = Tracer(path=trace)
    b = ContinuousBatcher(kv, tracer=tracer, clock=VirtualClock(),
                          queue_cap=2,
                          slo=SLOMonitor(ttft_s=1e9, itl_s=1e9))
    s = b.run(_requests(8, max_new=3))     # burst at t=0 → some shed
    tracer.close()
    recs = read_jsonl(trace)
    wf = serve_waterfall(recs)
    assert wf["requests_n"] == s["completed"]
    assert wf["shed_n"] == s["shed_requests"] > 0
    by_rid = {r.rid: r for r in s["results"]}
    for row in wf["requests"]:
        r = by_rid[row["rid"]]
        assert row["queue_wait_s"] == pytest.approx(r.queue_wait_s)
        assert row["prefill_s"] == pytest.approx(r.prefill_s)
        assert row["decode_s"] == pytest.approx(r.decode_s)
        assert row["ttft_s"] == pytest.approx(r.ttft_s)
        assert row["slo_met"] is True
        assert row["tokens"] == len(r.tokens)
    # overload events record the PRE-shed backlog that triggered them
    # (post-shed depth is always == cap — zero information)
    for shed_row in wf["shed"]:
        assert shed_row["queue_depth"] > 2
        assert shed_row["queue_cap"] == 2
    text = render_waterfall_text(wf)
    assert "shed (429)" in text and "legend" in text
    # `analyze spans` surfaces the overload engagement
    summ = trace_summary(recs)
    assert summ["stalls"]["overload_events"] == s["shed_requests"]
    assert summ["counters"]["shed_requests"] == s["shed_requests"]


def test_waterfall_multi_window_rid_reuse(model_params, tmp_path):
    """A bench-style trace holds several windows that all reuse rids
    0..n−1: every window's request span gets its OWN row, and each
    prefill_chunk attaches to the span whose interval contains it
    (regression: rid-keyed rows silently merged windows)."""
    from distributed_tensorflow_tpu.observability.analyze import (
        read_jsonl, serve_waterfall)

    model, params = model_params
    kv = SlotKVCache(model, params, 2)
    trace = tmp_path / "two_windows.jsonl"
    with Tracer(path=trace) as tracer:
        for _ in range(2):                 # two windows, same rids
            ContinuousBatcher(kv, tracer=tracer, clock=VirtualClock(),
                              prefill_chunk=2).run(
                _requests(3, max_new=2, lo=5, hi=6))
    recs = read_jsonl(trace)
    wf = serve_waterfall(recs)
    assert wf["requests_n"] == 6           # 3 rids × 2 windows
    n_chunk_spans = sum(1 for r in recs if r.get("event") == "span"
                        and r.get("name") == "prefill_chunk")
    attributed = sum(len(r["prefill_chunks"]) for r in wf["requests"])
    assert attributed == n_chunk_spans     # none lost, none duplicated
    assert all(len(r["prefill_chunks"]) >= 1 for r in wf["requests"])


def test_waterfall_text_shed_past_last_span_no_crash():
    """A partial trace can carry overload events later than every CLOSED
    request span (sheds are emitted immediately, spans only at exit):
    the text renderer clamps instead of crashing on a negative pad."""
    from distributed_tensorflow_tpu.observability.analyze import (
        render_waterfall_text)

    wf = {"requests": [{"rid": 0, "t": 100.0, "dur_s": 1.0,
                        "queue_wait_s": 0.1, "prefill_s": 0.2,
                        "decode_s": 0.7, "ttft_s": 0.3, "slo_met": None,
                        "prefill_chunks": []}],
          "shed": [{"rid": 1, "t": 5000.0, "queue_depth": 9,
                    "queue_cap": 2}],
          "requests_n": 1, "shed_n": 1, "slo_met_n": None}
    text = render_waterfall_text(wf, width=40)
    assert "shed (429) at depth 9" in text


def test_analyze_serve_cli_subcommand(model_params, tmp_path):
    from distributed_tensorflow_tpu.observability.analyze import main

    model, params = model_params
    kv = SlotKVCache(model, params, 2)
    trace = tmp_path / "serve.jsonl"
    with Tracer(path=trace) as tracer:
        ContinuousBatcher(kv, tracer=tracer, clock=VirtualClock()).run(
            _requests(3, max_new=2))
    assert main(["serve", str(trace)]) == 0
    assert main(["serve", str(trace), "--text"]) == 0


# ------------------------------------------------------------ analyze: diff

def test_diff_gates_slo_keys_directions():
    from distributed_tensorflow_tpu.observability.analyze import (
        diff_reports)

    base = {"serve_ttft_p99_s": 0.1, "serve_itl_p99_s": 0.01,
            "serve_queue_wait_p99_s": 0.05,
            "serve_goodput_under_slo": 10.0,
            "serve_max_goodput_under_slo": 20.0,
            "serve_knee_rate_per_s": 16.0,
            "serve_shed_rate": 0.1}
    worse = {"serve_ttft_p99_s": 0.2, "serve_itl_p99_s": 0.02,
             "serve_queue_wait_p99_s": 0.2,
             "serve_goodput_under_slo": 5.0,
             "serve_max_goodput_under_slo": 10.0,
             "serve_knee_rate_per_s": 8.0,
             "serve_shed_rate": 0.4}
    d = diff_reports(base, worse)
    assert {r["metric"] for r in d["regressions"]} == set(base)
    d2 = diff_reports(worse, base)
    assert not d2["regressions"]
    assert {r["metric"] for r in d2["improvements"]} == set(base)


def test_load_report_flattens_goodput_keys(tmp_path):
    from distributed_tensorflow_tpu.observability.analyze import (
        load_report)

    p = tmp_path / "summary.json"
    p.write_text(json.dumps({
        "serve": {"serve_goodput_under_slo": 4.2,
                  "serve_ttft_p99_s": 0.3,
                  "serve_queue_wait_p99_s": 0.1,
                  "serve_shed_rate": 0.0,
                  "shed_requests": 0}}))
    flat = load_report(p)
    assert flat["serve_goodput_under_slo"] == 4.2
    assert flat["serve_ttft_p99_s"] == 0.3
    assert flat["serve_queue_wait_p99_s"] == 0.1
    assert flat["serve_shed_rate"] == 0.0


# -------------------------------------------------------- shared percentile


def test_exact_percentile_matches_scheduler_percentile():
    """The scheduler's stored-sample path and the histogram module share
    literally the same percentile function (no drift possible)."""
    from distributed_tensorflow_tpu.serving import scheduler

    assert scheduler._percentile is exact_percentile
    vals = [3.0, 1.0, 2.0]
    assert exact_percentile(vals, 0.5) == 2.0
    assert exact_percentile([], 0.5) is None
    assert exact_percentile([7.0], 0.99) == 7.0
    assert exact_percentile(vals, 1.0) == 3.0
    assert math.isclose(exact_percentile(vals, 0.25), 1.5)
