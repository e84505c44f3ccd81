"""Time-series telemetry (ISSUE 17): GaugeSeries ring-buffer laws
(bound, merge≡record-all, serialization round-trip), the throttled
Timeline sampler, the sampler-off parity pin at the batcher level, the
fleet's per-replica series surviving a seeded kill, and the offline
reconstruction path (`analyze timeline` + Perfetto counter lanes) from
the emitted trace alone.  Host-side throughout — no shard_map.
"""

import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.gpt import GPTLM
from distributed_tensorflow_tpu.observability.analyze import (
    _TIMELINE_PID_BASE, render_timeline_text, timeline_series,
    timeline_summary, to_chrome_trace)
from distributed_tensorflow_tpu.observability.timeline import (
    GaugeSeries, Timeline, sparkline, split_series_key)
from distributed_tensorflow_tpu.observability.trace import Tracer
from distributed_tensorflow_tpu.serving import (
    ContinuousBatcher, FaultInjector, ReplicaSet, Request, SlotKVCache,
    VirtualClock, build_replica_kvs)


def tiny_gpt(**kw):
    kw.setdefault("vocab_size", 64)
    kw.setdefault("hidden", 32)
    kw.setdefault("layers", 1)
    kw.setdefault("heads", 2)
    kw.setdefault("ffn", 64)
    kw.setdefault("max_len", 48)
    kw.setdefault("dropout_rate", 0.0)
    return GPTLM(**kw)


@pytest.fixture(scope="module")
def model_params():
    model = tiny_gpt()
    x = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 8)),
                    jnp.int32)
    params = model.init(jax.random.key(0), x, train=False)["params"]
    return model, params


def _requests(n=6, seed=3, max_new=8, spread=0.5):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, 64, 6 + i % 4).astype(np.int32),
                    max_new_tokens=max_new, arrival_s=float(i) * spread)
            for i in range(n)]


# ------------------------------------------------------------ ring buffer


def test_ring_bound_and_exact_totals():
    """The ring retains the most recent `capacity` samples while the
    exact totals (count/sum/min/max) cover EVERY sample ever recorded —
    the window never lies about the extremes."""
    g = GaugeSeries(capacity=8)
    vals = [float(v) for v in range(100)]
    for i, v in enumerate(vals):
        g.record(v, t_mono=float(i), wall=float(i))
    assert g.values() == vals[-8:]
    assert g.count == 100 and g.dropped == 92
    assert g.sum == sum(vals)
    assert g.vmin == 0.0 and g.vmax == 99.0
    s = g.summary()
    assert s["retained"] == 8 and s["dropped"] == 92
    assert s["mean"] == pytest.approx(sum(vals) / 100)
    assert s["max"] == 99.0 and s["min"] == 0.0   # pre-drop extremes live
    assert s["last"] == 99.0


def test_ring_capacity_validation():
    with pytest.raises(ValueError):
        GaugeSeries(capacity=0)
    with pytest.raises(ValueError):
        Timeline(interval_s=-1.0)


def test_merge_equals_record_all():
    """THE merge law: a.merge(b) holds exactly what one series recording
    both sample streams in time order would hold — retained window,
    totals, extremes — including when the union overflows the ring."""
    rng = np.random.default_rng(7)
    for na, nb, cap in ((5, 5, 32), (40, 25, 32), (3, 60, 16)):
        ta = sorted(rng.uniform(0, 100, na))
        tb = sorted(rng.uniform(0, 100, nb))
        a = GaugeSeries(capacity=cap)
        b = GaugeSeries(capacity=cap)
        ref = GaugeSeries(capacity=cap)
        for t in ta:
            a.record(t * 2.0, t_mono=t, wall=t)
        for t in tb:
            b.record(-t, t_mono=t, wall=t)
        for t, v in sorted([(t, t * 2.0) for t in ta]
                           + [(t, -t) for t in tb]):
            ref.record(v, t_mono=t, wall=t)
        a.merge(b)
        assert a.samples() == ref.samples()
        assert a.count == ref.count
        assert a.sum == pytest.approx(ref.sum)
        assert a.vmin == ref.vmin and a.vmax == ref.vmax
        assert a.summary() == pytest.approx(ref.summary())


def test_serialization_round_trip():
    """to_dict → JSON → from_dict reproduces samples, totals, and every
    summary stat — including a ring that has dropped samples."""
    g = GaugeSeries(capacity=4)
    for i in range(9):
        g.record(float(i * i), t_mono=float(i), wall=100.0 + i)
    d = json.loads(json.dumps(g.to_dict()))
    h = GaugeSeries.from_dict(d)
    assert h.samples() == g.samples()
    assert h.count == g.count and h.sum == g.sum
    assert h.vmin == g.vmin and h.vmax == g.vmax
    assert h.summary() == g.summary()
    # Timeline round-trip carries every series + the overhead ledger
    tl = Timeline(interval_s=0.0, capacity=4)
    tl.sample_many({"a": 1.0, "b": 2.0})
    tl2 = Timeline.from_dict(json.loads(json.dumps(tl.to_dict())))
    assert tl2.names() == tl.names()
    assert tl2.summary() == tl.summary()


def test_auc_trapezoid():
    g = GaugeSeries()
    assert g.auc() is None
    g.record(2.0, t_mono=0.0, wall=0.0)
    assert g.auc() is None          # one sample spans no time
    g.record(4.0, t_mono=1.0, wall=1.0)
    g.record(0.0, t_mono=3.0, wall=3.0)
    # (2+4)/2*1 + (4+0)/2*2
    assert g.auc() == pytest.approx(7.0)


def test_sparkline_shapes():
    assert sparkline([]) == ""
    assert sparkline([5.0, 5.0, 5.0]) == "▁▁▁"
    s = sparkline(list(range(200)), width=60)
    assert len(s) == 60
    assert s[0] == "▁" and s[-1] == "█"
    assert len(sparkline([1.0, 9.0])) == 2


def test_split_series_key():
    assert split_series_key("queue_depth") == ("queue_depth", None)
    assert split_series_key("replica_load@r3") == ("replica_load", 3)
    assert split_series_key("odd@rx") == ("odd@rx", None)


# --------------------------------------------------------------- sampler


def test_timeline_throttle_interval():
    """One recorded sample per interval per throttle group; the skip
    path records nothing; interval 0 records at every boundary."""
    t = [0.0]
    tl = Timeline(interval_s=1.0, clock=lambda: t[0])
    assert tl.sample("g", 1.0) is True
    t[0] = 0.5
    assert tl.sample("g", 2.0) is False
    t[0] = 1.0
    assert tl.sample("g", 3.0) is True
    assert tl.series("g").values() == [1.0, 3.0]
    # distinct groups throttle independently
    assert tl.sample("h", 9.0) is True
    every = Timeline(interval_s=0.0, clock=lambda: t[0])
    for _ in range(5):
        assert every.sample("g", 1.0) is True
    assert every.series("g").count == 5
    assert tl.overhead_s >= 0.0


def test_timeline_merge_and_stat():
    a = Timeline(interval_s=0.0)
    b = Timeline(interval_s=0.0)
    a.sample("q", 1.0, replica=0)
    b.sample("q", 5.0, replica=1)
    b.sample("q", 3.0, replica=0)
    a.merge(b)
    assert a.names() == ["q@r0", "q@r1"]
    assert a.stat("q", "max", replica=0) == 3.0
    assert a.stat("q", "max", replica=1) == 5.0
    assert a.stat("missing", "max") is None


def test_emit_reconstruction_lossless(tmp_path):
    """emit() → trace file → analyze's timeline_series reproduces the
    retained window AND the exact totals even when the ring dropped
    samples — the counter-cliff forensics work from the file alone."""
    path = tmp_path / "trace.jsonl"
    tl = Timeline(interval_s=0.0, capacity=4)
    for i in range(11):
        tl.series("load", replica=1).record(float(i), t_mono=float(i),
                                            wall=float(i))
    with Tracer(path=path, annotate=False) as tr:
        tl.emit(tr)
    records = [json.loads(l) for l in path.read_text().splitlines()]
    series = timeline_series(records)
    g = series["load@r1"]
    assert g.values() == [7.0, 8.0, 9.0, 10.0]
    assert g.count == 11 and g.dropped == 7
    assert g.vmin == 0.0 and g.vmax == 10.0 and g.sum == sum(range(11))
    summ = timeline_summary(records)
    assert summ["series"]["load@r1"]["max"] == 10.0
    text = render_timeline_text(records)
    assert "load@r1" in text and "+7 dropped" in text
    assert render_timeline_text([]).startswith("(no timeline_series")


# ------------------------------------------------- batcher parity (off/on)


def test_batcher_sampler_off_parity(model_params):
    """The PR 11 parity pin at the batcher level: with the sampler OFF
    the token streams, compiled-program inventory, and summary key set
    are byte-identical to pre-timeline; flag ON adds EXACTLY the two
    timeline keys and changes no token."""
    model, params = model_params
    kv_off = SlotKVCache(model, params, slots=2)
    off = ContinuousBatcher(kv_off, clock=VirtualClock()).run(_requests())
    kv_on = SlotKVCache(model, params, slots=2)
    tl = Timeline(interval_s=0.0)
    on = ContinuousBatcher(kv_on, clock=VirtualClock(),
                           timeline=tl).run(_requests())
    for a, b in zip(off["results"], on["results"]):
        assert a.tokens == b.tokens, (a.rid, a.tokens, b.tokens)
    assert set(kv_on.compiled_programs()) == set(kv_off.compiled_programs())
    extra = set(on) - set(off)
    assert extra == {"queue_depth_auc", "timeline_overhead_s"}, extra
    assert set(off) - set(on) == set()
    assert on["queue_depth_auc"] is not None
    assert on["timeline_overhead_s"] == tl.overhead_s
    # the batcher sampled at decode boundaries: queue/slot/kv gauges live
    assert {"queue_depth", "active_slots", "prefill_pending"} <= \
        set(tl.names())


def test_batcher_timeline_overhead_budget(model_params):
    """Self-measured sampler cost stays under 1% of the run's wall time
    (the budget BASELINE.md states is measured, not assumed)."""
    import time
    model, params = model_params
    tl = Timeline(interval_s=0.0)
    t0 = time.perf_counter()
    ContinuousBatcher(SlotKVCache(model, params, slots=2),
                      clock=VirtualClock(), timeline=tl).run(_requests())
    elapsed = time.perf_counter() - t0
    assert tl.overhead_s < 0.01 * elapsed, (tl.overhead_s, elapsed)


# --------------------------------------------------- fleet kill → cliff


def test_fleet_per_replica_series_survive_kill(model_params, tmp_path):
    """A seeded kill of replica 0 leaves its per-replica lanes IN the
    emitted trace with the counter cliff visible: replica 0's load lane
    exists and ends at zero, the admitting-replicas gauge steps 2 → 1,
    and the survivor's lane keeps sampling."""
    model, params = model_params
    path = tmp_path / "fleet_trace.jsonl"
    tl = Timeline(interval_s=0.0)
    inj = FaultInjector("crash:replica=0,iter=3", seed=0)
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    clock=VirtualClock(), fault_injector=inj,
                    timeline=tl)
    s = rs.run(_requests())
    assert s["serve_fleet"]["failovers"] == 1
    assert s["completed"] == s["offered"] == 6
    with Tracer(path=path, annotate=False) as tr:
        tl.emit(tr)
    records = [json.loads(l) for l in path.read_text().splitlines()]
    series = timeline_series(records)
    # both replica lanes present (batcher gauges key by replica tag too)
    assert "replica_load@r0" in series and "replica_load@r1" in series
    assert "queue_depth@r0" in series and "queue_depth@r1" in series
    # the cliff: replica 0 stops serving → its load lane ends at 0 while
    # the fleet-level admitting count steps down to exactly 1
    assert series["replica_load@r0"].values()[-1] == 0.0
    adm = series["admitting_replicas"]
    assert adm.vmax == 2.0 and adm.vmin == 1.0 and adm.values()[-1] == 1.0
    # the journal charged the requeue
    assert series["journal_retries"].vmax >= 1.0
    # fleet summary carries the flag-on keys (folded across replicas)
    assert s["timeline_overhead_s"] == tl.overhead_s
    assert "queue_depth_auc" in s


def test_chrome_counter_lanes(tmp_path):
    """Perfetto export: per-replica timeline series render as counter
    tracks on synthetic per-replica pids with process_name metadata —
    replica lanes separate in the UI."""
    path = tmp_path / "trace.jsonl"
    tl = Timeline(interval_s=0.0)
    tl.sample_many({"queue_depth": 3.0}, replica=0)
    tl.sample_many({"queue_depth": 1.0}, replica=1)
    tl.sample_many({"admitting_replicas": 2.0})
    with Tracer(path=path, annotate=False) as tr:
        tl.emit(tr)
    records = [json.loads(l) for l in path.read_text().splitlines()]
    events = to_chrome_trace(records)["traceEvents"]
    counters = [e for e in events if e["ph"] == "C"]
    assert counters, "no counter events"
    pids = {e["pid"] for e in counters}
    assert _TIMELINE_PID_BASE in pids and _TIMELINE_PID_BASE + 1 in pids
    metas = {e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert "replica 0 (timeline)" in metas
    assert "replica 1 (timeline)" in metas
    # the fleet-level (replica-less) series stays on the host pid
    host = [e for e in counters if e["name"] == "admitting_replicas"]
    assert host and host[0]["pid"] not in (
        _TIMELINE_PID_BASE, _TIMELINE_PID_BASE + 1)
