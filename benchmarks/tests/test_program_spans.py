"""The readers of ``lib/program_spans.py`` on hand-made records: what a gap
is, what is taken off it, whose prefill a wait is charged to, and None
wherever there is nothing to read."""

from __future__ import annotations

import json

import pytest

from benchmarks.lib import program_spans as ps
from helpers import ROOT


def rec(name, start, end, rid=None, **attrs):
    return {"name": name, "start": start, "end": end, "id": 0,
            "parent": None, "rid": rid, "attrs": {"rid": rid, **attrs}}


def params_of(metric: str) -> dict:
    return json.loads(
        (ROOT / "benchmarks/metrics" / f"{metric}.json").read_text())


@pytest.fixture
def window(monkeypatch):
    def put(records):
        monkeypatch.setattr(ps, "window", lambda params: records)
    return put


STALL = {"span": "decode_step", "breaks": ["idle_wait"], "minus": [],
         "q": 100, "scale": 1000.0}


def test_the_stall_is_the_gap_between_two_rounds(window):
    window([rec("decode_step", 0.0, 0.09), rec("decode_step", 0.10, 0.19),
            rec("prefill", 0.20, 0.45, rid=3),
            rec("decode_step", 0.46, 0.55)])
    assert ps.gap_percentile(STALL, {}, {}) == pytest.approx(270.0)
    assert ps.gap_percentile({**STALL, "q": 0}, {}, {}) == pytest.approx(10.0)


def test_an_idle_wait_between_two_rounds_is_no_stall(window):
    window([rec("decode_step", 0.0, 0.09), rec("idle_wait", 0.091, 2.0),
            rec("decode_step", 2.3, 2.4), rec("decode_step", 2.42, 2.5)])
    assert ps.gap_percentile(STALL, {}, {}) == pytest.approx(20.0)
    window([rec("decode_step", 0.0, 0.09), rec("idle_wait", 0.091, 2.0),
            rec("decode_step", 2.3, 2.4)])
    assert ps.gap_percentile(STALL, {}, {}) is None


def test_the_host_gap_leaves_out_the_prefill_inside_it(window):
    window([rec("decode_step", 0.0, 0.09),
            rec("prefill", 0.091, 0.341, rid=1),
            rec("prefill", 0.342, 0.442, rid=2),
            rec("decode_step", 0.445, 0.535)])
    host = {**STALL, "minus": ["prefill"]}
    assert ps.gap_percentile(host, {}, {}) == pytest.approx(5.0)
    assert ps.gap_percentile(STALL, {}, {}) == pytest.approx(355.0)


def test_a_wait_is_charged_to_other_requests_prefills_only(window):
    p = {"span": "request", "wait": "queue_wait_s", "busy": "prefill"}
    window([
        # rid 1 waited 1.0 s before its claim at 2.0: rid 0's prefill
        # covers 0.6 s of that, its own prefill (after the claim) nothing
        rec("prefill", 1.2, 1.8, rid=0),
        rec("request", 2.0, 9.0, rid=1, queue_wait_s=1.0),
        rec("prefill", 2.0, 2.5, rid=1),
        # rid 2 waited 1.0 s before 3.0: rid 1's prefill covers 0.5 s
        rec("request", 3.0, 9.5, rid=2, queue_wait_s=1.0),
        rec("prefill", 3.0, 3.2, rid=2),
        # a request that did not wait adds nothing
        rec("request", 5.0, 9.7, rid=3, queue_wait_s=0.0)])
    assert ps.wait_overlap_share(p, {}, {}) == pytest.approx(55.0)
    window([rec("request", 5.0, 9.7, rid=3, queue_wait_s=0.0)])
    assert ps.wait_overlap_share(p, {}, {}) is None


def test_pad_share_and_occupancy(window):
    window([rec("prefill", 0, 1, rid=0, prompt_len=20, padded_len=32),
            rec("prefill", 1, 2, rid=1, prompt_len=100, padded_len=128),
            rec("prefill", 2, 3, rid=2, prompt_len=7),      # no bucket known
            rec("decode_step", 3, 4, active=8, slots=32),
            rec("decode_step", 4, 5, active=16, slots=32),
            rec("decode_step", 5, 6, active=12, slots=32)])
    pad = {"span": "prefill", "useful": "prompt_len", "total": "padded_len"}
    assert ps.attr_lost_share(pad, {}, {}) == pytest.approx(25.0)
    occ = {"span": "decode_step", "numerator": "active",
           "denominator": "slots", "q": 50, "scale": 100.0}
    assert ps.attr_ratio_percentile(occ, {}, {}) == pytest.approx(37.5)
    dur = {"span": "decode_step", "q": 50, "scale": 1000.0}
    assert ps.duration_percentile(dur, {}, {}) == pytest.approx(1000.0)


PROGRAM_SPAN_METRICS = [
    "kvcache.decode_step_ms_p50", "scheduler.decode_stall_ms_p95",
    "scheduler.host_gap_ms_p50", "scheduler.queue_wait_in_prefill_share",
    "kvcache.prefill_pad_share", "scheduler.batch_occupancy_p50"]


@pytest.mark.parametrize("metric", PROGRAM_SPAN_METRICS)
def test_an_empty_window_reads_none(window, metric):
    """As on a program that keeps no records: the metric is left out."""
    from benchmarks import run as runmod

    params = params_of(metric)
    window([])
    assert runmod.resolve(params["reader"])(params, {}, {}) is None


def test_the_window_is_the_programs_last_root(monkeypatch):
    """Unpatched: the records come from the program's recorder, the last
    finished root and what lies inside it."""
    from distributed_tensorflow_tpu.observability.trace import recorder

    tr = recorder()
    with tr.span("bench_root"):
        with tr.span("early"):
            pass
    with tr.span("bench_root"):
        with tr.span("late", active=1, slots=4):
            pass
    got = ps.window({"root": "bench_root"})
    assert [r["name"] for r in got] == ["bench_root", "late"]
    assert ps.window({"root": "no_such_root"}) == []
