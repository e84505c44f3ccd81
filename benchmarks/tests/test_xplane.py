"""The trace reducer: its arithmetic on hand-made events, and the whole
reduction on the small trace recorded on a v5e (``record_trace.py``)."""

from __future__ import annotations

from pathlib import Path

import pytest

from benchmarks.lib import xplane
from benchmarks.lib.xplane import Event

DATA = Path(__file__).resolve().parent / "data"


def ev(name, start, dur):
    return Event(name, float(start), float(dur))


OPS = [ev("while", 0, 100), ev("fusion.1", 10, 30), ev("flash_fwd", 50, 40),
       ev("fusion.1", 150, 20), ev("copy", 170, 20), ev("flash_fwd", 300, 50)]
HOST = [ev("fit", 0, 400), ev("materialize", 100, 45), ev("dispatch", 190, 105),
        ev("nap", 195, 100)]


def test_busy_is_the_union_of_intervals():
    # [0,100] + [150,190] + [300,350] inside [0, 400]
    assert xplane.merged(OPS, 0, 400) == [(0, 100), (150, 190), (300, 350)]
    assert xplane.busy_ns(OPS, 0, 400) == 190
    assert xplane.busy_ns(OPS, 50, 320) == 50 + 40 + 20     # clipped


def test_gaps_are_the_rest_of_the_window():
    assert xplane.gaps(OPS, 0, 400) == [(100, 150), (190, 300), (350, 400)]
    assert xplane.gaps([], 0, 10) == [(0, 10)]


def test_self_time_takes_nested_children_out():
    by_name = {}
    for e, s in xplane.self_times(OPS):
        by_name[e.name] = by_name.get(e.name, 0) + s
    assert by_name == {"while": 30, "fusion.1": 50, "flash_fwd": 90,
                       "copy": 20}
    assert xplane.top_ops(OPS, 2) == [("flash_fwd", 90e-9),
                                      ("fusion", 50e-9)]     # summed by short name
    long = ('%fusion.5493 = (f32[8,1024]{1,0}) fusion(bf16[8] %p.1), '
            'kind=kOutput, calls=%fused_computation.5417')
    assert xplane.short_name(long) == "fusion[kOutput]"
    assert xplane.short_name(
        '%Attn_0.756 = bf16[8] custom-call(bf16[8] %b), '
        'custom_call_target="tpu_custom_call"') == "Attn_0[tpu_custom_call]"
    assert xplane.short_name("%copy-done.1 = bf16[8] copy-done(%c)") == \
        "copy-done"


def test_time_by_pattern_sums_matching_events():
    assert xplane.time_by_pattern(OPS, r"^flash") == (90.0, 2)
    assert xplane.time_by_pattern(OPS, r"nothing") == (0.0, 0)


def test_a_gap_is_named_by_the_innermost_host_span_around_it():
    assert xplane.name_gap((100, 150), HOST) == "materialize"   # 45 of 50
    # fit, dispatch and nap all cover over half: the shortest is innermost
    assert xplane.name_gap((190, 300), HOST) == "nap"
    # nothing covers half of it: the one that covers most
    assert xplane.name_gap((380, 480), HOST) == "fit"
    assert xplane.name_gap((500, 600), HOST) == "unattributed"
    top = xplane.longest_gaps(OPS, HOST, 0, 400)
    assert top[0] == ("nap", 110e-9) and len(top) == 3


def test_reduce_averages_over_the_chips():
    trace = xplane.Trace({
        "/device:TPU:0": {"XLA Ops": OPS, "Steps": [ev("step", 0, 400)]},
        "/device:TPU:1": {"XLA Ops": [ev("fusion.1", 0, 100)]},
        "/host:CPU": {"python": HOST, "pool": [ev("ThreadpoolListener::x", 0, 9)]},
    })
    red = xplane.reduce(trace)
    assert (red.t0, red.t1) == (0, 400) and red.window_s == 400e-9
    assert red.busy_s == pytest.approx((190 + 100) / 2 * 1e-9)
    assert red.idle_share == pytest.approx(1 - 145 / 400)
    assert red.pattern_seconds(r"^fusion") == (pytest.approx(75e-9), 2)
    assert [name for name, _ in red.breakdown()["device_ops"]][0] == "flash_fwd"
    with pytest.raises(ValueError, match="no device plane"):
        xplane.reduce(xplane.Trace({"/host:CPU": {"python": HOST}}))


# ---- the recorded trace: three rounds of a four-matmul scan on one v5e,
# each under a `dispatch` span and followed by a 5 ms `host_pause` span

@pytest.fixture(scope="module")
def recorded():
    return xplane.load(str(DATA / "v5e_small.xplane.pb"))


def test_recorded_trace_planes_and_lines(recorded):
    assert recorded.device_planes() == ["/device:TPU:0"]
    assert len(recorded.ops("/device:TPU:0")) == 39      # 13 a round
    names = {e.name for e in recorded.host_events()}
    assert {"dispatch", "host_pause", "PjitFunction(work)"} <= names
    assert not any(n.startswith("ThreadpoolListener") for n in names)


def test_recorded_trace_reduces_to_the_numbers_read_by_hand(recorded):
    red = xplane.reduce(recorded)
    # three runs of the program of about 55 us each (`XLA Modules` says
    # 54.8, 55.2, 55.1), 6.6 ms apart, in a window of 20.9 ms
    assert red.window_s == pytest.approx(0.02086, rel=1e-3)
    assert red.busy_s == pytest.approx(165.1e-6, rel=1e-3)
    assert 100 * red.idle_share == pytest.approx(99.21, abs=0.01)
    # the matmul fusion: four a round, 11.6 us each; the while around them
    seconds, count = red.pattern_seconds(r"^%fusion\.9 = ")
    assert count == 12 and seconds == pytest.approx(139.2e-6, rel=1e-3)
    assert red.pattern_seconds(r"^%while")[1] == 3
    assert red.pattern_seconds(r"^%no_such_op") == (0.0, 0)
    # the time inside matching events is a union: the fusions lie inside
    # the whiles, and together they cover what the whiles cover alone
    inside = red.pattern_busy_seconds(r"^%while")
    assert seconds < inside <= red.busy_s
    assert red.pattern_busy_seconds(r"^%(while|fusion)") == inside
    assert red.pattern_busy_seconds(r"^%no_such_op") == 0.0
    # 8.6 GFLOP a round against 197 TFLOP/s: the matmuls run at 94% of peak
    assert 4 * 2 * 1024 ** 3 * 3 / seconds / 197e12 == pytest.approx(0.94, abs=0.01)


def test_recorded_trace_breakdown_names_ops_and_gaps(recorded):
    out = xplane.reduce(recorded).breakdown()
    assert out["device_ops"][0][0] == "fusion[kOutput]"
    assert out["device_ops"][0][1] == pytest.approx(139.2e-6, rel=1e-3)
    # the while's own time is what its body leaves: next to nothing
    assert dict(map(tuple, out["device_ops"]))["while"] < 1e-6
    # the idle time lies in the host's pauses (the device's clock runs
    # about 1 ms ahead of the host's in this trace, so the first pause
    # takes the time before it too)
    assert out["idle_gaps"][0][0] == "host_pause"
    assert out["idle_gaps"][0][1] == pytest.approx(0.0207, rel=1e-2)
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
