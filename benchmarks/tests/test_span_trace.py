"""The readers of ``lib/span_trace.py`` on a hand-made ``Reduced``: rounds
of known heads and tails give known medians, a pair with a prefill between
is left out, an offset between the planes moves nothing in ``between_rounds_idle`` and is taken back out by
``idle_inside``, and no event of the name gives None."""

from __future__ import annotations

import json
import statistics
from pathlib import Path

import pytest

from benchmarks.lib import span_trace as st
from benchmarks.lib import xplane
from benchmarks.lib.xplane import Event
from helpers import ROOT

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6                                # the trace counts nanoseconds
ROUND = 10 * MS
# a round on the host, from its decode_step's start: the dispatch, the fetch
DISPATCH, FETCH, STEP_END = (0.01 * MS, 0.5 * MS), (0.51 * MS, 9.8 * MS), 9.85 * MS
# the device starts a round's work HEAD after the decode_step opens and ends
# it TAIL before the fetch returns; 10 us of idle a quarter into the burst
HEADS = [0.30, 0.30, 0.20, 0.40, 0.30, 0.25, 0.30]
TAILS = [2.00, 1.00, 3.00, 2.00, 0.005, 2.50, 1.50]
INNER = 0.01 * MS
PREFILL = (30.5 * MS, 34.5 * MS)        # between round 2 and round 3
PREFILL_OPS = (31.0 * MS, 34.0 * MS)
T0, T1 = -0.5 * MS, 76 * MS             # the extents of what was recorded


def starts() -> list[float]:
    # rounds 3.. come 5 ms later: the prefill lies between
    return [i * ROUND + (5 * MS if i >= 3 else 0.0) for i in range(len(HEADS))]


def burst(i: int, s: float) -> tuple[float, float, float]:
    a, b = s + HEADS[i] * MS, s + FETCH[1] - TAILS[i] * MS
    return a, a + (b - a) / 4, b


def trace(moved: float = 0.0, planes: int = 1):
    host, ops = [], []
    for i, s in enumerate(starts()):
        host += [Event("decode_step", s, STEP_END),
                 Event("step_dispatch", s + DISPATCH[0],
                       DISPATCH[1] - DISPATCH[0]),
                 Event("token_fetch", s + FETCH[0], FETCH[1] - FETCH[0])]
        a, q, b = burst(i, s)
        ops += [Event("%fusion.1", a + moved, q - a),
                Event("%fusion.2", q + INNER + moved, b - q - INNER)]
    host.append(Event("prefill", PREFILL[0], PREFILL[1] - PREFILL[0]))
    ops.append(Event("%fusion.9", PREFILL_OPS[0] + moved,
                     PREFILL_OPS[1] - PREFILL_OPS[0]))
    ops.sort(key=lambda e: e.start_ns)
    always = [Event("%busy", T0, T1 - T0)]
    per = {f"/device:TPU:{k}": (ops if k == 0 else always)
           for k in range(planes)}
    busy = sum(xplane.busy_ns(o, T0, T1) for o in per.values()) / planes
    return xplane.Reduced(window_s=(T1 - T0) / 1e9, busy_s=busy / 1e9,
                          ops=per, host=sorted(host, key=lambda e: e.start_ns),
                          t0=T0, t1=T1)


def params_of(metric: str) -> dict:
    return json.loads(
        (ROOT / "benchmarks/metrics" / f"{metric}.json").read_text())


ROUND_IDLE = params_of("kvcache.round_device_idle_ms_p50")
FETCH_IDLE = params_of("kvcache.token_fetch_idle_ms_p50")
DISPATCH_IDLE = params_of("kvcache.step_dispatch_idle_ms_p50")
PREFILL_SHARE = params_of("kvcache.prefill_device_share")
# the pairs that count: (2, 3) has the prefill between
PAIRS = [(0, 1), (1, 2), (3, 4), (4, 5), (5, 6)]
# between two rounds the device idles: the tail, the host's 0.2 ms between
# fetch and next decode_step's start, the head, and the next burst's 10 us
WHOLE = [TAILS[i] + 0.2 + HEADS[j] + 0.01 for i, j in PAIRS]


def test_the_rounds_idle_is_read_between_midpoints():
    got = st.between_rounds_idle(ROUND_IDLE, {}, {"trace": trace()})
    assert got == pytest.approx(statistics.median(WHOLE))
    worst = st.between_rounds_idle({**ROUND_IDLE, "q": 100}, {},
                                   {"trace": trace()})
    # the pair with the prefill between would have read 4.2 ms of idle
    assert worst == pytest.approx(max(WHOLE)) and worst < 3.5


def test_a_pair_around_a_prefill_is_left_out():
    tr = trace()
    rounds = [(e.start_ns, e.end_ns) for e in st.events(tr, "decode_step")]
    assert len(rounds) == len(HEADS)
    pairs = list(st.consecutive(rounds, st.break_starts(tr, ROUND_IDLE)))
    assert len(pairs) == len(PAIRS)
    assert st.between_rounds_idle({**ROUND_IDLE, "breaks": [], "q": 100}, {},
                                  {"trace": tr}) > 4.0


def test_the_parts_inside_the_fetch_and_the_dispatch(capsys):
    tr = trace()
    fetch = [t + 0.01 for t in TAILS]
    dispatch = [h - 0.01 for h in HEADS]
    assert st.idle_inside(FETCH_IDLE, {}, {"trace": tr}) == pytest.approx(
        statistics.median(fetch))
    assert st.idle_inside(DISPATCH_IDLE, {}, {"trace": tr}) == pytest.approx(
        statistics.median(dispatch))
    # the two parts of one trace share its planes and its offset
    assert capsys.readouterr().err.count("span_trace:") == 1
    plane, = st.planes_of(tr)
    rounds = st.rounds_between(tr, "step_dispatch", "token_fetch")
    assert len(rounds) == len(HEADS)
    assert st.causal_shift(plane, rounds, st.break_starts(tr, FETCH_IDLE)) \
        == (0.0, len(PAIRS))


@pytest.mark.parametrize("moved_ms", [1.0, -1.0])
def test_an_offset_between_the_planes(moved_ms, capsys):
    """Device events 1 ms late end after their round's fetch has returned;
    1 ms early they start before their round was dispatched.  Neither moves
    the idle time between midpoints.  ``idle_inside`` takes out the least
    offset that restores causality: all of it but the tightest round's
    tail (5 us here: found to 1%), or but the tightest head (0.19 ms after
    the dispatch opens: what a real trace leaves unknown)."""
    still, moved = trace(), trace(moved=moved_ms * MS)
    assert st.between_rounds_idle(ROUND_IDLE, {}, {"trace": moved}) == \
        st.between_rounds_idle(ROUND_IDLE, {}, {"trace": still})
    plane, = st.planes_of(moved)
    rounds = st.rounds_between(moved, "step_dispatch", "token_fetch")
    shift, pairs = st.causal_shift(plane, rounds,
                                   st.break_starts(moved, FETCH_IDLE))
    assert pairs == len(PAIRS)
    if moved_ms > 0:
        assert shift / MS == pytest.approx(-1.0, rel=0.01)
        left = 0.005                    # of the tail, moved into the head
    else:
        head = min(HEADS[j] for _, j in PAIRS) - 0.01
        assert shift / MS == pytest.approx(1.0 - head)
        left = -head
    got = st.idle_inside(FETCH_IDLE, {}, {"trace": moved})
    assert f"shifted by {shift / MS:+.4f} ms" in capsys.readouterr().err
    assert got == pytest.approx(
        st.idle_inside(FETCH_IDLE, {}, {"trace": still}) - left, abs=1e-6)
    assert st.idle_inside(DISPATCH_IDLE, {}, {"trace": moved}) == \
        pytest.approx(st.idle_inside(DISPATCH_IDLE, {}, {"trace": still})
                      + left, abs=1e-6)


def test_the_prefills_share_of_the_busy_time():
    tr = trace()
    want = 100.0 * (PREFILL_OPS[1] - PREFILL_OPS[0]) / 1e9 / tr.busy_s
    assert st.busy_inside_share(PREFILL_SHARE, {}, {"trace": tr}) == \
        pytest.approx(want)
    assert 4.0 < want < 6.0
    # a span the device did nothing in: nothing to report, not 0
    idle = {"span": "nap"}
    tr.host.append(Event("nap", 75.2 * MS, 0.5 * MS))
    assert st.busy_inside_share(idle, {}, {"trace": tr}) is None


def test_several_chips_read_their_mean():
    one = st.between_rounds_idle(ROUND_IDLE, {}, {"trace": trace()})
    two = st.between_rounds_idle(ROUND_IDLE, {}, {"trace": trace(planes=2)})
    assert two == pytest.approx(one / 2)    # the second chip is never idle


@pytest.mark.parametrize("reader, params", [
    (st.between_rounds_idle, ROUND_IDLE), (st.idle_inside, FETCH_IDLE),
    (st.idle_inside, DISPATCH_IDLE), (st.busy_inside_share, PREFILL_SHARE)])
def test_nothing_to_read_gives_none(reader, params):
    tr = trace()
    tr.host = [Event("np.asarray(jax.Array)", e.start_ns, e.dur_ns)
               for e in tr.host]            # an older program's host line
    assert reader(params, {}, {"trace": tr}) is None
    assert reader(params, {}, {"trace": None}) is None      # untraced


def test_the_recorded_trace_has_the_device_a_millisecond_early():
    """``record_trace.py``: three rounds under a ``dispatch`` span on a
    v5e.  The device's operations lie before the dispatch that queued
    them; the least offset that puts them after it is 1.08 ms."""
    tr = xplane.reduce(xplane.load(str(DATA / "v5e_small.xplane.pb")))
    rounds = [(e.start_ns, e.end_ns) for e in st.events(tr, "dispatch")]
    assert len(rounds) == 3
    plane, = st.planes_of(tr)
    first_op = plane.starts[0]
    assert first_op < rounds[0][0]
    shift, pairs = st.causal_shift(plane, rounds, [])
    assert pairs == 2 and 1.0 < shift / MS < 1.2
    assert plane.idle(tr.t0, tr.t1) == pytest.approx(
        sum(b - a for a, b in xplane.gaps(next(iter(tr.ops.values())),
                                          tr.t0, tr.t1)))
