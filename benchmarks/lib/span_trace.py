"""Readers that join the program's span events with the device's operations.

A lexical span of the program enters a ``jax.profiler.TraceAnnotation`` of
its own name, so in a traced run its events lie on the profiler's host line
beside the device's operations.  These readers take ``ctx["trace"]``
(``xplane.Reduced``: the operations of each device plane, the host line's
events, the slice's ``t0`` and ``t1``) and nothing else.  Which span a metric
reads is in the metric's own file; this module names none.

The slice's ``t0`` and ``t1`` are the extents of what the profiler recorded
(``xplane.reduce``), so every span event it holds lies wholly inside.  With
several chips a reading is the mean over the device planes, as
``Reduced.pattern_seconds`` takes it.  A trace that holds no event of the span's name (an older program,
an untraced run) gives None from every reader, never 0.

The two planes' clocks need not agree: in the recorded trace of
``benchmarks/tests/data`` the device's operations lie 1.08 ms BEFORE the
host's dispatch that queued them.
``between_rounds_idle`` is built so that this does not move it;
``idle_inside`` corrects for it by causality (``causal_shift``)."""

from __future__ import annotations

import bisect
import itertools
import math
import sys
from statistics import fmean

from benchmarks.lib import xplane
from benchmarks.lib.program_spans import percentile_of


def events(trace, names) -> list[xplane.Event]:
    """The host line's events of the given name or names, in start order."""
    names = {names} if isinstance(names, str) else set(names)
    return [e for e in trace.host if e.name in names]


class Busy:
    """One device plane's busy union over the slice, with running totals:
    the busy and the idle time of any window are two look-ups."""

    def __init__(self, ops, t0: float, t1: float):
        union = xplane.merged(ops, t0, t1)
        self.starts = [a for a, _ in union]
        self.ends = [b for _, b in union]
        self.total = [0.0, *itertools.accumulate(b - a for a, b in union)]

    def _overlapping(self, lo: float, hi: float) -> tuple[int, int]:
        return (bisect.bisect_right(self.ends, lo),
                bisect.bisect_left(self.starts, hi))

    def busy(self, lo: float, hi: float) -> float:
        i, j = self._overlapping(lo, hi)
        if i >= j:
            return 0.0
        return (self.total[j] - self.total[i]
                - max(0.0, lo - self.starts[i])
                - max(0.0, self.ends[j - 1] - hi))

    def idle(self, lo: float, hi: float) -> float:
        return max(0.0, hi - lo) - self.busy(lo, hi)

    def largest_gap(self, lo: float, hi: float) -> tuple[float, float]:
        """The longest idle interval of ``[lo, hi]`` (``xplane.gaps``)."""
        i, j = self._overlapping(lo, hi)
        best, cur = (lo, lo), lo
        for k in range(i, j):
            a = max(self.starts[k], lo)
            if a - cur > best[1] - best[0]:
                best = (cur, a)
            cur = min(self.ends[k], hi)
        return (cur, hi) if hi - cur > best[1] - best[0] else best


_MEMO: dict = {}        # of the trace last read: its planes, its shifts


def memo(trace, key, make):
    """``make()`` once a trace: a run's metrics read the same slice."""
    if _MEMO.get("trace") is not trace:
        _MEMO.clear()
        _MEMO["trace"] = trace
    if key not in _MEMO:
        _MEMO[key] = make()
    return _MEMO[key]


def planes_of(trace) -> list[Busy]:
    return memo(trace, "planes", lambda: [
        Busy(ops, trace.t0, trace.t1) for ops in trace.ops.values()])


def midpoint(interval: tuple[float, float]) -> float:
    return (interval[0] + interval[1]) / 2.0


def consecutive(rounds: list[tuple[float, float]], breaks: list[float]):
    """Pairs of neighbouring rounds with no break starting between their
    starts (the loop did something else there: that is no round's
    boundary)."""
    for a, b in zip(rounds, rounds[1:]):
        at = bisect.bisect_left(breaks, a[0])
        if at == len(breaks) or breaks[at] >= b[0]:
            yield a, b


def break_starts(trace, params) -> list[float]:
    return [e.start_ns for e in events(trace, params.get("breaks", []))]


def between_rounds_idle(params, obs, ctx):
    """For each pair of consecutive ``span`` events with no event of
    ``breaks`` starting between them: the device's idle time between the
    two events' midpoints; a percentile ``q`` of those, times ``scale``.

    The interval holds exactly one boundary between rounds and the idle
    time inside it is read off the device's clock alone: an offset between
    the planes of up to half a round does not move it."""
    trace = ctx.get("trace")
    if trace is None:
        return None
    rounds = [(e.start_ns, e.end_ns) for e in events(trace, params["span"])]
    planes = planes_of(trace)
    return percentile_of(
        [fmean(p.idle(midpoint(a), midpoint(b)) for p in planes)
         for a, b in consecutive(rounds, break_starts(trace, params))],
        params)


def rounds_between(trace, opens: str, closes: str
                   ) -> list[tuple[float, float]]:
    """A round on the host's clock: from the start of an ``opens`` event
    to the end of the ``closes`` event that follows it before the next
    ``opens`` event."""
    first, last = events(trace, opens), events(trace, closes)
    starts = [e.start_ns for e in last]
    out = []
    for o, nxt in itertools.zip_longest(first, first[1:]):
        at = bisect.bisect_left(starts, o.end_ns)
        if at < len(last) and (nxt is None
                               or last[at].start_ns < nxt.start_ns):
            out.append((o.start_ns, last[at].end_ns))
    return out


def causal_shift(plane: Busy, rounds, breaks) -> tuple[float, int]:
    """The least offset (ns, added to the device's events) under which no
    operation of a round starts before the round opens on the host nor ends
    after it closes, and the pairs of rounds it was read from.

    The boundary between two consecutive rounds is the device's longest
    idle interval between their midpoints: it has to end no sooner than the
    second round opens and to start no later than the first one closes.
    Each pair bounds the offset from both sides; 0 where 0 meets every
    bound, else the bound nearest to it; where the pairs contradict one
    another (jitter), the middle of the two tightest bounds."""
    lo, hi, pairs = -math.inf, math.inf, 0
    for a, b in consecutive(rounds, breaks):
        m0, m1 = midpoint(a), midpoint(b)
        g0, g1 = plane.largest_gap(m0, m1)
        pairs += 1
        if g1 < m1:         # else the device was still idle at the midpoint
            lo = max(lo, b[0] - g1)
        if g0 > m0:
            hi = min(hi, a[1] - g0)
    if lo > hi:
        return (lo + hi) / 2.0, pairs
    return min(max(0.0, lo), hi), pairs


def shifts_of(trace, params) -> list[float]:
    """``causal_shift`` of every plane over the rounds ``opens`` ..
    ``closes``; the offsets go to standard error as one line a trace."""
    def make():
        rounds = rounds_between(trace, params["opens"], params["closes"])
        found = [causal_shift(p, rounds, break_starts(trace, params))
                 for p in planes_of(trace)]
        print(f"span_trace: device events shifted by "
              f"{', '.join(f'{s / 1e6:+.4f}' for s, _ in found)} ms "
              f"({len(rounds)} rounds, {found[0][1]} pairs of them)",
              file=sys.stderr, flush=True)
        return [s for s, _ in found]
    return memo(trace, ("shifts", params["opens"], params["closes"],
                        tuple(params.get("breaks", []))), make)


def idle_inside(params, obs, ctx):
    """A percentile of the device's idle time inside each ``span`` event.

    Before it cuts, it restores causality between the planes: the rounds
    are ``opens`` .. ``closes`` on the host line, and the device's events
    are shifted by ``causal_shift``.  The offset applied goes to standard
    error; it changes nothing in ``obs`` or the result."""
    trace = ctx.get("trace")
    spans = events(trace, params["span"]) if trace is not None else []
    if not spans:
        return None
    # the device's events later by s: the host's window earlier by s
    return percentile_of(
        [fmean(p.idle(e.start_ns - s, e.end_ns - s)
              for p, s in zip(planes_of(trace), shifts_of(trace, params)))
         for e in spans], params)


def busy_inside_share(params, obs, ctx):
    """The device's busy time inside the union of the ``span`` events over
    the slice's busy time, in %; None when it is 0."""
    trace = ctx.get("trace")
    spans = events(trace, params["span"]) if trace is not None else []
    if not spans or not trace.busy_s:
        return None
    union = xplane.merged(spans, trace.t0, trace.t1)
    inside = fmean(sum(p.busy(a, b) for a, b in union)
                  for p in planes_of(trace))
    return 100.0 * inside / 1e9 / trace.busy_s if inside else None
