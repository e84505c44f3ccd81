"""Readers over the program's own span records.

The program keeps a record of every span it closes (name, start, end, id,
parent, rid, attributes) in memory, on the host's clock, traced run or not.
These readers take the records of one window when the run has ended: the
last finished span named by the metric's ``root`` and everything inside its
interval, so warm-up is left out.  Which spans and which attributes a
metric reads is in the metric's own file; this module names none.

A program that keeps no such records (an older one) gives an empty window,
and every reader then returns None: nothing to read, never 0 for a share.
Besides the drivers this is the one module of the benchmark that imports
the program."""

from __future__ import annotations

import bisect

from benchmarks.lib import stats, xplane


def window(params) -> list[dict]:
    """The records inside the last finished ``params["root"]`` span."""
    try:
        from distributed_tensorflow_tpu.observability.trace import recorder
    except ImportError:         # the program has no recorder: nothing to read
        return []
    return recorder().records(root=params["root"])


def named(records: list[dict], names) -> list[dict]:
    """The records of the given name or names, in start order."""
    names = {names} if isinstance(names, str) else set(names)
    return sorted((r for r in records if r["name"] in names),
                  key=lambda r: r["start"])


def covered(records: list[dict], lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` inside the union of the records' intervals
    (the trace reducer's union, on the host's clock in seconds)."""
    return xplane.busy_ns([xplane.Event(r["name"], r["start"],
                                        r["end"] - r["start"])
                           for r in records], lo, hi)


def percentile_of(values: list[float], params) -> float | None:
    if not values:
        return None
    return stats.percentile(values, float(params["q"])) \
        * float(params.get("scale", 1.0))


def duration_percentile(params, obs, ctx):
    """A percentile of the durations of the spans named ``span``."""
    return percentile_of([r["end"] - r["start"] for r in
                          named(window(params), params["span"])], params)


def gap_percentile(params, obs, ctx):
    """A percentile of the gaps between consecutive ``span`` records: the
    next one's start minus the previous one's end.  A pair with a span of
    ``breaks`` starting between them is left out (the loop had nothing to
    do there: that is no stall); the time that spans of ``minus`` cover
    inside a gap is taken off it."""
    records = window(params)
    steps = named(records, params["span"])
    breaks = [r["start"] for r in named(records, params.get("breaks", []))]
    minus = named(records, params.get("minus", []))
    gaps = []
    for prev, nxt in zip(steps, steps[1:]):
        lo, hi = prev["end"], nxt["start"]
        at = bisect.bisect_left(breaks, lo)
        if at < len(breaks) and breaks[at] < hi:
            continue
        gaps.append(max(0.0, hi - lo - covered(minus, lo, hi)))
    return percentile_of(gaps, params)


def wait_overlap_share(params, obs, ctx):
    """Of the time the ``span`` records waited before they started (their
    attribute ``wait``, in seconds, ending at the record's start): the
    share, in %, during which a ``busy`` span of another ``rid`` ran."""
    records = window(params)
    busy = named(records, params["busy"])
    waited = overlapped = 0.0
    for r in named(records, params["span"]):
        wait = r["attrs"].get(params["wait"])
        if not wait or wait < 0:
            continue
        others = [b for b in busy if b["rid"] != r["rid"]]
        overlapped += covered(others, r["start"] - wait, r["start"])
        waited += wait
    return 100.0 * overlapped / waited if waited else None


def attr_lost_share(params, obs, ctx):
    """``100 x (1 - sum useful / sum total)`` over the ``span`` records
    that carry both attributes."""
    useful = total = 0.0
    for r in named(window(params), params["span"]):
        u, t = r["attrs"].get(params["useful"]), r["attrs"].get(
            params["total"])
        if u is None or t is None:
            continue
        useful, total = useful + u, total + t
    return 100.0 * (1.0 - useful / total) if total else None


def attr_ratio_percentile(params, obs, ctx):
    """A percentile of ``numerator / denominator`` (two attributes) over
    the ``span`` records that carry both."""
    ratios = []
    for r in named(window(params), params["span"]):
        n, d = r["attrs"].get(params["numerator"]), r["attrs"].get(
            params["denominator"])
        if n is not None and d:
            ratios.append(n / d)
    return percentile_of(ratios, params)
