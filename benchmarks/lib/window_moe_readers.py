"""Readers for a model that keeps rings beside its full-length rows.

As ``lib/moe_readers.py``: the records inside the last finished ``root``
span; which span and attribute a metric reads is in its own file.  A
program that leaves no such attribute or counter (an older one, or a model
without rings) gives nothing to read and every reader returns None."""

from __future__ import annotations

from benchmarks.lib import program_spans, stats, window_moe_costs


def _rounds(params, obs, ctx):
    """``(records, bytes by part)`` of the window's decode rounds, or None
    where the window or the driver left nothing to read.  A round's
    ``past`` streams have ``ring`` positions behind them in the window
    layers and the others the window's mean below it; all have the
    window's mean context behind them in the full layers."""
    records = [r for r in program_spans.named(program_spans.window(params),
                                              params["span"])
               if None not in (r["attrs"].get(params["experts"]),
                               r["attrs"].get(params["past"]))]
    context, below, rows, ring_bytes, ring = (obs.get(params[k]) for k in (
        "context", "context_below", "token_bytes", "ring_bytes", "ring"))
    if not records or None in (context, below, rows, ring_bytes, ring) \
            or "sliding_window" not in ctx["config"]:
        return None
    parts = []
    for r in records:
        active, past = r["attrs"]["active"], r["attrs"][params["past"]]
        parts.append(window_moe_costs.decode_round_bytes(
            ctx["config"], r["attrs"][params["experts"]], active * context,
            past * ring + (active - past) * below, rows, ring_bytes / ring))
    return records, parts


def decode_round_mbu(params, obs, ctx):
    """The decode round's share of its memory roofline: the bytes the
    median round has to move (the cost file's: weights outside the experts,
    the held experts its ``experts`` attribute says it touched, the
    full-length rows and the ring rows behind its streams) over the median
    duration of the ``span`` records and the chip's bandwidth."""
    got = _rounds(params, obs, ctx)
    if got is None:
        return None
    records, parts = got
    seconds = stats.percentile([r["end"] - r["start"] for r in records], 50)
    if not seconds:
        return None
    needed = stats.percentile([sum(p.values()) for p in parts], 50)
    return 100.0 * needed / seconds / ctx["peaks"]["hbm_bytes_per_s"]


def ring_share_of_round_bytes(params, obs, ctx):
    """Of the bytes the median round has to move, the share that is ring
    rows: what a change to the rings' dtype, layout or read moves."""
    got = _rounds(params, obs, ctx)
    if got is None:
        return None
    shares = [p["rings"] / sum(p.values()) for p in got[1]]
    return 100.0 * stats.percentile(shares, 50)
