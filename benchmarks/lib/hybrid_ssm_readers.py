"""Readers for a model that keeps per-slot state beside its rows.

As ``lib/moe_readers.py``: the records inside the last finished ``root``
span; which span and attribute a metric reads is in its own file.  A
program that leaves no such attribute or counter (an older one, or a model
without state) gives nothing to read and every reader returns None."""

from __future__ import annotations

from benchmarks.lib import hybrid_ssm_costs, program_spans, stats


def _rounds(params, obs, ctx):
    """``(records, bytes by part)`` of the window's decode rounds, or
    None where the window or the driver left nothing to read."""
    records = [r for r in program_spans.named(program_spans.window(params),
                                              params["span"])
               if r["attrs"].get(params["experts"]) is not None]
    context, rows, state = (obs.get(params[k]) for k in (
        "context", "token_bytes", "state_bytes"))
    if not records or None in (context, rows, state) \
            or "hybrid_override_pattern" not in ctx["config"]:
        return None
    return records, [hybrid_ssm_costs.decode_round_bytes(
        ctx["config"], r["attrs"]["active"], r["attrs"][params["experts"]],
        context, state, rows) for r in records]


def decode_round_mbu(params, obs, ctx):
    """The decode round's share of its memory roofline: the bytes the
    median round has to move (the cost file's: weights outside the experts,
    the held experts its ``experts`` attribute says it touched, the state
    of its ``active`` slots read and written, their attention rows at the
    window's mean context) over the median duration of the ``span``
    records and the chip's bandwidth."""
    got = _rounds(params, obs, ctx)
    if got is None:
        return None
    records, parts = got
    seconds = stats.percentile([r["end"] - r["start"] for r in records], 50)
    if not seconds:
        return None
    needed = stats.percentile([sum(p.values()) for p in parts], 50)
    return 100.0 * needed / seconds / ctx["peaks"]["hbm_bytes_per_s"]


def state_share_of_round_bytes(params, obs, ctx):
    """Of the bytes the median round has to move, the share that is the
    state's read and write: what a change to the state's dtype or layout
    moves."""
    got = _rounds(params, obs, ctx)
    if got is None:
        return None
    shares = [p["state"] / sum(p.values()) for p in got[1]]
    return 100.0 * stats.percentile(shares, 50)
