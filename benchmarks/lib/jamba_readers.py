"""Readers for the selective-scan / multi-query-attention hybrid decoder.

As ``lib/hybrid_ssm_readers.py``: the records inside the last finished
``root`` span, the reduced trace under ``ctx["trace"]``; which span,
attribute and pattern a metric reads is in its own file.  A program that
leaves nothing to read (an older one, a model without the kernel, an
untraced run) gives None from every reader, never 0."""

from __future__ import annotations

import re

from benchmarks.lib import jamba_costs, program_spans, stats


def _rounds(params, obs, ctx):
    """``(records, bytes by part)`` of the window's decode rounds, or None
    where the window or the driver left nothing to read."""
    records = [r for r in program_spans.named(program_spans.window(params),
                                              params["span"])
               if r["attrs"].get("active") is not None]
    context, rows, state = (obs.get(params[k]) for k in (
        "context", "token_bytes", "state_bytes"))
    if not records or None in (context, rows, state) \
            or "mamba_d_state" not in ctx["config"]:
        return None
    return records, [jamba_costs.decode_round_bytes(
        ctx["config"], r["attrs"]["active"], context, state, rows)
        for r in records]


def decode_round_mbu(params, obs, ctx):
    """The decode round's share of its memory roofline: the bytes the
    median round has to move (the cost file's: every weight once, the state
    of its ``active`` slots read and written, their attention rows at the
    window's mean context) over the median duration of the ``span`` records
    and the chip's bandwidth."""
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


def kernel_roofline_by_event(params, obs, ctx):
    """The least time the chip could take for the kernel's calls of the
    traced slice over the time their events took.  An event is named by its
    whole HLO instruction, so each call's own sizes are read off its name
    (``pattern``'s groups ``batch``, ``length``, ``groups`` of ``lanes``
    channels); the state's size is the configuration's.  The kernel has no
    matrix product: the least time is its operands' and results' bytes over
    the chip's bandwidth."""
    trace = ctx.get("trace")
    state = ctx["config"].get(params["state"])
    if trace is None or state is None:
        return None
    rx = re.compile(params["pattern"])
    lanes = int(params["lanes"])
    least = took = 0.0
    for evs in trace.ops.values():
        for e in evs:
            m = rx.search(e.name)
            if m is None:
                continue
            cost = jamba_costs.selective_scan_call(
                int(m["batch"]), int(m["length"]), int(m["groups"]) * lanes,
                int(state))
            least += cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
            took += e.dur_ns / 1e9
    return 100.0 * least / took if took else None
