"""Readers over the span attributes of a model with routed experts.

As ``lib/program_spans.py``: the records inside the last finished ``root``
span; which span and attribute a metric reads is in its own file.  A
program that leaves no such attribute (an older one, or a model without
experts) gives nothing to read and every reader returns None."""

from __future__ import annotations

from benchmarks.lib import mla_moe_costs, program_spans, stats


def attr_percentile(params, obs, ctx):
    """A percentile of one attribute over the ``span`` records that carry
    it."""
    values = [r["attrs"][params["attr"]]
              for r in program_spans.named(program_spans.window(params),
                                           params["span"])
              if r["attrs"].get(params["attr"]) is not None]
    return program_spans.percentile_of(values, params)


def decode_round_mbu(params, obs, ctx):
    """The decode round's share of its memory roofline: the bytes the
    median round has to read (the cost file's, with the experts its
    ``experts`` attribute says it touched and ``active`` streams of the
    window's mean context behind them) over the median duration of the
    ``span`` records and the chip's bandwidth."""
    records = [r for r in program_spans.named(program_spans.window(params),
                                              params["span"])
               if r["attrs"].get(params["experts"]) is not None]
    context, per_token = obs.get(params["context"]), obs.get(
        params["token_bytes"])
    if not records or context is None or per_token is None:
        return None
    needed = [mla_moe_costs.decode_round_bytes(
        ctx["config"], r["attrs"][params["experts"]],
        r["attrs"]["active"] * context, per_token) for r in records]
    seconds = stats.percentile([r["end"] - r["start"] for r in records], 50)
    if not seconds:
        return None
    return 100.0 * stats.percentile(needed, 50) / seconds \
        / ctx["peaks"]["hbm_bytes_per_s"]
