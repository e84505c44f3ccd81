"""Readers for the sparse-attention / softmax-expert decoder.

As ``lib/jamba_readers.py``: the records inside the last finished ``root``
span, the reduced trace under ``ctx["trace"]``; which span, attribute and
pattern a metric reads is in its own file.  A program that leaves nothing to
read (an older one, a model without an indexer, an untraced run) gives None
from every reader, never 0."""

from __future__ import annotations

import re
from statistics import fmean

from benchmarks.lib import keye_costs, program_spans, stats


def _least(cost: dict, peaks: dict) -> float:
    return max(cost["flops"] / peaks["bf16_flops_per_s"],
               cost["bytes"] / peaks["hbm_bytes_per_s"])


def _events(trace, pattern: str):
    rx = re.compile(pattern)
    for evs in trace.ops.values():
        for e in evs:
            m = rx.search(e.name)
            if m is not None:
                yield e, m


def prefill_roofline(params, obs, ctx):
    """The least time the chip could take for the prefill kernel's calls of
    the traced slice over the time their events took.  An event is named by
    its whole HLO instruction, so each call's length is read off its result
    (``pattern``'s group ``length``)."""
    trace = ctx.get("trace")
    if trace is None or "sa_config" not in ctx["config"]:
        return None
    least = took = 0.0
    for e, m in _events(trace, params["pattern"]):
        least += _least(keye_costs.prefill_call(ctx["config"],
                                                int(m["length"])),
                        ctx["peaks"])
        took += e.dur_ns / 1e9
    return 100.0 * least / took if took else None


def _rounds(params):
    return [r for r in program_spans.named(program_spans.window(params),
                                           params["span"])
            if r["attrs"].get(params["index_rows"]) is not None]


def step_roofline(params, obs, ctx):
    """The step's kernels against their roofline: what the window's mean
    decode round needs of them (the ``span`` records' index and attended
    rows, ``step_kernels``) times the rounds the traced slice holds (its
    events of ``rounds_pattern``, one a layer), over the time the events of
    ``pattern`` took."""
    trace = ctx.get("trace")
    records = _rounds(params)
    if trace is None or not records or "sa_config" not in ctx["config"]:
        return None
    layers = int(ctx["config"]["num_hidden_layers"])
    slots = int(ctx["cell"]["job"]["slots"])
    least = fmean(_least(keye_costs.step_kernels(
        ctx["config"], r["attrs"][params["index_rows"]],
        r["attrs"][params["attended_rows"]], slots), ctx["peaks"])
        for r in records)
    took = sum(e.dur_ns for e, _ in _events(trace, params["pattern"])) / 1e9
    rounds = sum(1 for _ in _events(trace, params["rounds_pattern"])) \
        / float(layers * len(trace.ops))
    return 100.0 * least * rounds / took if took and rounds else None


def decode_step_mbu(params, obs, ctx):
    """The decode round's share of its memory roofline: the bytes the
    median round has to move (``decode_round_bytes``: the weights outside
    the experts, the experts it touched, the index rows it scored and the
    K/V rows it attended, all from its span) over the median duration of
    the ``span`` records and the chip's bandwidth."""
    records = [r for r in _rounds(params)
               if r["attrs"].get(params["experts"]) is not None]
    if not records or "sa_config" not in ctx["config"]:
        return None
    needed = [sum(keye_costs.decode_round_bytes(
        ctx["config"], r["attrs"][params["experts"]],
        r["attrs"][params["index_rows"]],
        r["attrs"][params["attended_rows"]]).values()) for r in records]
    seconds = stats.percentile([r["end"] - r["start"] for r in records], 50)
    if not seconds:
        return None
    return 100.0 * stats.percentile(needed, 50) / seconds \
        / ctx["peaks"]["hbm_bytes_per_s"]
