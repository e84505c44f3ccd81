"""The readers that ``metrics/*.json`` name.

A reader is ``fn(params, obs, ctx) -> float | None``: ``params`` is the
metric's own file, ``obs`` what the driver observed in the window (plain
counts, clock readings and sample lists under the driver's own keys), and
``ctx`` carries the configuration, the cell, the chip's peaks and, in a
traced run, the reduced trace under ``ctx["trace"]``.  A reader that finds
nothing to read returns None and the metric is left out of the line; it
never returns 0 for a share.  A later PR that needs another reduction adds
a module of its own under ``benchmarks/`` and names it in its metric's
file (``"reader": "package.module:function"``)."""

from __future__ import annotations

from benchmarks.lib import costs, stats


def value(params, obs, ctx):
    """One observation as it is, times ``scale``."""
    v = obs.get(params["key"])
    return None if v is None else float(v) * float(params.get("scale", 1.0))


def rate(params, obs, ctx):
    """``numerator / denominator`` over the whole window, optionally per
    chip."""
    num, den = obs.get(params["numerator"]), obs.get(params["denominator"])
    if num is None or not den:
        return None
    chips = ctx["chips"] if params.get("per_chip") else 1
    return float(num) / float(den) / chips


def percentile(params, obs, ctx):
    """A percentile over every sample of a list, times ``scale``."""
    samples = obs.get(params["samples"])
    if not samples:
        return None
    return stats.percentile(samples, float(params["q"])) \
        * float(params.get("scale", 1.0))


def mfu(params, obs, ctx):
    """Model FLOPs of the window over its length, chips and the peak."""
    flops, seconds = obs.get(params["flops"]), obs.get(params["seconds"])
    if not flops or not seconds:
        return None
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * float(flops) / float(seconds) / peak


def idle_share(params, obs, ctx):
    trace = ctx.get("trace")
    return None if trace is None else 100.0 * trace.idle_share


def event_share(params, obs, ctx):
    """Share of the device's busy time inside the events that match."""
    trace = ctx.get("trace")
    if trace is None or not trace.busy_s:
        return None
    seconds = trace.pattern_busy_seconds(params["pattern"])
    return 100.0 * seconds / trace.busy_s if seconds else None


def kernel_roofline(params, obs, ctx):
    """The least time the chip could take for the kernel's calls over the
    time its events took in the trace.  ``shape`` names the observation
    that holds the call's sizes; ``events_per_call`` says how many
    matching events one call leaves."""
    trace = ctx.get("trace")
    shape = obs.get(params["shape"])
    if trace is None or not shape:
        return None
    seconds, count = trace.pattern_seconds(params["pattern"])
    if not count or not seconds:
        return None
    calls = count / float(params.get("events_per_call", 1))
    cost = getattr(costs, params["cost"])(**shape)
    least, _ = costs.roofline_seconds(cost, ctx["peaks"])
    return 100.0 * calls * least / seconds
