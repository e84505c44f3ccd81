"""From a profiler trace (``.xplane.pb``) to numbers.

One reduction for every PR: device busy time as the union of the intervals
in which an operation ran, the idle share, the time of the events whose
name matches a pattern, the device operations that took most time, and the
longest idle gaps named by what the host was doing in them.

``load`` needs nothing but JAX; everything after it works on plain tuples,
so the arithmetic is checked on a small recorded trace
(``benchmarks/tests/test_xplane.py``)."""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Iterable, NamedTuple

DEVICE_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = r"^XLA Ops$"
HOST_PLANE = r"^/host:CPU$"
# host events that say nothing about what the program was doing
HOST_NOISE = r"^(ThreadpoolListener|end: |\$|Thunk|PjRt|tsl::|BFCAllocator)"


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """``planes[plane][line]`` is that line's events in start order."""

    planes: dict[str, dict[str, list[Event]]]

    def device_planes(self, pattern: str = DEVICE_PLANE) -> list[str]:
        return sorted(p for p in self.planes if re.search(pattern, p))

    def ops(self, plane: str, line: str = OPS_LINE) -> list[Event]:
        out = [e for name, evs in self.planes[plane].items()
               if re.search(line, name) for e in evs]
        return sorted(out, key=lambda e: e.start_ns)

    def host_events(self, pattern: str = HOST_PLANE,
                    noise: str = HOST_NOISE) -> list[Event]:
        out = [e for p, lines in self.planes.items() if re.search(pattern, p)
               for evs in lines.values() for e in evs
               if e.dur_ns > 0 and not re.search(noise, e.name)]
        return sorted(out, key=lambda e: e.start_ns)

    def window(self) -> tuple[float, float]:
        """The traced window: first start to last end over the device
        operations and the host's events."""
        evs = [e for p in self.device_planes() for e in self.ops(p)]
        evs += self.host_events()
        if not evs:
            raise ValueError("the trace holds no event")
        return (min(e.start_ns for e in evs), max(e.end_ns for e in evs))


def find(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str, keep_planes: str = r"^(/device:|/host:CPU$)",
         device_lines: str = OPS_LINE) -> Trace:
    """Of a device plane only the lines matching ``device_lines`` are kept
    (a serving slice of five seconds holds millions of events, a quarter
    of them on lines nothing reads)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes: dict[str, dict[str, list[Event]]] = {}
    for plane in data.planes:
        if not re.search(keep_planes, plane.name):
            continue
        lines = planes.setdefault(plane.name, {})
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if on_device and not re.search(device_lines, line.name):
                continue
            evs = [Event(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in line.events]
            lines.setdefault(line.name, []).extend(evs)
    for lines in planes.values():
        for evs in lines.values():
            evs.sort(key=lambda e: e.start_ns)
    return Trace(planes)


def merged(events: Iterable[Event], t0: float, t1: float
           ) -> list[tuple[float, float]]:
    """Union of the events' intervals, clipped to ``[t0, t1]``."""
    out: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start_ns):
        a, b = max(e.start_ns, t0), min(e.end_ns, t1)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events: Iterable[Event], t0: float, t1: float) -> float:
    return sum(b - a for a, b in merged(events, t0, t1))


def gaps(events: Iterable[Event], t0: float, t1: float
         ) -> list[tuple[float, float]]:
    """The idle intervals of ``[t0, t1]``: its part outside the union."""
    out, cur = [], t0
    for a, b in merged(events, t0, t1):
        if a > cur:
            out.append((cur, a))
        cur = b
    if t1 > cur:
        out.append((cur, t1))
    return out


def self_times(events: list[Event]) -> list[tuple[Event, float]]:
    """Each event's duration minus what its nested children cover (a
    ``while`` holds the operations of its body on the same line)."""
    out: list[list] = []
    stack: list[int] = []
    for e in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and out[stack[-1]][0].end_ns <= e.start_ns:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= min(e.dur_ns,
                                     out[stack[-1]][0].end_ns - e.start_ns)
        out.append([e, e.dur_ns])
        stack.append(len(out) - 1)
    return [(e, max(s, 0.0)) for e, s in out]


def time_by_pattern(events: Iterable[Event], pattern: str
                    ) -> tuple[float, int]:
    """Summed duration (ns) and count of the events whose name matches."""
    rx = re.compile(pattern)
    hit = [e.dur_ns for e in events if rx.search(e.name)]
    return float(sum(hit)), len(hit)


def short_name(name: str) -> str:
    """A TPU operation's event is named by its whole HLO instruction; keep
    the instruction's name without its number, and what kind it is:
    ``%fusion.5493 = ... kind=kOutput ...`` -> ``fusion[kOutput]``."""
    head = re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))
    kind = (re.search(r'custom_call_target="([^"]+)"', name)
            or re.search(r"kind=(k\w+)", name))
    return f"{head}[{kind.group(1)}]" if kind else head


def top_ops(events: list[Event], n: int = 10) -> list[tuple[str, float]]:
    """The operations that took most self time, seconds summed by short
    name."""
    total: dict[str, float] = {}
    for e, s in self_times(events):
        key = short_name(e.name)
        total[key] = total.get(key, 0.0) + s
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns / 1e9) for name, ns in top]


def name_gap(gap: tuple[float, float], host: list[Event]) -> str:
    """What the host was doing in an idle gap: the innermost host event
    around it, taken as the shortest one that covers half of the gap or
    more; failing that, the one that covers most of it."""
    half = (gap[1] - gap[0]) / 2.0
    inner, most = None, None
    for e in host:
        if e.start_ns >= gap[1]:
            break
        cover = min(e.end_ns, gap[1]) - max(e.start_ns, gap[0])
        if cover <= 0:
            continue
        if cover >= half and (inner is None or e.dur_ns < inner.dur_ns):
            inner = e
        if most is None or cover > most[0]:
            most = (cover, e)
    if inner is not None:
        return inner.name
    return most[1].name if most else "unattributed"


def longest_gaps(ops: list[Event], host: list[Event], t0: float, t1: float,
                 n: int = 10, named: int = 500) -> list[tuple[str, float]]:
    """Idle seconds by what the host was doing, the most costly first.
    The ``named`` longest gaps are looked up one by one; the many short
    ones between back-to-back operations go under one name."""
    by_length = sorted(gaps(ops, t0, t1), key=lambda g: g[0] - g[1])
    total: dict[str, float] = {}
    rest = sum(b - a for a, b in by_length[named:])
    if rest:
        total[f"gaps beyond the {named} longest"] = rest
    for g in by_length[:named]:
        name = name_gap(g, host)
        total[name] = total.get(name, 0.0) + (g[1] - g[0])
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [(name, ns / 1e9) for name, ns in top]


@dataclasses.dataclass
class Reduced:
    """What the readers and the result line take from one trace."""

    window_s: float
    busy_s: float                       # mean over the device planes
    ops: dict[str, list[Event]]         # device plane -> its operations
    host: list[Event]
    t0: float
    t1: float

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def pattern_seconds(self, pattern: str) -> tuple[float, int]:
        """Mean over the chips of the matching events' time, and count."""
        per = [time_by_pattern(evs, pattern) for evs in self.ops.values()]
        n = len(per)
        return (sum(t for t, _ in per) / n / 1e9,
                int(round(sum(c for _, c in per) / n)))

    def pattern_busy_seconds(self, pattern: str) -> float:
        """Mean over the chips of the time inside matching events: the
        union of their intervals, so a matching event nested in another
        is counted once."""
        rx = re.compile(pattern)
        return sum(busy_ns([e for e in evs if rx.search(e.name)],
                           self.t0, self.t1)
                   for evs in self.ops.values()) / len(self.ops) / 1e9

    def breakdown(self) -> dict:
        first = next(iter(self.ops.values()))
        return {"device_ops": [[k, v] for k, v in top_ops(first)],
                "idle_gaps": [[k, v] for k, v in longest_gaps(
                    first, self.host, self.t0, self.t1)]}


def reduce(trace: Trace) -> Reduced:
    planes = trace.device_planes()
    if not planes:
        raise ValueError(
            f"the trace has no device plane: {sorted(trace.planes)}")
    t0, t1 = trace.window()
    ops = {p: trace.ops(p) for p in planes}
    busy = sum(busy_ns(evs, t0, t1) for evs in ops.values()) / len(planes)
    return Reduced(window_s=(t1 - t0) / 1e9, busy_s=busy / 1e9, ops=ops,
                   host=trace.host_events(), t0=t0, t1=t1)
