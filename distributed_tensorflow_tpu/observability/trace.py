"""Structured trace spans: records in memory, a JSONL timeline on request.

A span is one named region of host-side work — ``compile``,
``chunk_dispatch``, ``materialize``, ``checkpoint``, ``eval`` are the
Trainer's vocabulary, ``serve_run``, ``request``, ``prefill``,
``decode_step``, ``idle_wait`` the serve loop's, ``step_dispatch`` and
``token_fetch`` the slot table's inside a round.  Every finished span
leaves one record in a bounded ring on the tracer:

    {"name": "decode_step", "start": 12.0391, "end": 12.1310, "id": 4711,
     "parent": 17, "rid": None, "attrs": {"active": 11, "slots": 32}}

``start`` and ``end`` are two readings of ``time.perf_counter()``, ``id``
counts up per tracer, ``parent`` is the id of the enclosing lexical span
on the same thread (None at the top), ``rid`` the caller's request id
where it gave one.  ``records()`` returns them when the run ends — a
percentile, the gap between two spans or an overlap is the reader's to
take; ``records(root=...)`` returns one window.  With a ``path`` the same
span also goes out as one JSONL event at span exit:

    {"schema_version": 1, "event": "span", "name": "chunk_dispatch",
     "t": <start>, "dur_s": 0.0021, "id": 9, "parent": None,
     "run": "r-1a2b3c", "host": "tpu-vm-0", "pid": 12345, "process": 0,
     ...attrs}

plus ``event``/``gauge``/``counter`` instants with the same envelope.
Every timestamp is ``time.perf_counter()`` — one monotonic clock,
orderable within a run, immune to wall-clock steps; each JSONL record also
carries run/host/process ids so pod timelines from many processes can be
merged and disentangled.

Two kinds of span:

* ``span(name, **attrs)`` is lexical: a ``with`` block on one thread.  It
  enters a ``jax.profiler.TraceAnnotation`` of the same name with the
  span's ``id`` as its argument, so when a profile is being taken
  (``--profile-dir``, utils/metrics.profile, the benchmark's traced run)
  the program's spans lie on the profiler's clock beside the device's
  operations, and each can be matched with its record.  With no profile
  running that is one inactive ``TraceMe``.
* ``begin(name, **attrs)`` / ``end(handle)`` is detached: a lifetime that
  crosses loop iterations (``request``).  It is recorded like
  any other, is nobody's parent and has no annotation — it is not host
  work.

``recorder()`` is the process-wide tracer (no file) that the serve loop
and ``Trainer.fit`` record into when the caller passes none;
``NULL_TRACER`` is what a caller passes to have nothing recorded.

The tracer also tracks its own cost (``overhead_s``): time spent inside
span bookkeeping and event emission, surfaced by the run report so the
"telemetry within 5% of telemetry-off" budget is measured, not assumed.
"""

from __future__ import annotations

import collections
import itertools
import os
import socket
import threading
import time
import uuid
from pathlib import Path
from typing import Any

from distributed_tensorflow_tpu.observability.sink import AsyncJsonlSink

# the ring's size: a decode round leaves three records (``decode_step``
# and, inside it, ``step_dispatch`` and ``token_fetch``) and a request two,
# so half an hour of serving at ten rounds a second fits
RING_CAPACITY = 1 << 16


class OpenSpan:
    """Handle of a detached span between ``begin`` and ``end``; keys added
    to ``attrs`` before ``end`` ride the record."""

    __slots__ = ("name", "start", "id", "parent", "attrs")

    def __init__(self, name: str, start: float, sid: int,
                 parent: int | None, attrs: dict[str, Any]):
        self.name, self.start, self.id = name, start, sid
        self.parent, self.attrs = parent, attrs


class _NullSpan:
    """``with`` target of the inert tracer: hands out the attribute dict
    and drops it."""

    __slots__ = ("attrs",)

    def __init__(self, attrs: dict[str, Any]):
        self.attrs = attrs

    def __enter__(self) -> dict[str, Any]:
        return self.attrs

    def __exit__(self, *exc) -> None:
        pass


class _NullTracer:
    """Inert tracer: what a caller passes to have nothing recorded.  Every
    method is a no-op; ``span`` hands out the attribute dict and drops
    it."""

    enabled = False
    overhead_s = 0.0
    dropped = 0

    def span(self, name: str, **attrs: Any) -> _NullSpan:
        return _NullSpan(attrs)

    def begin(self, name: str, **attrs: Any) -> OpenSpan:
        return OpenSpan(name, 0.0, 0, None, attrs)

    def end(self, handle: OpenSpan) -> None:
        pass

    def records(self, root: str | None = None) -> list[dict]:
        return []

    def event(self, name: str, **fields: Any) -> None:
        pass

    def gauge(self, name: str, value: float, **fields: Any) -> None:
        pass

    def counter(self, name: str, inc: int = 1, **fields: Any) -> None:
        pass

    def span_summary(self) -> dict:
        return {}

    def stats(self) -> dict:
        return {}

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


NULL_TRACER = _NullTracer()


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, or None when jax (or the
    profiler) is unavailable — the tracer must not force a jax import on
    pure-host users."""
    try:
        import jax

        return jax.profiler.TraceAnnotation
    except Exception:  # pragma: no cover - jax always present in this repo
        return None


class _Span:
    """One lexical span of a ``Tracer``, as a ``with`` target (a class and
    not a generator: the serve loop opens one per decode round, and what
    it costs the host delays the next round)."""

    __slots__ = ("tracer", "name", "attrs", "id", "parent", "start",
                 "entry_s", "annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict[str, Any]):
        self.tracer, self.name, self.attrs = tracer, name, attrs

    def __enter__(self) -> dict[str, Any]:
        t_in = time.perf_counter()
        tracer = self.tracer
        self.id = next(tracer._ids)
        stack = tracer._stack()
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        self.annotation = None
        if tracer._annotation is not None:
            self.annotation = tracer._annotation(self.name, id=self.id)
            self.annotation.__enter__()
        self.start = time.perf_counter()
        self.entry_s = self.start - t_in
        return self.attrs

    def __exit__(self, *exc) -> None:
        end = time.perf_counter()
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        tracer = self.tracer
        tracer._stack().pop()
        tracer._record(self.name, self.start, end, self.id, self.parent,
                       self.attrs, self.entry_s)


class Tracer:
    """Span/event recorder (see module docstring).

    ``path=None`` → in memory only: spans go to the record ring and the
    per-name summary (for the run report) but no file is written.
    ``annotate`` mirrors lexical spans into XProf via ``TraceAnnotation``.
    """

    enabled = True

    def __init__(self, path: str | Path | None = None,
                 run_id: str | None = None, process_index: int = 0,
                 annotate: bool = True, sink: AsyncJsonlSink | None = None):
        self.run_id = run_id or f"r-{uuid.uuid4().hex[:8]}"
        self.host = socket.gethostname()
        self.pid = os.getpid()
        self.process_index = process_index
        self.overhead_s = 0.0
        self.dropped = 0        # records the full ring pushed out
        self._annotation = _trace_annotation() if annotate else None
        self._sink = sink if sink is not None else (
            AsyncJsonlSink(path) if path else None)
        # ring, per-name aggregates (name -> [count, total_s, max_s]) and
        # overhead are updated under one lock: the serving fleet
        # (serving/fleet.py) shares ONE tracer across N replica worker
        # threads, and concurrent span exits would otherwise lose counts
        # (the JSONL sink is queue-based and was already thread-safe)
        self._agg_lock = threading.Lock()
        self._ring: collections.deque = collections.deque(
            maxlen=RING_CAPACITY)
        self._ids = itertools.count(1)
        self._open = threading.local()      # .stack: this thread's open ids
        self._spans: dict[str, list] = {}
        self._counters: dict[str, int] = {}
        if self._sink is not None:
            self.event("trace_start", wall_time=time.time())

    # ------------------------------------------------------------ emission
    def _emit(self, record: dict[str, Any]) -> None:
        if self._sink is not None:
            self._sink.write({
                **record,
                "run": self.run_id, "host": self.host, "pid": self.pid,
                "process": self.process_index,
            })

    def _stack(self) -> list[int]:
        try:
            return self._open.stack
        except AttributeError:
            stack = self._open.stack = []
            return stack

    def _record(self, name: str, start: float, end: float, sid: int,
                parent: int | None, attrs: dict[str, Any],
                entry_s: float = 0.0) -> None:
        """A finished span: ring, aggregate, JSONL; ``entry_s`` is what
        the span's own opening cost."""
        dur = end - start
        rec = {"name": name, "start": start, "end": end, "id": sid,
               "parent": parent, "rid": attrs.get("rid"), "attrs": attrs}
        with self._agg_lock:
            if len(self._ring) == RING_CAPACITY:
                self.dropped += 1
            self._ring.append(rec)
            agg = self._spans.get(name)
            if agg is None:
                agg = self._spans[name] = [0, 0.0, 0.0]
            agg[0] += 1
            agg[1] += dur
            if dur > agg[2]:
                agg[2] = dur
            if self._sink is not None:      # one queue put, never blocks
                self._emit({"event": "span", "name": name, "t": start,
                            "dur_s": dur, "id": sid, "parent": parent,
                            **attrs})
            self.overhead_s += entry_s + time.perf_counter() - end

    def span(self, name: str, **attrs: Any) -> _Span:
        """Time a named region of this thread's work; one record at exit.
        ``with tracer.span(...) as attrs`` hands out the span's attr dict
        — keys added to it BEFORE exit ride the record, which is how the
        serving scheduler attaches what is known only at the boundary
        (``padded_len`` of a prefill)."""
        return _Span(self, name, attrs)

    def begin(self, name: str, **attrs: Any) -> OpenSpan:
        """Open a detached span: a lifetime that crosses loop iterations.
        Its parent is the lexical span open on this thread; it is never a
        parent itself and is not mirrored into the profile."""
        stack = self._stack()
        return OpenSpan(name, time.perf_counter(), next(self._ids),
                        stack[-1] if stack else None, attrs)

    def end(self, handle: OpenSpan) -> None:
        self._record(handle.name, handle.start, time.perf_counter(),
                     handle.id, handle.parent, handle.attrs)

    def records(self, root: str | None = None) -> list[dict]:
        """The ring's records, oldest first.  With ``root``: the last
        finished span of that name, then every record whose interval lies
        inside it, whichever thread left it — one window, warm-up left
        out.  Nothing where no such span has finished."""
        with self._agg_lock:
            recs = list(self._ring)
        if root is None:
            return recs
        top = next((r for r in reversed(recs) if r["name"] == root), None)
        if top is None:
            return []
        return [top] + [r for r in recs if r is not top
                        and r["start"] >= top["start"]
                        and r["end"] <= top["end"]]

    def event(self, name: str, **fields: Any) -> None:
        t0 = time.perf_counter()
        self._emit({"event": "event", "name": name, "t": t0, **fields})
        self.overhead_s += time.perf_counter() - t0

    def gauge(self, name: str, value: float, **fields: Any) -> None:
        t0 = time.perf_counter()
        self._emit({"event": "gauge", "name": name, "t": t0,
                    "value": value, **fields})
        self.overhead_s += time.perf_counter() - t0

    def counter(self, name: str, inc: int = 1, **fields: Any) -> None:
        t0 = time.perf_counter()
        with self._agg_lock:
            self._counters[name] = total = \
                self._counters.get(name, 0) + inc
        self._emit({"event": "counter", "name": name, "t": t0,
                    "inc": inc, "total": total, **fields})
        self.overhead_s += time.perf_counter() - t0

    # ------------------------------------------------------------- summary
    def span_summary(self) -> dict[str, dict[str, float]]:
        """Per-name {count, total_s, max_s} — the run report's span table."""
        return {name: {"count": c, "total_s": tot, "max_s": mx}
                for name, (c, tot, mx) in sorted(self._spans.items())}

    def stats(self) -> dict[str, Any]:
        out: dict[str, Any] = {"overhead_s": self.overhead_s,
                               "counters": dict(self._counters),
                               "records": len(self._ring),
                               "records_dropped": self.dropped}
        if self._sink is not None:
            out.update(self._sink.stats())
        return out

    def flush(self) -> None:
        """Drain the queued records to disk without closing the sink —
        the Trainer's failure-path cleanup calls this so no buffered span
        outlives a raising fit."""
        if self._sink is not None:
            self._sink.flush()

    def close(self) -> None:
        if self._sink is not None:
            self._sink.close()

    def __enter__(self) -> "Tracer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


_RECORDER: Tracer | None = None
_RECORDER_LOCK = threading.Lock()


def recorder() -> Tracer:
    """The process-wide tracer, made on first use: records in memory, no
    file.  ``ContinuousBatcher``, the serving fleet and ``Trainer.fit``
    record into it when no tracer is passed."""
    global _RECORDER
    if _RECORDER is None:
        with _RECORDER_LOCK:
            if _RECORDER is None:
                _RECORDER = Tracer(path=None)
    return _RECORDER
