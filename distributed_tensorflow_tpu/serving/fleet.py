"""Fault-tolerant serving fleet: supervised replicas with no-loss failover.

PRs 7–13 built one excellent single-replica batcher; "millions of users"
(ROADMAP item 2) means a *fleet*, and the difference between a benchmark
and a service is what happens when a replica dies mid-decode.  This module
is that difference, with robustness as the headline contract:

* :class:`ReplicaSet` runs N ``ContinuousBatcher`` replicas — each with
  its own ``SlotKVCache`` — behind a least-loaded front-end router.  In
  wall-clock mode every replica serves on its own thread; with a
  ``VirtualClock`` the supervisor drives replicas deterministically in id
  order, so chaos tests are exact, repeatable schedules (the Varuna
  lesson, arXiv:2111.04007: preemption tolerance must be a first-class,
  testable design axis).

* The :class:`RequestJournal` records every request's replica assignment
  and every token actually delivered.  When a replica fails — an
  exception out of its run loop, a watchdog stall, or an injected fault —
  its queued AND in-flight requests are requeued to surviving replicas
  with bounded retry + backoff, and the journal's **assignment fence**
  makes delivery exactly-once: an emission is accepted only from the
  request's CURRENT replica, so a stalled zombie waking up after failover
  cannot re-emit (fenced emissions are counted, never delivered).  A
  retried request resumes by re-prefilling prompt + already-emitted
  prefix (greedy decode makes the continuation exact — the vLLM
  iteration-level substrate, arXiv:2309.06180: the retry re-enters the
  continuous-batching loop of the survivor, it does not restart a batch),
  and its TTFT stays charged from the ORIGINAL arrival, the PR 7/11
  accounting discipline.

* :class:`FaultInjector` is the seeded test substrate (the serving twin
  of ``HealthConfig.inject_nan_at``): crash-at-site-k (decode iteration,
  prefill chunk, or between verify and commit), stall-for-s (caught by
  the supervisor's watchdog), and nonfinite-logits corruption — modeled
  as an out-of-range sampled token id, detected by the fleet's cheap
  per-token host check before anything reaches the journal.

* **Graceful drain + zero-downtime weight hot-swap**: each replica
  carries a ``LeaseManager`` (the PR 9 ``should_stop`` contract) whose
  programmatic ``trigger`` drains it — stop admitting, finish in-flight —
  after which ``SlotKVCache.swap_params`` installs the new weights
  between compiled-program dispatches (a swap never recompiles).  Swaps
  run replica-by-replica, so the fleet never drops below N−1 admitting
  replicas, and ``swap_generations`` counts completed fleet-wide swaps.

* Fleet accounting: per-replica ``MetricsRegistry`` histograms merge via
  PR 11's ``merge`` (built for exactly this aggregation), and the run
  summary carries a ``serve_fleet`` section — replicas, failovers,
  retries, requeued_requests, duplicate_emissions (== 0 is the
  exactly-once claim, measured not assumed), swap_generations, and
  per-replica + merged goodput — plus the two gated headline keys
  ``serve_failover_recovery_p95_s`` and ``serve_duplicate_emissions``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from typing import Any, Callable, Iterable

import numpy as np

from distributed_tensorflow_tpu.elastic.lease import LeaseManager
from distributed_tensorflow_tpu.observability.metrics import (
    MetricsRegistry, exact_percentile)
from distributed_tensorflow_tpu.observability.trace import recorder
from distributed_tensorflow_tpu.serving.kv_cache import SlotKVCache
from distributed_tensorflow_tpu.serving.scheduler import (
    ContinuousBatcher, Request, RequestQueue, RequestResult, VirtualClock,
    WallClock)


class InjectedFault(RuntimeError):
    """A FaultInjector fired: the replica's run loop dies here exactly the
    way an un-injected bug would — the supervisor must not special-case
    it (the whole point of injection is exercising the real path)."""


class CorruptionDetected(RuntimeError):
    """The fleet's cheap per-token host check rejected an emission (token
    id out of [0, vocab) — what nonfinite logits degrade sampling into).
    Raised BEFORE the journal records anything, so a corrupt token is
    never delivered; the replica fails over like any other death."""


# ------------------------------------------------------------ fault specs

_FAULT_KINDS = ("crash", "stall", "nanlogits")
_FAULT_SITES = ("decode", "prefill", "verify", "handoff")


@dataclasses.dataclass
class FaultSpec:
    """One seeded fault: ``kind`` at the ``at``-th ``site`` event on
    ``replica`` (1-based count of decode iterations / prefill programs /
    verify steps on that replica), or Bernoulli per event with ``prob``
    under the injector's seed.  ``stall_s`` is the stall duration."""

    kind: str
    replica: int
    site: str = "decode"
    at: int = 0
    prob: float = 0.0
    stall_s: float = 0.0

    def __post_init__(self):
        if self.kind not in _FAULT_KINDS:
            raise ValueError(f"fault kind must be one of {_FAULT_KINDS}, "
                             f"got '{self.kind}'")
        if self.site not in _FAULT_SITES:
            raise ValueError(f"fault site must be one of {_FAULT_SITES}, "
                             f"got '{self.site}'")
        if self.replica < 0:
            raise ValueError(f"fault replica must be >= 0, "
                             f"got {self.replica}")
        if (self.at <= 0) == (self.prob <= 0.0):
            raise ValueError(
                "a fault needs exactly one trigger: at=K (the K-th site "
                "event) or prob=P (seeded Bernoulli per event); got "
                f"at={self.at}, prob={self.prob}")
        if self.kind == "stall" and self.stall_s <= 0:
            raise ValueError("stall faults need stall_s > 0")
        if self.site != "decode" and self.kind != "crash":
            raise ValueError(
                f"site '{self.site}' supports crash only (stall/nanlogits "
                f"model decode-path failures)")


class FaultInjector:
    """Seeded fault injection over a replica's SlotKVCache programs.

    ``spec`` is a list of :class:`FaultSpec` or the CLI string grammar
    (``--serve-fault-spec``)::

        kind:key=val,key=val[;kind:...]

    e.g. ``crash:replica=0,iter=3`` (crash replica 0's 3rd decode
    iteration — a speculative verify round counts as one iteration, so
    spec-decoding replicas are killable too),
    ``crash:replica=1,prefill=2`` (during its 2nd prefill
    program — the kill-during-prefill-chunk case),
    ``crash:replica=0,verify=1`` (AFTER the verify step computed, BEFORE
    any commit — the kill-between-verify-and-commit case),
    ``crash:replica=0,handoff=1`` (a disaggregated prefill replica killed
    between prefill completion and decode admission — inside the KV
    extract, before the payload leaves the replica),
    ``stall:replica=1,iter=2,stall_s=0.5``, ``nanlogits:replica=0,iter=4``,
    ``crash:replica=0,prob=0.05`` (seeded Bernoulli per iteration).

    ``arm(replica_id, kv)`` wraps the instance's ``advance`` /
    ``insert``+``prefill_chunk`` / ``verify_block`` methods; every firing
    is recorded in ``fired`` with its site count.  One-shot per spec.
    """

    def __init__(self, spec: str | Iterable[FaultSpec], seed: int = 0):
        self.specs = (self.parse(spec) if isinstance(spec, str)
                      else list(spec))
        self._rng = np.random.default_rng(seed)
        self.seed = int(seed)
        self.fired: list[dict[str, Any]] = []
        self._done: set[int] = set()   # indices of one-shot specs fired

    @staticmethod
    def parse(spec: str) -> list[FaultSpec]:
        """CLI grammar → FaultSpec list (raises ValueError on any typo —
        the harness validates this pre-train, like every other serve
        flag)."""
        out: list[FaultSpec] = []
        for part in spec.split(";"):
            part = part.strip()
            if not part:
                continue
            kind, colon, body = part.partition(":")
            kind = kind.strip()
            if not colon or kind not in _FAULT_KINDS:
                raise ValueError(
                    f"--serve-fault-spec entries are 'kind:key=val,...' "
                    f"with kind in {_FAULT_KINDS}; got '{part}'")
            kw: dict[str, Any] = {"kind": kind, "replica": -1}
            for item in body.split(","):
                item = item.strip()
                if not item:
                    continue
                key, eq, val = item.partition("=")
                key = key.strip()
                val = val.strip()
                if not eq:
                    raise ValueError(
                        f"--serve-fault-spec items must be key=val, got "
                        f"'{item}'")
                try:
                    if key == "replica":
                        kw["replica"] = int(val)
                    elif key == "iter":
                        kw["site"], kw["at"] = "decode", int(val)
                    elif key == "prefill":
                        kw["site"], kw["at"] = "prefill", int(val)
                    elif key == "verify":
                        kw["site"], kw["at"] = "verify", int(val)
                    elif key == "handoff":
                        kw["site"], kw["at"] = "handoff", int(val)
                    elif key == "prob":
                        kw["prob"] = float(val)
                    elif key == "stall_s":
                        kw["stall_s"] = float(val)
                    else:
                        raise ValueError(
                            f"unknown --serve-fault-spec key '{key}' "
                            f"(replica/iter/prefill/verify/handoff/prob/"
                            f"stall_s)")
                except ValueError as e:
                    if "fault-spec" in str(e):
                        raise
                    raise ValueError(
                        f"--serve-fault-spec value for '{key}' must be "
                        f"numeric, got '{val}'") from None
            if kw["replica"] < 0:
                raise ValueError(
                    f"--serve-fault-spec entry '{part}' needs replica=N")
            out.append(FaultSpec(**kw))
        if not out:
            raise ValueError("--serve-fault-spec parsed to no faults")
        return out

    # ------------------------------------------------------------- arming
    def _check(self, replica: int, site: str, count: int) -> FaultSpec | None:
        """The fault (if any) firing at this site event; one-shot specs
        fire at most once, prob specs draw from the injector's seeded rng
        (one draw per matching event — deterministic given the seed and
        the event schedule)."""
        for i, s in enumerate(self.specs):
            if s.replica != replica or s.site != site or i in self._done:
                continue
            hit = (count == s.at) if s.at else \
                (float(self._rng.random()) < s.prob)
            if hit:
                self._done.add(i)
                self.fired.append({"kind": s.kind, "replica": replica,
                                   "site": site, "count": count,
                                   "stall_s": s.stall_s or None})
                return s
        return None

    def arm(self, replica_id: int, kv: SlotKVCache) -> None:
        """Wrap this table's device-program entry points.  Instance-level
        wrappers: the class and every other table stay untouched."""
        if not any(s.replica == replica_id for s in self.specs):
            return
        counts = {"decode": 0, "prefill": 0, "verify": 0, "handoff": 0}
        injector = self

        orig_advance = kv.advance
        orig_insert = kv.insert
        orig_chunk = kv.prefill_chunk
        orig_verify = kv.verify_block
        orig_extract = kv.extract_handoff

        def advance(only=None):
            if only is None:   # draft catch-up steps are not iterations
                counts["decode"] += 1
                s = injector._check(replica_id, "decode", counts["decode"])
                if s is not None:
                    if s.kind == "crash":
                        raise InjectedFault(
                            f"injected crash: replica {replica_id} decode "
                            f"iteration {counts['decode']}")
                    if s.kind == "stall":
                        time.sleep(s.stall_s)
                    elif s.kind == "nanlogits":
                        toks = orig_advance(only)
                        bad = np.asarray(toks).copy()
                        # what NaN logits degrade argmax sampling into: an
                        # id no vocabulary contains — the fleet's host
                        # check rejects it before delivery
                        bad[:] = -1
                        return bad
            return orig_advance(only)

        def _prefill_gate():
            counts["prefill"] += 1
            s = injector._check(replica_id, "prefill", counts["prefill"])
            if s is not None:
                raise InjectedFault(
                    f"injected crash: replica {replica_id} prefill "
                    f"program {counts['prefill']}")

        def insert(prompt, slot=None):
            _prefill_gate()
            return orig_insert(prompt, slot)

        def prefill_chunk(slot, max_tokens=None):
            _prefill_gate()
            return orig_chunk(slot, max_tokens)

        def verify_block(block):
            # a speculative round's verify IS the target decode iteration
            # (draft-k → verify-1): decode-site faults count and fire
            # here too, or a spec-decoding replica would be unkillable
            # by `iter=K`
            counts["decode"] += 1
            s = injector._check(replica_id, "decode", counts["decode"])
            corrupt = False
            if s is not None:
                if s.kind == "crash":
                    raise InjectedFault(
                        f"injected crash: replica {replica_id} decode "
                        f"iteration {counts['decode']} (verify round)")
                if s.kind == "stall":
                    time.sleep(s.stall_s)
                corrupt = s.kind == "nanlogits"
            g = orig_verify(block)
            counts["verify"] += 1
            sv = injector._check(replica_id, "verify", counts["verify"])
            if sv is not None:
                # AFTER the verify program ran, BEFORE any commit_block:
                # the kill-between-verify-and-commit window — nothing of
                # this round may survive into the emitted stream
                raise InjectedFault(
                    f"injected crash: replica {replica_id} between verify "
                    f"{counts['verify']} and commit")
            if corrupt:
                g = np.asarray(g).copy()
                g[:] = -1
            return g

        def extract_handoff(slot):
            # fires BEFORE the KV leaves the replica: prefill is complete,
            # decode admission has not happened — the batcher's handoff
            # guard evicts the slot, so the crash must not leak blocks
            counts["handoff"] += 1
            s = injector._check(replica_id, "handoff", counts["handoff"])
            if s is not None:
                raise InjectedFault(
                    f"injected crash: replica {replica_id} handoff "
                    f"{counts['handoff']} (between prefill completion and "
                    f"decode admission)")
            return orig_extract(slot)

        kv.advance = advance
        kv.insert = insert
        kv.prefill_chunk = prefill_chunk
        kv.verify_block = verify_block
        kv.extract_handoff = extract_handoff


# --------------------------------------------------------------- journal

@dataclasses.dataclass
class _Entry:
    """One offered request's journal record (journal lock held for every
    mutation)."""

    req: Request
    status: str = "pending"   # pending | done | shed | lost | unserved
    replica: int | None = None
    attempts: int = 0
    phase: str = "prefill"    # disagg role the request currently sits in:
    #                           "prefill" until its KV is handed off, then
    #                           "decode"; a requeue flips it back (resume
    #                           re-prefills).  Homogeneous fleets never
    #                           leave "prefill".
    emitted: list[int] = dataclasses.field(default_factory=list)
    emit_t: list[float] = dataclasses.field(default_factory=list)
    assigned_t: float = 0.0
    first_assigned_t: float | None = None
    failed_at: float | None = None   # set at its replica's failure, until
    #                                  the first post-requeue emission
    completed_by: int | None = None
    finish_t: float | None = None


class RequestJournal:
    """Assignment + emission ledger: the exactly-once substrate.

    Every token delivery flows through :meth:`emit`, which accepts an
    emission only from the request's CURRENT replica assignment (the
    fence): after failover, a zombie replica's late emissions are counted
    (``fenced_emissions``) and dropped, never delivered.  A request
    completes when its emitted stream reaches ``max_new_tokens`` (or its
    EOS) — the same rule the batchers apply — so journal state and
    replica state cannot disagree about doneness.

    ``duplicate_emissions`` counts deliveries that would repeat an
    already-delivered position; the fence makes this structurally zero,
    and the counter measures it instead of assuming it (the chaos
    acceptance gate).
    """

    def __init__(self, requests: Iterable[Request]):
        self._lock = threading.RLock()
        self.entries: dict[int, _Entry] = {}
        self.load: dict[int, int] = {}    # replica -> live assigned count
        self.fenced_emissions = 0
        self.duplicate_emissions = 0
        self.done_count = 0               # O(1) completion counter (the
        #                                   swap-threshold check runs on
        #                                   every completion — a counts()
        #                                   scan there would be O(n²))
        self.requeues = 0                 # re-assignments (retries)
        self.requeued_rids: set[int] = set()
        self.recovery_s: list[float] = []
        for req in requests:
            if req.rid in self.entries:
                raise ValueError(f"duplicate rid {req.rid} in workload")
            self.entries[req.rid] = _Entry(req=req)

    # ------------------------------------------------------------ routing
    def assign(self, rid: int, replica: int, t: float,
               retry: bool = False, transfer: bool = False) -> None:
        """``transfer`` moves a live assignment between replicas without
        consuming retry budget — a KV handoff (prefill → decode) or an
        autoscale rebalance is a routing event, not a failure."""
        with self._lock:
            e = self.entries[rid]
            if e.replica is not None:
                self.load[e.replica] = self.load.get(e.replica, 1) - 1
            e.replica = replica
            if not transfer:
                e.attempts += 1
                e.phase = "prefill"   # fresh/retried work re-prefills
            e.assigned_t = t
            if e.first_assigned_t is None:
                e.first_assigned_t = t
            self.load[replica] = self.load.get(replica, 0) + 1
            if retry:
                self.requeues += 1
                self.requeued_rids.add(rid)

    def set_phase(self, rid: int, phase: str) -> None:
        with self._lock:
            self.entries[rid].phase = phase

    def least_loaded(self, replicas: Iterable[int]) -> int:
        """Front-end routing: the serving replica with the fewest live
        assignments (ties → lowest id, so routing is deterministic)."""
        with self._lock:
            return min(replicas,
                       key=lambda r: (self.load.get(r, 0), r))

    # ----------------------------------------------------------- emission
    def emit(self, rid: int, replica: int, token: int,
             t: float) -> tuple[bool, bool, float | None]:
        """Record one token delivery; returns ``(accepted, completed_now,
        recovery_s)``.  ``accepted`` False = fenced (stale assignment or
        already-terminal request) — the caller must NOT deliver."""
        with self._lock:
            e = self.entries.get(rid)
            if e is None:
                self.fenced_emissions += 1
                return False, False, None
            if e.status != "pending" or e.replica != replica:
                self.fenced_emissions += 1
                return False, False, None
            if len(e.emitted) >= e.req.max_new_tokens:
                # structurally unreachable (completion flips status); a
                # hit here is a real double-delivery — measured, not
                # assumed away
                self.duplicate_emissions += 1
                return False, False, None
            e.emitted.append(int(token))
            e.emit_t.append(float(t))
            recovery = None
            if e.failed_at is not None:
                recovery = float(t) - e.failed_at
                self.recovery_s.append(recovery)
                e.failed_at = None
            done = (len(e.emitted) >= e.req.max_new_tokens
                    or (e.req.eos_id is not None
                        and int(token) == e.req.eos_id))
            if done:
                e.status = "done"
                e.completed_by = replica
                e.finish_t = float(t)
                self.done_count += 1
                self.load[replica] = self.load.get(replica, 1) - 1
            return True, done, recovery

    # ----------------------------------------------------------- failover
    def pending_for(self, replica: int) -> list[int]:
        with self._lock:
            return sorted(rid for rid, e in self.entries.items()
                          if e.status == "pending" and e.replica == replica)

    def mark_failed(self, rids: Iterable[int], t: float) -> None:
        """Atomically fence a dead replica's requests: the assignment is
        CLEARED here (under the journal lock), so a zombie emission
        racing the failover — after the supervisor decided to fail over
        but before the requeue lands — is already stale.  Without this,
        such an emission would record a near-zero bogus recovery sample
        and could complete the stream mid-handoff."""
        with self._lock:
            for rid in rids:
                e = self.entries[rid]
                if e.status != "pending":
                    continue
                if e.failed_at is None:
                    e.failed_at = float(t)
                if e.replica is not None:
                    self.load[e.replica] = self.load.get(e.replica, 1) - 1
                    e.replica = None

    def retry_request(self, rid: int) -> Request | None:
        """The resume request for a failed-over rid: original prompt +
        already-emitted prefix re-prefilled, remaining budget only —
        greedy decode makes the continuation exactly what the dead
        replica would have produced.  None when the stream is already
        complete (crash after the last emission: nothing to resume)."""
        with self._lock:
            e = self.entries[rid]
            if e.status != "pending":
                return None   # completed/terminal while failing over
            remaining = e.req.max_new_tokens - len(e.emitted)
            if remaining <= 0:
                # crash landed after the last delivery: the stream is
                # complete, attributed to the replica that finished it
                e.status = "done"
                e.completed_by = e.replica
                e.finish_t = e.emit_t[-1] if e.emit_t else None
                self.done_count += 1
                if e.replica is not None:
                    self.load[e.replica] = self.load.get(e.replica, 1) - 1
                return None
            prompt = np.concatenate([
                np.asarray(e.req.prompt, np.int32).reshape(-1),
                np.asarray(e.emitted, np.int32)])
            return Request(rid=rid, prompt=prompt,
                           max_new_tokens=remaining,
                           arrival_s=e.req.arrival_s,
                           eos_id=e.req.eos_id)

    def finalize(self, rid: int, status: str) -> None:
        """Terminal non-completion states: shed / lost / unserved."""
        with self._lock:
            e = self.entries[rid]
            if e.status == "pending":
                e.status = status
                if e.replica is not None:
                    self.load[e.replica] = self.load.get(e.replica, 1) - 1

    def finalize_if_assigned(self, rid: int, replica: int,
                             status: str) -> None:
        """Fenced finalize: only the request's CURRENT replica may
        terminal-ize it (a zombie's shed report must not kill a request
        a survivor now owns — same fence as emission)."""
        with self._lock:
            e = self.entries.get(rid)
            if e is not None and e.status == "pending" \
                    and e.replica == replica:
                e.status = status
                self.load[replica] = self.load.get(replica, 1) - 1

    # ----------------------------------------------------------- summary
    def all_terminal(self) -> bool:
        with self._lock:
            return all(e.status != "pending"
                       for e in self.entries.values())

    def counts(self) -> dict[str, int]:
        with self._lock:
            c = {"done": 0, "shed": 0, "lost": 0, "unserved": 0,
                 "pending": 0}
            for e in self.entries.values():
                c[e.status] += 1
            return c

    def role_counts(self) -> dict[str, dict[str, int]]:
        """Terminal status counts partitioned by the phase each request
        ENDED in.  Phase is single-valued, so the two partitions sum to
        ``counts()`` exactly — a dropped handoff flips the request back
        to "prefill" and it is counted once, there; it cannot
        double-count or vanish."""
        with self._lock:
            out = {p: {"done": 0, "shed": 0, "lost": 0, "unserved": 0,
                       "pending": 0} for p in ("prefill", "decode")}
            for e in self.entries.values():
                out[e.phase][e.status] += 1
            return out

    def results(self) -> list[RequestResult]:
        """Fleet-level per-request results from the journal's emission
        timeline: TTFT from the ORIGINAL arrival (retries do not reset
        the clock — the PR 7/11 accounting discipline), ITL gaps from
        consecutive delivery times (a failover's recovery gap lands in
        the retried request's own ITL tail, where its reader felt it)."""
        with self._lock:
            out = []
            for rid in sorted(self.entries):
                e = self.entries[rid]
                if e.status != "done" or not e.emit_t:
                    continue
                lp = int(np.asarray(e.req.prompt).reshape(-1).shape[0])
                r = RequestResult(
                    rid=rid, prompt_len=lp, tokens=list(e.emitted),
                    arrival_s=e.req.arrival_s,
                    admitted_s=(e.first_assigned_t
                                if e.first_assigned_t is not None
                                else e.req.arrival_s),
                    first_token_s=e.emit_t[0],
                    finished_s=e.emit_t[-1],
                    itl_s=[b - a for a, b in zip(e.emit_t, e.emit_t[1:])],
                    queue_wait_s=max(
                        (e.first_assigned_t or e.req.arrival_s)
                        - e.req.arrival_s, 0.0),
                    prefill_s=max(e.emit_t[0]
                                  - (e.first_assigned_t
                                     or e.req.arrival_s), 0.0))
                out.append(r)
            return out


# ---------------------------------------------------------- shared clock

class _SharedClock:
    """One fleet-wide clock behind every replica's batcher: ``start`` is
    idempotent (each ``ContinuousBatcher.run`` calls it; only the first
    may zero the timeline) and virtual mutations are serialized — the
    fleet timeline is shared state, per-replica restarts must not rewind
    it."""

    def __init__(self, base):
        self._base = base
        self._lock = threading.Lock()
        self._started = False
        self.poll_slice_s = getattr(base, "poll_slice_s", float("inf"))

    def start(self) -> None:
        with self._lock:
            if not self._started:
                self._base.start()
                self._started = True

    def now(self) -> float:
        return self._base.now()

    def on_decode_iteration(self) -> None:
        with self._lock:
            self._base.on_decode_iteration()

    def on_prefill(self, tokens: int) -> None:
        with self._lock:
            self._base.on_prefill(tokens)

    def wait_until(self, t: float) -> None:
        self._base.wait_until(t)


class _FleetQueue(RequestQueue):
    """RequestQueue whose mutations are lock-guarded, so the supervisor
    can requeue a failed replica's requests INTO a survivor's live run —
    the retry re-enters the continuous-batching loop between decode
    iterations instead of waiting for the survivor's batch to drain."""

    def __init__(self, requests=()):
        super().__init__(requests)
        self._qlock = threading.RLock()

    def push(self, request):
        with self._qlock:
            super().push(request)

    def __len__(self):
        with self._qlock:
            return super().__len__()

    def next_arrival(self):
        with self._qlock:
            return super().next_arrival()

    def pop_ready(self, now):
        with self._qlock:
            return super().pop_ready(now)

    def depth(self, now=None):
        with self._qlock:
            return super().depth(now)

    def shed_ready(self, now, keep):
        with self._qlock:
            return super().shed_ready(now, keep)

    def drain(self) -> list[Request]:
        with self._qlock:
            items, self._items = list(self._items), []
            return items


# ---------------------------------------------------------------- replica

class _Replica:
    """Supervisor-side record of one batcher replica."""

    def __init__(self, rid: int, kv: SlotKVCache,
                 registry: MetricsRegistry):
        self.id = rid
        self.kv = kv
        self.batcher: ContinuousBatcher | None = None  # set by ReplicaSet
        self.registry = registry
        self.lease = LeaseManager(signals=())   # trigger()-driven only
        self.queue = _FleetQueue()
        self.state = "serving"                  # serving | dormant | failed
        self.generation = 0                     # weight-swap count
        self.busy = False
        self.completed = 0
        self.role: str | None = None            # prefill | decode | None
        self.serve_start: float | None = None   # replica_seconds interval
        self.idle_since: float | None = None    # autoscale scale-down timer
        self.failure: str | None = None
        self.last_progress = time.monotonic()
        self.work = threading.Event()
        self.stop = threading.Event()
        self.thread: threading.Thread | None = None


@dataclasses.dataclass(frozen=True)
class AutoscalePolicy:
    """Queue-driven replica-count policy (``--serve-autoscale MIN:MAX``).

    Scale-up fires when the fleet's ARRIVED backlog per admitting
    replica crosses ``high_watermark`` — queue depth is the leading
    overload signal (the PR 11 finding: depth p95 climbs before goodput
    falls), so capacity is added before the knee, not after shed rate
    proves it arrived too late.  Scale-down retires one replica with no
    arrived work after ``idle_s`` of continuous idleness, transferring
    its not-yet-arrived assignments to the survivors.  ``cooldown_s``
    spaces consecutive scaling actions so one burst cannot thrash the
    fleet, and ``slice_s`` bounds each replica's serving slice so the
    supervisor gets a decision point at least that often in fleet time
    (without it a sequential replica would serve its whole queue —
    including idle gaps — before the policy could react).
    """

    min_replicas: int = 1
    max_replicas: int = 0          # 0 = every replica in the set
    high_watermark: float = 4.0    # arrived backlog per admitting replica
    idle_s: float = 2.0            # continuous idleness before scale-down
    cooldown_s: float = 1.0        # min spacing between scaling actions
    slice_s: float = 4.0           # max serving slice between decisions

    def __post_init__(self):
        if self.min_replicas < 1:
            raise ValueError(
                f"autoscale min_replicas must be >= 1, "
                f"got {self.min_replicas}")
        if self.max_replicas and self.max_replicas < self.min_replicas:
            raise ValueError(
                f"autoscale max_replicas ({self.max_replicas}) must be >= "
                f"min_replicas ({self.min_replicas})")
        if self.high_watermark <= 0:
            raise ValueError(
                f"autoscale high_watermark must be > 0, "
                f"got {self.high_watermark}")
        if self.idle_s < 0 or self.cooldown_s < 0 or self.slice_s <= 0:
            raise ValueError(
                "autoscale idle_s/cooldown_s must be >= 0 and "
                "slice_s > 0")

    @staticmethod
    def parse(spec: str) -> "AutoscalePolicy":
        """``--serve-autoscale MIN:MAX`` grammar (e.g. ``1:4``)."""
        lo, colon, hi = spec.partition(":")
        try:
            if not colon:
                raise TypeError
            lo_i, hi_i = int(lo), int(hi)
        except (TypeError, ValueError):
            raise ValueError(
                f"--serve-autoscale must be MIN:MAX (e.g. 1:4), "
                f"got '{spec}'") from None
        return AutoscalePolicy(min_replicas=lo_i, max_replicas=hi_i)


class ReplicaSet:
    """N-replica serving fleet supervisor (module docstring).

    ``kvs`` is one ``SlotKVCache`` per replica (each replica owns its
    table; params may share device buffers).  ``clock`` is fleet-wide:
    ``WallClock`` (default) serves every replica on its own thread;
    ``VirtualClock`` drives replicas sequentially in id order —
    deterministic chaos schedules (``threaded`` overrides the default).

    ``fault_injector`` arms seeded faults on the matching replicas'
    tables before serving.  ``watchdog_timeout_s`` (threaded mode) fails
    over a replica whose scheduler loop made no heartbeat for that long
    while busy — the heartbeat ticks at every loop iteration and idle
    poll slice (``_replica_should_stop``), so a replica idling toward a
    future arrival is NOT a stall; one wedged inside a device program
    is.  The zombie is fenced, not killed: its late emissions are
    rejected by the journal.  The watchdog still cannot tell a stall
    from a first-program XLA compile (the host blocks inside the same
    call), so set the timeout above worst-case compile time or warm the
    tables before serving (the harness's post-train window compiles in
    its first requests).

    ``retry_limit`` bounds per-request failover attempts (assignments
    beyond the first), with ``retry_backoff_s`` exponential arrival
    backoff; an exhausted request is terminal ``lost`` and counts into
    ``unserved_requests`` (conservation stays exact).

    Round 18 (all default-off — the defaults are class-, program- and
    summary-key-identical to the homogeneous fleet):

    - ``roles`` disaggregates the fleet (one ``"prefill"``/``"decode"``
      entry per replica): prefill replicas run admission + chunked
      prefill only and hand the finished KV to a decode replica as a
      serialized block payload (``SlotKVCache.extract_handoff``), taking
      ``handoff_s`` of simulated transfer time that lands inside the
      request's TTFT; decode replicas never share an iteration with a
      long prompt.  Retries re-prefill, so they route to the prefill
      side.
    - ``routing="affinity"`` keys fresh requests on the chained SHA-256
      digest of their first prefix block and lands shared-prefix
      traffic where that block is already resident (falling back to
      least-loaded for unkeyed prompts and retries).
    - ``autoscale`` (an :class:`AutoscalePolicy` or ``"MIN:MAX"``)
      drives the serving-replica count from arrived queue depth;
      replicas above the floor start dormant and ``replica_seconds``
      (integral of serving time) lands in the summary.
    - ``parallel_lanes`` (VirtualClock, sequential driver) gives each
      replica its own virtual-time lane so N replicas genuinely overlap
      in fleet time — cross-replica events (handoffs, retries) carry
      absolute stamps and the receiving lane jumps forward, never back.
      Fleet elapsed time is then the max over lanes.
    """

    def __init__(self, kvs: list[SlotKVCache], *, tracer=None,
                 clock=None, threaded: bool | None = None,
                 prefill_chunk: int = 0, queue_cap: int = 0, slo=None,
                 draft_kvs: list[SlotKVCache] | None = None,
                 draft_k: int = 4, retry_limit: int = 2,
                 retry_backoff_s: float = 0.0,
                 watchdog_timeout_s: float = 0.0,
                 fault_injector: FaultInjector | None = None,
                 timeline=None,
                 roles: list[str] | None = None,
                 routing: str = "least-loaded",
                 autoscale: AutoscalePolicy | str | None = None,
                 handoff_s: float = 0.0,
                 parallel_lanes: bool = False,
                 roofline=None, multi_step: int | None = None):
        if not kvs:
            raise ValueError("ReplicaSet needs at least one SlotKVCache")
        if draft_kvs is not None and len(draft_kvs) != len(kvs):
            raise ValueError(
                f"draft_kvs must pair replicas 1:1 ({len(draft_kvs)} "
                f"drafts vs {len(kvs)} replicas)")
        if retry_limit < 0:
            raise ValueError(f"retry_limit must be >= 0, got {retry_limit}")
        if routing not in ("least-loaded", "affinity"):
            raise ValueError(
                f"routing must be 'least-loaded' or 'affinity', "
                f"got '{routing}'")
        if roles is not None:
            roles = [str(r) for r in roles]
            if len(roles) != len(kvs):
                raise ValueError(
                    f"roles must pair replicas 1:1 ({len(roles)} roles "
                    f"vs {len(kvs)} replicas)")
            bad = sorted(set(roles) - {"prefill", "decode"})
            if bad:
                raise ValueError(
                    f"roles must be 'prefill' or 'decode', got {bad}")
            if "prefill" not in roles or "decode" not in roles:
                raise ValueError(
                    "a disaggregated fleet needs at least one prefill "
                    "AND one decode replica")
            if draft_kvs is not None:
                raise ValueError(
                    "speculative decoding is not supported in a "
                    "disaggregated fleet (draft KV state does not ride "
                    "the handoff payload)")
        if isinstance(autoscale, str):
            autoscale = AutoscalePolicy.parse(autoscale)
        if autoscale is not None:
            # with roles the policy drives each role pool independently
            # (the MIN:MAX range is clamped per group — see _role_range);
            # homogeneous fleets keep the exact round-18 validation
            n_max = autoscale.max_replicas or len(kvs)
            if roles is None and \
                    not autoscale.min_replicas <= n_max <= len(kvs):
                raise ValueError(
                    f"autoscale range {autoscale.min_replicas}:{n_max} "
                    f"must fit in the {len(kvs)}-replica set")
        if handoff_s < 0:
            raise ValueError(f"handoff_s must be >= 0, got {handoff_s}")
        # as in ContinuousBatcher: none passed = the process-wide recorder
        self.tracer = tracer = (tracer if tracer is not None
                                else recorder())
        base_clock = clock if clock is not None else WallClock()
        self.clock = _SharedClock(base_clock)
        if threaded is None:
            threaded = not isinstance(base_clock, VirtualClock)
        self.threaded = bool(threaded)
        if parallel_lanes:
            if not isinstance(base_clock, VirtualClock):
                raise ValueError(
                    "parallel_lanes needs a VirtualClock base (wall time "
                    "already overlaps replicas via threads)")
            if self.threaded:
                raise ValueError(
                    "parallel_lanes is a sequential-driver feature "
                    "(threaded=False)")
        self.roles = roles
        self.routing = routing
        self.autoscale = autoscale
        if multi_step is not None and int(multi_step) < 1:
            raise ValueError(
                f"multi_step must be >= 1, got {multi_step}")
        self.multi_step = None if multi_step is None else int(multi_step)
        self.handoff_s = float(handoff_s)
        self.parallel_lanes = bool(parallel_lanes)
        self.slo = slo
        self.retry_limit = int(retry_limit)
        self.retry_backoff_s = float(retry_backoff_s)
        self.watchdog_timeout_s = float(watchdog_timeout_s)
        self.fault_injector = fault_injector
        # --timeline: ONE shared sampler; per-replica series are keyed by
        # replica id (batchers tag their own series, the coordinator
        # samples fleet-level load/admitting/backlog gauges).  Concurrent
        # replica threads write DISTINCT series keys, so the host-side
        # ring writes never contend on one buffer.
        self.timeline = timeline
        # --roofline: ONE Roofline (device peaks + the analytic cost model
        # for the replicas' shared model) handed to every batcher; each
        # tallies its own host-side phase counters, and _summary sums
        # them across replicas flag-gated (key-set parity when off)
        self.roofline = roofline
        self.vocab = int(kvs[0].dm.vocab_size)
        self.draft_kvs = draft_kvs
        self._affinity_block = int(getattr(kvs[0], "prefix_block", 0) or 0)
        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        self._lanes: dict[int, _SharedClock] = {}
        self.replicas: list[_Replica] = []
        for i, kv in enumerate(kvs):
            registry = MetricsRegistry()
            replica = _Replica(i, kv, registry)
            role = None if roles is None else roles[i]
            replica.role = role
            rclock = self.clock
            if parallel_lanes:
                # each replica advances its own virtual lane; all lanes
                # share the epoch (start() zeroes them together in run())
                rclock = _SharedClock(VirtualClock(
                    tick=base_clock.tick,
                    prefill_token_tick=base_clock.prefill_token_tick))
                self._lanes[i] = rclock
            replica.batcher = ContinuousBatcher(
                kv, tracer=tracer, clock=rclock, mode="continuous",
                # decode replicas restore handed-off KV instead of
                # prefilling, and never shed (a handoff is admitted work)
                prefill_chunk=(0 if role == "decode" else prefill_chunk),
                metrics=registry,
                queue_cap=(0 if role == "decode" else queue_cap),
                should_stop=(lambda iters, r=replica:
                             self._replica_should_stop(r, iters)),
                draft_kv=(draft_kvs[i] if draft_kvs is not None else None),
                draft_k=draft_k, timeline=timeline, timeline_tag=i,
                role=role, roofline=roofline, multi_step=multi_step,
                handoff_out=(self._handoff_hook(replica)
                             if role == "prefill" else None))
            self.replicas.append(replica)
            if fault_injector is not None:
                fault_injector.arm(i, kv)
        # swap state survives _reset_run_state: schedule_swap may be
        # called BEFORE run(), and generations accumulate across windows
        self.swap_generations = 0
        self._swap: dict[str, Any] | None = None
        self._draining = 0
        # fleet-level ledgers, reset per run()
        self._reset_run_state()

    # ------------------------------------------------------------- state
    def _reset_run_state(self) -> None:
        self.journal: RequestJournal | None = None
        self.min_admitting_replicas: int | None = None
        self._failovers: list[dict[str, Any]] = []
        self._watchdog_stalls = 0
        self._preempted: str | None = None
        self._on_token: Callable[[int, int], None] | None = None
        self._sums: dict[str, float] = {}
        self._spec_sums: dict[str, int] = {}
        self._prefix_sums: dict[str, int] = {}
        self._phase_sums: dict[str, float] = {}
        # --roofline ledgers (identically empty flag-off): fleet totals of
        # the batchers' analytic counters + the same split per replica id
        self._rf_sums: dict[str, float] = {}
        self._rf_replica: dict[int, dict[str, float]] = {}
        self._shed_count = 0
        self._run_summaries = 0
        # round-18 per-run ledgers (all identically zero/empty flag-off)
        self._affinity: dict[bytes, int] = {}
        self._handoffs_initiated = 0
        self._handoffs_delivered = 0
        self._handoffs_dropped = 0
        self._replica_seconds = 0.0
        # per-role serving-time split (round 20): keys are replica roles
        # (None for a homogeneous fleet) — sums to _replica_seconds
        self._role_seconds: dict[str | None, float] = {}
        self._scale_ups = 0
        self._scale_downs = 0
        self._scale_events: list[dict[str, Any]] = []
        # per-role cooldown clocks (round 20): with roles each pool
        # scales on its own queue-watermark signal and cooldown — one
        # pool's action never starves the other's (homogeneous fleets
        # use the single None key, exactly the round-18 behavior)
        self._last_scale_t: dict[str | None, float] = {}
        self._slice_end: dict[int, float] = {}
        self._t_start = 0.0
        self._run_live = False

    def _serving(self) -> list[_Replica]:
        return [r for r in self.replicas if r.state == "serving"]

    def _clock_for(self, replica: _Replica):
        """The clock a replica's events are stamped with: its own lane
        under ``parallel_lanes``, the shared fleet clock otherwise."""
        return self._lanes.get(replica.id, self.clock)

    def _fleet_now(self) -> float:
        """Fleet time: max over replica lanes (a lane only ever jumps
        forward, so the max is monotone), or the shared clock."""
        if self._lanes:
            return max(lane.now() for lane in self._lanes.values())
        return self.clock.now()

    def _note_admitting(self) -> None:
        """Track the fleet's minimum admitting-replica count (serving and
        not draining) — the zero-downtime claim is measured, not assumed."""
        admitting = len(self._serving()) - self._draining
        if (self.min_admitting_replicas is None
                or admitting < self.min_admitting_replicas):
            self.min_admitting_replicas = admitting

    def _sample_timeline(self) -> None:
        """Fleet-level --timeline gauges, sampled by the run coordinator
        at its existing poll boundary: per-replica live load (a killed
        replica's lane drops to zero — the failover counter cliff the
        e2e test asserts), admitting-replica count, and the journal's
        retry backlog.  Pure host reads; None = off."""
        tl = self.timeline
        if tl is None or self.journal is None:
            return
        for r in self.replicas:
            load = (self.journal.load.get(r.id, 0)
                    if r.state == "serving" else 0)
            tl.sample("replica_load", load, replica=r.id)
        counts = self.journal.counts()
        gauges = {
            "admitting_replicas": len(self._serving()) - self._draining,
            "journal_pending": counts.get("pending", 0),
            "journal_retries": self.journal.requeues,
        }
        if self.roles is not None:
            # per-role load: where the fleet's live assignments sit —
            # the disaggregation dashboards' headline gauge pair
            for role in ("prefill", "decode"):
                gauges[f"{role}_load"] = sum(
                    self.journal.load.get(r.id, 0)
                    for r in self.replicas
                    if r.role == role and r.state == "serving")
        if self.autoscale is not None:
            gauges["serving_replicas"] = len(self._serving())
        tl.sample_many(gauges, group="fleet")

    def _replica_should_stop(self, replica: _Replica,
                             iters: int) -> str | None:
        """The per-replica drain hook — and the watchdog's heartbeat:
        the batcher consults it at every scheduler-loop iteration AND
        every idle poll slice, so a replica legitimately idling toward a
        future arrival keeps ticking while one wedged inside a device
        program (or an injected stall) freezes — exactly the distinction
        `busy` alone cannot make."""
        replica.last_progress = time.monotonic()
        reason = replica.lease.should_stop(iters)
        if reason is not None:
            return reason
        if self.autoscale is not None:
            end = self._slice_end.get(replica.id)
            if end is not None and self._clock_for(replica).now() >= end:
                # bounded serving slice: drain in-flight work and hand
                # control back so the autoscaler gets a decision point
                return "autoscale_slice"
        return None

    # ------------------------------------------------------------ routing
    def _route_candidates(self) -> list[_Replica]:
        """Replicas a fresh (or retried) request may land on: the whole
        serving set — or, disaggregated, the prefill side only (a resume
        re-prefills, so retries go there too)."""
        serving = self._serving()
        if self.roles is None:
            return serving
        return [r for r in serving if r.role == "prefill"]

    def _affinity_key(self, prompt) -> bytes | None:
        """The chained SHA-256 digest of the prompt's FIRST prefix block
        — the same key the prefix pool stores for that block, so routing
        on it lands a request where its shared prefix is already warm.
        None for prompts shorter than one block (nothing shareable to
        key on)."""
        blk = self._affinity_block
        p = np.asarray(prompt, np.int32).reshape(-1)
        if blk <= 0 or p.shape[0] < blk:
            return None
        h = hashlib.sha256(b"")
        h.update(p[:blk].tobytes())
        return h.digest()

    def _route(self, req: Request, retry: bool = False,
               from_replica: int | None = None,
               reason: str | None = None,
               at: float | None = None) -> bool:
        """Assign ``req`` among the route candidates — prefix-affinity
        first when enabled (a fresh request with a keyable first block
        follows earlier traffic with the same block), least-loaded
        otherwise; False when no replica can take it (the caller marks
        it lost)."""
        candidates = self._route_candidates()
        if not candidates:
            return False
        target = None
        if self.routing == "affinity" and not retry:
            key = self._affinity_key(req.prompt)
            if key is not None:
                by_id = {r.id: r for r in candidates}
                known = self._affinity.get(key)
                if known is not None and known in by_id:
                    target = by_id[known]
                else:
                    target = self.replicas[self.journal.least_loaded(
                        list(by_id))]
                    self._affinity[key] = target.id
        if target is None:
            target = self.replicas[self.journal.least_loaded(
                [r.id for r in candidates])]
        now = self.clock.now() if at is None else float(at)
        self.journal.assign(req.rid, target.id, now, retry=retry)
        if retry:
            entry = self.journal.entries[req.rid]
            backoff = (self.retry_backoff_s
                       * (2 ** max(entry.attempts - 2, 0)))
            req = dataclasses.replace(
                req, arrival_s=max(req.arrival_s, now + backoff))
            self.tracer.event(
                "requeue", rid=req.rid, from_replica=from_replica,
                to_replica=target.id, attempt=entry.attempts,
                arrival_s=entry.req.arrival_s, reason=reason,
                emitted=len(entry.emitted))
            self.tracer.counter("requeued_requests")
        target.queue.push(req)
        target.work.set()
        return True

    # ----------------------------------------------------------- emission
    def _emit_hook(self, replica: _Replica):
        def hook(rid: int, token: int) -> None:
            tok = int(token)
            if tok < 0 or tok >= self.vocab:
                # the cheap host check: two comparisons per token.  An id
                # outside the vocabulary is what nonfinite logits degrade
                # sampling into — fail the replica BEFORE delivery.
                raise CorruptionDetected(
                    f"replica {replica.id} emitted token id {tok} outside "
                    f"[0, {self.vocab}) for rid {rid} — nonfinite-logits "
                    f"corruption")
            accepted, done, _recovery = self.journal.emit(
                rid, replica.id, tok, self._clock_for(replica).now())
            replica.last_progress = time.monotonic()
            if not accepted:
                return   # fenced: counted by the journal, never delivered
            if self._on_token is not None:
                self._on_token(rid, tok)
            if done:
                replica.completed += 1
                with self._cond:
                    self._maybe_start_swap()
                    self._cond.notify_all()
        return hook

    # ----------------------------------------------------------- failover
    def _on_replica_failure(self, replica: _Replica, exc: BaseException,
                            kind: str | None = None) -> None:
        with self._lock:
            if replica.state == "failed":
                return   # watchdog + exception can race; first wins
            replica.state = "failed"
            replica.failure = f"{type(exc).__name__}: {exc}"
            self._note_admitting()
            now = self._clock_for(replica).now()
            if replica.serve_start is not None:
                self._replica_seconds += max(now - replica.serve_start, 0.0)
                replica.serve_start = None
            kind = kind or (
                "injected" if isinstance(exc, InjectedFault) else
                "corruption" if isinstance(exc, CorruptionDetected) else
                "crash")
            pending = self.journal.pending_for(replica.id)
            # fence first (a zombie's next emission must already be
            # stale), then requeue
            self.journal.mark_failed(pending, now)
            self.tracer.event("replica_failure", replica=replica.id,
                              kind=kind, error=replica.failure,
                              requests=len(pending))
            self.tracer.counter("replica_failures")
            self._failovers.append({
                "replica": replica.id, "kind": kind,
                "error": replica.failure, "t": now,
                "requeued": len(pending)})
            # a failed replica scheduled for a swap must not wedge the
            # rotation
            if self._swap is not None and self._swap.get("active") \
                    == replica.id:
                self._advance_swap()
            # queued-but-unadmitted requests still sit in its queue; the
            # journal assignment is the routing truth either way
            replica.queue.drain()
            for rid in pending:
                self._requeue(rid, replica.id,
                              reason=f"replica_failure:{kind}", at=now)
            self._cond.notify_all()

    def _requeue(self, rid: int, from_replica: int, reason: str,
                 at: float | None = None) -> None:
        entry = self.journal.entries[rid]
        retries_used = max(entry.attempts - 1, 0)
        if retries_used >= self.retry_limit:
            self.journal.finalize(rid, "lost")
            self.tracer.event("retry_exhausted", rid=rid,
                              attempts=entry.attempts,
                              limit=self.retry_limit)
            return
        req = self.journal.retry_request(rid)
        if req is None:
            return   # stream already complete — nothing to resume
        if not self._route(req, retry=True, from_replica=from_replica,
                           reason=reason, at=at):
            self.journal.finalize(rid, "lost")
            self.tracer.event("retry_exhausted", rid=rid,
                              attempts=entry.attempts,
                              limit=self.retry_limit,
                              error="no surviving replica")

    # ---------------------------------------------------------- handoff
    def _handoff_hook(self, replica: _Replica):
        """The prefill batcher's ``handoff_out`` callback (runs inline in
        the prefill replica's serving loop, right after the slot was
        extracted and evicted)."""
        def hook(req: Request, payload: dict[str, Any]) -> None:
            self._deliver_handoff(replica, req, payload)
        return hook

    def _deliver_handoff(self, src: _Replica, req: Request,
                         payload: dict[str, Any]) -> None:
        """Route a finished prefill's serialized KV to a decode replica.

        The payload rides the fleet queue inside the request
        (``Request.handoff``); the decode batcher restores it into a
        slot instead of prefilling.  Transfer takes ``handoff_s`` of
        fleet time, charged inside the request's TTFT (arrival →
        first-token, the PR 7 discipline).  With no decode replica
        serving, the handoff is DROPPED and the request re-enters the
        retry path (re-prefill on a surviving prefill replica) — the
        ledger identity ``initiated == delivered + dropped`` and the
        journal's single-phase accounting keep a dropped handoff from
        double-counting or vanishing."""
        with self._lock:
            self._handoffs_initiated += 1
            src_t = self._clock_for(src).now()
            decode = [r for r in self._serving() if r.role == "decode"]
            if not decode:
                self._handoffs_dropped += 1
                self.tracer.event("handoff_dropped", rid=req.rid,
                                  from_replica=src.id)
                self.tracer.counter("handoffs_dropped")
                # fence first (same discipline as failover), then retry
                self.journal.mark_failed([req.rid], src_t)
                self._requeue(req.rid, src.id, reason="handoff_no_decode",
                              at=src_t)
                return
            target = self.replicas[self.journal.least_loaded(
                [r.id for r in decode])]
            arrive = src_t + self.handoff_s
            # a transfer, not a retry: no attempt consumed, phase flips
            self.journal.assign(req.rid, target.id, arrive, transfer=True)
            self.journal.set_phase(req.rid, "decode")
            self._handoffs_delivered += 1
            hreq = dataclasses.replace(
                req, handoff=payload,
                arrival_s=max(req.arrival_s, arrive))
            self.tracer.event("kv_handoff", rid=req.rid,
                              from_replica=src.id, to_replica=target.id,
                              blocks=len(payload["blocks"]),
                              length=int(payload["length"]))
            self.tracer.counter("handoffs_delivered")
            target.queue.push(hreq)
            target.work.set()
            self._cond.notify_all()

    # ---------------------------------------------------------- autoscale
    def _autoscale_tick(self) -> None:
        """One scaling decision, evaluated at the run coordinator's poll
        boundary (threaded) or between sequential rounds.  At most one
        action per cooldown window: scale-up wakes ONE dormant replica
        when arrived backlog per admitting replica crosses the high
        watermark; scale-down retires ONE replica that held no arrived
        work for ``idle_s``.  Also re-arms every serving replica's
        bounded serving slice."""
        pol = self.autoscale
        if pol is None or self.journal is None:
            return
        with self._lock:
            serving = self._serving()
            if not serving:
                return
            now = self._fleet_now()
            for r in serving:
                self._slice_end[r.id] = (self._clock_for(r).now()
                                         + pol.slice_s)
            # the decision runs PER ROLE GROUP (round 20): a disaggregated
            # fleet's prefill and decode pools see different backlogs —
            # prefill queues hold routed arrivals, decode queues hold
            # handed-off streams — so each pool scales on its own
            # watermark signal, range, and cooldown.  A homogeneous fleet
            # has the single group None: exactly the round-18 decision.
            for role in self._role_groups():
                self._autoscale_tick_role(role, now)

    def _role_groups(self) -> list[str | None]:
        return ([None] if self.roles is None
                else sorted(set(self.roles)))

    def _role_range(self, role: str | None) -> tuple[int, int]:
        """The policy's MIN:MAX clamped to the role group's size (a 1:4
        policy over a 1P:3D split drives prefill at 1:1 and decode at
        1:3); at least one replica per group always serves — a pool
        scaled to zero could never observe the backlog that should wake
        it."""
        pol = self.autoscale
        group = [r for r in self.replicas if r.role == role]
        n_max = min(pol.max_replicas or len(group), len(group))
        n_min = max(min(pol.min_replicas, n_max), 1)
        return n_min, n_max

    def _autoscale_tick_role(self, role: str | None, now: float) -> None:
        pol = self.autoscale
        serving = [r for r in self._serving() if r.role == role]
        if not serving:
            return
        n_min, n_max = self._role_range(role)
        admitting = max(len(serving) - self._draining, 1)
        backlog = sum(r.queue.depth(now) for r in serving)
        # idle bookkeeping runs every tick (cooldown only gates the
        # actions, not the timers)
        idle = []
        for r in serving:
            if (r.queue.depth(now) == 0 and not r.busy
                    and not (self._swap is not None
                             and self._swap.get("active") == r.id)):
                if r.idle_since is None:
                    r.idle_since = now
                idle.append(r)
            else:
                r.idle_since = None
        last = self._last_scale_t.get(role)
        if last is not None and now - last < pol.cooldown_s:
            return
        if (backlog > pol.high_watermark * admitting
                and len(serving) < n_max):
            dormant = [r for r in self.replicas
                       if r.state == "dormant" and r.role == role]
            if dormant:
                self._scale_up(dormant[0], now, backlog)
                return
        if len(serving) > n_min:
            for r in reversed(idle):   # highest id retires first
                if now - r.idle_since >= pol.idle_s:
                    self._scale_down(r, now)
                    return

    def _scale_up(self, replica: _Replica, now: float,
                  backlog: int) -> None:
        """Wake a dormant replica and rebalance queued work over the
        grown fleet (routing happened upfront — without the rebalance
        the new replica would idle to the end of the trace)."""
        replica.state = "serving"
        replica.idle_since = None
        replica.serve_start = now
        self._scale_ups += 1
        self._last_scale_t[replica.role] = now
        event = {"action": "up", "replica": replica.id, "t": now,
                 "backlog": int(backlog), "serving": len(self._serving())}
        if self.roles is not None:
            event["role"] = replica.role
        self._scale_events.append(event)
        self.tracer.event("scale_up", replica=replica.id,
                          backlog=int(backlog),
                          serving=len(self._serving()))
        self.tracer.counter("scale_ups")
        # rebalance strictly WITHIN the role group: a woken decode
        # replica must never receive un-prefilled arrivals (and vice
        # versa) — role partitions are a routing invariant
        moved: list[Request] = []
        group = [r for r in self._serving() if r.role == replica.role]
        for r in group:
            if r.id != replica.id:
                moved.extend(r.queue.drain())
        serving_ids = [r.id for r in group]
        for req in sorted(moved, key=lambda q: (q.arrival_s, q.rid)):
            target = self.replicas[self.journal.least_loaded(serving_ids)]
            self.journal.assign(req.rid, target.id, now, transfer=True)
            target.queue.push(req)
            target.work.set()
        if self.threaded and self._run_live:
            self._start_worker(replica)

    def _scale_down(self, replica: _Replica, now: float) -> None:
        """Retire one idle serving replica; its not-yet-arrived
        assignments transfer to the survivors (a transfer, not a retry —
        no attempt consumed)."""
        replica.state = "dormant"
        replica.idle_since = None
        if replica.serve_start is not None:
            span = max(now - replica.serve_start, 0.0)
            self._replica_seconds += span
            self._role_seconds[replica.role] = (
                self._role_seconds.get(replica.role, 0.0) + span)
            replica.serve_start = None
        self._scale_downs += 1
        self._last_scale_t[replica.role] = now
        event = {"action": "down", "replica": replica.id, "t": now,
                 "serving": len(self._serving())}
        if self.roles is not None:
            event["role"] = replica.role
        self._scale_events.append(event)
        self.tracer.event("scale_down", replica=replica.id,
                          serving=len(self._serving()))
        self.tracer.counter("scale_downs")
        replica.work.set()   # the worker observes dormant and exits
        leftovers = replica.queue.drain()
        serving_ids = [r.id for r in self._serving()
                       if r.role == replica.role]
        for req in sorted(leftovers, key=lambda q: (q.arrival_s, q.rid)):
            if not serving_ids:
                self.journal.finalize(req.rid, "lost")
                continue
            target = self.replicas[self.journal.least_loaded(serving_ids)]
            self.journal.assign(req.rid, target.id, now, transfer=True)
            target.queue.push(req)
            target.work.set()

    # ---------------------------------------------------------- hot swap
    def schedule_swap(self, params, draft_params=None, *,
                      after_completions: int = 0) -> None:
        """Schedule a zero-downtime weight hot-swap: once
        ``after_completions`` requests have completed fleet-wide (0 =
        immediately), replicas drain and swap one at a time — the fleet
        never drops below N−1 admitting replicas.  Call before or during
        ``run``; ``swap_generations`` increments when every serving
        replica carries the new weights."""
        with self._lock:
            if self._swap is not None:
                raise RuntimeError("a weight swap is already in flight")
            self._swap = {"params": params, "draft_params": draft_params,
                          "after": int(after_completions),
                          "queue": None, "active": None}
            self._maybe_start_swap()

    def _maybe_start_swap(self) -> None:
        sw = self._swap
        if sw is None or sw["queue"] is not None or self.journal is None:
            return
        if self.journal.done_count < sw["after"]:
            return
        sw["queue"] = [r.id for r in self._serving()]
        self._advance_swap()

    def _advance_swap(self) -> None:
        sw = self._swap
        if sw is None:
            return
        if sw["active"] is not None:
            self._draining -= 1
            sw["active"] = None
        while sw["queue"]:
            rid = sw["queue"].pop(0)
            replica = self.replicas[rid]
            if replica.state != "serving":
                continue
            sw["active"] = rid
            self._draining += 1
            self._note_admitting()
            replica.lease.trigger("weight_swap")
            replica.work.set()
            return
        # rotation complete: one whole fleet generation
        self.swap_generations += 1
        self.tracer.event("weight_swap_generation",
                          generation=self.swap_generations)
        self._swap = None
        self._cond.notify_all()

    def _finish_pending_swap(self) -> None:
        """Complete a STARTED swap rotation once serving work is done:
        every remaining replica is idle, so each turn installs the new
        weights with nothing in flight.  A trigger can land exactly as
        the active replica's run loop empties — the run then exits
        without the drain marker, and without this sweep the rotation
        would stall one replica short of a generation."""
        for _ in range(len(self.replicas) + 1):
            with self._lock:
                sw = self._swap
                if sw is None or sw.get("queue") is None \
                        or sw.get("active") is None:
                    return
                active = self.replicas[sw["active"]]
            self._perform_swap(active)

    def _perform_swap(self, replica: _Replica) -> None:
        """The drained replica installs the new weights between compiled-
        program dispatches and resumes serving on the same lease."""
        with self._lock:
            sw = self._swap
            if sw is None or sw["active"] != replica.id:
                return
            # the first replica to swap narrows the checkpoint
            # (SlotKVCache._place_params); the others are handed its
            # tree and use it in place: one set of buffers a device
            replica.kv.swap_params(sw["params"])
            sw["params"] = replica.kv.params
            if self.draft_kvs is not None and sw["draft_params"] is not None:
                draft_kv = self.draft_kvs[replica.id]
                draft_kv.swap_params(sw["draft_params"])
                sw["draft_params"] = draft_kv.params
            replica.lease.reset_trigger()
            replica.generation += 1
            self.tracer.event("weight_swap", replica=replica.id,
                              generation=replica.generation)
            self._advance_swap()
            replica.work.set()

    # --------------------------------------------------------- the loop
    def _serve_once(self, replica: _Replica) -> None:
        """One batcher run over the replica's queue; failures fail over,
        a weight_swap drain performs the swap and leaves the leftover
        queue for the next run."""
        replica.busy = True
        replica.last_progress = time.monotonic()
        try:
            summary = replica.batcher.run(
                replica.queue, on_token=self._emit_hook(replica))
        except BaseException as e:  # noqa: BLE001 — any death fails over
            replica.busy = False
            self._on_replica_failure(replica, e)
            return
        replica.busy = False
        if replica.state == "failed":
            # a fenced zombie's late summary is not fleet truth: the
            # watchdog already failed this replica over mid-run, its
            # requests were requeued, and absorbing would double-count
            # the ledgers — worse, its shed_rids would finalize requests
            # a survivor now owns, truncating their streams
            return
        self._absorb(replica, summary)
        if summary.get("preempted") == "weight_swap":
            self._perform_swap(replica)
        elif summary.get("preempted") == "autoscale_slice":
            # benign: the slice expired; dis-arm it so the next run is
            # not preempted on entry (the next tick re-arms)
            self._slice_end.pop(replica.id, None)
        with self._cond:
            self._cond.notify_all()

    def _absorb(self, replica: _Replica, s: dict[str, Any]) -> None:
        """Fold one successful run summary into the fleet ledgers (a run
        that died contributes nothing here; the journal still has every
        delivered token)."""
        with self._lock:
            self._run_summaries += 1
            for k in ("decode_iterations", "prefills", "prefill_chunks",
                      "prefill_tokens", "decode_tokens", "idle_polls"):
                self._sums[k] = self._sums.get(k, 0) + (s.get(k) or 0)
            spec = s.get("speculative")
            if spec:
                for k in ("proposed_tokens", "accepted_tokens",
                          "rejected_tokens", "draft_iterations",
                          "draft_catchup_steps"):
                    self._spec_sums[k] = (self._spec_sums.get(k, 0)
                                          + spec.get(k, 0))
            pc = s.get("prefix_cache")
            if pc:
                for k, v in pc.items():
                    if isinstance(v, int):
                        self._prefix_sums[k] = (self._prefix_sums.get(k, 0)
                                                + v)
            for k, v in (s.get("device_phase_s") or {}).items():
                self._phase_sums[k] = self._phase_sums.get(k, 0.0) + v
            # multi-step dispatch ledger (keys absent flag-off): host
            # dispatches and host-gap seconds sum across replica windows
            if "serve_dispatches" in s:
                self._sums["serve_dispatches"] = (
                    self._sums.get("serve_dispatches", 0)
                    + (s.get("serve_dispatches") or 0))
                self._sums["serve_host_gap_s"] = (
                    self._sums.get("serve_host_gap_s", 0.0)
                    + (s.get("serve_host_gap_s") or 0.0))
            rf = s.get("roofline")
            if rf:
                per = self._rf_replica.setdefault(replica.id, {})
                for k in ("prefill_model_flops", "decode_model_flops",
                          "decode_must_read_bytes", "prefill_s",
                          "decode_s"):
                    v = float(rf.get(k) or 0.0)
                    self._rf_sums[k] = self._rf_sums.get(k, 0.0) + v
                    per[k] = per.get(k, 0.0) + v
            self._shed_count += s.get("shed_requests") or 0
            for rid in s.get("shed_rids") or ():
                self.journal.finalize_if_assigned(rid, replica.id, "shed")

    # sequential (deterministic) driver -------------------------------
    def _run_sequential(self, should_stop) -> None:
        while True:
            if should_stop is not None and self._preempted is None:
                reason = should_stop(0)
                if reason:
                    self._preempted = reason
                    break
            progressed = False
            self._sample_timeline()
            self._autoscale_tick()
            for replica in self.replicas:
                if replica.state != "serving":
                    continue
                if self._swap is not None \
                        and self._swap.get("active") == replica.id \
                        and not len(replica.queue):
                    # idle replica's swap turn: nothing in flight to drain
                    self._perform_swap(replica)
                if len(replica.queue):
                    progressed = True
                    self._serve_once(replica)
            if self.journal.all_terminal():
                break
            if not progressed:
                # no serving replica holds work but entries are pending —
                # every assignment points at a corpse (requeue already
                # exhausted or raced); terminal-ize so conservation holds
                for rid, e in self.journal.entries.items():
                    if e.status == "pending":
                        self.journal.finalize(rid, "lost")
                break

    # threaded driver --------------------------------------------------
    def _worker(self, replica: _Replica) -> None:
        while True:
            if replica.state != "serving":
                return
            if self._preempted is not None:
                # fleet drain: the current run already finished in-flight
                # (its lease was triggered); do not restart over the
                # leftover queue — those are the drain's unserved
                return
            with self._lock:
                if self._swap is not None \
                        and self._swap.get("active") == replica.id \
                        and not len(replica.queue) and not replica.busy:
                    pass_swap = True
                else:
                    pass_swap = False
            if pass_swap:
                self._perform_swap(replica)
                continue
            if replica.stop.is_set():
                return
            if not len(replica.queue):
                replica.work.wait(0.02)
                replica.work.clear()
                continue
            self._serve_once(replica)

    def _watchdog(self) -> None:
        timeout = self.watchdog_timeout_s
        while not self._wd_stop.wait(timeout / 4):
            for replica in self._serving():
                if replica.busy and (time.monotonic()
                                     - replica.last_progress) > timeout:
                    self._watchdog_stalls += 1
                    # fence + requeue; the zombie thread keeps running
                    # until it wakes, at which point its lease drains it
                    # and its emissions are already stale
                    replica.lease.trigger("watchdog_stall")
                    self._on_replica_failure(
                        replica,
                        TimeoutError(f"no progress for >{timeout}s"),
                        kind="watchdog_stall")

    # ----------------------------------------------------------- run
    def run(self, requests: Iterable[Request],
            on_token: Callable[[int, int], None] | None = None,
            should_stop: Callable[[int], str | None] | None = None,
            ) -> dict[str, Any]:
        """Serve every offered request to terminal state across the
        fleet; returns the fleet summary (serve-section compatible, plus
        ``serve_fleet``)."""
        requests = sorted(requests, key=lambda r: (r.arrival_s, r.rid))
        self._reset_run_state()
        for replica in self.replicas:
            # a previous run's shutdown left stop set; surviving replicas
            # serve again (failed ones stay dead — state is the gate)
            replica.stop.clear()
            replica.work.clear()
            # fresh per-run histograms: this run's summary must describe
            # THIS window (the ContinuousBatcher per-run-registry
            # convention) — the batcher merges its per-run records into
            # whatever registry it holds, so swap in a new one per run
            replica.registry = MetricsRegistry()
            replica.batcher.metrics = replica.registry
        self.journal = RequestJournal(requests)
        self._on_token = on_token
        offered = len(requests)
        if self.autoscale is not None:
            # start at the floor, PER ROLE GROUP; the rest of the set
            # sleeps until queue pressure wakes it (failed replicas stay
            # dead).  Homogeneous fleets have one group (None) and keep
            # the exact round-18 floor.
            for role in self._role_groups():
                n_min, _ = self._role_range(role)
                live = [r for r in self.replicas
                        if r.state != "failed" and r.role == role]
                for idx, replica in enumerate(live):
                    replica.state = ("serving" if idx < n_min
                                     else "dormant")
        self.min_admitting_replicas = len(self._serving())
        if self.slo is not None:
            self.slo.reset()
        self.clock.start()
        for lane in self._lanes.values():
            lane.start()   # every lane shares the run epoch
        t_start = self._t_start = self._fleet_now()
        for replica in self.replicas:
            replica.idle_since = None
            replica.serve_start = (t_start if replica.state == "serving"
                                   else None)
        for req in requests:
            if not self._route(req):
                self.journal.finalize(req.rid, "lost")
        with self._lock:
            self._maybe_start_swap()   # after_completions == 0 case
            self._autoscale_tick()     # arm the first serving slices
        if self.threaded:
            self._run_live = True
            self._wd_stop = threading.Event()
            wd = None
            if self.watchdog_timeout_s > 0:
                wd = threading.Thread(target=self._watchdog, daemon=True)
                wd.start()
            for replica in self._serving():
                self._start_worker(replica)
            try:
                with self._cond:
                    while not self.journal.all_terminal():
                        if should_stop is not None \
                                and self._preempted is None:
                            reason = should_stop(0)
                            if reason:
                                self._preempted = reason
                                for replica in self._serving():
                                    replica.lease.trigger(reason)
                                    replica.work.set()
                        if self._preempted is not None and not any(
                                r.busy for r in self.replicas):
                            break
                        if not self._serving():
                            break
                        self._sample_timeline()
                        self._autoscale_tick()
                        self._cond.wait(0.05)
            finally:
                self._run_live = False
                self._wd_stop.set()
                for replica in self.replicas:
                    replica.stop.set()
                    replica.work.set()
                for replica in self.replicas:
                    if replica.thread is not None:
                        # a stalled zombie may be asleep inside an
                        # injected fault; it is fenced and daemonized —
                        # do not hang the fleet on it
                        replica.thread.join(timeout=1.0)
                if wd is not None:
                    wd.join(timeout=1.0)
        else:
            self._run_sequential(should_stop)
        if self._preempted is None:
            self._finish_pending_swap()
        # terminal sweep: anything still pending (fleet drain, stop with
        # no survivors) is unserved — conservation stays exact
        for rid, e in list(self.journal.entries.items()):
            if e.status == "pending":
                self.journal.finalize(rid, "unserved")
        if self._preempted:
            self.tracer.event("serve_preempted", reason=self._preempted,
                              completed=self.journal.counts()["done"],
                              unserved=self.journal.counts()["unserved"])
        self._sample_timeline()   # final state (post-failover cliffs)
        elapsed = self._fleet_now() - t_start
        return self._summary(offered, elapsed)

    def _start_worker(self, replica: _Replica) -> None:
        replica.thread = threading.Thread(
            target=self._worker, args=(replica,), daemon=True)
        replica.thread.start()

    def close(self, timeout_s: float = 10.0) -> None:
        """Join worker threads left behind by ``run`` (a fenced zombie —
        e.g. a stalled replica sleeping through its watchdog failover —
        keeps running until it wakes; its emissions are already rejected,
        but a clean shutdown should wait it out rather than let the
        interpreter tear down under a live XLA dispatch)."""
        deadline = time.monotonic() + timeout_s
        for replica in self.replicas:
            replica.stop.set()
            replica.work.set()
        for replica in self.replicas:
            t = replica.thread
            if t is not None and t.is_alive():
                t.join(timeout=max(deadline - time.monotonic(), 0.0))

    # ----------------------------------------------------------- summary
    def _summary(self, offered: int, elapsed: float) -> dict[str, Any]:
        journal = self.journal
        results = journal.results()
        counts = journal.counts()
        tracer_stats = self.tracer.stats() or {}
        ttfts = [r.ttft_s for r in results]
        itls = [g for r in results for g in r.itl_s]
        tokens = sum(len(e.emitted) for e in journal.entries.values()
                     if e.emitted)
        # merged per-replica histograms: the PR 11 aggregation substrate —
        # windows → runs → FLEET, by bucket-count addition, no resampling
        merged = MetricsRegistry()
        for replica in self.replicas:
            merged.merge(replica.registry)
        # fleet-level goodput: every completed request judged on its
        # journal timeline (TTFT from original arrival), per replica and
        # merged — a retried request counts ONCE, for the replica that
        # finished it
        per_replica = []
        slo = self.slo
        fleet_good = 0
        for replica in self.replicas:
            done = [r for r in results
                    if journal.entries[r.rid].completed_by == replica.id]
            good = None
            if slo is not None:
                good = sum(
                    1 for r in done
                    if r.ttft_s <= slo.ttft_s
                    and ((exact_percentile(r.itl_s, slo.quantile)
                          or 0.0) <= slo.itl_s))
                fleet_good += good
            per_replica.append({
                "replica": replica.id,
                "state": replica.state,
                "failure": replica.failure,
                "completed": len(done),
                "tokens": sum(len(r.tokens) for r in done),
                "generation": replica.generation,
                "goodput_requests_per_sec": (
                    good / elapsed
                    if good is not None and elapsed > 0 else None),
            })
        slo_sec = None
        if slo is not None:
            slo.reset()
            for r in results:
                slo.observe(r.ttft_s, r.itl_s)
            slo.shed(counts["shed"])
            slo_sec = slo.summary(elapsed)
        recovery = list(journal.recovery_s)
        unserved = counts["lost"] + counts["unserved"]
        depth_hwm = max((r.queue.depth_high_watermark
                         for r in self.replicas), default=0)
        prefix_sec = None
        hit_rate = None
        if self._prefix_sums:
            prefix_sec = dict(self._prefix_sums)
            asked = prefix_sec.get("hits", 0) + prefix_sec.get("misses", 0)
            hit_rate = prefix_sec["hits"] / asked if asked else 0.0
        spec_sec = None
        accept_rate = None
        if self.draft_kvs is not None:
            spec_sec = dict(self._spec_sums)
            proposed = spec_sec.get("proposed_tokens", 0)
            accept_rate = (spec_sec.get("accepted_tokens", 0) / proposed
                           if proposed else None)
        qw = merged.histogram("queue_wait")
        qd = merged.histogram("queue_depth")
        prefill_tokens = int(self._sums.get("prefill_tokens", 0))
        decode_tokens = int(self._sums.get("decode_tokens", 0))
        summary = {
            "mode": "fleet",
            "replicas": len(self.replicas),
            "requests": len(results),
            "completed": counts["done"],
            "serve_kv_dtype": self.replicas[0].kv.kv_dtype,
            "serve_kv_bytes_per_slot":
                self.replicas[0].kv.kv_bytes_per_slot(),
            "serve_param_bytes": self.replicas[0].kv.param_bytes,
            "serve_accept_rate": accept_rate,
            "speculative": spec_sec,
            "decode_iterations": int(self._sums.get(
                "decode_iterations", 0)),
            "prefills": int(self._sums.get("prefills", 0)),
            "prefill_chunk": max(r.batcher.prefill_chunk
                                 for r in self.replicas),
            "prefill_chunks": int(self._sums.get("prefill_chunks", 0)),
            "prefill_tokens": prefill_tokens,
            "decode_tokens": decode_tokens,
            "idle_polls": int(self._sums.get("idle_polls", 0)),
            "tokens_generated": tokens,
            "elapsed_s": elapsed,
            "serve_requests_per_sec": (counts["done"] / elapsed
                                       if elapsed > 0 else None),
            "serve_tokens_per_sec": (tokens / elapsed
                                     if elapsed > 0 else None),
            "serve_prefill_tokens_per_sec": (prefill_tokens / elapsed
                                             if elapsed > 0 else None),
            "serve_decode_tokens_per_sec": (decode_tokens / elapsed
                                            if elapsed > 0 else None),
            "serve_prefix_cache_hit_rate": hit_rate,
            "prefix_cache": prefix_sec,
            "serve_ttft_p50_s": exact_percentile(ttfts, 0.50),
            "serve_ttft_p95_s": exact_percentile(ttfts, 0.95),
            "serve_ttft_p99_s": exact_percentile(ttfts, 0.99),
            "serve_itl_p50_s": exact_percentile(itls, 0.50),
            "serve_itl_p95_s": exact_percentile(itls, 0.95),
            "serve_itl_p99_s": exact_percentile(itls, 0.99),
            # attempt-level queue waits from the merged replica histograms
            # (each admission's claim wait on ITS replica's clock — the
            # fleet-level TTFT above is the original-arrival number)
            "serve_queue_wait_p50_s": qw.quantile(0.50),
            "serve_queue_wait_p95_s": qw.quantile(0.95),
            "serve_queue_wait_p99_s": qw.quantile(0.99),
            "queue_depth_p95": qd.quantile(0.95),
            "queue_depth_high_watermark": depth_hwm,
            "queue_cap": max(r.batcher.queue_cap for r in self.replicas),
            "offered": offered,
            "admitted": counts["done"],
            "shed_requests": counts["shed"],
            "unserved_requests": unserved,
            "serve_shed_rate": (counts["shed"] / offered
                                if offered else 0.0),
            "preempted": self._preempted,
            "serve_goodput_under_slo": (
                (slo_sec or {}).get("goodput_requests_per_sec")
                if slo_sec else None),
            "slo": slo_sec,
            "histograms": merged.snapshot(),
            "device_phase_s": dict(self._phase_sums),
            # fleet robustness headline keys (gated by `analyze diff`):
            # recovery time = replica-failure detection → the failed-over
            # request's first post-requeue delivery; duplicates == 0 is
            # the measured exactly-once claim
            "serve_failover_recovery_p95_s": exact_percentile(
                recovery, 0.95),
            "serve_duplicate_emissions": journal.duplicate_emissions,
            "serve_fleet": {
                "replicas": len(self.replicas),
                "serving_replicas": len(self._serving()),
                "failed_replicas": [r.id for r in self.replicas
                                    if r.state == "failed"],
                "failovers": len(self._failovers),
                "failover_events": self._failovers[:32],
                "retries": journal.requeues,
                "requeued_requests": len(journal.requeued_rids),
                "lost_requests": counts["lost"],
                "duplicate_emissions": journal.duplicate_emissions,
                "fenced_emissions": journal.fenced_emissions,
                "watchdog_stalls": self._watchdog_stalls,
                "faults_injected": (list(self.fault_injector.fired)
                                    if self.fault_injector is not None
                                    else []),
                "swap_generations": self.swap_generations,
                "min_admitting_replicas": self.min_admitting_replicas,
                "failover_recovery_s": recovery[:128],
                "failover_recovery_p95_s": exact_percentile(
                    recovery, 0.95),
                "per_replica": per_replica,
                "merged_goodput_under_slo": (
                    fleet_good / elapsed
                    if slo is not None and elapsed > 0 else None),
                # telemetry self-accounting (the fleet shares ONE tracer
                # across replica workers): sink drop counter + span-
                # bookkeeping overhead, both gated lower-is-better — a
                # fleet that drops trace records under load is flying a
                # partial instrument panel
                "sink_dropped": tracer_stats.get("dropped", 0),
                "sink_written": tracer_stats.get("written", 0),
                "trace_overhead_s": tracer_stats.get("overhead_s", 0.0),
            },
            "results": results,
        }
        if self.timeline is not None:
            # timeline-derived fleet keys only when sampling is on — the
            # flag-off key set stays byte-identical (parity pin)
            summary["queue_depth_auc"] = sum(
                filter(None, (self.timeline.stat("queue_depth", "auc",
                                                 replica=r.id)
                              for r in self.replicas))) or None
            summary["timeline_overhead_s"] = self.timeline.overhead_s
        if self.roofline is not None:
            # --roofline fleet keys only when attached (flag-off parity
            # pin).  Totals are the replica batchers' analytic counters
            # summed; the achieved rate divides total model work by total
            # per-replica device seconds, so the MFU/MBU headline is the
            # MEAN utilization of a serving replica — each replica runs
            # on the roofline's n_devices.  Unknown device kind → None.
            rf = self.roofline
            pre_s = self._rf_sums.get("prefill_s", 0.0)
            dec_s = self._rf_sums.get("decode_s", 0.0)
            pre_fps = (self._rf_sums.get("prefill_model_flops", 0.0)
                       / pre_s if pre_s > 0 else None)
            dec_fps = (self._rf_sums.get("decode_model_flops", 0.0)
                       / dec_s if dec_s > 0 else None)
            dec_bps = (self._rf_sums.get("decode_must_read_bytes", 0.0)
                       / dec_s if dec_s > 0 else None)
            summary["serve_prefill_mfu"] = rf.mfu(pre_fps)
            summary["serve_decode_mbu"] = rf.mbu(dec_bps)
            summary["roofline"] = {
                "prefill_model_flops": self._rf_sums.get(
                    "prefill_model_flops", 0.0),
                "decode_model_flops": self._rf_sums.get(
                    "decode_model_flops", 0.0),
                "decode_must_read_bytes": self._rf_sums.get(
                    "decode_must_read_bytes", 0.0),
                "prefill_s": pre_s,
                "decode_s": dec_s,
                "prefill_achieved_flops_per_sec": pre_fps,
                "decode_achieved_flops_per_sec": dec_fps,
                "decode_achieved_bytes_per_sec": dec_bps,
                "prefill_mfu": rf.mfu(pre_fps),
                "decode_mfu": rf.mfu(dec_fps),
                "decode_mbu": rf.mbu(dec_bps),
                "per_replica": [
                    {"replica": rid, **counters}
                    for rid, counters in sorted(
                        self._rf_replica.items())],
                "device": rf.describe(),
            }
        # ---- round-18 keys, each gated on its feature so the flag-off
        # summary key set stays byte-identical to round 17 (parity pin)
        if (self.roles is not None or self.autoscale is not None
                or self.parallel_lanes):
            end = self._t_start + elapsed
            summary["serve_replica_seconds"] = self._replica_seconds + sum(
                max(end - r.serve_start, 0.0) for r in self.replicas
                if r.serve_start is not None)
            if self.roles is not None:
                # per-role split (round 20): the capacity bill behind a
                # disaggregated + autoscaled fleet — which POOL the
                # replica-seconds went to; the two keys sum to
                # serve_replica_seconds exactly
                for role in self._role_groups():
                    summary[f"serve_replica_seconds_{role}"] = (
                        self._role_seconds.get(role, 0.0) + sum(
                            max(end - r.serve_start, 0.0)
                            for r in self.replicas
                            if r.role == role
                            and r.serve_start is not None))
        if self.parallel_lanes:
            summary["serve_parallel_lanes"] = True
        if self.routing != "least-loaded":
            summary["serve_routing"] = self.routing
            # the fleet-wide hit rate under THIS router, on this trace —
            # the number `analyze diff` gates against a least-loaded
            # baseline window of the same seeded trace
            summary["serve_fleet_prefix_hit_rate"] = hit_rate
        if self.roles is not None:
            role_counts = journal.role_counts()
            # per-role conservation: phase is single-valued, so the two
            # partitions sum to the fleet identity admitted+shed+unserved
            # == offered exactly — a dropped handoff flips its request
            # back to the prefill partition, counted once, never twice
            summary["serve_disagg"] = {
                "prefill_replicas": sum(1 for r in self.replicas
                                        if r.role == "prefill"),
                "decode_replicas": sum(1 for r in self.replicas
                                       if r.role == "decode"),
                "handoff_s": self.handoff_s,
                "handoffs_initiated": self._handoffs_initiated,
                "handoffs_delivered": self._handoffs_delivered,
                "handoffs_dropped": self._handoffs_dropped,
                "per_role": role_counts,
            }
        if self.autoscale is not None:
            pol = self.autoscale
            summary["autoscale"] = {
                "min_replicas": pol.min_replicas,
                "max_replicas": pol.max_replicas or len(self.replicas),
                "high_watermark": pol.high_watermark,
                "scale_ups": self._scale_ups,
                "scale_downs": self._scale_downs,
                "events": self._scale_events[:64],
                "serving_replicas_final": len(self._serving()),
            }
            if self.roles is not None:
                # the clamped per-pool ranges the tick actually drives
                summary["autoscale"]["per_role"] = {
                    role: {"min_replicas": rng[0], "max_replicas": rng[1],
                           "serving_final": sum(
                               1 for r in self._serving()
                               if r.role == role)}
                    for role in self._role_groups()
                    for rng in (self._role_range(role),)}
        if self.multi_step is not None:
            # multi-step keys ride ONLY flag-on (the flag-off fleet
            # summary key set stays byte-identical to round 19): total
            # host dispatches and host-gap seconds across every replica
            # window, same vocabulary as the single-batcher summary
            summary["serve_multi_step"] = self.multi_step
            summary["serve_dispatches"] = int(
                self._sums.get("serve_dispatches", 0))
            summary["serve_host_gap_s"] = float(
                self._sums.get("serve_host_gap_s", 0.0))
            if self.roofline is not None:
                summary["roofline"]["dispatches"] = \
                    summary["serve_dispatches"]
                summary["roofline"]["host_gap_s"] = \
                    summary["serve_host_gap_s"]
        return summary


# re-exported convenience: a fleet built from one (model, params) pair
def build_replica_kvs(model, params, n_replicas: int, slots: int,
                      **kv_kwargs) -> list[SlotKVCache]:
    """N independent slot tables over shared params (replicated params
    share device buffers; each replica owns its KV memory).  The first
    table narrows ``params`` to what the step uses
    (``SlotKVCache._place_params``) and the others take ITS tree, which
    they use in place.  n == 0 is legal and returns [] — callers
    extending an already-built first table pass n_replicas - 1 and that
    table's ``params``."""
    if n_replicas < 0:
        raise ValueError(f"n_replicas must be >= 0, got {n_replicas}")
    kvs: list[SlotKVCache] = []
    for _ in range(n_replicas):
        kvs.append(SlotKVCache(model, kvs[0].params if kvs else params,
                               slots, **kv_kwargs))
    return kvs


__all__ = [
    "AutoscalePolicy",
    "CorruptionDetected",
    "FaultInjector",
    "FaultSpec",
    "InjectedFault",
    "ReplicaSet",
    "RequestJournal",
    "build_replica_kvs",
]
