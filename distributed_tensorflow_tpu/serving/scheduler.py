"""Continuous batcher: the request-scheduling half of the serving engine.

The loop is the Orca/vLLM iteration-level scheduler shape: between decode
iterations it (a) admits arrived requests into free KV slots (jitted
prefill-insert, never recompiling the decode step), (b) runs ONE decode
iteration over the whole slot table, and (c) evicts finished slots so the
next arrivals claim them mid-flight.  ``mode='static'`` degrades the same
loop to the restart-per-batch ``generate`` baseline — admission only when
the table is empty — so continuous-vs-static comparisons share every line
of device code and the decode-iteration counter is directly comparable.

``prefill_chunk > 0`` enables Sarathi-Serve-style chunked prefill
(arXiv:2403.02310): admission only CLAIMS the slot; the prompt then fills
in ≤budget-token chunks, at most one chunk per loop iteration, so a long
prompt's prefill is spread across decode iterations instead of stalling
every live slot in one gap.  The first token is still sampled by the
final chunk — TTFT keeps its arrival→first-token meaning, queue wait and
chunk wait both included.  The summary splits throughput into
``serve_prefill_tokens_per_sec`` / ``serve_decode_tokens_per_sec`` and,
when the SlotKVCache's prefix pool is on, carries the run's block-level
``serve_prefix_cache_hit_rate`` with the hit/miss/evict ledger.

The request queue rebuilds the claim discipline of the unwired native
batch pipeline (native/batcher.py): one consumer claims the queue for a
run and releases it deterministically on exit, so two schedulers can never
interleave admissions from the same queue (the _EpochIterator busy-claim
contract, rebuilt in Python because requests arrive one at a time rather
than as a C++ epoch cursor).

Latency accounting follows the MLPerf inference convention (Mattson et
al., arXiv:1910.01500 — latency percentiles as machine-checked numbers):
TTFT is arrival→first-token (queue wait INCLUDED — an admitted-late
request is a slow request), ITL is the gap between consecutive token
deliveries, and both report p50/p95/p99 over the whole run.  Every request
emits ``request``/``prefill`` trace spans, and every round a
``decode_step`` (inside it the table's ``step_dispatch`` and
``token_fetch``), through the existing observability stack, so `analyze spans` and the Perfetto export read
serving timelines with no new machinery.

Round 13 makes the batcher service-grade observable — all host-side, so
the compiled program set and the greedy tokens stay byte-identical:

* **per-phase attribution**: each request's queue wait (arrival→claim),
  prefill (claim→first token, chunk wait included) and decode gaps land
  in a streaming log-bucketed histogram registry
  (observability/metrics.py — O(1) record, online p50/p95/p99,
  mergeable across windows) AND as attrs on the ``request`` span, which
  is what ``analyze serve`` renders as a per-request waterfall;
* **goodput under SLO**: an attached ``SLOMonitor``
  (observability/slo.py) judges every completed request against TTFT +
  ITL targets and the summary carries ``serve_goodput_under_slo`` —
  requests/sec that met BOTH, the MLPerf/Sarathi-Serve headline;
* **bounded-admission overload mode** (``queue_cap > 0``): arrived
  backlog past the cap is shed with exact 429 accounting
  (``shed_requests``/``serve_shed_rate``, a structured ``overload``
  trace event per rejection, admitted + shed + unserved == offered), so
  an overloaded batcher degrades to bounded queue wait instead of
  unbounded TTFT;
* **lease drain** (``should_stop``): the PR 9 preemption hook — a
  SIGTERM'd serve window stops admitting, finishes in-flight requests
  and flushes a consistent partial summary (``preempted`` names why).

Round 14 attacks the decode step itself — **greedy-exact speculative
decoding** (``draft_kv``/``draft_k``; Leviathan et al., arXiv:2211.17192):
a draft model's own SlotKVCache runs in slot lockstep (admitted/evicted
with the target), each iteration becomes draft-k → verify-1 (k draft
steps propose, ONE batched target step scores all k+1 positions), and
greedy acceptance — accept while draft token == target argmax, then take
the target's token — makes the emitted stream **bitwise identical** to
non-speculative decode; speculation changes iteration counts, never
tokens.  Rollback of rejected positions is pure length bookkeeping on
both tables (no KV rewrite).  ``serve_accept_rate`` + the
proposed/accepted/rejected ledger (exact conservation) ride the summary;
``serve_tokens_per_sec`` stays emitted-tokens-only, and ITL gaps are
attributed per emitted token (a round's batch-mates land at gap 0), so
the SLO math stays honest.

Clocks are injectable: ``WallClock`` (real time; idle waits sleep until
the next arrival — open-loop serving) or ``VirtualClock`` (time = decode
iterations; deterministic staggered-arrival tests).
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import time
from typing import Any, Callable, Iterable

import numpy as np

from distributed_tensorflow_tpu.observability.metrics import (
    MetricsRegistry, exact_percentile)
from distributed_tensorflow_tpu.observability.trace import recorder
from distributed_tensorflow_tpu.serving.kv_cache import (
    SlotKVCache, SlotOverflow)


# ------------------------------------------------------------------ clocks

class WallClock:
    """Real time: arrivals are seconds since ``start()``; idle waits sleep.

    ``poll_slice_s`` bounds each idle sleep: the batcher re-checks the
    queue between slices (a concurrent producer's earlier arrival is
    noticed within one slice) instead of either spinning or oversleeping.
    """

    def __init__(self, poll_slice_s: float = 0.05):
        self._t0 = None
        self.poll_slice_s = float(poll_slice_s)

    def start(self) -> None:
        self._t0 = time.monotonic()

    def now(self) -> float:
        return time.monotonic() - self._t0

    def on_decode_iteration(self) -> None:
        pass  # real time advances itself

    def on_prefill(self, tokens: int) -> None:
        pass  # real time advances itself

    def wait_until(self, t: float) -> None:
        delta = t - self.now()
        if delta > 0:
            time.sleep(delta)


class VirtualClock:
    """Deterministic time: one decode iteration = ``tick`` time units.

    Arrival times are then expressed in decode iterations, which makes
    "request arrives mid-decode" an exact, repeatable event — the
    staggered-arrival acceptance tests run on this clock.

    ``prefill_token_tick`` is the interference cost model: each prefilled
    prompt token advances time by this much (default 0 — prefill is free,
    the PR 7 accounting).  With it set, a monolithic admission of an
    L-token prompt stalls every live slot by ``L × prefill_token_tick``
    in one gap, while chunked prefill bounds the per-iteration stall to
    ``budget × prefill_token_tick`` — the chunked-prefill acceptance
    tests measure exactly that, deterministically."""

    poll_slice_s = float("inf")   # virtual idle waits jump, never slice

    def __init__(self, tick: float = 1.0, prefill_token_tick: float = 0.0):
        self.t = 0.0
        self.tick = float(tick)
        self.prefill_token_tick = float(prefill_token_tick)

    def start(self) -> None:
        self.t = 0.0

    def now(self) -> float:
        return self.t

    def on_decode_iteration(self) -> None:
        self.t += self.tick

    def on_prefill(self, tokens: int) -> None:
        self.t += tokens * self.prefill_token_tick

    def wait_until(self, t: float) -> None:
        self.t = max(self.t, t)


# ----------------------------------------------------------------- request

@dataclasses.dataclass
class Request:
    """One serving request of the open-loop arrival process."""

    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    arrival_s: float = 0.0
    eos_id: int | None = None
    # disaggregated fleet: a serialized KV payload (SlotKVCache
    # extract_handoff) attached by a prefill replica's handoff hook —
    # the decode replica admits by restoring it instead of prefilling.
    # None everywhere outside the disaggregated path.
    handoff: dict | None = None


class RequestQueue:
    """Arrival-ordered queue with the native batcher's busy-claim contract
    (native/batcher.py: one consumer owns the cursor; release is
    deterministic, not GC-time).  ``claim()`` returns a context manager —
    a second concurrent scheduler on the same queue performs a BOUNDED
    busy-claim (short doubling backoff sleeps, never a hot spin, attempt
    count pinned by tests) and raises once the bound is exhausted instead
    of silently interleaving admissions."""

    def __init__(self, requests: Iterable[Request] = ()):
        self._items: list[Request] = sorted(
            requests, key=lambda r: (r.arrival_s, r.rid))
        self.busy = False
        self.claim_attempts = 0   # attempts of the LAST claim() call
        # deepest ARRIVED backlog ever observed via depth(now) — the
        # queue-pressure number that used to be invisible until TTFT
        # blew up
        self.depth_high_watermark = 0

    def push(self, request: Request) -> None:
        self._items.append(request)
        self._items.sort(key=lambda r: (r.arrival_s, r.rid))

    def __len__(self) -> int:
        return len(self._items)

    def next_arrival(self) -> float | None:
        return self._items[0].arrival_s if self._items else None

    def pop_ready(self, now: float) -> Request | None:
        if self._items and self._items[0].arrival_s <= now:
            return self._items.pop(0)
        return None

    def depth(self, now: float | None = None) -> int:
        """Queue depth: all queued requests when ``now`` is None, else
        only those already ARRIVED by ``now`` (the admission backlog —
        the number bounded-admission caps).  ``now``-based reads update
        ``depth_high_watermark``.  O(log n): the batcher calls this every
        decode iteration, and a linear scan would make the host loop
        quadratic in the backlog exactly when overloaded."""
        if now is None:
            return len(self._items)
        d = bisect.bisect_right(self._items, now,
                                key=lambda r: r.arrival_s)
        if d > self.depth_high_watermark:
            self.depth_high_watermark = d
        return d

    def shed_ready(self, now: float, keep: int) -> list[Request]:
        """Bounded admission: remove and return every ARRIVED request
        beyond the oldest ``keep`` (the 429 path — newest arrivals shed
        first, FIFO preserved for the survivors)."""
        ready = self.depth(now)
        n_shed = ready - max(int(keep), 0)
        if n_shed <= 0:
            return []
        shed = self._items[ready - n_shed:ready]
        del self._items[ready - n_shed:ready]
        return shed

    @contextlib.contextmanager
    def claim(self, max_attempts: int = 8, backoff_s: float = 0.005):
        """Claim the queue for one scheduler run.

        A busy queue is retried ``max_attempts`` times with a short
        doubling sleep between attempts (bounded host cost — the claim
        loop can never spin a core), then raises.  ``claim_attempts``
        records how many attempts the call made, so tests pin the bound.
        """
        delay = float(backoff_s)
        self.claim_attempts = 0
        while True:
            self.claim_attempts += 1
            if not self.busy:
                break
            if self.claim_attempts >= max_attempts:
                raise RuntimeError(
                    "RequestQueue is busy: another scheduler run owns it "
                    "(the native/batcher.py single-consumer claim "
                    f"contract; gave up after {self.claim_attempts} "
                    f"bounded claim attempts)")
            time.sleep(delay)
            delay = min(delay * 2, 0.1)
        self.busy = True
        try:
            yield self
        finally:
            self.busy = False


@dataclasses.dataclass
class RequestResult:
    """Per-request outcome + latency timeline (clock units).

    Phase attribution: ``queue_wait_s`` is arrival → slot claim,
    ``prefill_s`` is claim → first token (chunk wait included), and the
    decode phase is the ``itl_s`` gap list — the three sum (with the
    decode gaps) to the request's total latency, and each phase also
    lands in the batcher's histogram registry and on the ``request``
    trace span."""

    rid: int
    prompt_len: int
    tokens: list[int]
    arrival_s: float
    admitted_s: float
    first_token_s: float
    finished_s: float = 0.0
    itl_s: list[float] = dataclasses.field(default_factory=list)
    queue_wait_s: float = 0.0
    prefill_s: float = 0.0
    slo_met: bool | None = None   # None: no SLOMonitor attached
    # speculative-decode accounting (zero when no draft is attached):
    # draft tokens proposed for / accepted by this request's slot —
    # conservation holds exactly: accepted + rejected == proposed
    proposed_tokens: int = 0
    accepted_tokens: int = 0

    @property
    def ttft_s(self) -> float:
        return self.first_token_s - self.arrival_s

    @property
    def decode_s(self) -> float:
        return self.finished_s - self.first_token_s


class _Live:
    """Host bookkeeping for one in-flight slot."""

    def __init__(self, req: Request, result: RequestResult,
                 req_span, last_t: float):
        self.req = req
        self.result = result
        self.req_span = req_span     # detached span (tracer.begin), ended
        self.last_t = last_t         # on finish (per-request span contract)


# stdlib-only linear-interpolated percentile (shared with the histogram
# module so the stored-sample path and the exactness tests use literally
# the same function)
_percentile = exact_percentile


# --------------------------------------------------------------- batcher

class ContinuousBatcher:
    """In-flight request scheduler over a SlotKVCache (module docstring).

    ``mode='continuous'`` admits between decode iterations (the tentpole
    path); ``mode='static'`` only admits into an EMPTY slot table — the
    restart-per-batch ``generate`` baseline, measured with the same
    counters so the comparison is apples-to-apples.
    """

    def __init__(self, kv: SlotKVCache, *, tracer=None,
                 clock=None, mode: str = "continuous",
                 prefill_chunk: int = 0, metrics=None, slo=None,
                 queue_cap: int = 0, should_stop=None,
                 draft_kv: SlotKVCache | None = None, draft_k: int = 4,
                 timeline=None, timeline_tag: int | None = None,
                 role: str | None = None, handoff_out=None,
                 roofline=None, multi_step: int | None = None):
        if mode not in ("continuous", "static"):
            raise ValueError(f"mode must be continuous|static, got {mode}")
        if prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0 (0 = monolithic prefill), "
                f"got {prefill_chunk}")
        if queue_cap < 0:
            raise ValueError(
                f"queue_cap must be >= 0 (0 = unbounded admission), got "
                f"{queue_cap}")
        if draft_kv is not None:
            # speculative decoding (--serve-draft-config/--serve-draft-k):
            # a small draft model proposes k tokens per live slot between
            # target iterations, the target scores all k+1 positions in
            # one batched verify step, and greedy acceptance keeps the
            # emitted stream bitwise identical to non-speculative decode.
            # The draft runs its own SlotKVCache in slot lockstep —
            # admitted/evicted with the target, resynced by length
            # bookkeeping after every round.
            if draft_k < 1:
                raise ValueError(
                    f"draft_k must be >= 1 (draft tokens proposed per "
                    f"verify round), got {draft_k}")
            if not (kv.greedy and draft_kv.greedy):
                raise ValueError(
                    "speculative decoding requires greedy sampling on "
                    "both the target and the draft: the exact acceptance "
                    "rule only exists for greedy decode")
            if draft_kv.slots != kv.slots:
                raise ValueError(
                    f"draft slot table ({draft_kv.slots}) must match the "
                    f"target's ({kv.slots}): slots run in lockstep")
            if draft_kv.max_len < kv.max_len:
                raise ValueError(
                    f"draft max_len ({draft_kv.max_len}) must cover the "
                    f"target's ({kv.max_len}): the draft mirrors every "
                    f"slot position")
        # disaggregated fleet roles (--serve-disaggregate): a 'prefill'
        # batcher runs admission + (chunked) prefill only and hands each
        # finished slot's KV to `handoff_out(req, payload)` instead of
        # decoding; a 'decode' batcher admits handoff-carrying requests
        # by restoring the payload.  role=None is the homogeneous batcher,
        # byte-identical to round 17.
        if role not in (None, "prefill", "decode"):
            raise ValueError(
                f"role must be None|prefill|decode, got {role!r}")
        if (role == "prefill") != (handoff_out is not None):
            raise ValueError(
                "role='prefill' and handoff_out go together: the prefill "
                "batcher needs a delivery hook for finished KV, and only "
                "a prefill batcher may have one")
        if role == "prefill" and draft_kv is not None:
            raise ValueError(
                "speculative decoding cannot ride a prefill-role batcher: "
                "it never decodes — attach the draft to decode replicas")
        self.role = role
        self.handoff_out = handoff_out
        self.draft_kv = draft_kv
        self.draft_k = int(draft_k)
        self.kv = kv
        # no tracer passed = the process-wide recorder (records kept in
        # memory, no file); NULL_TRACER is what a caller passes to have
        # nothing recorded.  The tables build their programs under the
        # same tracer (``program_build``).
        self.tracer = tracer if tracer is not None else recorder()
        kv.tracer = self.tracer
        if draft_kv is not None:
            draft_kv.tracer = self.tracer
        self.clock = clock if clock is not None else WallClock()
        self.mode = mode
        # per-iteration prompt-token budget (Sarathi-Serve chunked
        # prefill): 0 = admission prefills the whole prompt in one program
        # (the PR 7 path); >0 = at most one ≤prefill_chunk-token chunk
        # rides each decode iteration, so live slots keep emitting tokens
        # while a long prompt fills
        self.prefill_chunk = int(prefill_chunk)
        # observability hooks — ALL host-side, so the compiled program set
        # and the greedy tokens are byte-identical with them on or off:
        # `metrics` is an external MetricsRegistry the per-run histograms
        # merge into (windows → runs → fleet), `slo` an SLOMonitor
        # (goodput-under-SLO per window), `queue_cap` the bounded-
        # admission overload mode (>0: arrived backlog past the cap is
        # shed with 429 accounting instead of queuing unboundedly), and
        # `should_stop` the lease-drain hook (reason string → stop
        # admitting, finish in-flight, flush accounting)
        self.metrics = metrics
        self.slo = slo
        self.queue_cap = int(queue_cap)
        self.should_stop = should_stop
        # `timeline` (--timeline) is the same discipline: a throttled
        # host-side gauge sampler fed at the existing per-iteration
        # boundary; `timeline_tag` is the fleet's replica id, keying
        # per-replica series lanes.  None = sampling fully off.
        self.timeline = timeline
        self.timeline_tag = timeline_tag
        # `roofline` (--roofline) follows the same host-side discipline: a
        # Roofline carrying the analytic GPTCostModel for THIS kv's model.
        # The batcher tallies model FLOPs and must-read bytes per phase in
        # plain Python counters at boundaries that already exist — zero
        # device syncs, zero new programs — and the summary gains
        # serve_prefill_mfu / serve_decode_mbu plus a roofline section
        # ONLY when it is attached (flag-off key-set parity pin).  The
        # draft model's work is deliberately NOT counted: MFU/MBU describe
        # the TARGET model's efficiency, and crediting draft flops would
        # let a wasteful draft inflate the headline (BASELINE.md).
        self.roofline = roofline
        self._rf_cost = (roofline.cost if roofline is not None else None)
        # --serve-multi-step k: fuse k decode iterations per host
        # dispatch (SlotKVCache.dispatch_multi/drain_multi) and pipeline
        # round i+1's dispatch ahead of round i's materialization —
        # bounded admission staleness (a new arrival waits at most one
        # k-iteration round) for k× fewer host round-trips.  None = the
        # legacy per-iteration loop, byte-identical to round 19 (the
        # flag-off parity pin; k=1 runs the pipeline at legacy fusion).
        # With a draft attached the outer loop stays legacy (verify
        # rounds need host acceptance each iteration) but the draft's
        # proposal loop fuses through the same program.
        if multi_step is not None and int(multi_step) < 1:
            raise ValueError(
                f"multi_step must be >= 1 fused decode iterations per "
                f"dispatch, got {multi_step}")
        self.multi_step = None if multi_step is None else int(multi_step)
        self.idle_polls = 0

    # ------------------------------------------------------------ admission
    def _check_capacity(self, req: Request) -> int:
        lp = int(np.asarray(req.prompt).reshape(-1).shape[0])
        if lp + req.max_new_tokens > self.kv.max_len:
            raise ValueError(
                f"request {req.rid}: prompt ({lp}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds the slot capacity "
                f"max_len={self.kv.max_len}")
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.rid}: max_new_tokens must be positive")
        return lp

    def _admit(self, req: Request, live: dict[int, _Live]) -> int | None:
        kv, tracer = self.kv, self.tracer
        lp = self._check_capacity(req)
        t_claim = self.clock.now()
        req_span = tracer.begin("request", rid=req.rid, prompt_len=lp,
                                max_new_tokens=req.max_new_tokens)
        if req.handoff is not None:
            # disaggregated decode-side admission: the prompt KV arrives
            # serialized from a prefill replica — restore it instead of
            # prefilling.  The first token was already sampled by the
            # prefill replica's final chunk and rides the payload; no
            # prefill program runs here, so a long prompt can never
            # share this replica's iteration with live decodes.
            if self.role != "decode":
                raise ValueError(
                    f"request {req.rid} carries a KV handoff but this "
                    f"batcher's role is {self.role!r} — only decode-role "
                    f"batchers admit handoffs")
            with tracer.span("kv_handoff_restore", rid=req.rid,
                             length=int(req.handoff["length"])):
                slot, first = kv.restore_handoff(req.handoff)
            self._handoffs_in += 1
        else:
            before = kv.prefill_tokens_computed
            padded = kv.prefill_tokens_padded
            # the span ends after the host holds the first token (insert
            # returns it as an int): its duration is the prefill, not the
            # enqueue — the stall and queue-wait metrics rest on that
            with tracer.span("prefill", rid=req.rid, prompt_len=lp,
                             form=kv.prefill_form) as sp:
                slot, first = kv.insert(req.prompt)
                sp["padded_len"] = kv.prefill_tokens_padded - padded
                sp["slot"] = slot
                if kv.ring_leaves:      # rows written into each ring
                    sp["ring_rows"] = min(lp, *kv.ring_leaves.values())
            self.clock.on_prefill(kv.prefill_tokens_computed - before)
            if self._rf_cost is not None:
                # credit only positions actually computed: a prefix-cache
                # hit of r tokens leaves positions r..lp, whose new-token
                # attention still spans the cached context (the start
                # offset) — plus one LM head read sampling the first token
                done = kv.prefill_tokens_computed - before
                self._rf_prefill_flops += (
                    self._rf_cost.prefill_chunk_flops(done, lp - done)
                    + self._rf_cost.lm_head_flops)
        if hasattr(kv, "note_admission"):
            # register the paged block budget (prompt + decode growth) so
            # can_admit's outstanding ledger covers this slot's worst case
            kv.note_admission(slot, lp + req.max_new_tokens)
        if self.handoff_out is not None:
            # prefill role: the finished slot's KV leaves for a decode
            # replica — no local decode, no local token delivery (the
            # decode replica emits the payload's first token, so TTFT is
            # still charged arrival→first-token INCLUDING the handoff)
            self._handoff(req, slot, req_span)
            return None
        now = self.clock.now()
        result = RequestResult(
            rid=req.rid, prompt_len=lp, tokens=[first],
            arrival_s=req.arrival_s, admitted_s=now, first_token_s=now,
            queue_wait_s=t_claim - req.arrival_s,
            prefill_s=now - t_claim)
        live[slot] = _Live(req, result, req_span, now)
        self._arm_multi(slot, live[slot])
        self._draft_admit(req.prompt, slot, first)
        if self._finished(live[slot]):
            # max_new_tokens == 1 (or instant EOS): the prefill's token was
            # the whole continuation — finish without a decode iteration
            self._finish(slot, live)
        return first

    def _begin_admit(self, req: Request, pending: dict[int, dict]) -> None:
        """Chunked admission: claim the slot (longest cached prefix copied
        in) and queue the prompt for chunk-by-chunk prefill — the first
        token is sampled by the FINAL chunk (``_promote``), so TTFT keeps
        the arrival→first-token meaning, queue AND chunk wait included."""
        kv, tracer = self.kv, self.tracer
        lp = self._check_capacity(req)
        t_claim = self.clock.now()
        req_span = tracer.begin("request", rid=req.rid, prompt_len=lp,
                                max_new_tokens=req.max_new_tokens)
        slot, reused = kv.begin_insert(req.prompt)
        if hasattr(kv, "note_admission"):
            kv.note_admission(slot, lp + req.max_new_tokens)
        pending[slot] = {"req": req, "span": req_span, "lp": lp,
                         "admitted_s": t_claim, "reused": reused,
                         "queue_wait_s": t_claim - req.arrival_s}

    def _promote(self, slot: int, pend: dict, first: int,
                 live: dict[int, _Live]) -> bool:
        """Final chunk done: the slot joins the decode table — or, on a
        prefill-role batcher, leaves for a decode replica (returns False:
        the caller must not deliver the first token locally)."""
        req = pend["req"]
        if self.handoff_out is not None:
            self._handoff(req, slot, pend["span"])
            return False
        now = self.clock.now()
        result = RequestResult(
            rid=req.rid, prompt_len=pend["lp"], tokens=[first],
            arrival_s=req.arrival_s, admitted_s=pend["admitted_s"],
            first_token_s=now,
            queue_wait_s=pend["queue_wait_s"],
            prefill_s=now - pend["admitted_s"])
        live[slot] = _Live(req, result, pend["span"], now)
        self._arm_multi(slot, live[slot])
        self._draft_admit(req.prompt, slot, first)
        if self._finished(live[slot]):
            self._finish(slot, live)
        return True

    def _arm_multi(self, slot: int, lv: _Live) -> None:
        """Arm the kv's in-device deactivation for a freshly-live slot
        (multi-step mode only — the flag-off path never touches the
        vectors): the fused rounds stop a slot the moment it emits the
        request's EOS or exhausts its remaining token budget, so later
        fused iterations cannot decode past the stream's end.  The
        budget counts emissions still owed AFTER the prefill's first
        token; a request finished by that first token never dispatches
        (``_finished`` → ``_finish`` evicts it immediately)."""
        if self.multi_step is None:
            return
        remaining = lv.req.max_new_tokens - len(lv.result.tokens)
        self.kv.set_decode_limits(slot, lv.req.eos_id, max(remaining, 0))

    def _handoff(self, req: Request, slot: int, span) -> None:
        """Prefill-role completion: serialize the finished slot's KV
        (SlotKVCache.extract_handoff — the jitted block read programs +
        device_get), free the slot, and deliver (req, payload) to the
        fleet's handoff hook.  The evict-before-raise guard is the
        no-KV-leak fence the chaos tests pin: at this point the slot is
        visible to NEITHER run()'s live nor its pending cleanup, so a
        fault injected into the extract (or a real device error) must
        release the slot — under paging, its blocks and refcounts —
        right here, before the failure surfaces to the supervisor."""
        kv = self.kv
        try:
            with self.tracer.span("kv_handoff", rid=req.rid, slot=slot,
                                  length=int(kv.lengths[slot])):
                payload = kv.extract_handoff(slot)
        except BaseException:
            kv.evict(slot)
            self.tracer.end(span)
            raise
        kv.evict(slot)
        self._handoffs_out += 1
        span.attrs.update(handed_off=True,
                          handoff_blocks=len(payload["blocks"]))
        self.tracer.end(span)
        self.handoff_out(req, payload)

    def _draft_admit(self, prompt, slot: int, first: int) -> None:
        """Speculative decode: admit the same prompt into the draft
        table's SAME slot (slot lockstep).  The draft's prefill samples
        its own first token, which is DISCARDED — the committed pending
        token is the target's, so the draft's first proposal next round
        continues the real stream.  The draft prefill is monolithic and
        unpooled by design: the chunked-prefill stall bound covers the
        TARGET's programs, and this per-admission cost is draft-sized —
        the reason production drafts are small (MIGRATING round 14)."""
        if self.draft_kv is None:
            return
        self.draft_kv.insert(prompt, slot=slot)
        self.draft_kv.tokens[slot] = int(first)

    def _finished(self, lv: _Live) -> bool:
        if len(lv.result.tokens) >= lv.req.max_new_tokens:
            return True
        eos = lv.req.eos_id
        return eos is not None and lv.result.tokens[-1] == eos

    def _finish(self, slot: int, live: dict[int, _Live]) -> None:
        lv = live.pop(slot)
        r = lv.result
        r.finished_s = self.clock.now()
        # phase attribution: histogram observations (online percentiles,
        # mergeable across windows) + the same numbers as attrs on the
        # request span record, so `analyze serve` can render the
        # queue→prefill→decode waterfall from the trace alone
        reg = self._registry
        reg.record("ttft", r.ttft_s)
        reg.record("queue_wait", r.queue_wait_s)
        reg.record("prefill", r.prefill_s)
        for gap in r.itl_s:
            reg.record("itl", gap)
        if self.slo is not None:
            r.slo_met = self.slo.observe(r.ttft_s, r.itl_s)
        lv.req_span.attrs.update(
            queue_wait_s=r.queue_wait_s, prefill_s=r.prefill_s,
            decode_s=r.decode_s, ttft_s=r.ttft_s, tokens=len(r.tokens),
            **({} if r.slo_met is None else {"slo_met": r.slo_met}))
        self.tracer.end(lv.req_span)
        self.kv.evict(slot)
        if self.draft_kv is not None and self.draft_kv.active[slot]:
            self.draft_kv.evict(slot)
        self._results.append(lv.result)

    def _shed(self, req: Request, depth: int) -> None:
        """Bounded-admission rejection (the 429 path): exact accounting —
        a structured ``overload`` trace event + counter, the SLO monitor's
        shed ledger (shed is offered load, never goodput), and a bounded
        record list for the summary."""
        self._shed_count += 1
        if len(self._shed_rids) < 128:   # bounded: accounting, not a log
            self._shed_rids.append(req.rid)
        self.tracer.event("overload", rid=req.rid, queue_depth=depth,
                          queue_cap=self.queue_cap,
                          arrival_s=req.arrival_s)
        self.tracer.counter("shed_requests")
        if self.slo is not None:
            self.slo.shed()

    def _check_preempt(self, iters: int, queue: RequestQueue) -> bool:
        """Consult the lease-drain hook once (sticky): the first reason it
        returns stops admission and emits the structured drain event."""
        if self.should_stop is not None and self._preempted is None:
            reason = self.should_stop(iters)
            if reason:
                self._preempted = reason
                self.tracer.event("serve_preempted", reason=reason,
                                  completed=len(self._results),
                                  unserved=len(queue))
        return self._preempted is not None

    def _idle_wait(self, queue: RequestQueue, target: float,
                   iters: int) -> None:
        """Wait for the next arrival in bounded poll slices (the clock's
        ``poll_slice_s``): each slice re-reads the queue head, so a
        concurrent producer's earlier push is noticed within one slice and
        an idle batcher costs a counted, bounded number of wakeups — never
        a hot spin.  Each slice also consults the lease-drain hook: a
        preemption notice landing in a long idle gap must drain within
        one slice, not after the next arrival (typical grace periods are
        ~30 s — shorter than a sparse workload's gaps)."""
        clock = self.clock
        slice_s = getattr(clock, "poll_slice_s", float("inf"))
        with self.tracer.span("idle_wait"):
            while True:
                now = clock.now()
                nxt = queue.next_arrival()
                if nxt is None or now >= nxt:
                    return
                if self._check_preempt(iters, queue):
                    return   # the loop top turns this into the drain/break
                self.idle_polls += 1
                clock.wait_until(min(nxt, now + slice_s))

    # ------------------------------------------------------------- the loop
    def _serve(self, queue: RequestQueue, live: dict[int, _Live],
               pending: dict[int, dict],
               on_token: Callable[[int, int], None] | None,
               ) -> tuple[int, int, int]:
        """The iteration loop under run()'s claim + cleanup guard; returns
        (decode_iterations, prefills, prefill_chunks).

        With ``multi_step`` armed (and no draft / non-prefill role) the
        loop is replaced by the pipelined ``_serve_multi`` — same
        admission/shed/observe/chunk passes at the same per-iteration
        boundaries, but decode runs as fused k-step rounds with one
        round always in flight."""
        if (self.multi_step is not None and self.draft_kv is None
                and self.role != "prefill"):
            return self._serve_multi(queue, live, pending, on_token)
        clock = self.clock
        decode_iterations = 0
        prefills = 0
        chunks = 0
        while len(queue) or live or pending:
            # lease drain (should_stop hook, the PR 9 contract): a
            # preemption notice stops admission — in-flight slots finish,
            # claimed (pending) admissions complete, the rest of the
            # queue is left unserved and accounted — so a SIGTERM'd serve
            # window flushes a consistent partial summary instead of
            # dying mid-table
            self._check_preempt(decode_iterations, queue)
            if self._preempted is not None and not (live or pending):
                break
            prefills += self._admission_pass(queue, live, pending, on_token)
            self._shed_pass(queue)
            self._observe_pass(queue, live, pending)
            dc, dp = self._chunk_pass(live, pending, on_token)
            chunks += dc
            prefills += dp
            if not live:
                if pending:
                    continue   # keep chunking: nothing to decode yet
                nxt = queue.next_arrival()
                if nxt is None:
                    break
                self._idle_wait(queue, nxt,  # bounded-slice sleep/jump
                                decode_iterations)
                continue
            emitted = self._decode_round(live)
            decode_iterations += 1
            clock.on_decode_iteration()
            now = clock.now()
            for slot in sorted(live):
                lv = live[slot]
                for j, tok in enumerate(emitted[slot]):
                    lv.result.tokens.append(tok)
                    # ITL attribution per EMITTED token (the SLO math
                    # stays honest): a verify round delivers its accepted
                    # tokens at one host instant, so the first token of
                    # the round carries the inter-round gap and its
                    # batch-mates arrive at gap 0 — the gaps still sum to
                    # the request's decode wall time
                    lv.result.itl_s.append((now - lv.last_t) if j == 0
                                           else 0.0)
                    lv.last_t = now
                    self._decode_tokens += 1
                    if on_token is not None:
                        on_token(lv.req.rid, tok)
                    if self._finished(lv):
                        self._finish(slot, live)
                        break
        return decode_iterations, prefills, chunks

    # ------------------------------------------- shared per-iteration passes
    def _admission_pass(self, queue: RequestQueue, live: dict[int, _Live],
                        pending: dict[int, dict],
                        on_token: Callable[[int, int], None] | None) -> int:
        """Admission between decode iterations → prefill count delta:
        continuous mode fills any free slot from the arrived queue;
        static mode waits for the whole table to drain first."""
        kv, clock = self.kv, self.clock
        prefills = 0
        can_admit = (self._preempted is None
                     and (self.mode == "continuous"
                          or not (live or pending)))
        while can_admit and kv.free_slots:
            req = queue.pop_ready(clock.now())
            if req is None:
                break
            # paged block-exhaustion gate: a free SLOT is not enough
            # when the kv is a block pool — the request's worst-case
            # block need (prompt + max_new_tokens, plus live slots'
            # committed budgets) must fit the free list.  Deferral
            # pushes the request back (FIFO by arrival is preserved:
            # the queue re-sorts) until decode completions release
            # blocks.  With NOTHING in flight the pool is as free as
            # it will ever get, so deferring would busy-spin — admit
            # and let BlockPoolExhausted surface the impossible
            # configuration instead.
            if (hasattr(kv, "can_admit") and (live or pending)
                    and not kv.can_admit(
                        int(np.asarray(req.prompt).reshape(-1)
                            .shape[0]),
                        req.max_new_tokens)):
                queue.push(req)
                self._block_deferrals += 1
                break
            if self.prefill_chunk:
                self._begin_admit(req, pending)
            else:
                first = self._admit(req, live)
                prefills += 1
                if first is not None and on_token is not None:
                    on_token(req.rid, first)  # the prefill's own token
        return prefills

    def _shed_pass(self, queue: RequestQueue) -> None:
        """Bounded admission (overload mode): whatever arrived beyond the
        queue-depth cap after this round's admissions is shed with 429
        accounting — queue wait stays bounded by construction instead of
        growing with offered load."""
        if self.queue_cap and self._preempted is None:
            now = self.clock.now()
            # depth BEFORE shedding: the overload events must record
            # the backlog that triggered them (post-shed depth is
            # always == queue_cap — zero information)
            depth = queue.depth(now)
            for req in queue.shed_ready(now, self.queue_cap):
                self._shed(req, depth)

    def _observe_pass(self, queue: RequestQueue, live: dict[int, _Live],
                      pending: dict[int, dict]) -> None:
        """Queue-pressure attribution: the arrived backlog, per iteration,
        into the histogram the summary's queue_depth_p95 reads (+ the
        queue's own high watermark), and the --timeline sample batch at
        the same boundary."""
        clock = self.clock
        self._registry.record("queue_depth", queue.depth(clock.now()))
        if self.timeline is not None:
            # --timeline sampling at the SAME boundary: queue/slot/
            # prefill pressure plus the kv's host-counter gauges, one
            # throttled batch per iteration — no device syncs, no new
            # keys or programs with the flag off
            self.timeline.sample_many(
                {"queue_depth": queue.depth(clock.now()),
                 "active_slots": len(live),
                 "prefill_pending": len(pending),
                 **self.kv.timeline_gauges()},
                replica=self.timeline_tag, group="batcher")

    def _chunk_pass(self, live: dict[int, _Live], pending: dict[int, dict],
                    on_token: Callable[[int, int], None] | None,
                    ) -> tuple[int, int]:
        """At most ONE ≤budget-token chunk rides each iteration → (chunk,
        prefill) count deltas: the decode stall a filling prompt can
        inflict is bounded by the chunk budget, whatever the prompt
        length."""
        if not pending:
            return 0, 0
        kv, tracer, clock = self.kv, self.tracer, self.clock
        chunks = 0
        prefills = 0
        slot = next(iter(pending))    # FIFO admission order
        pend = pending[slot]
        n = min(kv.pending_tokens(slot), self.prefill_chunk)
        start = int(kv.lengths[slot])
        with tracer.span("prefill_chunk", rid=pend["req"].rid,
                         slot=slot, tokens=n, start=start):
            first = kv.prefill_chunk(slot, self.prefill_chunk)
        chunks += 1
        clock.on_prefill(n)
        if self._rf_cost is not None:
            # n new positions attending over `start` cached ones;
            # the LM head runs once, on the FINAL chunk's sample
            self._rf_prefill_flops += \
                self._rf_cost.prefill_chunk_flops(n, start)
            if first is not None:
                self._rf_prefill_flops += self._rf_cost.lm_head_flops
        if first is not None:
            pending.pop(slot)
            prefills += 1
            if self._promote(slot, pend, first, live) \
                    and on_token is not None:
                on_token(pend["req"].rid, first)
        return chunks, prefills

    # ------------------------------------------------- multi-step pipeline
    def _serve_multi(self, queue: RequestQueue, live: dict[int, _Live],
                     pending: dict[int, dict],
                     on_token: Callable[[int, int], None] | None,
                     ) -> tuple[int, int, int]:
        """The --serve-multi-step iteration loop: each pipeline iteration
        runs the same admission/shed/observe/chunk passes as the legacy
        loop, DISPATCHES the next fused k-step round, and only then
        DRAINS the previous round's token stack — so the device is
        already decoding round i+1 while the host materializes round i's
        tokens and runs scheduling (``copy_to_host_async`` at dispatch,
        the blocking ``np.asarray`` at drain).  Exactly one round is in
        flight at a time: admissions observed between a dispatch and its
        drain take effect on the NEXT round (the fused program's
        host-edit prologue folds them in), bounding admission staleness
        at k fused iterations.  Greedy streams are bitwise identical to
        k=1: the in-device EOS/budget deactivation mirrors
        ``_finished``'s stop conditions exactly, and per-token delivery
        replays the stack level by level with the same clock/ITL
        attribution the legacy loop uses per iteration."""
        kv, tracer = self.kv, self.tracer
        k = self.multi_step
        decode_iterations = 0
        prefills = 0
        chunks = 0
        inflight: tuple[dict, np.ndarray] | None = None
        while len(queue) or live or pending or inflight is not None:
            self._check_preempt(decode_iterations, queue)
            if self._preempted is not None \
                    and not (live or pending or inflight is not None):
                break
            prefills += self._admission_pass(queue, live, pending, on_token)
            self._shed_pass(queue)
            self._observe_pass(queue, live, pending)
            dc, dp = self._chunk_pass(live, pending, on_token)
            chunks += dc
            prefills += dp
            handle = pre = None
            # slots halted ON DEVICE (EOS/budget hit mid-round) never
            # re-dispatch; if every live slot is halted there is nothing
            # to decode — they all finish at this round's drain
            if live and any(not kv.halted[s] for s in live):
                pre = kv.lengths.copy()
                with tracer.span("decode_dispatch", active=len(live), k=k):
                    handle = kv.dispatch_multi(k)
            if inflight is not None:
                h, pre_prev = inflight
                inflight = None
                toks, acts = kv.drain_multi(h)
                decode_iterations += self._deliver_multi(
                    live, toks, acts, pre_prev, on_token)
                # a live slot still halted after delivery hit the
                # device-side stop conditions without ``_finished``
                # agreeing — only possible when the table ran out of
                # room (length == max_len) before the request's budget
                for slot in sorted(live):
                    if kv.halted[slot]:
                        raise SlotOverflow(
                            f"slot {slot} reached max_len={kv.max_len} "
                            f"mid-round with "
                            f"{live[slot].req.max_new_tokens} tokens "
                            "requested — admission must bound "
                            "prompt+max_new_tokens to max_len")
            if handle is not None:
                inflight = (handle, pre)
                continue
            if live or pending:
                continue
            nxt = queue.next_arrival()
            if nxt is None:
                break
            self._idle_wait(queue, nxt,  # bounded-slice sleep/jump
                            decode_iterations)
        return decode_iterations, prefills, chunks

    def _deliver_multi(self, live: dict[int, _Live], toks: np.ndarray,
                       acts: np.ndarray, pre: np.ndarray,
                       on_token: Callable[[int, int], None] | None) -> int:
        """Replay a drained (k, slots) stack level by level as if each
        level were one legacy decode iteration → iterations delivered.
        Every non-empty level advances the clock once and stamps each of
        its tokens with ``now - last_t`` — under VirtualClock this is
        bitwise the k=1 ITL attribution; under WallClock the first level
        of the round carries the real inter-round gap.  Levels where
        every slot was already deactivated (EOS'd mid-round) deliver
        nothing and don't count as iterations."""
        kv, clock = self.kv, self.clock
        iterations = 0
        for j in range(acts.shape[0]):
            if not acts[j].any():
                continue
            if self._rf_cost is not None:
                # context at level j is the dispatch-time length + j
                # committed fused steps — same per-token cost the legacy
                # loop would have tallied at that iteration
                contexts = [int(pre[s]) + j for s in sorted(live)
                            if acts[j, s]]
                if contexts:
                    self._rf_decode_flops += sum(
                        self._rf_cost.decode_flops_per_token(L)
                        for L in contexts)
                    self._rf_decode_bytes += \
                        self._rf_cost.decode_step_bytes(contexts)
            clock.on_decode_iteration()
            now = clock.now()
            iterations += 1
            for slot in sorted(np.flatnonzero(acts[j])):
                slot = int(slot)
                if slot not in live:
                    continue
                lv = live[slot]
                tok = int(toks[j, slot])
                lv.result.tokens.append(tok)
                lv.result.itl_s.append(now - lv.last_t)
                lv.last_t = now
                self._decode_tokens += 1
                if on_token is not None:
                    on_token(lv.req.rid, tok)
                if self._finished(lv):
                    self._finish(slot, live)
        return iterations

    # ------------------------------------------------- speculative decode
    def _decode_round(self, live: dict[int, _Live]) -> dict[int, list[int]]:
        """One target decode iteration → per-slot emitted tokens.

        Without a draft (or when speculation cannot help this round) this
        is the single-token ``advance`` emitting exactly one token per
        live slot — the compiled program and the tokens are byte-identical
        to the draft-off batcher.  With a draft, the round becomes
        draft-k → verify-1 (``_spec_round``): up to ``draft_k + 1``
        tokens per slot from ONE target iteration."""
        kv = self.kv
        k_eff = self._spec_k(live) if self.draft_kv is not None else 0
        if k_eff < 1:
            if self._rf_cost is not None:
                contexts = [int(kv.lengths[s]) for s in sorted(live)]
                self._rf_decode_flops += sum(
                    self._rf_cost.decode_flops_per_token(L)
                    for L in contexts)
                self._rf_decode_bytes += \
                    self._rf_cost.decode_step_bytes(contexts)
            # the span ends after the host holds the round's tokens
            # (advance returns them through np.asarray): its duration is
            # the decode step, and the gap to the next one is the stall
            with self.tracer.span("decode_step", active=len(live),
                                  slots=kv.slots) as sp:
                if kv.ring_leaves:
                    sp["past_window"] = kv.past_window()
                toks = kv.advance()
                if kv.last_routing is not None:     # a model with experts
                    sp.update(kv.last_routing)
            return {slot: [int(toks[slot])] for slot in live}
        return self._spec_round(live, k_eff)

    def _spec_k(self, live: dict[int, _Live]) -> int:
        """Per-round draft budget: ``draft_k`` capped by the table's
        remaining write capacity (all k+1 verify positions must fit EVERY
        live slot — SlotOverflow is a bookkeeping bug, never a tuning
        knob) and by the longest remaining request budget (proposing past
        every slot's finish line is pure draft waste; one round can emit
        at most k+1 tokens, so k = longest-remaining − 1 suffices)."""
        kv = self.kv
        cap = min(kv.max_len - int(kv.lengths[s]) for s in live) - 1
        needed = max(lv.req.max_new_tokens - len(lv.result.tokens)
                     for lv in live.values()) - 1
        return min(self.draft_k, cap, needed)

    def _spec_round(self, live: dict[int, _Live],
                    k_eff: int) -> dict[int, list[int]]:
        """Draft-k → verify-1.  The draft autoregressively proposes
        ``k_eff`` tokens for every live slot (k_eff single-token draft
        iterations over the whole table), the target scores all k_eff+1
        positions in ONE batched verify step, and each slot accepts the
        longest draft prefix matching the target argmaxes plus the
        target's own next token — exactly the tokens non-speculative
        greedy decode would have emitted, bitwise.  Draft resync is pure
        length bookkeeping (``rewind`` — rejected positions are never
        rewritten); only a FULLY-accepted slot needs one masked catch-up
        draft step, because its last proposal was never consumed by the
        draft itself."""
        kv, draft, tracer = self.kv, self.draft_kv, self.tracer
        slots = sorted(live)
        base = {s: int(kv.lengths[s]) for s in slots}
        if self._rf_cost is not None:
            # TARGET verify flops only (the draft's work is never
            # credited — see __init__); bytes are the one verify step's
            # param + live-KV reads, identical to a width-1 decode: the
            # verify width widens activations, not weight/KV traffic
            self._rf_decode_flops += sum(
                self._rf_cost.verify_flops(base[s], k_eff + 1)
                for s in slots)
            self._rf_decode_bytes += self._rf_cost.decode_step_bytes(
                [base[s] for s in slots])
        block = np.zeros((kv.slots, k_eff + 1), np.int32)
        block[:, 0] = kv.tokens
        with tracer.span("draft_propose", active=len(live), k=k_eff):
            if self.multi_step is not None and k_eff > 1:
                # --serve-multi-step: the draft's k_eff proposal loop IS
                # a fused multi-round (budget 0 = unlimited, no EOS — the
                # draft never self-deactivates; _spec_k already bounds
                # k_eff to the table's capacity), one dispatch instead of
                # k_eff.  Token-identical to the loop below: same program
                # body under lax.scan, same greedy feedback.
                stack, _ = draft.advance_multi(k_eff)
                block[:, 1:] = stack.T
                self._draft_iterations += k_eff
            else:
                for j in range(k_eff):
                    block[:, j + 1] = draft.advance()
                    self._draft_iterations += 1
        with tracer.span("decode_step", active=len(live), slots=kv.slots,
                         verify_width=k_eff + 1):
            g = kv.verify_block(block)
        emitted: dict[int, list[int]] = {}
        full = np.zeros(kv.slots, np.bool_)
        for s in slots:
            a = 0
            while a < k_eff and block[s, a + 1] == g[s, a]:
                a += 1
            emitted[s] = [int(t) for t in g[s, :a + 1]]
            lv = live[s]
            lv.result.proposed_tokens += k_eff
            lv.result.accepted_tokens += a
            self._proposed += k_eff
            self._accepted += a
            kv.commit_block(s, a + 1, int(g[s, a]))
            if a < k_eff:
                # rejected tail: rollback by length bookkeeping alone —
                # draft positions base..base+a already hold the committed
                # tokens' K/V (they were consumed during proposing)
                draft.rewind(s, base[s] + a + 1, int(g[s, a]))
            else:
                full[s] = True
        if full.any():
            # fully-accepted slots: the draft emitted its k-th proposal
            # without ever consuming it, so its cache is one committed
            # token short — one masked draft step writes it (the draft's
            # pending token IS that proposal), then the pending token is
            # overridden with the target's bonus token
            draft.advance(only=full)
            self._draft_catchup += 1
            for s in slots:
                if full[s]:
                    draft.tokens[s] = emitted[s][-1]
        return emitted

    def _release_failed_window(self, live: dict[int, _Live],
                               pending: dict[int, dict]) -> None:
        """``_serve`` raised: leave the slot table as a later window needs
        it, and end the in-flight requests' spans."""
        # a torn fused round first: host mirrors lag the device while a
        # round is in flight, and evict() below edits those mirrors —
        # drop the outstanding handles (their tokens are lost with the
        # window) before touching slots
        self.kv.abandon_multi()
        if self.draft_kv is not None:
            self.draft_kv.abandon_multi()
        # a failed window must not poison the slot table — windows may
        # share ONE SlotKVCache, and a leaked active slot shrinks every
        # later window's capacity (zero free slots + zero live = a
        # busy-spin).  Free the in-flight slots (decoding AND
        # mid-prefill) and end their spans so the records written so far
        # survive into the partial-results artifact.
        for slot in sorted(live):
            lv = live.pop(slot)
            self.tracer.end(lv.req_span)
            self.kv.evict(slot)
            if (self.draft_kv is not None
                    and self.draft_kv.active[slot]):
                self.draft_kv.evict(slot)
        for slot in sorted(pending):
            pend = pending.pop(slot)
            self.tracer.end(pend["span"])
            # a failure between the FINAL chunk and promotion leaves the
            # slot pending HERE but already active in the kv (its kv-side
            # pending entry is gone) — release whichever state it
            # reached; aborting an activated slot would raise over the
            # original error
            if self.kv.has_pending(slot):
                self.kv.abort_insert(slot)
            elif self.kv.active[slot]:
                self.kv.evict(slot)

    def run(self, requests: Iterable[Request] | RequestQueue,
            on_token: Callable[[int, int], None] | None = None,
            ) -> dict[str, Any]:
        """Serve every request to completion; returns the summary dict
        (per-request results under ``results``).  ``on_token(rid, token)``
        is the streaming hook — called at each token's host delivery."""
        queue = (requests if isinstance(requests, RequestQueue)
                 else RequestQueue(requests))
        offered = len(queue)
        self._results: list[RequestResult] = []
        self._decode_tokens = 0
        self.idle_polls = 0
        # fresh per-run registry (the summary's histograms describe THIS
        # window); an external self.metrics registry accumulates the
        # merged per-window histograms across windows/replicas
        self._registry = MetricsRegistry()
        self._shed_count = 0
        self._shed_rids: list[int] = []
        self._block_deferrals = 0   # paged pool admission deferrals
        self._preempted: str | None = None
        # speculative-decode ledger (zeros when no draft is attached):
        # conservation is exact — accepted + rejected == proposed
        self._proposed = 0
        self._accepted = 0
        self._draft_iterations = 0
        self._draft_catchup = 0
        # disaggregated handoff ledger (stays zero with role=None)
        self._handoffs_out = 0
        self._handoffs_in = 0
        # roofline tallies (stay zero with roofline=None): analytic model
        # FLOPs per phase + the bytes decode MUST read (params + live KV)
        self._rf_prefill_flops = 0.0
        self._rf_decode_flops = 0.0
        self._rf_decode_bytes = 0.0
        if self.slo is not None:
            self.slo.reset()   # one monitor measures one window
        live: dict[int, _Live] = {}
        pending: dict[int, dict] = {}
        prefix_before = self.kv.prefix_cache_stats()
        prefill_before = self.kv.prefill_tokens_computed
        phases_before = self.kv.phase_times()
        # paged-pool counter snapshot (zero-copy/CoW are cumulative on the
        # kv — windows may share one pool — so the summary reports
        # deltas over THIS run, like the prefix-pool ledger above)
        paged_before = (self.kv.paged_stats()
                        if hasattr(self.kv, "paged_stats") else None)
        # host-dispatch ledger (multi-step accounting): compiled-program
        # host calls as a delta over this run, and the REAL wall clock —
        # clock.now() may be virtual, but the host gap the multi-step
        # pipeline exists to shrink is wall time outside the device
        disp_before = self.kv.dispatch_count + (
            self.draft_kv.dispatch_count if self.draft_kv is not None else 0)
        with queue.claim():
            self.clock.start()
            t_start = self.clock.now()
            wall0 = time.perf_counter()
            # the window's root: a reader takes the records inside the
            # last of these and so leaves warm-up windows out
            counts_before = self.kv.counters()
            with self.tracer.span("serve_run", offered=offered,
                                  slots=self.kv.slots, mode=self.mode) as sp:
                try:
                    decode_iterations, prefills, chunks = self._serve(
                        queue, live, pending, on_token)
                except BaseException:
                    self._release_failed_window(live, pending)
                    raise
                # the table's counters over this window, on the window's
                # own record (`analyze serve` prints them)
                counts = self.kv.counters()
                sp["cache_bytes_per_token"] = counts["cache_bytes_per_token"]
                sp["state_bytes_per_slot"] = counts["state_bytes_per_slot"]
                sp["window_bytes_per_slot"] = counts["window_bytes_per_slot"]
                sp["expert_assignments"] = (counts["expert_assignments"]
                                            - counts_before["expert_assignments"])
            wall_elapsed = time.perf_counter() - wall0
            elapsed = self.clock.now() - t_start
        results = sorted(self._results, key=lambda r: r.rid)
        ttfts = [r.ttft_s for r in results]
        itls = [g for r in results for g in r.itl_s]
        queue_waits = [r.queue_wait_s for r in results]
        tokens = sum(len(r.tokens) for r in results)
        # overload/drain conservation ledger: every offered request is
        # admitted (and completed — run() drains), shed, or left unserved
        # by a lease drain; admitted + shed + unserved == offered exactly
        admitted = len(results)
        unserved = len(queue)
        slo_sec = (self.slo.summary(elapsed) if self.slo is not None
                   else None)
        if self.metrics is not None:
            self.metrics.merge(self._registry)
        depth_hist = self._registry.histogram("queue_depth")
        phases_after = self.kv.phase_times()
        # prefill/decode token split + prefix-pool accounting, as deltas
        # over this run (windows may share one SlotKVCache)
        prefill_tokens = self.kv.prefill_tokens_computed - prefill_before
        prefix_after = self.kv.prefix_cache_stats()
        prefix_sec = hit_rate = None
        if prefix_after is not None:
            prefix_sec = {
                k: prefix_after[k] - (prefix_before or {}).get(k, 0)
                for k in ("hits", "misses", "evictions", "tokens_reused",
                          "inserted_blocks")}
            prefix_sec["cached_blocks"] = prefix_after["cached_blocks"]
            asked = prefix_sec["hits"] + prefix_sec["misses"]
            hit_rate = prefix_sec["hits"] / asked if asked else 0.0
        # paged-pool accounting: utilization is CURRENT pool state
        # (blocks still backing live/pinned data), the zero-copy/CoW
        # ledger is the delta over this run.  zero-copy hit rate = aliased
        # blocks over blocks asked of the prefix pool — the fraction of
        # reusable prefix KV shared by POINTER instead of copied.
        paged_sec = zero_copy_rate = None
        if paged_before is not None:
            paged_after = self.kv.paged_stats()
            paged_sec = {
                k: paged_after[k] - paged_before.get(k, 0)
                for k in ("zero_copy_hits", "zero_copy_blocks",
                          "zero_copy_tokens", "cow_copies")}
            paged_sec["num_blocks"] = paged_after["num_blocks"]
            paged_sec["block"] = paged_after["block"]
            paged_sec["blocks_in_use"] = paged_after["blocks_in_use"]
            paged_sec["utilization"] = paged_after["utilization"]
            paged_sec["block_deferrals"] = self._block_deferrals
            if prefix_sec is not None:
                asked = prefix_sec["hits"] + prefix_sec["misses"]
                zero_copy_rate = (paged_sec["zero_copy_blocks"] / asked
                                  if asked else 0.0)
        summary = {
            "mode": self.mode,
            "requests": len(results),
            "completed": len(results),
            # KV-table storage dtype (SlotKVCache kv_dtype — the --serve-
            # kv-dtype memory knob) + the stored bytes behind it, per
            # slot (gated lower-is-better by `analyze diff`: the
            # capacity-per-chip number int8/bf16 storage exists to
            # shrink); both ride into the serve report section
            "serve_kv_dtype": getattr(self.kv, "kv_dtype", None),
            "serve_kv_bytes_per_slot": self.kv.kv_bytes_per_slot(),
            # the served tree as the table holds it (each leaf in the
            # dtype the step uses it in: SlotKVCache._place_params)
            "serve_param_bytes": getattr(self.kv, "param_bytes", None),
            # --serve-kv-layout: paged pool accounting (None/0 under
            # monolithic — the keys are always present so `analyze diff`
            # gates them when both runs page).  blocks_in_use is gated
            # lower (fewer physical blocks for the same streams = the
            # aliasing working), zero-copy rate higher.
            "serve_kv_layout": getattr(self.kv, "kv_layout", "monolithic"),
            "serve_kv_blocks_in_use": (paged_sec["blocks_in_use"]
                                       if paged_sec else None),
            "serve_kv_block_utilization": (paged_sec["utilization"]
                                           if paged_sec else None),
            "serve_prefix_zero_copy_hit_rate": zero_copy_rate,
            "serve_kv_block_deferrals": self._block_deferrals,
            "paged": paged_sec,
            # speculative decoding (draft-k → verify-1): accept rate over
            # THIS run's proposals (None: no draft attached — the key is
            # always present so `analyze diff` gates it when both runs
            # speculate) + the full ledger.  tokens_per_sec counts
            # EMITTED tokens only (BASELINE.md accounting rule); accept
            # rate is workload- and draft-dependent.
            "serve_accept_rate": (self._accepted / self._proposed
                                  if self._proposed else None),
            "speculative": (None if self.draft_kv is None else {
                "draft_k": self.draft_k,
                "proposed_tokens": self._proposed,
                "accepted_tokens": self._accepted,
                "rejected_tokens": self._proposed - self._accepted,
                "draft_iterations": self._draft_iterations,
                "draft_catchup_steps": self._draft_catchup,
                "draft_kv_dtype": self.draft_kv.kv_dtype,
            }),
            "decode_iterations": decode_iterations,
            "prefills": prefills,
            "prefill_chunk": self.prefill_chunk,
            "prefill_chunks": chunks,
            "prefill_tokens": prefill_tokens,
            "decode_tokens": self._decode_tokens,
            "idle_polls": self.idle_polls,
            "tokens_generated": tokens,
            "elapsed_s": elapsed,
            "serve_requests_per_sec": (len(results) / elapsed
                                       if elapsed > 0 else None),
            "serve_tokens_per_sec": (tokens / elapsed
                                     if elapsed > 0 else None),
            # the split the chunked-prefill trade is tuned by: prompt
            # tokens prefilled vs tokens decoded, per wall/virtual second
            "serve_prefill_tokens_per_sec": (prefill_tokens / elapsed
                                             if elapsed > 0 else None),
            "serve_decode_tokens_per_sec": (self._decode_tokens / elapsed
                                            if elapsed > 0 else None),
            # block-level prefix-pool hit rate for THIS run (None: pool
            # off) + the hit/miss/evict ledger behind it
            "serve_prefix_cache_hit_rate": hit_rate,
            "prefix_cache": prefix_sec,
            "serve_ttft_p50_s": _percentile(ttfts, 0.50),
            "serve_ttft_p95_s": _percentile(ttfts, 0.95),
            "serve_ttft_p99_s": _percentile(ttfts, 0.99),
            "serve_itl_p50_s": _percentile(itls, 0.50),
            "serve_itl_p95_s": _percentile(itls, 0.95),
            "serve_itl_p99_s": _percentile(itls, 0.99),
            # queue-pressure attribution (stored-sample path, like the
            # TTFT/ITL percentiles above; the histogram copies ride the
            # `histograms` section below and are asserted within one
            # bucket width of these)
            "serve_queue_wait_p50_s": _percentile(queue_waits, 0.50),
            "serve_queue_wait_p95_s": _percentile(queue_waits, 0.95),
            "serve_queue_wait_p99_s": _percentile(queue_waits, 0.99),
            "queue_depth_p95": depth_hist.quantile(0.95),
            "queue_depth_high_watermark": queue.depth_high_watermark,
            # bounded-admission overload accounting (exact conservation:
            # admitted + shed + unserved == offered)
            "queue_cap": self.queue_cap,
            "offered": offered,
            "admitted": admitted,
            "shed_requests": self._shed_count,
            "shed_rids": list(self._shed_rids),
            "unserved_requests": unserved,
            "serve_shed_rate": (self._shed_count / offered
                                if offered else 0.0),
            # lease drain: the should_stop reason when this window was
            # preempted mid-run (None = ran to completion) — the partial
            # accounting above is still exact
            "preempted": self._preempted,
            # goodput under the SLO (requests/sec meeting BOTH targets;
            # None when no SLOMonitor is attached) + the monitor's section
            "serve_goodput_under_slo": (
                slo_sec.get("goodput_requests_per_sec")
                if slo_sec else None),
            "slo": slo_sec,
            # online log-bucketed histograms of the per-phase attribution
            # (queue_wait / prefill / ttft / itl / queue_depth): p50/95/99
            # within one bucket's relative width of the stored-sample
            # percentiles, mergeable across windows via `metrics=`
            "histograms": self._registry.snapshot(),
            # host-observed seconds inside the kv's compiled programs,
            # as deltas over this run (SlotKVCache.phase_times)
            "device_phase_s": {
                k: phases_after[k] - phases_before.get(k, 0.0)
                for k in phases_after},
            "results": results,
        }
        if self.role is not None:
            # disaggregated-role keys ride the summary ONLY when a role
            # is assigned: the role=None key set stays byte-identical to
            # round 17 (the flag-off summary-key parity pin)
            summary["serve_role"] = self.role
            summary["handoffs_out"] = self._handoffs_out
            summary["handoffs_in"] = self._handoffs_in
        if self.timeline is not None:
            # timeline-derived keys ride the summary ONLY when sampling is
            # on: the flag-off key set stays byte-identical (parity pin)
            tag = self.timeline_tag
            summary["queue_depth_auc"] = self.timeline.stat(
                "queue_depth", "auc", replica=tag)
            summary["kv_blocks_in_use_p95"] = self.timeline.stat(
                "kv_blocks_in_use", "p95", replica=tag)
            summary["timeline_overhead_s"] = self.timeline.overhead_s
        if self.roofline is not None:
            # --roofline keys ride ONLY when a Roofline is attached: the
            # flag-off key set stays byte-identical to round 18 (parity
            # pin).  Achieved rates divide the analytic tallies by the
            # kv's own per-phase device seconds; on an unknown device
            # kind mfu()/mbu() return None — never a fabricated peak.
            rf = self.roofline
            dphase = summary["device_phase_s"]
            pre_s = dphase.get("prefill_s", 0.0)
            dec_s = dphase.get("decode_s", 0.0)
            pre_fps = (self._rf_prefill_flops / pre_s
                       if pre_s > 0 else None)
            dec_fps = (self._rf_decode_flops / dec_s
                       if dec_s > 0 else None)
            dec_bps = (self._rf_decode_bytes / dec_s
                       if dec_s > 0 else None)
            summary["serve_prefill_mfu"] = rf.mfu(pre_fps)
            summary["serve_decode_mbu"] = rf.mbu(dec_bps)
            summary["roofline"] = {
                # analytic model work (BASELINE.md: model flops, never
                # rematerialization; must-read bytes, never bytes moved)
                "prefill_model_flops": self._rf_prefill_flops,
                "decode_model_flops": self._rf_decode_flops,
                "decode_must_read_bytes": self._rf_decode_bytes,
                "prefill_s": pre_s,
                "decode_s": dec_s,
                "prefill_achieved_flops_per_sec": pre_fps,
                "decode_achieved_flops_per_sec": dec_fps,
                "decode_achieved_bytes_per_sec": dec_bps,
                "prefill_mfu": rf.mfu(pre_fps),
                "decode_mfu": rf.mfu(dec_fps),
                "decode_mbu": rf.mbu(dec_bps),
                "device": rf.describe(),
            }
        if self.multi_step is not None:
            # multi-step keys ride ONLY when the flag is set: the
            # flag-off summary key set stays byte-identical to round 19
            # (parity pin).  serve_dispatches counts compiled-program
            # host calls (every jitted entry: prefill, decode, fused
            # rounds, verify — the denominator the k× win divides);
            # serve_host_gap_s is REAL wall time minus host-observed
            # device seconds — Python scheduling + D2H sync + H2D upload,
            # exactly what fusing k iterations amortizes (gated
            # lower-is-better by `analyze diff`).
            dphase = summary["device_phase_s"]
            dispatches = (self.kv.dispatch_count
                          + (self.draft_kv.dispatch_count
                             if self.draft_kv is not None else 0)
                          - disp_before)
            summary["serve_multi_step"] = self.multi_step
            summary["serve_dispatches"] = dispatches
            summary["serve_host_gap_s"] = max(
                wall_elapsed - dphase.get("prefill_s", 0.0)
                - dphase.get("decode_s", 0.0), 0.0)
            if self.roofline is not None:
                summary["roofline"]["dispatches"] = dispatches
                summary["roofline"]["host_gap_s"] = \
                    summary["serve_host_gap_s"]
        return summary
