"""Fault-tolerant serving fleet (ISSUE 15): ReplicaSet supervision with
journaled no-loss failover, seeded fault injection, the exactly-once
emission fence, graceful drain + zero-downtime weight hot-swap, fleet
accounting, and the analyze/harness/CLI surfaces.  Everything here runs on
this container — the fleet is host Python over the GSPMD slot tables, no
shard_map anywhere.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.elastic.lease import LeaseManager
from distributed_tensorflow_tpu.models.gpt import GPTLM
from distributed_tensorflow_tpu.serving import (
    ContinuousBatcher, FaultInjector, FaultSpec, ReplicaSet, Request,
    SlotKVCache, VirtualClock, build_replica_kvs)
from distributed_tensorflow_tpu.serving.fleet import (
    InjectedFault, RequestJournal)


def tiny_gpt(**kw):
    kw.setdefault("vocab_size", 64)
    kw.setdefault("hidden", 32)
    kw.setdefault("layers", 1)
    kw.setdefault("heads", 2)
    kw.setdefault("ffn", 64)
    kw.setdefault("max_len", 48)
    kw.setdefault("dropout_rate", 0.0)
    return GPTLM(**kw)


@pytest.fixture(scope="module")
def model_params():
    model = tiny_gpt()
    x = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 8)),
                    jnp.int32)
    params = model.init(jax.random.key(0), x, train=False)["params"]
    return model, params


def _requests(n=6, seed=3, max_new=8, spread=0.5):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, 64, 6 + i % 4).astype(np.int32),
                    max_new_tokens=max_new, arrival_s=float(i) * spread)
            for i in range(n)]


@pytest.fixture(scope="module")
def oracle_tokens(model_params):
    """Per-request greedy streams from a single-replica batcher — THE
    bitwise reference every fleet schedule must reproduce (greedy decode
    is a pure function of (params, prompt), whatever the batching)."""
    model, params = model_params
    s = ContinuousBatcher(SlotKVCache(model, params, slots=2),
                          clock=VirtualClock()).run(_requests())
    return {r.rid: r.tokens for r in s["results"]}


def _check_parity(summary, oracle, n=6):
    assert summary["completed"] == n, summary["serve_fleet"]
    assert summary["serve_duplicate_emissions"] == 0
    got = {r.rid: r.tokens for r in summary["results"]}
    for rid, toks in oracle.items():
        assert got[rid] == toks, (rid, got[rid], toks)
    assert (summary["admitted"] + summary["shed_requests"]
            + summary["unserved_requests"]) == summary["offered"]


# ------------------------------------------------------------------ lease


def test_lease_trigger_programmatic():
    """trigger() flips the drain flag without a signal; the first reason
    is sticky until reset_trigger; a real preemption signal survives the
    reset (the process is still going away)."""
    lease = LeaseManager(signals=())
    assert lease.should_stop(0) is None
    lease.trigger("weight_swap")
    lease.trigger("later")              # first reason wins
    assert lease.should_stop(0) == "weight_swap"
    assert lease.report()["triggered"] == "weight_swap"
    lease.reset_trigger()
    assert lease.should_stop(0) is None
    with pytest.raises(ValueError, match="reason"):
        lease.trigger("")
    # a delivered SIGNAL is not cleared by reset_trigger
    lease.preempt_signal = 15
    lease.reset_trigger()
    assert lease.should_stop(0) == "signal:SIGTERM"


def test_lease_trigger_thread_safe():
    """Concurrent triggers settle on exactly one reason."""
    lease = LeaseManager(signals=())
    reasons = [f"r{i}" for i in range(16)]
    threads = [threading.Thread(target=lease.trigger, args=(r,))
               for r in reasons]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert lease.should_stop(0) in reasons


def test_lease_off_main_thread_degrade():
    """install() from a non-main thread degrades gracefully: no handler
    is armed (Python restricts signal.signal to the main thread), the
    step budget AND the programmatic trigger still work, and report()
    records that no handler was installed."""
    lease = LeaseManager(max_steps_per_lease=3)
    out = {}

    def worker():
        out["self"] = lease.install()
        out["installed"] = lease.installed

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert out["self"] is lease
    assert out["installed"] is False
    assert lease.report()["signal_handler_installed"] is False
    assert lease.should_stop(2) is None
    assert lease.should_stop(3) == "max_steps_per_lease:3"
    lease.trigger("drain")
    assert lease.should_stop(0) == "drain"
    lease.uninstall()   # no-op, must not raise


# ---------------------------------------------------------- fault injector


def test_fault_spec_parse_grammar():
    specs = FaultInjector.parse(
        "crash:replica=0,iter=3;stall:replica=1,iter=2,stall_s=0.5;"
        "nanlogits:replica=0,iter=4;crash:replica=1,prefill=2;"
        "crash:replica=0,verify=1;crash:replica=1,prob=0.1")
    kinds = [(s.kind, s.site) for s in specs]
    assert kinds == [("crash", "decode"), ("stall", "decode"),
                     ("nanlogits", "decode"), ("crash", "prefill"),
                     ("crash", "verify"), ("crash", "decode")]
    assert specs[0].at == 3 and specs[1].stall_s == 0.5
    assert specs[5].prob == 0.1 and specs[5].at == 0


def test_fault_spec_parse_rejects():
    for bad in ("boom:replica=0,iter=1",       # unknown kind
                "crash:iter=1",                # missing replica
                "crash:replica=0",             # no trigger
                "crash:replica=0,iter=1,prob=0.5",  # two triggers
                "crash:replica=0,wat=1",       # unknown key
                "crash:replica=0,iter=x",      # non-numeric
                "stall:replica=0,iter=1",      # stall without stall_s
                "nanlogits:replica=0,prefill=1",  # non-crash off-decode
                ""):
        with pytest.raises(ValueError):
            FaultInjector.parse(bad)
    with pytest.raises(ValueError, match="crash only"):
        FaultSpec(kind="stall", replica=0, site="verify", at=1,
                  stall_s=1.0)


def test_fault_injector_seeded_prob(model_params):
    """prob triggers draw from the injector's seeded rng: the same seed
    fires at the same site event, a different seed may not — determinism
    is what makes a chaos schedule a regression test."""
    model, params = model_params

    def fire_events(seed):
        inj = FaultInjector("crash:replica=0,prob=0.3", seed=seed)
        kv = SlotKVCache(model, params, slots=1)
        inj.arm(0, kv)
        kv.insert(np.arange(4, dtype=np.int32))
        fired_at = None
        for i in range(40):
            try:
                kv.advance()
            except InjectedFault:
                fired_at = i
                break
        return fired_at

    assert fire_events(7) == fire_events(7)


def test_fault_injector_one_shot(model_params):
    """An at=K spec fires exactly once: the recovered replica-path (or a
    later window over the same armed table) does not re-crash."""
    model, params = model_params
    inj = FaultInjector("crash:replica=0,iter=2", seed=0)
    kv = SlotKVCache(model, params, slots=1)
    inj.arm(0, kv)
    kv.insert(np.arange(4, dtype=np.int32))
    kv.advance()
    with pytest.raises(InjectedFault):
        kv.advance()
    assert len(inj.fired) == 1
    for _ in range(3):
        kv.advance()   # no re-fire
    assert len(inj.fired) == 1


# ---------------------------------------------------------------- journal


def test_journal_fence_exactly_once():
    """The assignment fence: emissions from a stale replica are counted
    and dropped; the current assignment's emissions deliver; a complete
    stream auto-finishes; delivered duplicates stay structurally zero."""
    reqs = [Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=3)]
    j = RequestJournal(reqs)
    j.assign(0, replica=0, t=0.0)
    assert j.emit(0, 0, 11, 1.0) == (True, False, None)
    # failover: replica 0 dies, rid 0 moves to replica 1
    j.mark_failed([0], t=2.0)
    j.assign(0, replica=1, t=2.0, retry=True)
    # zombie replica 0 wakes and keeps emitting: fenced, never delivered
    assert j.emit(0, 0, 12, 3.0)[0] is False
    assert j.fenced_emissions == 1
    accepted, done, recovery = j.emit(0, 1, 12, 4.0)
    assert accepted and not done
    assert recovery == pytest.approx(2.0)   # failure t=2 → first emit t=4
    accepted, done, _ = j.emit(0, 1, 13, 5.0)
    assert accepted and done                # 3 tokens == max_new
    # post-completion emissions (from anyone) are fenced
    assert j.emit(0, 1, 14, 6.0)[0] is False
    assert j.duplicate_emissions == 0
    e = j.entries[0]
    assert e.emitted == [11, 12, 13]
    assert e.completed_by == 1 and e.status == "done"
    assert j.requeues == 1 and j.requeued_rids == {0}


def test_journal_retry_request_resumes_prefix():
    """The retry request re-prefills prompt + emitted prefix with only
    the remaining budget — and a crash AFTER the last emission resumes
    nothing (the stream is already complete)."""
    reqs = [Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=3, eos_id=None),
            Request(rid=1, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=2)]
    j = RequestJournal(reqs)
    j.assign(0, 0, 0.0)
    j.assign(1, 0, 0.0)
    j.emit(0, 0, 50, 1.0)
    retry = j.retry_request(0)
    assert retry.max_new_tokens == 2
    assert retry.prompt.tolist() == [0, 1, 2, 3, 50]
    assert retry.arrival_s == 0.0           # ORIGINAL arrival
    # rid 1: both tokens emitted → done via auto-complete; nothing to
    # resume even if a crash raced the finish bookkeeping
    j.emit(1, 0, 7, 1.0)
    j.emit(1, 0, 8, 2.0)
    assert j.retry_request(1) is None
    assert j.entries[1].status == "done"


def test_journal_least_loaded_routing():
    reqs = [Request(rid=i, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=2) for i in range(5)]
    j = RequestJournal(reqs)
    picks = []
    for rid in range(5):
        r = j.least_loaded([0, 1])
        picks.append(r)
        j.assign(rid, r, 0.0)
    assert picks == [0, 1, 0, 1, 0]   # ties break to the lower id


# -------------------------------------------------- THE chaos acceptance


def test_chaos_kill_one_of_two_replicas_bitwise(model_params,
                                                oracle_tokens):
    """THE acceptance claim: on a seeded VirtualClock trace, killing 1 of
    2 replicas mid-run loses zero requests, duplicates zero emissions,
    and every result is bitwise equal to the unkilled single-replica
    oracle."""
    model, params = model_params
    inj = FaultInjector("crash:replica=0,iter=3", seed=0)
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    clock=VirtualClock(), fault_injector=inj)
    s = rs.run(_requests())
    _check_parity(s, oracle_tokens)
    fl = s["serve_fleet"]
    assert fl["failovers"] == 1
    assert fl["failed_replicas"] == [0]
    assert fl["requeued_requests"] >= 1
    assert fl["retries"] == fl["requeued_requests"]
    assert fl["duplicate_emissions"] == 0
    assert inj.fired and inj.fired[0]["site"] == "decode"
    assert fl["faults_injected"] == inj.fired
    # failover recovery is measured for requests that had emitted tokens
    # before the crash (only those have a stalled reader to recover)
    if any(e["requeued"] for e in fl["failover_events"]):
        assert s["serve_failover_recovery_p95_s"] is None or \
            s["serve_failover_recovery_p95_s"] >= 0


def test_chaos_retry_ttft_charged_from_original_arrival(model_params):
    """A failed-over request's TTFT spans original arrival → first
    delivery on the SURVIVOR when the crash predates its first token:
    the retry never resets the clock (PR 7/11 accounting)."""
    model, params = model_params
    # one request, arrival 0; replica 0 crashes during ITS prefill, so
    # the first token is only ever delivered by replica 1 — after the
    # failover round-trip
    inj = FaultInjector("crash:replica=0,prefill=1", seed=0)
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 1),
                    clock=VirtualClock(), fault_injector=inj)
    s = rs.run([Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                        max_new_tokens=4, arrival_s=0.0)])
    assert s["completed"] == 1
    r = s["results"][0]
    assert r.arrival_s == 0.0
    assert r.ttft_s == r.first_token_s - 0.0


def test_chaos_kill_during_prefill_chunk(model_params, oracle_tokens):
    """Kill-during-prefill-chunk (chunked prefill composed): the requeued
    request's emitted stream stays bitwise equal to the unkilled oracle —
    a dead mid-prefill admission re-prefills from scratch on the
    survivor."""
    model, params = model_params
    # chunking itself never changes tokens (PR 10 pin) — so the chunked
    # fleet is held to the same oracle
    inj = FaultInjector("crash:replica=0,prefill=2", seed=0)
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    clock=VirtualClock(), prefill_chunk=3,
                    fault_injector=inj)
    s = rs.run(_requests())
    _check_parity(s, oracle_tokens)
    assert s["serve_fleet"]["failovers"] == 1
    assert inj.fired[0]["site"] == "prefill"


# round 20 fast-lane repair: the heaviest chaos sites ride the slow
# lane — four cheaper chaos-site tests stay fast in this suite
@pytest.mark.slow
def test_chaos_kill_between_verify_and_commit(model_params,
                                              oracle_tokens):
    """Kill-between-verify-and-commit (speculative decoding composed):
    the verify round's proposals die with the replica — nothing of the
    uncommitted block reaches the journal, and the requeued requests'
    streams stay bitwise equal to the non-speculative oracle."""
    model, params = model_params
    inj = FaultInjector("crash:replica=0,verify=2", seed=0)
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    clock=VirtualClock(),
                    draft_kvs=build_replica_kvs(model, params, 2, 2),
                    draft_k=3, fault_injector=inj)
    s = rs.run(_requests())
    _check_parity(s, oracle_tokens)
    assert s["serve_fleet"]["failovers"] == 1
    assert inj.fired[0]["site"] == "verify"
    # self-draft: every surviving verify round accepts everything
    assert s["serve_accept_rate"] == 1.0
    led = s["speculative"]
    assert led["accepted_tokens"] + led["rejected_tokens"] \
        == led["proposed_tokens"]


# round 20 fast-lane repair: chaos × spec-decode composition variant
@pytest.mark.slow
def test_chaos_decode_site_kill_fires_under_spec_decode(model_params,
                                                        oracle_tokens):
    """`iter=K` must be able to kill a SPECULATIVE replica: its target
    iterations are verify rounds, not single-token advances — the
    injector counts them as decode iterations (a spec-decoding fleet
    was otherwise unkillable by the decode site)."""
    model, params = model_params
    inj = FaultInjector("crash:replica=0,iter=2", seed=0)
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    clock=VirtualClock(),
                    draft_kvs=build_replica_kvs(model, params, 2, 2),
                    draft_k=2, fault_injector=inj)
    s = rs.run(_requests())
    _check_parity(s, oracle_tokens)
    assert s["serve_fleet"]["failovers"] == 1
    assert inj.fired and inj.fired[0]["site"] == "decode"


def test_chaos_nanlogits_detected_never_delivered(model_params,
                                                  oracle_tokens):
    """Nonfinite-logits corruption: the injector degrades the sampled
    token vector to out-of-range ids; the fleet's cheap host check fails
    the replica BEFORE anything reaches the journal — delivered streams
    stay bitwise clean."""
    model, params = model_params
    vocab = 64
    inj = FaultInjector("nanlogits:replica=0,iter=2", seed=0)
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    clock=VirtualClock(), fault_injector=inj)
    s = rs.run(_requests())
    _check_parity(s, oracle_tokens)
    fl = s["serve_fleet"]
    assert fl["failovers"] == 1
    assert fl["failover_events"][0]["kind"] == "corruption"
    for r in s["results"]:
        assert all(0 <= t < vocab for t in r.tokens)


def test_chaos_threaded_wall_clock(model_params, oracle_tokens):
    """The same kill under real threads + WallClock: exactly-once and
    bitwise parity are schedule-independent claims."""
    model, params = model_params
    reqs = _requests()
    for r in reqs:
        r.arrival_s = 0.0
    inj = FaultInjector("crash:replica=0,iter=3", seed=0)
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    fault_injector=inj)
    try:
        s = rs.run(reqs)
    finally:
        rs.close()
    _check_parity(s, oracle_tokens)
    assert s["serve_fleet"]["failovers"] == 1


# round 20 fast-lane repair: test_zombie_late_summary_not_absorbed
# keeps the fast zombie-fencing representative
@pytest.mark.slow
def test_stall_watchdog_fences_zombie(model_params, oracle_tokens):
    """A stalled replica is failed over by the supervisor's watchdog and
    FENCED, not killed: when the zombie wakes and keeps emitting, the
    journal rejects its stale emissions — zero duplicates delivered, all
    requests complete on the survivor, streams bitwise clean."""
    model, params = model_params
    reqs = _requests()
    for r in reqs:
        r.arrival_s = 0.0
    kvs = build_replica_kvs(model, params, 2, 2)
    for kv in kvs:
        # warm every program OUTSIDE the watchdog window: the watchdog
        # cannot tell a stall from a first-program XLA compile
        for plen in (6, 7, 8, 9):
            slot, _ = kv.insert(np.arange(plen, dtype=np.int32) % 64)
            kv.advance()
            kv.evict(slot)
    inj = FaultInjector("stall:replica=0,iter=2,stall_s=1.5", seed=0)
    rs = ReplicaSet(kvs, watchdog_timeout_s=0.3, fault_injector=inj)
    try:
        s = rs.run(reqs)
    finally:
        rs.close(timeout_s=15.0)
    _check_parity(s, oracle_tokens)
    fl = s["serve_fleet"]
    assert fl["watchdog_stalls"] >= 1
    assert fl["failover_events"][0]["kind"] == "watchdog_stall"
    # the zombie woke AFTER failover and its live slots kept decoding:
    # those emissions must have been fenced (close() waited it out)
    assert rs.journal.fenced_emissions > 0
    assert rs.journal.duplicate_emissions == 0


def test_retry_exhaustion_is_lost_not_hung(model_params):
    """Bounded retry: when every replica dies, pending requests go
    terminal `lost` (counted into unserved_requests) instead of hanging
    the fleet — conservation stays exact."""
    model, params = model_params
    inj = FaultInjector("crash:replica=0,iter=2;crash:replica=1,iter=2",
                        seed=0)
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    clock=VirtualClock(), retry_limit=1,
                    fault_injector=inj)
    s = rs.run(_requests())
    fl = s["serve_fleet"]
    assert fl["failed_replicas"] == [0, 1]
    assert s["unserved_requests"] > 0
    assert fl["lost_requests"] == s["unserved_requests"]
    assert (s["admitted"] + s["shed_requests"]
            + s["unserved_requests"]) == s["offered"] == 6
    assert s["serve_duplicate_emissions"] == 0


# --------------------------------------------------------------- hot swap


def test_hot_swap_zero_downtime(model_params, oracle_tokens):
    """The hot-swap acceptance: all in-flight requests complete across
    the swap, swap_generations >= 1, and the fleet never dropped below
    N-1 admitting replicas (same params re-installed → tokens bitwise
    unchanged)."""
    model, params = model_params
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    clock=VirtualClock())
    rs.schedule_swap(params, after_completions=2)
    s = rs.run(_requests())
    _check_parity(s, oracle_tokens)
    fl = s["serve_fleet"]
    assert fl["swap_generations"] == 1
    assert rs.swap_generations == 1
    assert fl["min_admitting_replicas"] >= 1   # never below N-1 of 2
    assert all(pr["generation"] == 1 for pr in fl["per_replica"])


def test_hot_swap_installs_new_params(model_params):
    """A swap really installs the new weights: requests admitted after
    the swap decode under the swapped params (different streams), while
    requests that finished before it used the old ones.  One replica —
    the drain interrupts its run mid-window, the swap lands while the
    later arrivals are still queued, and serving resumes on the same
    lease with the new weights."""
    model, params = model_params
    new_params = jax.tree.map(lambda t: t * 0.5, params)
    # two phases: rids 0-1 complete pre-swap, rids 2-3 arrive after
    reqs = [Request(rid=i, prompt=np.arange(5, dtype=np.int32),
                    max_new_tokens=6,
                    arrival_s=0.0 if i < 2 else 50.0)
            for i in range(4)]
    rs = ReplicaSet(build_replica_kvs(model, params, 1, 2),
                    clock=VirtualClock())
    rs.schedule_swap(new_params, after_completions=2)
    s = rs.run(reqs)
    assert s["completed"] == 4
    assert rs.swap_generations == 1
    toks = {r.rid: r.tokens for r in s["results"]}
    old = ContinuousBatcher(SlotKVCache(model, params, slots=1),
                            clock=VirtualClock()).run(
        [Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                 max_new_tokens=6)])["results"][0].tokens
    new = ContinuousBatcher(SlotKVCache(model, new_params, slots=1),
                            clock=VirtualClock()).run(
        [Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                 max_new_tokens=6)])["results"][0].tokens
    assert toks[0] == old and toks[1] == old
    assert toks[2] == new and toks[3] == new
    assert old != new   # the perturbation must actually matter


def test_replicas_share_one_narrowed_tree():
    """A float32 checkpoint of a bfloat16 model is narrowed ONCE for the
    fleet (``SlotKVCache._place_params``): ``build_replica_kvs`` hands
    every replica the first table's tree and a hot swap hands every
    replica the first swapped one's, so replicas on one device still
    share one set of buffers."""
    model = tiny_gpt(dtype=jnp.bfloat16)
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    kvs = build_replica_kvs(model, params, 2, 2)

    def shared():
        first, second = (jax.tree.leaves(kv.params) for kv in kvs)
        assert any(t.dtype == jnp.bfloat16 for t in first)
        return all(a is b for a, b in zip(first, second))

    assert shared()
    built = jax.tree.leaves(kvs[0].params)
    rs = ReplicaSet(kvs, clock=VirtualClock())
    rs.schedule_swap(jax.tree.map(lambda t: t * 0.5, params),
                     after_completions=2)
    assert rs.run(_requests())["completed"] == 6
    assert rs.swap_generations == 1
    assert shared()
    assert not any(a is b for a, b in
                   zip(jax.tree.leaves(kvs[0].params), built))


def test_swap_params_validation(model_params):
    """swap_params must be a compiled-program cache hit: a different
    tree structure or leaf shape is rejected, the table untouched."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=1)
    other = tiny_gpt(hidden=16, ffn=32)
    x = jnp.zeros((1, 4), jnp.int32)
    other_params = other.init(jax.random.key(0), x, train=False)["params"]
    with pytest.raises(ValueError):
        kv.swap_params(other_params)
    flat = jax.tree.leaves(params)
    assert jax.tree.leaves(kv.params)[0].shape == flat[0].shape
    kv.swap_params(jax.tree.map(lambda t: t, params))   # same-shape OK


# ------------------------------------------------------- fleet accounting


def test_fleet_merged_histograms_and_goodput(model_params):
    """Per-replica MetricsRegistry histograms merge into fleet totals
    (the PR 11 merge, applied to its designed purpose): the merged ttft
    count equals completed requests, and the serve_fleet section carries
    per-replica + merged goodput under the SLO."""
    from distributed_tensorflow_tpu.observability import SLOMonitor

    model, params = model_params
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    clock=VirtualClock(),
                    slo=SLOMonitor(1e9, 1e9))   # everything is goodput
    s = rs.run(_requests())
    assert s["completed"] == 6
    assert s["histograms"]["ttft"]["count"] == 6
    fl = s["serve_fleet"]
    assert s["slo"]["good_requests"] == 6
    assert s["serve_goodput_under_slo"] > 0
    per = {pr["replica"]: pr for pr in fl["per_replica"]}
    assert sum(pr["completed"] for pr in per.values()) == 6
    assert fl["merged_goodput_under_slo"] == pytest.approx(
        sum(pr["goodput_requests_per_sec"] or 0 for pr in per.values()))
    # both replicas actually served (least-loaded routing spreads a
    # staggered trace)
    assert all(pr["completed"] > 0 for pr in per.values())


def test_fleet_serve_section_and_flatten(model_params):
    """The fleet summary rides serve_section/load_report unchanged: the
    per-chip keys derive, serve_fleet survives, and the new gate keys
    flatten to the top level for `analyze diff`."""
    import json

    from distributed_tensorflow_tpu.observability import serve_section
    from distributed_tensorflow_tpu.observability.analyze import (
        _DIFF_METRICS, load_report)

    model, params = model_params
    inj = FaultInjector("crash:replica=0,iter=3", seed=0)
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    clock=VirtualClock(), fault_injector=inj)
    sec = serve_section(rs.run(_requests()), 8)
    assert "results" not in sec
    assert sec["serve_requests_per_sec_per_chip"] == pytest.approx(
        sec["serve_requests_per_sec"] / 8)
    assert sec["serve_fleet"]["failovers"] == 1
    json.dumps(sec)   # the section must stay JSON
    directions = dict(_DIFF_METRICS)
    assert directions["serve_failover_recovery_p95_s"] == "lower"
    assert directions["serve_duplicate_emissions"] == "lower"
    flat = load_report_from_dict({"serve": sec}, load_report)
    assert flat["serve_duplicate_emissions"] == 0
    assert flat["serve_failover_recovery_p95_s"] is not None


def load_report_from_dict(obj, load_report):
    import json
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(obj, f)
        path = f.name
    return load_report(path)


def test_waterfall_requeue_rows(model_params, tmp_path):
    """analyze serve renders failover: the retried request's new span
    segment carries its attempt number + original arrival, the requeue
    hops ride the output, and the text renderer draws them."""
    from distributed_tensorflow_tpu.observability import Tracer
    from distributed_tensorflow_tpu.observability.analyze import (
        read_jsonl, render_waterfall_text, serve_waterfall)

    model, params = model_params
    trace = tmp_path / "fleet_trace.jsonl"
    tracer = Tracer(path=str(trace))
    inj = FaultInjector("crash:replica=0,iter=3", seed=0)
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    clock=VirtualClock(), tracer=tracer,
                    fault_injector=inj)
    s = rs.run(_requests())
    tracer.close()
    wf = serve_waterfall(read_jsonl(str(trace)))
    assert wf["requeue_n"] == s["serve_fleet"]["retries"] > 0
    hops = {q["rid"] for q in wf["requeues"]}
    retried_rows = [r for r in wf["requests"] if r["attempt"] > 1]
    assert retried_rows, wf["requests"]
    for row in retried_rows:
        assert row["rid"] in hops
        # keyed to the ORIGINAL arrival (the retry accounting rule)
        assert row["original_arrival_s"] == pytest.approx(
            row["rid"] * 0.5)
    text = render_waterfall_text(wf)
    assert ">" in text and "requeue r0→r1" in text
    assert "retry#2" in text
    # every hop records where the stream stood when it moved
    for q in wf["requeues"]:
        assert q["emitted"] >= 0 and q["reason"]


# ------------------------------------------------------- harness surface


def _lm_fn(batch_size, type="train", **kw):
    from distributed_tensorflow_tpu.data.loaders import load_lm_dataset

    return load_lm_dataset(seq_len=16, vocab_size=64, n_train=64,
                           n_test=32, split=type)


def test_harness_fleet_e2e_fsdp():
    """--serve-replicas 2 + --serve-fault-spec through the harness: the
    serve section carries serve_fleet + the gate keys, every request
    completes exactly once, and the exit policy flag is clean."""
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    summary = run(ExperimentConfig(
        engine="fsdp", model="gpt", dataset="lm_synth", dataset_fn=_lm_fn,
        n_devices=8, batch_size=4, log_every=0,
        model_args={"hidden": 32, "layers": 1, "heads": 2, "ffn": 64,
                    "max_len": 32},
        serve_requests=8, serve_slots=2, serve_max_new=4,
        serve_prompt_len=4, serve_replicas=2,
        serve_fault_spec="crash:replica=0,iter=2"))
    sec = summary["serve"]
    assert sec == summary["run_report"]["serve"]
    assert sec["mode"] == "fleet"
    assert sec["replicas"] == 2
    assert sec["completed"] == 8
    assert sec["serve_duplicate_emissions"] == 0
    assert sec["serve_fleet"]["failovers"] == 1
    assert sec["serve_fleet"]["faults_injected"]
    assert summary["serve_exit_policy"] == 0
    assert sec["serve_requests_per_sec_per_chip"] > 0
    assert sec["serve_goodput_under_slo_per_chip"] is not None


@pytest.mark.slow    # round 20 fast-lane repair: the e2e
# representative is test_harness_fleet_e2e_fsdp
def test_harness_fleet_hot_swap_e2e_fsdp():
    """--serve-hot-swap: the drill drains + swaps replica-by-replica —
    swap_generations >= 1, never below N-1 admitting, clean policy."""
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    summary = run(ExperimentConfig(
        engine="fsdp", model="gpt", dataset="lm_synth", dataset_fn=_lm_fn,
        n_devices=8, batch_size=4, log_every=0,
        model_args={"hidden": 32, "layers": 1, "heads": 2, "ffn": 64,
                    "max_len": 32},
        serve_requests=8, serve_slots=2, serve_max_new=4,
        serve_prompt_len=4, serve_replicas=2, serve_hot_swap=True))
    sec = summary["serve"]
    fl = sec["serve_fleet"]
    assert sec["completed"] == 8
    assert fl["swap_generations"] >= 1
    assert fl["min_admitting_replicas"] >= 1
    assert summary["serve_exit_policy"] == 0


@pytest.mark.slow    # round 20 fast-lane repair (see above)
def test_harness_degraded_window_flags_exit_policy(tmp_path):
    """A serve window that loses requests (single replica, killed, no
    survivor to fail over to) must surface it: serve_exit_policy = 1 and
    a structured serve_warning event in the result stream — CI gates on
    the flag instead of excavating the summary."""
    import json

    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    result_path = tmp_path / "results.jsonl"
    summary = run(ExperimentConfig(
        engine="fsdp", model="gpt", dataset="lm_synth", dataset_fn=_lm_fn,
        n_devices=8, batch_size=4, log_every=0,
        model_args={"hidden": 32, "layers": 1, "heads": 2, "ffn": 64,
                    "max_len": 32},
        result_path=str(result_path),
        serve_requests=6, serve_slots=2, serve_max_new=4,
        serve_prompt_len=4, serve_replicas=1,
        serve_fault_spec="crash:replica=0,iter=2"))
    sec = summary["serve"]
    assert sec["unserved_requests"] > 0
    assert summary["serve_exit_policy"] == 1
    events = [json.loads(line) for line in
              result_path.read_text().splitlines()]
    warnings = [e for e in events if e["event"] == "serve_warning"]
    assert warnings and any("unserved" in r for r in
                            warnings[0]["reasons"])
    # conservation still exact on the degraded window
    assert (sec["admitted"] + sec["shed_requests"]
            + sec["unserved_requests"]) == sec["offered"] == 6


def test_harness_fleet_validation_pre_train():
    """Bad fleet flags fail BEFORE training, like every other serve
    flag."""
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    base = dict(engine="fsdp", model="gpt", dataset="lm_synth",
                dataset_fn=_lm_fn, n_devices=8, batch_size=4,
                log_every=0,
                model_args={"hidden": 32, "layers": 1, "heads": 2,
                            "ffn": 64, "max_len": 32},
                serve_requests=4, serve_slots=2, serve_max_new=4,
                serve_prompt_len=4)
    with pytest.raises(ValueError, match="serve-replicas"):
        run(ExperimentConfig(**base, serve_replicas=0))
    with pytest.raises(ValueError, match="fault-spec"):
        run(ExperimentConfig(**base, serve_fault_spec="boom:replica=0"))
    with pytest.raises(ValueError, match="replica 3"):
        run(ExperimentConfig(**base, serve_replicas=2,
                             serve_fault_spec="crash:replica=3,iter=1"))
    with pytest.raises(ValueError, match="serve-watchdog"):
        run(ExperimentConfig(**base, serve_watchdog_s=-1.0))


def test_cli_fleet_flags_parse():
    from distributed_tensorflow_tpu.cli import build_parser

    args = build_parser().parse_args(
        ["--serve", "8", "--serve-replicas", "2",
         "--serve-fault-spec", "crash:replica=0,iter=3",
         "--serve-hot-swap", "--serve-watchdog", "5.5"])
    assert args.serve_replicas == 2
    assert args.serve_fault_spec == "crash:replica=0,iter=3"
    assert args.serve_hot_swap is True
    assert args.serve_watchdog == 5.5


def test_zombie_late_summary_not_absorbed(model_params):
    """A watchdog-failed replica's run eventually returns — its late
    summary must NOT fold into the fleet ledgers, and its shed report
    must not terminal-ize a request a survivor now owns (the same fence
    as emission, applied to accounting)."""
    model, params = model_params
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    clock=VirtualClock())
    rs.run(_requests())
    r0 = rs.replicas[0]
    rs.journal = RequestJournal([
        Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                max_new_tokens=4)])
    rs.journal.assign(0, 1, 0.0)   # the SURVIVOR owns rid 0 now
    r0.state = "failed"
    fake = {"shed_rids": [0], "shed_requests": 1,
            "decode_iterations": 99, "preempted": None}
    r0.batcher.run = lambda queue, on_token=None: fake
    before = dict(rs._sums)
    rs._serve_once(r0)
    assert rs._sums == before, "zombie summary was absorbed"
    assert rs.journal.entries[0].status == "pending"
    # the fenced finalize itself: the dead replica's shed claim is a
    # no-op on a request assigned elsewhere
    rs.journal.finalize_if_assigned(0, 0, "shed")
    assert rs.journal.entries[0].status == "pending"
    rs.journal.finalize_if_assigned(0, 1, "shed")
    assert rs.journal.entries[0].status == "shed"


def test_waterfall_attempts_not_fooled_by_multi_window(model_params,
                                                       tmp_path):
    """Bench traces hold several windows reusing rids 0..n−1: same-rid
    rows from LATER windows are not retries — attempt numbering anchors
    on requeue hops, not bare rid repetition."""
    from distributed_tensorflow_tpu.observability import Tracer
    from distributed_tensorflow_tpu.observability.analyze import (
        read_jsonl, serve_waterfall)

    model, params = model_params
    trace = tmp_path / "two_windows.jsonl"
    tracer = Tracer(path=str(trace))
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    clock=VirtualClock(), tracer=tracer)
    rs.run(_requests())
    rs.run(_requests())    # same rids, second window, zero failovers
    tracer.close()
    wf = serve_waterfall(read_jsonl(str(trace)))
    assert wf["requests_n"] == 12 and wf["requeue_n"] == 0
    assert all(r["attempt"] == 1 for r in wf["requests"]), \
        [r for r in wf["requests"] if r["attempt"] > 1]


# round 20 fast-lane repair: reuse variant of the fleet run path the
# fast e2e test already drives once
@pytest.mark.slow
def test_replica_set_run_reuse(model_params, oracle_tokens):
    """A ReplicaSet serves window after window (the bench shape): the
    second run()'s journal is fresh, surviving replicas serve again, and
    parity holds both times — including under real threads, where the
    first run's shutdown left stop events set."""
    model, params = model_params
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    clock=VirtualClock())
    for _ in range(2):
        s = rs.run(_requests())
        _check_parity(s, oracle_tokens)
    rs2 = ReplicaSet(build_replica_kvs(model, params, 2, 2))
    try:
        for _ in range(2):
            reqs = _requests()
            for r in reqs:
                r.arrival_s = 0.0
            s = rs2.run(reqs)
            _check_parity(s, oracle_tokens)
    finally:
        rs2.close()


def test_replica_set_validation(model_params):
    model, params = model_params
    with pytest.raises(ValueError, match="at least one"):
        ReplicaSet([])
    kvs = build_replica_kvs(model, params, 2, 2)
    with pytest.raises(ValueError, match="1:1"):
        ReplicaSet(kvs, draft_kvs=build_replica_kvs(model, params, 1, 2))
    with pytest.raises(ValueError, match="retry_limit"):
        ReplicaSet(kvs, retry_limit=-1)
    with pytest.raises(RuntimeError, match="already in flight"):
        rs = ReplicaSet(kvs, clock=VirtualClock())
        rs.schedule_swap(params)
        rs.schedule_swap(params)
