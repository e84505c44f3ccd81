"""Disaggregated prefill/decode fleet (ISSUE 18): serialized KV handoff
between heterogeneous replicas, prefix-affinity routing, queue-driven
autoscaling, per-role conservation, the handoff fault site, and the
analyze/harness/CLI surfaces.  Everything here runs on this container —
the fleet is host Python over the GSPMD slot tables, no shard_map
anywhere.  (File named to sort AFTER test_serving.py: the single-batcher
invariants must fail first when the shared substrate breaks.)
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.gpt import GPTLM
from distributed_tensorflow_tpu.serving import (
    AutoscalePolicy, ContinuousBatcher, FaultInjector, ReplicaSet,
    Request, SlotKVCache, VirtualClock, build_replica_kvs)
from distributed_tensorflow_tpu.serving.fleet import RequestJournal


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


def _oracle(model, params, requests):
    """Single-replica greedy streams — the bitwise reference every fleet
    schedule (homogeneous, disaggregated, autoscaled) must reproduce."""
    s = ContinuousBatcher(SlotKVCache(model, params, slots=2),
                          clock=VirtualClock()).run(list(requests))
    return {r.rid: r.tokens for r in s["results"]}


def _streams(summary):
    return {r.rid: r.tokens for r in summary["results"]}


def _assert_conservation(summary):
    assert (summary["admitted"] + summary["shed_requests"]
            + summary["unserved_requests"]) == summary["offered"]


@pytest.fixture(scope="module")
def default_oracle(model_params):
    model, params = model_params
    return _oracle(model, params, _requests())


# --------------------------------------------------- handoff roundtrip


def _roundtrip(model, params, steps=6, **kv_kwargs):
    """extract → restore into a SECOND table of the same config, decode
    BOTH on; returns (source stream, restored stream).  extract leaves
    the source slot live, so the source's own continuation is the
    reference the restored table must reproduce."""
    prompt = np.arange(1, 13, dtype=np.int32)
    src = SlotKVCache(model, params, 2, **kv_kwargs)
    s_src, f_src = src.insert(prompt)
    payload = src.extract_handoff(s_src)

    dst = SlotKVCache(model, params, 2, **kv_kwargs)
    slot, tok = dst.restore_handoff(payload)
    assert tok == int(f_src)
    ref_toks, got = [int(f_src)], [int(tok)]
    for _ in range(steps):
        ref_toks.append(int(src.advance()[s_src]))
        got.append(int(dst.advance()[slot]))
    src.evict(s_src)
    assert not src.active.any()
    return ref_toks, got


def test_handoff_roundtrip_f32_bitwise(model_params):
    """f32 storage: the serialized payload is byte-exact, so the greedy
    continuation after restore is bitwise the source table's."""
    model, params = model_params
    ref, got = _roundtrip(model, params)
    assert got == ref


def test_handoff_roundtrip_bf16_bitwise(model_params):
    model, params = model_params
    ref, got = _roundtrip(model, params, kv_dtype=jnp.bfloat16)
    assert got == ref


def test_handoff_roundtrip_int8_scales_ride_along(model_params):
    """int8 storage: the per-vector f32 scale leaves travel in the same
    block trees, so restore is byte-exact against the int8 source — the
    continuation agrees with the int8 reference (tolerance vs the f32
    oracle is the storage dtype's, not the handoff's)."""
    model, params = model_params
    ref, got = _roundtrip(model, params, kv_dtype="int8")
    assert got == ref


# round 20 fast-lane repair: mesh variant — the handoff roundtrips above
# keep the fast representatives
@pytest.mark.slow
def test_handoff_roundtrip_mesh8_slot_sharded(model_params, mesh8):
    """The handoff works across slot-sharded tables: extract gathers
    through the mesh, restore scatters back — streams stay bitwise."""
    model, params = model_params
    prompt = np.arange(1, 13, dtype=np.int32)
    ref = SlotKVCache(model, params, 8, mesh=mesh8)
    s_ref, f_ref = ref.insert(prompt)
    ref_toks = [int(f_ref)] + [int(ref.advance()[s_ref])
                               for _ in range(5)]
    src = SlotKVCache(model, params, 8, mesh=mesh8)
    s_src, _ = src.insert(prompt)
    payload = src.extract_handoff(s_src)
    dst = SlotKVCache(model, params, 8, mesh=mesh8)
    slot, tok = dst.restore_handoff(payload)
    got = [int(tok)] + [int(dst.advance()[slot]) for _ in range(5)]
    assert got == ref_toks


# ------------------------------------------------- disaggregated fleet


def _mixed_requests(n=9, max_new=6):
    """Every third request carries a long prompt — the interference
    shape disaggregation exists to remove from decode iterations."""
    rng = np.random.default_rng(5)
    reqs = []
    for i in range(n):
        plen = 36 if i % 3 == 2 else 6
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, 64, plen).astype(np.int32),
            max_new_tokens=max_new, arrival_s=float(i) * 0.5))
    return reqs


def test_disagg_parity_accounting_and_ttft(model_params, default_oracle):
    """1P+1D fleet on a virtual clock with a modeled 0.25 s transfer:
    greedy streams bitwise vs the single-batcher oracle (the transfer
    shifts time, never tokens), every request hands off exactly once,
    the per-role partitions sum to the fleet conservation identity, and
    TTFT is arrival → first token INCLUDING the handoff — every
    request's TTFT carries at least the 0.25 s."""
    model, params = model_params
    reqs = _requests()
    oracle = default_oracle
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    clock=VirtualClock(), roles=["prefill", "decode"],
                    handoff_s=0.25)
    summary = rs.run(list(reqs))
    assert _streams(summary) == oracle
    _assert_conservation(summary)
    d = summary["serve_disagg"]
    assert d["prefill_replicas"] == 1 and d["decode_replicas"] == 1
    assert d["handoffs_initiated"] == d["handoffs_delivered"] == len(reqs)
    assert d["handoffs_dropped"] == 0
    assert d["handoff_s"] == 0.25
    per = d["per_role"]
    for key in ("done", "shed", "lost", "unserved", "pending"):
        assert per["prefill"][key] + per["decode"][key] == {
            "done": summary["completed"], "shed": summary["shed_requests"],
            "lost": 0, "unserved": summary["unserved_requests"],
            "pending": 0}[key]
    assert summary["serve_replica_seconds"] > 0
    for r in summary["results"]:
        assert r.ttft_s >= 0.25, (r.rid, r.ttft_s)


# round 20 fast-lane repair: the ITL-headline lane race rides the slow
# lane; test_affinity_beats_least_loaded_hit_rate keeps a fast
# perf-claim representative for the disagg suite
@pytest.mark.slow
def test_disagg_beats_homogeneous_itl_on_same_trace(model_params):
    """The acceptance comparison: same seeded trace, same total replica
    count, virtual time with per-token prefill cost — the disaggregated
    fleet's ITL p95 AND p99 are strictly lower (decode replicas never
    share an iteration with a 36-token prompt), greedy streams equal."""
    model, params = model_params
    reqs = _mixed_requests()
    oracle = _oracle(model, params, reqs)

    def run(roles):
        clock = VirtualClock(tick=1.0, prefill_token_tick=0.25)
        rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                        clock=clock, prefill_chunk=8, roles=roles,
                        parallel_lanes=True)
        return rs.run(_mixed_requests())

    homog = run(None)
    disagg = run(["prefill", "decode"])
    assert _streams(homog) == oracle
    assert _streams(disagg) == oracle
    assert disagg["serve_itl_p95_s"] < homog["serve_itl_p95_s"], (
        disagg["serve_itl_p95_s"], homog["serve_itl_p95_s"])
    assert disagg["serve_itl_p99_s"] < homog["serve_itl_p99_s"]
    assert disagg["serve_parallel_lanes"] is True


def test_roles_validation(model_params):
    model, params = model_params
    kvs = build_replica_kvs(model, params, 2, 2)
    with pytest.raises(ValueError, match="1:1"):
        ReplicaSet(kvs, clock=VirtualClock(), roles=["prefill"])
    with pytest.raises(ValueError, match="prefill"):
        ReplicaSet(kvs, clock=VirtualClock(), roles=["decode", "decode"])
    with pytest.raises(ValueError, match="role"):
        ReplicaSet(kvs, clock=VirtualClock(), roles=["prefill", "chef"])


# ------------------------------------------------- affinity routing


def _shared_requests(n=8, shared_len=8, tail=4, seed=11):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, 64, shared_len).astype(np.int32)
    return [Request(rid=i,
                    prompt=np.concatenate(
                        [shared,
                         rng.integers(0, 64, tail).astype(np.int32)]),
                    max_new_tokens=4, arrival_s=float(i) * 0.5)
            for i in range(n)]


def test_affinity_beats_least_loaded_hit_rate(model_params):
    """Same seeded shared-prefix trace, same 2-replica fleet: the
    affinity router lands repeats where the pool is warm, so its
    fleet-wide hit rate is STRICTLY higher than least-loaded's — and
    the gate key only exists under the non-default router."""
    model, params = model_params

    def run(routing):
        kvs = build_replica_kvs(model, params, 2, 2,
                                prefix_cache_blocks=8, prefix_block=4)
        rs = ReplicaSet(kvs, clock=VirtualClock(), routing=routing)
        return rs.run(_shared_requests())

    ll = run("least-loaded")
    aff = run("affinity")
    assert ll["completed"] == aff["completed"] == 8
    assert "serve_fleet_prefix_hit_rate" not in ll
    assert "serve_routing" not in ll
    assert aff["serve_routing"] == "affinity"
    assert aff["serve_fleet_prefix_hit_rate"] \
        > ll["serve_prefix_cache_hit_rate"]
    # same streams either way: routing changes placement, not tokens
    assert _streams(ll) == _streams(aff)


def test_routing_validation(model_params):
    model, params = model_params
    with pytest.raises(ValueError, match="routing"):
        ReplicaSet(build_replica_kvs(model, params, 2, 2),
                   clock=VirtualClock(), routing="round-robin")


# ------------------------------------------------- autoscaling


def _bursty_requests(n=26, max_new=4):
    """Quiet head, a burst in the middle, quiet tail — the diurnal shape
    the queue-watermark policy exists for."""
    rng = np.random.default_rng(9)
    reqs, t = [], 0.0
    for i in range(n):
        t += 0.1 if 8 <= i < 20 else 2.0
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, 64, 6).astype(np.int32),
            max_new_tokens=max_new, arrival_s=t))
    return reqs


def test_autoscale_diurnal_scales_up_and_saves_replica_seconds(
        model_params):
    """The burst wakes dormant replicas (scale_ups >= 1), every request
    completes, conservation holds, and the replica-seconds actually paid
    stay under the static-fleet bill (3 × elapsed)."""
    model, params = model_params
    reqs = _bursty_requests()
    oracle = _oracle(model, params, reqs)
    rs = ReplicaSet(build_replica_kvs(model, params, 3, 2),
                    clock=VirtualClock(), autoscale="1:3")
    summary = rs.run(_bursty_requests())
    assert _streams(summary) == oracle
    _assert_conservation(summary)
    auto = summary["autoscale"]
    assert auto["min_replicas"] == 1 and auto["max_replicas"] == 3
    assert auto["scale_ups"] >= 1
    assert summary["serve_replica_seconds"] > 0
    assert summary["serve_replica_seconds"] \
        < 3 * summary["elapsed_s"], summary["serve_replica_seconds"]
    for ev in auto["events"]:
        assert ev["action"] in ("up", "down")


def test_autoscale_policy_grammar():
    pol = AutoscalePolicy.parse("2:5")
    assert pol.min_replicas == 2 and pol.max_replicas == 5
    with pytest.raises(ValueError, match="MIN:MAX"):
        AutoscalePolicy.parse("3")
    with pytest.raises(ValueError, match="MIN:MAX"):
        AutoscalePolicy.parse("a:b")
    with pytest.raises(ValueError, match="max_replicas"):
        AutoscalePolicy.parse("4:2")
    with pytest.raises(ValueError, match="min_replicas"):
        AutoscalePolicy(min_replicas=0)
    with pytest.raises(ValueError, match="high_watermark"):
        AutoscalePolicy(high_watermark=0)


def test_autoscale_validation(model_params):
    model, params = model_params
    kvs = build_replica_kvs(model, params, 2, 2)
    with pytest.raises(ValueError, match="must fit"):
        ReplicaSet(kvs, clock=VirtualClock(), autoscale="1:5")
    # round 20: roles + autoscale COMPOSE — the MIN:MAX range is clamped
    # per role pool, so a 1:2 policy over a 1P:1D split is legal (each
    # pool drives 1:1)
    rs = ReplicaSet(kvs, clock=VirtualClock(),
                    roles=["prefill", "decode"], autoscale="1:2")
    assert rs._role_range("prefill") == (1, 1)
    assert rs._role_range("decode") == (1, 1)


# ------------------------------------------------- handoff fault site


def test_fault_grammar_handoff_site():
    faults = FaultInjector.parse("crash:replica=0,handoff=2")
    assert faults[0].site == "handoff" and faults[0].at == 2
    with pytest.raises(ValueError, match="handoff"):
        FaultInjector.parse("crash:replica=0,banana=1")


def test_handoff_crash_requeues_no_leak_no_duplicates(model_params,
                                                      default_oracle):
    """A prefill replica killed between prefill completion and decode
    admission (the handoff site): its request requeues to the surviving
    prefill replica, streams stay bitwise, conservation holds per role,
    and no slot leaks anywhere."""
    model, params = model_params
    reqs = _requests()
    oracle = default_oracle
    kvs = build_replica_kvs(model, params, 3, 2)
    inj = FaultInjector("crash:replica=0,handoff=1", seed=0)
    rs = ReplicaSet(kvs, clock=VirtualClock(),
                    roles=["prefill", "prefill", "decode"],
                    fault_injector=inj)
    summary = rs.run(list(reqs))
    assert summary["serve_fleet"]["failovers"] == 1
    assert summary["serve_fleet"]["faults_injected"]
    assert summary["serve_duplicate_emissions"] == 0
    assert _streams(summary) == oracle
    _assert_conservation(summary)
    per = summary["serve_disagg"]["per_role"]
    assert sum(per[r]["done"] for r in per) == summary["completed"] == 6
    for kv in kvs:
        assert not (kv.active.any() or kv.reserved.any()), kv.active


def test_handoff_with_no_decode_survivor_is_accounted(model_params):
    """Killing the ONLY decode replica: prefill-side work cannot be
    delivered — the window ends with every request accounted (done on a
    survivor is impossible, so they land in unserved), never hung."""
    model, params = model_params
    kvs = build_replica_kvs(model, params, 2, 2)
    inj = FaultInjector("crash:replica=1,iter=1", seed=0)
    rs = ReplicaSet(kvs, clock=VirtualClock(),
                    roles=["prefill", "decode"], fault_injector=inj,
                    retry_limit=1)
    summary = rs.run(_requests())
    _assert_conservation(summary)
    d = summary["serve_disagg"]
    assert d["handoffs_dropped"] >= 0
    assert summary["completed"] + summary["unserved_requests"] == 6


# ------------------------------------------------- journal semantics


def test_journal_transfer_assign_consumes_no_attempt():
    """A handoff is a transfer, not a retry: assign(transfer=True) moves
    ownership without touching the attempt budget or resetting phase."""
    reqs = [Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=2, arrival_s=0.0)]
    j = RequestJournal(reqs)
    j.assign(0, 0, 0.0)
    e = j.entries[0]
    assert e.attempts == 1 and e.phase == "prefill"
    j.set_phase(0, "decode")
    j.assign(0, 1, 1.0, transfer=True)
    assert e.attempts == 1          # no attempt consumed
    assert e.phase == "decode"      # phase preserved across transfer
    j.assign(0, 0, 2.0, retry=True)
    assert e.attempts == 2
    assert e.phase == "prefill"     # a real retry re-prefills
    counts = j.role_counts()
    assert set(counts) == {"prefill", "decode"}


# ------------------------------------------------- flag-off parity pins


def test_flag_off_fleet_summary_keys_unchanged(model_params):
    """Round-17 pin: a default ReplicaSet run carries NONE of the
    round-18 gated keys — flag-off summaries stay key-identical."""
    model, params = model_params
    rs = ReplicaSet(build_replica_kvs(model, params, 2, 2),
                    clock=VirtualClock())
    summary = rs.run(_requests(n=3, max_new=4))
    for key in ("serve_disagg", "autoscale", "serve_replica_seconds",
                "serve_routing", "serve_fleet_prefix_hit_rate",
                "serve_parallel_lanes"):
        assert key not in summary, key


def test_flag_off_batcher_summary_keys_unchanged(model_params):
    """The single batcher without a role carries no handoff keys."""
    model, params = model_params
    summary = ContinuousBatcher(SlotKVCache(model, params, 2),
                                clock=VirtualClock()).run(_requests(n=2))
    for key in ("serve_role", "handoffs_out", "handoffs_in"):
        assert key not in summary, key


def test_handoff_programs_gated_out_of_compiled_set(model_params):
    """compiled_programs() is a pinned exact dict (test_serving.py): the
    handoff program family only appears once a handoff actually built
    its ops — a never-handed-off table reports the round-17 set."""
    model, params = model_params
    kv = SlotKVCache(model, params, 2)
    kv.insert(np.arange(6, dtype=np.int32))
    assert "handoff_block_ops" not in kv.compiled_programs()
    kv.extract_handoff(0)
    assert kv.compiled_programs()["handoff_block_ops"] >= 1


# ------------------------------------------------- analyze gates


def test_round18_diff_gates_and_directions():
    from distributed_tensorflow_tpu.observability.analyze import (
        _DIFF_METRICS)

    directions = dict(_DIFF_METRICS)
    assert directions["serve_fleet_prefix_hit_rate"] == "higher"
    assert directions["serve_replica_seconds"] == "lower"
    assert directions["disagg_vs_homogeneous_itl_p95"] == "lower"


def test_value_direction_round18_pins():
    """_value_direction pins: the disagg bench headline is a latency
    RATIO (< 1 = disagg wins) — lower-is-better — while the rate-valued
    serving headlines stay higher-is-better."""
    from distributed_tensorflow_tpu.observability.analyze import (
        _value_direction)

    assert _value_direction(
        {"metric": "gpt_serve_disagg_itl_p95_ratio",
         "unit": "disagg/homogeneous itl_p95 ratio (< 1 = disagg "
                 "wins)"}) == "lower"
    assert _value_direction(
        {"metric": "gpt_serve_fleet_requests_per_sec_per_chip",
         "unit": "requests/sec/chip"}) == "higher"


def test_round18_keys_flatten_through_serve_section(model_params,
                                                    tmp_path):
    """The gated keys survive serve_section and flatten through
    load_report for `analyze diff` — and a self-diff is clean."""
    import json

    from distributed_tensorflow_tpu.observability import serve_section
    from distributed_tensorflow_tpu.observability.analyze import (
        diff_reports, load_report)

    model, params = model_params
    kvs = build_replica_kvs(model, params, 2, 2,
                            prefix_cache_blocks=8, prefix_block=4)
    rs = ReplicaSet(kvs, clock=VirtualClock(), routing="affinity",
                    roles=["prefill", "decode"])
    sec = serve_section(rs.run(_shared_requests()), 8)
    json.dumps(sec)
    path = tmp_path / "report.json"
    path.write_text(json.dumps({"serve": sec}))
    flat = load_report(path)
    assert "serve_fleet_prefix_hit_rate" in flat
    assert "serve_replica_seconds" in flat
    diff = diff_reports(flat, dict(flat))
    assert diff["regressions"] == []


# ------------------------------------------------- harness + CLI


def _lm_fn(batch_size, type="train", **kw):
    from distributed_tensorflow_tpu.data.loaders import load_lm_dataset

    return load_lm_dataset(seq_len=16, vocab_size=64, n_train=64,
                           n_test=32, split=type)


_HARNESS_BASE = dict(
    engine="fsdp", model="gpt", dataset="lm_synth", dataset_fn=_lm_fn,
    n_devices=8, batch_size=4, log_every=0,
    model_args={"hidden": 32, "layers": 1, "heads": 2, "ffn": 64,
                "max_len": 48},
    serve_requests=8, serve_slots=2, serve_max_new=4,
    serve_prompt_len=4)


@pytest.mark.slow
def test_harness_disagg_e2e_fsdp():
    """--serve-disaggregate 1:1 through the harness: fleet forced on,
    every request hands off and completes, per-role conservation sums to
    the fleet identity, replica-seconds lands in the section.  (slow:
    trains a model; the tier1.yml Disagg smoke drives the same surface
    through the CLI in CI.)"""
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    summary = run(ExperimentConfig(**_HARNESS_BASE,
                                   serve_disaggregate="1:1"))
    sec = summary["serve"]
    assert sec["mode"] == "fleet"
    assert sec["replicas"] == 2
    assert sec["completed"] == 8
    d = sec["serve_disagg"]
    assert d["handoffs_delivered"] == 8
    per = d["per_role"]
    assert per["prefill"]["done"] + per["decode"]["done"] == 8
    assert sec["serve_replica_seconds"] > 0
    assert summary["serve_exit_policy"] == 0


def test_harness_round18_validation_pre_train():
    """Bad round-18 flags fail BEFORE training, like every other serve
    flag — including the disagg-aware fault-spec replica bound."""
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    cases = [
        (dict(serve_disaggregate="2"), "P:D"),
        (dict(serve_disaggregate="0:1"), "at least one"),
        (dict(serve_disaggregate="1:1", serve_draft_config="self"),
         "draft"),
        (dict(serve_disaggregate="1:1", serve_hot_swap=True),
         "hot-swap"),
        (dict(serve_routing="bogus"), "serve-routing"),
        (dict(serve_routing="affinity"), "prefix"),
        (dict(serve_autoscale="2:1"), "max_replicas"),
        (dict(serve_autoscale="1:4", serve_replicas=2), "exceeds"),
        # round 20: autoscale + disaggregate now COMPOSES (per-role
        # pools) — the old rejection is gone; bad k still rejected
        (dict(serve_multi_step=0), "multi-step"),
        (dict(serve_fault_spec="crash:replica=3,iter=1",
              serve_disaggregate="1:2"), "replica 3"),
    ]
    for kw, pattern in cases:
        with pytest.raises(ValueError, match=pattern):
            run(ExperimentConfig(**_HARNESS_BASE, **kw))


def test_parse_disaggregate_grammar():
    from distributed_tensorflow_tpu.utils.harness import (
        parse_disaggregate)

    assert parse_disaggregate("2:3") == (2, 3)
    for bad in ("3", "a:b", "1:", "0:2", "2:0"):
        with pytest.raises(ValueError):
            parse_disaggregate(bad)


def test_cli_round18_flags_parse():
    from distributed_tensorflow_tpu.cli import build_parser

    args = build_parser().parse_args(
        ["--serve", "8", "--serve-disaggregate", "1:2",
         "--serve-routing", "affinity", "--serve-autoscale", "1:3"])
    assert args.serve_disaggregate == "1:2"
    assert args.serve_routing == "affinity"
    assert args.serve_autoscale == "1:3"
    # defaults stay round-17: no disagg, least-loaded, no autoscale
    args = build_parser().parse_args(["--serve", "8"])
    assert args.serve_disaggregate is None
    assert args.serve_routing == "least-loaded"
    assert args.serve_autoscale is None
