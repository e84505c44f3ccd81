"""Paged KV serving (ISSUE 16): the aliased block pool behind
``SlotKVCache(..., kv_layout="paged")`` — dispatch and the flag-off
program-set pin, decode/verify parity against the monolithic oracle
(fused and gather paths, staggered + chunked + prefix + speculative +
int8 composed, mesh-sharded variant), the zero-copy prefix ledger
(pool stores each shared prefix exactly once), copy-on-write isolation,
block-exhaustion admission (``can_admit`` deferral + the scheduler's
``serve_kv_block_deferrals``), honest ``kv_bytes_per_slot``, the
round-16 ``analyze diff`` gates, and the harness surface.
Everything runs on this container — Pallas interpret mode on CPU.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.gpt import GPTLM, generate
from distributed_tensorflow_tpu.serving import (
    BlockPoolExhausted, ContinuousBatcher, PagedSlotKVCache, Request,
    SlotKVCache, VirtualClock, build_replica_kvs)


def tiny_gpt(**kw):
    kw.setdefault("vocab_size", 64)
    kw.setdefault("hidden", 32)
    kw.setdefault("layers", 2)
    kw.setdefault("heads", 2)
    kw.setdefault("ffn", 64)
    kw.setdefault("max_len", 32)
    kw.setdefault("dropout_rate", 0.0)
    return GPTLM(**kw)


@pytest.fixture(scope="module")
def model_params():
    model = tiny_gpt()
    x = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 8)),
                    jnp.int32)
    params = model.init(jax.random.key(0), x, train=False)["params"]
    return model, params


def _prompts(n, seed=0, lo=3, hi=9):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, int(rng.integers(lo, hi))).astype(np.int32)
            for _ in range(n)]


def _oracle(model, params, prompt, n_new):
    return np.asarray(generate(model, params, prompt[None, :], n_new,
                               greedy=True))[0]


def _shared_prefix_prompts(n, seed, shared_len=8, suffix_len=4):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, 64, shared_len).astype(np.int32)
    return [np.concatenate([shared,
                            rng.integers(0, 64, suffix_len)
                            .astype(np.int32)]) for _ in range(n)]


# ------------------------------------------------- dispatch + program pins


def test_kv_layout_dispatch_and_flag_off_identity(model_params):
    """kv_layout='paged' dispatches to the subclass; the default stays
    the EXACT monolithic class with the PR 7 compiled-program family
    (no paged key in its inventory — the flag-off byte-identity pin at
    the program-set level), and the paged knobs are rejected outside
    the paged layout."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=2, kv_layout="paged",
                     paged_block=4)
    assert isinstance(kv, PagedSlotKVCache)
    assert kv.kv_layout == "paged"
    mono = SlotKVCache(model, params, slots=2)
    assert type(mono) is SlotKVCache
    assert mono.kv_layout == "monolithic"
    assert "paged_block_copies" not in mono.compiled_programs()
    with pytest.raises(ValueError, match="only apply"):
        SlotKVCache(model, params, slots=2, paged_block=4)
    with pytest.raises(ValueError, match="kv_layout"):
        SlotKVCache(model, params, slots=2, kv_layout="blocked")
    # paged inventory: admission ALWAYS chunks (no slice-out monolithic
    # prefill over a shared pool), prefix hits are pointer writes (no
    # block-op programs, ever)
    kv.insert(np.arange(5, dtype=np.int32))
    kv.advance()
    progs = kv.compiled_programs()
    assert progs["prefill_buckets"] == 0
    assert progs["prefix_block_ops"] == 0
    assert progs["paged_block_copies"] == 0
    assert progs["decode_steps"] == 1


def test_paged_constructor_validation(model_params):
    model, params = model_params
    with pytest.raises(ValueError, match="divide"):
        SlotKVCache(model, params, slots=1, kv_layout="paged",
                    paged_block=5)                      # 32 % 5
    with pytest.raises(ValueError, match="equal prefix_block"):
        SlotKVCache(model, params, slots=1, kv_layout="paged",
                    paged_block=8, prefix_cache_blocks=4, prefix_block=4)
    with pytest.raises(ValueError, match="one full slot"):
        SlotKVCache(model, params, slots=1, kv_layout="paged",
                    paged_block=4, paged_blocks=3)      # < max_blocks


# ------------------------------------------------------------ decode parity


def test_paged_decode_matches_oracle_staggered(model_params):
    """Slots of different ages over ONE shared block pool, advanced by
    one fused (Pallas) step: token-for-token the sequential sampler —
    the paged twin of the monolithic staggered-age parity test."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=4, kv_layout="paged",
                     paged_block=4)
    prompts = _prompts(3, seed=2)
    firsts = {}

    def collect(toks):
        for _, (slot, got) in firsts.items():
            got.append(int(toks[slot]))

    for i, p in enumerate(prompts):
        slot, first = kv.insert(p)
        firsts[i] = (slot, [first])
        collect(kv.advance())
    for _ in range(3):
        collect(kv.advance())
    for i, p in enumerate(prompts):
        n = len(firsts[i][1])
        np.testing.assert_array_equal(_oracle(model, params, p, n),
                                      np.asarray(firsts[i][1]), str(i))


# round 20 fast-lane repair: internal-equivalence variant (fused is
# the production path and is oracle-pinned fast)
@pytest.mark.slow
def test_paged_gather_path_matches_fused(model_params):
    """paged_fused=False keeps decode on the gather+dense path (the
    bitwise-monolithic oracle in paged clothes): same greedy stream as
    the fused Pallas kernel on the same workload."""
    model, params = model_params

    def run(fused):
        kv = SlotKVCache(model, params, slots=2, kv_layout="paged",
                         paged_block=4, paged_fused=fused)
        p = _prompts(1, seed=7, lo=6, hi=7)[0]
        slot, first = kv.insert(p)
        return [first] + [int(kv.advance()[slot]) for _ in range(5)]

    fused, gather = run(True), run(False)
    assert fused == gather
    p = _prompts(1, seed=7, lo=6, hi=7)[0]
    np.testing.assert_array_equal(_oracle(model, params, p, 6), fused)


# round 20 fast-lane repair: spec-verify × paged composition variant
@pytest.mark.slow
def test_paged_verify_block_parity(model_params):
    """The speculative (slots, k+1) verify over the block pool: feeding
    the committed pending token + the oracle's own continuation returns
    exactly the oracle's next argmaxes, and committed drafts decode on
    correctly — the fused block-query kernel behind verify_block."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=2, kv_layout="paged",
                     paged_block=4)
    p = _prompts(1, seed=3, lo=5, hi=6)[0]
    orc = _oracle(model, params, p, 6)
    slot, first = kv.insert(p)
    assert first == orc[0]
    block = np.zeros((2, 4), np.int32)
    block[slot] = orc[:4]
    g = kv.verify_block(block)
    np.testing.assert_array_equal(g[slot], orc[1:5])
    kv.commit_block(slot, 4, int(g[slot, 3]))
    assert int(kv.advance()[slot]) == orc[5]


# round 20 fast-lane repair: int8 × paged composition variant
@pytest.mark.slow
def test_paged_int8_decode_matches_monolithic_int8(model_params):
    """int8 pools with in-kernel dequant: the paged fused stream equals
    the monolithic int8 stream (both quantize identically on write; the
    kernel dequantizes what the gather path dequantizes)."""
    model, params = model_params
    p = _prompts(1, seed=8, lo=7, hi=8)[0]

    def run(**kw):
        kv = SlotKVCache(model, params, slots=2, kv_dtype="int8", **kw)
        slot, first = kv.insert(p)
        return [first] + [int(kv.advance()[slot]) for _ in range(5)]

    np.testing.assert_array_equal(
        run(), run(kv_layout="paged", paged_block=4))


def test_paged_on_mesh(model_params, mesh8):
    """The paged layout under GSPMD: pool leaves REPLICATE (any slot
    may touch any block), slot vectors shard over 'data', and the
    sharded fused decode still matches the sequential oracle."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=8, mesh=mesh8,
                     kv_layout="paged", paged_block=4)
    for leaf in jax.tree.leaves(kv.cache):
        assert leaf.sharding.is_fully_replicated
    out = {}
    for p in _prompts(3, seed=9):
        slot, first = kv.insert(p)
        out[slot] = (p, [first])
    for _ in range(4):
        toks = kv.advance()
        for slot, (_, got) in out.items():
            got.append(int(toks[slot]))
    for slot, (p, got) in out.items():
        np.testing.assert_array_equal(_oracle(model, params, p, 5), got)


# -------------------------------------------- zero-copy prefix sharing + CoW


def test_zero_copy_prefix_counters_and_single_storage(model_params):
    """THE zero-copy pin: admissions 2 and 3 of a shared 8-token prefix
    alias the SAME two physical blocks by pointer — counters exact, the
    pool stores the prefix once (blocks_in_use arithmetic), block
    tables agree on the shared ids, and refcounts account every sharer
    plus the pool pin.  Greedy tokens stay oracle-exact throughout."""
    model, params = model_params
    prompts = _shared_prefix_prompts(3, seed=11)     # 8 shared + 4 own
    kv = SlotKVCache(model, params, slots=3, kv_layout="paged",
                     prefix_cache_blocks=8, prefix_block=4)
    out = {}
    for i, p in enumerate(prompts):
        slot, first = kv.insert(p)
        out[i] = (slot, p, [first])
    for _ in range(3):
        toks = kv.advance()
        for i, (slot, _, got) in out.items():
            got.append(int(toks[slot]))
    for i, (slot, p, got) in out.items():
        np.testing.assert_array_equal(_oracle(model, params, p, 4),
                                      got, str(i))
    stats = kv.paged_stats()
    # admissions 2+3 each matched the 2 shared blocks (8 tokens)
    assert stats["zero_copy_hits"] == 2
    assert stats["zero_copy_blocks"] == 4
    assert stats["zero_copy_tokens"] == 16
    # reuse boundary aligned mid-prompt: nothing wrote a shared block
    assert stats["cow_copies"] == 0
    # stored ONCE: 2 shared + 3 private suffix + 3 private decode blocks
    # (naive per-slot storage would be 12)
    assert stats["blocks_in_use"] == 8
    bt = kv.block_tables_np
    slots_live = [out[i][0] for i in range(3)]
    shared_ids = bt[slots_live[0], :2]
    for s in slots_live[1:]:
        np.testing.assert_array_equal(bt[s, :2], shared_ids)
    # each shared block: 3 slot references + the pool's pin
    for bid in shared_ids:
        assert kv._block_refs[int(bid)] == 4
    # the suffix blocks are private
    assert len({int(bt[s, 2]) for s in slots_live}) == 3


def test_cow_isolation_on_fully_aligned_hit(model_params):
    """Copy-on-write: a block-aligned prefix hit recomputes its final
    token INTO a shared block — the writer gets a private copy (one
    jitted block copy, counted), every other sharer and the pool keep
    the original, and BOTH streams stay oracle-exact (the isolation
    claim)."""
    model, params = model_params
    p = _prompts(1, seed=12, lo=8, hi=9)[0]          # exactly 2 blocks
    kv = SlotKVCache(model, params, slots=2, kv_layout="paged",
                     prefix_cache_blocks=8, prefix_block=4)
    slot_a, first_a = kv.insert(p)
    got_a = [first_a, int(kv.advance()[slot_a])]
    assert kv.paged_stats()["cow_copies"] == 0
    slot_b, first_b = kv.insert(p)                   # fully-aligned hit
    st = kv.paged_stats()
    assert st["zero_copy_hits"] == 1 and st["zero_copy_blocks"] == 2
    assert st["zero_copy_tokens"] == 7               # reuse capped at lp-1
    assert st["cow_copies"] == 1
    bt = kv.block_tables_np
    assert bt[slot_a, 0] == bt[slot_b, 0]            # still shared
    assert bt[slot_a, 1] != bt[slot_b, 1]            # B owns its copy
    got_b = [first_b]
    for _ in range(3):
        toks = kv.advance()
        got_a.append(int(toks[slot_a]))
        got_b.append(int(toks[slot_b]))
    orc = _oracle(model, params, p, 5)
    np.testing.assert_array_equal(orc, got_a)        # A uncorrupted
    np.testing.assert_array_equal(orc[:4], got_b)    # B's copy correct


def test_prefix_pool_pins_survive_evict_and_reset_releases(model_params):
    """Pool = pin: evicting the admitting slot releases ITS references
    but the pooled blocks stay resident (that is the cache); a warm
    re-admission still zero-copies; reset_prefix_cache drains the pins
    back to the free list."""
    model, params = model_params
    p = _shared_prefix_prompts(1, seed=13)[0]        # 12 tokens, 3 blocks
    kv = SlotKVCache(model, params, slots=1, kv_layout="paged",
                     prefix_cache_blocks=8, prefix_block=4)
    slot, _ = kv.insert(p)
    assert kv.blocks_in_use == 3
    kv.evict(slot)
    assert kv.blocks_in_use == 3                     # the pool's pins
    hits_before = kv.paged_stats()["zero_copy_hits"]
    slot, first = kv.insert(p)
    assert kv.paged_stats()["zero_copy_hits"] == hits_before + 1
    np.testing.assert_array_equal(_oracle(model, params, p, 1), [first])
    kv.evict(slot)
    kv.reset_prefix_cache()
    assert kv.blocks_in_use == 0
    assert kv.paged_stats()["zero_copy_hits"] == 0


# --------------------------------------------- capacity + exhaustion gates


def test_block_pool_exhausted_and_can_admit(model_params):
    """A pool sized below slots × max_blocks: can_admit accounts live
    slots' committed worst-case budgets (not just allocated blocks),
    and actually running dry raises BlockPoolExhausted instead of
    corrupting a shared block."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=2, kv_layout="paged",
                     paged_block=4, paged_blocks=8)
    assert kv.can_admit(16, 16)                      # 8 blocks, 8 free
    slot, _ = kv.insert(np.arange(16, dtype=np.int32) % 64)
    kv.note_admission(slot, 32)                      # worst case: 8 blocks
    # 4 free, but the live slot may still claim 4 more → nothing fits
    assert not kv.can_admit(4, 4)
    kv.evict(slot)
    assert kv.can_admit(16, 16)
    # two 4-block prompts fill the pool; the next decode write must fail
    kv.insert(np.arange(16, dtype=np.int32) % 64)
    kv.insert(np.arange(16, dtype=np.int32)[::-1].copy() % 64)
    assert kv.blocks_in_use == 8
    with pytest.raises(BlockPoolExhausted, match="exhausted"):
        kv.advance()


def test_scheduler_defers_admission_on_block_pressure(model_params):
    """The scheduler's block-exhaustion gate: a pool that fits one
    request at a time serializes admissions (serve_kv_block_deferrals
    counts the pushbacks) yet completes every request oracle-exact —
    and the summary carries the round-16 paged vocabulary."""
    model, params = model_params
    prompts = [np.asarray(np.arange(16) * (i + 1) % 64, np.int32)
               for i in range(3)]
    kv = SlotKVCache(model, params, slots=2, kv_layout="paged",
                     paged_block=4, paged_blocks=8)
    res = ContinuousBatcher(kv, clock=VirtualClock()).run(
        [Request(rid=i, prompt=p, max_new_tokens=4, arrival_s=0.0)
         for i, p in enumerate(prompts)])
    assert res["completed"] == 3
    assert res["serve_kv_block_deferrals"] > 0
    assert res["serve_kv_layout"] == "paged"
    assert res["serve_kv_blocks_in_use"] == 0        # all evicted at end
    assert res["serve_kv_block_utilization"] == 0.0
    assert res["paged"]["block_deferrals"] == res["serve_kv_block_deferrals"]
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            _oracle(model, params, p, 4),
            np.asarray(res["results"][i].tokens), str(i))
    # monolithic summaries carry the same keys as None/monolithic
    res_m = ContinuousBatcher(
        SlotKVCache(model, params, slots=2),
        clock=VirtualClock()).run(
        [Request(rid=0, prompt=prompts[0], max_new_tokens=2,
                 arrival_s=0.0)])
    assert res_m["serve_kv_layout"] == "monolithic"
    assert res_m["serve_kv_blocks_in_use"] is None
    assert res_m["serve_prefix_zero_copy_hit_rate"] is None
    assert res_m["serve_kv_block_deferrals"] == 0


def test_paged_kv_bytes_per_slot_honest(model_params):
    """Paged capacity reports bytes BACKING live sequences (allocated
    blocks + tables, amortized over live slots) — below the monolithic
    slots × max_len claim for short sequences, growing with allocation,
    shrinking back on evict."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=2, kv_layout="paged",
                     paged_block=4)
    mono = SlotKVCache(model, params, slots=2)
    assert kv.blocks_in_use == 0
    assert kv.kv_bytes_per_slot() == kv.block_tables_np.nbytes
    slot, _ = kv.insert(np.arange(6, dtype=np.int32))
    assert kv.blocks_in_use == 2
    short_bytes = kv.kv_bytes_per_slot()
    assert short_bytes < mono.kv_bytes_per_slot()
    for _ in range(3):
        kv.advance()                                 # crosses into block 2
    assert kv.blocks_in_use == 3
    assert kv.kv_bytes_per_slot() > short_bytes
    kv.evict(slot)
    assert kv.blocks_in_use == 0
    # freed blocks are immediately reusable
    slot, _ = kv.insert(np.arange(5, dtype=np.int32))
    assert kv.blocks_in_use == 2


# ------------------------------------------------------- composed workloads


@pytest.mark.slow    # round 20 fast-lane repair: the fast paged
# representative is test_harness_paged_e2e + the parity suites
def test_paged_composed_chunk_prefix_spec_int8(model_params):
    """THE parity acceptance: staggered arrivals + chunked prefill +
    prefix pool + speculative decode + int8, paged vs monolithic on the
    same seeded trace — identical greedy streams, and the paged run's
    summary shows zero-copy sharing actually happened."""
    model, params = model_params
    prompts = _shared_prefix_prompts(6, seed=14)
    arrivals = [0.0, 0.0, 1.0, 2.0, 3.0, 4.0]

    def run(**layout):
        kv = SlotKVCache(model, params, slots=2, kv_dtype="int8",
                         prefix_cache_blocks=16, prefix_block=4,
                         **layout)
        return ContinuousBatcher(
            kv, clock=VirtualClock(), prefill_chunk=3,
            draft_kv=SlotKVCache(model, params, slots=2),
            draft_k=2).run(
            [Request(rid=i, prompt=p, max_new_tokens=4,
                     arrival_s=arrivals[i])
             for i, p in enumerate(prompts)])

    paged = run(kv_layout="paged")
    mono = run()
    assert paged["completed"] == mono["completed"] == 6
    for i in range(6):
        np.testing.assert_array_equal(
            np.asarray(mono["results"][i].tokens),
            np.asarray(paged["results"][i].tokens), str(i))
    assert paged["paged"]["zero_copy_hits"] > 0
    assert paged["serve_prefix_zero_copy_hit_rate"] > 0
    assert paged["serve_prefix_cache_hit_rate"] > 0
    assert mono["serve_kv_blocks_in_use"] is None


# round 20 fast-lane repair: mesh composition variant —
# test_paged_on_mesh keeps the fast mesh representative
@pytest.mark.slow
def test_paged_composed_on_mesh(model_params, mesh8):
    """The composed workload's mesh-sharded variant: chunked + prefix +
    int8 over a slot-sharded paged table — streams match the monolithic
    mesh run on the same trace."""
    model, params = model_params
    prompts = _shared_prefix_prompts(4, seed=15)

    def run(**layout):
        kv = SlotKVCache(model, params, slots=8, mesh=mesh8,
                         kv_dtype="int8", prefix_cache_blocks=16,
                         prefix_block=4, **layout)
        return ContinuousBatcher(kv, clock=VirtualClock(),
                                 prefill_chunk=4).run(
            [Request(rid=i, prompt=p, max_new_tokens=3,
                     arrival_s=float(i)) for i, p in enumerate(prompts)])

    paged = run(kv_layout="paged")
    mono = run()
    for i in range(4):
        np.testing.assert_array_equal(
            np.asarray(mono["results"][i].tokens),
            np.asarray(paged["results"][i].tokens), str(i))
    assert paged["paged"]["zero_copy_hits"] > 0


def test_fleet_build_replica_kvs_forwards_layout(model_params):
    """The fleet constructs paged replicas through the same kv_kwargs
    pass-through as every other layout knob."""
    model, params = model_params
    kvs = build_replica_kvs(model, params, 2, 2, kv_layout="paged",
                            paged_block=4)
    assert all(isinstance(kv, PagedSlotKVCache) for kv in kvs)
    assert all(kv.num_blocks == kvs[0].num_blocks for kv in kvs)


# ----------------------------------------------------- observability / gates


def test_analyze_diff_round16_directions():
    """serve_kv_blocks_in_use gates lower-is-better (footprint), the
    zero-copy hit rate higher — more blocks or fewer pointer-hits at
    equal workload are regressions."""
    from distributed_tensorflow_tpu.observability.analyze import (
        diff_reports)

    base = {"serve_kv_blocks_in_use": 8,
            "serve_prefix_zero_copy_hit_rate": 0.8}
    worse = {"serve_kv_blocks_in_use": 16,
             "serve_prefix_zero_copy_hit_rate": 0.2}
    d = diff_reports(base, worse, threshold=0.1)
    assert {r["metric"] for r in d["regressions"]} == {
        "serve_kv_blocks_in_use", "serve_prefix_zero_copy_hit_rate"}
    better = diff_reports(worse, base, threshold=0.1)
    assert not better["regressions"]
    assert {r["metric"] for r in better["improvements"]} == {
        "serve_kv_blocks_in_use", "serve_prefix_zero_copy_hit_rate"}


def test_value_direction_round16_pins():
    """_value_direction pins (the `byte`/`sec_per` substring bug
    class): block/byte-valued footprint headlines gate lower, every
    rate — including the zero-copy hit rate and the per-chip serving
    rate whose name CONTAINS 'sec_per' — stays higher."""
    from distributed_tensorflow_tpu.observability.analyze import (
        _value_direction)

    assert _value_direction(
        {"metric": "serve_kv_block_bytes", "unit": "bytes/block"}) \
        == "lower"
    assert _value_direction(
        {"metric": "serve_kv_bytes_per_slot", "unit": "bytes/slot"}) \
        == "lower"
    assert _value_direction(
        {"metric": "serve_prefix_zero_copy_hit_rate",
         "unit": "fraction"}) == "higher"
    assert _value_direction(
        {"metric": "gpt_serve_requests_per_sec_per_chip",
         "unit": "requests/sec/chip"}) == "higher"


# ----------------------------------------------- harness (run() in process)


def _lm_fn(batch_size, type="train", **kw):
    from distributed_tensorflow_tpu.data.loaders import load_lm_dataset

    return load_lm_dataset(seq_len=16, vocab_size=64, n_train=64,
                           n_test=32, split=type)


def test_harness_paged_e2e():
    """--serve-kv-layout paged through the harness, shared synthetic
    prefix + prefix pool on: the serve section carries the round-16
    keys, zero-copy sharing fires, and the run report mirrors it."""
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    summary = run(ExperimentConfig(
        engine="fsdp", model="gpt", dataset="lm_synth",
        dataset_fn=_lm_fn, n_devices=8, batch_size=4, log_every=0,
        model_args={"hidden": 32, "layers": 1, "heads": 2, "ffn": 64,
                    "max_len": 32},
        serve_requests=6, serve_slots=8, serve_max_new=4,
        serve_prompt_len=4, serve_shared_prefix=8,
        serve_prefix_cache=8, serve_prefix_block=4,
        serve_kv_layout="paged"))
    sec = summary["serve"]
    assert sec == summary["run_report"]["serve"]
    assert sec["completed"] == 6
    assert sec["serve_kv_layout"] == "paged"
    assert sec["serve_kv_blocks_in_use"] is not None
    assert sec["serve_kv_block_utilization"] is not None
    assert sec["paged"]["zero_copy_hits"] > 0
    assert sec["serve_prefix_zero_copy_hit_rate"] > 0
    assert sec["serve_kv_block_deferrals"] == 0      # default pool fits


def test_harness_round16_flag_validation():
    """Bad paged flags fail BEFORE training (the --serve contract)."""
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    base = dict(engine="fsdp", model="gpt", dataset="lm_synth",
                n_devices=8, serve_requests=2,
                model_args={"hidden": 32, "layers": 1, "heads": 2,
                            "ffn": 64, "max_len": 32})
    with pytest.raises(ValueError, match="serve-kv-layout"):
        run(ExperimentConfig(**base, serve_kv_layout="blocked"))
    with pytest.raises(ValueError, match="kv-layout paged"):
        run(ExperimentConfig(**base, serve_paged_block=4))
    with pytest.raises(ValueError, match="divide"):
        run(ExperimentConfig(**base, serve_kv_layout="paged",
                             serve_paged_block=5))
    with pytest.raises(ValueError, match="equal"):
        run(ExperimentConfig(**base, serve_kv_layout="paged",
                             serve_prefix_cache=8, serve_paged_block=8,
                             serve_prefix_block=4))
