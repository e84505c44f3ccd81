"""Raw decode speed (ISSUE 14): greedy-exact speculative decoding +
int8-quantized KV cache.

Speculative decoding (draft-k → verify-1) must be BITWISE invisible in
the token stream: greedy acceptance emits exactly the tokens
non-speculative decode would have, whatever the draft proposes — the
draft only changes how many target iterations it takes.  The int8 table
is tolerance-based instead: greedy-token AGREEMENT with the bf16/f32
oracle on the test workload, plus the memory claim
(``serve_kv_bytes_per_slot``).  Everything here runs on this container —
plain GSPMD jit + host Python, like tests/test_serving.py.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.gpt import GPTLM, generate
from distributed_tensorflow_tpu.observability import SLOMonitor
from distributed_tensorflow_tpu.serving import (
    ContinuousBatcher, Request, SlotKVCache, SlotOverflow, VirtualClock)


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


@pytest.fixture(scope="module")
def draft_params():
    """A DIFFERENT (smaller, independently seeded) draft: proposals
    disagree with the target often, exercising rejection/rollback."""
    model = tiny_gpt(hidden=16, layers=1, ffn=32)
    x = jnp.asarray(np.random.default_rng(1).integers(0, 64, (2, 8)),
                    jnp.int32)
    params = model.init(jax.random.key(7), x, train=False)["params"]
    return model, params


def _prompts(n, seed=0, lo=3, hi=9):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, int(rng.integers(lo, hi))).astype(np.int32)
            for _ in range(n)]


def _oracle(model, params, prompt, n_new):
    return np.asarray(generate(model, params, prompt[None, :], n_new,
                               greedy=True))[0]


def _staggered(prompts, news, arrivals):
    return [Request(rid=i, prompt=p, max_new_tokens=news[i],
                    arrival_s=arrivals[i]) for i, p in enumerate(prompts)]


# ------------------------------------------------------ kv-cache verify units


def test_verify_block_matches_sequential_argmaxes(model_params):
    """The verify program's core contract: feeding the committed pending
    token + the ORACLE's own continuation returns exactly the oracle's
    next tokens at every position — the (slots, k+1) batched step scores
    like k+1 sequential single-token steps, bitwise."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=2)
    p = _prompts(1, seed=3, lo=5, hi=6)[0]
    orc = _oracle(model, params, p, 6)
    slot, first = kv.insert(p)
    assert first == orc[0]
    block = np.zeros((2, 4), np.int32)
    block[slot] = orc[:4]                   # pending + 3 correct "drafts"
    g = kv.verify_block(block)
    np.testing.assert_array_equal(g[slot], orc[1:5])
    # committing all 4 then decoding continues the oracle stream
    kv.commit_block(slot, 4, int(g[slot, 3]))
    assert int(kv.advance()[slot]) == orc[5]


def test_verify_rollback_is_length_bookkeeping_only(model_params):
    """Rejected draft positions are invalidated by LENGTH bookkeeping
    alone — no KV rewrite: after a verify whose tail is junk, committing
    only the accepted prefix leaves the (stale) buffer contents in place,
    and the continuation still matches the oracle because validity is
    length-driven."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=1)
    p = _prompts(1, seed=5, lo=4, hi=5)[0]
    orc = _oracle(model, params, p, 6)
    slot, first = kv.insert(p)
    base = int(kv.lengths[slot])
    # pending + 1 correct draft + 2 JUNK drafts
    block = np.asarray([[orc[0], orc[1], (orc[2] + 1) % 64,
                         (orc[3] + 5) % 64]], np.int32)
    g = kv.verify_block(block)
    assert int(g[0, 0]) == orc[1]           # target argmax after pending
    stale = jax.tree.map(lambda t: np.asarray(t), kv.cache)
    # accept a=1 draft token + the target's own token at the mismatch
    kv.commit_block(slot, 2, int(g[0, 1]))
    assert int(kv.lengths[slot]) == base + 2
    assert int(kv.tokens[slot]) == orc[2]   # g[1] conditioned on orc[:2]
    # rollback touched NO device buffer — byte-identical cache
    for a, b in zip(jax.tree.leaves(stale),
                    jax.tree.leaves(jax.tree.map(
                        lambda t: np.asarray(t), kv.cache))):
        np.testing.assert_array_equal(a, b)
    # the rejected junk at positions base+2.. is invisible: decode
    # continues the oracle stream right over it
    got = [int(kv.advance()[slot]) for _ in range(3)]
    np.testing.assert_array_equal(got, orc[3:6])


def test_rewind_guards(model_params):
    model, params = model_params
    kv = SlotKVCache(model, params, slots=1)
    p = _prompts(1, seed=6)[0]
    slot, _ = kv.insert(p)
    with pytest.raises(ValueError, match="extend"):
        kv.rewind(slot, int(kv.lengths[slot]) + 1, 0)
    kv.rewind(slot, int(kv.lengths[slot]) - 1, 3)
    assert int(kv.tokens[slot]) == 3
    kv.evict(slot)
    with pytest.raises(RuntimeError, match="not active"):
        kv.rewind(slot, 0, 0)
    with pytest.raises(RuntimeError, match="not active"):
        kv.commit_block(slot, 1, 0)


def test_verify_block_guards(model_params):
    model, params = model_params
    kv = SlotKVCache(model, params, slots=2)
    with pytest.raises(ValueError, match="slots, width"):
        kv.verify_block(np.zeros((3, 2), np.int32))
    kv_t = SlotKVCache(model, params, slots=2, greedy=False)
    with pytest.raises(ValueError, match="greedy"):
        kv_t.verify_block(np.zeros((2, 2), np.int32))
    # capacity: a near-full slot rejects an over-wide block
    kv.insert(np.zeros(model.max_len - 2, np.int32))
    with pytest.raises(SlotOverflow, match="verify width"):
        kv.verify_block(np.zeros((2, 3), np.int32))


def test_masked_advance_only_moves_masked_slots(model_params):
    """advance(only=mask) — the draft catch-up step — advances exactly
    the masked slots' lengths/tokens; unmasked active slots keep both,
    and their streams stay oracle-exact afterwards."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=2)
    ps = _prompts(2, seed=8)
    s0, f0 = kv.insert(ps[0])
    s1, f1 = kv.insert(ps[1])
    len1, tok1 = int(kv.lengths[s1]), int(kv.tokens[s1])
    mask = np.zeros(2, np.bool_)
    mask[s0] = True
    toks = kv.advance(only=mask)
    assert int(kv.lengths[s0]) == len(ps[0]) + 1
    assert int(kv.lengths[s1]) == len1           # untouched
    assert int(kv.tokens[s1]) == tok1
    got0 = [f0, int(toks[s0])]
    # both slots keep decoding correctly after the partial step
    full = kv.advance()
    got0.append(int(full[s0]))
    got1 = [f1, int(full[s1])]
    np.testing.assert_array_equal(_oracle(model, params, ps[0], 3), got0)
    np.testing.assert_array_equal(_oracle(model, params, ps[1], 2), got1)


# ------------------------------------------------------- scheduler (tentpole)


def test_spec_decode_bitwise_and_fewer_iterations(model_params):
    """THE acceptance claim: on the staggered-arrival test workload,
    speculative decode (draft = the target itself, the deterministic
    always-accept configuration) emits BITWISE-identical greedy tokens
    to the non-speculative run and completes in STRICTLY fewer decode
    iterations (program-relative count, BASELINE prefill-accounting
    rule: both runs admit identically)."""
    model, params = model_params
    prompts = _prompts(5, seed=4)
    news = [6, 3, 8, 2, 5]
    arrivals = [0.0, 0.0, 1.0, 4.0, 6.0]

    kv0 = SlotKVCache(model, params, slots=2)
    base = ContinuousBatcher(kv0, clock=VirtualClock()).run(
        _staggered(prompts, news, arrivals))
    kv = SlotKVCache(model, params, slots=2)
    spec = ContinuousBatcher(
        kv, clock=VirtualClock(),
        draft_kv=SlotKVCache(model, params, slots=2), draft_k=3).run(
        _staggered(prompts, news, arrivals))

    assert spec["completed"] == base["completed"] == 5
    for i, p in enumerate(prompts):
        orc = _oracle(model, params, p, news[i])
        np.testing.assert_array_equal(
            orc, np.asarray(spec["results"][i].tokens), str(i))
        np.testing.assert_array_equal(
            np.asarray(base["results"][i].tokens),
            np.asarray(spec["results"][i].tokens), str(i))
    assert spec["decode_iterations"] < base["decode_iterations"], \
        (spec["decode_iterations"], base["decode_iterations"])
    assert spec["serve_accept_rate"] == 1.0   # draft == target, greedy
    assert base["serve_accept_rate"] is None
    assert kv.free_slots == [0, 1]


# round 20 fast-lane repair: robustness variant —
# test_spec_decode_bitwise_and_fewer_iterations keeps the fast core pin
@pytest.mark.slow
def test_spec_decode_random_draft_still_bitwise(model_params,
                                                draft_params):
    """Parity holds for ANY draft: a small independently-initialized
    draft proposes mostly-rejected tokens, yet the emitted stream is
    bitwise the oracle's — rejection costs only iterations."""
    model, params = model_params
    dmodel, dparams = draft_params
    prompts = _prompts(5, seed=4)
    news = [6, 3, 8, 2, 5]
    arrivals = [0.0, 0.0, 1.0, 4.0, 6.0]
    res = ContinuousBatcher(
        SlotKVCache(model, params, slots=2), clock=VirtualClock(),
        draft_kv=SlotKVCache(dmodel, dparams, slots=2), draft_k=2).run(
        _staggered(prompts, news, arrivals))
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            _oracle(model, params, p, news[i]),
            np.asarray(res["results"][i].tokens), str(i))
    assert 0.0 <= res["serve_accept_rate"] <= 1.0


def test_accept_accounting_conservation(model_params, draft_params):
    """accepted + rejected == proposed, exactly — per request AND in the
    run ledger; tokens/sec still counts emitted tokens only."""
    model, params = model_params
    dmodel, dparams = draft_params
    prompts = _prompts(4, seed=9)
    res = ContinuousBatcher(
        SlotKVCache(model, params, slots=2), clock=VirtualClock(),
        draft_kv=SlotKVCache(dmodel, dparams, slots=2), draft_k=3).run(
        [Request(rid=i, prompt=p, max_new_tokens=5, arrival_s=0.0)
         for i, p in enumerate(prompts)])
    spec = res["speculative"]
    assert spec["proposed_tokens"] > 0
    assert (spec["accepted_tokens"] + spec["rejected_tokens"]
            == spec["proposed_tokens"])
    assert spec["proposed_tokens"] == sum(
        r.proposed_tokens for r in res["results"])
    assert spec["accepted_tokens"] == sum(
        r.accepted_tokens for r in res["results"])
    assert res["serve_accept_rate"] == pytest.approx(
        spec["accepted_tokens"] / spec["proposed_tokens"])
    # emitted-token accounting unchanged: every request got exactly its
    # budget, and the rate divides emitted tokens by elapsed
    assert res["tokens_generated"] == 4 * 5
    assert res["serve_tokens_per_sec"] == pytest.approx(
        res["tokens_generated"] / res["elapsed_s"])
    assert spec["draft_iterations"] > 0


# round 20 fast-lane repair: four-feature composition variant
@pytest.mark.slow
def test_spec_composes_with_chunk_prefix_cap_slo(model_params):
    """Spec decode under the WHOLE round-10/13 surface at once — chunked
    prefill, prefix pool, bounded admission, SLO monitor: completed
    requests are oracle-exact, shed conservation stays exact, the pool
    reports hits."""
    model, params = model_params
    rng = np.random.default_rng(11)
    shared = rng.integers(0, 64, 8).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(0, 64, 4).astype(np.int32)])
               for _ in range(6)]
    kv = SlotKVCache(model, params, slots=2, prefix_cache_blocks=16,
                     prefix_block=4)
    res = ContinuousBatcher(
        kv, clock=VirtualClock(), prefill_chunk=4,
        slo=SLOMonitor(100.0, 100.0), queue_cap=3,
        draft_kv=SlotKVCache(model, params, slots=2), draft_k=2).run(
        [Request(rid=i, prompt=p, max_new_tokens=4, arrival_s=float(i))
         for i, p in enumerate(prompts)])
    assert (res["admitted"] + res["shed_requests"]
            + res["unserved_requests"]) == res["offered"] == 6
    assert res["serve_prefix_cache_hit_rate"] > 0
    assert res["prefill_chunks"] > 0
    assert res["serve_goodput_under_slo"] is not None
    served = {r.rid: r for r in res["results"]}
    for rid, r in served.items():
        np.testing.assert_array_equal(
            _oracle(model, params, prompts[rid], 4),
            np.asarray(r.tokens), str(rid))
    assert kv.free_slots == [0, 1]


def test_spec_decode_respects_eos(model_params):
    """An EOS landing mid-verify-block truncates the stream exactly
    where non-speculative decode would stop."""
    model, params = model_params
    p = _prompts(1, seed=12)[0]
    orc = _oracle(model, params, p, 8)
    eos = int(orc[3])                       # stop after the 4th token

    def run(draft):
        return ContinuousBatcher(
            SlotKVCache(model, params, slots=1), clock=VirtualClock(),
            draft_kv=draft, draft_k=4).run(
            [Request(rid=0, prompt=p, max_new_tokens=8, arrival_s=0.0,
                     eos_id=eos)])

    spec = run(SlotKVCache(model, params, slots=1))
    base = ContinuousBatcher(
        SlotKVCache(model, params, slots=1), clock=VirtualClock()).run(
        [Request(rid=0, prompt=p, max_new_tokens=8, arrival_s=0.0,
                 eos_id=eos)])
    np.testing.assert_array_equal(np.asarray(base["results"][0].tokens),
                                  np.asarray(spec["results"][0].tokens))
    assert spec["results"][0].tokens[-1] == eos
    assert len(spec["results"][0].tokens) == 4


def test_spec_itl_per_emitted_token(model_params):
    """ITL gaps are attributed per EMITTED token: a verify round's batch
    delivers at one instant — first token of the round carries the gap,
    batch-mates land at 0 — so the gaps still sum to decode wall time
    (the SLO math stays honest)."""
    model, params = model_params
    p = _prompts(1, seed=13)[0]
    res = ContinuousBatcher(
        SlotKVCache(model, params, slots=1), clock=VirtualClock(),
        draft_kv=SlotKVCache(model, params, slots=1), draft_k=3).run(
        [Request(rid=0, prompt=p, max_new_tokens=8, arrival_s=0.0)])
    r = res["results"][0]
    assert len(r.itl_s) == len(r.tokens) - 1
    assert sum(r.itl_s) == pytest.approx(r.decode_s)
    assert 0.0 in r.itl_s                   # some tokens were batch-mates


def test_flags_off_parity_pin(model_params):
    """With spec decode (and every other serving flag) OFF, the compiled
    program set and the serve-section vocabulary are the PR 11 ones:
    verify family empty, draft section None, accept rate None — and the
    tokens are the oracle's (the byte-identity pin for round 14)."""
    model, params = model_params
    prompts = _prompts(3, seed=4)
    kv = SlotKVCache(model, params, slots=2)
    res = ContinuousBatcher(kv, clock=VirtualClock()).run(
        [Request(rid=i, prompt=p, max_new_tokens=4, arrival_s=0.0)
         for i, p in enumerate(prompts)])
    assert kv.compiled_programs()["verify_widths"] == 0
    assert kv.compiled_programs()["prefill_chunk_buckets"] == 0
    assert kv.compiled_programs()["prefix_block_ops"] == 0
    assert res["serve_accept_rate"] is None
    assert res["speculative"] is None
    assert res["serve_kv_dtype"] == "float32"
    assert res["serve_kv_bytes_per_slot"] == kv.kv_bytes_per_slot()
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            _oracle(model, params, p, 4),
            np.asarray(res["results"][i].tokens), str(i))


def test_draft_validation(model_params):
    model, params = model_params
    kv = SlotKVCache(model, params, slots=2)
    with pytest.raises(ValueError, match="draft_k"):
        ContinuousBatcher(kv, draft_kv=SlotKVCache(model, params, 2),
                          draft_k=0)
    with pytest.raises(ValueError, match="match the"):
        ContinuousBatcher(kv, draft_kv=SlotKVCache(model, params, 4))
    with pytest.raises(ValueError, match="greedy"):
        ContinuousBatcher(
            kv, draft_kv=SlotKVCache(model, params, 2, greedy=False))


def test_spec_failure_cleanup_frees_draft_slots(model_params):
    """The mid-run-failure guard extends to the draft table: both tables
    come back empty and serve the next window."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=2)
    draft = SlotKVCache(model, params, slots=2)
    calls = [0]

    def boom(rid, tok):
        calls[0] += 1
        if calls[0] >= 3:
            raise RuntimeError("sink died")

    reqs = [Request(rid=i, prompt=p, max_new_tokens=4, arrival_s=0.0)
            for i, p in enumerate(_prompts(2, seed=7))]
    with pytest.raises(RuntimeError, match="sink died"):
        ContinuousBatcher(kv, clock=VirtualClock(), draft_kv=draft,
                          draft_k=2).run(reqs, on_token=boom)
    assert kv.free_slots == [0, 1]
    assert draft.free_slots == [0, 1]
    res = ContinuousBatcher(kv, clock=VirtualClock(), draft_kv=draft,
                            draft_k=2).run(reqs)
    assert res["completed"] == 2


# ------------------------------------------------------------- int8 KV cache


def test_int8_kv_bytes_and_capacity(model_params):
    """The memory claim: the int8 payload is exactly half of bf16's (a
    quarter of f32's); with the per-written-vector f32 scales included,
    serve_kv_bytes_per_slot lands at (1 + 4/head_dim)/2 of bf16 — and
    DOUBLING the slots at int8 costs no more than (1 + 8/head_dim)× the
    bf16 table, the doubled-capacity check."""
    model, params = model_params
    head_dim = model.hidden // model.heads
    kv8 = SlotKVCache(model, params, slots=4, kv_dtype="int8")
    kv16 = SlotKVCache(model, params, slots=4, kv_dtype=jnp.bfloat16)
    kv32 = SlotKVCache(model, params, slots=4)
    assert kv8.kv_dtype == "int8" and kv8.quantized

    def payload(kv):
        return sum(leaf.size * jnp.dtype(leaf.dtype).itemsize
                   for leaf in jax.tree.leaves(kv.cache)
                   if jnp.dtype(leaf.dtype) == jnp.int8
                   or jnp.issubdtype(leaf.dtype, jnp.floating))

    int8_payload = sum(leaf.size for leaf in jax.tree.leaves(kv8.cache)
                       if jnp.dtype(leaf.dtype) == jnp.int8)
    assert int8_payload * 2 == payload(kv16)
    assert int8_payload * 4 == payload(kv32)
    b8, b16 = kv8.kv_bytes_per_slot(), kv16.kv_bytes_per_slot()
    assert b8 == pytest.approx(b16 * (1 + 4 / head_dim) / 2)
    # doubled slots at int8 vs the bf16 table: within the scale overhead
    kv8x2 = SlotKVCache(model, params, slots=8, kv_dtype="int8")
    assert (kv8x2.kv_bytes_per_slot() * 8
            <= kv16.kv_bytes_per_slot() * 4 * (1 + 8 / head_dim))


def test_int8_kv_matches_oracle_greedy(model_params):
    """The tolerance-based acceptance: int8 storage agrees with the
    full-precision oracle's greedy tokens on the serving test workload,
    through staggered-age slots."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=4, kv_dtype="int8")
    prompts = _prompts(3, seed=11)
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


def test_int8_kv_full_scheduler_workload(model_params):
    """int8 through the batcher on the staggered workload: greedy tokens
    agree with the f32 run, the summary carries dtype + bytes."""
    model, params = model_params
    prompts = _prompts(5, seed=4)
    news = [6, 3, 8, 2, 5]
    arrivals = [0.0, 0.0, 1.0, 4.0, 6.0]

    def run(dtype):
        return ContinuousBatcher(
            SlotKVCache(model, params, slots=2, kv_dtype=dtype),
            clock=VirtualClock()).run(
            _staggered(prompts, news, arrivals))

    res8, res32 = run("int8"), run(None)
    assert res8["serve_kv_dtype"] == "int8"
    assert (res8["serve_kv_bytes_per_slot"]
            < res32["serve_kv_bytes_per_slot"])
    for i in range(len(prompts)):
        np.testing.assert_array_equal(
            np.asarray(res32["results"][i].tokens),
            np.asarray(res8["results"][i].tokens), str(i))


# round 20 fast-lane repair: int8 composition variant —
# test_int8_kv_matches_oracle_greedy keeps the fast int8 pin
@pytest.mark.slow
def test_int8_kv_composes_with_chunk_and_prefix(model_params):
    """Chunked prefill + the prefix pool over an int8 table: pooled
    blocks byte-copy the int8 payload AND its scale leaves (the 3-dim
    block-op path), so a hit reproduces the cold prefill exactly."""
    model, params = model_params
    rng = np.random.default_rng(14)
    shared = rng.integers(0, 64, 8).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(0, 64, 4).astype(np.int32)])
               for _ in range(4)]

    def run(dtype, blocks):
        kv = SlotKVCache(model, params, slots=2, kv_dtype=dtype,
                         prefix_cache_blocks=blocks, prefix_block=4)
        res = ContinuousBatcher(kv, clock=VirtualClock(),
                                prefill_chunk=3).run(
            [Request(rid=i, prompt=p, max_new_tokens=4,
                     arrival_s=float(i)) for i, p in enumerate(prompts)])
        return res

    hot = run("int8", 16)
    cold = run("int8", 0)
    oracle = run(None, 0)
    assert hot["serve_prefix_cache_hit_rate"] > 0
    for i in range(len(prompts)):
        t_hot = np.asarray(hot["results"][i].tokens)
        np.testing.assert_array_equal(
            np.asarray(cold["results"][i].tokens), t_hot, str(i))
        np.testing.assert_array_equal(
            np.asarray(oracle["results"][i].tokens), t_hot, str(i))


def test_int8_kv_on_mesh(model_params, mesh8):
    """The int8 table's payload AND scale leaves shard the slot dim over
    'data' (the scale leaf is 3-dim — kv_slot_sharding generalizes), and
    sharded decode agrees with the oracle."""
    from distributed_tensorflow_tpu.parallel import mesh as meshlib

    model, params = model_params
    kv = SlotKVCache(model, params, slots=8, mesh=mesh8, kv_dtype="int8")
    for leaf in jax.tree.leaves(kv.cache):
        assert leaf.sharding.spec[0] == meshlib.DATA_AXIS
    p = _prompts(1, seed=15)[0]
    slot, first = kv.insert(p)
    got = [first] + [int(kv.advance()[slot]) for _ in range(3)]
    np.testing.assert_array_equal(_oracle(model, params, p, 4), got)


# round 20 fast-lane repair: spec × int8 composition variant
@pytest.mark.slow
def test_spec_decode_over_int8_table(model_params):
    """Both round-14 flags at once: the draft speculates over an int8
    target table — the verify is exact AGAINST THAT TABLE's decode, so
    spec-on tokens equal spec-off tokens on the same int8 table (the
    spec-parity discipline survives quantization)."""
    model, params = model_params
    prompts = _prompts(4, seed=16)

    def run(draft):
        return ContinuousBatcher(
            SlotKVCache(model, params, slots=2, kv_dtype="int8"),
            clock=VirtualClock(), draft_kv=draft, draft_k=2).run(
            [Request(rid=i, prompt=p, max_new_tokens=5, arrival_s=0.0)
             for i, p in enumerate(prompts)])

    spec = run(SlotKVCache(model, params, slots=2))
    base = ContinuousBatcher(
        SlotKVCache(model, params, slots=2, kv_dtype="int8"),
        clock=VirtualClock()).run(
        [Request(rid=i, prompt=p, max_new_tokens=5, arrival_s=0.0)
         for i, p in enumerate(prompts)])
    for i in range(len(prompts)):
        np.testing.assert_array_equal(
            np.asarray(base["results"][i].tokens),
            np.asarray(spec["results"][i].tokens), str(i))
    assert spec["serve_accept_rate"] is not None


# ----------------------------------------------------- observability / gates


def test_analyze_diff_round14_directions():
    """serve_accept_rate gates higher-is-better, serve_kv_bytes_per_slot
    lower — a rate drop and a footprint growth are both regressions."""
    from distributed_tensorflow_tpu.observability.analyze import (
        diff_reports)

    base = {"serve_accept_rate": 0.8, "serve_kv_bytes_per_slot": 1000.0,
            "serve_tokens_per_sec": 50.0}
    worse = {"serve_accept_rate": 0.4, "serve_kv_bytes_per_slot": 2000.0,
             "serve_tokens_per_sec": 20.0}
    d = diff_reports(base, worse, threshold=0.1)
    assert {r["metric"] for r in d["regressions"]} == {
        "serve_accept_rate", "serve_kv_bytes_per_slot",
        "serve_tokens_per_sec"}
    better = diff_reports(worse, base, threshold=0.1)
    assert not better["regressions"]
    assert {r["metric"] for r in better["improvements"]} == {
        "serve_accept_rate", "serve_kv_bytes_per_slot",
        "serve_tokens_per_sec"}


def test_value_direction_round14_pins():
    """_value_direction pins (the `sec_per` substring bug class): the
    tokens/sec family stays higher-better, byte-valued headlines gate
    lower-better."""
    from distributed_tensorflow_tpu.observability.analyze import (
        _value_direction)

    assert _value_direction(
        {"metric": "gpt_serve_tokens_per_sec", "unit": "tokens/sec"}) \
        == "higher"
    assert _value_direction(
        {"metric": "serve_kv_bytes_per_slot", "unit": "bytes/slot"}) \
        == "lower"
    assert _value_direction(
        {"metric": "gpt_lm_decode_bytes_per_token",
         "unit": "bytes/token"}) == "lower"
    # the round-7 rate pins must survive the 'byte' substring addition
    assert _value_direction(
        {"metric": "gpt_serve_requests_per_sec_per_chip",
         "unit": "requests/sec/chip"}) == "higher"


def test_load_report_flattens_round14_keys(tmp_path):
    from distributed_tensorflow_tpu.observability.analyze import (
        diff_reports, load_report)

    summary = {"steps": 2, "run_report": {
        "serve": {"serve_accept_rate": 0.9,
                  "serve_kv_bytes_per_slot": 4096}}}
    p = tmp_path / "summary.json"
    p.write_text(json.dumps(summary))
    flat = load_report(p)
    assert flat["serve_accept_rate"] == 0.9
    assert flat["serve_kv_bytes_per_slot"] == 4096
    worse = dict(flat, serve_accept_rate=0.2)
    d = diff_reports(flat, worse)
    assert [r["metric"] for r in d["regressions"]] == \
        ["serve_accept_rate"]


# ----------------------------------------------- harness (run() in process)


def _lm_fn(batch_size, type="train", **kw):
    from distributed_tensorflow_tpu.data.loaders import load_lm_dataset

    return load_lm_dataset(seq_len=16, vocab_size=64, n_train=64,
                           n_test=32, split=type)


def test_harness_spec_decode_e2e():
    """--serve-draft-config self --serve-draft-k through the harness:
    the serve section carries accept rate 1 (draft == target) and the
    speculative ledger, in summary AND run report."""
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    summary = run(ExperimentConfig(
        engine="fsdp", model="gpt", dataset="lm_synth",
        dataset_fn=_lm_fn, n_devices=8, batch_size=4, log_every=0,
        model_args={"hidden": 32, "layers": 1, "heads": 2, "ffn": 64,
                    "max_len": 32},
        serve_requests=6, serve_slots=8, serve_max_new=6,
        serve_prompt_len=4, serve_draft_config="self", serve_draft_k=2))
    sec = summary["serve"]
    assert sec == summary["run_report"]["serve"]
    assert sec["completed"] == 6
    assert sec["serve_accept_rate"] == 1.0
    spec = sec["speculative"]
    assert spec["draft_k"] == 2
    assert (spec["accepted_tokens"] + spec["rejected_tokens"]
            == spec["proposed_tokens"])


@pytest.mark.slow    # round 20 fast-lane repair: the e2e
# representative is test_harness_spec_decode_e2e
def test_harness_spec_decode_sized_draft_e2e():
    """A size-spec draft ('hidden=16,layers=1'): fresh-initialized from
    the seed, runs the same window — accept rate is whatever it is, but
    the window completes and the ledger conserves."""
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    summary = run(ExperimentConfig(
        engine="fsdp", model="gpt", dataset="lm_synth",
        dataset_fn=_lm_fn, n_devices=8, batch_size=4, log_every=0,
        model_args={"hidden": 32, "layers": 1, "heads": 2, "ffn": 64,
                    "max_len": 32},
        serve_requests=4, serve_slots=8, serve_max_new=4,
        serve_prompt_len=4, serve_draft_config="hidden=16,layers=1",
        serve_draft_k=2))
    sec = summary["serve"]
    assert sec["completed"] == 4
    spec = sec["speculative"]
    assert (spec["accepted_tokens"] + spec["rejected_tokens"]
            == spec["proposed_tokens"])
    assert 0.0 <= sec["serve_accept_rate"] <= 1.0


@pytest.mark.slow    # round 20 fast-lane repair (see above)
def test_harness_int8_kv_e2e():
    """--serve-kv-dtype int8 through the harness: dtype + bytes in the
    serve section, at 2× the slots of the bf16 run (the capacity
    check)."""
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    base = dict(
        engine="fsdp", model="gpt", dataset="lm_synth",
        dataset_fn=_lm_fn, n_devices=8, batch_size=4, log_every=0,
        model_args={"hidden": 32, "layers": 1, "heads": 2, "ffn": 64,
                    "max_len": 32},
        serve_requests=4, serve_max_new=4, serve_prompt_len=4)
    s8 = run(ExperimentConfig(**base, serve_slots=16,
                              serve_kv_dtype="int8"))
    s16 = run(ExperimentConfig(**base, serve_slots=8,
                               serve_kv_dtype="bfloat16"))
    sec8, sec16 = s8["serve"], s16["serve"]
    assert sec8["serve_kv_dtype"] == "int8"
    assert sec16["serve_kv_dtype"] == "bfloat16"
    assert sec8["completed"] == sec16["completed"] == 4
    # int8 at DOUBLE the slots fits in (about) the bf16 table's bytes:
    # payload exactly half, plus the per-vector scale overhead
    head_dim = 32 // 2
    assert (sec8["serve_kv_bytes_per_slot"] * 16
            <= sec16["serve_kv_bytes_per_slot"] * 8 * (1 + 8 / head_dim))


def test_harness_round14_flag_validation():
    """Bad draft/kv-dtype flags fail BEFORE training (the --serve
    contract), with the draft-spec parser's message."""
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, parse_draft_config, run)

    base = dict(engine="fsdp", model="gpt", dataset="lm_synth",
                n_devices=8, serve_requests=2,
                model_args={"hidden": 32, "layers": 1, "heads": 2,
                            "ffn": 64})
    with pytest.raises(ValueError, match="serve-draft-k"):
        run(ExperimentConfig(**base, serve_draft_k=0))
    with pytest.raises(ValueError, match="key=int"):
        run(ExperimentConfig(**base, serve_draft_config="hidden:16"))
    with pytest.raises(ValueError, match="serve-kv-dtype"):
        run(ExperimentConfig(**base, serve_kv_dtype="int4"))
    # parser unit: 'self' → None, sizes parse, junk raises
    assert parse_draft_config("self") is None
    assert parse_draft_config("hidden=16, layers=1") == {
        "hidden": 16, "layers": 1}
    with pytest.raises(ValueError, match="vocab/max_len"):
        parse_draft_config("vocab_size=8")
    with pytest.raises(ValueError, match="int"):
        parse_draft_config("hidden=big")
