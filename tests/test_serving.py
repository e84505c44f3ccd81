"""Continuous-batching serving engine (ISSUE 7): slot KV cache semantics,
scheduler equivalence against the sequential ``generate`` oracle, the
continuous-vs-static decode-iteration claim, the serve observability
vocabulary (`analyze diff` directions, run-report section), and the harness
surface.  Everything here runs on this container — the slot cache and the
scheduler are plain GSPMD jit + host Python, no shard_map anywhere.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.gpt import GPTLM, generate
from distributed_tensorflow_tpu.serving import (
    ContinuousBatcher, Request, RequestQueue, SlotKVCache, SlotOverflow,
    VirtualClock)


def tiny_gpt(**kw):
    kw.setdefault("vocab_size", 64)
    kw.setdefault("hidden", 32)
    kw.setdefault("layers", 2)
    kw.setdefault("heads", 2)
    kw.setdefault("ffn", 64)
    kw.setdefault("max_len", 32)
    kw.setdefault("dropout_rate", 0.0)
    return kw.pop("cls", GPTLM)(**kw)


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


# ----------------------------------------------------------- slot KV cache


def test_slot_insert_evict_advance_bookkeeping(model_params):
    """The slot table's host contract: insert claims a named or first-free
    slot and sets length to the prompt length, advance moves ONLY active
    slots, evict frees the slot for reuse."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=3)
    assert kv.free_slots == [0, 1, 2]

    p = _prompts(3, seed=1)
    slot0, first0 = kv.insert(p[0], slot=1)
    assert slot0 == 1 and 0 <= first0 < 64
    assert kv.free_slots == [0, 2]
    assert kv.lengths[1] == len(p[0]) and kv.active[1]

    slot1, _ = kv.insert(p[1])          # first free slot
    assert slot1 == 0

    lengths_before = kv.lengths.copy()
    kv.advance()
    # active slots advanced by one, the free slot did not
    assert kv.lengths[0] == lengths_before[0] + 1
    assert kv.lengths[1] == lengths_before[1] + 1
    assert kv.lengths[2] == 0

    with pytest.raises(RuntimeError, match="active"):
        kv.insert(p[2], slot=1)
    kv.evict(1)
    assert 1 in kv.free_slots and kv.lengths[1] == 0
    with pytest.raises(RuntimeError, match="not active"):
        kv.evict(1)
    # freed slot is immediately reusable
    slot2, _ = kv.insert(p[2], slot=1)
    assert slot2 == 1 and kv.active[1]

    kv.insert(p[0], slot=2)
    with pytest.raises(RuntimeError, match="free slot"):
        kv.insert(p[0])


def test_slot_decode_matches_generate_per_slot(model_params):
    """Slots of DIFFERENT ages advanced by one shared step reproduce the
    sequential sampler token-for-token: the per-slot positions/validity
    machinery is what makes one compiled step serve all of them."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=4)
    prompts = _prompts(3, seed=2)
    firsts = {}

    def collect(toks):
        for _, (slot, got) in firsts.items():
            got.append(int(toks[slot]))

    for i, p in enumerate(prompts):
        # staggered ages: insert, then advance the table between inserts
        slot, first = kv.insert(p)
        firsts[i] = (slot, [first])
        collect(kv.advance())
    for _ in range(3):
        collect(kv.advance())
    for i, p in enumerate(prompts):
        n = len(firsts[i][1])
        np.testing.assert_array_equal(_oracle(model, params, p, n),
                                      np.asarray(firsts[i][1]), str(i))


def test_insert_never_recompiles_decode(model_params):
    """The recompile-freedom invariant: admissions compile one prefill per
    padded-length bucket and the decode step exactly once — and with
    chunking and the prefix pool OFF, the chunk/block program families are
    EMPTY: the compiled set is exactly the PR 7 one (the acceptance pin
    for `--serve-prefill-chunk 0` + cache off)."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=2, prefill_bucket=4)
    kv.insert(np.arange(3, dtype=np.int32))         # bucket 4
    kv.advance()
    kv.evict(0)
    kv.insert(np.arange(4, dtype=np.int32) % 64)    # bucket 4 (cached)
    kv.insert(np.arange(7, dtype=np.int32) % 64)    # bucket 8
    kv.advance()
    # round 14 adds the speculative-verify family to the pinned set:
    # with spec decode (and chunking and the pool) off it is EMPTY — the
    # compiled program set is exactly the PR 7 one
    # round 20 adds the fused multi-step family: with --serve-multi-step
    # off it is EMPTY — the compiled program set is exactly the PR 7 one
    assert kv.compiled_programs() == {"decode_steps": 1,
                                      "prefill_buckets": 2,
                                      "prefill_chunk_buckets": 0,
                                      "prefix_block_ops": 0,
                                      "verify_widths": 0,
                                      "decode_multi_widths": 0}


def test_chunked_prefill_programs_bucketed(model_params):
    """Chunk programs compile once per power-of-two CHUNK bucket — a
    budget-4 admission of any prompt length reuses {4, 2, 1} buckets and
    never touches the monolithic prefill family or the decode step."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=2)
    slot, _ = kv.begin_insert(np.arange(11, dtype=np.int32) % 64)
    seen = []
    while True:
        first = kv.prefill_chunk(slot, 4)
        seen.append(first)
        if first is not None:
            break
    assert seen[-1] is not None and all(s is None for s in seen[:-1])
    assert len(seen) == 3                           # 4 + 4 + 3 tokens
    kv.advance()
    # second admission at the same budget: no new programs (full chunks
    # pad to bucket 4, the 3-token tails bucket to 4 as well)
    slot2, _ = kv.begin_insert(np.arange(7, dtype=np.int32) % 64)
    while kv.prefill_chunk(slot2, 4) is None:
        pass
    progs = kv.compiled_programs()
    assert progs["decode_steps"] == 1
    assert progs["prefill_buckets"] == 0
    assert progs["prefill_chunk_buckets"] == 1
    assert progs["prefix_block_ops"] == 0
    # a 1-token tail (prompt 5 = 4 + 1) adds exactly the bucket-1 program
    kv.advance()
    kv.evict(slot)
    slot3, _ = kv.begin_insert(np.arange(5, dtype=np.int32) % 64)
    while kv.prefill_chunk(slot3, 4) is None:
        pass
    assert kv.compiled_programs()["prefill_chunk_buckets"] == 2


# ------------------------------------------------ block prefill (insert)

# (max_len, prompt length, its bucket at the default floor of 8); a
# max_len of 24 caps the 32 bucket, so that lpad == max_len
BLOCK_CASES = {"bucket_edge": (32, 8, 8), "one_under": (32, 7, 8),
               "one_token": (32, 1, 8), "capped_bucket": (24, 20, 24)}
block_cases = pytest.mark.parametrize("max_len, lp, lpad",
                                      BLOCK_CASES.values(), ids=BLOCK_CASES)


def _block_case(max_len, lp):
    """A model of its own ``max_len``, its params, a prompt of ``lp``."""
    model = tiny_gpt(max_len=max_len)
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    prompt = np.random.default_rng(lp).integers(0, 64, lp).astype(np.int32)
    return model, params, prompt


@block_cases
def test_block_prefill_serves_the_oracles_tokens(max_len, lp, lpad):
    """``insert`` is one call over the padded prompt: its first token and
    the greedy continuation the decode step reads off the rows it wrote
    are the sequential sampler's, at a bucket's edge, one under it, for a
    one-token prompt and in the bucket that ``max_len`` caps."""
    model, params, prompt = _block_case(max_len, lp)
    kv = SlotKVCache(model, params, slots=2)
    slot, first = kv.insert(prompt, slot=1)
    assert kv.prefill_tokens_padded == lpad and kv.prefill_form == "batched"
    toks = [first] + [int(kv.advance()[slot]) for _ in range(3)]
    np.testing.assert_array_equal(_oracle(model, params, prompt, 4), toks)


@block_cases
def test_block_prefill_writes_the_rows_the_scan_writes(max_len, lp, lpad):
    """Rows ``[0, lp)`` of every table leaf agree with what the scan of
    the one-token step (the chunk program) writes, to float32 rounding;
    rows at or past the bucket, and the other slots, are untouched."""
    model, params, prompt = _block_case(max_len, lp)
    tables = {}
    for form in ("block", "scan"):
        kv = SlotKVCache(model, params, slots=2)
        kv.cache = jax.tree.map(lambda t: jnp.full_like(t, 7.0), kv.cache)
        if form == "block":
            _, first = kv.insert(prompt, slot=1)
        else:
            kv.begin_insert(prompt, slot=1)
            first = kv.prefill_chunk(1)
        tables[form] = (first, jax.tree.leaves(kv.cache))
    assert tables["block"][0] == tables["scan"][0]
    for block, scan in zip(tables["block"][1], tables["scan"][1]):
        block, scan = np.asarray(block), np.asarray(scan)
        np.testing.assert_allclose(block[1, :lp], scan[1, :lp],
                                   atol=1e-5, rtol=1e-5)
        assert (block[1, :lp] != 7.0).any()
        assert (block[1, lpad:] == 7.0).all() and (block[0] == 7.0).all()


def test_block_prefill_into_a_slot_that_held_a_longer_sequence(model_params):
    """Stale rows past the new prompt stay invisible: the block writes
    ``[0, lpad)`` only, and validity is length-driven."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=2)
    long, short = _prompts(1, seed=5, lo=20, hi=21)[0], \
        _prompts(1, seed=6, lo=5, hi=6)[0]
    slot, _ = kv.insert(long, slot=0)
    for _ in range(3):
        kv.advance()
    kv.evict(slot)
    slot, first = kv.insert(short, slot=0)
    toks = [first] + [int(kv.advance()[slot]) for _ in range(7)]
    np.testing.assert_array_equal(_oracle(model, params, short, 8), toks)


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["float", "int8"])
def test_block_prefill_over_a_model_axis(kv_dtype):
    """A tensor-parallel served model (Megatron-annotated params on a
    data x model mesh, the table's kv heads over ``model``): the block
    branch shares its projections with every other mode, so the
    annotations hold, and the oracle's tokens are served."""
    import flax.linen as nn
    from jax.sharding import NamedSharding
    from distributed_tensorflow_tpu.parallel import mesh as meshlib

    mesh = meshlib.create_mesh(8, axis_names=("data", "model"), shape=(4, 2))
    model = tiny_gpt(partition_model=True)
    boxed = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                       train=False)["params"]
    params = jax.tree.map(
        lambda p, spec: jax.device_put(p, NamedSharding(mesh, spec)),
        nn.meta.unbox(boxed), nn.get_partition_spec(boxed))
    kv = SlotKVCache(model, params, slots=4, mesh=mesh, kv_dtype=kv_dtype)
    assert kv.dm.partition_model
    assert jax.tree.leaves(kv.cache)[0].sharding.spec[2] == "model"
    host = jax.device_get(params)
    for lp in (1, 8, 13):
        prompt = np.random.default_rng(lp).integers(0, 64, lp).astype(
            np.int32)
        slot, first = kv.insert(prompt)
        toks = [first] + [int(kv.advance()[slot]) for _ in range(4)]
        np.testing.assert_array_equal(
            _oracle(tiny_gpt(), host, prompt, 5), toks, str(lp))
        kv.evict(slot)


def test_block_prefill_applies_the_head_to_one_row():
    """The lowered ``kv_prefill_batched_l8`` holds logits for ONE position
    (``(1, 1, vocab)``; the row is gathered ahead of the final LayerNorm
    and the tied head), none for the bucket's 8, and no ``while``."""
    model = tiny_gpt(vocab_size=80)     # a width no other tensor has
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    programs = {}

    class Probe(SlotKVCache):
        def _jit(self, fn, name, **jit_kwargs):
            programs[name] = (fn, jit_kwargs)
            return super()._jit(fn, name, **jit_kwargs)

    kv = Probe(model, params, slots=2)
    kv._prefill(8)
    fn, jit_kwargs = programs["kv_prefill_batched_l8"]
    text = jax.jit(fn, **jit_kwargs).lower(
        params, kv.cache, jnp.int32(0), jnp.zeros((8,), jnp.int32),
        jnp.int32(3), jax.random.key(0)).as_text()
    assert "tensor<1x1x80xf32>" in text
    assert "x8x80x" not in text and "stablehlo.while" not in text


def test_slot_overflow_guard(model_params):
    """Advancing an at-capacity slot raises instead of silently clamping
    (the serving twin of the decode cache's sticky overflow flag)."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=1)
    kv.insert(np.zeros(model.max_len - 1, np.int32))
    kv.advance()                    # writes at max_len-1: the last legal slot
    with pytest.raises(SlotOverflow, match="max_len"):
        kv.advance()
    with pytest.raises(ValueError, match="room to generate"):
        SlotKVCache(model, params, slots=1).insert(
            np.zeros(model.max_len, np.int32))


def test_slot_cache_shards_over_mesh(model_params, mesh8):
    """Slots shard over the 'data' axis (parallel/mesh.kv_slot_sharding)
    and the sharded table still matches the sequential oracle."""
    from distributed_tensorflow_tpu.parallel import mesh as meshlib

    model, params = model_params
    with pytest.raises(ValueError, match="divide"):
        SlotKVCache(model, params, slots=6, mesh=mesh8)
    kv = SlotKVCache(model, params, slots=8, mesh=mesh8)
    leaf = jax.tree.leaves(kv.cache)[0]
    assert leaf.sharding.spec[0] == meshlib.DATA_AXIS
    prompts = _prompts(8, seed=3)
    out = {}
    for p in prompts:
        slot, first = kv.insert(p)
        out[slot] = (p, [first])
    for _ in range(4):
        toks = kv.advance()
        for slot, (_, got) in out.items():
            got.append(int(toks[slot]))
    for slot, (p, got) in out.items():
        np.testing.assert_array_equal(_oracle(model, params, p, 5),
                                      np.asarray(got))


def test_prefill_bucket_not_divisible_by_data_axis(model_params, mesh8):
    """The padded prompt is replicated scan data, not a slot vector: a
    prefill bucket (4) that does NOT divide the 8-way data axis must still
    admit (regression: insert sharded the prompt with the slot-vector
    sharding and device_put raised at admission — after training already
    ran)."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=8, mesh=mesh8, prefill_bucket=4)
    p = np.asarray([5, 9, 13], np.int32)          # bucket 4 on dp=8
    slot, first = kv.insert(p)
    got = [first]
    for _ in range(2):
        got.append(int(kv.advance()[slot]))
    np.testing.assert_array_equal(_oracle(model, params, p, 3),
                                  np.asarray(got))


# --------------------------------------------------------------- scheduler


def test_continuous_run_matches_generate(model_params):
    """E2E: staggered arrivals (VirtualClock — requests land MID-decode),
    mixed prompt and continuation lengths; every request's greedy tokens
    equal the sequential `generate` rollout."""
    model, params = model_params
    prompts = _prompts(5, seed=4)
    news = [6, 3, 8, 2, 5]
    arrivals = [0.0, 0.0, 1.0, 4.0, 6.0]
    kv = SlotKVCache(model, params, slots=2)
    res = ContinuousBatcher(kv, clock=VirtualClock()).run(
        [Request(rid=i, prompt=p, max_new_tokens=news[i],
                 arrival_s=arrivals[i]) for i, p in enumerate(prompts)])
    assert res["completed"] == 5
    assert res["prefills"] == 5
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            _oracle(model, params, p, news[i]),
            np.asarray(res["results"][i].tokens), str(i))
    # all slots freed at the end
    assert kv.free_slots == [0, 1]


def test_continuous_fewer_iterations_than_static(model_params):
    """THE acceptance claim: on a staggered-arrival workload the
    continuous batcher completes in measurably fewer decode iterations
    than restart-per-batch static batching, with identical greedy tokens."""
    model, params = model_params
    prompts = _prompts(6, seed=5)
    news = [12, 3, 12, 3, 12, 3]  # mixed lengths: static pays the max
    reqs = lambda: [Request(rid=i, prompt=p, max_new_tokens=news[i],  # noqa: E731
                            arrival_s=float(i))
                    for i, p in enumerate(prompts)]
    kv_c = SlotKVCache(model, params, slots=2)
    cont = ContinuousBatcher(kv_c, clock=VirtualClock(),
                             mode="continuous").run(reqs())
    kv_s = SlotKVCache(model, params, slots=2)
    stat = ContinuousBatcher(kv_s, clock=VirtualClock(),
                             mode="static").run(reqs())
    assert cont["decode_iterations"] < stat["decode_iterations"], \
        (cont["decode_iterations"], stat["decode_iterations"])
    for i in range(len(prompts)):
        np.testing.assert_array_equal(
            np.asarray(cont["results"][i].tokens),
            np.asarray(stat["results"][i].tokens), str(i))


def test_ttft_includes_queue_wait(model_params):
    """TTFT is arrival→first-token (BASELINE.md rule): with one slot, the
    second request's TTFT carries the time it queued behind the first."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=1)
    res = ContinuousBatcher(kv, clock=VirtualClock()).run([
        Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                max_new_tokens=5, arrival_s=0.0),
        Request(rid=1, prompt=np.arange(4, dtype=np.int32),
                max_new_tokens=2, arrival_s=1.0),
    ])
    r0, r1 = res["results"]
    # r0 admitted at t=0; its 4 post-prefill tokens take 4 iterations, so
    # r1 (arrived at 1.0) waits until t=4 — TTFT 3 ticks vs 0
    assert r0.ttft_s == 0.0
    assert r1.ttft_s == pytest.approx(3.0)
    assert all(g == pytest.approx(1.0) for g in r0.itl_s)
    assert res["serve_ttft_p95_s"] >= res["serve_ttft_p50_s"]


def test_request_queue_claim_and_order():
    """The rebuilt native-batcher claim contract: arrival-ordered pops,
    one consumer at a time, deterministic release."""
    q = RequestQueue([
        Request(rid=1, prompt=np.zeros(2, np.int32), max_new_tokens=1,
                arrival_s=2.0),
        Request(rid=0, prompt=np.zeros(2, np.int32), max_new_tokens=1,
                arrival_s=0.0),
    ])
    assert q.next_arrival() == 0.0
    assert q.pop_ready(0.0).rid == 0
    assert q.pop_ready(1.0) is None      # rid 1 hasn't arrived yet
    with q.claim():
        with pytest.raises(RuntimeError, match="busy"):
            with q.claim():
                pass
    with q.claim():
        pass  # released deterministically


def test_run_failure_frees_slots_and_closes_spans(model_params, tmp_path):
    """A window that dies mid-run must not poison the slot table (bench
    windows share ONE SlotKVCache — a leaked active slot busy-spins the
    next window): live slots are evicted, their spans closed (the records
    written so far survive into the partial-results artifact), and the
    same cache serves the next window."""
    from distributed_tensorflow_tpu.observability import Tracer
    from distributed_tensorflow_tpu.observability.analyze import (
        read_jsonl, trace_summary)

    model, params = model_params
    kv = SlotKVCache(model, params, slots=2)
    path = tmp_path / "t.jsonl"
    tracer = Tracer(path=path)
    calls = [0]

    def boom(rid, tok):
        calls[0] += 1
        if calls[0] >= 3:
            raise RuntimeError("stream sink died")

    def reqs():
        return [Request(rid=i, prompt=p, max_new_tokens=4, arrival_s=0.0)
                for i, p in enumerate(_prompts(2, seed=7))]

    with pytest.raises(RuntimeError, match="stream sink died"):
        ContinuousBatcher(kv, tracer=tracer,
                          clock=VirtualClock()).run(reqs(), on_token=boom)
    tracer.close()
    assert kv.free_slots == [0, 1]          # nothing leaked
    # every entered request span was closed on the way out
    spans = trace_summary(read_jsonl(path))["spans"]
    assert spans["request"]["count"] == 2
    # the same cache serves the next window cleanly
    res = ContinuousBatcher(kv, clock=VirtualClock()).run(reqs())
    assert res["completed"] == 2


def test_scheduler_rejects_overcapacity_request(model_params):
    model, params = model_params
    kv = SlotKVCache(model, params, slots=1)
    with pytest.raises(ValueError, match="max_len"):
        ContinuousBatcher(kv, clock=VirtualClock()).run([
            Request(rid=0, prompt=np.zeros(8, np.int32),
                    max_new_tokens=model.max_len, arrival_s=0.0)])


def test_scheduler_emits_request_spans(model_params, tmp_path):
    """Per-request request/prefill spans, and a round's decode_step with
    its two children, ride the existing tracer; `analyze spans` reads
    them with no new machinery.  No record is named ``decode``."""
    from distributed_tensorflow_tpu.observability import Tracer
    from distributed_tensorflow_tpu.observability.analyze import (
        read_jsonl, trace_summary)

    model, params = model_params
    path = tmp_path / "serve_trace.jsonl"
    tracer = Tracer(path=path)
    kv = SlotKVCache(model, params, slots=2)
    ContinuousBatcher(kv, tracer=tracer, clock=VirtualClock()).run(
        [Request(rid=i, prompt=p, max_new_tokens=3, arrival_s=0.0)
         for i, p in enumerate(_prompts(3, seed=6))])
    tracer.close()
    spans = trace_summary(read_jsonl(path))["spans"]
    assert spans["request"]["count"] == 3
    assert spans["prefill"]["count"] == 3
    assert "decode" not in spans
    rounds = spans["decode_step"]["count"]
    assert rounds >= 1
    assert spans["step_dispatch"]["count"] == rounds
    assert spans["token_fetch"]["count"] == rounds


# ------------------------------------------- the serve loop's own records


def _record_requests():
    # rid 2 falls due long after the first two are done: an idle wait
    arrivals = [0.0, 1.0, 40.0]
    return [Request(rid=10 + i, prompt=p, max_new_tokens=4,
                    arrival_s=arrivals[i])
            for i, p in enumerate(_prompts(3, seed=11, lo=3, hi=14))]


@pytest.fixture(scope="module")
def recorded_window(model_params):
    """One small window with NO tracer passed: what it leaves in the
    process-wide recorder, read when the run has ended."""
    from distributed_tensorflow_tpu.observability import recorder

    model, params = model_params
    kv = SlotKVCache(model, params, slots=2)
    summary = ContinuousBatcher(kv, clock=VirtualClock()).run(
        _record_requests())
    return {"summary": summary, "kv": kv,
            "records": recorder().records(root="serve_run")}


def test_default_run_records_one_root_and_one_request_each(recorded_window):
    recs, summary = recorded_window["records"], recorded_window["summary"]
    root = recs[0]
    assert root["name"] == "serve_run" and root["parent"] is None
    kv = recorded_window["kv"]
    assert root["attrs"] == {
        "offered": 3, "slots": 2, "mode": "continuous",
        # the table's counters over the window, set as the root closes
        "cache_bytes_per_token": kv.kv_bytes_per_slot() // kv.max_len,
        "state_bytes_per_slot": 0, "window_bytes_per_slot": 0,
        "expert_assignments": 0}
    assert sum(r["name"] == "serve_run" for r in recs) == 1
    requests = {r["rid"]: r for r in recs if r["name"] == "request"}
    assert sorted(requests) == [10, 11, 12]
    for res in summary["results"]:
        attrs = requests[res.rid]["attrs"]
        assert attrs["prompt_len"] == res.prompt_len
        assert attrs["max_new_tokens"] == 4 and attrs["tokens"] == 4
        assert attrs["queue_wait_s"] == res.queue_wait_s
        assert attrs["ttft_s"] == res.ttft_s
        assert {"prefill_s", "decode_s"} <= set(attrs)
        # detached: recorded under the root, nobody's parent
        assert requests[res.rid]["parent"] == root["id"]
    assert not any(r["name"] == "decode" for r in recs)
    # what is inside a round belongs to no request
    inner = [r for r in recs
             if r["name"] in ("step_dispatch", "token_fetch")]
    assert inner and all(r["rid"] is None for r in inner)
    detached = {r["id"] for r in recs if r["name"] == "request"}
    assert not detached & {r["parent"] for r in recs}
    # every record lies inside the root's interval
    assert all(root["start"] <= r["start"] <= r["end"] <= root["end"]
               for r in recs)


def test_default_run_records_every_prefill_with_its_bucket(recorded_window):
    recs, kv = recorded_window["records"], recorded_window["kv"]
    prefills = [r for r in recs if r["name"] == "prefill"]
    assert sorted(r["rid"] for r in prefills) == [10, 11, 12]
    # the cache counts the positions its programs ran; the scheduler
    # does not recompute the bucket rule
    assert sum(r["attrs"]["padded_len"] for r in prefills) \
        == kv.prefill_tokens_padded
    assert sum(r["attrs"]["prompt_len"] for r in prefills) \
        == kv.prefill_tokens_computed
    for r in prefills:
        lp, lpad = r["attrs"]["prompt_len"], r["attrs"]["padded_len"]
        assert lpad >= lp and lpad in (8, 16)
    # each program's first call is a program_build under the span that
    # made the call
    builds = {r["attrs"]["program"]: r for r in recs
              if r["name"] == "program_build"}
    assert "kv_decode_step" in builds
    assert {f"kv_prefill_batched_l{r['attrs']['padded_len']}"
            for r in prefills} <= set(builds)
    by_id = {r["id"]: r for r in recs}
    # (the step's is the round's dispatch, inside the round)
    dispatch = by_id[builds["kv_decode_step"]["parent"]]
    assert dispatch["name"] == "step_dispatch"
    assert by_id[dispatch["parent"]]["name"] == "decode_step"


def test_default_run_records_every_decode_round_and_idle_wait(
        recorded_window):
    recs, summary = recorded_window["records"], recorded_window["summary"]
    rounds = [r for r in recs if r["name"] == "decode_step"]
    assert len(rounds) == summary["decode_iterations"] > 0
    assert all(r["attrs"]["slots"] == 2 and 1 <= r["attrs"]["active"] <= 2
               for r in rounds)
    assert any(r["attrs"]["active"] == 2 for r in rounds)
    assert all(r["parent"] == recs[0]["id"] for r in rounds)
    waits = [r for r in recs if r["name"] == "idle_wait"]
    assert len(waits) >= 1 and summary["idle_polls"] >= 1


def test_null_tracer_records_nothing_and_serves_the_same_tokens(
        model_params, recorded_window):
    from distributed_tensorflow_tpu.observability import (
        NULL_TRACER, recorder)

    model, params = model_params
    kv = SlotKVCache(model, params, slots=2)
    last = recorder().records()[-1]["id"]
    summary = ContinuousBatcher(kv, tracer=NULL_TRACER,
                                clock=VirtualClock()).run(_record_requests())
    assert recorder().records()[-1]["id"] == last     # not one record more
    assert kv.tracer is NULL_TRACER
    assert [r.tokens for r in summary["results"]] \
        == [r.tokens for r in recorded_window["summary"]["results"]]
    assert summary["decode_iterations"] \
        == recorded_window["summary"]["decode_iterations"]


class _SlowToken:
    """A first token that takes 50 ms to reach the host."""

    def __init__(self, token):
        self.token = token

    def __int__(self):
        import time

        time.sleep(0.05)
        return int(self.token)


@pytest.mark.parametrize("path", ["insert", "final_chunk"])
def test_prefill_s_covers_the_first_tokens_materialisation(model_params,
                                                           path):
    """``phase_times()["prefill_s"]`` times the prefill, not its enqueue:
    the clock stops after the host holds the first token."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=1)
    prompt = _prompts(1, seed=12)[0]

    def admit():
        if path == "insert":
            return kv.insert(prompt)
        slot, _ = kv.begin_insert(prompt)
        return slot, kv.prefill_chunk(slot)

    slot, first = admit()            # builds the real program
    kv.evict(slot)
    programs = kv._prefills if path == "insert" else kv._chunks
    (lpad, real), = programs.items()

    def slow(*args):
        cache, token = real(*args)
        return cache, _SlowToken(token)

    programs[lpad] = slow
    before = kv.phase_times()["prefill_s"]
    assert admit() == (slot, first)
    assert kv.phase_times()["prefill_s"] - before >= 0.05


# ---------------------------------------- chunked prefill + prefix caching


# round 20 fast-lane repair: one chunk budget pins the claim fast; the
# second budget rides the slow lane
@pytest.mark.parametrize("budget", [
    2, pytest.param(4, marks=pytest.mark.slow)])
def test_chunked_run_matches_generate(model_params, budget):
    """Chunked prefill is bitwise: the same staggered workload as the
    monolithic e2e test, greedy tokens identical to the sequential
    ``generate`` oracle at every chunk budget."""
    model, params = model_params
    prompts = _prompts(5, seed=4)
    news = [6, 3, 8, 2, 5]
    arrivals = [0.0, 0.0, 1.0, 4.0, 6.0]
    kv = SlotKVCache(model, params, slots=2)
    res = ContinuousBatcher(kv, clock=VirtualClock(),
                            prefill_chunk=budget).run(
        [Request(rid=i, prompt=p, max_new_tokens=news[i],
                 arrival_s=arrivals[i]) for i, p in enumerate(prompts)])
    assert res["completed"] == 5
    assert res["prefill_chunk"] == budget
    assert res["prefill_chunks"] > 5     # at least one prompt needed >1
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            _oracle(model, params, p, news[i]),
            np.asarray(res["results"][i].tokens), str(i))
    assert kv.free_slots == [0, 1]


def test_chunked_prefill_bounds_decode_stall(model_params):
    """THE round-10 chunked-prefill acceptance claim, deterministic in
    decode-iteration time: one near-max-length prompt admitted into a
    table of live short requests stalls each live slot by at most one
    chunk per decode iteration (worst inter-token gap ≤ tick +
    budget × prefill_token_tick), strictly smaller than the monolithic
    admission's whole-prompt stall on the same seeded trace — with
    identical greedy tokens in both modes."""
    model, params = model_params
    rng = np.random.default_rng(9)
    short = [rng.integers(0, 64, 4).astype(np.int32) for _ in range(2)]
    long_p = rng.integers(0, 64, 24).astype(np.int32)

    def reqs():
        rs = [Request(rid=i, prompt=p, max_new_tokens=7, arrival_s=0.0)
              for i, p in enumerate(short)]
        rs.append(Request(rid=2, prompt=long_p, max_new_tokens=4,
                          arrival_s=2.0))
        return rs

    C, budget = 0.25, 4
    out = {}
    for b in (0, budget):
        kv = SlotKVCache(model, params, slots=3)
        res = ContinuousBatcher(
            kv, clock=VirtualClock(prefill_token_tick=C),
            prefill_chunk=b).run(reqs())
        worst = max(g for r in res["results"][:2] for g in r.itl_s)
        out[b] = (worst, [r.tokens for r in res["results"]])
    chunk_worst, chunk_toks = out[budget]
    mono_worst, mono_toks = out[0]
    assert chunk_worst <= 1.0 + budget * C + 1e-9, chunk_worst
    assert mono_worst >= 1.0 + len(long_p) * C - 1e-9, mono_worst
    assert chunk_worst < mono_worst
    assert chunk_toks == mono_toks    # greedy tokens identical


def test_prefix_cache_hit_bitwise_parity(model_params):
    """Shared-prefix prompts served through the prefix pool produce
    bitwise-identical greedy tokens to the no-cache sequential oracle,
    and the pool reports hits for every request after the first."""
    model, params = model_params
    rng = np.random.default_rng(11)
    shared = rng.integers(0, 64, 10).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(0, 64, 4).astype(np.int32)])
               for _ in range(4)]
    kv = SlotKVCache(model, params, slots=2, prefix_cache_blocks=32,
                     prefix_block=4)
    res = ContinuousBatcher(kv, clock=VirtualClock()).run(
        [Request(rid=i, prompt=p, max_new_tokens=5, arrival_s=0.0)
         for i, p in enumerate(prompts)])
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            _oracle(model, params, p, 5),
            np.asarray(res["results"][i].tokens), str(i))
    assert res["serve_prefix_cache_hit_rate"] > 0
    pc = res["prefix_cache"]
    # the 10-token shared prefix spans blocks 0 and 1; requests 2-4 each
    # reuse both (block 2 mixes shared and per-request tokens)
    assert pc["hits"] == 6 and pc["tokens_reused"] == 24
    assert pc["evictions"] == 0
    # the reused tokens were NOT recomputed
    assert res["prefill_tokens"] == sum(len(p) for p in prompts) - 24


# round 20 fast-lane repair: composition variant — the core prefix-hit
# and chunked-prefill pins each stay fast on their own
@pytest.mark.slow
def test_prefix_cache_composes_with_chunked_prefill(model_params):
    """Chunk + pool together: prefill resumes at the first uncached block
    AND fills in budget-sized chunks — still bitwise vs the oracle."""
    model, params = model_params
    rng = np.random.default_rng(12)
    shared = rng.integers(0, 64, 8).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(0, 64, 5).astype(np.int32)])
               for _ in range(3)]
    kv = SlotKVCache(model, params, slots=2, prefix_cache_blocks=16,
                     prefix_block=4)
    res = ContinuousBatcher(kv, clock=VirtualClock(),
                            prefill_chunk=3).run(
        [Request(rid=i, prompt=p, max_new_tokens=4, arrival_s=float(i))
         for i, p in enumerate(prompts)])
    for i, p in enumerate(prompts):
        np.testing.assert_array_equal(
            _oracle(model, params, p, 4),
            np.asarray(res["results"][i].tokens), str(i))
    assert res["serve_prefix_cache_hit_rate"] > 0


def test_prefix_cache_lru_eviction_and_pool_full(model_params):
    """A bounded pool evicts least-recently-used blocks and keeps
    admitting correctly: capacity 2 blocks across three distinct
    prompts forces evictions; every admission still completes with
    oracle-exact tokens, and a re-admission of an evicted prefix misses
    then re-pools."""
    model, params = model_params
    prompts = _prompts(3, seed=13, lo=9, hi=10)   # 9 tokens = 2 blocks ea
    kv = SlotKVCache(model, params, slots=1, prefix_cache_blocks=2,
                     prefix_block=4)

    def admit(p):
        slot, first = kv.insert(p)
        got = [first]
        for _ in range(2):
            got.append(int(kv.advance()[slot]))
        kv.evict(slot)
        np.testing.assert_array_equal(_oracle(model, params, p, 3),
                                      np.asarray(got))

    for p in prompts:
        admit(p)
    stats = kv.prefix_cache_stats()
    assert stats["evictions"] >= 2           # 3×2 blocks through a 2-pool
    assert stats["cached_blocks"] <= 2
    hits_before = stats["hits"]
    admit(prompts[0])                        # evicted prefix: full miss
    assert kv.prefix_cache_stats()["hits"] == hits_before
    admit(prompts[0])                        # freshly re-pooled: hits
    assert kv.prefix_cache_stats()["hits"] > hits_before
    kv.reset_prefix_cache()
    assert kv.prefix_cache_stats()["hits"] == 0
    assert kv.prefix_cache_stats()["cached_blocks"] == 0


def test_prefix_cache_lowers_virtual_ttft(model_params):
    """The TTFT acceptance claim on the deterministic clock: with prefill
    cost modeled (prefill_token_tick > 0), the cached run's TTFT p50 is
    LOWER than the cache-off run on the same trace — reused blocks are
    prefill work that never happens."""
    model, params = model_params
    rng = np.random.default_rng(14)
    shared = rng.integers(0, 64, 12).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(0, 64, 3).astype(np.int32)])
               for _ in range(4)]

    def run(blocks):
        kv = SlotKVCache(model, params, slots=2,
                         prefix_cache_blocks=blocks, prefix_block=4)
        return ContinuousBatcher(
            kv, clock=VirtualClock(prefill_token_tick=0.5)).run(
            [Request(rid=i, prompt=p, max_new_tokens=4,
                     arrival_s=float(i)) for i, p in enumerate(prompts)])

    cached, cold = run(32), run(0)
    assert cached["serve_prefix_cache_hit_rate"] > 0
    assert cold["serve_prefix_cache_hit_rate"] is None
    assert cached["serve_ttft_p50_s"] < cold["serve_ttft_p50_s"]
    for i in range(len(prompts)):
        np.testing.assert_array_equal(
            np.asarray(cached["results"][i].tokens),
            np.asarray(cold["results"][i].tokens), str(i))


# round 20 fast-lane repair: mesh composition variant —
# test_slot_cache_shards_over_mesh keeps the fast mesh representative
@pytest.mark.slow
def test_chunked_prefix_cache_on_mesh(model_params, mesh8):
    """Chunk-resumable prefill + the prefix pool on a slot-sharded table
    (8-way data axis): pooled blocks replicate, hits restore into ANY
    slot, and staggered-age slots still match the sequential oracle."""
    model, params = model_params
    rng = np.random.default_rng(21)
    shared = rng.integers(0, 64, 8).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.integers(0, 64, 3).astype(np.int32)])
               for _ in range(4)]
    kv = SlotKVCache(model, params, slots=8, mesh=mesh8,
                     prefix_cache_blocks=16, prefix_block=4)
    out = {}
    for p in prompts:           # sequential admissions: the pool warms
        slot, _ = kv.begin_insert(p)
        while True:
            first = kv.prefill_chunk(slot, 4)
            if first is not None:
                break
        out[slot] = (p, [first])
        toks = kv.advance()
        for s, (_, got) in out.items():
            got.append(int(toks[s]))
    for _ in range(2):
        toks = kv.advance()
        for s, (_, got) in out.items():
            got.append(int(toks[s]))
    for s, (p, got) in out.items():
        np.testing.assert_array_equal(
            _oracle(model, params, p, len(got)), np.asarray(got))
    stats = kv.prefix_cache_stats()
    assert stats["hits"] >= 6   # blocks 0-1 shared by requests 2-4
    leaf = jax.tree.leaves(kv.cache)[0]
    from distributed_tensorflow_tpu.parallel import mesh as meshlib
    assert leaf.sharding.spec[0] == meshlib.DATA_AXIS


def test_run_failure_frees_pending_chunked_slots(model_params):
    """A window dying MID-CHUNKED-PREFILL must release reserved slots and
    close their request spans (the PR 7 cleanup guard extended to the
    pending table): the same cache serves the next window cleanly."""
    from distributed_tensorflow_tpu.observability import Tracer
    from distributed_tensorflow_tpu.observability.analyze import (
        read_jsonl, trace_summary)

    model, params = model_params
    kv = SlotKVCache(model, params, slots=2)

    class Boom(RuntimeError):
        pass

    class BoomClock(VirtualClock):
        def on_prefill(self, tokens):
            raise Boom("chunk died")

    import tempfile
    with tempfile.TemporaryDirectory() as td:
        path = f"{td}/t.jsonl"
        tracer = Tracer(path=path)
        reqs = [Request(rid=0, prompt=_prompts(1, seed=8, lo=8, hi=9)[0],
                        max_new_tokens=3, arrival_s=0.0)]
        with pytest.raises(Boom):
            ContinuousBatcher(kv, tracer=tracer, clock=BoomClock(),
                              prefill_chunk=2).run(reqs)
        tracer.close()
        assert kv.free_slots == [0, 1]
        assert not kv._pending
        spans = trace_summary(read_jsonl(path))["spans"]
        assert spans["request"]["count"] == 1
    res = ContinuousBatcher(kv, clock=VirtualClock()).run(
        [Request(rid=1, prompt=_prompts(1, seed=9)[0], max_new_tokens=2,
                 arrival_s=0.0)])
    assert res["completed"] == 1


def test_run_failure_after_final_chunk_releases_activated_slot(
        model_params):
    """A failure landing BETWEEN the final chunk (which activates the
    slot in the kv) and the scheduler's promotion must surface the
    ORIGINAL error — not an abort-of-nothing-pending RuntimeError — and
    must release the activated slot (regression: the cleanup called
    abort_insert unconditionally, masking the error and leaking the slot
    active forever)."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=2)

    class Boom(RuntimeError):
        pass

    class BoomClock(VirtualClock):
        def on_prefill(self, tokens):
            raise Boom("after final chunk")

    # 3-token prompt ≤ budget 4: the FIRST chunk is the final one
    with pytest.raises(Boom, match="after final chunk"):
        ContinuousBatcher(kv, clock=BoomClock(), prefill_chunk=4).run(
            [Request(rid=0, prompt=np.arange(3, dtype=np.int32),
                     max_new_tokens=3, arrival_s=0.0)])
    assert kv.free_slots == [0, 1]
    assert not kv._pending
    res = ContinuousBatcher(kv, clock=VirtualClock()).run(
        [Request(rid=0, prompt=np.arange(3, dtype=np.int32),
                 max_new_tokens=2, arrival_s=0.0)])
    assert res["completed"] == 1


def test_insert_failure_after_activation_releases_slot(model_params):
    """insert() with the pool on: a failure AFTER the final chunk
    activated the slot (e.g. inside the pool-extraction step) must
    re-raise the original error and leave the slot evicted, not raise
    'no pending admission' over it."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=1, prefix_cache_blocks=4,
                     prefix_block=2)

    class Boom(RuntimeError):
        pass

    def boom_pool(prompt, lp, slot):
        raise Boom("pool extraction died")

    kv._pool_prefix = boom_pool
    with pytest.raises(Boom, match="pool extraction died"):
        kv.insert(np.arange(5, dtype=np.int32))
    assert kv.free_slots == [0]
    assert not kv._pending


def test_serve_summary_token_split(model_params):
    """prefill/decode token accounting: prefill_tokens counts prompt
    tokens actually computed, decode_tokens the advance-emitted tokens
    (every request's FIRST token is prefill-side), and the *_per_sec
    split divides by the same elapsed as the other rates."""
    model, params = model_params
    prompts = _prompts(3, seed=15)
    kv = SlotKVCache(model, params, slots=2)
    res = ContinuousBatcher(kv, clock=VirtualClock()).run(
        [Request(rid=i, prompt=p, max_new_tokens=4, arrival_s=0.0)
         for i, p in enumerate(prompts)])
    assert res["prefill_tokens"] == sum(len(p) for p in prompts)
    assert res["decode_tokens"] == res["tokens_generated"] - 3
    assert res["serve_prefill_tokens_per_sec"] == pytest.approx(
        res["prefill_tokens"] / res["elapsed_s"])
    assert res["serve_decode_tokens_per_sec"] == pytest.approx(
        res["decode_tokens"] / res["elapsed_s"])
    assert res["serve_prefix_cache_hit_rate"] is None  # pool off
    assert res["prefix_cache"] is None


# ------------------------------------------------- queue backoff / idle


def test_queue_claim_bounded_backoff():
    """The busy-claim loop is BOUNDED: a claim against a busy queue
    retries with short backoff sleeps a fixed number of times (attempt
    count recorded), then raises — never a hot spin, never unbounded."""
    import time as _time

    q = RequestQueue()
    with q.claim():
        assert q.claim_attempts == 1
        t0 = _time.monotonic()
        with pytest.raises(RuntimeError, match="bounded claim attempts"):
            with q.claim(max_attempts=4, backoff_s=0.001):
                pass
        elapsed = _time.monotonic() - t0
        assert q.claim_attempts == 4
        assert elapsed < 1.0          # 3 sleeps of ≤8 ms: bounded cost
    with q.claim():                   # released deterministically
        pass


def test_idle_wait_bounded_polls(model_params):
    """An idle batcher waiting for the next arrival wakes a bounded,
    counted number of times (poll slices), not once per loop spin: the
    wait to a far-future arrival under a sliced clock performs
    ~wait/slice polls, and the VirtualClock (slice = ∞) exactly one."""
    model, params = model_params

    class SlicedClock(VirtualClock):
        poll_slice_s = 2.0

    kv = SlotKVCache(model, params, slots=1)
    b = ContinuousBatcher(kv, clock=SlicedClock())
    res = b.run([Request(rid=0, prompt=np.arange(3, dtype=np.int32),
                         max_new_tokens=2, arrival_s=9.0)])
    assert res["completed"] == 1
    # 9.0 of idle in 2.0-slices: 5 polls (the last lands on the arrival)
    assert res["idle_polls"] == 5
    kv2 = SlotKVCache(model, params, slots=1)
    res2 = ContinuousBatcher(kv2, clock=VirtualClock()).run(
        [Request(rid=0, prompt=np.arange(3, dtype=np.int32),
                 max_new_tokens=2, arrival_s=9.0)])
    assert res2["idle_polls"] == 1    # jump straight to the arrival


# ------------------------------------------------ observability vocabulary


def test_analyze_diff_serve_directions():
    """serve_ttft/itl p50/p95 gate lower-is-better, requests/sec/chip
    higher — a latency increase and a throughput drop are both
    regressions."""
    from distributed_tensorflow_tpu.observability.analyze import diff_reports

    base = {"serve_ttft_p95_s": 1.0, "serve_itl_p95_s": 0.1,
            "serve_requests_per_sec_per_chip": 10.0,
            "serve_prefix_cache_hit_rate": 0.8,
            "serve_prefill_tokens_per_sec": 100.0,
            "serve_decode_tokens_per_sec": 200.0}
    worse = {"serve_ttft_p95_s": 2.0, "serve_itl_p95_s": 0.3,
             "serve_requests_per_sec_per_chip": 5.0,
             "serve_prefix_cache_hit_rate": 0.2,
             "serve_prefill_tokens_per_sec": 50.0,
             "serve_decode_tokens_per_sec": 100.0}
    d = diff_reports(base, worse, threshold=0.1)
    regressed = {r["metric"] for r in d["regressions"]}
    assert regressed == {"serve_ttft_p95_s", "serve_itl_p95_s",
                         "serve_requests_per_sec_per_chip",
                         "serve_prefix_cache_hit_rate",
                         "serve_prefill_tokens_per_sec",
                         "serve_decode_tokens_per_sec"}
    better = diff_reports(worse, base, threshold=0.1)
    assert not better["regressions"]
    assert {r["metric"] for r in better["improvements"]} == regressed


def test_analyze_value_direction_rates_are_higher_better():
    """Regression pin for the `sec_per` substring bug: `…_per_sec_per_chip`
    bench headlines are rates (higher-better); time-valued lines stay
    lower-better."""
    from distributed_tensorflow_tpu.observability.analyze import (
        _value_direction)

    assert _value_direction(
        {"metric": "gpt_serve_requests_per_sec_per_chip",
         "unit": "requests/sec/chip"}) == "higher"
    assert _value_direction(
        {"metric": "mnist_cnn_sync_examples_per_sec_per_chip",
         "unit": "examples/sec/chip"}) == "higher"
    assert _value_direction(
        {"metric": "attention_fwd_bwd_step_ms", "unit": "ms"}) == "lower"
    assert _value_direction(
        {"metric": "some_latency_probe", "unit": "seconds_per_step"}) \
        == "lower"
    # round-10 keys: the prefill/decode split and the hit rate are rates
    # — each new *_per_sec key must resolve higher-better (the `sec_per`
    # substring bug class this test pins)
    assert _value_direction(
        {"metric": "gpt_serve_prefill_tokens_per_sec",
         "unit": "tokens/sec"}) == "higher"
    assert _value_direction(
        {"metric": "gpt_serve_decode_tokens_per_sec",
         "unit": "tokens/sec"}) == "higher"


def test_load_report_flattens_round10_serve_keys(tmp_path):
    """The new serve keys flatten out of a run report's nested serve
    section and diff with the standard machinery."""
    from distributed_tensorflow_tpu.observability.analyze import (
        diff_reports, load_report)

    summary = {"steps": 2, "run_report": {
        "serve": {"serve_prefix_cache_hit_rate": 0.75,
                  "serve_prefill_tokens_per_sec": 120.0,
                  "serve_decode_tokens_per_sec": 300.0}}}
    p = tmp_path / "summary.json"
    p.write_text(json.dumps(summary))
    flat = load_report(p)
    assert flat["serve_prefix_cache_hit_rate"] == 0.75
    worse = dict(flat, serve_prefix_cache_hit_rate=0.1)
    d = diff_reports(flat, worse)
    assert [r["metric"] for r in d["regressions"]] == \
        ["serve_prefix_cache_hit_rate"]


def test_load_report_flattens_serve_section(tmp_path):
    """A run report's nested serve section diffs like a training metric."""
    from distributed_tensorflow_tpu.observability.analyze import (
        diff_reports, load_report)

    summary = {"steps": 2, "run_report": {
        "serve": {"serve_ttft_p95_s": 0.5, "mode": "continuous",
                  "serve_requests_per_sec_per_chip": 7.0}}}
    p = tmp_path / "summary.json"
    p.write_text(json.dumps(summary))
    flat = load_report(p)
    assert flat["serve_ttft_p95_s"] == 0.5
    assert flat["serve_requests_per_sec_per_chip"] == 7.0
    d = diff_reports(flat, flat)
    assert d["compared"] >= 2 and not d["regressions"]


def test_serve_section_per_chip_normalization():
    from distributed_tensorflow_tpu.observability import serve_section

    import types

    sec = serve_section({"serve_requests_per_sec": 8.0, "completed": 4,
                         "results": [types.SimpleNamespace(tokens=[3, 5])]},
                        4)
    assert sec["serve_requests_per_sec_per_chip"] == 2.0
    # the result objects are reduced to their token streams (JSON)
    assert "results" not in sec
    assert sec["generated_tokens"] == [[3, 5]]
    assert serve_section(None) is None


# ------------------------------------------------------------ KV dtype

def test_bf16_kv_cache_matches_sequential_oracle(model_params):
    """--serve-kv-dtype bfloat16 (ISSUE 8 satellite): the KV slot table
    stored in bf16 — half the KV memory — still decodes greedy tokens
    identical to the sequential f32 ``generate`` oracle on the test
    model, through staggered-age slots (the attention read promotes the
    bf16 table back to the compute dtype)."""
    import jax.numpy as jnp

    model, params = model_params
    kv = SlotKVCache(model, params, slots=4, kv_dtype=jnp.bfloat16)
    assert kv.kv_dtype == "bfloat16"
    f32_bytes = sum(
        leaf.size * 4 for leaf in jax.tree.leaves(
            SlotKVCache(model, params, slots=4).cache)
        if jnp.issubdtype(leaf.dtype, jnp.floating))
    bf16_bytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(kv.cache)
        if jnp.issubdtype(leaf.dtype, jnp.floating))
    assert bf16_bytes * 2 == f32_bytes  # half the KV memory per slot

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


def test_kv_dtype_surfaces_in_serve_summary(model_params):
    import jax.numpy as jnp

    model, params = model_params
    kv = SlotKVCache(model, params, slots=2, kv_dtype=jnp.bfloat16)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=3, arrival_s=0.0)
            for i, p in enumerate(_prompts(2, seed=5))]
    summary = ContinuousBatcher(kv).run(reqs)
    assert summary["serve_kv_dtype"] == "bfloat16"
    from distributed_tensorflow_tpu.observability import serve_section

    assert serve_section(summary, 1)["serve_kv_dtype"] == "bfloat16"
    # default table reports the model dtype
    kv32 = SlotKVCache(model, params, slots=2)
    summary32 = ContinuousBatcher(kv32).run(
        [Request(rid=0, prompt=_prompts(1, seed=6)[0], max_new_tokens=2,
                 arrival_s=0.0)])
    assert summary32["serve_kv_dtype"] == "float32"


def _two_index_scatter(table, update, pos):
    """The slot table's write as plain indexing, the oracle for
    ``select_slot_row`` and ``write_slot_rows``: ``table[b, pos[b, j]] =
    update[b, j]``, a position past ``max_len`` dropped.  Indexing would
    wrap a negative position; the table's rule drops it like one past the
    end, so it is sent there."""
    rows = jnp.arange(table.shape[0])[:, None]
    pos = jnp.where(pos < 0, table.shape[1], pos)
    return table.at[rows, pos].set(update, mode="drop")


def _write_case_starts(case, max_len, width):
    """First position of each slot's block of ``width`` tokens."""
    return {
        "in_range": [0, 5, max_len - width, 17],
        # slot 0 ends on the table's last cell, slots 1 and 2 lie past it
        "past_max_len": [max_len - 1, max_len, max_len + 5, 7],
        # a wrap would hit max_len - 1, a clamp position 0
        "negative": [-1, -max_len, 9, -width - 3],
        # ``_chunk`` hands the scan one slot's (1, max_len, ...) sub-table,
        # and its bucket's pad rows run past the table
        "sub_table": [11],
        "sub_table_past_max_len": [max_len],
    }[case]


@pytest.mark.parametrize("case", ["in_range", "past_max_len", "negative",
                                  "sub_table", "sub_table_past_max_len"])
@pytest.mark.parametrize("width", [1, 3], ids=["one_token", "token_block"])
@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8"])
def test_slot_table_write_matches_two_index_scatter(kv_dtype, width, case):
    """Both monolithic branches of the slot-decode attention (one token:
    ``models/gpt.select_slot_row``; token block, which int8 always takes:
    ``write_slot_rows``) leave the table bitwise as ``.at[rows, pos].set``
    of the same K/V rows leaves it, and keep the DROP RULE: a position
    outside ``[0, max_len)`` changes nothing — position ``max_len - 1``,
    where ``dynamic_update_slice`` would clamp to and a negative index
    wrap to, keeps the real token it held.  The oracle is applied to the
    rows the attention's own ``key`` / ``value`` projections gave in the
    same call, not to a second run of the model."""
    from distributed_tensorflow_tpu.parallel import compression

    max_len = 32
    start = np.asarray(_write_case_starts(case, max_len, width))
    slots = len(start)
    dm = tiny_gpt(max_len=max_len).clone(
        decode=True, decode_slots=True, attention_impl="dense",
        kv_quant=kv_dtype == "int8")
    dummy = jnp.zeros((slots, 1), jnp.int32)
    variables = dm.init(jax.random.key(0), dummy, train=False,
                        positions=dummy)
    rng = np.random.default_rng(26)

    def filled(leaf):       # every cell holds a "real token" beforehand
        if leaf.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, leaf.shape), jnp.int8)
        dtype = leaf.dtype if kv_dtype == "int8" else jnp.dtype(kv_dtype)
        return jnp.asarray(rng.normal(size=leaf.shape), dtype)

    before = jax.tree.map(filled, variables["cache"])
    pos = jnp.asarray(start[:, None] + np.arange(width)[None, :], jnp.int32)
    toks = jnp.asarray(rng.integers(0, 64, (slots, width)), jnp.int32)
    _, upd = dm.apply(
        {"params": variables["params"], "cache": before}, toks, train=False,
        positions=pos, mutable=["cache", "intermediates"],
        capture_intermediates=lambda m, _: m.name in ("key", "value"))
    got = upd["cache"]

    def want(path, table):
        """The oracle on one leaf: its layer's projected rows, stored the
        way the leaf stores them."""
        *layer, name = [k.key for k in path]
        rows = upd["intermediates"]
        for k in layer + ["key" if "key" in name else "value", "__call__"]:
            rows = rows[k]
        rows = rows[0].reshape(slots, width, dm.heads, -1)
        if kv_dtype == "int8":
            q, scale = compression.int8_channel_encode(rows)
            rows = scale if name.endswith("_scale") else q
        return _two_index_scatter(table, rows.astype(table.dtype), pos)

    wanted = jax.tree_util.tree_map_with_path(want, before)
    changed = 0
    for b, g, w in zip(*map(jax.tree.leaves, (before, got, wanted))):
        assert g.dtype == b.dtype and g.shape == b.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        b, g = np.asarray(b), np.asarray(g)
        for s in range(slots):
            live = [int(p) for p in np.asarray(pos[s]) if 0 <= p < max_len]
            rest = np.setdiff1d(np.arange(max_len), live)
            np.testing.assert_array_equal(g[s, rest], b[s, rest])
            changed += int((g[s, live] != b[s, live]).any())
    # the in-range rows did take their tokens (the check above is not
    # vacuous), and a slot whose block lies outside the table was dropped
    # whole
    in_table = ((np.asarray(pos) >= 0) & (np.asarray(pos) < max_len)).any(1)
    assert changed == len(jax.tree.leaves(before)) * int(in_table.sum())


# ------------------------------- the weights in the dtype the step uses


class _HeldAsGiven(GPTLM):
    """``GPTLM`` with no narrowing rule: its table holds the tree it is
    given, as every table did before ``step_param_dtype``."""

    def step_param_dtype(self, path):
        return None


@pytest.fixture(scope="module")
def bf16_model_params():
    """bfloat16 compute over float32 parameters, every leaf perturbed (a
    fresh init has zero biases and unit gains, which round to
    themselves)."""
    model = tiny_gpt(dtype=jnp.bfloat16)
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(7), len(leaves))
    return model, jax.tree.unflatten(tree, [
        t + 0.1 * jax.random.normal(k, t.shape, t.dtype)
        for t, k in zip(leaves, keys)])


def _leaf_names(tree):
    return {jax.tree_util.keystr(path): leaf for path, leaf in
            jax.tree_util.tree_leaves_with_path(tree)}


def _last_params_place():
    """The attributes of the newest ``params_place`` span."""
    from distributed_tensorflow_tpu.observability import recorder

    return [r for r in recorder().records()
            if r["name"] == "params_place"][-1]["attrs"]


def test_table_holds_weights_in_the_dtype_the_step_uses(bf16_model_params):
    """A float32 checkpoint served at bfloat16 is narrowed once, when the
    table takes it: kernels, biases and embeddings bfloat16, the
    ``LayerNorm`` leaves float32 (flax multiplies those in float32), and
    the same bits come out as from the tree held as given: the step's
    logits bit for bit, the served tokens (here, on the CPU; where the
    v5e's compiler parts from this: PERF.md section 6, PR 36)."""
    model, params = bf16_model_params
    kv = SlotKVCache(model, params, slots=3)
    given = SlotKVCache(tiny_gpt(cls=_HeldAsGiven, dtype=jnp.bfloat16),
                        params, slots=3)
    assert all(a is b for a, b in zip(jax.tree.leaves(given.params),
                                      jax.tree.leaves(params)))
    held = _leaf_names(kv.params)
    assert set(held) == set(_leaf_names(params))
    for name, leaf in held.items():
        want = jnp.float32 if "LayerNorm" in name else jnp.bfloat16
        assert leaf.dtype == want, (name, leaf.dtype)
    assert any("LayerNorm" in name for name in held)
    assert given.param_bytes == sum(t.nbytes for t in jax.tree.leaves(params))
    assert kv.param_bytes == sum(t.nbytes for t in jax.tree.leaves(kv.params))
    assert kv.param_bytes < 0.55 * given.param_bytes

    def step_logits(table):
        tokens = jnp.asarray([[3], [11], [40]], jnp.int32)
        logits, _ = table.dm.apply(
            {"params": table.params, "cache": table.cache}, tokens,
            train=False, positions=jnp.zeros((3, 1), jnp.int32),
            mutable=["cache"])
        return np.asarray(logits)

    np.testing.assert_array_equal(step_logits(kv), step_logits(given))

    def served(table):
        reqs = [Request(rid=i, prompt=p, max_new_tokens=10, arrival_s=0.0)
                for i, p in enumerate(_prompts(5, seed=11, lo=3, hi=20))]
        summary = ContinuousBatcher(table, clock=VirtualClock()).run(reqs)
        assert summary["serve_param_bytes"] == table.param_bytes
        return {r.rid: r.tokens for r in summary["results"]}

    assert served(kv) == served(given)


@pytest.mark.parametrize("name", ["gpt-float32", "mla_moe", "hybrid_ssm",
                                  "window_moe"])
def test_table_uses_in_place_a_tree_the_step_reads_as_held(name):
    """A model that declares no rule, and a ``GPTLM`` that computes in
    the dtype of its leaves: every leaf of ``kv.params`` IS the leaf
    handed in, and the placement's span says that nothing was narrowed."""
    from distributed_tensorflow_tpu.models import create_model

    model = (tiny_gpt(dtype=jnp.float32) if name == "gpt-float32"
             else create_model(name, vocab_size=64, max_len=64,
                               dtype="bfloat16"))
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    kv = SlotKVCache(model, params, slots=2)
    assert all(a is b for a, b in zip(jax.tree.leaves(kv.params),
                                      jax.tree.leaves(params)))
    span = _last_params_place()
    assert span["narrowed"] == 0
    assert span["bytes_given"] == span["bytes_held"] == kv.param_bytes


def test_committed_tensor_parallel_params_get_a_twin_of_the_same_sharding():
    """A tensor-parallel engine's committed float32 params, served at
    bfloat16: the narrowed twin keeps each leaf's sharding over the mesh,
    and the leaves the rule leaves alone are used in place."""
    import flax.linen as nn
    from jax.sharding import NamedSharding
    from distributed_tensorflow_tpu.parallel import mesh as meshlib

    mesh = meshlib.create_mesh(8, axis_names=("data", "model"), shape=(4, 2))
    model = tiny_gpt(partition_model=True, dtype=jnp.bfloat16)
    boxed = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                       train=False)["params"]
    params = jax.tree.map(
        lambda p, spec: jax.device_put(p, NamedSharding(mesh, spec)),
        nn.meta.unbox(boxed), nn.get_partition_spec(boxed))
    kv = SlotKVCache(model, params, slots=4, mesh=mesh)
    given = _leaf_names(params)
    for name, leaf in _leaf_names(kv.params).items():
        assert leaf.sharding == given[name].sharding, name
        if "LayerNorm" in name:
            assert leaf is given[name]
        else:
            assert leaf.dtype == jnp.bfloat16, name
    assert any(not t.sharding.is_fully_replicated
               for t in jax.tree.leaves(kv.params))


def test_swap_takes_a_float32_checkpoint_and_narrows_it_once(
        bf16_model_params):
    """``swap_params`` takes the tree ``__init__`` takes: a float32
    checkpoint is narrowed by the same rule and only then compared with
    what is served, a wrong shape is still refused, and a tree that was
    narrowed before (another table's ``params``) is used in place."""
    model, params = bf16_model_params
    kv = SlotKVCache(model, params, slots=2)
    before = _leaf_names(kv.params)
    kv.swap_params(jax.tree.map(lambda t: t * 0.5, params))
    span = _last_params_place()
    after = _leaf_names(kv.params)
    assert span["narrowed"] == sum(
        leaf.dtype == jnp.bfloat16 for leaf in after.values()) > 0
    assert span["bytes_given"] > span["bytes_held"] == kv.param_bytes
    for name, leaf in _leaf_names(params).items():
        assert after[name].dtype == before[name].dtype
        np.testing.assert_array_equal(
            np.asarray(after[name], np.float32),
            np.asarray((leaf * 0.5).astype(after[name].dtype), np.float32),
            name)
    wrong = jax.tree.map(lambda t: jnp.zeros(t.shape + (2,), t.dtype),
                         params)
    with pytest.raises(ValueError, match="shape/dtype mismatch"):
        kv.swap_params(wrong)
    assert all(a is b for a, b in zip(jax.tree.leaves(kv.params),
                                      after.values()))    # untouched
    # narrowing a narrowed tree: the same arrays, through both doors
    twin = SlotKVCache(model, kv.params, slots=1)
    assert all(a is b for a, b in zip(jax.tree.leaves(twin.params),
                                      jax.tree.leaves(kv.params)))
    served = jax.tree.leaves(kv.params)
    kv.swap_params(kv.params)
    assert all(a is b for a, b in zip(jax.tree.leaves(kv.params), served))


def test_abstract_tree_is_narrowed_abstractly(bf16_model_params):
    """``tests/test_tpu_compile.py`` hands the table ``jax.eval_shape``'s
    tree: ``jax.ShapeDtypeStruct`` has no ``astype``, and the held tree is
    what the programs are lowered with."""
    model, params = bf16_model_params
    shapes = jax.eval_shape(lambda: params)
    kv = SlotKVCache(model, shapes, slots=2)
    real = SlotKVCache(model, params, slots=2)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(kv.params),
            jax.tree.leaves(real.params)):
        assert isinstance(a, jax.ShapeDtypeStruct)
        assert (a.shape, a.dtype) == (b.shape, b.dtype), path
    assert kv.param_bytes == real.param_bytes


@pytest.mark.slow    # round 20 fast-lane repair: kv-dtype threading
# is covered fast by the library suites; the e2e representative is
# test_harness_serve_e2e_fsdp
def test_harness_serve_kv_dtype_e2e():
    """--serve-kv-dtype threads through the harness into the serve
    report section."""
    from distributed_tensorflow_tpu.data.loaders import load_lm_dataset
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    def lm_fn(batch_size, type="train", **kw):
        return load_lm_dataset(seq_len=16, vocab_size=64, n_train=64,
                               n_test=32, split=type)

    summary = run(ExperimentConfig(
        engine="fsdp", model="gpt", dataset="lm_synth", dataset_fn=lm_fn,
        n_devices=8, batch_size=4, log_every=0,
        model_args={"hidden": 32, "layers": 1, "heads": 2, "ffn": 64,
                    "max_len": 32},
        serve_requests=4, serve_slots=8, serve_max_new=4,
        serve_prompt_len=4, serve_kv_dtype="bfloat16"))
    assert summary["serve"]["serve_kv_dtype"] == "bfloat16"
    assert summary["run_report"]["serve"]["serve_kv_dtype"] == "bfloat16"
    assert summary["serve"]["completed"] == 4


# ----------------------------------------------- harness (run() in process)


def test_harness_serve_validation_pre_train():
    """--serve on a non-LM model fails BEFORE training (the --sample
    contract), as does an overcapacity prompt+max_new budget."""
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    with pytest.raises(ValueError, match="GPT causal LM"):
        run(ExperimentConfig(engine="fsdp", model="mlp",
                             dataset="synthetic", n_devices=8,
                             serve_requests=2))
    with pytest.raises(ValueError, match="max_len"):
        run(ExperimentConfig(engine="fsdp", model="gpt",
                             dataset="lm_synth", n_devices=8,
                             serve_requests=2, serve_prompt_len=8,
                             serve_max_new=1024,
                             model_args={"hidden": 32, "layers": 1,
                                         "heads": 2, "ffn": 64}))


def test_harness_serve_e2e_fsdp():
    """Train a tiny GPT through the harness (fsdp — GSPMD, runs on this
    container) and serve it: the summary and run report carry the same
    serve section with percentiles + per-chip throughput, slots sharded
    over the run's 8-way data axis."""
    from distributed_tensorflow_tpu.data.loaders import load_lm_dataset
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    def lm_fn(batch_size, type="train", **kw):
        return load_lm_dataset(seq_len=16, vocab_size=64, n_train=64,
                               n_test=32, split=type)

    summary = run(ExperimentConfig(
        engine="fsdp", model="gpt", dataset="lm_synth", dataset_fn=lm_fn,
        n_devices=8, batch_size=4, log_every=0,
        model_args={"hidden": 32, "layers": 1, "heads": 2, "ffn": 64,
                    "max_len": 32},
        serve_requests=10, serve_slots=8, serve_max_new=4,
        serve_prompt_len=4))
    sec = summary["serve"]
    assert sec == summary["run_report"]["serve"]
    assert sec["completed"] == 10
    assert sec["mode"] == "continuous"
    assert sec["serve_requests_per_sec_per_chip"] > 0
    assert sec["serve_ttft_p95_s"] >= sec["serve_ttft_p50_s"] > 0
    assert sec["serve_itl_p95_s"] >= sec["serve_itl_p50_s"] >= 0
    assert sec["tokens_generated"] == 40


@pytest.mark.slow    # round 20 fast-lane repair (see above)
def test_harness_serve_chunked_prefix_e2e():
    """--serve-prefill-chunk + --serve-prefix-cache + --serve-shared-prefix
    thread through the harness: the serve section carries the token split,
    a nonzero hit rate (every request shares the synthetic system prompt)
    and the chunk accounting, in summary AND run report."""
    from distributed_tensorflow_tpu.data.loaders import load_lm_dataset
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    def lm_fn(batch_size, type="train", **kw):
        return load_lm_dataset(seq_len=16, vocab_size=64, n_train=64,
                               n_test=32, split=type)

    summary = run(ExperimentConfig(
        engine="fsdp", model="gpt", dataset="lm_synth", dataset_fn=lm_fn,
        n_devices=8, batch_size=4, log_every=0,
        model_args={"hidden": 32, "layers": 1, "heads": 2, "ffn": 64,
                    "max_len": 32},
        # 2 slots for 6 requests: later admissions arrive after earlier
        # prefills pooled the shared blocks (with slots ≥ requests the
        # whole burst admits cold — pooling happens at prefill
        # completion, so a simultaneous burst cannot share)
        serve_requests=6, serve_slots=2, serve_max_new=4,
        serve_prompt_len=4, serve_prefill_chunk=4, serve_prefix_cache=16,
        serve_prefix_block=4, serve_shared_prefix=6))
    sec = summary["serve"]
    assert sec == summary["run_report"]["serve"]
    assert sec["completed"] == 6
    assert sec["prefill_chunk"] == 4
    assert sec["prefill_chunks"] >= 6
    assert sec["serve_prefix_cache_hit_rate"] > 0
    assert sec["prefix_cache"]["hits"] > 0
    assert sec["serve_prefill_tokens_per_sec"] > 0
    assert sec["serve_decode_tokens_per_sec"] > 0
    # shared prefix rides every prompt: 6 + 4 tokens each, minus reuse
    assert sec["prefill_tokens"] < 6 * 10


def test_harness_serve_validation_round10_flags():
    """Bad chunk/pool/shared-prefix flags fail BEFORE training, like every
    other deterministically-knowable --serve failure."""
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    base = dict(engine="fsdp", model="gpt", dataset="lm_synth",
                n_devices=8, serve_requests=2,
                model_args={"hidden": 32, "layers": 1, "heads": 2,
                            "ffn": 64})
    with pytest.raises(ValueError, match="serve-prefill-chunk"):
        run(ExperimentConfig(**base, serve_prefill_chunk=-1))
    with pytest.raises(ValueError, match="serve-prefix-cache"):
        run(ExperimentConfig(**base, serve_prefix_cache=-1))
    with pytest.raises(ValueError, match="serve-prefix-block"):
        run(ExperimentConfig(**base, serve_prefix_block=0))
    with pytest.raises(ValueError, match="max_len"):
        run(ExperimentConfig(**base, serve_shared_prefix=1024))


def test_native_pipeline_rejects_lm_labels():
    """The native C++ gather stages scalar labels; (B, L) next-token
    targets must take the Python path (silently flattening them is the
    bug the serving CLI smoke exposed)."""
    from distributed_tensorflow_tpu.data.loaders import load_lm_dataset
    from distributed_tensorflow_tpu.native import load as native_load

    ds = load_lm_dataset(seq_len=8, vocab_size=64, n_train=32, n_test=16)
    for bx, by, _ in ds.batches(8, shuffle=False):
        assert by.shape == (8, 8)   # default path: labels keep their L dim
        break
    if native_load() is not None:
        with pytest.raises(RuntimeError, match="scalar labels"):
            ds.batches(8, native=True)
