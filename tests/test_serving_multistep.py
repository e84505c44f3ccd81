"""Multi-step decode dispatch (ISSUE 20): the fused k-iteration decode
scan on the slot table (``advance_multi`` / ``dispatch_multi`` +
``drain_multi``), the scheduler's pipelined dispatch loop behind
``multi_step=k``, and the dispatch-accounting vocabulary
(``serve_dispatches`` / ``serve_host_gap_s``).

The non-negotiable pin: greedy token streams are BITWISE IDENTICAL at
every k (and to the flag-off engine) in STRICTLY FEWER host dispatches —
fusing iterations moves only the host round-trip, never the math.  EOS
and budget deactivation happen in-device mid-scan; admissions quantize
at round boundaries (staleness bounded by k iterations); ITL stays
per-token attribution under VirtualClock.  Everything runs on this
container — jit + lax.scan + host Python, no shard_map anywhere.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models.gpt import GPTLM, generate
from distributed_tensorflow_tpu.serving import (
    ContinuousBatcher, Request, RequestQueue, SlotKVCache, VirtualClock)


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
    model = tiny_gpt(hidden=16, layers=1, ffn=32)
    x = jnp.asarray(np.random.default_rng(0).integers(0, 64, (2, 8)),
                    jnp.int32)
    params = model.init(jax.random.key(1), x, train=False)["params"]
    return model, params


def _requests(n=8, seed=7, spread=0.05):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, 64, 3 + (i % 5)).astype(
                        np.int32),
                    max_new_tokens=4 + (i % 6),
                    arrival_s=spread * i)
            for i in range(n)]


def _streams(summary):
    return {r.rid: r.tokens for r in summary["results"]}


def _run(model, params, multi_step, *, slots=3, kv_kw=None, b_kw=None,
         reqs=None):
    kv = SlotKVCache(model, params, slots=slots, **(kv_kw or {}))
    b = ContinuousBatcher(kv, clock=VirtualClock(), multi_step=multi_step,
                          **(b_kw or {}))
    s = b.run(RequestQueue(reqs if reqs is not None else _requests()))
    return kv, s


# ------------------------------------------------------- slot-table layer


def test_advance_multi_matches_k_single_steps(model_params):
    """The fused scan IS k calls of the single-step program: same tokens,
    same lengths, one dispatch.  The acts stack is a contiguous True
    prefix per column (active-at-entry per iteration)."""
    model, params = model_params
    single = SlotKVCache(model, params, slots=3)
    fused = SlotKVCache(model, params, slots=3)
    prompts = _requests(3, seed=2)
    for r in prompts:
        single.insert(r.prompt)
        fused.insert(r.prompt)
    want = np.stack([single.advance() for _ in range(4)])
    d0 = fused.dispatch_count
    toks, acts = fused.advance_multi(4)
    assert fused.dispatch_count == d0 + 1
    np.testing.assert_array_equal(toks, want)
    assert acts.shape == (4, 3) and acts.all()
    np.testing.assert_array_equal(single.lengths, fused.lengths)
    np.testing.assert_array_equal(single.tokens, fused.tokens)
    # program accounting: exactly one fused width compiled, and the
    # single-step table never compiled one
    assert fused.compiled_programs()["decode_multi_widths"] == 1
    assert single.compiled_programs()["decode_multi_widths"] == 0


def test_in_device_deactivation_eos_and_budget(model_params):
    """``set_decode_limits`` arms per-slot EOS/budget; the scan stops
    emitting for a slot the iteration AFTER its budget hits zero or it
    emits EOS — no host round-trip in between.  Deactivated slots land
    ``halted`` and are excluded from the next dispatch mask."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=2)
    prompts = _requests(2, seed=3)
    s0, _ = kv.insert(prompts[0].prompt)
    s1, _ = kv.insert(prompts[1].prompt)
    kv.set_decode_limits(s0, None, 2)      # budget: 2 more tokens
    toks, acts = kv.advance_multi(5)
    assert acts[:, s0].tolist() == [True, True, False, False, False]
    assert acts[:, s1].all()
    assert kv.halted[s0] and not kv.halted[s1]
    # the halted slot is excluded from the next fused round entirely
    toks2, acts2 = kv.advance_multi(2)
    assert not acts2[:, s0].any() and acts2[:, s1].all()
    # EOS: arm the slot's SECOND upcoming greedy token (oracle index 2;
    # index 0 is insert's first token) — the scan emits it at iteration
    # 1 and deactivates the same iteration, in-device
    for seed in range(20):     # untrained logits love to repeat — find a
        p = _requests(1, seed=seed)[0].prompt   # prompt whose stream moves
        nxt = _oracle(model, params, p, 4)
        if int(nxt[1]) != int(nxt[2]) and int(nxt[0]) != int(nxt[2]):
            break
    else:
        pytest.skip("no non-degenerate greedy stream in 20 seeds")
    kv2 = SlotKVCache(model, params, slots=1)
    kv2.insert(p)
    kv2.set_decode_limits(0, int(nxt[2]), 0)   # 0 budget = unlimited
    toks3, acts3 = kv2.advance_multi(4)
    assert acts3[:, 0].tolist() == [True, True, False, False]
    assert int(toks3[1, 0]) == int(nxt[2]) and kv2.halted[0]


def test_pipeline_discipline_guards(model_params):
    """The in-flight contract: single-step ``advance`` refuses while a
    fused round is outstanding, rounds drain strictly FIFO, and
    ``abandon_multi`` drops outstanding rounds so evict() can't race a
    half-drained round."""
    model, params = model_params
    kv = SlotKVCache(model, params, slots=2)
    kv.insert(_requests(1, seed=4)[0].prompt)
    h1 = kv.dispatch_multi(2)
    h2 = kv.dispatch_multi(2)
    assert kv.pending_multi == 2
    with pytest.raises(RuntimeError, match="in flight"):
        kv.advance()
    with pytest.raises(RuntimeError, match="dispatch order"):
        kv.drain_multi(h2)
    kv.drain_multi(h1)
    kv.drain_multi(h2)
    assert kv.pending_multi == 0
    # abandon: outstanding rounds vanish without touching host mirrors
    lens = kv.lengths.copy()
    kv.dispatch_multi(3)
    kv.abandon_multi()
    assert kv.pending_multi == 0
    np.testing.assert_array_equal(kv.lengths, lens)
    kv.evict(0)                      # must not raise after abandon
    # and the table still works: next fused round re-uploads from host
    kv.insert(_requests(1, seed=5)[0].prompt)
    toks, acts = kv.advance_multi(2)
    assert acts[:, 0].all()


def _oracle(model, params, prompt, n_new):
    return np.asarray(generate(model, params, prompt[None, :], n_new,
                               greedy=True))[0]


# ------------------------------------------------------- scheduler layer


def test_bitwise_parity_and_fewer_dispatches(model_params):
    """THE acceptance pin: greedy streams at k in {2, 4, 8} are bitwise
    identical to k=1 AND to the flag-off engine, in strictly fewer host
    dispatches; the flag-off summary key set is untouched."""
    model, params = model_params
    kv0, s0 = _run(model, params, None)
    kv1, s1 = _run(model, params, 1)
    oracle = _streams(s0)
    assert oracle == _streams(s1)
    # flag-off: no multi program compiled, no multi keys in the summary
    assert kv0.compiled_programs()["decode_multi_widths"] == 0
    assert "serve_dispatches" not in s0 and "serve_host_gap_s" not in s0
    assert "serve_multi_step" not in s0
    assert set(s0) == set(s1) - {"serve_multi_step", "serve_dispatches",
                                 "serve_host_gap_s"}
    prev = s1["serve_dispatches"]
    for k in (2, 4, 8):
        kvk, sk = _run(model, params, k)
        assert _streams(sk) == oracle, f"k={k} diverged"
        assert sk["serve_dispatches"] < s1["serve_dispatches"], k
        assert sk["serve_dispatches"] <= prev, k
        assert sk["serve_multi_step"] == k
        assert sk["serve_host_gap_s"] >= 0.0
        assert kvk.compiled_programs()["decode_multi_widths"] == 1
        prev = sk["serve_dispatches"]


def test_itl_is_per_token_under_virtual_clock(model_params):
    """Fused rounds must NOT lump k tokens into one ITL gap: delivery
    attributes each emitted token its own decode-iteration tick — every
    non-first gap is exactly 1.0 under VirtualClock at any k."""
    model, params = model_params
    for k in (1, 4, 8):
        _, s = _run(model, params, k)
        for r in s["results"]:
            assert all(g == 1.0 for g in r.itl_s[1:]), (k, r.rid, r.itl_s)


def test_admission_staleness_bounded_by_k(model_params):
    """Admissions interleave BETWEEN dispatches, so a request arriving
    mid-round waits at most k iterations beyond what it waits at k=1 —
    the bounded-staleness trade the flag documents.  A request arriving
    into an idle engine is admitted immediately at any k."""
    model, params = model_params
    k = 4
    _, s1 = _run(model, params, 1, slots=6,
                 reqs=_requests(6, seed=9, spread=0.6))
    _, sk = _run(model, params, k, slots=6,
                 reqs=_requests(6, seed=9, spread=0.6))
    w1 = {r.rid: r.queue_wait_s for r in s1["results"]}
    wk = {r.rid: r.queue_wait_s for r in sk["results"]}
    for rid in w1:
        assert wk[rid] <= w1[rid] + (k - 1) + 1e-9, (rid, wk[rid], w1[rid])
    # t=0 arrival, idle engine: admitted before the first dispatch
    assert wk[0] == w1[0] == 0.0


def test_multi_step_validation(model_params):
    model, params = model_params
    kv = SlotKVCache(model, params, slots=2)
    with pytest.raises(ValueError, match=">= 1"):
        kv.dispatch_multi(0)
    with pytest.raises(RuntimeError, match="no fused round"):
        kv.drain_multi()


# ------------------------------------------------- composition (slow lane)


SHARED = np.arange(16, dtype=np.int32) % 64


@pytest.mark.slow
@pytest.mark.parametrize("case", ["chunk", "prefix", "int8"])
def test_parity_composes_with_serving_features(model_params, case):
    """Multi-step under chunked prefill, the prefix pool and int8 KV
    storage: same bitwise-parity + fewer-dispatches
    pin — the fused scan runs the SAME per-iteration step the feature
    already compiled, so composition is free by construction."""
    model, params = model_params
    cfg = {
        "chunk": (dict(), dict(prefill_chunk=4), False),
        "prefix": (dict(prefix_cache_blocks=8, prefix_block=8), dict(),
                   True),
        "int8": (dict(kv_dtype="int8"), dict(), False),
    }[case]
    kv_kw, b_kw, prefix = cfg

    def reqs():
        out = _requests()
        if prefix:
            out = [Request(rid=r.rid,
                           prompt=np.concatenate([SHARED, r.prompt]),
                           max_new_tokens=r.max_new_tokens,
                           arrival_s=r.arrival_s) for r in out]
        return out

    _, s_off = _run(model, params, None, kv_kw=kv_kw, b_kw=b_kw,
                    reqs=reqs())
    _, s1 = _run(model, params, 1, kv_kw=kv_kw, b_kw=b_kw, reqs=reqs())
    _, s4 = _run(model, params, 4, kv_kw=kv_kw, b_kw=b_kw, reqs=reqs())
    assert _streams(s_off) == _streams(s1) == _streams(s4)
    assert s4["serve_dispatches"] < s1["serve_dispatches"]


@pytest.mark.slow
def test_spec_decode_reuses_fused_draft_loop(model_params, draft_params):
    """With a draft attached the pipelined loop steps aside (verify owns
    the cadence) but the draft's k-token proposal loop fuses into ONE
    ``advance_multi`` dispatch: tokens stay bitwise identical and total
    dispatches (target + draft) drop vs flag-off — identically at any
    k, because the win is the proposal fusion, not the pipeline."""
    model, params = model_params
    dmodel, dparams = draft_params

    def run(ms, chunk=0):
        kv = SlotKVCache(model, params, slots=3)
        dkv = SlotKVCache(dmodel, dparams, slots=3)
        b = ContinuousBatcher(kv, clock=VirtualClock(), multi_step=ms,
                              draft_kv=dkv, draft_k=3,
                              prefill_chunk=chunk)
        s = b.run(RequestQueue(_requests()))
        return s, kv.dispatch_count + dkv.dispatch_count

    for chunk in (0, 4):
        s_off, d_off = run(None, chunk)
        s1, _ = run(1, chunk)
        s4, _ = run(4, chunk)
        assert _streams(s_off) == _streams(s1) == _streams(s4)
        assert s4["serve_dispatches"] < d_off
        assert s4["serve_dispatches"] == s1["serve_dispatches"]


@pytest.mark.slow
def test_fleet_parity_and_dispatch_aggregation(model_params):
    """ReplicaSet threads ``multi_step`` to every batcher: homogeneous
    and disaggregated fleets keep bitwise parity, the fleet summary
    aggregates ``serve_dispatches``/``serve_host_gap_s`` across
    replicas, and the flag-off fleet summary key set is untouched."""
    from distributed_tensorflow_tpu.serving import (
        ReplicaSet, build_replica_kvs)

    model, params = model_params

    def fleet(ms, **kw):
        rs = ReplicaSet(build_replica_kvs(model, params, kw.pop("n", 2),
                                          2),
                        clock=VirtualClock(), threaded=False,
                        multi_step=ms, **kw)
        return rs.run(_requests(spread=0.5))

    s_off, s1, s4 = fleet(None), fleet(1), fleet(4)
    assert _streams(s_off) == _streams(s1) == _streams(s4)
    assert s4["serve_dispatches"] < s1["serve_dispatches"]
    assert s4["serve_host_gap_s"] >= 0.0
    assert "serve_dispatches" not in s_off
    assert set(s_off) == set(s4) - {"serve_multi_step",
                                    "serve_dispatches",
                                    "serve_host_gap_s"}
    d_off = fleet(None, n=3, roles=["prefill", "decode", "decode"],
                  handoff_s=0.01)
    d4 = fleet(4, n=3, roles=["prefill", "decode", "decode"],
               handoff_s=0.01)
    assert _streams(d_off) == _streams(d4) == _streams(s_off)
    assert d4["serve_dispatches"] > 0
