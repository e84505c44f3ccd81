"""``ops/sparse_attention`` and ``models/keye.KeyeLM`` against the plain
reference (``tests/keye_reference.py``), in training mode and through
``SlotKVCache`` / ``ContinuousBatcher``: keys, values and the indexer's keys a
slot, a step that attends to the rows its indexer selects, softmax-routed
experts.

A small size that keeps every mechanism: hidden 32, 4 query heads on 2
key/value heads of 8, an indexer of 4 heads of 8 (4 dims rotated), 8 experts
of 16 at 2 a token, vocabulary 97, two layers, and an index top-k of 6: every
prompt here is longer, so the selection drops rows.  float32 weights drawn
from a seed at std 0.2, gains around 1.  Off the TPU the model runs the
kernels' plain forms; the kernels themselves run in the Pallas interpreter
here, on inputs whose scores are small integers (many ties, summed exactly
in any order), so that the selection must be the reference's row for row.

TOL: program and reference both compute in float32 here and differ in the
order of their sums only; measured 2e-6 on logits of size 5.  2e-5 leaves a
factor of ten, and the least planted fault moves a logit by 1.7."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import keye_reference as ref
from benchmarks.drivers import serve_keye
from distributed_tensorflow_tpu.models import create_model
from distributed_tensorflow_tpu.models.moe import DroplessMoE
from distributed_tensorflow_tpu.observability.trace import recorder
from distributed_tensorflow_tpu.ops import sparse_attention as dsa
from distributed_tensorflow_tpu.serving import SlotKVCache
from distributed_tensorflow_tpu.serving.scheduler import (
    ContinuousBatcher, Request)

TOL = 2e-5
TOP_K, MAX_LEN, LAYERS = 6, 64, 2
SIZES = dict(vocab_size=97, hidden=32, layers=LAYERS, heads=4, kv_heads=2,
             head_dim=8, index_heads=4, index_head_dim=8, index_rope_dim=4,
             index_topk=TOP_K, num_experts=8, experts_per_token=2,
             expert_ffn=16, max_len=MAX_LEN)
DIMS = dict(q_heads=4, kv_heads=2, head_dim=8, index_heads=4, index_dim=8,
            index_rope=4, top_k=TOP_K, per_token=2, norm_topk=True,
            theta=1e7, eps=1e-6)


def make_weights(seed: int, std: float = 0.2) -> dict:
    """The reference's weight tree at the small size."""
    rng = np.random.default_rng(seed)

    def n(*shape, offset=0.0):
        return jnp.asarray(offset + std * rng.standard_normal(shape),
                           jnp.float32)

    layer = lambda: {
        "norm": n(32, offset=1), "ffn_norm": n(32, offset=1),
        "q": n(32, 32), "k": n(32, 16), "v": n(32, 16), "o": n(32, 32),
        "q_norm": n(8, offset=1), "k_norm": n(8, offset=1),
        "index_q": n(32, 32), "index_k": n(32, 8),
        "index_k_norm": n(8, offset=1), "index_k_bias": n(8),
        "index_weights": n(32, 4), "router": n(32, 8),
        "w_gate": n(8, 32, 16), "w_up": n(8, 32, 16), "w_down": n(8, 16, 32)}
    return {"embed": n(97, 32), "final_norm": n(32, offset=1),
            "head": n(32, 97), "layers": [layer() for _ in range(LAYERS)]}


def to_flax(w: dict) -> dict:
    from benchmarks.drivers import keye_tree

    return keye_tree.to_flax(w)


@pytest.fixture(scope="module")
def weights():
    return make_weights(0)


@pytest.fixture(scope="module")
def model():
    return create_model("keye", **SIZES)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(1).integers(0, 97, 40),
                       jnp.int32)


def test_the_two_copies_of_the_reference_are_one():
    from pathlib import Path

    here = Path(__file__).resolve().parent
    assert (here / "keye_reference.py").read_text() == (
        here.parent / "benchmarks" / "lib" / "keye_reference.py").read_text()


def test_training_mode_logits_match_the_reference(model, weights, tokens):
    got = jax.jit(model.apply)({"params": to_flax(weights)}, tokens[None])[0]
    want = ref.logits_fn(weights, tokens, DIMS)
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_the_models_own_init_has_the_mapped_tree(model, weights, tokens):
    own = jax.eval_shape(lambda: model.init(jax.random.key(0),
                                            tokens[None]))["params"]
    mapped = to_flax(weights)
    assert jax.tree.structure(own) == jax.tree.structure(mapped)
    assert all(a.shape == b.shape for a, b in zip(jax.tree.leaves(own),
                                                  jax.tree.leaves(mapped)))
    assert "choice_bias" not in own["ffn_0"]     # a softmax router has none


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_planted_fault_moves_the_logits(fault, weights, tokens):
    want = ref.logits_fn(weights, tokens, DIMS)
    got = ref.logits_fn(weights, tokens, DIMS, fault=fault)
    assert float(jnp.max(jnp.abs(got - want))) > 0.1


def _served(model, weights, tokens, lp, steps, slots=3, slot=1,
            before=None):
    """Prefill ``tokens[:lp]`` into ``slot`` of a fresh table (after a
    longer ``before`` where given), then ``steps`` teacher-forced steps:
    the logits of the prompt's last position and of each step."""
    kv = SlotKVCache(model, to_flax(weights), slots, prefill_bucket=8)
    dm, params = kv.dm, kv.params
    if before is not None:      # a longer occupant leaves its rows
        kv.insert(before, slot=slot)
        for _ in range(5):
            kv.advance()
        kv.evict(slot)
    kv.insert(np.asarray(tokens[:lp]), slot=slot)
    sub = jax.tree.map(lambda t: t[slot:slot + 1], kv.cache)
    bucket = 1 << (lp - 1).bit_length()
    padded = jnp.zeros((1, max(bucket, 8)), jnp.int32).at[0, :lp].set(
        tokens[:lp])
    logits, upd = jax.jit(lambda c, ids: dm.apply(
        {"params": params, "cache": c}, ids, train=False,
        positions=jnp.arange(ids.shape[1])[None],
        prompt_len=jnp.array([lp]), mutable=["cache"]))(sub, padded)
    out = [logits[0, -1]]
    cache = jax.tree.map(lambda full, s: full.at[slot:slot + 1].set(s),
                         kv.cache, upd["cache"])
    active = jnp.zeros(slots, bool).at[slot].set(True)
    step = jax.jit(lambda c, tok, pos: dm.apply(
        {"params": params, "cache": c}, tok, train=False, positions=pos,
        active=active, mutable=["cache"]))
    for i in range(steps):
        tok = jnp.zeros((slots, 1), jnp.int32).at[slot, 0].set(tokens[lp + i])
        pos = jnp.full((slots, 1), 0, jnp.int32).at[slot, 0].set(lp + i)
        logits, upd = step(cache, tok, pos)
        cache = upd["cache"]
        out.append(logits[slot, -1])
    return jnp.stack(out)


def test_prefill_then_decode_logits_match_the_full_forward(model, weights,
                                                           tokens):
    lp, steps = 21, 12
    got = _served(model, weights, tokens, lp, steps)
    want = ref.logits_fn(weights, tokens, DIMS)[lp - 1:lp + steps]
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_a_slot_reused_after_a_longer_occupant_serves_as_a_fresh_one(
        model, weights, tokens):
    """The longer occupant's rows lie past the new length: the step's
    selection reads the slot's rows to its position only."""
    lp, steps = 13, 8
    longer = np.asarray(jax.random.randint(jax.random.key(5), (50,), 0, 97))
    got = _served(model, weights, tokens, lp, steps, before=longer)
    want = ref.logits_fn(weights, tokens, DIMS)[lp - 1:lp + steps]
    assert float(jnp.max(jnp.abs(got - want))) < TOL


@pytest.mark.parametrize("fault", [None, *serve_keye.PROGRAM_FAULTS])
def test_what_the_served_attention_left_holds_it_to_its_rule(
        fault, model, weights, tokens):
    """A slot's ``seen_*`` state, read back with its rows after a prefill
    (in a slot a longer occupant left) and 6 steps: from the program's own
    query, K/V and index rows, its output at the step and at the prompt's
    last position is attention over the selection its own index query makes
    (to float32 rounding), and the rows the step recorded are that
    selection.  A fault planted in the program breaks one of the two."""
    slot, lp, steps = 1, 21, 6
    longer = np.asarray(jax.random.randint(jax.random.key(5), (50,), 0, 97))
    with serve_keye.planted(fault) if fault else contextlib.nullcontext():
        kv = SlotKVCache(model, to_flax(weights), 3, prefill_bucket=8)
        kv.insert(longer, slot=slot)
        for _ in range(5):
            kv.advance()
        kv.evict(slot)
        kv.insert(np.asarray(tokens[:lp]), slot=slot)
        for _ in range(steps):
            kv.advance()
    n, at = lp + steps, jnp.array([lp + steps - 1, lp - 1])
    worst, same = 0.0, True
    for i in range(LAYERS):
        c = jax.tree.map(lambda t: t[slot], kv.cache[f"attn_{i}"])
        mask, gap = serve_keye.witness(
            {k: c[k] for k in serve_keye.SEEN[:4]}, c["cached_key"][:n],
            c["cached_value"][:n], c["index_key"][:, :n], at, top_k=TOP_K,
            kv_heads=2)
        worst = max(worst, float(jnp.max(gap)))
        rows = np.sort(np.asarray(c["seen_rows"]))
        same &= rows.tolist() == np.flatnonzero(mask[0]).tolist()
    if fault is None:
        assert worst < 1e-5 and same
    else:
        assert worst > 0.05 or not same, (worst, same)


def test_the_batcher_serves_each_request_as_the_reference(model, weights,
                                                          tokens):
    kv = SlotKVCache(model, to_flax(weights), 2, prefill_bucket=8)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(0, 97, lp).astype(np.int32),
                    max_new_tokens=new, arrival_s=0.0)
            for i, (lp, new) in enumerate([(20, 9), (9, 5), (33, 7)])]
    summary = ContinuousBatcher(kv).run(reqs)
    assert summary["completed"] == 3
    for r in summary["results"]:
        seq = np.concatenate([reqs[r.rid].prompt, r.tokens[:-1]])
        logits = ref.logits_fn(weights, jnp.asarray(seq), DIMS)
        assert np.array_equal(np.asarray(jnp.argmax(
            logits[r.prompt_len - 1:], -1)), r.tokens)
    spans = recorder().records(root="serve_run")
    prefill = [s["attrs"] for s in spans if s["name"] == "prefill"]
    assert {p["prompt_len"]: p["index_pairs"] for p in prefill} == {
        lp: lp * (lp + 1) // 2 * LAYERS for lp in (20, 9, 33)}
    assert {p["prompt_len"]: p["attended_pairs"] for p in prefill} == {
        lp: (21 + (lp - TOP_K) * TOP_K) * LAYERS for lp in (20, 9, 33)}
    steps = [s["attrs"] for s in spans if s["name"] == "decode_step"]
    assert steps and all(
        s["attended_rows"] <= min(s["index_rows"], TOP_K * LAYERS
                                  * s["active"]) for s in steps)


def test_the_counters_rules():
    m = create_model("keye", **SIZES)
    got = m.slot_index_rows(np.array([3, 10, 40]),
                            np.array([True, False, True]))
    assert got == {"index_rows": (4 + 41) * LAYERS,
                   "attended_rows": (4 + TOP_K) * LAYERS}
    assert m.prefill_index_pairs(4) == {"index_pairs": 10 * LAYERS,
                                        "attended_pairs": 10 * LAYERS}


@pytest.mark.parametrize("feature", ["prefix", "int8"])
def test_what_is_not_built_says_so(model, weights, feature):
    kw = {"prefix": dict(prefix_cache_blocks=4),
          "int8": dict(kv_dtype=jnp.int8)}[feature]
    with pytest.raises(NotImplementedError):
        SlotKVCache(model, to_flax(weights), 2, **kw)


# ------------------------------------------------------------- the kernels

def _index_inputs(rng, t, hi=4, di=8):
    """Index queries and keys of small integers and head weights of powers
    of two: every score is exact in any order of its sums, and many tie."""
    qi = jnp.asarray(rng.integers(-2, 3, (1, t, hi, di)), jnp.float32)
    ki = jnp.asarray(rng.integers(-2, 3, (1, t, di)), jnp.float32)
    w = jnp.asarray(rng.choice([-1.0, 0.5, 1.0, 2.0], (1, t, hi)),
                    jnp.float32)
    return qi, w, ki


@pytest.mark.parametrize("t, k", [(64, 8), (256, 40), (384, 150), (100, 30),
                                  (640, 200), (1000, 64)])
def test_the_prefill_kernel_selects_as_the_reference(t, k):
    """Through the interpreter, at one block (of 64, and of 100: not a
    multiple of the counts' 128 lanes), at several query blocks of 128
    over tiles of 512 (1,000: the last block partly padding) and of 128
    (640: rows up to 199 take every key): the selection (ties to the lower
    position) is the reference's row for row; with q = k = 0 each output is
    the mean of the selected values, so a row selected otherwise shows."""
    rng = np.random.default_rng(t)
    qi, w, ki = _index_inputs(rng, t)
    scores = ref.index_scores(qi[0], w[0], ki[0], {"index_dim": 1})
    assert len(np.unique(np.asarray(scores))) < scores.size // 4   # ties
    q = jnp.zeros((1, t, 4, 8), jnp.float32)
    k_ = jnp.zeros((1, t, 2, 8), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, t, 2, 8)), jnp.float32)
    got = dsa.dsa_prefill(q, k_, v, qi, w, ki, top_k=k, scale=0.3,
                          interpret=True)
    mask = ref.select(scores, jnp.arange(t), k)
    want = (mask.astype(jnp.float32) @ v[0].reshape(t, -1)
            / mask.sum(1, keepdims=True)).reshape(t, 2, 1, 8)
    got = got[0].reshape(t, 2, 2, 8)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def _selection_scores(kind, t, rng):
    """Scores (t, t) float32 for ``test_the_kernels_selection_...``."""
    if kind == "straddle":
        # 100 keys above 1.0, then 40 ties at 1.0 over positions 500-539
        # (the boundary of the first two tiles of 512), the rest below
        scores = -1.0 - rng.permutation(t * t).reshape(t, t) / (t * t)
        scores[:, :100] = 2.0
        scores[:, 500:540] = 1.0
        return scores
    if kind == "zeros":
        return rng.choice([0.0, -0.0, -0.5, -2.0, 0.25, 3.0], (t, t))
    if kind == "ties":
        return rng.choice([0.0, 1.0, 2.0], (t, t), p=[0.2, 0.75, 0.05])
    return rng.standard_normal((t, t))


def _kernel_selection(scores, top_k):
    """The prefill kernel's selection (``dsa._select``) over ``scores``,
    query block by query block in the interpreter, as a mask (t, t); and
    each row's ``cut``."""
    t = scores.shape[0]
    bq, bk, tp = dsa._blocks(t)
    nq, nk = tp // bq, tp // bk
    keys = dsa.order_key(jnp.pad(scores, ((0, tp - t), (0, tp - t))))
    tiles = keys.reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3).reshape(
        nq * nk, bq, bk)

    def kernel(keys_ref, thr_ref, cut_ref, flag):
        i = pl.program_id(0)
        t_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        dsa._select(keys_ref, thr_ref, cut_ref, flag, t_pos,
                    (i * bq + bq - 1) // bk, top_k)

    thr, cut = pl.pallas_call(
        kernel, grid=(nq,),
        in_specs=[pl.BlockSpec((nk, bq, bk), lambda i: (i, 0, 0))],
        out_specs=[pl.BlockSpec((bq, 1), lambda i: (i, 0))] * 2,
        out_shape=[jax.ShapeDtypeStruct((tp, 1), jnp.int32)] * 2,
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=True)(tiles)
    s, row = jnp.arange(tp)[None, :], jnp.arange(tp)[:, None]
    mask = ((keys > thr) | ((keys == thr) & (s <= cut))) & (s <= row)
    return mask[:t, :t], cut[:t, 0]


@pytest.mark.parametrize("kind, t, k", [
    ("straddle", 1024, 120),    # ties at the threshold across two tiles
    ("zeros", 640, 40),         # +0 and -0 as one value, negatives
    ("ties", 384, 200),         # tie-halving; rows t + 1 <= top_k
    ("normal", 1000, 64),       # 512-key tiles, last block partly padding
    ("normal", 100, 30),        # one block, not a multiple of 128 lanes
    ("ties", 72, 16),           # tie-halving in one block
])
def test_the_kernels_selection_is_the_references_row_for_row(kind, t, k):
    """``dsa._select`` alone, on scores chosen to reach each of its paths:
    the mask its ``thr`` and ``cut`` make is ``ref.select``'s in every row;
    where ties at the threshold outnumber what the top_k still needs, the
    tie-halving loop ran (a cut other than -1 and INT_MAX)."""
    rng = np.random.default_rng(t + k)
    scores = jnp.asarray(_selection_scores(kind, t, rng), jnp.float32)
    got, cut = _kernel_selection(scores, k)
    want = ref.select(scores, jnp.arange(t), k)
    assert bool(jnp.all(got == want))
    assert got.sum(1).tolist() == jnp.minimum(jnp.arange(t) + 1, k).tolist()
    halved = (cut != -1) & (cut != dsa.INT_MAX)
    if kind in ("straddle", "ties"):
        assert bool(jnp.any(halved))
    if kind == "straddle":      # 20 of the 40 ties: the 20th is at 519
        assert set(np.asarray(cut[540:]).tolist()) == {519}


@pytest.mark.parametrize("t, hq, hk", [(256, 4, 2), (256, 8, 1), (640, 8, 1),
                                      (1000, 16, 2), (100, 4, 4)])
def test_the_prefill_kernel_is_its_plain_form_on_random_inputs(t, hq, hk):
    """Phase 1 attends a K/V head's whole group of queries per key tile:
    groups of 2, 8 and 1, key tiles of 512 and of 128 (640), a last block
    partly padding (1,000) and one block of 100 positions."""
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.standard_normal((1, t, hq, 8)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, t, hk, 8)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, t, hk, 8)), jnp.float32)
    qi, w, ki = _index_inputs(rng, t)
    got = dsa.dsa_prefill(q, k, v, qi, w, ki, top_k=24, scale=0.3,
                          interpret=True)
    want = dsa.prefill_plain(q, k, v, qi, w, ki, top_k=24, scale=0.3)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def _step_index_inputs(rng, slots, max_len, hi=4, di=8):
    qi = jnp.asarray(rng.integers(-2, 3, (slots, hi, di)), jnp.float32)
    w = jnp.asarray(rng.choice([-1.0, 1.0, 2.0], (slots, hi)), jnp.float32)
    rows = jnp.asarray(rng.integers(-2, 3, (slots, di, max_len)),
                       jnp.float32)
    new = jnp.asarray(rng.integers(3, 5, (slots, di)), jnp.float32)
    return qi, w, rows, new


def test_the_step_kernels_select_and_attend_as_the_reference():
    """Slots at a position below ``top_k`` (fewer rows than it), in the
    middle, at ``max_len - 1`` and past the table (a slot frozen there
    reads to its end and writes nothing); the scores past a slot's position
    are not read, and the new index key lands at the slot's position only,
    scored with it."""
    rng = np.random.default_rng(4)
    slots, max_len, k = 4, 512, 16
    qi, w, rows, new = _step_index_inputs(rng, slots, max_len)
    pos = jnp.array([5, 300, max_len - 1, max_len])
    got, table = dsa.dsa_step_index(qi, w, rows, new, pos, interpret=True)
    want, want_table = dsa.step_index_plain(qi, w, rows, new, pos, pos)
    seen = jnp.arange(max_len)[None] <= pos[:, None]
    assert bool(jnp.all(jnp.where(seen, got == want, True)))
    assert bool(jnp.all(table == want_table))
    written = np.asarray(jnp.any(table != rows, axis=1))
    assert [np.flatnonzero(r).tolist() for r in written] == [
        [5], [300], [max_len - 1], []]
    idx, count = dsa.step_select(got, pos, k)
    assert count.tolist() == [6, k, k, k]
    mask = ref.select(want, jnp.minimum(pos, max_len - 1), k)
    picked = jnp.zeros_like(mask).at[jnp.arange(slots)[:, None], idx].set(
        jnp.arange(k)[None] < count[:, None])
    assert bool(jnp.all(picked == mask))
    q = jnp.asarray(rng.standard_normal((slots, 4, 8)), jnp.float32)
    keys, values = (jnp.asarray(rng.standard_normal((slots, k, 16)),
                                jnp.float32) for _ in range(2))
    got = dsa.dsa_step_attend(q, keys, values, count, scale=0.3,
                              interpret=True)
    want = dsa.step_attend_plain(q, keys, values, count, scale=0.3)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


@pytest.mark.parametrize("max_len", [200, 333])
def test_the_step_scoring_at_a_table_of_no_whole_tiles(max_len, monkeypatch):
    """A table whose length is not a multiple of the scoring's tile: the
    last tile's rows past the table are scored as no position, the rows in
    it are, and a slot at the last position writes its key there; the
    write position of a slot that sits the round out (past the table)
    writes nothing."""
    monkeypatch.setattr(dsa, "STEP_BLOCK", 64)
    rng = np.random.default_rng(max_len)
    slots = 4
    qi, w, rows, new = _step_index_inputs(rng, slots, max_len)
    pos = jnp.array([3, 130, max_len - 1, max_len - 2])
    put = pos.at[3].set(max_len)
    got, table = dsa.dsa_step_index(qi, w, rows, new, pos, put,
                                    interpret=True)
    want, want_table = dsa.step_index_plain(qi, w, rows, new, pos, put)
    assert got.shape == (slots, max_len) and table.shape == rows.shape
    seen = jnp.arange(max_len)[None] <= pos[:, None]
    assert bool(jnp.all(jnp.where(seen, got == want, True)))
    assert bool(jnp.all(table == want_table))
    assert bool(jnp.all(table[3] == rows[3]))


def test_the_model_through_the_kernels_is_the_model_through_the_plain_forms(
        model, weights, tokens, monkeypatch):
    """The served path with every kernel interpreted: prefill and steps give
    the logits of the plain forms."""
    want = _served(model, weights, tokens, 21, 4)
    for name in ("dsa_prefill", "dsa_step_index", "dsa_step_attend"):
        fn = getattr(dsa, name)
        monkeypatch.setattr(dsa, name, lambda *a, _fn=fn, **kw: _fn(
            *a, **{**kw, "interpret": True}))
    got = _served(model, weights, tokens, 21, 4)
    assert float(jnp.max(jnp.abs(got - want))) < TOL


# ------------------------------------------------------ softmax experts

@pytest.mark.parametrize("norm_topk", [True, False])
def test_softmax_experts_are_a_plain_loop(norm_topk):
    rng = np.random.default_rng(2)
    e, k, d, f, t = 8, 3, 16, 12, 10
    layer = DroplessMoE(num_experts=e, top_k=k, hidden=f, norm_topk=norm_topk,
                        scoring="softmax", token_block=4)
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    params = jax.jit(layer.init)(jax.random.key(0), x)["params"]
    assert set(params) == {"router", "w_gate", "w_up", "w_down"}
    got = jax.jit(layer.apply)({"params": params}, x)
    x, p = np.asarray(x, np.float64), jax.tree.map(
        lambda a: np.asarray(a, np.float64), params)
    want = np.zeros((t, d))
    for i in range(t):
        logit = x[i] @ p["router"]
        prob = np.exp(logit - logit.max())
        prob /= prob.sum()
        top = np.argsort(-prob, kind="stable")[:k]
        weight = prob[top] / (prob[top].sum() if norm_topk else 1.0)
        for wt, j in zip(weight, top):
            gate = x[i] @ p["w_gate"][j]
            h = gate / (1.0 + np.exp(-gate)) * (x[i] @ p["w_up"][j])
            want[i] += wt * (h @ p["w_down"][j])
    assert np.max(np.abs(np.asarray(got) - want)) < 1e-5


@pytest.mark.parametrize("seed", [0, 1])
def test_the_references_selection_is_lax_top_k(seed):
    """The reference finds its selection by counting; on scores with many
    ties and many zeros of either sign it is the rows ``lax.top_k`` returns
    (ties to the lower position), every row ``s <= t`` while ``t < top_k``."""
    rng = np.random.default_rng(seed)
    r, p, k = 40, 300, 24
    scores = jnp.asarray(rng.integers(-3, 4, (r, p)) * rng.choice(
        [0.5, -0.0, 1.0], (r, p)), jnp.float32)
    at = jnp.asarray(rng.integers(0, p, r))
    got = ref.select(scores, at, k)
    valid = jnp.arange(p)[None] <= at[:, None]
    masked = jnp.where(valid, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    _, idx = jax.lax.top_k(masked, k)
    want = jnp.zeros((r, p), bool).at[jnp.arange(r)[:, None], idx].set(
        True) & valid
    assert bool(jnp.all(got == want))
    assert got.sum(1).tolist() == jnp.minimum(at + 1, k).tolist()
