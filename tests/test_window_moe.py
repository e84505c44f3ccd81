"""``models/window_moe.WindowMoELM`` and ``models/moe.DroplessMoE``'s averaged
shared experts and token blocks against the plain reference
(``tests/window_moe_reference.py``), in training mode and through
``SlotKVCache`` / ``ContinuousBatcher``: rings for the window layers beside
full-length rows in one slot table.

A small size that keeps every mechanism: hidden 32; pattern ``WWWF`` at
window 8 (so a prompt of 21 in its bucket of 32 is wider than the window,
its pads would wrap the ring, and thirty decoded tokens wrap it four
times); 8 query and 2 key/value heads of 8; 16 experts of width 16 at 4 a
token, 4 shared experts of width 8, averaged; vocabulary 96; ``max_len``
64; float32 weights drawn from a seed at std 0.1, gains around 1.

TOL: program and reference both compute in float32 here and differ in the
order of their sums only (the carried softmax against the plain one, the
grouped products against one expert at a time); measured 2e-6 on logits of
size 3.  2e-5 leaves a factor of ten."""

import functools
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import window_moe_reference as ref
from distributed_tensorflow_tpu.models import create_model
from distributed_tensorflow_tpu.models.moe import DroplessMoE
from distributed_tensorflow_tpu.models.window_moe import (
    _ring_rows, window_attention_blocked)
from distributed_tensorflow_tpu.observability.trace import recorder
from distributed_tensorflow_tpu.serving import SlotKVCache
from distributed_tensorflow_tpu.serving.scheduler import (
    ContinuousBatcher, Request)

TOL = 2e-5
H, VOCAB, PATTERN, MAX_LEN, WINDOW = 32, 96, "WWWF", 64, 8
QH, KVH, HD = 8, 2, 8
EXPERTS, TOP_K, FFN, SHARED, SHARED_FFN = 16, 4, 16, 4, 8
SIZES = dict(vocab_size=VOCAB, hidden=H, pattern=PATTERN, window=WINDOW,
             heads=QH, kv_heads=KVH, head_dim=HD, num_experts=EXPERTS,
             experts_per_token=TOP_K, expert_ffn=FFN, shared_experts=SHARED,
             shared_ffn=SHARED_FFN, max_len=MAX_LEN, moe_token_block=16)
DIMS = dict(q_heads=QH, kv_heads=KVH, head_dim=HD, window=WINDOW,
            theta=50000.0, windowed=tuple(k == "W" for k in PATTERN),
            top_k=TOP_K, norm_topk=True, shared=SHARED, logit_scale=1.0,
            eps=1e-5, held=None)
# what a slot keeps (float32 here): keys and values of WINDOW rows in each
# of the three window layers, and a token of the one full layer
RING_BYTES = 3 * 2 * WINDOW * KVH * HD * 4
ROW_BYTES = 2 * KVH * HD * 4


def make_weights(seed: int, std: float = 0.1) -> dict:
    """The reference's weight tree at the small size."""
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(0, std, shape), jnp.float32)
    gain = lambda: 1.0 + draw(H)
    wide = SHARED * SHARED_FFN
    return {"embed": draw(VOCAB, H), "final_norm": gain(), "layers": [
        {"norm": gain(), "q": draw(H, QH * HD), "k": draw(H, KVH * HD),
         "v": draw(H, KVH * HD), "o": draw(QH * HD, H),
         "router": draw(H, EXPERTS), "w_gate": draw(EXPERTS, H, FFN),
         "w_up": draw(EXPERTS, H, FFN), "w_down": draw(EXPERTS, FFN, H),
         "shared_gate": draw(H, wide), "shared_up": draw(H, wide),
         "shared_down": draw(wide, H)} for _ in PATTERN]}


def moe_to_flax(w: dict) -> dict:
    dense = lambda k: {"kernel": k}
    return {"router": w["router"],
            "choice_bias": jnp.zeros(w["router"].shape[1], jnp.float32),
            "w_gate": w["w_gate"], "w_up": w["w_up"], "w_down": w["w_down"],
            "shared": {k: dense(w[f"shared_{k}"])
                       for k in ("gate", "up", "down")}}


def to_flax(w: dict) -> dict:
    tree = {"token_embed": {"embedding": w["embed"]},
            "final_norm": {"scale": w["final_norm"]}}
    for i, lw in enumerate(w["layers"]):
        tree[f"norm_{i}"] = {"scale": lw["norm"]}
        tree[f"attn_{i}"] = {f"{k}_proj": {"kernel": lw[k]} for k in "qkvo"}
        tree[f"ffn_{i}"] = moe_to_flax(lw)
    return tree


@pytest.fixture(scope="module")
def weights():
    return make_weights(0)


@pytest.fixture(scope="module")
def model():
    return create_model("window_moe", **SIZES)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(1).integers(0, VOCAB, 56),
                       jnp.int32)


@pytest.fixture(scope="module")
def program_logits(model, weights, tokens):
    return model.apply({"params": to_flax(weights)}, tokens[None, :40])[0]


def gap(a, b) -> float:
    return float(jnp.max(jnp.abs(a - b)))


@functools.partial(jax.jit, static_argnames=("module", "mode", "fault"))
def _ref_logits(weights, seq, prompt_len, pads, *, module, mode, fault):
    return module.logits_fn(weights, seq, DIMS, mode=mode, fault=fault,
                            prompt_len=prompt_len, pads=pads)


def ref_logits(weights, seq, *, module=ref, mode="f32", fault=None,
               prompt_len=None, pads=0):
    return _ref_logits(weights, seq, prompt_len, pads, module=module,
                       mode=mode, fault=fault)


# ------------------------------------------------- the blocked attention

def dense_attention(q, k, v, scale, window):
    length = q.shape[1]
    s = jnp.einsum("bqhgd,bshd->bhgqs", q, k) * scale
    t, at = jnp.arange(length)[:, None], jnp.arange(length)[None, :]
    mask = at <= t if window is None else (at <= t) & (at > t - window)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhgqs,bshd->bqhgd", p, v)


@pytest.mark.parametrize("length, window, block", [
    (40, 8, 8), (40, 8, 16), (21, 8, 4), (40, None, 8), (37, 5, 8),
    (16, 32, 4), (7, 3, 512), (64, 16, 16)])
def test_the_blocked_attention_is_the_masked_one(length, window, block):
    rng = np.random.default_rng(length)
    q = jnp.asarray(rng.normal(size=(2, length, 2, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, length, 2, 8)), jnp.float32)
            for _ in range(2))
    got = window_attention_blocked(q, k, v, 0.3, window, block)
    assert got.shape == q.shape
    assert gap(got, dense_attention(q, k, v, 0.3, window)) < 1e-5


def test_key_blocks_outside_the_window_are_skipped():
    """The skip is a conditional in the lowered program, and the skipped
    blocks' keys never reach a result: NaNs planted in every key block that
    no query of the last query block sees leave its output finite."""
    length, window, block = 64, 8, 8
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, length, 2, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, length, 2, 8)), jnp.float32)
            for _ in range(2))
    fn = jax.jit(lambda q, k, v: window_attention_blocked(
        q, k, v, 0.3, window, block))
    assert re.search(r"stablehlo\.(case|if)", fn.lower(q, k, v).as_text())
    # the last query block (56..63) sees keys 49..63: blocks 6 and 7
    spoiled = fn(q, k.at[:, :48].set(jnp.nan), v.at[:, :48].set(jnp.nan))
    assert bool(jnp.isfinite(spoiled[:, 56:]).all())
    assert gap(spoiled[:, 56:], fn(q, k, v)[:, 56:]) == 0.0


@pytest.mark.parametrize("ring, length, n", [
    (8, 32, 21), (8, 32, 32), (8, 8, 3), (8, 8, 8), (8, 4, 3), (8, 16, 9),
    (8, 16, 1), (16, 8, 5)])
def test_a_prefill_puts_the_prompts_last_positions_into_the_ring(ring, length,
                                                                 n):
    """Rows hold exactly the positions ``max(0, n - ring) .. n - 1``, each
    at ``p mod ring``; a row no prompt position falls on keeps what it
    held, and no pad is written."""
    held = -jnp.ones((1, ring, 2), jnp.float32)
    new = jnp.arange(length, dtype=jnp.float32)[None, :, None] \
        * jnp.ones((1, length, 2))
    rows = _ring_rows(held, new, jnp.asarray([n]))
    got = np.asarray(jnp.concatenate([rows, held[:, rows.shape[1]:]], 1))
    want = -np.ones(ring)
    for p in range(max(0, n - ring), n):
        want[p % ring] = p
    assert (got[0, :, 0] == want).all() and (got[0, :, 1] == want).all()


# ------------------------------------------------- training-mode forward

@pytest.mark.parametrize("length", [40, 21, 5])
def test_training_mode_logits_match_the_reference(model, weights, tokens,
                                                  length):
    got = model.apply({"params": to_flax(weights)}, tokens[None, :length])[0]
    want = ref_logits(weights, tokens[:length])
    assert got.shape == (length, VOCAB)
    assert gap(got, want) < TOL


def test_the_models_own_init_has_the_mapped_tree(model, weights, tokens):
    own = model.init(jax.random.key(0), tokens[None, :8])["params"]
    shape = lambda tree: jax.tree.map(lambda t: (t.shape, t.dtype), tree)
    assert shape(own) == shape(to_flax(weights))


def test_the_two_copies_of_the_reference_are_one():
    here = Path(__file__).resolve().parent
    assert (here / "window_moe_reference.py").read_text() == (
        here.parent / "benchmarks" / "lib"
        / "window_moe_reference.py").read_text()


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_planted_fault_moves_the_logits(fault, program_logits, weights,
                                          tokens):
    """Each fault, planted in the reference, moves the logits the program
    agrees with: at the prompt's last position on for the two that a
    prefill commits (a prompt of 21 in a bucket of 32, or of 3 under a
    ring of 8 that an occupant left)."""
    prompt_len = 3 if fault == "ring_kept" else 21
    moved = ref_logits(weights, tokens[:40], fault=fault,
                       prompt_len=prompt_len, pads=32 - prompt_len)
    assert gap(program_logits[prompt_len:], moved[prompt_len:]) > 50 * TOL


def test_the_float8_control_moves_the_logits(program_logits, weights, tokens):
    assert gap(program_logits, ref_logits(weights, tokens[:40],
                                          mode="fp8")) > 1000 * TOL


# ------------------------------------------------- through the slot table

def slot_logits(dm, params, cache, tokens, positions, **kw):
    """The served module over a table as it stands: what a program of the
    cache computes, with the logits kept."""
    return dm.apply({"params": params, "cache": cache}, tokens, train=False,
                    positions=positions, mutable=["cache"], **kw)[0]


def serve_alone(kv, prompt, new, slot):
    """``insert`` and ``new`` rounds of ``advance``; the logits the served
    module computes at every position from the prompt's last on (over the
    table the real programs left), and the tokens the programs gave."""
    lp = len(prompt)
    lpad = max(kv.prefill_bucket, 1 << (lp - 1).bit_length())
    blank = jax.tree.map(lambda t: jnp.zeros_like(t[:1]), kv.cache)
    got_slot, first = kv.insert(np.asarray(prompt), slot=slot)
    assert got_slot == slot
    padded = jnp.zeros((1, lpad), jnp.int32).at[0, :lp].set(prompt)
    logits = slot_logits(kv.dm, kv.params, blank, padded,
                         jnp.arange(lpad)[None], prompt_len=jnp.asarray([lp]))
    served, rows = [first], [logits[0, -1]]
    step = jax.jit(functools.partial(slot_logits, kv.dm))
    for _ in range(new):
        logits = step(kv.params, kv.cache, jnp.asarray(kv.tokens)[:, None],
                      jnp.asarray(kv.lengths)[:, None])
        rows.append(logits[slot, -1])
        served.append(int(kv.advance()[slot]))
    return jnp.stack(rows), served


def against_the_reference(weights, prompt, rows, served):
    """The widest gap between the logits served and the reference's full
    forward over the prompt and the served tokens."""
    seq = jnp.concatenate([jnp.asarray(prompt, jnp.int32),
                           jnp.asarray(served[:-1], jnp.int32)])
    return gap(rows, ref_logits(weights, seq)[len(prompt) - 1:])


@pytest.mark.parametrize("lp", [21, 3, 8, 9, 32])
def test_prefill_then_decode_logits_match_the_full_forward(model, weights,
                                                           tokens, lp):
    """A prompt in its bucket (21 in 32: wider than the window, with pads
    that would wrap the ring; 3 in 8; 8 and 9 at the ring's edge; 32 with
    no pad) and thirty decoded tokens, which wrap the ring of 8 four
    times: every position's logits are the reference's full forward."""
    kv = SlotKVCache(model, to_flax(weights), 2, prefill_bucket=8)
    rows, served = serve_alone(kv, tokens[:lp], 30, slot=1)
    assert against_the_reference(weights, tokens[:lp], rows, served) < TOL


def test_a_short_request_takes_the_slot_a_longer_one_left(model, weights,
                                                          tokens):
    """The slot's rings are full of the long occupant's keys, its full
    rows hold 41 of them; the short request that follows (3 tokens: rows 3
    to 7 of every ring stay stale until it writes them) is served as if
    alone, while the other slot's stream goes on beside it."""
    kv = SlotKVCache(model, to_flax(weights), 2, prefill_bucket=8)
    kv.insert(np.asarray(tokens[40:52]), slot=0)       # a neighbour
    serve_alone(kv, tokens[:30], 11, slot=1)
    kv.evict(1)
    for _ in range(3):          # the freed slot sits rounds out
        kv.advance()
    rows, served = serve_alone(kv, tokens[33:36], 12, slot=1)
    assert against_the_reference(weights, tokens[33:36], rows, served) < TOL


@pytest.mark.parametrize("lp", [5, 21])
def test_a_slot_that_sits_rounds_out_goes_on_as_if_it_had_not(model, weights,
                                                              tokens, lp):
    """``advance(only=...)`` leaves a live slot out of three rounds (its
    ring takes a write at its own next row each time): what it serves
    afterwards is what it serves when never left out."""
    def run(pauses):
        kv = SlotKVCache(model, to_flax(weights), 2, prefill_bucket=8)
        kv.insert(np.asarray(tokens[40:47]), slot=0)
        _, first = kv.insert(np.asarray(tokens[:lp]), slot=1)
        out = [first]
        for i in range(12):
            if i in pauses:
                for _ in range(3):
                    kv.advance(only=np.array([True, False]))
            out.append(int(kv.advance()[1]))
        return out

    assert run({2, 9}) == run(set())


def test_a_slot_that_is_not_active_sends_its_token_to_no_expert(model,
                                                                weights):
    """The step is handed ``active``: the live slot's logits do not know
    of it, the other slot's lose their routed part (its stale token is
    sorted past the last group: no expert is read for it)."""
    kv = SlotKVCache(model, to_flax(weights), 2, prefill_bucket=8)
    kv.insert(np.arange(5, dtype=np.int32), slot=0)
    args = (kv.dm, kv.params, kv.cache, jnp.asarray([[7], [9]]),
            jnp.asarray([[5], [0]]))
    both = slot_logits(*args, active=jnp.asarray([True, True]))
    one = slot_logits(*args, active=jnp.asarray([True, False]))
    assert bool((both[0] == one[0]).all())
    assert gap(both[1], one[1]) > 100 * TOL
    assert gap(slot_logits(*args), both) == 0.0


def requests():
    rng = np.random.default_rng(5)
    return [Request(rid=i, prompt=rng.integers(0, VOCAB, lp, dtype=np.int32),
                    max_new_tokens=new, arrival_s=0.0)
            for i, (lp, new) in enumerate(
                [(5, 6), (17, 12), (9, 8), (30, 5), (12, 1), (3, 14)])]


def test_the_batcher_serves_each_request_as_the_reference_would(model,
                                                                weights):
    """Six requests of mixed length through three slots (every slot is
    reused, a short request follows a long one): continuous batching
    changes nobody's tokens, each token is the reference's greedy choice
    given what came before it, and the spans say what went into the rings
    and how many streams had left the window behind."""
    kv = SlotKVCache(model, to_flax(weights), 3, prefill_bucket=8)
    summary = ContinuousBatcher(kv).run(requests())
    together = {r.rid: r.tokens for r in summary["results"]}
    assert [len(together[r.rid]) for r in requests()] == [6, 12, 8, 5, 1, 14]
    for req in requests():
        served = together[req.rid]
        seq = jnp.concatenate([jnp.asarray(req.prompt),
                               jnp.asarray(served[:-1], jnp.int32)])
        logits = ref_logits(weights, seq)[len(req.prompt) - 1:]
        below = jnp.max(logits, -1) - logits[jnp.arange(len(served)),
                                             jnp.asarray(served)]
        assert float(below.max()) < TOL

    window = recorder().records(root="serve_run")
    prefills = {r["rid"]: r["attrs"] for r in window
                if r["name"] == "prefill"}
    assert {rid: a["ring_rows"] for rid, a in prefills.items()} == {
        r.rid: min(len(r.prompt), WINDOW) for r in requests()}
    steps = [r["attrs"] for r in window if r["name"] == "decode_step"]
    assert steps and all(0 <= a["past_window"] <= a["active"] for a in steps)
    assert max(a["past_window"] for a in steps) >= 2
    assert all(1 <= a["experts_touched"] <= EXPERTS for a in steps)
    root = window[0]["attrs"]
    assert root["cache_bytes_per_token"] == ROW_BYTES
    assert root["window_bytes_per_slot"] == RING_BYTES
    assert root["state_bytes_per_slot"] == 0
    # every expert is held: each of the 76 prompt tokens and of the 40
    # served tokens that were fed chooses TOP_K experts in each layer
    assert root["expert_assignments"] == (76 + 40) * len(PATTERN) * TOP_K
    builds = {r["attrs"]["program"] for r in recorder().records()
              if r["name"] == "program_build"}
    assert {"kv_decode_step_routed", "kv_prefill_batched_l8",
            "kv_prefill_batched_l32"} <= builds


def test_analyze_serve_prints_the_ring_counter(model, weights, tmp_path):
    from distributed_tensorflow_tpu.observability.analyze import (
        read_jsonl, render_waterfall_text, serve_waterfall)
    from distributed_tensorflow_tpu.observability.trace import Tracer

    path = tmp_path / "t.jsonl"
    with Tracer(path=path) as tracer:
        kv = SlotKVCache(model, to_flax(weights), 2, prefill_bucket=8)
        ContinuousBatcher(kv, tracer=tracer).run(requests()[:2])
    wf = serve_waterfall(read_jsonl(str(path)))
    assert wf["windows"][0]["window_bytes_per_slot"] == RING_BYTES
    assert (f"{ROW_BYTES} bytes a token, 0 bytes of state a slot, "
            f"{RING_BYTES} of rings" in render_waterfall_text(wf))


# ---------------------------------------------------- counters, five models

SERVED = {
    "gpt": (dict(vocab_size=64, hidden=32, layers=2, heads=4, ffn=64,
                 max_len=64), 2 * 2 * 32 * 4, 0, 0),
    "mla_moe": (dict(vocab_size=64, max_len=64), None, 0, 0),
    "hybrid_ssm": (dict(vocab_size=64, max_len=64), None, None, 0),
    "window_moe": (SIZES, ROW_BYTES, 0, RING_BYTES),
    "jamba": (dict(vocab_size=64, max_len=64), None, None, 0),
}


@pytest.mark.parametrize("name", list(SERVED))
def test_counters_tell_the_three_kinds_of_leaf_apart(name):
    """Full-length rows a token, state a slot, rings a slot: for the three
    older models the counters read what they read before rings were known
    (no ring leaf, ``window_bytes_per_slot`` 0), and the kinds add up to
    the table.  The two counts of the selective-scan kernel (PR 37) stand
    at 0 in a table that has served nothing."""
    sizes, row_bytes, state_bytes, ring_bytes = SERVED[name]
    model = create_model(name, **sizes)
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    kv = SlotKVCache(model, params, 3)
    counts = kv.counters()
    assert set(counts) == {
        "prefill_tokens_computed", "prefill_tokens_padded",
        "expert_assignments", "cache_bytes_per_token",
        "state_bytes_per_slot", "window_bytes_per_slot",
        "ssm_scan_positions", "ssm_scan_tokens"}
    assert counts["ssm_scan_positions"] == counts["ssm_scan_tokens"] == 0
    assert counts["window_bytes_per_slot"] == ring_bytes
    assert bool(kv.contract.slot_rings) == bool(ring_bytes)
    if row_bytes is not None:
        assert counts["cache_bytes_per_token"] == row_bytes
    if state_bytes is not None:
        assert counts["state_bytes_per_slot"] == state_bytes
    rows, state, rings = kv._table_bytes()
    assert rows == 3 * 64 * counts["cache_bytes_per_token"]
    assert kv.kv_bytes_per_slot() == (rows + state + rings) // 3
    full = [leaf for path, leaf in
            jax.tree_util.tree_leaves_with_path(kv.cache)
            if path[-1].key not in kv.contract.slot_state
            and path[-1].key not in kv.contract.slot_rings]
    assert full and all(leaf.shape[1] == 64 for leaf in full)


def test_the_table_holds_rings_beside_full_length_rows(model, weights):
    kv = SlotKVCache(model, to_flax(weights), 4)
    assert kv.contract.slot_rings == {"ring_key": WINDOW,
                                      "ring_value": WINDOW}
    assert {leaf.shape for leaf in jax.tree.leaves(kv.cache)} == {
        (4, WINDOW, KVH, HD), (4, MAX_LEN, KVH, HD)}
    assert kv.kv_bytes_per_slot() == ROW_BYTES * MAX_LEN + RING_BYTES
    half = SlotKVCache(model, to_flax(weights), 4, kv_dtype=jnp.bfloat16)
    assert half.kv_dtype == "bfloat16"
    assert half.counters()["cache_bytes_per_token"] == ROW_BYTES // 2
    assert half.counters()["window_bytes_per_slot"] == RING_BYTES // 2
    # a window that max_len never reaches: the ring is max_len rows
    wide = create_model("window_moe", **{**SIZES, "window": 100})
    assert wide.slot_rings == {"ring_key": MAX_LEN, "ring_value": MAX_LEN}


def test_a_gpt_window_carries_no_ring_attribute():
    sizes = SERVED["gpt"][0]
    model = create_model("gpt", **sizes)
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[
        "params"]
    kv = SlotKVCache(model, params, 2)
    ContinuousBatcher(kv).run([Request(
        rid=0, prompt=np.arange(5, dtype=np.int32), max_new_tokens=4,
        arrival_s=0.0)])
    window = recorder().records(root="serve_run")
    assert window[0]["attrs"]["window_bytes_per_slot"] == 0
    for r in window:
        assert "past_window" not in r["attrs"]
        assert "ring_rows" not in r["attrs"]


# --------------------------------------- what is not built for this model

def _live(model, params):
    kv = SlotKVCache(model, params, 2)
    kv.insert([1, 2, 3], slot=0)
    return kv


@pytest.mark.parametrize("feature, call", [
    ("prefix pool", lambda m, p: SlotKVCache(m, p, 2, prefix_cache_blocks=4)),
    ("int8 storage", lambda m, p: SlotKVCache(m, p, 2, kv_dtype="int8")),
    ("chunked", lambda m, p: SlotKVCache(m, p, 2).begin_insert([1, 2, 3])),
    ("multi-step", lambda m, p: SlotKVCache(m, p, 2).dispatch_multi(2)),
    ("verify", lambda m, p: SlotKVCache(m, p, 2).verify_block(
        np.zeros((2, 2), np.int32))),
    ("commit_block", lambda m, p: _live(m, p).commit_block(0, 1, 5)),
    ("rewind", lambda m, p: _live(m, p).rewind(0, 2, 5)),
    ("handoff", lambda m, p: _live(m, p).extract_handoff(0)),
    ("handoff", lambda m, p: SlotKVCache(m, p, 2).restore_handoff({})),
], ids=["prefix_pool", "int8", "chunk_resume", "multi_step", "verify",
        "commit_block", "rewind", "handoff_out", "handoff_in"])
def test_what_cannot_hold_over_a_ring_says_so(model, weights, feature, call):
    with pytest.raises(NotImplementedError, match=feature) as err:
        call(model, to_flax(weights))
    if feature != "int8 storage":
        assert "rings" in str(err.value)


def test_a_token_block_is_refused_by_the_step_over_a_ring(model, weights):
    kv = SlotKVCache(model, to_flax(weights), 2)
    with pytest.raises(ValueError, match="one token a slot"):
        slot_logits(kv.dm, kv.params, kv.cache, jnp.zeros((2, 3), jnp.int32),
                    jnp.arange(3)[None].repeat(2, 0))


# ----------------------------------------------------- the expert layer

def moe_layer(**kw):
    return DroplessMoE(**{**dict(
        num_experts=EXPERTS, top_k=TOP_K, hidden=FFN,
        shared_hidden=SHARED * SHARED_FFN, shared_experts=SHARED), **kw})


@pytest.fixture(scope="module")
def layer_input():
    return jnp.asarray(np.random.default_rng(3).normal(size=(40, H)),
                       jnp.float32)


def test_the_layer_is_the_references(weights, layer_input):
    w = weights["layers"][0]
    got = moe_layer().apply({"params": moe_to_flax(w)}, layer_input)
    assert gap(got, ref.experts(layer_input, w, DIMS)) < TOL


def test_the_eight_shares_of_the_experts_add_up_to_the_layer(weights,
                                                             layer_input):
    """Eight chips hold two experts of sixteen each and all hold the
    shared experts: their outputs, the shared part counted once, sum to
    the uncut layer's."""
    w = weights["layers"][1]
    whole = moe_layer().apply({"params": moe_to_flax(w)}, layer_input)
    shares = []
    for first in range(0, EXPERTS, 2):
        params = moe_to_flax(w)
        for name in ("w_gate", "w_up", "w_down"):
            params[name] = params[name][first:first + 2]
        shares.append(moe_layer(held=(first, 2)).apply(
            {"params": params}, layer_input))
        assert gap(shares[-1], ref.experts(layer_input, w, DIMS,
                                           held=(first, 2))) < TOL
    # a share with no token routed to it is the shared part alone
    shared = moe_layer(held=(0, 2)).apply(
        {"params": params}, layer_input,
        jnp.zeros(40, bool))
    assert gap(sum(shares) - 7 * shared, whole) < TOL


def test_one_fused_shared_product_is_the_four_averaged(weights, layer_input):
    w = weights["layers"][2]
    fused = moe_layer().apply({"params": moe_to_flax(w)}, layer_input)
    routed = moe_layer(shared_hidden=0).apply(
        {"params": {k: v for k, v in moe_to_flax(w).items()
                    if k != "shared"}}, layer_input)
    four = []
    for j in range(SHARED):
        cols = slice(j * SHARED_FFN, (j + 1) * SHARED_FFN)
        hidden = jax.nn.silu(layer_input @ w["shared_gate"][:, cols]) \
            * (layer_input @ w["shared_up"][:, cols])
        four.append(hidden @ w["shared_down"][cols])
    assert gap(fused, routed + sum(four) / SHARED) < TOL
    summed = moe_layer(shared_experts=1).apply(
        {"params": moe_to_flax(w)}, layer_input)
    assert gap(summed, routed + sum(four)) < TOL


@pytest.mark.parametrize("held", [None, (4, 8)])
def test_token_blocks_give_the_layer_of_one_block(weights, layer_input, held):
    """40 tokens in blocks of 16 (the last one ragged), pads masked: the
    same rows as in one block; a block no smaller than the tokens IS the
    one block, bit for bit."""
    params = moe_to_flax(weights["layers"][3])
    if held:
        for name in ("w_gate", "w_up", "w_down"):
            params[name] = params[name][held[0]:held[0] + held[1]]
    valid = jnp.arange(40) < 33
    run = lambda block: moe_layer(held=held, token_block=block).apply(
        {"params": params}, layer_input, valid,
        mutable=["intermediates"])
    (one, sown), (blocked, sown_b), (big, _) = run(0), run(16), run(40)
    assert gap(one, blocked) < 1e-6
    assert bool((one == big).all())
    choice = lambda s: s["intermediates"]["expert_choice"][0]
    assert choice(sown).shape == (40, TOP_K)
    assert bool((choice(sown) == choice(sown_b)).all())
