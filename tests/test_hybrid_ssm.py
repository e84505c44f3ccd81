"""``models/hybrid_ssm.HybridSSMLM`` and ``models/moe.DroplessMoE``'s latent
squared-ReLU form against the plain reference
(``tests/hybrid_ssm_reference.py``), in training mode and through
``SlotKVCache`` / ``ContinuousBatcher``: recurrent state beside keys and
values in one slot table.

A small size that keeps every mechanism: hidden 64; pattern ``MEM*E`` (two
state-space layers of 8 heads x 16, 2 groups, state 16, 4 taps, chunk 8;
one attention layer of 4 query and 2 key/value heads of 16; two expert
layers of 16 experts, 4 a token, in a latent of 32, a shared expert of 48,
factor 5); vocabulary 512; float32 weights drawn from a seed at std 0.1
with a choice bias of std 0.1, gains around 1, dt_bias in [-3, 0].

TOL: program and reference both compute in float32 here and differ in the
order of their sums only (the chunked scan against the plain one, the
grouped products against one expert at a time); measured 2e-6 on logits of
size 3.  2e-5 leaves a factor of ten and is forty thousand times under the
least planted fault (0.78)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_ssm_reference as ref
from distributed_tensorflow_tpu.models import create_model
from distributed_tensorflow_tpu.models.hybrid_ssm import (
    ssd_chunked, ssd_step)
from distributed_tensorflow_tpu.models.moe import DroplessMoE
from distributed_tensorflow_tpu.observability.trace import recorder
from distributed_tensorflow_tpu.serving import SlotKVCache
from distributed_tensorflow_tpu.serving.scheduler import (
    ContinuousBatcher, Request)

TOL = 2e-5
H, VOCAB, PATTERN, MAX_LEN = 64, 512, "MEM*E", 64
SH, SP, SG, SN, TAPS, CHUNK = 8, 16, 2, 16, 4, 8
QH, KVH, HD = 4, 2, 16
EXPERTS, TOP_K, FFN, LATENT, SHARED, SCALE = 16, 4, 24, 32, 48, 5.0
DI, WIDTH = SH * SP, SH * SP + 2 * SG * SN
SIZES = dict(vocab_size=VOCAB, hidden=H, pattern=PATTERN, ssm_heads=SH,
             ssm_head_dim=SP, ssm_groups=SG, ssm_state=SN, conv_kernel=TAPS,
             chunk=CHUNK, heads=QH, kv_heads=KVH, head_dim=HD,
             num_experts=EXPERTS, experts_per_token=TOP_K, expert_ffn=FFN,
             expert_latent=LATENT, shared_ffn=SHARED, routed_scale=SCALE,
             max_len=MAX_LEN)
DIMS = dict(ssm_heads=SH, ssm_head_dim=SP, groups=SG, state=SN, q_heads=QH,
            kv_heads=KVH, head_dim=HD, top_k=TOP_K, routed_scale=SCALE,
            norm_topk=True, eps=1e-5, held=None)
# what a slot keeps: a state and a tail a state-space layer, keys and
# values a token of the one attention layer (float32 here)
STATE_BYTES = 2 * (SH * SP * SN * 4 + (TAPS - 1) * WIDTH * 4)
ROW_BYTES = 2 * KVH * HD * 4


def make_weights(seed: int, std: float = 0.1) -> dict:
    """The reference's weight tree at the small size."""
    rng = np.random.default_rng(seed)

    def n(*shape):
        return jnp.asarray(std * rng.standard_normal(shape), jnp.float32)

    def one(*shape):
        return 1.0 + n(*shape)

    layers = []
    for kind in PATTERN:
        w = {"norm": one(H)}
        if kind == "M":
            w.update(in_proj=n(H, DI + WIDTH + SH), conv_w=3 * n(TAPS, WIDTH),
                     conv_b=n(WIDTH),
                     dt_bias=jnp.asarray(rng.uniform(-3, 0, SH), jnp.float32),
                     a_log=jnp.asarray(np.log(rng.uniform(1, 4, SH)),
                                       jnp.float32),
                     d=one(SH), gate_norm=one(DI), out_proj=n(DI, H))
        elif kind == "*":
            w.update(q=n(H, QH * HD), k=n(H, KVH * HD), v=n(H, KVH * HD),
                     o=n(QH * HD, H))
        else:
            w.update(router=n(H, EXPERTS), choice_bias=n(EXPERTS),
                     latent_down=n(H, LATENT), latent_up=n(LATENT, H),
                     w_up=n(EXPERTS, LATENT, FFN),
                     w_down=n(EXPERTS, FFN, LATENT), shared_up=n(H, SHARED),
                     shared_down=n(SHARED, H))
        layers.append(w)
    return {"embed": n(VOCAB, H), "head": n(H, VOCAB), "final_norm": one(H),
            "layers": layers}


def moe_to_flax(w: dict) -> dict:
    return {"router": w["router"], "choice_bias": w["choice_bias"],
            "latent_down": {"kernel": w["latent_down"]},
            "latent_up": {"kernel": w["latent_up"]},
            "w_up": w["w_up"], "w_down": w["w_down"],
            "shared": {"up": {"kernel": w["shared_up"]},
                       "down": {"kernel": w["shared_down"]}}}


def to_flax(w: dict) -> dict:
    """The reference's weights as ``HybridSSMLM``'s parameter tree."""
    tree = {"token_embed": {"embedding": w["embed"]},
            "lm_head": {"kernel": w["head"]},
            "final_norm": {"scale": w["final_norm"]}}
    for i, lw in enumerate(w["layers"]):
        tree[f"norm_{i}"] = {"scale": lw["norm"]}
        if "in_proj" in lw:
            mixer = {"in_proj": {"kernel": lw["in_proj"]},
                     "conv_weight": lw["conv_w"], "conv_bias": lw["conv_b"],
                     "dt_bias": lw["dt_bias"], "A_log": lw["a_log"],
                     "D": lw["d"], "norm": lw["gate_norm"],
                     "out_proj": {"kernel": lw["out_proj"]}}
        elif "router" in lw:
            mixer = moe_to_flax(lw)
        else:
            mixer = {f"{k}_proj": {"kernel": lw[k]} for k in "qkvo"}
        tree[f"mixer_{i}"] = mixer
    return tree


@pytest.fixture(scope="module")
def weights():
    return make_weights(0)


@pytest.fixture(scope="module")
def model():
    return create_model("hybrid_ssm", **SIZES)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(1).integers(0, VOCAB, 40),
                       jnp.int32)


@pytest.fixture(scope="module")
def program_logits(model, weights, tokens):
    return jax.jit(model.apply)({"params": to_flax(weights)}, tokens[None])[0]


def gap(a, b) -> float:
    return float(jnp.max(jnp.abs(a - b)))


@functools.partial(jax.jit, static_argnames=("module", "mode", "fault"))
def _ref_logits(weights, seq, prompt_len, pads, *, module, mode, fault):
    return module.logits_fn(weights, seq, DIMS, mode=mode, fault=fault,
                            prompt_len=prompt_len, pads=pads)


def ref_logits(weights, seq, *, module=ref, mode="f32", fault=None,
               prompt_len=0, pads=0):
    """The reference's logits over ``seq``, computed over ``seq`` padded to
    48 (it is causal: the pads after the end move nothing before it), so
    that one compiled program serves every length."""
    padded = jnp.zeros((48,), jnp.int32).at[:len(seq)].set(seq)
    return _ref_logits(weights, padded, prompt_len, pads, module=module,
                       mode=mode, fault=fault)[:len(seq)]


# ------------------------------------------- the recurrence's three forms

def recurrence_inputs(length: int, batch: int = 2):
    rng = np.random.default_rng(length)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return (f(batch, length, SH, SP),
            jnp.asarray(rng.uniform(0.01, 0.5, (batch, length, SH)),
                        jnp.float32),
            -jnp.asarray(rng.uniform(1, 4, SH), jnp.float32),
            f(batch, length, SG, SN), f(batch, length, SG, SN))


@pytest.mark.parametrize("length", [1, 5, 8, 16, 21, 40])
def test_chunked_scan_is_the_one_token_recurrence(length):
    """``ssd_chunked`` (chunk 8), a loop of ``ssd_step`` and the
    reference's plain scan over the same inputs, at lengths that are and
    are not multiples of the chunk: the same outputs and the same last
    state."""
    x, dt, a, b, c = recurrence_inputs(length)
    y_chunked, s_chunked = ssd_chunked(x, dt, a, b, c, CHUNK)
    state, ys = jnp.zeros((2, SH, SP, SN)), []
    for t in range(length):
        y, state = ssd_step(state, x[:, t], dt[:, t], a, b[:, t], c[:, t])
        ys.append(y)
    y_step = jnp.stack(ys, 1)
    for row in range(2):
        y_ref, s_ref = ref.ssm_scan(x[row], dt[row], a, b[row], c[row])
        assert gap(y_chunked[row], y_ref) < TOL
        assert gap(y_step[row], y_ref) < TOL
        assert gap(s_chunked[row], s_ref) < TOL
        assert gap(state[row], s_ref) < TOL
    assert float(jnp.abs(s_chunked).max()) > 0.1


def test_a_position_with_dt_zero_is_inert():
    """Pads: ``dt = 0`` from position 13 on leaves the state where the
    first 13 tokens put it, bit for bit whatever the pads hold."""
    x, dt, a, b, c = recurrence_inputs(24)
    _, want = ssd_chunked(x[:, :13], dt[:, :13], a, b[:, :13], c[:, :13],
                          CHUNK)
    cut = jnp.where(jnp.arange(24)[None, :, None] < 13, dt, 0.0)
    _, got = ssd_chunked(x, cut, a, b, c, CHUNK)
    assert gap(got, want) < 1e-6


# --------------------------------------------- the model in training mode

@pytest.mark.parametrize("length", [40, 21, 5])
def test_training_mode_logits_match_the_reference(model, weights, tokens,
                                                  length):
    got = jax.jit(model.apply)({"params": to_flax(weights)},
                               tokens[None, :length])[0]
    want = ref_logits(weights, tokens[:length])
    assert float(jnp.max(jnp.abs(want))) > 1.0      # there is something to miss
    assert gap(got, want) < TOL


def test_the_models_own_init_has_the_mapped_tree(model, weights, tokens):
    init = jax.jit(model.init)(jax.random.key(0), tokens[None, :8])["params"]
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(
        jnp.shape, to_flax(weights))
    dt = jax.nn.softplus(init["mixer_0"]["dt_bias"])
    assert bool(jnp.all((dt >= 1e-3 * 0.999) & (dt <= 0.1 * 1.001)))
    assert init["mixer_0"]["A_log"].dtype == jnp.float32


def test_the_two_copies_of_the_reference_are_one(weights, tokens):
    """``benchmarks/lib/hybrid_ssm_reference.py`` imports nothing of the
    program and is what decides ``correct`` on the chip; this copy is what
    the program's tests compare with."""
    from benchmarks.lib import hybrid_ssm_reference as bench_ref

    assert bench_ref.FAULTS == ref.FAULTS
    for kw in ({}, {"fault": "no_shared"}, {"mode": "fp8"},
               {"fault": "pads_advance", "prompt_len": 20, "pads": 12}):
        np.testing.assert_array_equal(
            ref_logits(weights, tokens, **kw),
            ref_logits(weights, tokens, module=bench_ref, **kw))


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_planted_fault_moves_the_logits(fault, program_logits, weights,
                                          tokens):
    """Each departure from the equations or from the slot contract,
    planted in the reference (a prompt of 20 in a bucket of 32), reads far
    outside the tolerance (the least, ``no_dt_bias``, 0.78)."""
    wrong = ref_logits(weights, tokens, fault=fault, prompt_len=20, pads=12)
    assert gap(program_logits, wrong) > 1e3 * TOL


def test_the_float8_control_moves_the_logits(program_logits, weights, tokens):
    assert gap(program_logits,
               ref_logits(weights, tokens, mode="fp8")) > 1e3 * TOL


# ------------------------------------------------- through the slot table

@functools.partial(jax.jit, static_argnums=0)
def slot_logits(dm, params, cache, tokens, positions, **kw):
    """The served module over a table as it stands: what a program of the
    cache computes, with the logits kept."""
    return dm.apply({"params": params, "cache": cache}, tokens, train=False,
                    positions=positions, mutable=["cache"], **kw)[0]


def state_of(kv, slot):
    """The slot's per-slot state leaves, on the host."""
    return [np.asarray(leaf[slot]) for path, leaf in
            jax.tree_util.tree_leaves_with_path(kv.cache)
            if path[-1].key in kv.state_leaves]


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
    for _ in range(new):
        logits = slot_logits(kv.dm, kv.params, kv.cache,
                             jnp.asarray(kv.tokens)[:, None],
                             jnp.asarray(kv.lengths)[:, None],
                             active=jnp.asarray(kv.active))
        rows.append(logits[slot, -1])
        served.append(int(kv.advance()[slot]))
    return jnp.stack(rows), served


@pytest.mark.parametrize("lp", [21, 3, 2, 32])
def test_prefill_then_decode_logits_match_the_full_forward(model, weights,
                                                           tokens, lp):
    """A prompt through ``insert`` (the chunked-scan prefill: 21 in a
    bucket of 32, 3 and 2 shorter than the convolution's reach, 32 filling
    its bucket) and eight rounds of ``advance`` (the one-token
    recurrence): at every position from the prompt's last on the logits
    are the reference's full forward over prompt and served tokens, and
    their argmax is the token the program gave."""
    new = 8
    kv = SlotKVCache(model, to_flax(weights), 4, prefill_bucket=8)
    got, served = serve_alone(kv, tokens[:lp], new, slot=2)
    seq = jnp.concatenate([tokens[:lp], jnp.asarray(served[:-1], jnp.int32)])
    want = ref_logits(weights, seq)[lp - 1:]
    assert gap(got, want) < TOL
    assert [int(t) for t in jnp.argmax(got, -1)] == served
    assert kv.lengths[2] == lp + new


def test_the_same_prompt_in_two_buckets_leaves_the_same_state(model, weights,
                                                              tokens):
    """13 tokens in a bucket of 16 and in a bucket of 64: the pads move
    nothing, so the state and the tail are the same (to float32 rounding:
    the chunks fall differently) and so is the first token; and they are
    the reference's state after 13 tokens, not after the bucket."""
    tables = []
    for bucket in (16, 64):
        kv = SlotKVCache(model, to_flax(weights), 2, prefill_bucket=bucket)
        slot, first = kv.insert(np.asarray(tokens[:13]), slot=1)
        assert kv.prefill_tokens_padded == bucket
        tables.append((first, state_of(kv, slot)))
    (first_a, state_a), (first_b, state_b) = tables
    assert first_a == first_b
    for a, b in zip(state_a, state_b):
        assert np.abs(a).max() > 0.01 and np.abs(a - b).max() < TOL
    # the tail is rows 10, 11, 12 of the first layer's pre-activation xBC
    w0 = weights["layers"][0]
    y = ref._rms(weights["embed"][tokens[:13]], w0["norm"], 1e-5)
    xbc = (y @ w0["in_proj"])[:, DI:DI + WIDTH]
    tail = kv.cache["mixer_0"]["conv_tail"][1]
    assert gap(tail, xbc[10:13]) < TOL


def test_a_slot_reused_after_evict_serves_as_a_fresh_one(model, weights,
                                                         tokens):
    """The state a slot's last occupant left is not read: a prompt served
    in a slot that another request held and left gives the logits it
    gives in a table nobody touched."""
    params = to_flax(weights)
    fresh = SlotKVCache(model, params, 2, prefill_bucket=8)
    want, want_tokens = serve_alone(fresh, tokens[20:31], 5, slot=0)
    used = SlotKVCache(model, params, 2, prefill_bucket=8)
    serve_alone(used, tokens[:17], 6, slot=0)
    assert any(np.abs(leaf).max() > 0.01 for leaf in state_of(used, 0))
    used.evict(0)
    got, got_tokens = serve_alone(used, tokens[20:31], 5, slot=0)
    assert got_tokens == want_tokens and gap(got, want) == 0.0


@pytest.mark.parametrize("excluded", ["free", "only"])
def test_a_slot_left_out_of_a_round_keeps_its_state_bit_for_bit(
        model, weights, tokens, excluded):
    """``free``: slot 1 was evicted and rounds go on around it.  ``only``:
    slot 1 is live and ``advance(only=...)`` leaves it out.  Either way
    its state and tail are the same bytes after the rounds, the slots
    that did advance moved theirs, and the left-out live slot then goes
    on as if the rounds had not been."""
    kv = SlotKVCache(model, to_flax(weights), 3, prefill_bucket=8)
    for slot, lo in ((0, 0), (1, 9), (2, 20)):
        kv.insert(np.asarray(tokens[lo:lo + 7 + slot]), slot=slot)
    kv.advance()
    if excluded == "free":
        kv.evict(1)
        only = None
    else:
        only = np.asarray([True, False, True])
    before = [state_of(kv, s) for s in range(3)]
    length, token = int(kv.lengths[1]), int(kv.tokens[1])
    for _ in range(3):
        kv.advance(only=only)
    for a, b in zip(before[1], state_of(kv, 1)):
        np.testing.assert_array_equal(a, b)
    assert all(np.abs(a - b).max() > 1e-4
               for a, b in zip(before[0], state_of(kv, 0)))
    assert (int(kv.lengths[1]), int(kv.tokens[1])) == (length, token)
    if excluded == "only":
        alone = SlotKVCache(model, to_flax(weights), 3, prefill_bucket=8)
        alone.insert(np.asarray(tokens[9:17]), slot=1)
        alone.advance()
        assert int(alone.advance()[1]) == int(kv.advance()[1])


def requests():
    rng = np.random.default_rng(5)
    return [Request(rid=i, prompt=rng.integers(0, VOCAB, lp, dtype=np.int32),
                    max_new_tokens=new, arrival_s=0.0)
            for i, (lp, new) in enumerate(
                [(5, 6), (17, 3), (9, 8), (30, 5), (12, 1), (3, 7)])]


def test_the_batcher_serves_each_request_as_the_reference_would(model,
                                                                weights):
    """Six requests of mixed length through three slots (every slot is
    reused): continuous batching changes nobody's tokens, each token is
    the reference's greedy choice given what came before it, and the
    spans say which prefill ran, where, and what the rounds' routing
    touched."""
    params = to_flax(weights)
    kv = SlotKVCache(model, params, 3, prefill_bucket=8)
    summary = ContinuousBatcher(kv).run(requests())
    together = {r.rid: r.tokens for r in summary["results"]}
    assert [len(together[r.rid]) for r in requests()] == [6, 3, 8, 5, 1, 7]
    for req in requests():
        served = together[req.rid]
        seq = jnp.concatenate([jnp.asarray(req.prompt),
                               jnp.asarray(served[:-1], jnp.int32)])
        logits = ref_logits(weights, seq)[len(req.prompt) - 1:]
        below = jnp.max(logits, -1) - logits[jnp.arange(len(served)),
                                             jnp.asarray(served)]
        assert float(below.max()) < TOL

    window = recorder().records(root="serve_run")
    prefills = [r for r in window if r["name"] == "prefill"]
    assert len(prefills) == 6
    assert {r["attrs"]["form"] for r in prefills} == {"batched"}
    slots = [r["attrs"]["slot"] for r in prefills]
    assert set(slots) == {0, 1, 2} and len(slots) == 6
    steps = [r for r in window if r["name"] == "decode_step"]
    assert steps and all(
        1 <= r["attrs"]["experts_touched"] <= EXPERTS
        and 1 <= r["attrs"]["expert_load_max"] <= r["attrs"]["active"]
        for r in steps)
    # every expert is held: a token chooses TOP_K experts in each of the 2
    # expert layers: the 76 prompt tokens, and each request's tokens but
    # the last as it is fed
    root = window[0]["attrs"]
    assert root["cache_bytes_per_token"] == ROW_BYTES
    assert root["state_bytes_per_slot"] == STATE_BYTES
    assert root["expert_assignments"] == (76 + 24) * 2 * TOP_K
    builds = {r["attrs"]["program"] for r in recorder().records()
              if r["name"] == "program_build"}
    assert {"kv_decode_step_routed", "kv_prefill_batched_l8"} <= builds


def test_a_share_of_the_experts_counts_what_it_computed(weights):
    """Held (4, 8): the routing integers and ``expert_assignments`` count
    the pairs that fell to the eight experts held here, which is fewer
    than every choice and what the reference's route gives."""
    model = create_model("hybrid_ssm", **SIZES, experts_held=(4, 8))
    params = to_flax(weights)
    for i in (1, 4):
        for name in ("w_up", "w_down"):
            params[f"mixer_{i}"][name] = params[f"mixer_{i}"][name][4:12]
    kv = SlotKVCache(model, params, 2, prefill_bucket=8)
    prompt = np.arange(3, 14, dtype=np.int32)
    kv.insert(prompt)
    held = dict(DIMS, held=(4, 8))
    x = weights["embed"][prompt].astype(jnp.float32)
    want = 0
    for w in weights["layers"]:         # the reference, layer by layer
        y = ref._rms(x, w["norm"], 1e-5)
        if "router" in w:
            want += int((ref.route(y, w, held)[:, 4:12] > 0).sum())
            y = ref.experts(y, w, held)
        elif "in_proj" in w:
            y, _ = ref.mamba(y, w, held)
        else:
            y = ref.attention(y, w, held)
        x = x + y
    assert 0 < want < 11 * 2 * TOP_K
    assert kv.counters()["expert_assignments"] == want
    kv.advance()
    assert kv.last_routing["experts_touched"] <= 8
    assert want < kv.counters()["expert_assignments"] <= want + 2 * TOP_K


def test_analyze_serve_prints_the_state_counter(model, weights, tmp_path):
    from distributed_tensorflow_tpu.observability.analyze import (
        read_jsonl, render_waterfall_text, serve_waterfall)
    from distributed_tensorflow_tpu.observability.trace import Tracer

    path = tmp_path / "t.jsonl"
    with Tracer(path=path) as tracer:
        kv = SlotKVCache(model, to_flax(weights), 2, prefill_bucket=8)
        ContinuousBatcher(kv, tracer=tracer).run(requests()[:2])
    wf = serve_waterfall(read_jsonl(str(path)))
    assert wf["windows"][0]["state_bytes_per_slot"] == STATE_BYTES
    assert (f"{ROW_BYTES} bytes a token, {STATE_BYTES} bytes of state a "
            f"slot, {(22 + 7) * 2 * TOP_K} expert assignments"
            in render_waterfall_text(wf))


def test_counters_report_the_two_kinds_of_bytes(model, weights):
    """Rows a token and state a slot, counted apart; ``kv_dtype`` narrows
    the rows and the tail, never the recurrent state."""
    kv = SlotKVCache(model, to_flax(weights), 4)
    counts = kv.counters()
    assert counts["cache_bytes_per_token"] == ROW_BYTES
    assert counts["state_bytes_per_slot"] == STATE_BYTES
    assert kv.kv_bytes_per_slot() == ROW_BYTES * MAX_LEN + STATE_BYTES
    assert {leaf.shape for leaf in jax.tree.leaves(kv.cache)} == {
        (4, MAX_LEN, KVH, HD), (4, SH, SP, SN), (4, TAPS - 1, WIDTH)}
    half = SlotKVCache(model, to_flax(weights), 4, kv_dtype=jnp.bfloat16)
    assert half.kv_dtype == "bfloat16"
    assert half.counters()["cache_bytes_per_token"] == ROW_BYTES // 2
    assert half.counters()["state_bytes_per_slot"] == 2 * (
        SH * SP * SN * 4 + (TAPS - 1) * WIDTH * 2)
    assert half.cache["mixer_0"]["ssm_state"].dtype == jnp.float32
    assert half.timeline_gauges()["kv_live_bytes"] == 0


# --------------------------------------- what is not built for this model

def _live(model, params):
    kv = SlotKVCache(model, params, 2)
    kv.insert([1, 2, 3], slot=0)
    return kv


@pytest.mark.parametrize("feature, call", [
    ("paged layout", lambda m, p: SlotKVCache(m, p, 2, kv_layout="paged")),
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
    ("tensor-parallel", lambda m, p: m.slot_decode_clone(
        partition_model=True)),
], ids=["paged", "prefix_pool", "int8", "chunk_resume", "multi_step",
        "verify", "commit_block", "rewind", "handoff_out", "handoff_in",
        "tensor_parallel"])
def test_what_is_not_built_for_per_slot_state_says_so(model, weights,
                                                      feature, call):
    with pytest.raises(NotImplementedError, match=feature):
        call(model, to_flax(weights))


def test_a_token_block_is_refused_by_the_state_space_step(model, weights):
    kv = SlotKVCache(model, to_flax(weights), 2)
    with pytest.raises(ValueError, match="one token a slot"):
        slot_logits(kv.dm, kv.params, kv.cache, jnp.zeros((2, 3), jnp.int32),
                    jnp.arange(3)[None].repeat(2, 0))


# ------------------------------------------------------- the expert layer

def moe_layer(**kw):
    return DroplessMoE(num_experts=EXPERTS, top_k=TOP_K, hidden=FFN,
                       shared_hidden=SHARED, routed_scale=SCALE,
                       expert_act="relu2", latent=LATENT, **kw)


def skewed_layer(weights):
    """The second block's expert layer with a choice bias that sends every
    token to expert 3 first (no capacity: all tokens are computed)."""
    w = dict(weights["layers"][1])
    w["choice_bias"] = w["choice_bias"].at[3].set(10.0)
    return w


def test_the_latent_relu2_layer_is_the_references(weights):
    w = skewed_layer(weights)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((48, H)),
                    jnp.float32)
    got, sown = moe_layer().apply({"params": moe_to_flax(w)}, x,
                                  mutable=["intermediates"])
    assert gap(got, ref.experts(x, w, DIMS)) < TOL
    choice = sown["intermediates"]["expert_choice"][0]
    assert choice.shape == (48, TOP_K) and bool(jnp.all(choice[:, 0] == 3))


@pytest.mark.parametrize("shares", [
    [(0, 4), (4, 4), (8, 4), (12, 4)], [(0, 16)], [(0, 3), (3, 1), (4, 12)]],
    ids=["four_quarters", "whole", "uneven"])
def test_the_shares_of_the_experts_add_up_to_the_layer(weights, shares):
    """What disjoint shares of the experts give (four quarters: the
    benchmark's deployment, of which its configuration holds one), each
    routing over all 16 and computing its own experts' part in the latent,
    with the shared expert (which every share computes alike) counted
    once, is the whole layer of the uncut reference."""
    w = skewed_layer(weights)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((20, H)),
                    jnp.float32)
    shared = ref._relu2_mlp(x, w["shared_up"], w["shared_down"], "f32", None)
    total = jnp.zeros_like(x)
    for first, count in shares:
        params = moe_to_flax(w)
        for name in ("w_up", "w_down"):
            params[name] = params[name][first:first + count]
        part = moe_layer(held=(first, count)).apply({"params": params}, x)
        assert gap(part, ref.experts(x, w, DIMS, held=(first, count))) < TOL
        total = total + part - shared
    assert gap(total + shared, ref.experts(x, w, DIMS)) < TOL


def old_layer(params, x, valid=None, *, k=TOP_K, hidden=FFN, shared=SHARED,
              scale=2.448, held=None):
    """``DroplessMoE.__call__`` as it stood before it knew ``expert_act``
    and ``latent`` (PR 27's, the layer ``serve-kanana2-longdoc`` runs),
    written out over the same parameters."""
    t, d = x.shape
    e = params["router"].shape[1]
    first, n = held or (0, e)
    scores = jax.nn.sigmoid(jnp.dot(x, params["router"],
                                    precision=jax.lax.Precision.HIGHEST))
    _, choice = jax.lax.top_k(scores + params["choice_bias"], k)
    weight = jnp.take_along_axis(scores, choice, axis=-1)
    weight = weight / (weight.sum(-1, keepdims=True) + 1e-20) * scale
    local = choice - first
    here = (local >= 0) & (local < n)
    if valid is not None:
        here = here & valid[:, None]
    local = jnp.where(here, local, n).reshape(-1)
    order = jnp.argsort(local, stable=True)
    sizes = jnp.bincount(local, length=n + 1)[:n].astype(jnp.int32)
    xs = x[order // k]
    gate = jax.lax.ragged_dot(xs, params["w_gate"], sizes)
    up = jax.lax.ragged_dot(xs, params["w_up"], sizes)
    ys = jax.lax.ragged_dot(jax.nn.silu(gate) * up, params["w_down"], sizes)
    ys = jnp.where((jnp.arange(t * k) < sizes.sum())[:, None], ys, 0)
    ys = ys[jnp.argsort(order)].reshape(t, k, d)
    y = jnp.einsum("tkd,tk->td", ys, jnp.where(here, weight, 0.0))
    sh = params["shared"]
    h = jax.nn.silu(x @ sh["gate"]["kernel"]) * (x @ sh["up"]["kernel"])
    return y + h @ sh["down"]["kernel"]


@pytest.mark.parametrize("held, masked", [(None, False), ((4, 8), False),
                                          (None, True)],
                         ids=["all_held", "a_share", "pads_masked"])
def test_the_layer_with_the_old_settings_is_bit_for_bit_the_old_layer(
        held, masked):
    """With ``expert_act`` and ``latent`` left at their defaults the layer
    creates the parameters it created (``w_gate``, ``w_up``, ``w_down``,
    ``shared/gate|up|down``: no latent projection) and returns, bit for
    bit, what the layer written out above returns."""
    layer = DroplessMoE(num_experts=EXPERTS, top_k=TOP_K, hidden=FFN,
                        shared_hidden=SHARED, routed_scale=2.448, held=held)
    x = jnp.asarray(np.random.default_rng(8).standard_normal((24, H)),
                    jnp.float32)
    valid = (jnp.arange(24) < 17) if masked else None
    params = layer.init(jax.random.key(3), x)["params"]
    assert sorted(params) == ["choice_bias", "router", "shared", "w_down",
                              "w_gate", "w_up"]
    assert sorted(params["shared"]) == ["down", "gate", "up"]
    assert params["w_up"].shape == ((held or (0, EXPERTS))[1], H, FFN)
    params["choice_bias"] = 0.1 * jax.random.normal(jax.random.key(4),
                                                    (EXPERTS,))
    got = layer.apply({"params": params}, x, valid)
    np.testing.assert_array_equal(got, old_layer(params, x, valid, held=held))
    assert float(jnp.abs(got).max()) > 0.1
