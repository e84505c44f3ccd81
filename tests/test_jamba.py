"""``ops/selective_scan`` and ``models/jamba.JambaLM`` against the plain
reference (``tests/jamba_reference.py``), in training mode and through
``SlotKVCache`` / ``ContinuousBatcher``: a ``(d_inner, d_state)`` recurrent
state and a convolution tail a slot beside one key/value head's rows.

A small size that keeps every mechanism: hidden 64, ``d_i`` 128, state 16,
step rank 4, 4 taps; 4 query heads on 1 key/value head of 16; SwiGLU of 96;
vocabulary 512; four layers with attention at ``i % 4 == 2`` (three
state-space layers, one attention layer), and once a whole period of the
published rule (14 layers, attention at 7); float32 weights drawn from a
seed at std 0.1, gains around 1, ``dt_bias`` in [-3, 0], ``A = -(n + 1)``.
Off the TPU the kernel runs in the Pallas interpreter.

TOL: program and reference both compute in float32 here and differ in the
order of their sums only (the kernel adds the state's 16 terms in turn where
the reference reduces an axis; one product over a block where the step makes
one a token); measured 3e-6 on logits of size 2.  2e-5 leaves a factor of
six and is three thousand times under the least planted fault (0.06)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import jamba_reference as ref
from distributed_tensorflow_tpu.models import create_model
from distributed_tensorflow_tpu.models.jamba import SelectiveMixer
from distributed_tensorflow_tpu.observability.trace import recorder
from distributed_tensorflow_tpu.ops.selective_scan import (
    selective_scan, selective_scan_plain, selective_step)
from distributed_tensorflow_tpu.serving import SlotKVCache
from distributed_tensorflow_tpu.serving.scheduler import (
    ContinuousBatcher, Request)

TOL = 2e-5
H, VOCAB, MAX_LEN = 64, 512, 64
DI, SN, RANK, TAPS = 128, 16, 4, 4
QH, KVH, HD, FFN = 4, 1, 16, 96
LAYERS, PERIOD, OFFSET = 4, 4, 2
SIZES = dict(vocab_size=VOCAB, hidden=H, layers=LAYERS, attn_period=PERIOD,
             attn_offset=OFFSET, ssm_state=SN, ssm_conv=TAPS, ssm_expand=2,
             ssm_dt_rank=RANK, heads=QH, kv_heads=KVH, head_dim=HD, ffn=FFN,
             max_len=MAX_LEN)
DIMS = dict(q_heads=QH, kv_heads=KVH, head_dim=HD, state=SN, dt_rank=RANK,
            eps=1e-6)
# what a slot keeps: a state and a tail a state-space layer, keys and
# values a token of the one attention layer (float32 here)
STATE_BYTES = 3 * (DI * SN * 4 + (TAPS - 1) * DI * 4)
ROW_BYTES = 2 * KVH * HD * 4


def make_weights(seed: int, layers: int = LAYERS, period: int = PERIOD,
                 offset: int = OFFSET, std: float = 0.1) -> dict:
    """The reference's weight tree at the small size."""
    rng = np.random.default_rng(seed)

    def n(*shape):
        return jnp.asarray(std * rng.standard_normal(shape), jnp.float32)

    def one(*shape):
        return 1.0 + n(*shape)

    out = []
    for i in range(layers):
        w = {"norm": one(H), "ffn_norm": one(H), "gate": n(H, FFN),
             "up": n(H, FFN), "down": n(FFN, H)}
        if i % period == offset:
            w.update(q=n(H, QH * HD), k=n(H, KVH * HD), v=n(H, KVH * HD),
                     o=n(QH * HD, H))
        else:
            w.update(in_proj=n(H, 2 * DI), conv_w=3 * n(TAPS, DI),
                     conv_b=n(DI), x_proj=3 * n(DI, RANK + 2 * SN),
                     dt_norm=one(RANK), b_norm=one(SN), c_norm=one(SN),
                     dt_proj=3 * n(RANK, DI),
                     dt_bias=jnp.asarray(rng.uniform(-3, 0, DI), jnp.float32),
                     a_log=jnp.log(jnp.broadcast_to(
                         jnp.arange(1, SN + 1, dtype=jnp.float32), (DI, SN))),
                     d=one(DI), out_proj=n(DI, H))
        out.append(w)
    return {"embed": n(VOCAB, H), "final_norm": one(H), "layers": out}


def to_flax(w: dict) -> dict:
    """The reference's weights as ``JambaLM``'s parameter tree (the
    benchmark's own mapping: ``tests/test_benchmark_drivers.py`` and
    ``benchmarks/tests`` drive it too)."""
    from benchmarks.drivers import jamba_tree

    return jamba_tree.to_flax(w)


@pytest.fixture(scope="module")
def weights():
    return make_weights(0)


@pytest.fixture(scope="module")
def model():
    return create_model("jamba", **SIZES)


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


# ------------------------------------------------------------- the kernel

def scan_inputs(length: int, d: int, n: int, batch: int = 2):
    rng = np.random.default_rng(length + d)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return (f(batch, length, d),
            jnp.asarray(rng.uniform(0.01, 0.5, (batch, length, d)),
                        jnp.float32),
            -jnp.asarray(rng.uniform(0.5, 4, (d, n)), jnp.float32),
            f(batch, length, n), f(batch, length, n), f(d), f(batch, d, n))


@pytest.mark.parametrize("initial", [False, True], ids=["from_zero",
                                                        "from_a_state"])
@pytest.mark.parametrize("length, d, n, block", [
    (40, 128, 16, 16), (21, 128, 16, 8), (5, 200, 8, 256), (64, 1024, 16, 32),
    (1, 128, 16, 256)], ids=["blocks_of_16", "ragged_blocks", "ragged_lanes",
                             "a_whole_tile", "one_position"])
def test_the_kernel_is_the_plain_scan(length, d, n, block, initial):
    """``selective_scan`` (the Pallas kernel, interpreted here) against the
    ``lax.scan`` of the one-token update and against the reference's own
    scan: the same outputs and the same last state, where the sequence and
    the channels do and do not fill their blocks, from zero and from a
    given state."""
    u, dt, a, b, c, skip, s0 = scan_inputs(length, d, n)
    start = s0 if initial else None
    y, last = selective_scan(u, dt, a, b, c, skip, start, seq_block=block)
    y_plain, last_plain = selective_scan_plain(u, dt, a, b, c, skip, start)
    assert y.shape == (2, length, d) and last.shape == (2, d, n)
    assert gap(y, y_plain) < TOL and gap(last, last_plain) < TOL
    for row in range(2):
        y_ref, s_ref = ref.ssm_scan(u[row], dt[row], a, b[row], c[row],
                                    s0[row] if initial else None)
        assert gap(y[row], y_ref + skip * u[row]) < TOL
        assert gap(last[row], s_ref) < TOL
    assert float(jnp.abs(last).max()) > 0.1


@pytest.mark.parametrize("initial", [False, True], ids=["from_zero",
                                                        "from_a_state"])
def test_a_position_with_dt_zero_is_inert(initial):
    """Pads: ``dt = 0`` from position 13 on leaves the state where the
    first 13 tokens put it, bit for bit whatever the pads hold, and a
    bucket of pads alone hands the initial state through."""
    u, dt, a, b, c, skip, s0 = scan_inputs(24, 128, 16)
    start = s0 if initial else None
    _, want = selective_scan(u[:, :13], dt[:, :13], a, b[:, :13], c[:, :13],
                             skip, start, seq_block=8)
    cut = jnp.where(jnp.arange(24)[None, :, None] < 13, dt, 0.0)
    _, got = selective_scan(u, cut, a, b, c, skip, start, seq_block=8)
    np.testing.assert_array_equal(got, want)
    _, still = selective_scan(u, jnp.zeros_like(dt), a, b, c, skip, s0,
                              seq_block=8)
    np.testing.assert_array_equal(still, s0)


def test_the_step_is_one_position_of_the_scan():
    u, dt, a, b, c, skip, s0 = scan_inputs(6, 128, 16)
    state, ys = s0, []
    for t in range(6):
        y, state = selective_step(state, u[:, t], dt[:, t], a, b[:, t],
                                  c[:, t], skip)
        ys.append(y)
    y_scan, last = selective_scan(u, dt, a, b, c, skip, s0)
    assert gap(jnp.stack(ys, 1), y_scan) < TOL and gap(state, last) < TOL


# --------------------------------------------------- the mixer's two forms

@pytest.mark.parametrize("length", [1, 3, 9])
def test_the_block_form_of_the_mixer_is_its_step_form(length):
    """``SelectiveMixer`` over a block (the kernel) and a token at a time
    from a zero state and tail (the step of the slot table): the same
    outputs, at lengths below and above the convolution's reach."""
    kw = dict(hidden=H, inner=DI, state=SN, dt_rank=RANK, taps=TAPS, eps=1e-6,
              dtype=jnp.float32, param_dtype=jnp.float32)
    block, step = (SelectiveMixer(decode_slots=on, **kw)
                   for on in (False, True))
    x = jnp.asarray(np.random.default_rng(7).standard_normal((2, length, H)),
                    jnp.float32)
    params = block.init(jax.random.key(2), x, None, None)["params"]
    params["conv_bias"] = 0.1 * jnp.ones_like(params["conv_bias"])
    want = block.apply({"params": params}, x, None, None)
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
            lambda: step.init(jax.random.key(0), x[:, :1], None,
                              None))["cache"])
    got = []
    for t in range(length):
        y, upd = step.apply({"params": params, "cache": cache}, x[:, t:t + 1],
                            None, jnp.ones((2,), bool), mutable=["cache"])
        cache = upd["cache"]
        got.append(y)
    assert float(jnp.abs(want).max()) > 0.01
    assert gap(jnp.concatenate(got, 1), want) < TOL


# --------------------------------------------- the model in training mode

@pytest.mark.parametrize("length", [40, 21, 5])
def test_training_mode_logits_match_the_reference(model, weights, tokens,
                                                  length):
    got = jax.jit(model.apply)({"params": to_flax(weights)},
                               tokens[None, :length])[0]
    want = ref_logits(weights, tokens[:length])
    assert float(jnp.max(jnp.abs(want))) > 1.0      # there is something to miss
    assert gap(got, want) < TOL


def test_a_whole_period_of_the_published_rule_matches_the_reference(tokens):
    """14 layers with attention where ``i % 14 == 7``: one period of the
    configuration's 28."""
    w = make_weights(3, layers=14, period=14, offset=7)
    assert ["q" in lw for lw in w["layers"]] == [i == 7 for i in range(14)]
    model = create_model("jamba", **{**SIZES, "layers": 14,
                                     "attn_period": 14, "attn_offset": 7})
    assert model.selective_scan_layers == 13
    got = jax.jit(model.apply)({"params": to_flax(w)}, tokens[None, :24])[0]
    want = jax.jit(lambda w, s: ref.logits_fn(w, s, DIMS))(w, tokens[:24])
    assert gap(got, want) < TOL


def test_the_models_own_init_has_the_mapped_tree(model, weights, tokens):
    init = jax.jit(model.init)(jax.random.key(0), tokens[None, :8])["params"]
    assert jax.tree.map(jnp.shape, init) == jax.tree.map(
        jnp.shape, to_flax(weights))
    dt = jax.nn.softplus(init["mixer_0"]["dt_bias"])
    assert bool(jnp.all((dt >= 1e-3 * 0.999) & (dt <= 0.1 * 1.001)))
    for name in ("A_log", "D", "dt_bias"):
        assert init["mixer_0"][name].dtype == jnp.float32
    np.testing.assert_allclose(jnp.exp(init["mixer_0"]["A_log"][5]),
                               np.arange(1, SN + 1), rtol=1e-6)
    half = create_model("jamba", **SIZES, dtype="bfloat16",
                        param_dtype="bfloat16")
    init = jax.eval_shape(half.init, jax.random.key(0),
                          tokens[None, :8])["params"]["mixer_0"]
    assert {k: v.dtype for k, v in init.items() if k in (
        "A_log", "D", "dt_bias", "dt_proj", "conv_weight")} == {
            "A_log": jnp.float32, "D": jnp.float32, "dt_bias": jnp.float32,
            "dt_proj": jnp.bfloat16, "conv_weight": jnp.bfloat16}


def test_the_two_copies_of_the_reference_are_one(weights, tokens):
    """``benchmarks/lib/jamba_reference.py`` imports nothing of the program
    and is what decides ``correct`` on the chip; this copy is what the
    program's tests compare with."""
    from pathlib import Path

    from benchmarks.lib import jamba_reference as bench_ref

    assert Path(bench_ref.__file__).read_text() == Path(
        ref.__file__).read_text()
    np.testing.assert_array_equal(
        ref_logits(weights, tokens),
        ref_logits(weights, tokens, module=bench_ref))


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_planted_fault_moves_the_logits(fault, program_logits, weights,
                                          tokens):
    """Each departure from the equations or from the slot contract,
    planted in the reference (a prompt of 20 in a bucket of 32), reads far
    outside the tolerance."""
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
    """A prompt through ``insert`` (the kernel over the bucket: 21 in a
    bucket of 32, 3 and 2 shorter than the convolution's reach, 32 filling
    its bucket) and eight rounds of ``advance`` (the one-token step): at
    every position from the prompt's last on the logits are the reference's
    full forward over prompt and served tokens, and their argmax is the
    token the program gave."""
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
    nothing, so the state and the tail are the same and so is the first
    token; the tail is the prompt's last three pre-activation rows, not the
    bucket's."""
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
    w0 = weights["layers"][0]
    y = ref._rms(weights["embed"][tokens[:13]], w0["norm"], 1e-6)
    pre = (y @ w0["in_proj"])[:, :DI]
    assert gap(kv.cache["mixer_0"]["conv_tail"][1], pre[10:13]) < TOL
    # and the state is the reference's after 13 tokens
    _, (last, _) = ref.mamba(y, w0, DIMS)
    assert gap(kv.cache["mixer_0"]["ssm_state"][1], last) < TOL


def test_a_slot_reused_after_a_longer_occupant_serves_as_a_fresh_one(
        model, weights, tokens):
    """The state, the tail and the rows a slot's last, LONGER occupant left
    are not read: a prompt served in a slot that another request held and
    left gives the logits it gives in a table nobody touched."""
    params = to_flax(weights)
    fresh = SlotKVCache(model, params, 2, prefill_bucket=8)
    want, want_tokens = serve_alone(fresh, tokens[20:31], 5, slot=0)
    used = SlotKVCache(model, params, 2, prefill_bucket=8)
    serve_alone(used, tokens[:29], 6, slot=0)
    assert any(np.abs(leaf).max() > 0.01 for leaf in state_of(used, 0))
    used.evict(0)
    got, got_tokens = serve_alone(used, tokens[20:31], 5, slot=0)
    assert got_tokens == want_tokens and gap(got, want) == 0.0
    seq = jnp.concatenate([tokens[20:31],
                           jnp.asarray(got_tokens[:-1], jnp.int32)])
    assert gap(got, ref_logits(weights, seq)[10:]) < TOL


@pytest.mark.parametrize("excluded", ["free", "only"])
def test_a_slot_left_out_of_a_round_keeps_its_state_bit_for_bit(
        model, weights, tokens, excluded):
    """``free``: slot 1 was evicted and rounds go on around it.  ``only``:
    slot 1 is live and ``advance(only=...)`` leaves it out.  Either way
    its state and tail are the same bytes after the rounds, the slots
    that did advance moved theirs, and the left-out live slot then goes
    on as the reference does, as if the rounds had not been."""
    kv = SlotKVCache(model, to_flax(weights), 3, prefill_bucket=8)
    firsts = [kv.insert(np.asarray(tokens[lo:lo + 7 + slot]), slot=slot)[1]
              for slot, lo in ((0, 0), (1, 9), (2, 20))]
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
        logits = slot_logits(kv.dm, kv.params, kv.cache,
                             jnp.asarray(kv.tokens)[:, None],
                             jnp.asarray(kv.lengths)[:, None],
                             active=jnp.asarray(kv.active))[1, -1]
        seq = jnp.concatenate([tokens[9:17],
                               jnp.asarray([firsts[1], token], jnp.int32)])
        assert gap(logits, ref_logits(weights, seq)[-1]) < TOL


def requests():
    rng = np.random.default_rng(5)
    return [Request(rid=i, prompt=rng.integers(0, VOCAB, lp, dtype=np.int32),
                    max_new_tokens=new, arrival_s=0.0)
            for i, (lp, new) in enumerate(
                [(5, 6), (17, 3), (9, 8), (30, 5), (12, 1), (3, 7)])]


def test_the_batcher_serves_each_request_as_alone_and_as_the_reference(
        model, weights):
    """Six requests of mixed length through three slots (every slot is
    reused): continuous batching changes nobody's tokens (the same tokens
    as one request at a time in a table of its own), each token is the
    reference's greedy choice given what came before it, and the spans and
    counters say which prefill ran, where, and what the kernel scanned."""
    params = to_flax(weights)
    kv = SlotKVCache(model, params, 3, prefill_bucket=8)
    summary = ContinuousBatcher(kv).run(requests())
    together = {r.rid: r.tokens for r in summary["results"]}
    assert [len(together[r.rid]) for r in requests()] == [6, 3, 8, 5, 1, 7]
    window = recorder().records(root="serve_run")
    alone = SlotKVCache(model, params, 1, prefill_bucket=8)
    for req in requests():
        one = ContinuousBatcher(alone).run([req])["results"][0].tokens
        served = together[req.rid]
        assert served == one
        seq = jnp.concatenate([jnp.asarray(req.prompt),
                               jnp.asarray(served[:-1], jnp.int32)])
        logits = ref_logits(weights, seq)[len(req.prompt) - 1:]
        below = jnp.max(logits, -1) - logits[jnp.arange(len(served)),
                                             jnp.asarray(served)]
        assert float(below.max()) < TOL

    prefills = [r for r in window if r["name"] == "prefill"]
    assert len(prefills) == 6
    assert {r["attrs"]["form"] for r in prefills} == {"batched"}
    assert [r["attrs"]["padded_len"] for r in prefills] == [
        8, 32, 16, 32, 16, 8]
    slots = [r["attrs"]["slot"] for r in prefills]
    assert set(slots) == {0, 1, 2} and len(slots) == 6
    root = window[0]["attrs"]
    assert root["cache_bytes_per_token"] == ROW_BYTES
    assert root["state_bytes_per_slot"] == STATE_BYTES
    assert root["expert_assignments"] == 0
    # the kernel ran over every bucket position of the three state-space
    # layers; 76 of the 112 were prompt tokens
    counts = kv.counters()
    assert counts["ssm_scan_positions"] == 3 * 112
    assert counts["ssm_scan_tokens"] == 3 * 76
    builds = {r["attrs"]["program"] for r in recorder().records()
              if r["name"] == "program_build"}
    assert {"kv_decode_step", "kv_prefill_batched_l8"} <= builds


def test_counters_report_the_two_kinds_of_bytes(model, weights):
    """Rows a token and state a slot, counted apart; ``kv_dtype`` narrows
    the rows and the tail, never the recurrent state; a model without
    state-space layers counts no scan."""
    kv = SlotKVCache(model, to_flax(weights), 4)
    counts = kv.counters()
    assert counts["cache_bytes_per_token"] == ROW_BYTES
    assert counts["state_bytes_per_slot"] == STATE_BYTES
    assert counts["ssm_scan_positions"] == counts["ssm_scan_tokens"] == 0
    assert kv.kv_bytes_per_slot() == ROW_BYTES * MAX_LEN + STATE_BYTES
    assert {leaf.shape for leaf in jax.tree.leaves(kv.cache)} == {
        (4, MAX_LEN, KVH, HD), (4, DI, SN), (4, TAPS - 1, DI)}
    half = SlotKVCache(model, to_flax(weights), 4, kv_dtype=jnp.bfloat16)
    assert half.kv_dtype == "bfloat16"
    assert half.counters()["cache_bytes_per_token"] == ROW_BYTES // 2
    assert half.counters()["state_bytes_per_slot"] == 3 * (
        DI * SN * 4 + (TAPS - 1) * DI * 2)
    assert half.cache["mixer_0"]["ssm_state"].dtype == jnp.float32
    gpt = create_model("gpt", vocab_size=64, hidden=32, layers=1, heads=2,
                       ffn=64, max_len=16)
    plain = SlotKVCache(gpt, gpt.init(jax.random.key(0), jnp.zeros(
        (1, 4), jnp.int32))["params"], 2)
    plain.insert([1, 2, 3])
    assert plain.counters()["ssm_scan_positions"] == 0


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
    """By the feature's name, and by this model's: the refusal names the
    class that keeps the state and its leaves."""
    with pytest.raises(NotImplementedError, match=feature) as err:
        call(model, to_flax(weights))
    assert "JambaLM" in str(err.value)


def test_a_token_block_is_refused_by_the_state_space_step(model, weights):
    kv = SlotKVCache(model, to_flax(weights), 2)
    with pytest.raises(ValueError, match="one token a slot"):
        slot_logits(kv.dm, kv.params, kv.cache, jnp.zeros((2, 3), jnp.int32),
                    jnp.arange(3)[None].repeat(2, 0))


# ------------------------------------------- a block longer than one piece

@pytest.fixture(scope="module")
def pieces_of_eight():
    """``SEQ_CHUNK`` 8 in place of 4,096: a block of 16 or more positions
    that is a multiple of 8 is taken in pieces (a ``lax.scan`` that carries
    the state and the convolution's last rows), as a bucket of 8,192 or more
    is at the real size.  Programs traced under the other setting are
    dropped before and after; the tests that ask for it are the file's
    last."""
    from distributed_tensorflow_tpu.models import jamba

    jax.clear_caches()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jamba, "SEQ_CHUNK", 8)
        yield
    jax.clear_caches()


@pytest.mark.parametrize("length", [40, 16, 21])
def test_a_block_in_pieces_matches_the_reference(pieces_of_eight, model,
                                                 weights, tokens, length):
    """Five pieces, two, and a length no piece divides (one piece)."""
    from distributed_tensorflow_tpu.models import jamba

    assert jamba._pieces(length) == {40: 5, 16: 2, 21: 1}[length]
    got = jax.jit(model.apply)({"params": to_flax(weights)},
                               tokens[None, :length])[0]
    assert gap(got, ref_logits(weights, tokens[:length])) < TOL


@pytest.mark.parametrize("lp", [21, 16, 17, 32, 2])
def test_a_prefill_in_pieces_leaves_the_state_and_the_tail_of_the_prompt(
        pieces_of_eight, model, weights, tokens, lp):
    """A bucket of 32 in four pieces (16 in two): the prompt ends inside a
    piece (21), at a piece's end (16, 32), one past it (17: its tail
    reaches back into the piece before) or within the first three rows (2);
    the logits from the prompt's last position on are the reference's."""
    kv = SlotKVCache(model, to_flax(weights), 2, prefill_bucket=16)
    kv.insert(np.asarray(tokens[:lp]), slot=1)
    w0 = weights["layers"][0]
    y = ref._rms(weights["embed"][tokens[:lp]], w0["norm"], 1e-6)
    pre = jnp.concatenate([jnp.zeros((3, DI)), (y @ w0["in_proj"])[:, :DI]])
    assert gap(kv.cache["mixer_0"]["conv_tail"][1], pre[-3:]) < TOL
    kv.evict(1)
    got, served = serve_alone(kv, tokens[:lp], 4, slot=1)
    seq = jnp.concatenate([tokens[:lp], jnp.asarray(served[:-1], jnp.int32)])
    assert gap(got, ref_logits(weights, seq)[lp - 1:]) < TOL

