"""``models/mla_moe.LatentMoELM`` and ``models/moe.DroplessMoE`` against the
plain reference (``tests/mla_moe_reference.py``), in training mode and
through ``SlotKVCache`` / ``ContinuousBatcher``.

A small size that keeps every mechanism: hidden 64, 4 heads of 16 + 8
(value 16), latent rank 32, one dense layer and two expert layers of 16
experts, 4 a token, 2 shared, vocabulary 512; float32 weights drawn from a
seed at std 0.1 with a choice bias of std 0.1, norm gains around 1.

TOL: program and reference both compute in float32 here and differ in the
order of their sums only; measured 2e-6 on logits of size 3 (the absorbed
form 4e-6).  2e-5 leaves a factor of five and is still ten thousand times
under the least planted fault (0.37)."""

import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mla_moe_reference as ref
from distributed_tensorflow_tpu.models import create_model
from distributed_tensorflow_tpu.models.moe import DroplessMoE
from distributed_tensorflow_tpu.observability.trace import recorder
from distributed_tensorflow_tpu.serving import SlotKVCache
from distributed_tensorflow_tpu.serving.scheduler import (
    ContinuousBatcher, Request)

TOL = 2e-5
H, HEADS, DN, DR, DV, RANK = 64, 4, 16, 8, 16, 32
EXPERTS, TOP_K, EXPERT_FFN, DENSE_FFN, VOCAB, LAYERS = 16, 4, 24, 192, 512, 3
SIZES = dict(vocab_size=VOCAB, hidden=H, layers=LAYERS, heads=HEADS,
             qk_nope_dim=DN, qk_rope_dim=DR, v_dim=DV, kv_rank=RANK,
             dense_ffn=DENSE_FFN, first_dense=1, num_experts=EXPERTS,
             experts_per_token=TOP_K, expert_ffn=EXPERT_FFN, shared_experts=2,
             routed_scale=2.448, max_len=64)
DIMS = dict(heads=HEADS, d_n=DN, d_r=DR, d_v=DV, rank=RANK, top_k=TOP_K,
            routed_scale=2.448, norm_topk=True, theta=1e6, eps=1e-6)


def make_weights(seed: int, std: float = 0.1) -> dict:
    """The reference's weight tree at the small size."""
    rng = np.random.default_rng(seed)

    def n(*shape):
        return jnp.asarray(std * rng.standard_normal(shape), jnp.float32)

    def one(*shape):
        return 1.0 + n(*shape)

    layers = []
    for i in range(LAYERS):
        w = {"attn_norm": one(H), "q": n(H, HEADS * (DN + DR)),
             "kv_a": n(H, RANK + DR), "kv_a_norm": one(RANK),
             "kv_b": n(RANK, HEADS * (DN + DV)), "o": n(HEADS * DV, H),
             "ffn_norm": one(H)}
        if i < 1:
            w.update(gate=n(H, DENSE_FFN), up=n(H, DENSE_FFN),
                     down=n(DENSE_FFN, H))
        else:
            s = 2 * EXPERT_FFN
            w.update(router=n(H, EXPERTS), choice_bias=n(EXPERTS),
                     w_gate=n(EXPERTS, H, EXPERT_FFN),
                     w_up=n(EXPERTS, H, EXPERT_FFN),
                     w_down=n(EXPERTS, EXPERT_FFN, H), shared_gate=n(H, s),
                     shared_up=n(H, s), shared_down=n(s, H))
        layers.append(w)
    return {"embed": n(VOCAB, H), "head": n(H, VOCAB), "final_norm": one(H),
            "layers": layers}


def moe_to_flax(w: dict) -> dict:
    return {"router": w["router"], "choice_bias": w["choice_bias"],
            "w_gate": w["w_gate"], "w_up": w["w_up"], "w_down": w["w_down"],
            "shared": {k: {"kernel": w[f"shared_{k}"]}
                       for k in ("gate", "up", "down")}}


def to_flax(w: dict) -> dict:
    """The reference's weights as ``LatentMoELM``'s parameter tree."""
    tree = {"token_embed": {"embedding": w["embed"]},
            "lm_head": {"kernel": w["head"]},
            "final_norm": {"scale": w["final_norm"]}}
    for i, lw in enumerate(w["layers"]):
        block = {"attn_norm": {"scale": lw["attn_norm"]},
                 "ffn_norm": {"scale": lw["ffn_norm"]},
                 "attn": {"q_proj": {"kernel": lw["q"]},
                          "kv_a_proj": {"kernel": lw["kv_a"]},
                          "kv_a_norm": {"scale": lw["kv_a_norm"]},
                          "kv_b_proj": lw["kv_b"],
                          "o_proj": {"kernel": lw["o"]}}}
        if "router" in lw:
            block["moe"] = moe_to_flax(lw)
        else:
            block["mlp"] = {k: {"kernel": lw[k]}
                            for k in ("gate", "up", "down")}
        tree[f"block_{i}"] = block
    return tree


@pytest.fixture(scope="module")
def weights():
    return make_weights(0)


@pytest.fixture(scope="module")
def model():
    return create_model("mla_moe", **SIZES)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(1).integers(0, VOCAB, 40),
                       jnp.int32)


@pytest.fixture(scope="module")
def program_logits(model, weights, tokens):
    return model.apply({"params": to_flax(weights)}, tokens[None])[0]


def gap(a, b) -> float:
    return float(jnp.max(jnp.abs(a - b)))


def test_training_mode_logits_match_the_reference(program_logits, weights,
                                                  tokens):
    want = ref.logits_fn(weights, tokens, DIMS)
    assert float(jnp.max(jnp.abs(want))) > 1.0      # there is something to miss
    assert gap(program_logits, want) < TOL


def test_the_two_copies_of_the_reference_are_one(weights, tokens):
    """``benchmarks/lib/mla_moe_reference.py`` imports nothing of the
    program and is what decides ``correct`` on the chip; this copy is what
    the program's tests compare with."""
    from benchmarks.lib import mla_moe_reference as bench_ref

    assert bench_ref.FAULTS == ref.FAULTS
    for kw in ({}, {"fault": "no_shared"}, {"mode": "fp8"}):
        np.testing.assert_array_equal(
            ref.logits_fn(weights, tokens, DIMS, **kw),
            bench_ref.logits_fn(weights, tokens, DIMS, **kw))


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_planted_fault_moves_the_logits(fault, program_logits, weights,
                                          tokens):
    """Each departure from the equations, planted in the reference, reads
    far outside the tolerance (the least, ``bias_in_weights``, 0.37)."""
    assert gap(program_logits,
               ref.logits_fn(weights, tokens, DIMS, fault=fault)) > 1e3 * TOL


def test_the_float8_control_moves_the_logits(program_logits, weights, tokens):
    assert gap(program_logits,
               ref.logits_fn(weights, tokens, DIMS, mode="fp8")) > 1e3 * TOL


def slot_apply(kv, tokens, positions, **kw):
    """The served module over the table as it stands: what a step of the
    cache computes, with the logits kept."""
    return kv.dm.apply({"params": kv.params, "cache": kv.cache}, tokens,
                       train=False, positions=positions, mutable=["cache"],
                       **kw)


def test_prefill_then_decode_logits_match_the_full_forward(model, weights,
                                                           tokens):
    """A prompt through ``insert`` (the batched expanded prefill) and eight
    rounds of ``advance`` (the absorbed step): at every position from the
    prompt's last on, the logits the served module computes over the table
    the real programs left are the reference's full forward over prompt
    and served tokens, and their argmax is the token the program gave."""
    lp, new = 21, 8
    kv = SlotKVCache(model, to_flax(weights), 4, prefill_bucket=8)
    sub = jax.tree.map(lambda t: jnp.zeros_like(t[:1]), kv.cache)
    slot, first = kv.insert(np.asarray(tokens[:lp]), slot=2)
    assert slot == 2 and kv.prefill_tokens_padded == 32
    padded = jnp.zeros((1, 32), jnp.int32).at[0, :lp].set(tokens[:lp])
    logits, _ = kv.dm.apply(
        {"params": kv.params, "cache": sub}, padded, train=False,
        positions=jnp.arange(32)[None], prompt_len=jnp.asarray([lp]),
        mutable=["cache"])
    served, rows = [first], [logits[0, -1]]
    for _ in range(new):
        logits, _ = slot_apply(kv, jnp.asarray(kv.tokens)[:, None],
                               jnp.asarray(kv.lengths)[:, None])
        rows.append(logits[slot, -1])
        served.append(int(kv.advance()[slot]))
    seq = jnp.concatenate([tokens[:lp], jnp.asarray(served[:-1], jnp.int32)])
    want = ref.logits_fn(weights, seq, DIMS)[lp - 1:]
    got = jnp.stack(rows)
    assert gap(got, want) < TOL
    assert [int(t) for t in jnp.argmax(got, -1)] == served
    assert kv.lengths[slot] == lp + new


def test_absorbed_and_expanded_attention_are_one_function(model, weights,
                                                          tokens):
    """The same block of tokens from position 0 on an empty table: as a
    prefill (expanded, attends within the block) and as a token block of
    the decode mode (absorbed, attends to the latents it has just
    written).  Same table afterwards (the first layer's rows bit for bit,
    the later ones within TOL: they follow the attention before them),
    same logits at every position as the training-mode forward."""
    t = 24
    kv = SlotKVCache(model, to_flax(weights), 1)
    block, pos = tokens[None, :t], jnp.arange(t)[None]
    absorbed, upd_a = slot_apply(kv, block, pos)
    expanded, upd_e = slot_apply(kv, block, pos,
                                 prompt_len=jnp.asarray([t]))
    full = model.apply({"params": to_flax(weights)}, block)
    assert gap(absorbed, full) < TOL
    assert gap(expanded[:, -1], full[:, -1]) < TOL
    for a, e in zip(jax.tree.leaves(upd_a["cache"]),
                    jax.tree.leaves(upd_e["cache"])):
        assert gap(a, e) < TOL
        assert float(jnp.abs(a[:, :t]).min()) > 0 and not a[:, t:].any()
    first = lambda upd: upd["cache"]["block_0"]["attn"]["cached_latent"]
    np.testing.assert_array_equal(first(upd_a), first(upd_e))


def test_the_table_holds_latents_only(model, weights):
    """A token a layer: the latent (rank 32) and one rotated key head (8),
    float32 here; no per-head keys or values (4 heads x (24 + 16) each)."""
    kv = SlotKVCache(model, to_flax(weights), 4)
    per_token = LAYERS * (RANK + DR) * 4
    assert kv.kv_bytes_per_slot() == per_token * SIZES["max_len"]
    assert kv.counters()["cache_bytes_per_token"] == per_token
    assert {leaf.shape for leaf in jax.tree.leaves(kv.cache)} == {
        (4, 64, RANK), (4, 64, DR)}
    half = SlotKVCache(model, to_flax(weights), 4, kv_dtype=jnp.bfloat16)
    assert half.kv_bytes_per_slot() == per_token * SIZES["max_len"] // 2


def requests():
    rng = np.random.default_rng(5)
    return [Request(rid=i, prompt=rng.integers(0, VOCAB, lp, dtype=np.int32),
                    max_new_tokens=new, arrival_s=0.0)
            for i, (lp, new) in enumerate(
                [(5, 6), (17, 3), (9, 8), (30, 5), (12, 1), (3, 7)])]


def test_the_batcher_serves_each_request_as_if_alone(model, weights):
    """Six requests of mixed length through three slots: continuous
    batching changes nobody's tokens; the spans say which prefill ran and
    what the rounds' routing touched."""
    params = to_flax(weights)
    alone = {}
    for req in requests():
        one = SlotKVCache(model, params, 1, prefill_bucket=8)
        alone[req.rid] = ContinuousBatcher(one).run([req])[
            "results"][0].tokens
    kv = SlotKVCache(model, params, 3, prefill_bucket=8)
    summary = ContinuousBatcher(kv).run(requests())
    together = {r.rid: r.tokens for r in summary["results"]}
    assert together == alone
    assert [len(together[r.rid]) for r in requests()] == [6, 3, 8, 5, 1, 7]

    window = recorder().records(root="serve_run")
    prefills = [r for r in window if r["name"] == "prefill"]
    assert len(prefills) == 6
    assert {r["attrs"]["form"] for r in prefills} == {"batched"}
    steps = [r for r in window if r["name"] == "decode_step"]
    assert steps and all(
        1 <= r["attrs"]["experts_touched"] <= EXPERTS
        and 1 <= r["attrs"]["expert_load_max"] <= r["attrs"]["active"]
        for r in steps)
    # a token chooses TOP_K experts in each of the 2 expert layers: the 76
    # prompt tokens, and each request's tokens but the last as it is fed
    root = window[0]["attrs"]
    assert root["cache_bytes_per_token"] == LAYERS * (RANK + DR) * 4
    assert root["expert_assignments"] == (76 + 24) * 2 * TOP_K


def test_analyze_serve_prints_the_tables_counters(model, weights, tmp_path):
    from distributed_tensorflow_tpu.observability.analyze import (
        read_jsonl, render_waterfall_text, serve_waterfall)
    from distributed_tensorflow_tpu.observability.trace import Tracer

    path = tmp_path / "t.jsonl"
    with Tracer(path=path) as tracer:
        kv = SlotKVCache(model, to_flax(weights), 2, prefill_bucket=8)
        ContinuousBatcher(kv, tracer=tracer).run(requests()[:2])
    wf = serve_waterfall(read_jsonl(str(path)))
    assert wf["windows"] == [{**wf["windows"][0], "offered": 2, "slots": 2,
                              "cache_bytes_per_token": 480,
                              "state_bytes_per_slot": 0,
                              "expert_assignments": (22 + 7) * 2 * TOP_K}]
    assert ("480 bytes a token, 0 bytes of state a slot, 232 expert "
            "assignments") in render_waterfall_text(wf)


def test_a_gpt_window_reports_a_batched_prefill_and_no_routing():
    gpt = create_model("gpt", vocab_size=64, hidden=32, layers=1, heads=2,
                       ffn=64, max_len=32)
    params = gpt.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                      train=False)["params"]
    kv = SlotKVCache(gpt, params, 2)
    ContinuousBatcher(kv).run([Request(
        rid=0, prompt=np.arange(5, dtype=np.int32), max_new_tokens=3,
        arrival_s=0.0)])
    window = recorder().records(root="serve_run")
    assert [r["attrs"]["form"] for r in window
            if r["name"] == "prefill"] == ["batched"]
    assert all("experts_touched" not in r["attrs"] for r in window)
    assert window[0]["attrs"]["expert_assignments"] == 0
    assert kv.last_routing is None


# ------------------------------------------------------- the expert layer

def skewed_layer(weights):
    """The second block's expert layer with a choice bias that sends every
    token to expert 3 first (no capacity: all 48 tokens are computed)."""
    w = dict(weights["layers"][1])
    w["choice_bias"] = w["choice_bias"].at[3].set(10.0)
    return w


def moe_layer(**kw):
    return DroplessMoE(num_experts=EXPERTS, top_k=TOP_K, hidden=EXPERT_FFN,
                       shared_hidden=2 * EXPERT_FFN, routed_scale=2.448, **kw)


def test_the_expert_layer_drops_nothing_under_a_skewed_router(weights):
    w = skewed_layer(weights)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((48, H)),
                    jnp.float32)
    assert bool(jnp.all(ref.route(x, w, DIMS)[:, 3] > 0))
    got, sown = moe_layer().apply({"params": moe_to_flax(w)}, x,
                                  mutable=["intermediates"])
    assert gap(got, ref.experts(x, w, DIMS)) < TOL
    choice = sown["intermediates"]["expert_choice"][0]
    assert choice.shape == (48, TOP_K) and bool(jnp.all(choice[:, 0] == 3))


@pytest.mark.parametrize("shares", [[(0, 16)], [(0, 8), (8, 8)],
                                    [(0, 3), (3, 1), (4, 12)]])
def test_the_shares_of_the_experts_add_up_to_the_layer(weights, shares):
    """What disjoint shares of the experts give, each routing over all 16
    and computing its own experts' part, with the shared expert (which
    every share computes alike) counted once, is the whole layer of the
    uncut reference.  The benchmark's configuration holds one share of
    all."""
    w = skewed_layer(weights)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((20, H)),
                    jnp.float32)
    shared = ref._swiglu(x, w["shared_gate"], w["shared_up"],
                         w["shared_down"], "f32")
    total = jnp.zeros_like(x)
    for first, count in shares:
        params = moe_to_flax(w)
        for name in ("w_gate", "w_up", "w_down"):
            params[name] = params[name][first:first + count]
        part = moe_layer(held=(first, count)).apply({"params": params}, x)
        assert gap(part, ref.experts(x, w, DIMS, held=(first, count))) < TOL
        total = total + part - shared
    assert gap(total + shared, ref.experts(x, w, DIMS)) < TOL


def test_the_choice_margin_is_the_step_between_chosen_and_not(weights):
    w = weights["layers"][2]
    x = jnp.asarray(np.random.default_rng(7).standard_normal((9, H)),
                    jnp.float32)
    scores = jax.nn.sigmoid(x @ w["router"]) + w["choice_bias"]
    ranked = np.sort(np.asarray(scores), axis=-1)[:, ::-1]
    np.testing.assert_allclose(ref.choice_margin(x, w, DIMS),
                               ranked[:, TOP_K - 1] - ranked[:, TOP_K],
                               atol=1e-6)
    tok = jnp.arange(9, dtype=jnp.int32)
    hidden, least = ref.hidden_fn(weights, tok, DIMS, margins=True)
    np.testing.assert_array_equal(hidden, ref.hidden_fn(weights, tok, DIMS))
    assert least.shape == (9,) and bool(jnp.all((least >= 0) & (least < 1)))


def test_tokens_marked_invalid_reach_no_expert(weights):
    """Pad rows of a prefill bucket: the routed part is zero for them and
    the other rows are as without the mask."""
    w = weights["layers"][1]
    x = jnp.asarray(np.random.default_rng(4).standard_normal((12, H)),
                    jnp.float32)
    valid = jnp.arange(12) < 7
    layer = DroplessMoE(num_experts=EXPERTS, top_k=TOP_K, hidden=EXPERT_FFN,
                        routed_scale=2.448)        # no shared expert
    params = {k: v for k, v in moe_to_flax(w).items() if k != "shared"}
    masked = layer.apply({"params": params}, x, valid)
    plain = layer.apply({"params": params}, x)
    assert gap(masked[:7], plain[:7]) == 0.0
    assert not masked[7:].any() and bool(jnp.abs(plain[7:]).max() > 0.01)


# ------------------------------------- what is not built for this model

@pytest.mark.parametrize("feature, call", [
    ("paged layout", lambda m, p: SlotKVCache(m, p, 2, kv_layout="paged")),
    ("prefix pool", lambda m, p: SlotKVCache(m, p, 2, prefix_cache_blocks=4)),
    ("int8 storage", lambda m, p: SlotKVCache(m, p, 2, kv_dtype="int8")),
    ("chunked", lambda m, p: SlotKVCache(m, p, 2).begin_insert([1, 2, 3])),
    ("multi-step", lambda m, p: SlotKVCache(m, p, 2).dispatch_multi(2)),
    ("verify", lambda m, p: SlotKVCache(m, p, 2).verify_block(
        np.zeros((2, 2), np.int32))),
    ("handoff", lambda m, p: SlotKVCache(m, p, 2).extract_handoff(0)),
    ("handoff", lambda m, p: SlotKVCache(m, p, 2).restore_handoff({})),
], ids=["paged", "prefix_pool", "int8", "chunk_resume", "multi_step",
        "verify", "handoff_out", "handoff_in"])
def test_what_is_not_built_for_a_batched_prefill_says_so(model, weights,
                                                         feature, call):
    with pytest.raises(NotImplementedError, match=feature):
        call(model, to_flax(weights))


# ------------------------------------- GPTLM's slot programs: unchanged

class _ProgramProbe(SlotKVCache):
    """Keeps what ``SlotKVCache`` hands to ``jax.jit`` (as
    tests/test_tpu_compile.py does)."""

    def _jit(self, fn, name, **jit_kwargs):
        self.__dict__.setdefault("programs", {})[name] = (fn, jit_kwargs)
        return super()._jit(fn, name, **jit_kwargs)


def lowered_ops(kv, name, *args) -> collections.Counter:
    fn, jit_kwargs = kv.programs[name]
    text = jax.jit(fn, **jit_kwargs).lower(*args).as_text()
    return collections.Counter(re.findall(r"= \"?(stablehlo\.[a-z_]+)", text))


KEPT = ("while", "scatter", "dot_general", "dynamic_update_slice",
        "dynamic_slice", "gather")


@pytest.mark.parametrize("program, total, kept", [
    ("kv_decode_step", 422, (0, 0, 17, 0, 1, 2)),
    ("kv_prefill_batched_l8", 499, (0, 0, 17, 8, 5, 3)),
])
def test_gpt_slot_programs_lower_to_what_they_did(program, total, kept):
    """The operation counts of the lowered programs of a tiny ``GPTLM``
    (2 layers, 4 slots x 32).  The step's are PR 32's: the 4 scatters
    that wrote the new K/V rows are 4 selects over the position axis
    (``models/gpt.select_slot_row``; 407 operations with the scatters,
    unmoved since commit 27a7b5f, before the table learnt a second
    model), the same 17 matrix products, no ``while``, nothing new
    returned.  The prefill's are PR 30's block program: no ``while``
    over the prompt and no scatter (the scan of the
    one-token step had one ``while``, 4 scatters and 489 operations), the
    same 17 matrix products, and the block's K and V in 4 of the 8
    ``dynamic_update_slice`` (the other 4 put the slot's leaves back)."""
    gpt = create_model("gpt", vocab_size=64, hidden=32, layers=2, heads=2,
                       ffn=64, max_len=32)
    params = gpt.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
                      train=False)["params"]
    kv = _ProgramProbe(gpt, params, 4)
    kv._prefill(8)
    vec, key = jnp.zeros((4,), jnp.int32), jax.random.key(0)
    args = {"kv_decode_step": (vec, vec, vec.astype(bool), key),
            "kv_prefill_batched_l8": (jnp.int32(0),
                                      jnp.zeros((8,), jnp.int32),
                                      jnp.int32(3), key)}[program]
    ops = lowered_ops(kv, program, params, kv.cache, *args)
    assert sum(ops.values()) == total
    assert tuple(ops[f"stablehlo.{k}"] for k in KEPT) == kept
    assert set(kv.programs) == {"kv_decode_step", "kv_prefill_batched_l8"}


def test_the_new_models_programs_have_their_own_names(model, weights):
    kv = _ProgramProbe(model, to_flax(weights), 2, prefill_bucket=8)
    kv.insert(np.arange(1, 12, dtype=np.int32))
    assert set(kv.programs) == {"kv_decode_step_routed",
                                "kv_prefill_batched_l16"}
    ops = lowered_ops(kv, "kv_prefill_batched_l16", kv.params, kv.cache,
                      jnp.int32(0), jnp.zeros((16,), jnp.int32),
                      jnp.int32(11), jax.random.key(0))
    assert ops["stablehlo.while"] == 0      # one batched call, no scan
    builds = [r["attrs"]["program"] for r in recorder().records()
              if r["name"] == "program_build"]
    assert "kv_prefill_batched_l16" in builds


# ------------------------------------------------------------- the rest

def test_rope_rotates_adjacent_pairs_by_position():
    from distributed_tensorflow_tpu.models.mla_moe import rope_adjacent

    x = jnp.asarray(np.random.default_rng(6).standard_normal((1, 5, 2, 8)),
                    jnp.float32)
    pos = jnp.asarray([[0, 1, 2, 3, 1000]])
    got = rope_adjacent(x, pos, 1e6)
    np.testing.assert_array_equal(got[0, 0], x[0, 0])       # position 0
    inv = 1e6 ** (-np.arange(4) / 4)
    z = (np.asarray(x[..., 0::2]) + 1j * np.asarray(x[..., 1::2])) \
        * np.exp(1j * np.asarray(pos)[..., None, None] * inv)
    np.testing.assert_allclose(got[..., 0::2], z.real, atol=2e-5)
    np.testing.assert_allclose(got[..., 1::2], z.imag, atol=2e-5)


def plain_causal_attention(q, k, v, scale):
    """All scores at once, one softmax a row: what the blocked form has to
    equal."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * scale
    length = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((length, length), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                      precision="highest")


def attention_inputs(length, seed=8, d_qk=12, d_v=5):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((2, length, 3, d)), jnp.float32)
            for d in (d_qk, d_qk, d_v)]


# (length, query block, key block): a key block that is a multiple of the
# query block (the module's 4,096 over 512), one that is not (the diagonal
# piece then starts inside the query block and rows before it see no key
# of it), lengths that are a multiple of neither, keys that fit one piece
@pytest.mark.parametrize("length, block, key_block", [
    (67, 8, 16), (67, 8, 12), (45, 16, 8), (64, 8, 16), (23, 8, 5),
    (29, 8, 32), (7, 8, 4)])
def test_blocked_attention_is_plain_causal_attention(length, block,
                                                     key_block):
    from distributed_tensorflow_tpu.models.mla_moe import (
        causal_attention_blocked)

    q, k, v = attention_inputs(length)
    with jax.default_matmul_precision("highest"):
        got = causal_attention_blocked(q, k, v, 0.3, block=block,
                                       key_block=key_block)
    want = plain_causal_attention(q, k, v, 0.3)
    assert got.shape == want.shape == (2, length, 3, 5)
    assert gap(got, want) < TOL
    assert bool(jnp.isfinite(got).all())


def test_blocked_attention_has_plain_attentions_gradient():
    """The carried softmax is differentiated like any other code (the
    expanded form is the training-mode forward); the running maximum
    carries no gradient and the result does not depend on it."""
    from distributed_tensorflow_tpu.models.mla_moe import (
        causal_attention_blocked)

    q, k, v = attention_inputs(45, seed=9)
    w = jnp.asarray(np.random.default_rng(10).standard_normal((2, 45, 3, 5)),
                    jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda q, k, v: causal_attention_blocked(
            q, k, v, 0.3, block=8, key_block=12)), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: plain_causal_attention(
        q, k, v, 0.3)), (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert bool(jnp.isfinite(g).all())
        assert gap(g, r) < TOL


def test_keys_that_fit_one_block_take_the_one_softmax_path():
    """The module's constants: a length up to ``ATTN_KEY_BLOCK`` lowers to
    one softmax a query block and no carried state, whatever the key
    block; beyond it no score tile is wider than the key block."""
    from distributed_tensorflow_tpu.models import mla_moe

    assert mla_moe.ATTN_KEY_BLOCK % mla_moe.ATTN_QUERY_BLOCK == 0
    q, k, v = (jax.ShapeDtypeStruct((1, 40, 2, d), jnp.float32)
               for d in (12, 12, 5))

    def text(key_block):
        return jax.jit(lambda q, k, v: mla_moe.causal_attention_blocked(
            q, k, v, 0.3, block=8, key_block=key_block)).lower(
                q, k, v).as_text()

    def widths(t):      # of the (B, H, query block, keys) score tiles
        return {int(w) for w in re.findall(r"tensor<1x2x8x(\d+)xf32>", t)}

    short = text(40)
    assert short == text(mla_moe.ATTN_KEY_BLOCK)
    assert short.count("stablehlo.exponential") == 5     # one a query block
    assert {8, 16, 24, 32, 40} <= widths(short)
    assert max(widths(text(16))) == 16


def test_bfloat16_weights_are_held_and_served(model, weights, tokens):
    """The serving configuration: weights HELD in bfloat16, products in
    bfloat16, logits float32 and near the float32 program's (bfloat16
    keeps 8 bits: a logit of size 3 moves by a few hundredths, and by a
    step where a token's fourth and fifth expert change places)."""
    half = model.clone(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = jax.tree.map(lambda t: t.astype(jnp.bfloat16)
                          if t.dtype == jnp.float32 and t.ndim > 1 else t,
                          to_flax(weights))
    shapes = jax.eval_shape(lambda: half.init(jax.random.key(0),
                                              tokens[None, :4]))["params"]
    assert {str(s.dtype) for s in jax.tree.leaves(shapes)} == {
        "bfloat16", "float32"}                      # float32: the choice bias
    logits = half.apply({"params": params}, tokens[None])[0]
    assert logits.dtype == jnp.float32
    off = jnp.abs(logits - ref.logits_fn(weights, tokens, DIMS))
    assert float(jnp.median(off)) < 0.02 and float(off.max()) < 1.0
    kv = SlotKVCache(half, params, 2, kv_dtype=jnp.bfloat16)
    slot, _ = kv.insert(np.asarray(tokens[:9]))
    assert kv.advance().shape == (2,) and kv.lengths[slot] == 10


def test_the_old_layer_points_to_the_dropless_one():
    from distributed_tensorflow_tpu.models.moe import MoELayer

    with pytest.raises(ValueError, match="DroplessMoE"):
        MoELayer(num_experts=4, router_top_k=3).init(
            jax.random.key(0), jnp.zeros((8, 16)))


def test_modes_that_do_not_exist_are_refused(model, weights, tokens):
    params = {"params": to_flax(weights)}
    with pytest.raises(ValueError, match="decode_slots"):
        model.clone(decode=True).apply(params, tokens[None])
    with pytest.raises(ValueError, match="positions"):
        model.apply(params, tokens[None], positions=jnp.arange(40)[None])
    with pytest.raises(ValueError, match="max_len"):
        model.apply(params, jnp.zeros((1, 65), jnp.int32))
    with pytest.raises(NotImplementedError, match="tensor-parallel"):
        model.slot_decode_clone(partition_model=True)
