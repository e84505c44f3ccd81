"""The slot contract (serving/kv_cache.py ``SlotContract``): every served
family's declaration holds against what its module does, and a decoder that
no file of the package names is served through it alone."""

import functools
import inspect
import json
from pathlib import Path

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_tensorflow_tpu.models import create_model
from distributed_tensorflow_tpu.parallel import mesh as meshlib
from distributed_tensorflow_tpu.serving import (
    ContinuousBatcher, Request, SlotContract, SlotKVCache, VirtualClock)

MAX_LEN = 64
TINY = dict(vocab_size=64, max_len=MAX_LEN)
# family -> its sizes (GPT-2 computes in bfloat16 over float32 weights, so
# its dtype rule has leaves to narrow)
FAMILIES = {
    "gpt": dict(TINY, hidden=32, layers=2, heads=4, ffn=64,
                dtype="bfloat16"),
    "mla_moe": TINY,
    "hybrid_ssm": TINY,
    "window_moe": TINY,
    "jamba": TINY,
    "keye": TINY,
}
# the span attributes each family reports (round, prefill): a round's are
# read by the benchmark's metrics (benchmarks/metrics), a prefill's by the
# family's own tests; routing load comes from the routed step, not a hook
REPORTS = {
    "gpt": ({"rows_read"}, set()),
    "mla_moe": (set(), set()),
    "hybrid_ssm": (set(), set()),
    "window_moe": ({"past_window"}, {"ring_rows"}),
    "jamba": (set(), set()),
    "keye": ({"index_rows", "attended_rows"},
             {"index_pairs", "attended_pairs"}),
}
CHECKS = ["state", "rings", "active", "experts", "dtype", "span_names"]


def _abstract_params(model):
    return nn.meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32),
        train=False))["params"])


@functools.cache
def _family(name: str) -> dict:
    """The training module, its abstract float32 params, the served clone,
    the clone's abstract variables, and the contract."""
    model = create_model(name, **FAMILIES[name])
    dm = model.slot_decode_clone()
    dummy = jnp.zeros((4, 1), jnp.int32)
    variables = jax.eval_shape(lambda: dm.init(
        jax.random.key(0), dummy, train=False, positions=dummy))
    return {"model": model, "params": _abstract_params(model), "dm": dm,
            "variables": variables, "contract": dm.slot_contract()}


def _leaves(cache) -> dict[str, list]:
    out: dict[str, list] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        out.setdefault(path[-1].key, []).append(leaf)
    return out


def _decode_step_attrs() -> set[str]:
    """Every string a metric of the benchmark names for the
    ``decode_step`` span: the span attributes (and other names) it reads."""
    root = Path(__file__).resolve().parents[1] / "benchmarks" / "metrics"
    names: set[str] = set()
    for f in root.glob("*.json"):
        spec = json.loads(f.read_text())
        if spec.get("span") == "decode_step":
            names |= {v for v in spec.values() if isinstance(v, str)}
    return names


def _check(name: str, check: str) -> None:
    fam = _family(name)
    c, dm = fam["contract"], fam["dm"]
    leaves = _leaves(fam["variables"]["cache"])
    if check == "state":
        # declared state and rings are leaves of the cache, apart; every
        # other leaf is per-position rows with the table's max_len
        assert set(c.slot_state) <= set(leaves)
        assert set(c.slot_rings) <= set(leaves)
        assert not set(c.slot_state) & set(c.slot_rings)
        rows = set(leaves) - set(c.slot_state) - set(c.slot_rings)
        assert rows
        for key in rows:
            assert all(MAX_LEN in leaf.shape[1:] for leaf in leaves[key]), key
        assert all(isinstance(v, bool) for v in c.slot_state.values())
    elif check == "rings":
        for key, ring in c.slot_rings.items():
            assert all(leaf.shape[1] == ring for leaf in leaves[key]), key
            assert ring < MAX_LEN
        assert bool(c.slot_rings) == (name == "window_moe")
    elif check == "active":
        takes = "active" in inspect.signature(type(dm).__call__).parameters
        assert c.takes_active == takes
    elif check == "experts":
        # the routing choices the step sows: one leaf an expert layer, a
        # token's choices last; the router is as wide as declared
        _, upd = jax.eval_shape(lambda v: dm.apply(
            v, jnp.ones((4, 1), jnp.int32), train=False,
            positions=jnp.full((4, 1), 3), mutable=["cache", "intermediates"],
            **({"active": jnp.ones(4, bool)} if c.takes_active else {})),
            fam["variables"])
        sown = jax.tree.leaves(upd.get("intermediates", {}))
        assert len(sown) == c.expert_layers
        assert (c.expert_layers == 0) == (name in ("gpt", "jamba"))
        for choice in sown:
            assert choice.shape[-1] == c.experts_per_token
            assert jnp.issubdtype(choice.dtype, jnp.integer)
        if c.expert_layers:
            assert c.num_experts in {
                leaf.shape[-1]
                for leaf in jax.tree.leaves(fam["variables"]["params"])}
    elif check == "dtype":
        given = fam["params"]
        kv = SlotKVCache(fam["model"], given, 2)
        held = kv.params
        rule = c.step_param_dtype
        assert (rule is None) == (name != "gpt")
        narrowed = 0
        for (path, g), h in zip(jax.tree_util.tree_flatten_with_path(given)[0],
                                jax.tree.leaves(held)):
            want = None if rule is None else rule(
                tuple(k.key for k in path))
            if want is None or jnp.dtype(g.dtype).itemsize \
                    <= jnp.dtype(want).itemsize:
                assert h is g, path
            else:
                assert h.dtype == want, path
                narrowed += 1
        assert (narrowed > 0) == (rule is not None)
    elif check == "span_names":
        lengths = np.array([3, 40, 0, 7], np.int32)
        advancing = np.array([True, True, False, True])
        round_names, prefill_names = REPORTS[name]
        assert set(c.round_attrs(lengths, advancing)) == round_names
        assert set(c.prefill_attrs(5)) == prefill_names
        assert round_names <= _decode_step_attrs()


@pytest.mark.parametrize(
    "family, check", [(f, c) for f in FAMILIES for c in CHECKS],
    ids=[f"{f}-{c}" for f in FAMILIES for c in CHECKS])
def test_the_slot_contract_holds(family, check):
    _check(family, check)


@pytest.mark.parametrize("family", list(FAMILIES))
def test_only_a_declared_tensor_parallel_table_splits_its_heads(family):
    """Under a data x model mesh the table shards its kv-head dim over
    ``model`` exactly where the contract says the served module splits
    its heads: GPT-2 built partitioned, no other family.  No family's
    clone takes a request to split that its contract does not declare."""
    sizes = FAMILIES[family]
    if family == "gpt":
        sizes = dict(sizes, partition_model=True)
    model = create_model(family, **sizes)
    mesh = meshlib.create_mesh(8, axis_names=("data", "model"), shape=(4, 2))
    held = NamedSharding(mesh, P())     # committed: used in place
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=held),
        _abstract_params(model))
    kv = SlotKVCache(model, params, 4, mesh=mesh)
    specs = [leaf.sharding.spec for leaf in jax.tree.leaves(kv.cache)]
    split = [meshlib.MODEL_AXIS in tuple(spec) for spec in specs]
    assert kv.contract.tensor_parallel == (family == "gpt")
    assert all(split) if kv.contract.tensor_parallel else not any(split)
    assert all(spec[0] == meshlib.DATA_AXIS for spec in specs)
    with pytest.raises(TypeError, match="partition_model"):
        model.slot_decode_clone(partition_model=True)


# ------------------------------------------------ a decoder of this file

VOCAB, HIDDEN = 32, 16


class ToyDecoder(nn.Module):
    """A decoder of one per-slot state leaf, ``acc`` ``(slots, hidden)``:
    ``acc <- acc / 2 + embed(token)``, logits ``tanh(acc) @ out``.  In
    training mode it runs a whole sequence from a zero state; in the slot
    mode a prefill starts the slot's state from zero over its prompt (pads
    inert) and a step advances the ``active`` slots only."""

    max_len: int = 32
    decode: bool = False
    decode_slots: bool = False

    def slot_decode_clone(self, *, mesh=None,
                          kv_quant: bool = False) -> "ToyDecoder":
        return self.clone(decode=True, decode_slots=True)

    def slot_contract(self) -> SlotContract:
        return SlotContract(takes_active=True, slot_state={"acc": False})

    @nn.compact
    def __call__(self, token_ids, train: bool = False, positions=None,
                 prompt_len=None, active=None):
        embed = self.param("embed", nn.initializers.normal(1.0),
                           (VOCAB, HIDDEN))
        out = self.param("out", nn.initializers.normal(1.0), (HIDDEN, VOCAB))
        b, t = token_ids.shape

        def run(acc, xs):
            tok, real = xs
            new = acc / 2 + embed[tok]
            acc = jnp.where(real[:, None], new, acc)
            return acc, jnp.tanh(acc) @ out

        real = jnp.ones((t, b), bool)
        if not self.decode_slots:
            _, logits = jax.lax.scan(run, jnp.zeros((b, HIDDEN)),
                                     (token_ids.T, real))
            return logits.transpose(1, 0, 2)
        acc = self.variable("cache", "acc", jnp.zeros, (b, HIDDEN),
                            jnp.float32)
        if prompt_len is not None:      # a prefill from a zero state
            real = jnp.arange(t)[:, None] < prompt_len[None, :]
            start = jnp.zeros((b, HIDDEN))
        else:                           # one token a slot
            if active is not None:
                real = real & active[None]
            start = acc.value
        last, logits = jax.lax.scan(run, start, (token_ids.T, real))
        acc.value = last
        if prompt_len is not None:
            return (jnp.tanh(last) @ out)[:, None, :]
        return logits.transpose(1, 0, 2)


def _greedy(model, params, prompt, n: int) -> list[int]:
    """The decoder's own unbatched forward, one whole sequence a token."""
    seq = list(prompt)
    for _ in range(n):
        logits = model.apply({"params": params},
                             jnp.asarray([seq], jnp.int32))
        seq.append(int(jnp.argmax(logits[0, -1])))
    return seq[len(prompt):]


def test_a_decoder_no_file_of_the_package_names_is_served():
    model = ToyDecoder()
    params = model.init(jax.random.key(1), jnp.zeros((1, 4), jnp.int32))[
        "params"]
    kv = SlotKVCache(model, params, 3)
    assert kv.contract.slot_state == {"acc": False}
    prompts = [np.array([3, 1, 4, 1, 5], np.int32), np.array([9, 2], np.int32)]
    got = {}
    for p in prompts:
        slot, first = kv.insert(p)
        got[slot] = [first]
    for _ in range(2):
        toks = kv.advance()
        for slot in got:
            got[slot].append(int(toks[slot]))
    for slot, p in zip(sorted(got), prompts):
        assert got[slot] == _greedy(model, params, p, 3)
    kv.evict(0)
    assert not kv.active[0] and kv.active[1]
    # the freed slot serves a new prompt from a zero state
    slot, first = kv.insert(prompts[1])
    assert slot == 0 and [first, int(kv.advance()[0])] == got[1][:2]
    # a batcher drives it too, with no argument of its own
    summary = ContinuousBatcher(SlotKVCache(model, params, 2),
                                clock=VirtualClock()).run([
        Request(rid=i, prompt=p, max_new_tokens=3, arrival_s=0.0)
        for i, p in enumerate(prompts)])
    assert {r.rid: list(r.tokens) for r in summary["results"]} == {
        i: _greedy(model, params, p, 3) for i, p in enumerate(prompts)}


def test_a_model_that_declares_no_contract_is_refused_by_name():
    class Undeclared(nn.Module):
        max_len: int = 8

        @nn.compact
        def __call__(self, x):
            return x

    with pytest.raises(TypeError, match="Undeclared"):
        SlotKVCache(Undeclared(), {}, 2)
    model = ToyDecoder()
    params = model.init(jax.random.key(1), jnp.zeros((1, 4), jnp.int32))[
        "params"]
    with pytest.raises(NotImplementedError, match="ToyDecoder.*int8"):
        SlotKVCache(model, params, 2, kv_dtype="int8")
