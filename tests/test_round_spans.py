"""The inside of a decode round: ``SlotKVCache.advance`` leaves a
``step_dispatch`` and a ``token_fetch`` record under the scheduler's
``decode_step``, for every served model family, and
leaves nothing (and serves the same tokens) under ``NULL_TRACER``."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_tensorflow_tpu.models import create_model
from distributed_tensorflow_tpu.observability import NULL_TRACER, recorder
from distributed_tensorflow_tpu.serving import (
    ContinuousBatcher, Request, SlotKVCache, VirtualClock)

GPT = dict(vocab_size=64, hidden=32, layers=2, heads=4, ffn=64, max_len=64)
TINY = dict(vocab_size=64, max_len=64)
# family -> (registered model, its sizes, table arguments)
FAMILIES = {
    "gpt": ("gpt", GPT, {}),
    "mla_moe": ("mla_moe", TINY, {}),
    "hybrid_ssm": ("hybrid_ssm", TINY, {}),
    "window_moe": ("window_moe", TINY, {}),
}


def _requests():
    # two slots, three requests due at once: the third is admitted when
    # the shortest has finished, so rounds follow rounds and prefills
    rng = np.random.default_rng(3)
    return [Request(rid=i, prompt=rng.integers(0, 64, lp, dtype=np.int32),
                    max_new_tokens=new, arrival_s=0.0)
            for i, (lp, new) in enumerate([(5, 9), (7, 4), (6, 5)])]


def _tokens(summary):
    return {r.rid: list(r.tokens) for r in summary["results"]}


@pytest.fixture(scope="module", params=list(FAMILIES))
def window(request) -> dict:
    """One recorded window a family: both tests read it, and the second
    serves the same requests again through the same table."""
    name, sizes, table = FAMILIES[request.param]
    model = create_model(name, **sizes)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    kv = SlotKVCache(model, params, 2, **table)
    summary = ContinuousBatcher(kv, clock=VirtualClock()).run(_requests())
    return {"kv": kv, "tokens": _tokens(summary), "summary": summary,
            "records": recorder().records(root="serve_run")}


def test_a_round_has_its_dispatch_and_its_fetch_inside(window):
    recs = window["records"]
    rounds = [r for r in recs if r["name"] == "decode_step"]
    assert len(rounds) == window["summary"]["decode_iterations"] >= 6
    inner = [r for r in recs if r["name"] in ("step_dispatch", "token_fetch")]
    assert len(inner) == 2 * len(rounds)
    assert all(r["rid"] is None and not r["attrs"] for r in inner)
    for rnd in rounds:
        kids = sorted((r for r in recs if r["parent"] == rnd["id"]),
                      key=lambda r: r["start"])
        assert [k["name"] for k in kids] == ["step_dispatch", "token_fetch"]
        dispatch, fetch = kids
        assert (rnd["start"] <= dispatch["start"] <= dispatch["end"]
                <= fetch["start"] <= fetch["end"] <= rnd["end"])
    assert not any(r["name"] == "decode" for r in recs)


def test_the_inert_tracer_serves_the_same_tokens_and_records_nothing(window):
    kv, ring = window["kv"], recorder().records()
    summary = ContinuousBatcher(kv, tracer=NULL_TRACER,
                                clock=VirtualClock()).run(_requests())
    assert kv.tracer is NULL_TRACER
    assert _tokens(summary) == window["tokens"]
    after = recorder().records()
    assert len(after) == len(ring) and after[-1] is ring[-1]
