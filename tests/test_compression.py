"""Gradient-compression layer (ISSUE 3): codecs, wire accounting, engine
wiring, and the satellite knobs that ride along.

Covers the codec math, the GSPMD engines (FSDP is pure jit), the
explicit-collective engine variants (sync/async/gossip, whose codecs own a
real shard_map collective) and the Trainer/report/harness plumbing.
"""

import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.data.loaders import (
    Dataset, load_dataset, synthetic_classification)
from distributed_tensorflow_tpu.engines import Trainer
from distributed_tensorflow_tpu.engines.fsdp import FSDPEngine
from distributed_tensorflow_tpu.models import create_model
from distributed_tensorflow_tpu.parallel import compression
from distributed_tensorflow_tpu.parallel import mesh as meshlib


def _vec(n=256, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=(n,)).astype(np.float32))


# ------------------------------------------------------------ codec units

def test_make_codec_resolution():
    assert compression.make_codec("none").name == "none"
    assert compression.make_codec(None).name == "none"
    assert compression.make_codec("bf16").name == "bf16"
    assert compression.make_codec("int8").name == "int8"
    codec = compression.Bf16Codec()
    assert compression.make_codec(codec) is codec  # instance passthrough
    with pytest.raises(ValueError, match="unknown grad_compression"):
        compression.make_codec("fp4")


def test_none_roundtrip_is_identity():
    x = _vec()
    tree = {"w": x, "b": jnp.ones((3,), jnp.int32)}
    out = compression.make_codec("none").roundtrip(tree, rng=jax.random.key(0))
    np.testing.assert_array_equal(out["w"], x)
    np.testing.assert_array_equal(out["b"], tree["b"])


def test_bf16_roundtrip_cast_bounds():
    x = _vec()
    out = compression.make_codec("bf16").roundtrip({"w": x})["w"]
    assert out.dtype == jnp.float32
    # bf16 keeps 8 mantissa bits: relative rounding error <= 2^-8
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=1 / 256)
    # non-f32 leaves pass through untouched (already-narrow or integral)
    half = x.astype(jnp.bfloat16)
    ints = jnp.arange(5, dtype=jnp.int32)
    rt = compression.make_codec("bf16").roundtrip({"h": half, "i": ints})
    np.testing.assert_array_equal(rt["h"], half)
    np.testing.assert_array_equal(rt["i"], ints)


def test_int8_roundtrip_within_one_quantum():
    x = _vec()
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    codec = compression.make_codec("int8")
    # deterministic (no rng): round-to-nearest, error <= scale/2
    det = codec.roundtrip({"w": x})["w"]
    assert float(jnp.abs(det - x).max()) <= scale / 2 + 1e-7
    # stochastic: error <= one quantum
    sto = codec.roundtrip({"w": x}, rng=jax.random.key(1))["w"]
    assert float(jnp.abs(sto - x).max()) <= scale + 1e-7


def test_int8_stochastic_rounding_unbiased_in_expectation():
    """E[decode(encode(x, rng))] == x for stochastic rounding — the
    property that keeps quantization noise from biasing the descent
    direction.  Deterministic given the fixed seed."""
    x = _vec(64, seed=2)
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    keys = jax.random.split(jax.random.key(0), 2048)
    dec = jax.vmap(lambda k: compression._int8_decode(
        *compression._int8_encode(x, k), jnp.float32))(keys)
    mean_err = float(jnp.abs(dec.mean(0) - x).max())
    # per-sample error is <= 1 quantum with variance <= s^2/4; over 2048
    # draws the mean sits within a few percent of a quantum
    assert mean_err < 0.08 * scale
    # round-to-nearest (rng=None) is biased by construction; the stochastic
    # mean must beat a half-quantum systematically
    assert mean_err < scale / 2


def test_wire_bytes_accounting():
    f32 = jnp.zeros((100,), jnp.float32)
    i32 = jnp.zeros((10,), jnp.int32)
    bf = jnp.zeros((8,), jnp.bfloat16)
    assert compression.make_codec("none").wire_bytes([f32]) == 400
    assert compression.make_codec("bf16").wire_bytes([f32]) == 200
    assert compression.make_codec("int8").wire_bytes([f32]) == 104  # + scale
    # integral leaves keep their width under every codec; bf16 leaves are
    # already at (or below) the bf16 wire width
    for name in ("none", "bf16", "int8"):
        assert compression.make_codec(name).wire_bytes([i32]) == 40
    assert compression.make_codec("bf16").wire_bytes([bf]) == 16
    assert compression.make_codec("int8").wire_bytes([bf]) == 12  # 8 + scale


# ------------------------- compressed collectives under vmap emulation
# (jax.vmap with an axis_name implements the same collectives as
# shard_map, so the codec's reduce math is verified on EVERY container —
# including the two-phase int8 layout's padding/chunking — while the
# shard_map renderings below stay guarded)

@pytest.mark.parametrize("size", [64, 61])  # 61: pad-to-chunks tail
@pytest.mark.parametrize("mean", [False, True])
def test_codec_reduce_math_under_vmap(size, mean):
    n = 8
    vals = jnp.asarray(np.random.default_rng(4).normal(
        size=(n, size)).astype(np.float32))
    ref = np.asarray(vals.mean(0) if mean else vals.sum(0))
    op = "all_reduce_mean" if mean else "all_reduce_sum"

    def run(codec):
        def device(x, key):
            return getattr(codec, op)(x, "data", rng=key)

        keys = jax.random.split(jax.random.key(9), n)  # per-device rounding
        return np.asarray(jax.vmap(device, axis_name="data")(vals, keys)[0])

    np.testing.assert_allclose(run(compression.make_codec("none")), ref,
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(run(compression.make_codec("bf16")), ref,
                               rtol=0.05, atol=0.05)
    q = np.abs(np.asarray(vals)).max(axis=1) / 127.0
    tol = 2 * q.sum() / (n if mean else 1)
    assert np.abs(run(compression.make_codec("int8")) - ref).max() \
        <= tol + 1e-6


def test_int8_reduce_accepts_no_rng_under_vmap():
    """rng=None is the documented deterministic-rounding mode — both
    quantization phases must tolerate it (regression: phase 2 once
    fold_in'd the None key)."""
    vals = jnp.asarray(np.random.default_rng(6).normal(
        size=(8, 24)).astype(np.float32))
    codec = compression.make_codec("int8")
    out = jax.vmap(lambda x: codec.all_reduce_sum(x, "data"),
                   axis_name="data")(vals)[0]
    q = np.abs(np.asarray(vals)).max(axis=1) / 127.0
    assert np.abs(np.asarray(out) - np.asarray(vals.sum(0))).max() \
        <= 2 * q.sum() + 1e-6


def test_int8_reduce_unbiased_under_vmap():
    """The two-phase int8 reduce composes two unbiased stochastic
    roundings — averaging the reduced value over many key draws recovers
    the exact sum."""
    n = 8
    vals = jnp.asarray(np.random.default_rng(5).normal(
        size=(n, 32)).astype(np.float32))
    codec = compression.make_codec("int8")

    def one(seed):
        keys = jax.random.split(jax.random.key(seed), n)
        return jax.vmap(
            lambda x, k: codec.all_reduce_sum(x, "data", rng=k),
            axis_name="data")(vals, keys)[0]

    reduced = jax.vmap(one)(jnp.arange(512))
    err = np.abs(np.asarray(reduced.mean(0)) - np.asarray(vals.sum(0)))
    q = np.abs(np.asarray(vals)).max() / 127.0
    assert err.max() < 0.25 * q  # noise ~q/sample shrinks ~sqrt(512)


# ------------------------------------- compressed collectives (shard_map)

@pytest.mark.parametrize("reduce_name", ["all_reduce_sum", "all_reduce_mean"])
def test_compressed_reduce_none_bitwise_and_lossy_close(mesh8, reduce_name):
    from jax.sharding import PartitionSpec as P

    vals = jnp.asarray(
        np.random.default_rng(0).normal(size=(8, 64)).astype(np.float32))

    def run(codec):
        # returned per device: the int8 reduce ends in an all_gather, so
        # its result is replicated in VALUE but shard_map cannot prove it
        # (the engines declare P() for it under check_vma=False).  Every
        # device's copy is compared, which is what that declaration
        # relies on.
        def body(x):
            return getattr(codec, reduce_name)(
                x[0], "data", rng=jax.random.key(3))[None]

        out = np.asarray(jax.jit(jax.shard_map(
            body, mesh=mesh8, in_specs=(P("data"),),
            out_specs=P("data")))(vals))
        np.testing.assert_array_equal(out, np.broadcast_to(out[0], out.shape))
        return out[0]

    exact = run(compression.make_codec("none"))
    ref = vals.sum(0) if reduce_name == "all_reduce_sum" else vals.mean(0)
    np.testing.assert_allclose(np.asarray(exact), np.asarray(ref), rtol=1e-6)

    close = np.asarray(run(compression.make_codec("bf16")))
    np.testing.assert_allclose(close, np.asarray(ref), rtol=0.05, atol=0.05)

    # int8 two-phase reduce: one quantum per sender (phase 1) plus one
    # for the re-quantized sum (phase 2, scale <= sum of sender scales)
    q = np.abs(np.asarray(vals)).max(axis=1) / 127.0
    tol = 2 * q.sum()
    if reduce_name == "all_reduce_mean":
        tol /= vals.shape[0]
    int8 = np.asarray(run(compression.make_codec("int8")))
    assert np.abs(int8 - np.asarray(ref)).max() <= tol + 1e-6


def test_compressed_neighbor_mean_close_to_exact(mesh8):
    from jax.sharding import PartitionSpec as P

    from distributed_tensorflow_tpu.parallel import collectives as coll

    vals = jnp.asarray(
        np.random.default_rng(1).normal(size=(8, 32)).astype(np.float32))

    def run(fn):
        return jax.jit(jax.shard_map(
            lambda x: fn(x), mesh=mesh8,
            in_specs=(P("data"),), out_specs=P("data")))(vals)

    exact = np.asarray(run(lambda x: coll.neighbor_mean(x, "data", 1)))
    none = np.asarray(run(lambda x: compression.make_codec("none")
                          .neighbor_mean(x, "data", 1)))
    np.testing.assert_array_equal(none, exact)
    for name, tol in (("bf16", 0.05), ("int8", 0.1)):
        mixed = np.asarray(run(
            lambda x, n=name: compression.make_codec(n).neighbor_mean(
                x, "data", 1, rng=jax.random.key(5))))
        np.testing.assert_allclose(mixed, exact, rtol=tol, atol=tol)


# --------------------------------------- GSPMD engines (run on any jax)

def _tiny_ds(n=512, split="train"):
    x, y = synthetic_classification((8, 8), 4, n, seed=3, split=split)
    return Dataset(x=x, y=y, num_classes=4, name="tiny", synthetic=True)


def _fsdp_engine(codec, mesh, lr=5e-3):
    return FSDPEngine(create_model("mlp", num_classes=4, hidden=32),
                      mesh=mesh, learning_rate=lr, grad_compression=codec)


def _run_steps(eng, ds, n_steps=3, k=1):
    state = eng.init_state(jax.random.key(0), ds.x[:8])
    batches = [eng.shard_batch(ds.x[i * 32:(i + 1) * 32],
                               ds.y[i * 32:(i + 1) * 32])
               for i in range(n_steps)]
    if k == 1:
        losses = []
        for bx, by in batches:
            state, m = eng.step(state, bx, by)
            losses.append(np.asarray(m["loss"]))
        return np.asarray(losses), jax.device_get(state.params)
    state, m = eng.many_step(state, [b[0] for b in batches],
                             [b[1] for b in batches])
    return np.asarray(m["loss"]), jax.device_get(state.params)


def test_fsdp_none_codec_bitwise_identical_at_k1_and_k8(mesh8):
    """Acceptance: --grad-compression none is bitwise identical to the
    pre-codec path, through both the single step and the scanned drain."""
    ds = _tiny_ds()
    base1, pbase1 = _run_steps(FSDPEngine(
        create_model("mlp", num_classes=4, hidden=32), mesh=mesh8,
        learning_rate=5e-3), ds)
    none1, pnone1 = _run_steps(_fsdp_engine("none", mesh8), ds)
    np.testing.assert_array_equal(base1, none1)
    for a, b in zip(jax.tree.leaves(pbase1), jax.tree.leaves(pnone1)):
        np.testing.assert_array_equal(a, b)
    base8, pbase8 = _run_steps(FSDPEngine(
        create_model("mlp", num_classes=4, hidden=32), mesh=mesh8,
        learning_rate=5e-3), ds, n_steps=8, k=8)
    none8, pnone8 = _run_steps(_fsdp_engine("none", mesh8), ds,
                               n_steps=8, k=8)
    np.testing.assert_array_equal(base8, none8)
    for a, b in zip(jax.tree.leaves(pbase8), jax.tree.leaves(pnone8)):
        np.testing.assert_array_equal(a, b)


def test_fsdp_wire_bytes_halved_and_quartered(mesh8):
    """Acceptance: bf16 halves the reported gradient wire bytes; int8
    quarters them plus one f32 scale per leaf."""
    ds = _tiny_ds(64)
    engines = {name: _fsdp_engine(name, mesh8)
               for name in ("none", "bf16", "int8")}
    states = {name: eng.init_state(jax.random.key(0), ds.x[:8])
              for name, eng in engines.items()}
    raw = engines["none"].grad_collective_bytes_raw(states["none"])
    assert raw > 0
    assert engines["none"].grad_collective_bytes(states["none"]) == raw
    assert engines["bf16"].grad_collective_bytes(states["bf16"]) == raw // 2
    n_leaves = len(jax.tree.leaves(states["int8"].params))
    assert engines["int8"].grad_collective_bytes(states["int8"]) == \
        raw // 4 + 4 * n_leaves
    # raw is codec-independent
    for name in ("bf16", "int8"):
        assert engines[name].grad_collective_bytes_raw(states[name]) == raw


# round 20 fast-lane repair: drain-parity variant —
# test_fsdp_none_codec_bitwise_identical_at_k1_and_k8 keeps the fast
# k-invariance representative
@pytest.mark.slow
def test_fsdp_compressed_drain_parity_k1_vs_k8(mesh8):
    """The multi-step scan drain is UNCHANGED by compression: with the
    SAME codec, k=8 reproduces k=1 step for step (the stochastic-rounding
    key is derived from state.step, so the trajectory is deterministic)."""
    ds = _tiny_ds()
    for name in ("bf16", "int8"):
        l1, p1 = _run_steps(_fsdp_engine(name, mesh8), ds, n_steps=8, k=1)
        l8, p8 = _run_steps(_fsdp_engine(name, mesh8), ds, n_steps=8, k=8)
        np.testing.assert_array_equal(l1, l8)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p8)):
            np.testing.assert_array_equal(a, b)


# round 20 fast-lane repair: convergence variant of the codec paths
# pinned bitwise/unbiased by the fast unit tests
@pytest.mark.slow
def test_fsdp_bf16_and_int8_converge_close_to_f32(mesh8):
    """Convergence-tolerance: compressed-gradient training lands within a
    few points of uncompressed on the tiny classification task (the
    everywhere-runnable mirror of the guarded MNIST MLP variant below)."""
    train, test = _tiny_ds(), _tiny_ds(128, "test")
    accs = {}
    for name in ("none", "bf16", "int8"):
        tr = Trainer(None, engine=_fsdp_engine(name, mesh8), seed=0)
        tr.fit(train, epochs=6, batch_size=64, log_every=0)
        accs[name] = tr.evaluate(test)["accuracy"]
    assert accs["none"] > 0.9
    assert accs["bf16"] > accs["none"] - 0.08
    assert accs["int8"] > accs["none"] - 0.12


def test_async_wire_bytes_counted_on_one_destacked_copy(mesh8):
    """The async/gossip exchange moves ONE de-stacked param copy per
    device: the codec accounting must see those shapes — the int8 per-leaf
    scale overhead is 4 bytes per MODEL leaf, not 4/n (a stacked-total
    divided by n would truncate it away)."""
    from distributed_tensorflow_tpu.engines import AsyncLocalEngine

    ds = _tiny_ds(64)
    engines = {name: AsyncLocalEngine(
        create_model("mlp", num_classes=4, hidden=32), mesh=mesh8,
        sync_every=4, grad_compression=name)
        for name in ("none", "bf16", "int8")}
    states = {name: eng.init_state(jax.random.key(0), ds.x[:8])
              for name, eng in engines.items()}
    raw = engines["none"].grad_collective_bytes_raw(states["none"])
    assert raw > 0
    assert engines["none"].grad_collective_bytes(states["none"]) == raw
    assert engines["bf16"].grad_collective_bytes(states["bf16"]) == raw // 2
    n_leaves = len(jax.tree.leaves(states["int8"].params))
    assert engines["int8"].grad_collective_bytes(states["int8"]) == \
        raw // 4 + 4 * n_leaves


def test_resolve_steps_per_call_with_reason():
    """fit's clamp attribution comes from the resolver itself — same
    branch picks k AND names why."""
    resolve = Trainer.resolve_steps_per_call_with_reason
    assert resolve(None) == (8, None)
    assert resolve(None, target_accuracy=0.9) == (1, "target_accuracy")
    # the checkpoint clamp rule is shared, but the reason distinguishes
    # the blocking-save discipline from the overlapped one (ISSUE 5)
    assert resolve(None, checkpoint_every=3) == (3, "checkpoint_sync")
    assert resolve(None, checkpoint_every=3, checkpoint_async=True) == \
        (3, "checkpoint_async")
    assert resolve(None, checkpoint_every=50) == (8, None)
    assert resolve(4, checkpoint_every=3) == (4, None)  # explicit: no clamp
    with pytest.raises(ValueError):
        resolve(0)


# --------------------------------------- Trainer / report / harness wiring

def test_fit_reports_wire_raw_and_codec(mesh8, tmp_path):
    from distributed_tensorflow_tpu.observability import (
        Tracer, build_run_report)

    ds = _tiny_ds(128)
    eng = _fsdp_engine("bf16", mesh8)
    tr = Trainer(None, engine=eng, seed=0)
    trace = tmp_path / "trace.jsonl"
    tracer = Tracer(path=trace)
    r = tr.fit(ds, epochs=1, batch_size=32, log_every=0, max_steps=2,
               tracer=tracer)
    tracer.close()
    assert r["grad_compression"] == "bf16"
    assert r["grad_allreduce_bytes"] * 2 == r["grad_allreduce_bytes_raw"]
    report = build_run_report(r)
    assert report["grad_allreduce_bytes"] == r["grad_allreduce_bytes"]
    assert report["grad_allreduce_bytes_raw"] == r["grad_allreduce_bytes_raw"]
    assert report["grad_compression"] == "bf16"
    events = [json.loads(line) for line in
              trace.read_text().splitlines()]
    prof = [e for e in events if e.get("name") == "collective_profile"]
    assert prof and prof[0]["grad_allreduce_bytes"] * 2 == \
        prof[0]["grad_allreduce_bytes_raw"]
    assert prof[0]["grad_compression"] == "bf16"


def test_checkpoint_clamp_warns_and_lands_in_report(mesh8, tmp_path):
    """Satellite: auto steps_per_call silently capped by checkpoint_every
    now warns once and surfaces the clamp (reason included) in the fit
    result and run report."""
    from distributed_tensorflow_tpu.observability import build_run_report
    from distributed_tensorflow_tpu.utils.checkpoint import CheckpointManager

    ds = _tiny_ds(256)
    tr = Trainer(None, engine=_fsdp_engine("none", mesh8), seed=0)
    cm = CheckpointManager(tmp_path / "ck")
    with pytest.warns(UserWarning, match="checkpoint_every=3 caps"):
        r = tr.fit(ds, epochs=1, batch_size=32, log_every=0,
                   checkpoint_manager=cm, checkpoint_every=3, max_steps=6)
    assert r["steps_per_call"] == 3
    assert r["steps_per_call_clamp"] == {
        "requested": 8, "effective": 3, "reason": "checkpoint_sync"}
    assert build_run_report(r)["steps_per_call_clamp"]["reason"] == \
        "checkpoint_sync"


def test_explicit_steps_per_call_never_warns(mesh8, tmp_path):
    from distributed_tensorflow_tpu.utils.checkpoint import CheckpointManager

    ds = _tiny_ds(256)
    tr = Trainer(None, engine=_fsdp_engine("none", mesh8), seed=0)
    cm = CheckpointManager(tmp_path / "ck")
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # any warning fails the test
        r = tr.fit(ds, epochs=1, batch_size=32, log_every=0,
                   steps_per_call=4, checkpoint_manager=cm,
                   checkpoint_every=3, max_steps=6)
    assert r["steps_per_call"] == 4
    assert "steps_per_call_clamp" not in r


def test_target_accuracy_downshift_surfaces_in_result(mesh8):
    ds = _tiny_ds(256)
    tr = Trainer(None, engine=_fsdp_engine("none", mesh8), seed=0)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # the target downshift must NOT warn
        r = tr.fit(ds, epochs=1, batch_size=32, log_every=0,
                   eval_ds=_tiny_ds(64, "test"), target_accuracy=0.05,
                   eval_every=2, max_steps=4)
    assert r["steps_per_call"] == 1
    assert r["steps_per_call_clamp"]["reason"] == "target_accuracy"


def test_cli_flags_parse():
    from distributed_tensorflow_tpu.cli import build_parser

    args = build_parser().parse_args([])
    assert args.grad_compression == "none"
    args = build_parser().parse_args(["--grad-compression", "bf16"])
    assert args.grad_compression == "bf16"
    with pytest.raises(SystemExit):
        build_parser().parse_args(["--grad-compression", "fp4"])
    # the cache directory is the environment's to place, not a flag
    with pytest.raises(SystemExit):
        build_parser().parse_args(["--compile-cache", "/tmp/xc"])


def test_harness_rejects_pipeline_compression():
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, _setup)

    with pytest.raises(ValueError, match="pipeline"):
        _setup(ExperimentConfig(model="bert_tiny", dataset="glue_synth",
                                pipeline_parallel=2,
                                grad_compression="bf16"))
    with pytest.raises(ValueError, match="unknown grad_compression"):
        _setup(ExperimentConfig(grad_compression="fp4"))


_RESOLVE = (
    "import json, os, jax\n"
    "updates = []\n"
    "real = jax.config.update\n"
    "jax.config.update = lambda k, v: (updates.append(k), real(k, v))[1]\n"
    "from distributed_tensorflow_tpu.utils.harness import "
    "resolve_compile_cache\n"
    "got = resolve_compile_cache()\n"
    "from jax._src import xla_bridge\n"
    "print(json.dumps({'got': got, 'updates': updates,\n"
    "    'dir': jax.config.jax_compilation_cache_dir,\n"
    "    'backends': sorted(xla_bridge._backends)}))\n")


def _start_resolve(cwd, **env_extra):
    """Start resolve_compile_cache() in a fresh interpreter (jax reads
    JAX_COMPILATION_CACHE_DIR and JAX_PLATFORMS at import).  No backend is
    touched, so leaving JAX_PLATFORMS unset costs nothing here."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    env["PYTHONPATH"] = str(repo)
    env.update(env_extra)
    return subprocess.Popen([sys.executable, "-c", _RESOLVE], cwd=str(cwd),
                            env=env, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def _resolved(proc):
    out, err = proc.communicate(timeout=120)
    assert proc.returncode == 0, err[-2000:]
    return json.loads(out.strip().splitlines()[-1])


def test_compile_cache_placed_by_the_environment(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself: the
    resolver sets no directory in code (only the two persistence gates)
    and JAX reports the environment's directory."""
    placed = tmp_path / "placed"
    out = _resolved(_start_resolve(
        tmp_path, JAX_COMPILATION_CACHE_DIR=str(placed)))
    assert out["got"] == out["dir"] == str(placed)
    assert "jax_compilation_cache_dir" not in out["updates"]
    assert sorted(out["updates"]) == [
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes"]
    assert out["backends"] == []


def test_compile_cache_default_is_fixed_under_the_checkout(tmp_path,
                                                           monkeypatch):
    """Unset, the directory is one fixed path beside the package — the
    same from any working directory (the path is part of the cache key) —
    and a process held to the CPU gets none."""
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    other = tmp_path / "elsewhere"
    other.mkdir()
    procs = [_start_resolve(tmp_path), _start_resolve(other)]
    first, second = map(_resolved, procs)
    assert first["dir"] == second["dir"] == str(repo / ".jax_cache")
    assert first["got"] == first["dir"]
    assert first["backends"] == []          # placing it touched no backend
    # this process is held to the CPU (tests/conftest.py)
    from distributed_tensorflow_tpu.utils.harness import (
        resolve_compile_cache)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jax.config.jax_platforms == "cpu"
    assert resolve_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir is None


# ------------------------------ explicit-collective engines (shard_map)

def test_sync_none_codec_bitwise_identical(mesh8):
    """Acceptance: SyncEngine with --grad-compression none keeps the
    implicit AD-transpose psum — bitwise identical trajectories and params
    at k=1 and through the k=8 drain."""
    from distributed_tensorflow_tpu.engines import SyncEngine

    ds = _tiny_ds()

    def run(codec_kw, k):
        eng = SyncEngine(create_model("mlp", num_classes=4, hidden=32),
                         mesh=mesh8, learning_rate=5e-3, **codec_kw)
        return _run_steps(eng, ds, n_steps=8, k=k)

    for k in (1, 8):
        base_l, base_p = run({}, k)
        none_l, none_p = run({"grad_compression": "none"}, k)
        np.testing.assert_array_equal(base_l, none_l)
        for a, b in zip(jax.tree.leaves(base_p), jax.tree.leaves(none_p)):
            np.testing.assert_array_equal(a, b)


def test_sync_bf16_mnist_mlp_converges_close_to_f32(mesh8):
    """Acceptance (ISSUE 3): short MNIST MLP run with bf16-compressed
    gradient allreduce lands within tolerance of full-f32 grads, and the
    engine reports half the wire bytes."""
    from distributed_tensorflow_tpu.engines import SyncEngine

    train = load_dataset("mnist", split="train")
    test = load_dataset("mnist", split="test")
    accs, engines = {}, {}
    for name in ("none", "bf16"):
        eng = SyncEngine(create_model("mlp", num_classes=train.num_classes),
                         mesh=mesh8, grad_compression=name)
        tr = Trainer(None, engine=eng, seed=0)
        tr.fit(train, epochs=1, batch_size=256, log_every=0, max_steps=80)
        accs[name] = tr.evaluate(test, batch_size=500)["accuracy"]
        engines[name] = (eng, tr.state)
    assert accs["none"] > 0.8          # the task trains at all
    assert abs(accs["bf16"] - accs["none"]) < 0.05
    eng_n, st_n = engines["none"]
    eng_b, st_b = engines["bf16"]
    assert eng_b.grad_collective_bytes(st_b) * 2 == \
        eng_n.grad_collective_bytes(st_n)


@pytest.mark.parametrize("codec", ["bf16", "int8"])
def test_sync_compressed_step_stays_close(mesh8, codec):
    """One compressed sync step tracks the uncompressed update within the
    codec's quantization tolerance at k=1 and k=2.  Compared on the param
    DELTAS (update = params_after − params_before), which a mis-scaled
    gradient — e.g. an accidental extra data-axis psum doubling/8×-ing the
    reduce — would blow far past the tolerance, while raw param values
    (identical init ± lr-sized steps) would hide it."""
    import optax

    from distributed_tensorflow_tpu.engines import SyncEngine

    ds = _tiny_ds(128)
    outs = {}
    for name in ("none", codec):
        for K in (1, 2):
            # SGD, not Adam: Adam's sqrt(v) normalization makes the first
            # update ~lr regardless of gradient SCALE, which would hide
            # exactly the mis-reduction this test exists to catch
            eng = SyncEngine(create_model("mlp", num_classes=4, hidden=32),
                             mesh=mesh8, optimizer=optax.sgd(0.1),
                             grad_accum=K, grad_compression=name)
            state = eng.init_state(jax.random.key(0), ds.x[:8])
            p0 = jax.device_get(state.params)
            bx, by = eng.shard_batch(ds.x[:64], ds.y[:64])
            state, m = eng.step(state, bx, by)
            delta = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                                 jax.device_get(state.params), p0)
            outs[(name, K)] = (float(m["loss"]), delta)
    for K in (1, 2):
        base_loss, base_d = outs[("none", K)]
        comp_loss, comp_d = outs[(codec, K)]
        assert np.isfinite(comp_loss)
        assert abs(comp_loss - base_loss) < 0.1
        scale = max(float(np.abs(l).max())
                    for l in jax.tree.leaves(base_d))
        for a, b in zip(jax.tree.leaves(base_d), jax.tree.leaves(comp_d)):
            assert np.all(np.isfinite(b))
            # within 30% of the exact update magnitude everywhere — a
            # double-counted reduce (2x/8x delta) fails by a wide margin
            np.testing.assert_allclose(a, b, atol=0.3 * scale)


@pytest.mark.parametrize("engine_name", ["async", "gossip"])
def test_async_and_gossip_compressed_exchange(mesh8, engine_name):
    """The periodic parameter exchange (async pmean / gossip neighbor mix)
    goes through the codec: a bf16 round lands within cast tolerance of
    the exact round, and the wire figure halves."""
    from distributed_tensorflow_tpu.engines import create_engine

    ds = _tiny_ds(128)
    kw = ({"sync_every": 1} if engine_name == "async"
          else {"degree": 1, "mix_every": 1})
    results = {}
    for name in ("none", "bf16"):
        eng = create_engine(engine_name,
                            create_model("mlp", num_classes=4, hidden=32),
                            mesh=mesh8, learning_rate=1e-2,
                            grad_compression=name, **kw)
        state = eng.init_state(jax.random.key(0), ds.x[:8])
        bx, by = eng.shard_batch(ds.x[:64], ds.y[:64])
        state, _m = eng.step(state, bx, by)  # step 1: exchange fires
        results[name] = (eng, state, jax.device_get(state.params))
    _, st_n, p_none = results["none"]
    eng_b, st_b, p_bf16 = results["bf16"]
    for a, b in zip(jax.tree.leaves(p_none), jax.tree.leaves(p_bf16)):
        np.testing.assert_allclose(a, b, rtol=0.05, atol=0.02)
    assert eng_b.grad_collective_bytes(st_b) * 2 == \
        results["none"][0].grad_collective_bytes(st_n)
