"""Communication/compute overlap (ISSUE 6): bucketer invariants, the
bucketed codec, the exposed-vs-hidden probe, and the engine/report/CLI
plumbing.

Layout mirrors tests/test_compression.py's shard_map split: the bucketer
math (vmap axis emulation), the GSPMD engines (FSDP is pure jit), the
probe accounting (host-level fakes), the harness/report plumbing, and the
sync-engine variants whose bucketed collectives ride a real shard_map.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_tpu.data.loaders import (
    Dataset, synthetic_classification)
from distributed_tensorflow_tpu.engines import Trainer
from distributed_tensorflow_tpu.engines.base import TrainState
from distributed_tensorflow_tpu.engines.fsdp import FSDPEngine
from distributed_tensorflow_tpu.models import create_model
from distributed_tensorflow_tpu.parallel import compression, overlap

def _leaves(seed=0):
    """A mixed tree: odd sizes (padding tails), a large splittable leaf,
    an integer leaf, an empty leaf."""
    rng = np.random.default_rng(seed)
    return [
        jnp.asarray(rng.normal(size=(37,)).astype(np.float32)),
        jnp.asarray(rng.normal(size=(64, 9)).astype(np.float32)),
        jnp.arange(5, dtype=jnp.int32),
        jnp.zeros((0,), jnp.float32),
        jnp.asarray(rng.normal(size=(3, 3)).astype(np.float32)),
    ]


# ---------------------------------------------------------- bucketer units

def test_plan_exact_partition_and_determinism():
    """Every element of every non-empty leaf is covered by exactly one
    slice of exactly one bucket; the plan is a pure function of the
    shapes/dtypes (deterministic across processes)."""
    leaves = _leaves()
    plan = overlap.plan_buckets(leaves, bucket_bytes=64)  # 16 f32 elems
    cover = [np.zeros(int(np.prod(l.shape)), bool) for l in leaves]
    for b in plan:
        total = 0
        for s in b.slices:
            assert not cover[s.leaf][s.start:s.stop].any(), "double cover"
            cover[s.leaf][s.start:s.stop] = True
            total += s.stop - s.start
        assert total == b.size
        # single-dtype buckets, payload within the byte target
        dtypes = {str(jnp.dtype(leaves[s.leaf].dtype)) for s in b.slices}
        assert dtypes == {str(jnp.dtype(b.dtype))}
        assert b.size * jnp.dtype(b.dtype).itemsize <= 64
    for i, c in enumerate(cover):
        assert c.all() or c.size == 0, f"leaf {i} not fully covered"
    # deterministic: same structure → identical plan
    assert plan == overlap.plan_buckets(_leaves(seed=7), bucket_bytes=64)


def test_plan_reverse_backward_order():
    """The first bucket holds the LAST leaf's gradient — flatten order
    tracks the forward pass, so its reverse approximates backward
    readiness order (the slices XLA can exchange earliest)."""
    leaves = _leaves()
    plan = overlap.plan_buckets(leaves, bucket_bytes=1 << 20)
    first_leaves = [s.leaf for s in plan[0].slices]
    assert first_leaves[0] == len(leaves) - 1
    # within the plan, leaf indices never increase bucket over bucket
    seen = [s.leaf for b in plan for s in b.slices]
    assert seen == sorted(seen, reverse=True)


def test_plan_splits_large_leaves_and_rejects_bad_target():
    big = [jnp.zeros((1000,), jnp.float32)]  # 4000 B
    plan = overlap.plan_buckets(big, bucket_bytes=1024)  # 256 elems/bucket
    assert len(plan) == 4  # 256+256+256+232
    assert [b.size for b in plan] == [256, 256, 256, 232]
    with pytest.raises(ValueError, match="bucket_bytes"):
        overlap.plan_buckets(big, bucket_bytes=0)


def test_pack_unpack_bitwise_roundtrip():
    leaves = _leaves()
    plan = overlap.plan_buckets(leaves, bucket_bytes=100)
    packed = overlap.pack_buckets(leaves, plan)
    assert all(p.ndim == 1 for p in packed)
    out = overlap.unpack_buckets(packed, plan, leaves)
    for a, b in zip(leaves, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype and a.shape == b.shape


# ----------------------------------------------------------- codec wrapper

def test_make_overlap_codec_resolution():
    assert overlap.make_overlap_codec("none", 0.0).name == "none"
    assert not getattr(overlap.make_overlap_codec("none", 0.0),
                       "bucketed", False)
    bucketed = overlap.make_overlap_codec("int8", 4.0)
    assert bucketed.bucketed and bucketed.name == "int8"
    assert bucketed.bucket_mb == pytest.approx(4.0)
    with pytest.raises(ValueError, match="grad_bucket_mb"):
        overlap.BucketedCodec(compression.make_codec("none"), -1.0)
    with pytest.raises(ValueError, match="already bucketed"):
        overlap.BucketedCodec(bucketed, 4.0)


def test_bucketed_wire_bytes_scale_per_bucket_not_per_leaf():
    """Satellite: the int8 scale overhead is 4 B per BUCKET once
    bucketing lands — many tiny leaves share one bucket scale, while the
    per-leaf accounting would charge 4 B each."""
    leaves = [jnp.zeros((16,), jnp.float32) for _ in range(32)]  # 2 KB raw
    raw = 32 * 16 * 4
    per_leaf = compression.make_codec("int8")
    assert per_leaf.wire_bytes(leaves) == raw // 4 + 4 * 32
    bucketed = overlap.BucketedCodec(per_leaf, bucket_mb=1.0)  # one bucket
    plan = bucketed.plan_for(leaves)
    assert len(plan) == 1
    assert bucketed.wire_bytes(leaves) == raw // 4 + 4 * 1
    # none/bf16 payloads are granularity-independent
    assert overlap.BucketedCodec(
        compression.make_codec("none"), 1.0).wire_bytes(leaves) == raw
    assert overlap.BucketedCodec(
        compression.make_codec("bf16"), 1.0).wire_bytes(leaves) == raw // 2
    # per-leaf attribution is ill-posed under bucketing (bucket overhead
    # is shared): the wrapper refuses rather than return numbers that
    # would not sum to wire_bytes(leaves)
    with pytest.raises(NotImplementedError, match="wire_bytes"):
        bucketed.leaf_wire_bytes((16,), jnp.float32)


def test_bucketed_none_roundtrip_and_reduce_are_exact():
    tree = {"a": _leaves()[0], "b": _leaves()[1]}
    codec = overlap.BucketedCodec(compression.make_codec("none"),
                                  bucket_mb=0.0001)
    rt = codec.roundtrip(tree, rng=jax.random.key(0))
    for k in tree:
        np.testing.assert_array_equal(np.asarray(rt[k]),
                                      np.asarray(tree[k]))
    n = 8
    stacked = jax.tree.map(
        lambda x: jnp.stack([x + i for i in range(n)]), tree)
    out = jax.vmap(lambda t: codec.all_reduce_sum(t, "data"),
                   axis_name="data")(stacked)
    expect = jax.vmap(
        lambda t: jax.tree.map(
            lambda x: jax.lax.psum(x, axis_name="data"), t),
        axis_name="data")(stacked)
    for k in tree:
        np.testing.assert_allclose(np.asarray(out[k]),
                                   np.asarray(expect[k]), rtol=1e-5)


def test_bucketed_int8_reduce_padding_tail_correct():
    """Satellite: odd bucket sizes force the int8 two-phase reduce's
    ceil-chunking zero-pad on every bucket — the reduced values must
    still land within the codec's documented error bound."""
    n = 8
    rng = np.random.default_rng(3)
    tree = {
        "w": jnp.asarray(rng.normal(size=(n, 61)).astype(np.float32)),
        "v": jnp.asarray(rng.normal(size=(n, 7, 5)).astype(np.float32)),
    }
    codec = overlap.BucketedCodec(compression.make_codec("int8"),
                                  bucket_mb=0.0001)  # ~104 B → 26-elem buckets
    plan = codec.plan_for_tree(jax.tree.map(lambda x: x[0], tree))
    assert len(plan) > 2 and any(b.size % n for b in plan)
    out = jax.vmap(
        lambda t: codec.all_reduce_sum(t, "data", rng=jax.random.key(0)),
        axis_name="data")(tree)
    for k in tree:
        got = np.asarray(out[k])
        expect = np.asarray(tree[k]).sum(axis=0)
        # every device computes the same reduced value...
        np.testing.assert_array_equal(got[0], got[-1])
        # ...within the two-rounding error bound (n+1 quanta per bucket,
        # scales bounded by the bucket max — generous envelope)
        assert np.abs(got[0] - expect).max() < 0.5


def test_bucketed_int8_roundtrip_quantizes_per_bucket():
    x = _leaves()[1]
    codec = overlap.BucketedCodec(compression.make_codec("int8"),
                                  bucket_mb=4.0)
    out = codec.roundtrip({"w": x}, rng=jax.random.key(2))["w"]
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    assert out.dtype == x.dtype
    assert float(jnp.abs(out - x).max()) <= scale + 1e-7


# ---------------------------------------------- GSPMD engines (any jax)

def _tiny_ds(n=512, split="train"):
    x, y = synthetic_classification((8, 8), 4, n, seed=3, split=split)
    return Dataset(x=x, y=y, num_classes=4, name="tiny", synthetic=True)


def _fsdp(mesh, **kw):
    kw.setdefault("learning_rate", 5e-3)
    return FSDPEngine(create_model("mlp", num_classes=4, hidden=32),
                      mesh=mesh, **kw)


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


# round 20 fast-lane repair: bucket-size variants ride the slow lane;
# test_fsdp_bucketed_none_keeps_program_untouched and the padding-tail
# test keep the fast bucketing representatives
@pytest.mark.slow
def test_fsdp_bucket_zero_is_bitwise_pre_overlap(mesh8):
    """Acceptance: --grad-bucket-mb 0 --grad-accum 1 compiles the
    byte-identical pre-overlap program — trajectory bitwise equal at k=1
    and through the scanned drain."""
    ds = _tiny_ds()
    for k, steps in ((1, 3), (8, 8)):
        base, pbase = _run_steps(_fsdp(mesh8), ds, n_steps=steps, k=k)
        off, poff = _run_steps(_fsdp(mesh8, grad_bucket_mb=0.0,
                                     grad_accum=1), ds,
                               n_steps=steps, k=k)
        np.testing.assert_array_equal(base, off)
        for a, b in zip(jax.tree.leaves(pbase), jax.tree.leaves(poff)):
            np.testing.assert_array_equal(a, b)


def test_fsdp_bucketed_none_keeps_program_untouched(mesh8):
    """On the GSPMD engines the codec gate stays on the INNER name:
    bucketed-'none' skips the roundtrip entirely (the per-microbatch
    reduces of gspmd_grad_accum are already scheduler-overlappable), so
    the trajectory stays bitwise equal to the baseline."""
    ds = _tiny_ds()
    base, pbase = _run_steps(_fsdp(mesh8, grad_accum=2), ds,
                             n_steps=8, k=8)
    on, pon = _run_steps(_fsdp(mesh8, grad_accum=2, grad_bucket_mb=1.0),
                         ds, n_steps=8, k=8)
    np.testing.assert_array_equal(base, on)
    for a, b in zip(jax.tree.leaves(pbase), jax.tree.leaves(pon)):
        np.testing.assert_array_equal(a, b)


# round 20 fast-lane repair: int8 × bucketing composition variant
@pytest.mark.slow
def test_fsdp_bucketed_int8_drain_parity_k1_vs_k8(mesh8):
    """Acceptance: with overlap on, k=1 vs k=8 drain parity holds (the
    rounding key derives from state.step — deterministic trajectory)."""
    ds = _tiny_ds()
    l1, p1 = _run_steps(_fsdp(mesh8, grad_compression="int8",
                              grad_bucket_mb=0.05, grad_accum=2),
                        ds, n_steps=8, k=1)
    l8, p8 = _run_steps(_fsdp(mesh8, grad_compression="int8",
                              grad_bucket_mb=0.05, grad_accum=2),
                        ds, n_steps=8, k=8)
    np.testing.assert_array_equal(l1, l8)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p8)):
        np.testing.assert_array_equal(a, b)


# round 20 fast-lane repair: convergence variant of the int8 bucketing
# path already pinned bitwise above
@pytest.mark.slow
def test_fsdp_bucketed_int8_converges_close_to_unbucketed(mesh8):
    """Acceptance: the bucketed loss trajectory matches the unbucketed
    path within the documented accumulation/quantization tolerance."""
    train, test = _tiny_ds(), _tiny_ds(128, "test")
    accs = {}
    for label, kw in (("plain", {}),
                      ("bucketed", {"grad_compression": "int8",
                                    "grad_bucket_mb": 0.05,
                                    "grad_accum": 2})):
        tr = Trainer(None, engine=_fsdp(mesh8, **kw), seed=0)
        tr.fit(train, epochs=6, batch_size=64, log_every=0)
        accs[label] = tr.evaluate(test)["accuracy"]
    assert accs["plain"] > 0.9
    assert accs["bucketed"] > accs["plain"] - 0.12


def test_engine_wire_bytes_per_bucket(mesh8):
    """Engine.grad_collective_bytes accounts codec overhead per bucket
    once bucketing lands (the honest wire-vs-raw satellite)."""
    ds = _tiny_ds(64)
    eng = _fsdp(mesh8, grad_compression="int8", grad_bucket_mb=1.0)
    state = eng.init_state(jax.random.key(0), ds.x[:8])
    raw = eng.grad_collective_bytes_raw(state)
    n_buckets = len(eng.grad_codec.plan_for_tree(state.params))
    n_leaves = len(jax.tree.leaves(state.params))
    assert n_buckets < n_leaves  # tiny MLP: leaves coalesce into buckets
    assert eng.grad_collective_bytes(state) == raw // 4 + 4 * n_buckets


# ------------------------------------------------------------- the probe

def test_overlap_split_math():
    s = overlap.overlap_split(full_s=1.2, compute_s=1.0, collective_s=0.5)
    assert s["exposed_s"] == pytest.approx(0.2)
    assert s["hidden_s"] == pytest.approx(0.3)
    assert s["serialized_step_s"] == pytest.approx(1.5)
    assert s["exposed_frac"] == pytest.approx(0.4)
    # perfect overlap / fully serialized ends
    assert overlap.overlap_split(1.0, 1.0, 0.5)["exposed_s"] == 0.0
    full = overlap.overlap_split(1.5, 1.0, 0.5)
    assert full["exposed_s"] == pytest.approx(0.5)
    assert full["hidden_s"] == 0.0
    # noisy: full < compute never goes negative
    assert overlap.overlap_split(0.9, 1.0, 0.5)["exposed_s"] == 0.0


class _FakeOverlapEngine:
    """Host-level CPU proxy for the acceptance criterion: an engine whose
    'collective' is artificially slowed (sleeps) and whose full step
    hides most of it — the probe must measure exposed < 50% of the
    serialized baseline.  (On-CPU XLA runs serially, so true scheduler
    overlap is only observable on hardware; this fake validates the
    measurement pipeline end to end at the host boundary the probe
    times.)"""

    grad_accum = 4

    def __init__(self, compute_s=0.10, collective_s=0.10, exposed_s=0.02):
        import time as _t

        self.grad_codec = overlap.BucketedCodec(
            compression.make_codec("none"), 1.0)
        self._t = _t
        self.compute_s, self.collective_s = compute_s, collective_s
        self.exposed_s = exposed_s

    def init_state(self, rng, sample_x):
        return TrainState(step=jnp.zeros((), jnp.int32),
                          params={"w": jnp.ones((4,), jnp.float32)},
                          opt_state=(), rng=rng)

    def build_overlap_probe_fns(self):
        def full(state, xs, ys):
            self._t.sleep(self.compute_s + self.exposed_s)
            return state, {}

        def compute(state, xs, ys):
            self._t.sleep(self.compute_s)
            return state, {}

        def collective(params):
            self._t.sleep(self.collective_s)
            return params

        return {"full": full, "compute": compute, "collective": collective}


def test_probe_measures_overlapped_collective_under_50_percent():
    """Acceptance: with an artificially slowed collective (CPU proxy),
    exposed time under overlap measures < 50% of the serialized
    baseline (here: < 50% of the collective that WOULD be exposed
    serialized)."""
    eng = _FakeOverlapEngine()
    xs = ys = jnp.zeros((2,))
    out = overlap.probe_engine_overlap(eng, xs, ys,
                                       sample_x=np.zeros((1, 4)),
                                       repeats=2)
    assert out is not None
    assert out["collective_s"] > 0.05
    assert out["exposed_s"] < 0.5 * out["collective_s"]
    assert out["exposed_s"] < 0.5 * (out["serialized_step_s"]
                                     - out["compute_s"]) + 1e-9
    assert out["hidden_s"] > 0.0
    assert out["grad_compression"] == "none"
    assert out["grad_bucket_mb"] == pytest.approx(1.0)
    assert out["n_buckets"] == 1
    assert out["grad_accum"] == 4


def test_probe_serialized_engine_exposes_the_whole_collective():
    """The same proxy with NO hiding: exposed ≈ the collective — the
    serialized baseline the overlapped figure is compared against."""
    eng = _FakeOverlapEngine(exposed_s=0.10, collective_s=0.10)
    out = overlap.probe_engine_overlap(eng, jnp.zeros((2,)),
                                       jnp.zeros((2,)),
                                       sample_x=np.zeros((1, 4)),
                                       repeats=2)
    assert out["exposed_s"] > 0.5 * out["collective_s"]
    assert out["hidden_s"] < 0.5 * out["collective_s"]


def test_probe_unsupported_engine_returns_none(mesh8):
    """GSPMD engines (compiler-inserted collectives) have no probe —
    None, never an exception."""
    eng = _fsdp(mesh8, grad_bucket_mb=1.0)
    assert overlap.probe_engine_overlap(
        eng, None, None, sample_x=np.zeros((8, 8, 8))) is None


def test_probe_preserves_caller_state():
    """Probe steps donate THEIR copies; the caller's state must survive."""
    eng = _FakeOverlapEngine()
    state = eng.init_state(jax.random.key(0), np.zeros((1, 4)))
    overlap.probe_engine_overlap(eng, jnp.zeros((2,)), jnp.zeros((2,)),
                                 state=state, repeats=1)
    np.testing.assert_array_equal(np.asarray(state.params["w"]),
                                  np.ones((4,), np.float32))


# --------------------------------------------- report / harness plumbing

def test_fit_result_carries_bucket_mb(mesh8, tmp_path):
    from distributed_tensorflow_tpu.observability import Tracer

    ds = _tiny_ds(128)
    eng = _fsdp(mesh8, grad_compression="int8", grad_bucket_mb=0.5)
    tr = Trainer(None, engine=eng, seed=0)
    trace = tmp_path / "trace.jsonl"
    tracer = Tracer(path=trace)
    r = tr.fit(ds, epochs=1, batch_size=32, log_every=0, max_steps=2,
               tracer=tracer)
    tracer.close()
    assert r["grad_bucket_mb"] == pytest.approx(0.5)
    events = [json.loads(line) for line in trace.read_text().splitlines()]
    prof = [e for e in events if e.get("name") == "collective_profile"]
    assert prof and prof[0]["grad_bucket_mb"] == pytest.approx(0.5)


def test_run_report_surfaces_overlap_split_and_environment():
    from distributed_tensorflow_tpu.observability import build_run_report

    split = overlap.overlap_split(1.2, 1.0, 0.5)
    report = build_run_report({"steps": 2, "elapsed": 1.0,
                               "grad_bucket_mb": 4.0,
                               "collective_overlap": split},
                              devices=jax.devices())
    assert report["grad_bucket_mb"] == 4.0
    assert report["grad_collective_exposed_s"] == pytest.approx(0.2)
    assert report["grad_collective_hidden_s"] == pytest.approx(0.3)
    assert report["collective_overlap"]["serialized_step_s"] == \
        pytest.approx(1.5)
    env = report["environment"]
    assert env["jax_version"] == jax.__version__
    assert env["device_kind"] == jax.devices()[0].device_kind
    assert env["platform"] == "cpu"
    assert env["device_count"] == len(jax.devices())
    # overlap off: keys present but None — "off" ≠ "measured 0"
    off = build_run_report({"steps": 2, "elapsed": 1.0})
    assert off["grad_collective_exposed_s"] is None
    assert off["grad_bucket_mb"] is None


# round 20 fast-lane repair: harness e2e variant — the probe flags are
# also pinned by the cheaper unit tests above
@pytest.mark.slow
def test_harness_run_spans_probe_and_records_flags(tmp_path):
    """End-to-end --grad-bucket-mb run on this container (fsdp engine):
    the collective_overlap span/event family is emitted (unsupported
    probe → supported:false event), the report carries grad_bucket_mb +
    the environment section, and the overlap XLA flags landed in
    LIBTPU_INIT_ARGS."""
    import os

    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, run)

    trace = tmp_path / "trace.jsonl"
    cfg = ExperimentConfig(engine="fsdp", model="mlp", dataset="synthetic",
                           batch_size=8, epochs=1, log_every=0,
                           grad_accum=2, grad_bucket_mb=1.0,
                           trace_path=str(trace))
    summary = run(cfg)
    rep = summary["run_report"]
    assert rep["grad_bucket_mb"] == pytest.approx(1.0)
    assert rep["grad_collective_exposed_s"] is None  # probe unsupported
    assert rep["environment"]["jax_version"] == jax.__version__
    assert "--xla_tpu_enable_latency_hiding_scheduler=true" in \
        os.environ.get("LIBTPU_INIT_ARGS", "")
    records = [json.loads(line) for line in trace.read_text().splitlines()]
    spans = {r.get("name") for r in records if r.get("event") == "span"}
    assert "collective_overlap" in spans
    events = [r for r in records if r.get("event") == "event"
              and r.get("name") == "collective_overlap"]
    assert events and events[0]["supported"] is False


def test_harness_rejects_bad_bucket_configs():
    from distributed_tensorflow_tpu.utils.harness import (
        ExperimentConfig, _setup)

    with pytest.raises(ValueError, match="grad-bucket-mb"):
        _setup(ExperimentConfig(grad_bucket_mb=-1.0))
    with pytest.raises(ValueError, match="pipeline"):
        _setup(ExperimentConfig(grad_bucket_mb=4.0, pipeline_parallel=2))


def test_run_rejects_bad_bucket_config_without_mutating_env(monkeypatch):
    """run() must validate --grad-bucket-mb BEFORE enable_overlap_flags():
    a rejected config mutating process-global LIBTPU_INIT_ARGS would
    poison every later run in the same process (the bucket-0 bitwise
    guarantee rides on the flags being absent)."""
    from distributed_tensorflow_tpu.utils import harness

    monkeypatch.delenv("LIBTPU_INIT_ARGS", raising=False)
    with pytest.raises(ValueError, match="grad-bucket-mb"):
        harness.run(harness.ExperimentConfig(grad_bucket_mb=-1.0))
    assert "LIBTPU_INIT_ARGS" not in os.environ
    with pytest.raises(ValueError, match="pipeline"):
        harness.run(harness.ExperimentConfig(grad_bucket_mb=4.0,
                                             pipeline_parallel=2))
    assert "LIBTPU_INIT_ARGS" not in os.environ


def test_runtime_environment_does_not_initialize_backend():
    """report.runtime_environment() must be initialization-free: a backend
    brought up as a side effect would lock in LIBTPU_INIT_ARGS BEFORE
    enable_overlap_flags() could act, while the section still showed the
    flags as effective.  The device facts come only from the devices the
    caller hands it (the run's mesh).  Probed in a subprocess (this test
    process already has a backend)."""
    code = (
        "from distributed_tensorflow_tpu.observability.report import "
        "runtime_environment\n"
        "env = runtime_environment()\n"
        "assert env['jax_version'], env\n"
        "assert env['device_kind'] is None, env\n"
        "assert env['platform'] is None, env\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, 'backend was initialized'\n"
        "import jax\n"
        "env2 = runtime_environment(jax.devices())\n"
        "assert env2['device_kind'] == 'cpu', env2\n"
        "assert env2['platform'] == 'cpu', env2\n"
        "assert env2['device_count'] == len(jax.devices()), env2\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_enable_overlap_flags_idempotent_and_respects_overrides():
    from distributed_tensorflow_tpu.utils.harness import (
        OVERLAP_XLA_TPU_FLAGS, enable_overlap_flags)

    env = {}
    first = enable_overlap_flags(env)
    for flag in OVERLAP_XLA_TPU_FLAGS:
        assert flag in first.split()
    assert enable_overlap_flags(env) == first  # idempotent
    # a user override of one key is left alone
    env2 = {"LIBTPU_INIT_ARGS":
            "--xla_tpu_enable_latency_hiding_scheduler=false"}
    out = enable_overlap_flags(env2)
    assert "--xla_tpu_enable_latency_hiding_scheduler=false" in out.split()
    assert "--xla_tpu_enable_latency_hiding_scheduler=true" not in \
        out.split()


def test_cli_flag_parses():
    from distributed_tensorflow_tpu.cli import build_parser

    args = build_parser().parse_args(["--grad-bucket-mb", "4"])
    assert args.grad_bucket_mb == 4.0
    assert build_parser().parse_args([]).grad_bucket_mb == 0.0


def test_analyze_diff_gates_exposed_seconds(tmp_path):
    """`analyze diff` treats grad_collective_exposed_s lower-is-better:
    a run whose exposed time grew past threshold regresses (exit 1
    semantics), an equal self-diff compares it unchanged."""
    from distributed_tensorflow_tpu.observability.analyze import (
        diff_reports, load_report)

    base = {"steps": 8, "grad_collective_exposed_s": 0.10}
    worse = {"steps": 8, "grad_collective_exposed_s": 0.20}
    d = diff_reports(base, worse, threshold=0.1)
    assert [r["metric"] for r in d["regressions"]] == \
        ["grad_collective_exposed_s"]
    d_self = diff_reports(base, base, threshold=0.1)
    assert [r["metric"] for r in d_self["unchanged"]] == \
        ["grad_collective_exposed_s"]
    # and through the file loader (the CI smoke's self-diff path)
    p = tmp_path / "r.json"
    p.write_text(json.dumps(base))
    assert load_report(p)["grad_collective_exposed_s"] == 0.10


# ------------------------------------ sync engine variants (shard_map)

def test_sync_bucketed_none_matches_exact(mesh8):
    """The bucketed explicit-psum step reproduces the exact path's
    trajectory (per-bucket psums are the same elementwise sums)."""
    from distributed_tensorflow_tpu.engines.sync import SyncEngine

    ds = _tiny_ds()
    model = create_model("mlp", num_classes=4, hidden=32)
    exact = SyncEngine(model, mesh=mesh8, learning_rate=5e-3)
    bucketed = SyncEngine(create_model("mlp", num_classes=4, hidden=32),
                          mesh=mesh8, learning_rate=5e-3,
                          grad_bucket_mb=0.05)
    le, _pe = _run_steps(exact, ds, n_steps=4)
    lb, _pb = _run_steps(bucketed, ds, n_steps=4)
    np.testing.assert_allclose(le, lb, rtol=1e-5, atol=1e-6)


def test_sync_overlap_accum_reduce_in_scan_close_to_exact(mesh8):
    """Overlap restructure (grad_accum with per-microbatch reduces inside
    the scan): Σᵢ psum(gᵢ) matches psum(Σᵢ gᵢ) within fp accumulation
    tolerance — the documented semantics (MIGRATING.md)."""
    from distributed_tensorflow_tpu.engines.sync import SyncEngine

    ds = _tiny_ds()
    exact = SyncEngine(create_model("mlp", num_classes=4, hidden=32),
                       mesh=mesh8, learning_rate=5e-3, grad_accum=2)
    ov = SyncEngine(create_model("mlp", num_classes=4, hidden=32),
                    mesh=mesh8, learning_rate=5e-3, grad_accum=2,
                    grad_bucket_mb=0.05)
    le, _ = _run_steps(exact, ds, n_steps=4)
    lo, _ = _run_steps(ov, ds, n_steps=4)
    np.testing.assert_allclose(le, lo, rtol=1e-4, atol=1e-5)


def test_sync_probe_reports_real_split(mesh8):
    """The real probe on the sync engine: three programs compile, the
    split is internally consistent, and the caller's state survives."""
    from distributed_tensorflow_tpu.engines.sync import SyncEngine

    ds = _tiny_ds(64)
    eng = SyncEngine(create_model("mlp", num_classes=4, hidden=32),
                     mesh=mesh8, grad_bucket_mb=0.05)
    xs, ys = eng.shard_batch(ds.x[:32], ds.y[:32])
    out = overlap.probe_engine_overlap(eng, xs, ys, sample_x=ds.x[:8],
                                       repeats=2)
    assert out is not None
    for key in ("full_step_s", "compute_s", "collective_s", "exposed_s",
                "hidden_s", "serialized_step_s"):
        assert out[key] >= 0.0
    assert out["n_buckets"] >= 1
    assert out["exposed_s"] <= out["serialized_step_s"]
