"""Steady-state training-loop layer: device prefetch + multi-step drain.

Covers the contracts ISSUE 1 names: prefetch ordering/exhaustion/early
close, padded-final-batch mask correctness through a scanned drain,
``steps_per_call > 1`` bitwise parity with ``steps_per_call = 1`` on a
fixed seed, and the auto-downshift to 1 — which since ISSUE 2 applies
ONLY to ``target_accuracy`` runs: telemetry (metrics sink, watchdog)
rides the chunked drain with zero downshift, including the k=8 vs k=1
bitwise parity of the on-disk metrics stream and the watchdog's
chunk-rescaled stall budget (firing and non-firing).

The shard_map engines need a newer jax than some CI containers carry, so
the Trainer/Engine machinery is exercised through a minimal pure-jit
Engine (``JitEngine``) that runs everywhere; the acceptance-letter MNIST
CNN + SyncEngine parity variant is guarded by ``jax.shard_map``
availability and runs wherever the engine layer itself runs.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_tensorflow_tpu.data.device_prefetch import DevicePrefetch
from distributed_tensorflow_tpu.data.loaders import (
    Dataset, synthetic_classification)
from distributed_tensorflow_tpu.data.pipeline import iter_batches
from distributed_tensorflow_tpu.engines.allreduce import (
    DEFAULT_STEPS_PER_CALL, Trainer)
from distributed_tensorflow_tpu.engines.base import (
    Engine, cross_entropy)
from distributed_tensorflow_tpu.utils.metrics import MetricsLogger

# --------------------------------------------------------------- prefetcher

def _host_batches(n, rows=4):
    return [(np.full((rows, 2), i, np.float32),
             np.full((rows,), i, np.int32),
             np.ones((rows,), np.float32)) for i in range(n)]


def test_prefetch_orders_and_reads_ahead():
    placed = []

    def place(b):
        placed.append(int(b[1][0]))
        return jax.device_put(b[0]), jax.device_put(b[1])

    pf = DevicePrefetch(iter(_host_batches(6)), place, depth=2)
    seen = []
    for _xs, ys in pf:
        seen.append(int(np.asarray(ys)[0]))
        # the transfer for the NEXT depth batches was already issued when
        # the consumer got this one — bounded read-ahead, source order kept
        assert placed == list(range(min(len(seen) + 2, 6)))
    assert seen == list(range(6))
    with pytest.raises(StopIteration):
        next(pf)
    assert pf.take(3) == []  # exhausted stays exhausted


class _CloseableSource:
    def __init__(self, items):
        self._it = iter(items)
        self.closed = False

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._it)

    def close(self):
        self.closed = True


def test_prefetch_close_releases_source_early():
    src = _CloseableSource(_host_batches(6))
    pf = DevicePrefetch(src, lambda b: b, depth=2)
    next(pf)
    pf.close()  # consumer stops early (max_steps / early-stop / exception)
    assert src.closed
    assert pf.take(3) == []


def test_prefetch_exhaustion_closes_source():
    src = _CloseableSource(_host_batches(2))
    pf = DevicePrefetch(src, lambda b: b, depth=4)  # deeper than the epoch
    assert len(list(pf)) == 2
    assert src.closed


def test_prefetch_depth_gauge_slow_consumer():
    """Satellite: a consumer slower than the source sees the queue-depth
    gauge pinned at the configured --prefetch depth (the buffer is
    refilled before every hand-off), and never counts starvation."""
    pf = DevicePrefetch(iter(_host_batches(8)), lambda b: b, depth=3)
    assert pf.depth == 3
    assert pf.queue_depth == 3  # staged eagerly at construction
    for _ in range(4):  # slow consumer: source always ahead
        next(pf)
        assert pf.queue_depth == 3
    assert pf.starvation == 0
    stats = pf.stats()
    assert stats["depth"] == stats["queue_depth"] == 3
    assert stats["fill_wait_s"] >= 0.0


def test_prefetch_starvation_counts_empty_readahead():
    """depth=1 leaves zero batches staged after every hand-off — each
    next() is a starvation event (the following transfer cannot overlap
    compute); at depth=2 the same traffic never starves."""
    pf1 = DevicePrefetch(iter(_host_batches(6)), lambda b: b, depth=1)
    for i in range(4):
        next(pf1)
    assert pf1.starvation == 4
    pf2 = DevicePrefetch(iter(_host_batches(6)), lambda b: b, depth=2)
    for i in range(4):
        next(pf2)
    assert pf2.starvation == 0


def test_prefetch_take_and_depth_validation():
    pf = DevicePrefetch(iter(_host_batches(5)), lambda b: b, depth=1)
    assert len(pf.take(0)) == 0
    assert len(pf.take(3)) == 3
    assert len(pf.take(8)) == 2  # remainder only
    with pytest.raises(ValueError):
        DevicePrefetch(iter(()), lambda b: b, depth=0)


def test_padded_final_batch_mask_through_scanned_drain(mesh8):
    """A padded final batch prefetched to device and consumed by a jitted
    lax.scan drain must contribute exactly its real rows: the mask rides
    the prefetcher with the batch and zeroes the padding inside the scan."""
    from distributed_tensorflow_tpu.parallel import mesh as meshlib

    x, y = synthetic_classification((4,), 3, 100, seed=1)
    n_batches = 3  # 48 + 48 + (4 real + 44 padded)

    def place(b):
        return tuple(
            jax.device_put(a, meshlib.data_sharding(mesh8, np.ndim(a)))
            for a in b)

    pf = DevicePrefetch(iter_batches(x, y, 48, shuffle=False), place, depth=2)
    chunk = pf.take(n_batches + 1)  # over-ask: epoch has exactly 3
    assert len(chunk) == n_batches
    xs = jnp.stack([c[0] for c in chunk])
    ys = jnp.stack([c[1] for c in chunk])
    ms = jnp.stack([c[2] for c in chunk])

    @jax.jit
    def drain(xs, ys, ms):
        def body(carry, batch):
            _bx, by, bm = batch
            count, label_sum = carry
            return (count + bm.sum(), label_sum + (bm * by).sum()), None

        init = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
        (count, label_sum), _ = jax.lax.scan(body, init, (xs, ys, ms))
        return count, label_sum

    count, label_sum = drain(xs, ys, ms)
    assert float(count) == 100.0  # every real row once, no padding rows
    assert float(label_sum) == float(y.sum())


# ------------------------------------------------- minimal pure-jit engine

class JitEngine(Engine):
    """Smallest Engine whose step runs on any jax: one jitted SGD step of a
    linear softmax classifier (no shard_map) — lets every container verify
    the Trainer's steady-state machinery (prefetch consumption, chunked
    many_step drain, bookkeeping parity) independent of the engine layer."""

    def __init__(self, num_classes: int = 4, learning_rate: float = 0.1,
                 mesh=None):
        import flax.linen as nn

        class _Linear(nn.Module):
            @nn.compact
            def __call__(self, x, train: bool = False):
                return nn.Dense(num_classes)(x.reshape((x.shape[0], -1)))

        super().__init__(_Linear(), optimizer=optax.sgd(learning_rate),
                         mesh=mesh)

    def _build_step(self):
        tx, apply_fn = self.tx, self.model.apply

        def train_step(state, x, y):
            def loss_fn(p):
                logits = apply_fn({"params": p}, x)
                loss = cross_entropy(logits, y).mean()
                return loss, (logits.argmax(-1) == y).mean()

            (loss, acc), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params)
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)
            return state.replace(step=state.step + 1, params=params,
                                 opt_state=opt_state), \
                {"loss": loss, "accuracy": acc}

        return jax.jit(train_step, donate_argnums=0)

    def _build_eval(self):
        apply_fn = self.model.apply
        return self._build_eval_gspmd(
            lambda params, x: apply_fn({"params": params}, x))


def _tiny_ds(n=208):
    x, y = synthetic_classification((8,), 4, n, seed=3)
    return Dataset(x=x, y=y, num_classes=4, name="tiny", synthetic=True)


def test_many_step_matches_sequential_steps():
    ds = _tiny_ds()
    batches = None
    runs = {}
    for name in ("scan", "loop"):
        eng = JitEngine()
        state = eng.init_state(jax.random.key(0), ds.x[:8])
        if batches is None:
            batches = [eng.shard_batch(ds.x[i * 16:(i + 1) * 16],
                                       ds.y[i * 16:(i + 1) * 16])
                       for i in range(3)]
        if name == "scan":
            state, m = eng.many_step(state, [b[0] for b in batches],
                                     [b[1] for b in batches])
            assert m["loss"].shape == (3,)  # per-step trajectory, stacked
            runs[name] = (np.asarray(m["loss"]),
                          jax.device_get(state.params))
        else:
            losses = []
            for bx, by in batches:
                state, m = eng.step(state, bx, by)
                losses.append(np.asarray(m["loss"]))
            runs[name] = (np.asarray(losses), jax.device_get(state.params))
    np.testing.assert_array_equal(runs["scan"][0], runs["loop"][0])
    for a, b in zip(jax.tree.leaves(runs["scan"][1]),
                    jax.tree.leaves(runs["loop"][1])):
        np.testing.assert_array_equal(a, b)


def test_build_many_step_validates_k():
    with pytest.raises(ValueError, match="steps_per_call"):
        JitEngine().build_many_step(0)


# ------------------------------------------------------ Trainer drain/parity

def _run_fit(k, max_steps=13, n=208, **fit_kw):
    eng = JitEngine()
    tr = Trainer(None, engine=eng, seed=0)
    ml = MetricsLogger(None, log_every=1)  # records EVERY step's metrics
    r = tr.fit(_tiny_ds(n), epochs=2, batch_size=16, log_every=0,
               steps_per_call=k, metrics_logger=ml, max_steps=max_steps,
               **fit_kw)
    return r, ml.records, jax.device_get(tr.state.params)


def test_steps_per_call_parity_bitwise():
    """k=8 must produce the step-for-step identical loss/accuracy
    trajectory and final params as k=1 on the same seed — including a
    5-step tail chunk (13 = 8 + 5) and an epoch boundary."""
    r1, recs1, p1 = _run_fit(1)
    r8, recs8, p8 = _run_fit(8)
    assert r1["steps"] == r8["steps"] == 13
    traj1 = [(m["step"], m["loss"], m["accuracy"]) for m in recs1]
    traj8 = [(m["step"], m["loss"], m["accuracy"]) for m in recs8]
    assert traj1 == traj8
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p8)):
        np.testing.assert_array_equal(a, b)


def test_resolve_steps_per_call():
    resolve = Trainer.resolve_steps_per_call
    assert resolve(None) == DEFAULT_STEPS_PER_CALL
    # zero-downshift telemetry: metric records ride the scan's stacked
    # trajectory and the watchdog rescales its budget to the chunk, so
    # neither forces the host between every step any more
    assert resolve(None, metrics_logger=object()) == DEFAULT_STEPS_PER_CALL
    assert resolve(None, watchdog=object()) == DEFAULT_STEPS_PER_CALL
    assert resolve(None, target_accuracy=0.9) == 1
    # a sub-chunk checkpoint cadence caps auto's k (state only exists at
    # chunk boundaries; the requested crash-loss window is honored)
    assert resolve(None, checkpoint_every=4) == 4
    assert resolve(None, checkpoint_every=50) == DEFAULT_STEPS_PER_CALL
    assert resolve(3) == 3
    assert resolve(5, metrics_logger=object()) == 5  # explicit wins
    assert resolve(8, checkpoint_every=2) == 8       # explicit wins
    with pytest.raises(ValueError):
        resolve(0)


def test_fit_auto_chunks_and_reports_shape():
    eng = JitEngine()
    tr = Trainer(None, engine=eng, seed=0)
    r = tr.fit(_tiny_ds(), epochs=1, batch_size=16, log_every=0,
               max_steps=10)
    assert r["steps_per_call"] == DEFAULT_STEPS_PER_CALL
    assert r["prefetch_depth"] == 2
    assert r["steps"] == 10  # 8-chunk + truncated 2-chunk honors max_steps
    assert r["step_time"]["steps"] == 10  # per-step times, not per-chunk


def test_fit_auto_keeps_chunking_with_metrics_logger():
    """A metrics logger no longer downshifts auto mode: records are
    flushed per chunk from the scan's stacked trajectory, step-exact."""
    eng = JitEngine()
    tr = Trainer(None, engine=eng, seed=0)
    ml = MetricsLogger(None, log_every=1)
    r = tr.fit(_tiny_ds(64), epochs=1, batch_size=16, log_every=0,
               metrics_logger=ml, max_steps=3)
    assert r["steps_per_call"] == DEFAULT_STEPS_PER_CALL
    assert [rec["step"] for rec in ml.records] == [1, 2, 3]


def test_metrics_stream_parity_k8_vs_k1_on_disk(tmp_path):
    """Acceptance: with a file-backed metrics sink and steps_per_call=8,
    fit does NOT downshift, and the per-step loss/accuracy records in the
    JSONL stream are bitwise identical to k=1 on the same seed."""
    def run(k):
        eng = JitEngine()
        tr = Trainer(None, engine=eng, seed=0)
        path = tmp_path / f"metrics_k{k}.jsonl"
        ml = MetricsLogger(path, log_every=1)
        r = tr.fit(_tiny_ds(), epochs=2, batch_size=16, log_every=0,
                   steps_per_call=k, metrics_logger=ml, max_steps=13)
        ml.close()
        lines = [json.loads(line) for line in path.read_text().splitlines()]
        return r, lines

    r1, recs1 = run(1)
    r8, recs8 = run(8)
    assert r8["steps_per_call"] == 8  # no downshift under the sink
    assert r8["chunk_sizes"] == [5, 8]  # 13 = 8 + 5-step tail
    traj = lambda recs: [(m["step"], m["loss"], m["accuracy"])  # noqa: E731
                         for m in recs]
    assert len(recs8) == 13
    assert traj(recs1) == traj(recs8)
    assert all(m["schema_version"] == 1 for m in recs8)


def test_watchdog_rides_chunked_drain_without_firing():
    """Satellite: watchdog_timeout works with steps_per_call=8 — the stall
    budget rescales to k × per-step budget and chunk-boundary beats keep
    it fed, so a healthy run never fires."""
    from distributed_tensorflow_tpu.utils.failure import Watchdog

    eng = JitEngine()
    tr = Trainer(None, engine=eng, seed=0)
    stalls = []
    with Watchdog(timeout=5.0, on_stall=stalls.append,
                  poll_interval=0.01) as wd:
        r = tr.fit(_tiny_ds(), epochs=2, batch_size=16, log_every=0,
                   steps_per_call=8, watchdog=wd, max_steps=13)
    assert r["steps_per_call"] == 8      # no downshift under the watchdog
    assert wd.timeout == 40.0            # k × per-step budget
    assert r["watchdog_beats"] == wd.beats >= 2  # one per chunk flush
    assert r["watchdog_stalls"] == 0 and not stalls


def test_watchdog_fires_on_stalled_chunk():
    """Satellite: a chunk that exceeds k × per-step budget IS a stall —
    the on_stall callback fires from the monitor thread mid-chunk."""
    import time as _time

    from distributed_tensorflow_tpu.utils.failure import Watchdog

    class SlowEngine(JitEngine):
        def many_step(self, state, xs_seq, ys_seq):
            state, m = super().many_step(state, xs_seq, ys_seq)
            jax.block_until_ready(m)
            _time.sleep(0.6)  # well past the scaled 8 × 0.02 s budget
            return state, m

    eng = SlowEngine()
    tr = Trainer(None, engine=eng, seed=0)
    stalls = []
    # armed from construction: the stalled chunk is the FIRST dispatch,
    # before any beat exists to arm on
    with Watchdog(timeout=0.02, on_stall=stalls.append,
                  poll_interval=0.01, arm_on_first_beat=False) as wd:
        tr.fit(_tiny_ds(), epochs=1, batch_size=16, log_every=0,
               steps_per_call=8, watchdog=wd, max_steps=13)
        assert abs(wd.timeout - 0.16) < 1e-9
    assert wd.stall_episodes >= 1 and stalls


def test_fit_auto_downshifts_for_target_accuracy():
    eng = JitEngine()
    tr = Trainer(None, engine=eng, seed=0)
    r = tr.fit(_tiny_ds(), epochs=1, batch_size=16, log_every=0,
               eval_ds=_tiny_ds(64), target_accuracy=0.05, eval_every=2,
               max_steps=6)
    assert r["steps_per_call"] == 1
    assert r["reached_target"]  # 5% on a 4-class task: first eval crosses


def test_explicit_chunked_drain_with_target_evals_at_boundaries():
    eng = JitEngine()
    tr = Trainer(None, engine=eng, seed=0)
    r = tr.fit(_tiny_ds(), epochs=2, batch_size=16, log_every=0,
               steps_per_call=4, eval_ds=_tiny_ds(64),
               target_accuracy=0.05, eval_every=4, max_steps=20)
    assert r["steps_per_call"] == 4
    assert r["reached_target"]
    assert r["steps"] % 4 == 0  # early-stop landed on a chunk boundary


def test_auto_caps_chunk_at_checkpoint_cadence(tmp_path):
    from distributed_tensorflow_tpu.utils.checkpoint import CheckpointManager

    mgr = CheckpointManager(tmp_path / "c", max_to_keep=10)
    eng = JitEngine()
    tr = Trainer(None, engine=eng, seed=0)
    r = tr.fit(_tiny_ds(), epochs=2, batch_size=16, log_every=0,
               checkpoint_manager=mgr, checkpoint_every=4, max_steps=13)
    # auto caps k at checkpoint_every, so every due step IS a boundary —
    # the crash-loss window the user asked for is honored
    assert r["steps_per_call"] == 4
    assert {4, 8, 12} <= set(mgr.steps())
    assert mgr.latest_step() == 13  # final state always checkpointed


def test_explicit_chunk_checkpoints_at_boundaries(tmp_path):
    from distributed_tensorflow_tpu.utils.checkpoint import CheckpointManager

    mgr = CheckpointManager(tmp_path / "c", max_to_keep=10)
    eng = JitEngine()
    tr = Trainer(None, engine=eng, seed=0)
    r = tr.fit(_tiny_ds(), epochs=2, batch_size=16, log_every=0,
               steps_per_call=8, checkpoint_manager=mgr, checkpoint_every=4,
               max_steps=13)
    # explicit k wins: due steps 4/8/12 land on the first chunk boundary
    # at/after them (8, 13); the final state is always checkpointed
    assert r["steps_per_call"] == 8
    assert 8 in mgr.steps()
    assert mgr.latest_step() == 13


def test_chunked_heartbeat_logs_exact_steps():
    eng = JitEngine()
    tr = Trainer(None, engine=eng, seed=0)
    lines = []
    tr.fit(_tiny_ds(), epochs=1, batch_size=16, log_every=3,
           log_fn=lines.append, max_steps=8)  # one chunk of 8
    # per-step metrics come back stacked, so mid-chunk heartbeat steps
    # (3, 6) log their OWN step's values, not the chunk boundary's
    assert [int(line.split()[1]) for line in lines] == [3, 6]


def test_chunked_nan_guard_raises():
    import flax.linen as nn

    from distributed_tensorflow_tpu.utils.failure import TrainingDiverged

    class NaNEngine(JitEngine):
        def __init__(self):
            super().__init__()

            class _Bad(nn.Module):
                @nn.compact
                def __call__(self, x, train: bool = False):
                    return nn.Dense(4)(x.reshape((x.shape[0], -1))) / 0.0

            self.model = _Bad()

    tr = Trainer(None, engine=NaNEngine(), seed=0)
    with pytest.raises(TrainingDiverged):
        tr.fit(_tiny_ds(64), epochs=1, batch_size=16, log_every=1,
               log_fn=lambda s: None, steps_per_call=4)


# -------------------------------------- acceptance config (shard_map envs)

def test_mnist_cnn_sync_parity_steps_per_call(mesh8):
    """The acceptance-letter configuration: MNIST CNN under SyncEngine,
    steps_per_call=8 vs 1, identical per-step loss/accuracy trajectory on
    the same seed."""
    from distributed_tensorflow_tpu.data.loaders import load_dataset
    from distributed_tensorflow_tpu.engines import SyncEngine
    from distributed_tensorflow_tpu.models import create_model

    ds = load_dataset("mnist", split="train")

    def run(k):
        eng = SyncEngine(create_model("cnn", num_classes=ds.num_classes),
                         mesh=mesh8)
        tr = Trainer(None, engine=eng, seed=0)
        ml = MetricsLogger(None, log_every=1)
        r = tr.fit(ds, epochs=1, batch_size=64, log_every=0,
                   steps_per_call=k, metrics_logger=ml, max_steps=12)
        return r, [(m["step"], m["loss"], m["accuracy"])
                   for m in ml.records]

    r1, traj1 = run(1)
    r8, traj8 = run(8)
    assert r1["steps"] == r8["steps"] == 12
    assert r8["steps_per_call"] == 8  # metrics sink never downshifts
    assert traj1 == traj8
