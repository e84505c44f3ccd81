"""Drives ``engines/allreduce.Trainer.fit`` over the sync engine for a timed
window, and compares its first chunk with the plain reference.

The cell's file gives the job (``job``: per-chip batch, sequence length,
attention, dtype, learning rate, chunk length, corpus) and the limits of
the comparison (``limits``).  One ``Trainer`` with its engine and state is
built in set-up, driven from the seed through one chunk of
``steps_per_call`` steps (the one compiled program the window runs, and
the only one this driver ever builds), then through the warm-up chunks,
and handed to the window: every step goes through ``fit`` and its device
prefetch, on rows of the seeded corpus that all differ (the stream is
continued from call to call through ``fit``'s ``data_state``).  The mesh
is a 1-D ``data`` mesh over all the devices the run was given."""

from __future__ import annotations

import contextlib
import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.drivers import gpt_tree
from benchmarks.lib import costs, reference, traffic, weights

ADAM_B1 = 0.9           # optax.adam's defaults, which the engine uses
EPOCHS = 10 ** 9        # fit() loops over the corpus; max_steps ends it


class SpanClock:
    """The tracer handed to ``fit``: keeps every span's name, start and end
    on the host clock and mirrors it into the profiler's trace."""

    def __init__(self):
        self.spans: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def span(self, name, **attrs):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield attrs
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def event(self, *a, **k): pass      # fit() also sends these two
    def gauge(self, *a, **k): pass


class LossLog:
    """``fit``'s ``metrics_logger``: every step's loss, in order."""

    def __init__(self):
        self.losses: list[float] = []

    def should_log(self, step): return True
    def log(self, step, **floats): self.losses.append(float(floats["loss"]))


def flat(norms: dict) -> np.ndarray:
    """Leaf norms (``weights.leaf_norms``) as one vector, in key order."""
    parts = []
    for key in sorted(norms):
        node = norms[key]
        vals = [node[k] for k in sorted(node)] if isinstance(node, dict) \
            else [node]
        parts += [np.asarray(v, np.float64).reshape(-1) for v in vals]
    return np.concatenate(parts)


def worst_gap(got: np.ndarray, ref: np.ndarray, keep=None) -> float:
    """Worst leaf's gap between the two norms, against the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    scale = np.maximum(ref, np.median(ref))
    gap = np.abs(got - ref) / scale
    return float(np.max(gap if keep is None else gap[keep]))


def compare(got: dict, ref: dict) -> dict[str, float]:
    """The numbers of ``correct`` after the first chunk of k steps: each
    step's loss, and by the worst leaf the norm of Adam's first moment
    (the moving average of the k gradients as the optimizer got them) and
    of the parameters' change.  Leaves whose mean gradient in the
    reference is under a thousandth of the median leaf's move under Adam
    by round-off alone and are left out of the change."""
    out = {f"loss_step{i + 1}_rel":
           abs(got["losses"][i] - ref["losses"][i]) / abs(ref["losses"][i])
           for i in range(len(ref["losses"]))}
    out["moment_norm_gap"] = worst_gap(got["moment"], ref["moment"])
    moved = ref["moment"] >= 1e-3 * np.median(ref["moment"])
    out["update_norm_gap"] = worst_gap(got["change"], ref["change"], moved)
    return out


class Run:
    def __init__(self, cell: dict, config: dict, *, seed: int, seconds: float,
                 devices, note=print):
        self.cell, self.config, self.job = cell, config, cell["job"]
        self.seed, self.seconds = int(seed), float(seconds)
        self.devices, self.note = list(devices), note
        self.seq = int(self.job["seq_len"])
        self.k = int(self.job["steps_per_call"])
        self.global_batch = int(self.job["per_chip_batch"]) * len(self.devices)
        self.ref_kw = dict(heads=int(config["n_head"]),
                           eps=float(config["layer_norm_epsilon"]))
        self.steps_done = 0

    # ------------------------------------------------------------ set-up
    def build(self) -> None:
        """The engine and the trainer: once per process."""
        from distributed_tensorflow_tpu.engines.allreduce import Trainer
        from distributed_tensorflow_tpu.engines.sync import SyncEngine
        from distributed_tensorflow_tpu.models import create_model
        from distributed_tensorflow_tpu.parallel import mesh as meshlib

        self.mesh = meshlib.create_mesh(devices=self.devices)
        model = create_model(
            "gpt", dtype=self.job["dtype"],
            attention_impl=self.job["attention"],
            **gpt_tree.model_kwargs(self.config))
        engine = SyncEngine(model, mesh=self.mesh,
                            learning_rate=float(self.job["learning_rate"]))
        self.trainer = Trainer(None, engine=engine, seed=self.seed % 2 ** 31)

    def seed_state(self, seed: int) -> None:
        """Weights on the device from the seed, in one jitted call, laid
        into a fresh ``TrainState`` as ``Engine.init_state`` lays its own;
        the corpus and the rows of the first chunk."""
        from distributed_tensorflow_tpu.data import Dataset
        from distributed_tensorflow_tpu.engines.base import TrainState
        from distributed_tensorflow_tpu.parallel import mesh as meshlib

        self.seed = int(seed)
        self.trainer.seed = self.seed % 2 ** 31
        engine = self.trainer.engine
        params = jax.jit(lambda w: engine.precision.cast_params(
            gpt_tree.to_flax(w)))(weights.make(self.config, self.seed))
        state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                           opt_state=jax.jit(engine.tx.init)(params),
                           rng=jax.random.key(self.seed))
        self.trainer.state = meshlib.state_to_global(
            state, meshlib.replicated(self.mesh))
        corpus = traffic.token_corpus(
            self.seed, int(self.job["corpus_rows"]), self.seq,
            int(self.config["vocab_size"]),
            float(self.job.get("zipf_exponent", 1.0)))
        self.dataset = Dataset(
            x=corpus[:, :-1], y=corpus[:, 1:],
            num_classes=int(self.config["vocab_size"]), name="bench_corpus",
            synthetic=True, batch_size=self.global_batch)
        self.steps_done = 0
        first = list(itertools.islice(self.dataset.batches(
            self.global_batch, shuffle=True, seed=self.trainer.seed, epoch=0,
            drop_remainder=True), self.k))
        self.first_x = np.stack([b[0] for b in first])
        self.first_y = np.stack([b[1] for b in first])
        rows = self.first_x.reshape(-1, self.seq)
        if len({r.tobytes() for r in rows}) != len(rows):
            raise RuntimeError("the first chunk's rows do not all differ")

    def fit(self, steps: int, **kw) -> dict:
        """``steps`` more steps of the one trainer, continuing the corpus
        where the last call stopped."""
        per_epoch = len(self.dataset) // self.global_batch
        if per_epoch % self.k:
            raise ValueError(
                f"corpus_rows / global batch = {per_epoch} steps an epoch "
                f"is no multiple of steps_per_call = {self.k}")
        where = {"epoch": self.steps_done // per_epoch,
                 "batch_index": self.steps_done % per_epoch,
                 "seed": self.trainer.seed, "batch_size": self.global_batch,
                 "dataset_len": len(self.dataset),
                 "dataset": self.dataset.name, "version": 1}
        result = self.trainer.fit(
            self.dataset, epochs=EPOCHS, max_steps=steps, log_every=0,
            steps_per_call=self.k, data_state=where, **kw)
        if result.get("resume_replay_steps"):
            raise RuntimeError("fit() restarted the corpus instead of "
                               "continuing it")
        self.steps_done += result["steps"]
        return result

    def first_chunk(self) -> dict:
        """Steps 1 to k from the seeded state, through ``fit`` and the
        chunk program that the window runs: each step's loss, Adam's first
        moment after the chunk and the parameters' change over it."""
        log = LossLog()
        self.fit(self.k, metrics_logger=log)
        state = self.trainer.state
        mu = next(s.mu for s in jax.tree.leaves(
            state.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
            if hasattr(s, "mu"))
        moment, change = jax.device_get(jax.jit(lambda m, p, w0: (
            weights.leaf_norms(gpt_tree.from_flax(m)),
            weights.leaf_norms(jax.tree.map(
                jnp.subtract, gpt_tree.from_flax(p), w0))))(
                    mu, state.params, weights.make(self.config, self.seed)))
        return {"losses": log.losses[:self.k], "moment": flat(moment),
                "change": flat(change)}

    def setup(self) -> None:
        self.build()
        self.seed_state(self.seed)
        self.note(f"weights and corpus made; first chunk of {self.k} steps")
        self.got = self.first_chunk()
        self.note(f"losses {self.got['losses']}; warming")
        warm = int(self.job.get("warm_chunks", 2))
        t0 = time.perf_counter()
        self.fit(self.k * warm)
        self.chunk_s = (time.perf_counter() - t0) / warm
        self.note(f"a chunk of {self.k} steps takes {self.chunk_s:.3f} s")

    # ------------------------------------------------------------ window
    def trace_slice(self) -> tuple[float, float]:
        """About three chunks, a third of the way into the window."""
        chunks = float(self.job.get("trace_chunks", 3))
        return self.seconds / 3.0, chunks * self.chunk_s

    def window(self) -> dict:
        chunks = max(1, int(self.seconds / self.chunk_s))
        clock = SpanClock()
        t0 = time.perf_counter()
        result = self.fit(self.k * chunks, tracer=clock)
        window_s = time.perf_counter() - t0
        ends = [end for name, _, end in clock.spans if name == "materialize"]
        tokens = result["steps"] * self.global_batch * self.seq
        heads = int(self.config["n_head"])
        return {
            "attempted": self.k * chunks,
            "failed": self.k * chunks - result["steps"],
            "window_s": window_s, "tokens": tokens, "steps": result["steps"],
            "chunk_gap_s": list(np.diff(ends)),
            "model_flops": tokens * costs.train_flops_per_token(
                self.config, self.seq),
            "final_loss": result.get("final_loss"),
            "flash_call": {"batch": int(self.job["per_chip_batch"]),
                           "heads": heads, "seq": self.seq,
                           "head_dim": int(self.config["n_embd"]) // heads},
        }

    # ------------------------------------------------------------- check
    def free(self) -> None:
        self.trainer.state = None
        jax.clear_caches()

    def reference(self, mode: str = "f32", fault: str | None = None) -> dict:
        """The plain reference over the same k batches, from the same
        weights; another ``mode`` is the control of ``correct`` and a
        ``fault`` one of ``reference.FAULTS``."""
        lr = float(self.job["learning_rate"])

        @jax.jit
        def follow(w0, xs, ys):
            losses, m, w = reference.adam_steps(
                w0, xs, ys, lr=lr, b1=ADAM_B1, mode=mode, fault=fault,
                **self.ref_kw)
            return (losses, weights.leaf_norms(m),
                    weights.leaf_norms(jax.tree.map(jnp.subtract, w, w0)))

        losses, moment, change = jax.device_get(follow(
            weights.make(self.config, self.seed),
            jnp.asarray(self.first_x), jnp.asarray(self.first_y)))
        return {"losses": [float(x) for x in losses], "moment": flat(moment),
                "change": flat(change)}

    def check(self, obs: dict) -> list[dict]:
        self.free()
        t0 = time.perf_counter()
        readings = compare(self.got, self.reference())
        self.note(f"reference followed {self.k} steps in "
                  f"{time.perf_counter() - t0:.1f} s")
        limits = self.cell["limits"]
        self.note("read, not compared: " + ", ".join(
            f"{k} {v:.3g}" for k, v in readings.items() if k not in limits))
        return [{"name": name, "value": readings[name], "limit": limits[name]}
                for name in limits]
