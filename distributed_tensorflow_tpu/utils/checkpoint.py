"""Checkpoint / resume.

The reference has NO persistence: weights live only in process memory and
cross the wire as pickle, never touching disk (SURVEY.md §5 — reference
server.py:81, :104); any crash loses the run.  Here full TrainState
(params + optimizer state + step + rng) checkpoints atomically via Orbax,
with retention and resume — including per-device-stacked states from the
async/gossip engines (Orbax gathers sharded arrays transparently).

Two write disciplines share one on-disk format:

* :class:`CheckpointManager` — synchronous: ``save`` blocks the caller
  for the full device→host transfer + Orbax write + retention sweep.
* :class:`AsyncCheckpointManager` — ``save`` snapshots the TrainState off
  the live (donated) device buffers, starts a non-blocking device→host
  transfer, and hands the snapshot to a single background writer thread;
  the caller dispatches its next chunk immediately.  At most one save is
  in flight (a second ``save`` waits on the previous write — bounded host
  memory); writer errors re-raise at the next ``save``/``wait``/
  ``close``; ``restore`` begins with a drain barrier so resume never
  races a pending write.

Both write atomically: Orbax writes into ``tmp_step_N``, the directory is
fsynced, then renamed to ``step_N`` — a crash mid-write leaves only a
``tmp_`` directory (invisible to ``steps()``/``restore`` and cleaned on
the next manager start), never a half-written visible checkpoint.
"""

from __future__ import annotations

import contextlib
import json
import os
import queue
import re
import shutil
import threading
import time
from pathlib import Path
from typing import Any

import jax
import orbax.checkpoint as ocp

_STEP_DIR = re.compile(r"^step_(\d+)$")
_TMP_DIR = re.compile(r"^tmp_step_(\d+)$")

# JSON sidecar riding INSIDE each step directory (Orbax ignores files it
# did not write): the elastic payload — data-iterator state + save wall
# time — that makes a checkpoint a resumable, exactly-once object
# (elastic/data_state.py).  Written into tmp_step_N BEFORE the fsync +
# rename, so the payload is atomic with the checkpoint itself: a visible
# step_N either carries its sidecar or was written by an older build
# (restore then degrades to replay accounting, never to a torn read).
_EXTRA_FILE = "elastic.json"


def _is_key(x) -> bool:
    return hasattr(x, "dtype") and jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key)


def _unkey(tree):
    """Typed PRNG keys aren't serializable — store their raw uint32 data."""
    return jax.tree.map(
        lambda x: jax.random.key_data(x) if _is_key(x) else x, tree)


def _rekey(template, tree):
    return jax.tree.map(
        lambda t, r: jax.random.wrap_key_data(jax.numpy.asarray(r))
        if _is_key(t) else r,
        template, tree)


def _host_template(template):
    """Host-side restore template (structure + shape + dtype).  Single
    process: the real values via device_get.  Multi-process: shape/dtype
    zeros — device_get cannot read non-addressable shards, and Orbax only
    needs the structure to restore into."""
    t = _unkey(template)
    if jax.process_count() == 1:
        return jax.device_get(t)
    import numpy as np

    return jax.tree.map(
        lambda a: np.zeros(a.shape, a.dtype) if hasattr(a, "shape") else a, t)


def _fsync_dir(path: Path) -> None:
    """Best-effort directory fsync: make the tmp→final rename (and the
    entries under it) durable before the checkpoint becomes visible."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _fsync_tree(path: Path) -> None:
    """fsync every file (then directory) under ``path``: the rename must
    never become durable before the bytes it points at — a power loss
    after a data-less rename would persist a visible ``step_N`` whose
    array files are still page-cache-only, the exact torn state the
    tmp/rename discipline exists to rule out."""
    for p in sorted(path.rglob("*")):
        if p.is_dir():
            _fsync_dir(p)
            continue
        try:
            fd = os.open(p, os.O_RDONLY)
        except OSError:
            continue
        try:
            os.fsync(fd)
        except OSError:
            pass
        finally:
            os.close(fd)
    _fsync_dir(path)


def _snapshot(state: Any) -> Any:
    """Decouple a TrainState from its live device buffers.

    Every engine's step donates its input state (``donate_argnums=0``), so
    a background writer cannot read the trainer's arrays once the next
    chunk is dispatched.  The copy is an on-device op (async dispatch —
    XLA orders it after the producing chunk and before the donated
    reuse), and ``copy_to_host_async`` starts the device→host transfer on
    the stream without blocking, so by the time the writer calls
    ``device_get`` the bytes are typically already on the host."""
    def snap(x):
        if isinstance(x, jax.Array):
            c = x.copy()
            with contextlib.suppress(Exception):  # transfer hint only —
                c.copy_to_host_async()            # device_get still works
            return c
        return x

    return jax.tree.map(snap, _unkey(state))


class AsyncCheckpointError(RuntimeError):
    """A background checkpoint write failed; re-raised on the training
    thread at the next ``save``/``wait``/``close``."""


class CheckpointManager:
    """Step-numbered checkpoints under ``directory`` with retention."""

    asynchronous = False

    def __init__(self, directory: str | Path, max_to_keep: int = 3):
        self.directory = Path(directory).absolute()
        self.directory.mkdir(parents=True, exist_ok=True)
        self.max_to_keep = max_to_keep
        self._ckptr = ocp.PyTreeCheckpointer()
        self._clean_tmp()

    def _clean_tmp(self) -> None:
        """A ``tmp_step_N`` left by a crashed write is garbage by
        definition (the rename never happened): sweep it on start —
        under EITHER discipline, a torn tmp dir holds a full TrainState
        of dead disk."""
        for p in self.directory.iterdir():
            if _TMP_DIR.match(p.name) and p.is_dir():
                shutil.rmtree(p, ignore_errors=True)

    # ------------------------------------------------------------------ save
    def _resolve_step(self, state: Any, step: int | None) -> int:
        if step is not None:
            return int(step)
        s = state.step
        if getattr(s, "is_fully_addressable", True):
            return int(jax.device_get(s).max())
        # device_get rejects non-addressable shards (stacked async state on
        # multi-process meshes); all rows carry the same step, so local
        # shards suffice
        import numpy as np

        return int(max(np.asarray(sh.data).max()
                       for sh in s.addressable_shards))

    def _write(self, step: int, host_state: Any,
               extra: dict | None = None) -> None:
        """Atomic visible write: Orbax into ``tmp_step_N``, fsync, rename
        to ``step_N``.  A crash anywhere before the rename leaves only the
        ``tmp_`` directory — never a half-written ``step_N``.  ``extra``
        (the elastic sidecar) is written into the tmp directory, so it
        becomes visible atomically with the checkpoint."""
        tmp = self.directory / f"tmp_step_{step}"
        final = self.directory / f"step_{step}"
        if tmp.exists():
            shutil.rmtree(tmp)
        self._ckptr.save(tmp, host_state, force=True)
        if extra is not None:
            (tmp / _EXTRA_FILE).write_text(json.dumps(extra))
        _fsync_tree(tmp)
        if final.exists():
            shutil.rmtree(final)
        os.replace(tmp, final)
        _fsync_dir(self.directory)

    def save(self, state: Any, step: int | None = None,
             extra: dict | None = None) -> Path:
        step = self._resolve_step(state, step)
        path = self.directory / f"step_{step}"
        state = _unkey(state)
        if jax.process_count() > 1:
            # device_get cannot read non-addressable shards (tp/pp/ep state
            # on multi-process meshes): gather full host copies everywhere,
            # then let exactly one process write the shared directory
            from jax.experimental import multihost_utils

            host_state = multihost_utils.process_allgather(state)
            if jax.process_index() == 0:
                self._write(step, host_state, extra)
                self._retain()
            multihost_utils.sync_global_devices(f"ckpt_save_{step}")
        else:
            self._write(step, jax.device_get(state), extra)
            self._retain()
        return path

    def _retain(self) -> None:
        steps = sorted(self.steps())
        for s in steps[: -self.max_to_keep] if self.max_to_keep else []:
            shutil.rmtree(self.directory / f"step_{s}", ignore_errors=True)

    # ------------------------------------------------------- async interface
    # no-ops on the synchronous manager, so the Trainer/harness treat both
    # disciplines uniformly (drain barriers cost nothing here)
    def wait(self, reraise: bool = True) -> None:
        """No save is ever in flight on the synchronous manager."""

    def close(self, reraise: bool = True) -> None:
        """Nothing to join on the synchronous manager."""

    # --------------------------------------------------------------- restore
    def steps(self) -> list[int]:
        out = []
        for p in self.directory.iterdir():
            m = _STEP_DIR.match(p.name)
            if m and p.is_dir():
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_step(self) -> int | None:
        steps = self.steps()
        return steps[-1] if steps else None

    def load_extra(self, step: int | None = None) -> dict | None:
        """The elastic sidecar saved with ``step`` (latest when None):
        data-iterator state + save wall time (elastic/data_state.py).
        ``None`` when the checkpoint predates the sidecar (older builds) —
        callers then fall back to replay accounting — or when the step
        does not exist."""
        if step is None:
            step = self.latest_step()
        if step is None:
            return None
        path = self.directory / f"step_{step}" / _EXTRA_FILE
        try:
            payload = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError):
            return None
        return payload if isinstance(payload, dict) else None

    def restore(self, template: Any, step: int | None = None) -> Any:
        """Restore into the structure/shardings of ``template`` (a freshly
        initialized TrainState — engine.init_state output)."""
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self.directory}")
        restored = self._ckptr.restore(
            self.directory / f"step_{step}",
            item=_host_template(template))
        restored = _rekey(template, restored)
        if jax.process_count() > 1:
            # device_put rejects non-addressable shardings; the jit-identity
            # placement (mesh.state_to_global) reshards host-replicated
            # values onto the global mesh instead
            from distributed_tensorflow_tpu.parallel import mesh as meshlib

            shardings = jax.tree.map(
                lambda t: t.sharding if hasattr(t, "sharding") else None,
                template)
            return meshlib.state_to_global(restored, shardings)
        # re-place on device with the template's shardings
        return jax.tree.map(
            lambda t, r: jax.device_put(r, t.sharding)
            if hasattr(t, "sharding") else r,
            template, restored)


class AsyncCheckpointManager(CheckpointManager):
    """Checkpointing off the training critical path (see module docstring).

    ``save`` costs the training thread a device snapshot (+ any wait for a
    still-running previous write — the at-most-one-in-flight backpressure
    that bounds host memory to one extra TrainState); the device→host
    transfer, Orbax write, fsync-rename and retention sweep run on one
    background writer thread.  Training-thread seconds spent blocked
    accumulate in ``wait_s``; writer seconds that ran GENUINELY
    concurrently with training accumulate in ``overlapped_s`` (write
    wall time the trainer stood blocked on is counted once, in
    ``wait_s`` — never double-booked as overlap) — the split the run
    report surfaces.

    ``tracer``, when set (the Trainer wires its own in), gets a
    ``ckpt_write`` span per background write, the overlapped twin of the
    training thread's ``ckpt_snapshot`` span.

    Multi-process meshes fall back to the synchronous path per save: the
    pod save is a collective (process_allgather + barrier) and cannot
    leave the training thread without racing training's own collectives.
    """

    asynchronous = True

    def __init__(self, directory: str | Path, max_to_keep: int = 3):
        super().__init__(directory, max_to_keep)
        self.tracer = None          # optional observability.Tracer
        self.wait_s = 0.0           # training-thread seconds blocked here
        # writer seconds GENUINELY concurrent with training: the writer
        # tallies its wall time, minus any of it the trainer spent
        # blocked waiting on that same write (see _blocked)
        self.overlapped_s = 0.0
        self.saves = 0
        self._idle = threading.Event()
        self._idle.set()
        self._error: BaseException | None = None
        self._queue: queue.SimpleQueue = queue.SimpleQueue()
        self._thread: threading.Thread | None = None
        self._acct_lock = threading.Lock()

    # ----------------------------------------------------------- writer side
    def _ensure_writer(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._writer_loop, name="ckpt-writer", daemon=True)
            self._thread.start()

    def _writer_loop(self) -> None:
        while True:
            job = self._queue.get()
            if job is None:
                return
            step, snapshot, extra = job
            t0 = time.perf_counter()
            try:
                span = (self.tracer.span("ckpt_write", step=step)
                        if self.tracer is not None
                        else contextlib.nullcontext())
                with span:
                    # the transfer was started by copy_to_host_async at
                    # snapshot time; device_get here mostly just collects
                    self._write(step, jax.device_get(snapshot), extra)
                    self._retain()
            except BaseException as e:  # noqa: BLE001 — surfaced on the
                self._error = e         # training thread at the next sync
            finally:
                with self._acct_lock:
                    self.overlapped_s += time.perf_counter() - t0
                self._idle.set()

    def _reraise(self) -> None:
        if self._error is not None:
            err, self._error = self._error, None
            raise AsyncCheckpointError(
                f"background checkpoint write under {self.directory} "
                f"failed: {type(err).__name__}: {err}") from err

    # --------------------------------------------------------- training side
    def _blocked(self, seconds: float) -> None:
        """Account training-thread seconds spent waiting on an in-flight
        write.  They go into ``wait_s`` AND come back out of
        ``overlapped_s``: the writer tallies its full wall time, but time
        the trainer stood blocked on it was not overlap — the two windows
        nest (the wait ends when the write's ``_idle.set`` fires, after
        the writer's own tally), so the difference is the genuinely
        concurrent share.  Clamped at 0 against enqueue→dequeue jitter."""
        self.wait_s += seconds
        with self._acct_lock:
            self.overlapped_s = max(0.0, self.overlapped_s - seconds)

    def save(self, state: Any, step: int | None = None,
             extra: dict | None = None) -> Path:
        if jax.process_count() > 1:
            return super().save(state, step, extra)  # pod saves stay collective
        step = self._resolve_step(state, step)
        t0 = time.perf_counter()
        self._idle.wait()  # backpressure: at most ONE save in flight
        self._blocked(time.perf_counter() - t0)
        self._reraise()
        snapshot = _snapshot(state)
        self._idle.clear()
        self._ensure_writer()
        self._queue.put((step, snapshot, extra))
        self.saves += 1
        return self.directory / f"step_{step}"

    def wait(self, reraise: bool = True) -> None:
        """Drain barrier: block until no write is in flight; surface any
        writer error (unless ``reraise=False`` — exception-path cleanup
        must not mask the original failure)."""
        t0 = time.perf_counter()
        self._idle.wait()
        self._blocked(time.perf_counter() - t0)
        if reraise:
            self._reraise()

    def close(self, reraise: bool = True) -> None:
        """Drain, stop the writer thread, surface any pending error."""
        self.wait(reraise=False)
        if self._thread is not None and self._thread.is_alive():
            self._queue.put(None)
            self._thread.join(timeout=60)
        self._thread = None
        if reraise:
            self._reraise()

    def restore(self, template: Any, step: int | None = None) -> Any:
        self.wait()  # resume must never read a directory mid-write
        return super().restore(template, step)

    def latest_step(self) -> int | None:
        self.wait()  # an in-flight write IS the latest step once visible
        return super().latest_step()

    def load_extra(self, step: int | None = None) -> dict | None:
        self.wait()  # the sidecar lands with the write it rides
        return super().load_extra(step)

    def stats(self) -> dict[str, Any]:
        return {"saves": self.saves, "wait_s": self.wait_s,
                "overlapped_s": self.overlapped_s}
