"""Dataset loading: local archives when present, deterministic synthetic
fallback otherwise (this environment has zero egress — nothing downloads).

Real-data formats understood:
  mnist / fashion_mnist  — keras-style ``.npz`` with x_train/y_train/x_test/y_test
  cifar10                — either ``cifar10.npz`` (same keys) or the original
                           ``cifar-10-batches-py`` pickle directory

Search order: $DTF_TPU_DATA_DIR, ~/.keras/datasets, ./datasets, /root/data.
The synthetic fallback draws each example from a fixed per-class prototype
plus noise, so models genuinely *learn* (accuracy targets in tests are
meaningful), and is deterministic in (name, split).
"""

from __future__ import annotations

import dataclasses
import os
import pickle
from pathlib import Path

import numpy as np

_SHAPES = {
    "mnist": ((28, 28), 10),
    "fashion_mnist": ((28, 28), 10),
    "cifar10": ((32, 32, 3), 10),
}


@dataclasses.dataclass
class Dataset:
    """Host-side dataset: plain numpy, batched lazily by the pipeline."""

    x: np.ndarray
    y: np.ndarray
    num_classes: int
    name: str = "dataset"
    synthetic: bool = False
    batch_size: int | None = None
    buffer_size: int = 10000
    # (index, count) when this dataset is one PROCESS's shard of a larger
    # logical dataset (multi-host input sharding): the Trainer then treats
    # batches as process-local rows of a global batch (engines/allreduce.py)
    process_shard: tuple[int, int] | None = None
    # which producer the last ``batches()`` call chose: "native" (the C++
    # prefetcher) or "python"; None before the first epoch
    input_path: str | None = None

    def __len__(self) -> int:
        return len(self.x)

    def shard(self, n_shards: int, index: int, even: bool = False) -> "Dataset":
        """Every n-th example, like `tf.data .shard` (reference initializer.py:44).

        ``even=True`` truncates every shard to ``len // n_shards`` so all
        shards are the same size — required when shards drive lock-step
        SPMD processes (unequal batch counts would deadlock collectives)."""
        x, y = self.x[index::n_shards], self.y[index::n_shards]
        if even:
            m = len(self.x) // n_shards
            x, y = x[:m], y[:m]
        return dataclasses.replace(self, x=x, y=y)

    def process_shard_of(self, n_procs: int, index: int) -> "Dataset":
        """This process's shard for multi-host training: EVEN shards (all
        processes must run the same batch count — uneven ones would wedge
        lock-step collectives) plus the ``process_shard`` marker the
        Trainer reads to assemble global batches from process-local rows.
        The two must always travel together; use this, not bare shard()."""
        return dataclasses.replace(
            self.shard(n_procs, index, even=True),
            process_shard=(index, n_procs))

    def with_batching(self, batch_size: int, buffer_size: int = 10000) -> "Dataset":
        return dataclasses.replace(
            self, batch_size=batch_size, buffer_size=buffer_size
        )

    def batches(self, batch_size: int | None = None, *, shuffle: bool = True,
                seed: int = 0, epoch: int = 0, drop_remainder: bool = False,
                native: bool | None = None, start_batch: int = 0):
        """Iterate (x, y, mask) batches for one epoch.

        ``native=None`` (default) uses the C++ prefetching pipeline when the
        native library is available AND the host has >1 core (the prefetch
        thread needs a core of its own to overlap with the training step;
        measured a wash on 1-core hosts), falling back to the pure-Python
        path; True requires the native path; False forces Python.  Both
        paths yield byte-identical batches (tests/test_native.py) and honor
        the shared iterator contract (data/pipeline.py module docstring):
        same-size (x, y, mask) batches plus ``close()`` for early release —
        what data.device_prefetch wraps to stage batches on device ahead
        of the training loop.

        ``start_batch`` > 0 resumes the epoch at its N-th batch (elastic
        restore, elastic/data_state.py): the shuffle permutation depends
        only on (seed, epoch), so the resumed stream continues the exact
        batch sequence the uninterrupted epoch would have produced.  The
        C++ pipeline stages from batch 0 only, so a mid-epoch resume takes
        the Python path (byte-identical batches either way); ``native=True``
        is rejected rather than silently replaying the skipped prefix.
        """
        from distributed_tensorflow_tpu.data.pipeline import iter_batches

        bs = batch_size or self.batch_size
        if bs is None:
            raise ValueError("batch_size not set; pass it or use with_batching()")
        if start_batch:
            if native:
                raise RuntimeError(
                    "the native pipeline has no mid-epoch resume (its C++ "
                    "cursor starts at batch 0); start_batch > 0 requires "
                    "the Python path")
            native = False
        if getattr(self.y, "ndim", 1) > 1:
            # the C++ gather stages SCALAR labels (native/batcher.py fills
            # a (batch,) int32 buffer): an LM dataset's (B, L) next-token
            # targets would silently flatten to (B,) garbage — gate to the
            # Python path, loudly when the caller forced native
            if native:
                raise RuntimeError(
                    "the native pipeline gathers scalar labels only; "
                    f"this dataset's targets are {self.y.ndim - 1}-D per "
                    "row (LM next-token layout) — use the Python path")
            native = False
        if native is None and (os.cpu_count() or 1) < 2:
            native = False
        if native is not False:
            try:
                nb = self._native_batcher(bs)
                it = nb.epoch(shuffle=shuffle, seed=seed, epoch=epoch,
                              drop_remainder=drop_remainder)
                self.input_path = "native"
                return it
            except RuntimeError:
                if native:
                    raise
        self.input_path = "python"
        return iter_batches(
            self.x, self.y, bs, shuffle=shuffle, seed=seed, epoch=epoch,
            drop_remainder=drop_remainder, start_batch=start_batch,
        )

    def _native_batcher(self, batch_size: int):
        """Cached per-batch-size native pipeline — reusing it across epochs
        keeps one C++ worker pool + staging buffers (and, for sharded
        datasets, one contiguous copy) alive for the whole run.  If the
        cached pipeline is mid-epoch (a concurrent iterator is active), a
        fresh uncached one preserves the independent-iterators contract of
        the Python path."""
        from distributed_tensorflow_tpu.native.batcher import NativeBatcher

        cache = self.__dict__.setdefault("_batcher_cache", {})
        nb = cache.get(batch_size)
        if nb is None:
            nb = NativeBatcher(self.x, self.y, batch_size)
            cache[batch_size] = nb
        elif nb.busy:
            nb = NativeBatcher(self.x, self.y, batch_size)
        return nb


def _search_dirs() -> list[Path]:
    dirs = []
    if os.environ.get("DTF_TPU_DATA_DIR"):
        dirs.append(Path(os.environ["DTF_TPU_DATA_DIR"]))
    dirs += [
        Path.home() / ".keras" / "datasets",
        Path("datasets"),
        Path("/root/data"),
    ]
    return [d for d in dirs if d.is_dir()]


def _find(*names: str) -> Path | None:
    for d in _search_dirs():
        for n in names:
            p = d / n
            if p.exists():
                return p
    return None


def _load_npz(path: Path, split: str):
    with np.load(path, allow_pickle=False) as f:
        if split == "train":
            return f["x_train"], f["y_train"]
        return f["x_test"], f["y_test"]


def _load_cifar_batches(path: Path, split: str):
    def one(p: Path):
        with open(p, "rb") as fh:
            d = pickle.load(fh, encoding="bytes")
        x = d[b"data"].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
        return x, np.asarray(d[b"labels"])

    if split == "train":
        parts = [one(path / f"data_batch_{i}") for i in range(1, 6)]
        return (np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]))
    return one(path / "test_batch")


def synthetic_classification(
    shape: tuple[int, ...],
    num_classes: int,
    n: int,
    seed: int,
    split: str = "train",
    noise: float = 0.35,
):
    """Per-class Gaussian prototypes + noise: learnable, deterministic.

    Prototypes depend only on ``seed`` (shared across splits); the noise and
    label draws are keyed by (seed, split) so train/test are disjoint samples
    of the same underlying task.
    """
    proto_rng = np.random.default_rng(seed)
    protos = proto_rng.normal(0.5, 0.25, size=(num_classes, *shape)).clip(0, 1)
    rng = np.random.default_rng((seed, 0 if split == "train" else 1))
    y = rng.integers(0, num_classes, size=n).astype(np.int32)
    x = protos[y] + rng.normal(0.0, noise, size=(n, *shape))
    return x.clip(0.0, 1.0).astype(np.float32), y


def synthetic_text_classification(
    n: int,
    seq_len: int = 128,
    vocab_size: int = 1024,
    num_classes: int = 2,
    seed: int = 0,
    split: str = "train",
):
    """Topic-model synthetic text: each class draws tokens from its own
    Zipf-reweighted vocabulary distribution (BERT-tiny learns it quickly —
    the GLUE-stand-in for the zero-egress environment).  Token id 0 is
    reserved for padding; sequences are full-length."""
    proto_rng = np.random.default_rng(seed)
    base = 1.0 / np.arange(1, vocab_size)  # ids 1..V-1, Zipf-ish
    class_logits = np.stack([
        np.log(base) + 0.75 * proto_rng.normal(size=vocab_size - 1)
        for _ in range(num_classes)
    ])
    probs = np.exp(class_logits)
    probs /= probs.sum(axis=1, keepdims=True)
    rng = np.random.default_rng((seed, 0 if split == "train" else 1))
    y = rng.integers(0, num_classes, size=n).astype(np.int32)
    x = np.stack([
        rng.choice(vocab_size - 1, size=seq_len, p=probs[c]) + 1 for c in y
    ]).astype(np.int32)
    x[:, 0] = 1  # fixed [CLS]-like token at position 0
    return x, y


def synthetic_lm(
    n: int,
    seq_len: int = 128,
    vocab_size: int = 128,
    seed: int = 0,
    split: str = "train",
    concentration: float = 0.1,
):
    """First-order Markov-chain token streams for language modeling.

    Each row of the transition matrix is a Dirichlet(concentration) draw —
    low concentration makes transitions peaked, so an LM that learns the
    chain reaches high next-token accuracy while an untrained one sits at
    ~1/vocab: the gap is what tests assert.  Deterministic in (seed, split);
    the chain (like the classification prototypes above) is shared across
    splits while the trajectories are disjoint.

    Returns ``(x, y)`` with x = tokens[:, :-1] and y = tokens[:, 1:] —
    next-token targets are materialized by the DATASET, so models never
    shift internally and every engine's (input, label) contract is identical
    to classification (just with (B, L)-shaped labels).
    """
    proto_rng = np.random.default_rng(seed)
    trans = proto_rng.dirichlet(
        np.full(vocab_size, concentration), size=vocab_size)
    cdf = np.cumsum(trans, axis=1)
    rng = np.random.default_rng((seed, 0 if split == "train" else 1))
    seq = np.empty((n, seq_len + 1), np.int64)
    seq[:, 0] = rng.integers(0, vocab_size, size=n)
    for t in range(1, seq_len + 1):
        u = rng.random(n)
        # inverse-CDF sampling, vectorized over rows; clip guards the float
        # edge where a row's cumsum tops out below 1.0 and a draw lands past
        # it — unclipped that yields the out-of-range id == vocab_size
        seq[:, t] = np.minimum(
            (cdf[seq[:, t - 1]] < u[:, None]).sum(axis=1), vocab_size - 1)
    seq = seq.astype(np.int32)
    return seq[:, :-1], seq[:, 1:]


def load_lm_dataset(
    name: str = "lm_synth",
    split: str = "train",
    seq_len: int = 128,
    vocab_size: int | None = None,
    n_train: int = 4096,
    n_test: int = 1024,
    holdout: float = 0.1,
) -> Dataset:
    """Language-modeling workload: (B, L) token inputs with (B, L)
    next-token targets (``num_classes`` = vocab size, so the engines' loss —
    which broadcasts over label dims, engines/base.py — trains it unchanged).

    Real corpora: a local ``<name>.bin`` (or ``lm_tokens.bin``) in the data
    search path — the standard flat binary of uint16 token ids (nanoGPT-
    style) — is memory-mapped and windowed into non-overlapping seq_len
    chunks with the final ``holdout`` fraction as the test split; the
    window arrays are materialized (one contiguous read), so the engines
    see plain numpy either way.  Pass ``vocab_size`` for large corpora —
    when omitted it is derived with a full-file max scan (per split).
    Otherwise the deterministic Markov-chain synthetic corpus (zero-egress
    environment)."""
    path = _find(f"{name}.bin", "lm_tokens.bin")
    if path is not None:
        tokens = np.memmap(path, dtype=np.uint16, mode="r")
        cut = int(len(tokens) * (1.0 - holdout))
        lo, hi = (0, cut) if split == "train" else (cut, len(tokens))
        n = (hi - lo - 1) // seq_len
        if n < 1:
            # clamping to one window would read past the region (train
            # would silently leak held-out tokens; test past EOF)
            raise ValueError(
                f"{split} region of {path.name} has {hi - lo} tokens — "
                f"fewer than seq_len + 1 = {seq_len + 1}; shrink seq_len "
                f"or holdout")
        base = lo + np.arange(n * seq_len)
        x = np.asarray(tokens[base]).reshape(n, seq_len).astype(np.int32)
        y = np.asarray(tokens[base + 1]).reshape(n, seq_len).astype(np.int32)
        vocab = (vocab_size if vocab_size is not None
                 else int(tokens.max()) + 1)
        if vocab_size is not None:
            # an undersized explicit vocab would otherwise be silently
            # clamped downstream (nn.Embed gather + CE label gather) and
            # train on corrupted ids (ADVICE r3)
            top = int(max(x.max(), y.max()))
            if top >= vocab_size:
                raise ValueError(
                    f"vocab_size {vocab_size} does not cover {path.name}: "
                    f"{split} split contains token id {top}; pass "
                    f"vocab_size >= {top + 1} or omit it to derive from "
                    f"the corpus")
        return Dataset(x=x, y=y, num_classes=vocab, name=name,
                       synthetic=False)
    vocab = vocab_size if vocab_size is not None else 128
    n = n_train if split == "train" else n_test
    x, y = synthetic_lm(n, seq_len=seq_len, vocab_size=vocab,
                        seed=sum(ord(c) for c in name) % (2**31), split=split)
    return Dataset(x=x, y=y, num_classes=vocab, name=name,
                   synthetic=True)


def load_text_dataset(
    name: str = "glue_synth",
    split: str = "train",
    seq_len: int = 128,
    vocab_size: int = 1024,
    n_train: int = 4096,
    n_test: int = 1024,
) -> Dataset:
    """Text workload loader (BASELINE.md BERT-tiny stretch config).
    Currently synthetic-only: real GLUE needs downloads this env can't do."""
    n = n_train if split == "train" else n_test
    x, y = synthetic_text_classification(
        n, seq_len=seq_len, vocab_size=vocab_size,
        seed=sum(ord(c) for c in name) % (2**31), split=split)
    return Dataset(x=x, y=y, num_classes=2, name=name, synthetic=True)


def load_dataset(
    name: str,
    split: str = "train",
    reshape: bool = True,
    n_synthetic_train: int = 8192,
    n_synthetic_test: int = 2048,
) -> Dataset:
    """Load a named dataset; silently fall back to synthetic when no local copy.

    ``reshape`` mirrors the reference's flag (reference initializer.py:28-35):
    True adds a trailing channel dim to 2-D images ((28,28) → (28,28,1)).
    """
    if name in ("glue_synth", "text", "glue"):
        return load_text_dataset(name, split=split)
    if name in ("lm_synth", "lm"):
        return load_lm_dataset(name, split=split)
    if name in ("synthetic", "synth"):
        name, shape, ncls, path = "synthetic", (28, 28), 10, None
    elif name in _SHAPES:
        shape, ncls = _SHAPES[name]
        if name == "mnist":
            path = _find("mnist.npz")
        elif name == "fashion_mnist":
            path = _find("fashion_mnist.npz", "fashion-mnist.npz")
        else:
            path = _find("cifar10.npz") or _find("cifar-10-batches-py")
    else:
        raise KeyError(f"unknown dataset '{name}'; known: {sorted(_SHAPES)} + synthetic")

    if path is not None:
        if path.is_dir():
            x, y = _load_cifar_batches(path, split)
        else:
            x, y = _load_npz(path, split)
        x = x.astype(np.float32) / 255.0
        synthetic = False
    else:
        n = n_synthetic_train if split == "train" else n_synthetic_test
        # stable per-dataset seed (hash() is salted per process — don't use it)
        seed = sum(ord(c) for c in name) * 1000003 % (2**31)
        x, y = synthetic_classification(shape, ncls, n, seed, split=split)
        synthetic = True

    if reshape and x.ndim == 3:  # (N,28,28) → (N,28,28,1), reference initializer.py:28-29
        x = x[..., None]
    return Dataset(
        x=x, y=y.astype(np.int32), num_classes=ncls,
        name=name, synthetic=synthetic,
    )
