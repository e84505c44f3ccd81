"""Native (C++) runtime components: build, wire framing, batch pipeline.

The wire tests check byte-compatibility BOTH directions against the pure
Python framing (which matches the reference's network.py:4-28 format); the
pipeline tests check batch-for-batch identity with the Python input path.
"""

import socket
import threading

import numpy as np
import pytest

from distributed_tensorflow_tpu import native
from distributed_tensorflow_tpu.data.pipeline import iter_batches
from distributed_tensorflow_tpu.utils import wire

pytestmark = pytest.mark.skipif(
    not native.is_available(), reason="native toolchain unavailable")


# ------------------------------------------------------------------ build
def test_build_is_cached():
    p1 = native.build()
    p2 = native.build()
    assert p1 == p2 and p1.exists()


def test_build_is_keyed_on_source_content(tmp_path, monkeypatch):
    """A source byte changes while the mtimes say nothing did (a copied
    tree, a checkout): the library is rebuilt under a new name and the old
    binary is gone — a stale one can never be loaded."""
    import os
    import shutil

    src = tmp_path / "src"
    shutil.copytree(native._SRC_DIR, src)
    monkeypatch.setattr(native, "_SRC_DIR", src)
    monkeypatch.setattr(native, "_BUILD_DIR", tmp_path / "_build")
    first = native.build()
    assert first is not None and first.exists()
    assert native.build() == first                    # same content: reused

    wire = src / "wire.cc"
    stamp = wire.stat()
    wire.write_bytes(wire.read_bytes() + b"// one more byte\n")
    os.utime(wire, ns=(stamp.st_atime_ns, stamp.st_mtime_ns))
    assert wire.stat().st_mtime_ns == stamp.st_mtime_ns
    assert first.stat().st_mtime_ns >= stamp.st_mtime_ns  # "up to date"
    second = native.build()
    assert second is not None and second.exists() and second != first
    assert not first.exists()


# ------------------------------------------------------------------- wire
def _blocking_socketpair():
    a, b = socket.socketpair()
    a.settimeout(None)
    b.settimeout(None)
    return a, b


def test_native_frame_roundtrip():
    a, b = _blocking_socketpair()
    try:
        for payload in (b"", b"x", b"hello world" * 100, bytes(range(256)) * 64):
            wire.send_bytes(a, payload)
            assert wire.recv_bytes(b) == payload
    finally:
        a.close()
        b.close()


def test_native_interop_with_python_framing():
    """Native writer ↔ Python reader and vice versa (same bytes on the wire
    as the reference's 4-byte big-endian framing)."""
    lib = native.load()
    a, b = _blocking_socketpair()
    try:
        # native write → python read
        assert lib.dtw_send_frame(a.fileno(), b"ping", 4) == 0
        header = wire.recvall(b, 4)
        assert header == (4).to_bytes(4, "big")
        assert wire.recvall(b, 4) == b"ping"
        # python write → native read
        import ctypes

        b.sendall((3).to_bytes(4, "big") + b"abc")
        buf = ctypes.create_string_buffer(16)
        assert lib.dtw_recv_frame(a.fileno(), buf, 16) == 3
        assert buf.raw[:3] == b"abc"
    finally:
        a.close()
        b.close()


def test_native_recv_on_close_returns_none():
    a, b = _blocking_socketpair()
    a.close()
    try:
        assert wire.recv_bytes(b) is None
    finally:
        b.close()


def test_native_listen_connect_accept():
    lib = native.load()
    lfd = lib.dtw_listen(0)
    assert lfd >= 0
    port = lib.dtw_port(lfd)
    assert port > 0
    results = {}

    def server():
        cfd = lib.dtw_accept(lfd)
        import ctypes

        buf = ctypes.create_string_buffer(64)
        n = lib.dtw_recv_frame(cfd, buf, 64)
        results["msg"] = buf.raw[:n]
        lib.dtw_send_frame(cfd, b"ack", 3)
        lib.dtw_close(cfd)

    t = threading.Thread(target=server)
    t.start()
    fd = lib.dtw_connect(b"127.0.0.1", port)
    assert fd >= 0
    assert lib.dtw_send_frame(fd, b"syn", 3) == 0
    import ctypes

    buf = ctypes.create_string_buffer(8)
    assert lib.dtw_recv_frame(fd, buf, 8) == 3
    assert buf.raw[:3] == b"ack"
    t.join(timeout=5)
    lib.dtw_close(fd)
    lib.dtw_close(lfd)
    assert results["msg"] == b"syn"


# --------------------------------------------------------- race detection
def test_pipeline_under_thread_sanitizer():
    """TSAN over the producer/worker-pool/consumer concurrency (the race
    detection the reference lacks outright, SURVEY.md §5)."""
    import subprocess

    binary = native.build_race_test()
    if binary is None:
        pytest.skip("TSAN unavailable")
    proc = subprocess.run([str(binary)], capture_output=True, text=True,
                          timeout=120)
    assert "WARNING: ThreadSanitizer" not in proc.stderr, proc.stderr[:4000]
    assert proc.returncode == 0, (proc.returncode, proc.stderr[:2000])
    assert "tsan-driver-ok" in proc.stdout


# --------------------------------------------------------------- pipeline
def _ref_batches(x, y, bs, **kw):
    return list(iter_batches(x, y, bs, **kw))


def _native_batches(x, y, bs, **kw):
    from distributed_tensorflow_tpu.native.batcher import NativeBatcher

    nb = NativeBatcher(x, y, bs)
    try:
        return list(nb.epoch(**kw))
    finally:
        nb.close()


@pytest.mark.parametrize("n,bs", [(64, 16), (100, 32), (10, 32), (96, 32)])
def test_pipeline_matches_python(n, bs):
    rng = np.random.default_rng(7)
    x = rng.random((n, 5, 3), np.float32)
    y = rng.integers(0, 10, n).astype(np.int32)
    for shuffle in (True, False):
        for epoch in (0, 1, 3):
            ref = _ref_batches(x, y, bs, shuffle=shuffle, seed=11, epoch=epoch)
            got = _native_batches(x, y, bs, shuffle=shuffle, seed=11, epoch=epoch)
            assert len(ref) == len(got)
            for (rx, ry, rm), (gx, gy, gm) in zip(ref, got):
                np.testing.assert_array_equal(rx, gx)
                np.testing.assert_array_equal(ry, gy)
                np.testing.assert_array_equal(rm, gm)


def test_pipeline_drop_remainder():
    x = np.arange(100, dtype=np.float32).reshape(100, 1)
    y = np.arange(100, dtype=np.int32)
    got = _native_batches(x, y, 32, shuffle=False, drop_remainder=True)
    assert len(got) == 3
    assert all(m.all() for _, _, m in got)


def test_pipeline_epoch_restart_and_reuse():
    """Abandoning an epoch mid-way then restarting must not deadlock."""
    from distributed_tensorflow_tpu.native.batcher import NativeBatcher

    x = np.arange(256, dtype=np.float32).reshape(64, 4)
    y = np.arange(64, dtype=np.int32)
    nb = NativeBatcher(x, y, 8, prefetch_depth=2)
    it = nb.epoch(shuffle=True, seed=1, epoch=0)
    next(it)  # consume one batch, abandon the rest while producer is staged
    with pytest.raises(RuntimeError):
        nb.epoch()  # handle is busy while the first iterator is live
    it.close()  # releases the handle
    full = list(nb.epoch(shuffle=True, seed=1, epoch=1))
    ref = _ref_batches(x, y, 8, shuffle=True, seed=1, epoch=1)
    assert len(full) == len(ref)
    for (rx, ry, rm), (gx, gy, gm) in zip(ref, full):
        np.testing.assert_array_equal(rx, gx)
        np.testing.assert_array_equal(ry, gy)
    nb.close()


def test_dataset_concurrent_iterators_independent():
    """Two live Dataset.batches() iterators must not corrupt each other
    (each gets its own native pipeline when the cached one is busy)."""
    from distributed_tensorflow_tpu.data.loaders import Dataset

    x = np.arange(4 * 64, dtype=np.float32).reshape(64, 4)
    y = np.arange(64, dtype=np.int32)
    ds = Dataset(x=x, y=y, num_classes=10)
    it1 = ds.batches(8, shuffle=True, seed=5, epoch=0, native=True)
    it2 = ds.batches(8, shuffle=True, seed=5, epoch=1, native=True)
    got1, got2 = [], []
    for a, b in zip(it1, it2):  # interleave consumption
        got1.append(a)
        got2.append(b)
    ref1 = _ref_batches(x, y, 8, shuffle=True, seed=5, epoch=0)
    ref2 = _ref_batches(x, y, 8, shuffle=True, seed=5, epoch=1)
    for ref, got in ((ref1, got1), (ref2, got2)):
        assert len(ref) == len(got)
        for (rx, ry, rm), (gx, gy, gm) in zip(ref, got):
            np.testing.assert_array_equal(rx, gx)
            np.testing.assert_array_equal(ry, gy)


def test_dataset_batches_native_parity():
    """Dataset.batches native vs forced-Python paths agree."""
    from distributed_tensorflow_tpu.data.loaders import load_dataset

    ds = load_dataset("synthetic", split="test")
    a = list(ds.batches(33, shuffle=True, seed=3, epoch=2, native=True))
    b = list(ds.batches(33, shuffle=True, seed=3, epoch=2, native=False))
    assert len(a) == len(b)
    for (ax, ay, am), (bx, by, bm) in zip(a, b):
        np.testing.assert_array_equal(ax, bx)
        np.testing.assert_array_equal(ay, by)
        np.testing.assert_array_equal(am, bm)
