"""Native (C++) runtime components, loaded via ctypes.

The reference's runtime-around-the-compute is Python sockets + tf.data
(/root/reference/centralized/network.py, initializer.py:24-55).  Here the
equivalent runtime pieces are C++:

  src/wire.cc      — framed socket transport (byte-compatible with the
                     reference's 4-byte big-endian framing)
  src/pipeline.cc  — multithreaded batch-gather input pipeline with a
                     bounded prefetch queue (overlaps host input prep with
                     device steps)

The library builds on demand with g++ (baked into the image; pybind11 is
not, so the ABI is plain C + ctypes).  Everything degrades gracefully: if
the toolchain or a build is unavailable, ``load()`` returns None and pure
Python paths take over.  Set ``DTF_TPU_NO_NATIVE=1`` to force Python paths.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from pathlib import Path

_SRC_DIR = Path(__file__).parent / "src"
_BUILD_DIR = Path(__file__).parent / "_build"
_lib: ctypes.CDLL | None = None
_load_failed = False


def _build_keyed(stem: str, suffix: str, sources: list[Path],
                 flags: list[str], timeout: int,
                 force: bool = False) -> Path | None:
    """g++ ``sources`` into ``_build/<stem>-<digest><suffix>``, the digest
    taken over the source bytes and the compile flags.  ``_build/`` is not
    tracked and a copy of the tree carries whatever sits in it with
    whatever mtimes the copy gave, so a binary is reused only when its
    name says it was built from exactly these sources — a stale one can
    never be loaded.  The build is atomic (compile to a temp name, rename
    over — parallel pytest safe) and drops the binaries of other source
    contents; None when the toolchain or the build fails."""
    h = hashlib.sha256(" ".join(flags).encode())
    for src in sources:
        h.update(src.name.encode() + b"\0" + src.read_bytes() + b"\0")
    out = _BUILD_DIR / f"{stem}-{h.hexdigest()[:16]}{suffix}"
    if out.exists() and not force:
        return out
    out.parent.mkdir(parents=True, exist_ok=True)
    with tempfile.NamedTemporaryFile(
            dir=out.parent, suffix=suffix, delete=False) as tmp:
        tmp_path = Path(tmp.name)
    cmd = [os.environ.get("CXX", "g++"), *flags, *map(str, sources),
           "-o", str(tmp_path)]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=timeout)
    except (OSError, subprocess.SubprocessError):
        tmp_path.unlink(missing_ok=True)
        return None
    tmp_path.replace(out)
    for old in out.parent.glob(f"{stem}-*{suffix}"):
        if old != out:
            old.unlink(missing_ok=True)
    return out


def build(force: bool = False) -> Path | None:
    """Compile src/*.cc into the package-local _build/ dir; None on failure."""
    sources = [s for s in sorted(_SRC_DIR.glob("*.cc"))
               if not s.stem.endswith("_test")]
    if not sources:
        return None
    return _build_keyed(
        "libdtf_native", ".so", sources,
        ["-O3", "-std=c++17", "-shared", "-fPIC", "-pthread", "-Wall"],
        timeout=120, force=force)


def build_race_test() -> Path | None:
    """Build the ThreadSanitizer driver over pipeline.cc (race detection for
    the native runtime — a capability the reference lacks outright,
    SURVEY.md §5).  Returns the binary path, or None when the toolchain or
    libtsan is unavailable.  Run it; any 'WARNING: ThreadSanitizer' output
    (exit code 66 under default TSAN options) is a detected race.
    """
    sources = [_SRC_DIR / "pipeline.cc", _SRC_DIR / "pipeline_tsan_test.cc"]
    if not all(s.exists() for s in sources):
        return None
    return _build_keyed(
        "pipeline_tsan_test", ".bin", sources,
        ["-O1", "-g", "-std=c++17", "-pthread", "-fsanitize=thread"],
        timeout=180)


def load() -> ctypes.CDLL | None:
    """Load (building if needed) the native library; None when unavailable."""
    global _lib, _load_failed
    if _lib is not None:
        return _lib
    if _load_failed or os.environ.get("DTF_TPU_NO_NATIVE"):
        return None
    path = build()
    if path is None:
        _load_failed = True
        return None
    try:
        lib = ctypes.CDLL(str(path))
    except OSError:
        _load_failed = True
        return None
    _declare(lib)
    _lib = lib
    return lib


def is_available() -> bool:
    return load() is not None


def _declare(lib: ctypes.CDLL) -> None:
    c = ctypes
    # wire.cc
    lib.dtw_send_frame.argtypes = [c.c_int, c.c_char_p, c.c_uint32]
    lib.dtw_send_frame.restype = c.c_int64
    lib.dtw_recv_frame.argtypes = [c.c_int, c.c_void_p, c.c_uint32]
    lib.dtw_recv_frame.restype = c.c_int64
    lib.dtw_recv_header.argtypes = [c.c_int]
    lib.dtw_recv_header.restype = c.c_int64
    lib.dtw_recv_body.argtypes = [c.c_int, c.c_void_p, c.c_uint32]
    lib.dtw_recv_body.restype = c.c_int64
    lib.dtw_connect.argtypes = [c.c_char_p, c.c_int]
    lib.dtw_connect.restype = c.c_int64
    lib.dtw_listen.argtypes = [c.c_int]
    lib.dtw_listen.restype = c.c_int64
    lib.dtw_port.argtypes = [c.c_int]
    lib.dtw_port.restype = c.c_int64
    lib.dtw_accept.argtypes = [c.c_int]
    lib.dtw_accept.restype = c.c_int64
    lib.dtw_close.argtypes = [c.c_int]
    lib.dtw_close.restype = c.c_int64
    # pipeline.cc
    lib.dtp_create.argtypes = [c.c_void_p, c.c_void_p, c.c_int64, c.c_int64,
                               c.c_int64, c.c_int, c.c_int]
    lib.dtp_create.restype = c.c_void_p
    lib.dtp_start_epoch.argtypes = [c.c_void_p, c.c_void_p, c.c_int64]
    lib.dtp_start_epoch.restype = c.c_int64
    lib.dtp_next.argtypes = [c.c_void_p, c.c_void_p, c.c_void_p]
    lib.dtp_next.restype = c.c_int64
    lib.dtp_destroy.argtypes = [c.c_void_p]
    lib.dtp_destroy.restype = None
