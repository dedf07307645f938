"""ctypes loader for the native CPU core (cdc_core.cpp).

Compiles on first use with g++ into ``_build/`` next to the source (no
pybind11 in the image, so the binding is plain ctypes over an extern-C
ABI). A built object is reused only if it was built from THIS source
with THESE flags on THIS CPU: its file name carries a hash of all three
(``-march=native`` code loaded on another CPU is a SIGILL, and a tree
copied between machines carries its build directory along). Every entry
point degrades to pure Python/NumPy when the toolchain is unavailable —
the framework never *requires* the native library; :func:`engine` says
which one a process got.
"""

from __future__ import annotations

import ctypes
import os
import platform
import subprocess
import threading
from pathlib import Path

import numpy as np

_DIR = Path(__file__).resolve().parent
_SRC = _DIR / "cdc_core.cpp"
_BUILD_DIR = _DIR / "_build"
_LIB_FLAGS = ("-O3", "-march=native", "-shared", "-fPIC")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _cpu_identity() -> str:
    """What ``-march=native`` resolved against: the first CPU's model and
    feature flags."""
    keep = []
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if not line.strip():
                    break                       # first processor only
                if line.split(":")[0].strip() in (
                        "vendor_id", "model name", "flags", "Features"):
                    keep.append(line.strip())
    except OSError:
        pass
    return platform.machine() + "\n" + "\n".join(keep)


def _artifact(src: Path, flags: tuple[str, ...], suffix: str) -> Path:
    """Build-cache path keyed on source bytes, flags and CPU."""
    from dfs_tpu.utils.hashing import sha256_new

    h = sha256_new()
    h.update(src.read_bytes())
    h.update("\0".join(flags).encode())
    h.update(_cpu_identity().encode())
    return _BUILD_DIR / f"{src.stem}-{h.hexdigest()[:16]}{suffix}"


def _compile(src: Path, flags: tuple[str, ...], dst: Path) -> bool:
    # compile to a temp path and rename over the target: a half-written
    # object must never be visible under the keyed name, and rename swaps
    # a fresh inode in atomically for concurrent builders/loaders
    tmp = dst.with_name(f"{dst.name}.tmp{os.getpid()}")
    try:
        _BUILD_DIR.mkdir(exist_ok=True)
        subprocess.run(["g++", *flags, str(src), "-o", str(tmp)],
                       check=True, capture_output=True, timeout=120)
        os.replace(tmp, dst)
        return True
    except (OSError, subprocess.SubprocessError):
        tmp.unlink(missing_ok=True)
        return False


def _built(src: Path, flags: tuple[str, ...], suffix: str) -> Path | None:
    """The keyed artifact, building it if absent; None without a
    toolchain."""
    dst = _artifact(src, flags, suffix)
    if dst.is_file() or _compile(src, flags, dst):
        return dst
    return None


def get_lib() -> ctypes.CDLL | None:
    """Load (building if needed) the native library, or None."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        so = _built(_SRC, _LIB_FLAGS, ".so")
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(str(so))
            _bind(lib)
        except (OSError, AttributeError):
            return None
        _lib = lib
        return _lib


def engine() -> str:
    """``"native"`` when the C++ core is loaded, ``"numpy"`` when every
    entry point is answering from its NumPy fallback."""
    return "native" if get_lib() is not None else "numpy"


def _bind(lib: ctypes.CDLL) -> None:
    """Declare the extern-C signatures."""
    lib.dfs_sha256_batch.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p]
    lib.dfs_sha256_batch.restype = None
    lib.dfs_gear_cuts.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
        ctypes.c_uint32, ctypes.c_uint64, ctypes.c_uint64,
        ctypes.c_void_p, ctypes.c_uint64]
    lib.dfs_gear_cuts.restype = ctypes.c_int64
    lib.dfs_anchored_spans.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint32,
        ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint64,
        ctypes.c_uint64, ctypes.c_uint64,
        ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint32,
        ctypes.c_uint64, ctypes.c_uint64, ctypes.c_void_p,
        ctypes.c_uint64]
    lib.dfs_anchored_spans.restype = ctypes.c_int64
    lib.dfs_anchored_spans_region.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
        ctypes.c_uint64, ctypes.c_int, ctypes.c_uint32,
        ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint64,
        ctypes.c_uint64, ctypes.c_uint64,
        ctypes.c_uint64, ctypes.c_uint32, ctypes.c_uint32,
        ctypes.c_uint64, ctypes.c_uint64, ctypes.c_void_p,
        ctypes.c_uint64, ctypes.c_void_p, ctypes.c_void_p]
    lib.dfs_anchored_spans_region.restype = ctypes.c_int64


def native_sha256_many(chunks: list[bytes]) -> list[str] | None:
    """Batch sha256 via the native lib; None if unavailable.

    NOT a Python-side accelerator: hashlib's OpenSSL SHA-NI path measured
    5x faster. This binding exists to validate the C ABI that a
    non-Python host (the reference's Java-calls-sidecar shape) would link
    — production Python paths use hashlib."""
    lib = get_lib()
    if lib is None or not chunks:
        return None if lib is None else []
    data = b"".join(chunks)
    offsets = np.zeros(len(chunks) + 1, dtype=np.uint64)
    np.cumsum([len(c) for c in chunks], out=offsets[1:])
    out = np.empty(len(chunks) * 32, dtype=np.uint8)
    buf = np.frombuffer(data, dtype=np.uint8) if data else np.empty(0, np.uint8)
    lib.dfs_sha256_batch(
        buf.ctypes.data if buf.size else None,
        offsets.ctypes.data, len(chunks), out.ctypes.data)
    raw = out.tobytes()
    return [raw[32 * i:32 * (i + 1)].hex() for i in range(len(chunks))]


def native_anchored_spans(data: bytes | np.ndarray,
                          params) -> np.ndarray | None:
    """Anchored two-level CDC spans in C++ (bit-identical to
    ops.cdc_anchored.chunk_spans_anchored_np); returns [n, 2] int64
    (offset, length) or None if the native lib is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    arr = np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray, memoryview)) else data
    arr = np.ascontiguousarray(arr)    # .ctypes.data needs C-contiguity
    n = int(arr.shape[0])
    if n == 0:
        return np.zeros((0, 2), dtype=np.int64)
    cp = params.chunk
    # worst case: one cut per min_blocks plus one forced tail per segment
    cap = n // (cp.min_blocks * 64) + n // params.strong_min + 3
    spans = np.empty((cap, 2), dtype=np.uint64)
    from dfs_tpu.ops.cdc_anchored import TILE_BYTES

    wrote = lib.dfs_anchored_spans(
        arr.ctypes.data, n, params.seed, params.seg_mask,
        params.strong_mask, params.strong_min,
        params.seg_min, params.seg_max, TILE_BYTES,
        cp.seed, cp.mask, cp.min_blocks, cp.max_blocks,
        spans.ctypes.data, cap)
    if wrote < 0:
        return None
    return spans[:wrote].astype(np.int64)


def native_anchored_spans_region(
        data: bytes | np.ndarray, lookback: np.ndarray, start0: int,
        final: bool, params, cut_kinds: np.ndarray | None = None
        ) -> tuple[np.ndarray, int] | None:
    """Window edition of :func:`native_anchored_spans` (the C mirror of
    ops.cdc_anchored.region_chunks semantics): returns ([n, 2] int64
    region-local (offset, length), consumed) or None if the native lib is
    unavailable. The stream offset of data[0] must be TILE_BYTES-aligned.
    ``cut_kinds`` ([4] uint64), when given, receives how many of the
    emitted segments ended by each ``ops.cdc_anchored.CUT_*`` kind."""
    lib = get_lib()
    if lib is None:
        return None
    arr = np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray, memoryview)) else data
    arr = np.ascontiguousarray(arr)
    n = int(arr.shape[0])
    if n == 0:
        if cut_kinds is not None:
            cut_kinds[:] = 0
        return np.zeros((0, 2), dtype=np.int64), start0
    cp = params.chunk
    cap = n // (cp.min_blocks * 64) + n // params.strong_min + 3
    spans = np.empty((cap, 2), dtype=np.uint64)
    lb = np.ascontiguousarray(lookback, dtype=np.uint8)
    consumed = ctypes.c_uint64(0)
    from dfs_tpu.ops.cdc_anchored import TILE_BYTES

    wrote = lib.dfs_anchored_spans_region(
        arr.ctypes.data, n, lb.ctypes.data, start0, int(final),
        params.seed, params.seg_mask, params.strong_mask,
        params.strong_min, params.seg_min, params.seg_max,
        TILE_BYTES, cp.seed, cp.mask, cp.min_blocks, cp.max_blocks,
        spans.ctypes.data, cap, ctypes.byref(consumed),
        None if cut_kinds is None else cut_kinds.ctypes.data)
    if wrote < 0:
        return None
    return spans[:wrote].astype(np.int64), int(consumed.value)


def native_gear_cuts(data: bytes | np.ndarray, table: np.ndarray, mask: int,
                     min_size: int, max_size: int) -> np.ndarray | None:
    """Sequential CDC cut selection in C++; None if unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    arr = np.frombuffer(data, dtype=np.uint8) if isinstance(
        data, (bytes, bytearray, memoryview)) else data
    n = arr.shape[0]
    if n == 0:
        return np.zeros(0, dtype=np.uint64)
    cap = n // min_size + 2
    cuts = np.empty(cap, dtype=np.uint64)
    table32 = np.ascontiguousarray(table, dtype=np.uint32)
    wrote = lib.dfs_gear_cuts(arr.ctypes.data, n, table32.ctypes.data,
                              mask, min_size, max_size,
                              cuts.ctypes.data, cap)
    if wrote < 0:
        return None
    return cuts[:wrote].astype(np.int64)


_SIDECAR_SRC = _DIR / "sidecar_client.cpp"


def build_sidecar_client() -> Path | None:
    """Build (once, cached like the library) the dependency-free C++
    sidecar conformance client — POSIX sockets + hand-rolled HTTP/2, no
    gRPC library (see sidecar_client.cpp and docs/sidecar_wire.md).
    Returns the binary path, or None when the toolchain is
    unavailable."""
    return _built(_SIDECAR_SRC, ("-O2",), "")
