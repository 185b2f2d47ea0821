"""ctypes binding to the native coordination engine (``native/src/``).

The reference loads its compiled engine with ``ctypes.CDLL(RTLD_GLOBAL)``
(reference: horovod/common/__init__.py:51-68).  Same approach here, with
one addition: if ``libhvdtpu.so`` is missing, it is compiled on first use
with ``g++`` from the in-tree sources — there is no wheel-building step in
a TPU pod image, and the engine has zero dependencies beyond libstdc++.

The native layer carries control-plane METADATA only (names, dtypes,
shapes, fused batch assignments); tensor payloads never leave device HBM —
the Python side dispatches one compiled XLA collective per returned batch.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import struct
import subprocess
import threading
from dataclasses import dataclass, field

from horovod_tpu.native import _build_flags

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(os.path.dirname(_HERE))
_SO_PATH = os.path.join(_HERE, "libhvdtpu.so")
# Digest of the sources the library beside it was built from.
_DIGEST_PATH = _SO_PATH + ".sha256"


def _find_src_dir() -> str:
    """Locate the native sources: repo layout first, then the copy the
    package build vendors into horovod_tpu/native/src (setup.py)."""
    for cand in (os.path.join(_REPO, "native", "src"),
                 os.path.join(_HERE, "src")):
        if os.path.exists(os.path.join(cand, "controller.cc")):
            return cand
    return os.path.join(_REPO, "native", "src")


_SRC_DIR = _find_src_dir()

# OpKind / DType wire values — must match native/src/types.h.
KIND_ALLREDUCE, KIND_ALLGATHER, KIND_BROADCAST, KIND_SPARSE = 0, 1, 2, 3
KIND_ALLTOALL, KIND_REDUCESCATTER, KIND_JOIN = 4, 5, 6

# Dispatch-program codes (types.h OpCode): what a JOINED rank must launch
# to participate in a batch it never submitted.
OP_PLAIN_SUM, OP_PLAIN_AVERAGE, OP_OTHER = 0, 1, 2

_DTYPE_CODES = {
    "uint8": 0, "int8": 1, "uint16": 2, "int16": 3, "int32": 4,
    "int64": 5, "float16": 6, "bfloat16": 7, "float32": 8, "float64": 9,
    "bool": 10, "uint32": 11, "uint64": 12,
}
DTYPE_NAMES = {v: k for k, v in _DTYPE_CODES.items()}

_build_lock = threading.Lock()
_lib = None


class NativeBuildError(RuntimeError):
    pass


def _sources() -> list[str]:
    srcs = [os.path.join(_SRC_DIR, f) for f in _build_flags.SOURCES]
    headers = [os.path.join(_SRC_DIR, f) for f in _build_flags.HEADERS]
    return srcs + [h for h in headers if os.path.exists(h)]


def _source_digest() -> str:
    """sha256 over the compile line and every source and header."""
    h = hashlib.sha256(" ".join(
        _build_flags.compile_cmd("libhvdtpu.so", "src")).encode())
    for path in _sources():
        h.update(os.path.basename(path).encode())
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _so_stale() -> bool:
    if not os.path.exists(_SO_PATH):
        return True
    if _SRC_DIR != os.path.join(_REPO, "native", "src"):
        # Installed layout: a wheel's prebuilt .so is trusted as-is, never
        # "refreshed" — a rebuild there would discard the prebuild (or fail
        # on read-only site-packages / missing g++).  Staleness only means
        # anything in the repo layout, where sources are actually edited.
        return False
    # By content, not mtime: a copied or checked-out tree keeps no mtimes,
    # and must never load a library built from other sources.
    try:
        with open(_DIGEST_PATH) as f:
            built_from = f.read().strip()
    except FileNotFoundError:
        return True
    return built_from != _source_digest()


def _build_so() -> None:
    srcs = [s for s in _sources() if s.endswith(".cc")]
    if not all(os.path.exists(s) for s in srcs):
        raise NativeBuildError(
            f"native sources not found under {_SRC_DIR}; "
            "cannot build libhvdtpu.so"
        )
    # Compile to a per-pid temp path and rename into place: rename is atomic
    # on one filesystem, so concurrent first-use builds from multiple local
    # ranks can never dlopen a partially-written .so.
    tmp = f"{_SO_PATH}.tmp.{os.getpid()}"
    cmd = _build_flags.compile_cmd(tmp, _SRC_DIR)
    digest = _source_digest()      # of what g++ is about to read
    # hvdlint: disable=HVD008 -- one-shot cold-start g++ build, intentionally serialized under _build_lock before any engine thread exists
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise NativeBuildError(
            "building libhvdtpu.so failed:\n" + proc.stderr[-2000:]
        )
    os.replace(tmp, _SO_PATH)
    with open(tmp, "w") as f:
        f.write(digest + "\n")
    os.replace(tmp, _DIGEST_PATH)


def load_library() -> ctypes.CDLL:
    """Load (building if needed) the native engine library."""
    global _lib
    if _lib is not None:
        return _lib
    with _build_lock:
        if _lib is not None:
            return _lib
        if _so_stale():
            _build_so()
        lib = ctypes.CDLL(_SO_PATH, mode=ctypes.RTLD_GLOBAL)
        lib.hvdtpu_controller_create.restype = ctypes.c_void_p
        lib.hvdtpu_controller_create.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_longlong,
            ctypes.c_double, ctypes.c_char_p, ctypes.c_int,
        ]
        lib.hvdtpu_controller_destroy.argtypes = [ctypes.c_void_p]
        lib.hvdtpu_controller_submit.restype = ctypes.c_int
        lib.hvdtpu_controller_submit.argtypes = [
            ctypes.c_void_p, ctypes.c_ubyte, ctypes.c_ubyte, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_longlong), ctypes.c_int, ctypes.c_int,
            ctypes.c_longlong, ctypes.c_ubyte,
        ]
        lib.hvdtpu_controller_request_shutdown.argtypes = [ctypes.c_void_p]
        lib.hvdtpu_controller_tick.restype = ctypes.c_int
        lib.hvdtpu_controller_tick.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_ubyte)),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.hvdtpu_controller_stall_report.restype = ctypes.c_int
        lib.hvdtpu_controller_stall_report.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_ubyte)),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.hvdtpu_controller_enable_tick_trace.argtypes = [
            ctypes.c_void_p, ctypes.c_int,
        ]
        lib.hvdtpu_controller_set_tuned.argtypes = [
            ctypes.c_void_p, ctypes.c_longlong, ctypes.c_double,
        ]
        lib.hvdtpu_controller_drain_ticks.restype = ctypes.c_int
        lib.hvdtpu_controller_drain_ticks.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.POINTER(ctypes.c_ubyte)),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.hvdtpu_free.argtypes = [ctypes.POINTER(ctypes.c_ubyte)]
        _lib = lib
        return _lib


def available() -> bool:
    try:
        load_library()
        return True
    except (NativeBuildError, OSError):
        return False


@dataclass
class Batch:
    kind: int
    error: str
    names: list[str] = field(default_factory=list)
    # Wire dtype code + dispatch-program code + per-name shapes: a JOINED
    # rank reconstructs the exact collective for tensors it never saw.
    dtype: int = 8  # kF32
    op_code: int = OP_OTHER
    shapes: list[tuple[int, ...]] = field(default_factory=list)


@dataclass
class BatchList:
    shutdown: bool
    batches: list[Batch] = field(default_factory=list)
    # Rank-0-tuned knobs piggybacked on the response (None = unset); every
    # rank observes a move in the same tick (control-plane autotune).
    tuned_threshold_bytes: int | None = None
    tuned_cycle_ms: float | None = None
    # >= 0 once every rank has joined (hvd.join): the last rank to join.
    last_joined: int = -1


def _parse_batch_list(data: bytes) -> BatchList:
    # Mirrors native/src/wire.h SerializeBatchList.
    off = 0

    def u8():
        nonlocal off
        v = data[off]
        off += 1
        return v

    def u32():
        nonlocal off
        (v,) = struct.unpack_from("<I", data, off)
        off += 4
        return v

    def i64():
        nonlocal off
        (v,) = struct.unpack_from("<q", data, off)
        off += 8
        return v

    def s():
        n = u32()
        nonlocal off
        v = data[off:off + n].decode()
        off += n
        return v

    def i32():
        nonlocal off
        (v,) = struct.unpack_from("<i", data, off)
        off += 4
        return v

    shutdown = u8() != 0
    thr = i64()
    cyc_us = i64()
    last_joined = i32()
    batches = []
    for _ in range(u32()):
        kind = u8()
        dtype = u8()
        op_code = u8()
        error = s()
        names = [s() for _ in range(u32())]
        shapes = [
            tuple(i64() for _ in range(u32())) for _ in range(len(names))
        ]
        batches.append(Batch(kind, error, names, dtype=dtype,
                             op_code=op_code, shapes=shapes))
    return BatchList(
        shutdown, batches,
        tuned_threshold_bytes=thr if thr >= 0 else None,
        tuned_cycle_ms=cyc_us / 1000.0 if cyc_us >= 0 else None,
        last_joined=last_joined,
    )


class NativeController:
    """Python handle on one rank's native coordination controller."""

    def __init__(self, rank: int, size: int, transport_spec: str,
                 fusion_threshold_bytes: int, stall_warning_s: float = 60.0):
        lib = load_library()
        err = ctypes.create_string_buffer(512)
        self._lib = lib
        self._ptr = lib.hvdtpu_controller_create(
            rank, size, transport_spec.encode(), fusion_threshold_bytes,
            stall_warning_s, err, len(err),
        )
        if not self._ptr:
            raise RuntimeError(
                f"native controller init failed: {err.value.decode()}"
            )
        self.rank, self.size = rank, size

    def submit(self, kind: int, dtype: str, name: str,
               shape: tuple[int, ...], root_rank: int = 0,
               group: int = -1, op_code: int = OP_OTHER) -> None:
        code = _DTYPE_CODES.get(str(dtype))
        if code is None:
            raise ValueError(f"dtype {dtype} not supported by the native wire")
        arr = (ctypes.c_longlong * len(shape))(*shape)
        rc = self._lib.hvdtpu_controller_submit(
            self._ptr, kind, code, name.encode(), arr, len(shape),
            root_rank, group, op_code,
        )
        if rc != 0:
            raise RuntimeError(f"native submit rejected request {name!r}")

    def submit_join(self) -> None:
        """Flip this rank's joined bit (hvd.join): its missing submissions
        stop blocking readiness from the next tick."""
        rc = self._lib.hvdtpu_controller_submit(
            self._ptr, KIND_JOIN, 4, b"__join__", None, 0, 0, -1, OP_OTHER,
        )
        if rc != 0:
            raise RuntimeError("native submit rejected the join request")

    def tick(self) -> BatchList:
        if not self._ptr:
            return BatchList(shutdown=True)
        out = ctypes.POINTER(ctypes.c_ubyte)()
        n = ctypes.c_uint64()
        rc = self._lib.hvdtpu_controller_tick(
            self._ptr, ctypes.byref(out), ctypes.byref(n))
        if rc < 0:
            raise RuntimeError("native controller tick failed (transport)")
        try:
            data = ctypes.string_at(out, n.value)
        finally:
            self._lib.hvdtpu_free(out)
        return _parse_batch_list(data)

    def request_shutdown(self) -> None:
        self._lib.hvdtpu_controller_request_shutdown(self._ptr)

    def stall_report(self) -> str:
        if not self._ptr:
            return ""
        out = ctypes.POINTER(ctypes.c_ubyte)()
        n = ctypes.c_uint64()
        self._lib.hvdtpu_controller_stall_report(
            self._ptr, ctypes.byref(out), ctypes.byref(n))
        try:
            return ctypes.string_at(out, n.value).decode()
        finally:
            self._lib.hvdtpu_free(out)

    def set_tuned(self, threshold_bytes: int = -1,
                  cycle_ms: float = -1.0) -> None:
        """Install rank-0-tuned knobs (control-plane autotune).  Fusion
        batching is decided only by rank 0's controller, so a threshold set
        here governs the whole gang from the next tick; both values ride
        every response so all ranks observe the move together.  Negative =
        leave that knob unchanged; no-op off rank 0."""
        if self._ptr:
            self._lib.hvdtpu_controller_set_tuned(
                self._ptr, int(threshold_bytes), float(cycle_ms)
            )

    def enable_tick_trace(self, on: bool = True) -> None:
        """Record per-rank request arrivals on rank 0 (timeline NEGOTIATE
        ticks, reference timeline.cc:98-132).  Off by default."""
        if self._ptr:
            self._lib.hvdtpu_controller_enable_tick_trace(self._ptr, int(on))

    def drain_ticks(self) -> list[tuple[str, int]]:
        """Drain buffered (tensor_name, rank) arrival events (rank 0)."""
        if not self._ptr:
            return []
        out = ctypes.POINTER(ctypes.c_ubyte)()
        n = ctypes.c_uint64()
        self._lib.hvdtpu_controller_drain_ticks(
            self._ptr, ctypes.byref(out), ctypes.byref(n))
        try:
            text = ctypes.string_at(out, n.value).decode()
        finally:
            self._lib.hvdtpu_free(out)
        events = []
        for line in text.splitlines():
            rank_str, _, name = line.partition(" ")
            if name:
                events.append((name, int(rank_str)))
        return events

    def close(self) -> None:
        if self._ptr:
            self._lib.hvdtpu_controller_destroy(self._ptr)
            self._ptr = None

    def __del__(self):  # pragma: no cover - best effort
        try:
            self.close()
        except Exception:
            pass
