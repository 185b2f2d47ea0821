"""Environment-variable configuration, read once at engine start.

TPU-native re-design of the reference's env knob system
(reference: horovod/common/operations.h:52-59, parsed in
horovod/common/operations.cc:1614-1685).  The same knob names are kept so a
Horovod user can bring their launch scripts across unchanged; TPU-specific
knobs use the ``HOROVOD_TPU_`` prefix.
"""

from __future__ import annotations

import dataclasses
import os

# Knob names kept for parity with the reference.
HOROVOD_TIMELINE = "HOROVOD_TIMELINE"
HOROVOD_FUSION_THRESHOLD = "HOROVOD_FUSION_THRESHOLD"
HOROVOD_CYCLE_TIME = "HOROVOD_CYCLE_TIME"
HOROVOD_STALL_CHECK_DISABLE = "HOROVOD_STALL_CHECK_DISABLE"
HOROVOD_HIERARCHICAL_ALLREDUCE = "HOROVOD_HIERARCHICAL_ALLREDUCE"
HOROVOD_SPARSE_ALLREDUCE = "HOROVOD_SPARSE_ALLREDUCE"
# Autotune knob names shared with later Horovod releases, which grew an
# online tuner for the same two knobs (threshold/cycle); see autotune.py.
HOROVOD_AUTOTUNE = "HOROVOD_AUTOTUNE"
HOROVOD_AUTOTUNE_LOG = "HOROVOD_AUTOTUNE_LOG"
HOROVOD_AUTOTUNE_WARMUP_SAMPLES = "HOROVOD_AUTOTUNE_WARMUP_SAMPLES"
HOROVOD_AUTOTUNE_STEADY_STATE_SAMPLES = "HOROVOD_AUTOTUNE_STEADY_STATE_SAMPLES"
HOROVOD_TPU_SERIALIZE_DISPATCH = "HOROVOD_TPU_SERIALIZE_DISPATCH"

# Defaults mirror reference horovod/common/operations.cc:151 (64 MiB fusion
# buffer), :155 (5 ms cycle) and :273 (60 s stall warning).
DEFAULT_FUSION_THRESHOLD_BYTES = 64 * 1024 * 1024
DEFAULT_CYCLE_TIME_MS = 5.0
DEFAULT_STALL_WARNING_TIME_S = 60.0


def _get_int(name: str, default: int) -> int:
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return int(raw)
    except ValueError:
        return default


def _get_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return float(raw)
    except ValueError:
        return default


def _get_bool(name: str, default: bool = False) -> bool:
    raw = os.environ.get(name)
    if raw is None:
        return default
    return raw.strip().lower() not in ("", "0", "false", "no", "off")


def _get_tristate(name: str) -> str:
    """on/off/auto knob, accepting the same truthy/falsy spellings as
    ``_get_bool`` (so ``=1`` forces on, like every other knob); an
    unrecognized value warns and falls back to auto instead of silently
    misconfiguring."""
    raw = os.environ.get(name, "auto").strip().lower()
    if raw in ("on", "1", "true", "yes"):
        return "on"
    if raw in ("off", "0", "false", "no"):
        return "off"
    if raw in ("auto", ""):
        return "auto"
    import warnings

    warnings.warn(
        f"{name}={raw!r} not recognized (want on/off/auto); using auto",
        RuntimeWarning,
        stacklevel=2,
    )
    return "auto"


@dataclasses.dataclass
class EngineConfig:
    """Snapshot of all engine knobs, taken once when the engine starts.

    Mirrors the one-shot parse at background-thread startup in the reference
    (horovod/common/operations.cc:1614-1685).
    """

    timeline_file: str | None = None
    fusion_threshold_bytes: int = DEFAULT_FUSION_THRESHOLD_BYTES
    cycle_time_ms: float = DEFAULT_CYCLE_TIME_MS
    stall_check_enabled: bool = True
    stall_warning_time_s: float = DEFAULT_STALL_WARNING_TIME_S
    hierarchical_allreduce: bool = False
    # Inner (ici) extent of the hierarchical dispatch mesh; None → this
    # process's local device count (the reference's local/cross comm split
    # by MPI_COMM_TYPE_SHARED, operations.cc:1558-1590).  Settable for
    # tests via HOROVOD_TPU_HIERARCHY_LOCAL_SIZE.
    hierarchy_local_size: int | None = None
    sparse_allreduce: bool = False
    # Native coordination engine (native/src/): "auto" enables it for
    # multi-controller jobs when libhvdtpu builds; "on" forces it (tests,
    # single-host soak); "off" keeps pure-Python coordination.
    native_controller: str = "auto"
    # Transport spec for the native control plane: "tcp:<host>:<port>"
    # (multi-host; rank 0 binds) or "local:<world>" (in-process).
    controller_transport: str | None = None
    # Online (threshold, cycle-time) tuning — horovod_tpu/autotune.py.
    # These two knobs are the only MUTABLE config fields: the autotuner
    # rewrites them mid-run and the engine re-reads both every tick.
    autotune: bool = False
    autotune_log: str | None = None
    autotune_warmup_samples: int = 3
    autotune_steady_state_samples: int = 10
    # Dispatch serialization: "auto" blocks per launch on the CPU backend
    # only (multi-controller CPU collectives are matched by arrival order
    # — concurrent launches can pair mismatched messages); "off" keeps the
    # TPU-style async pipeline everywhere (safe single-process, where one
    # launch covers all ranks); "on" forces depth-1 even on TPU.
    serialize_dispatch: str = "auto"

    @classmethod
    def from_env(cls) -> "EngineConfig":
        return cls(
            timeline_file=os.environ.get(HOROVOD_TIMELINE) or None,
            fusion_threshold_bytes=_get_int(
                HOROVOD_FUSION_THRESHOLD, DEFAULT_FUSION_THRESHOLD_BYTES
            ),
            cycle_time_ms=_get_float(HOROVOD_CYCLE_TIME, DEFAULT_CYCLE_TIME_MS),
            stall_check_enabled=not _get_bool(HOROVOD_STALL_CHECK_DISABLE),
            stall_warning_time_s=_get_float(
                "HOROVOD_STALL_CHECK_TIME", DEFAULT_STALL_WARNING_TIME_S
            ),
            hierarchical_allreduce=_get_bool(HOROVOD_HIERARCHICAL_ALLREDUCE),
            hierarchy_local_size=(
                _get_int("HOROVOD_TPU_HIERARCHY_LOCAL_SIZE", 0) or None
            ),
            sparse_allreduce=_get_bool(HOROVOD_SPARSE_ALLREDUCE),
            native_controller=os.environ.get(
                "HOROVOD_TPU_NATIVE_CONTROLLER", "auto"
            ).strip().lower(),
            controller_transport=os.environ.get(
                "HOROVOD_TPU_CONTROLLER_TRANSPORT"
            ) or None,
            autotune=_get_bool(HOROVOD_AUTOTUNE),
            autotune_log=os.environ.get(HOROVOD_AUTOTUNE_LOG) or None,
            autotune_warmup_samples=_get_int(HOROVOD_AUTOTUNE_WARMUP_SAMPLES, 3),
            serialize_dispatch=_get_tristate(HOROVOD_TPU_SERIALIZE_DISPATCH),
            autotune_steady_state_samples=_get_int(
                HOROVOD_AUTOTUNE_STEADY_STATE_SAMPLES, 10
            ),
        )


def compile_cache_dir(checkout: str) -> str:
    """Where this process keeps jax's persistent compilation cache.

    If ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it itself and nothing
    is set in code; otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache``.  The path is part of the cache key, so it
    has no per-host, per-process or per-run component.  For entry scripts
    (``chip_smoke.py``, ``benchmark/run.py``), before their first compilation.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
