"""Process / device model: ``init``, ``rank``, ``size``, mesh management.

TPU-native re-design of the reference's process model
(reference: horovod/common/__init__.py:51-154 ``HorovodBasics`` and the C API
horovod/common/operations.cc:2040-2095).

The reference runs ONE process per GPU under ``mpirun``; ``rank()`` names the
process and ``local_rank()`` pins its GPU.  On TPU the idiomatic model is
single-controller-per-host JAX: one Python process drives ``local_device_count``
chips and multi-host jobs use ``jax.distributed``.  The mapping is:

==================  ==========================================================
Horovod concept      TPU-native equivalent
==================  ==========================================================
world (all ranks)    all devices of the global ``Mesh`` (axis ``"hvd"``)
``size()``           global device count (chips == Horovod ranks)
``local_size()``     chips driven from THIS host (all processes sharing it)
``rank()``           global index of this process's first device
``local_rank()``     index of this process's first chip among the host's
                     chips — {0..nproc-1} for one-process-per-chip gangs,
                     0 for a single controller process
``cross_size()``     ``jax.process_count()``   (number of hosts)
``cross_rank()``     ``jax.process_index()``   (this host's index)
==================  ==========================================================

``local_rank``/``local_size`` follow the reference's per-host communicator
(operations.cc:1558-1590, ``MPI_COMM_TYPE_SHARED``): processes are grouped
by physical host.  The topology source is layered — the launcher's
``HOROVOD_TPU_LOCAL_RANK``/``HOROVOD_TPU_LOCAL_SIZE`` env when present
(it knows the per-host process layout it spawned), else a hostname
exchange over the ``jax.distributed`` key-value store for externally
launched multi-process gangs, else the single-controller identity.

Inside compiled SPMD code (``shard_map`` over the mesh) the *per-chip* rank is
``jax.lax.axis_index("hvd")`` — exposed here as :func:`axis_rank`.

Eager collectives (see :mod:`horovod_tpu.ops.eager`) operate on **rank-major**
arrays: a logical "tensor held by every rank" is represented as one
``jax.Array`` of shape ``[size(), *shape]`` sharded along axis 0, so each chip
holds its own slice — the single-controller analogue of per-process tensors.
"""

from __future__ import annotations

import atexit
import os
import threading
from typing import Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.utils.env import EngineConfig

AXIS_NAME = "hvd"

# Analogue of CPU_DEVICE_ID (reference horovod/common/common.h:100): kept for
# API parity where a device id is reported for host-resident tensors.
CPU_DEVICE_ID = -1


class NotInitializedError(RuntimeError):
    """Raised when the API is used before ``init()``.

    Parity with the reference's "Horovod has not been initialized; use
    hvd.init()." ctypes-level errors (horovod/common/operations.cc:2047-2095).
    """


class HorovodInternalError(RuntimeError):
    """An ENVIRONMENTAL collective failure: the control plane broke, the
    engine was shut down underneath in-flight ops, or a peer vanished
    mid-negotiation — the failures :mod:`horovod_tpu.elastic` recovers
    from by re-initializing and replaying from the last committed state.

    Deterministic caller mistakes (shape/dtype mismatch between ranks,
    invalid arguments) stay plain ``ValueError``/``RuntimeError`` —
    retrying those would loop forever.  Name-parity with the exception
    Horovod's elastic mode keys on (its 0.20+ ``HorovodInternalError``;
    the 0.15.1 reference's closest analogue is the SHUT_DOWN_ERROR
    callback status, operations.cc:278-283)."""


class _State:
    """Global framework state — the analogue of ``HorovodGlobalState``
    (reference horovod/common/operations.cc:112-264), minus everything XLA
    already owns (streams, communicators, fusion buffers on device)."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.initialized = False
        self.shut_down = False
        self.mesh: Mesh | None = None
        self.config: EngineConfig = EngineConfig()
        self.engine = None  # lazily created EagerEngine
        self.timeline = None  # lazily created Timeline
        self.profiler_active = False  # start_timeline(profiler_dir=...)
        # (local_rank, local_size) — resolved lazily, cached per init()
        self.local_topology: tuple[int, int] | None = None
        # The (devices, mesh) arguments of the last successful init(),
        # kept through shutdown() so an elastic in-process retry can
        # replay the SAME world: a bare re-init() would silently widen a
        # device-subset/custom-mesh world to all devices, changing
        # size() and the rank mapping mid-training.
        self.last_init_args: tuple | None = None


_state = _State()


_distributed_initialized = False


def _maybe_init_distributed() -> None:
    """Initialize multi-host JAX when a coordinator is configured.

    The reference calls ``MPI_Init_thread`` on its background thread
    (horovod/common/operations.cc:1505-1525); the TPU equivalent is
    ``jax.distributed.initialize()``, driven by env config rather than MPI.

    Must run before any other JAX call initializes the XLA backend, so the
    guard is a module flag — probing ``jax.process_count()`` here would
    itself initialize the backend and poison ``initialize()``.
    """
    global _distributed_initialized
    if _distributed_initialized:
        return
    addr = os.environ.get("HOROVOD_TPU_COORDINATOR") or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    nproc = os.environ.get("HOROVOD_TPU_NUM_PROCESSES")
    pid = os.environ.get("HOROVOD_TPU_PROCESS_ID")
    if addr and nproc and pid:
        try:
            jax.distributed.initialize(
                coordinator_address=addr,
                num_processes=int(nproc),
                process_id=int(pid),
            )
        except RuntimeError as e:
            raise RuntimeError(
                "horovod_tpu.init() could not start multi-host JAX: "
                f"{e}.  Call hvd.init() before any other JAX API so the "
                "distributed runtime can be set up first."
            ) from e
        _distributed_initialized = True


def _my_mesh_device_count(st: "_State") -> int:
    return sum(
        1 for d in st.mesh.devices.flat
        if d.process_index == jax.process_index()
    )


def _distributed_client():
    """The ``jax.distributed`` key-value client, or ``None`` when there is
    none: a single process, or a jax whose private ``global_state`` moved.
    Either way the layered fallback in ``_local_topology`` takes over."""
    try:
        from jax._src.distributed import global_state
    except ImportError:
        return None
    return getattr(global_state, "client", None)


def _post_host_card(st: "_State") -> None:
    """Publish this process's ``hostname|mesh_device_count`` card to the
    ``jax.distributed`` key-value store so every peer can group ranks by
    physical host — the TPU-native stand-in for the reference's
    ``MPI_Comm_split_type(MPI_COMM_TYPE_SHARED)`` local communicator
    (reference operations.cc:1558-1590).  Posted once at ``init()`` AFTER
    the mesh is built (device-subset worlds advertise their mesh share,
    not the raw device count, so per-host local_size sums to size());
    reads happen lazily at the first ``local_rank()``/``local_size()``
    call."""
    client = _distributed_client()
    if client is None:
        return
    import socket

    client.key_value_set(
        f"horovod_tpu/hostcard/{jax.process_index()}",
        f"{socket.gethostname()}|{_my_mesh_device_count(st)}",
        allow_overwrite=True,  # re-init may change the mesh subset
    )


def _negotiate_timeout_s() -> float:
    """Host-card negotiation deadline: ``HVD_TPU_NEGOTIATE_TIMEOUT_S``
    (seconds, default 60).  An unparsable value falls back to the
    default rather than wedging ``init()``."""
    raw = os.environ.get("HVD_TPU_NEGOTIATE_TIMEOUT_S", "60")
    try:
        return float(raw)
    except ValueError:
        import warnings

        warnings.warn(
            f"ignoring unparsable HVD_TPU_NEGOTIATE_TIMEOUT_S={raw!r}; "
            f"using the 60 s default",
            RuntimeWarning,
            stacklevel=2,
        )
        return 60.0


def _kv_topology() -> tuple[int, int] | None:
    """Group processes by host via the cards ``_post_host_card`` published.

    Returns ``(local_rank, local_size)`` in CHIP units: local_size is the
    total device count across the host's processes, local_rank the number
    of devices owned by lower-ranked processes on the same host — which
    reduces to process indices {0..n-1} under one-process-per-chip, and to
    (0, n_chips) under one-controller-per-host.

    One ``key_value_dir_get`` poll loop, not per-process blocking gets: a
    pod-scale gang fetches every card in O(1) round-trips per poll, and a
    peer that never posts (mixed versions) costs one shared deadline
    (``HVD_TPU_NEGOTIATE_TIMEOUT_S``, default 60) before the fallback —
    not a full stall per missing key.  A timed-out negotiation WARNS
    with the posted-vs-expected peer count before falling back, so a
    wrong local topology is diagnosable instead of silent."""
    import time

    client = _distributed_client()
    n = jax.process_count()
    if client is None or n <= 1:
        return None
    from horovod_tpu import metrics as metrics_mod

    timeout_s = _negotiate_timeout_s()
    deadline = time.monotonic() + timeout_s
    while True:
        metrics_mod.DEFAULT.counter("hvd.negotiate_polls").inc()
        entries = client.key_value_dir_get("horovod_tpu/hostcard/")
        if len(entries) >= n:
            break
        if time.monotonic() >= deadline:
            import warnings

            metrics_mod.DEFAULT.counter(
                "hvd.negotiate_timeouts").inc()
            metrics_mod.DEFAULT.event(
                "hvd.negotiate_timeout", posted=len(entries),
                expected=n, timeout_s=timeout_s)
            warnings.warn(
                f"host-card negotiation timed out after "
                f"{timeout_s:g}s: {len(entries)} of {n} peers "
                f"posted host cards (set HVD_TPU_NEGOTIATE_TIMEOUT_S "
                f"to adjust); falling back to launcher-env/"
                f"single-host local topology",
                RuntimeWarning,
                stacklevel=3,
            )
            return None
        time.sleep(0.1)
    try:
        cards: dict[int, tuple[str, int]] = {}
        for key, raw in entries:
            host, ndev = raw.rsplit("|", 1)
            cards[int(key.rsplit("/", 1)[1])] = (host, int(ndev))
        my_host = cards[jax.process_index()][0]
    except (ValueError, KeyError):
        # A peer's card is not in this version's format, or ours is
        # missing: same fallback as a peer that never posted.
        return None
    me = jax.process_index()
    before = sum(
        nd for i, (h, nd) in cards.items() if h == my_host and i < me
    )
    total = sum(nd for h, nd in cards.values() if h == my_host)
    return before, total


def _local_topology(st: "_State") -> tuple[int, int]:
    """Resolve (local_rank, local_size), layered: launcher env (exact for
    the one-device-per-process model the launcher spawns — ignored when
    this process drives several chips, where process units would
    under-count) → KV-store host grouping → single-controller identity."""
    if st.local_topology is not None:
        return st.local_topology
    lr = os.environ.get("HOROVOD_TPU_LOCAL_RANK")
    ls = os.environ.get("HOROVOD_TPU_LOCAL_SIZE")
    topo = None
    if lr is not None and ls is not None and _my_mesh_device_count(st) == 1:
        topo = (int(lr), int(ls))
        world = st.mesh.devices.size
        if not (0 <= topo[0] < topo[1] <= world):
            # e.g. a launcher-spawned worker re-init()ed with a device
            # subset: the launcher's process-unit numbers no longer
            # describe this world (local_size would exceed size()).  Fall
            # through to the KV cards, which count mesh shares.
            topo = None
    if topo is None:
        topo = _kv_topology()
    if topo is None:
        topo = (0, _my_mesh_device_count(st))
    st.local_topology = topo
    return topo


def init(
    devices: Sequence[jax.Device] | None = None,
    mesh: Mesh | None = None,
    comm=None,
) -> None:
    """Initialize the framework.  Analogue of ``hvd.init()``
    (reference horovod/common/__init__.py:58-84 → operations.cc:2011-2029).

    Args:
      devices: optional subset of devices to form the world (the analogue of
        the reference's ``init(comm=[ranks])`` rank-subset form).  Defaults to
        all devices.
      mesh: optional pre-built 1-D mesh whose single axis becomes the Horovod
        world.  Overrides ``devices``.
      comm: reference-parity spelling of the subset form: a list of ints
        selects those ranks' chips — ``init(comm=[0, 2])`` ≡
        ``init(devices=[jax.devices()[0], jax.devices()[2]])``.  An mpi4py
        communicator is not a TPU concept (there is no MPI runtime to
        share); passing one raises with that explanation.
    """
    if comm is not None:
        if devices is not None or mesh is not None:
            raise ValueError("init(): pass comm= or devices=/mesh=, not both")
        import numbers

        if not (isinstance(comm, (list, tuple)) and comm and all(
            isinstance(r, numbers.Integral) and not isinstance(r, bool)
            for r in comm
        )):
            raise TypeError(
                "init(comm=...) takes a non-empty list of int ranks on "
                "TPU.  MPI communicators don't exist here — the process "
                "world comes from jax.distributed (the launcher sets it "
                "up); for a rank-subset world pass the rank list, for "
                "subset COLLECTIVES on a full world use hvd.ProcessSet."
            )
        comm = [int(r) for r in comm]  # numpy integers welcome
    with _state.lock:
        if _state.initialized:
            return
        _maybe_init_distributed()
        if comm is not None:
            # Resolve ranks only AFTER the jax.distributed bring-up:
            # jax.devices() commits the XLA backend, and calling it first
            # would poison it (the invariant _maybe_init_distributed
            # documents).
            all_devs = jax.devices()
            bad = [r for r in comm if not 0 <= r < len(all_devs)]
            if bad:
                raise ValueError(
                    f"init(comm={list(comm)}): ranks {bad} outside "
                    f"[0, {len(all_devs)})"
                )
            devices = [all_devs[r] for r in comm]
        if mesh is not None:
            if len(mesh.axis_names) != 1:
                raise ValueError(
                    "init(mesh=...) expects a 1-D mesh; for multi-axis "
                    "parallelism build your own mesh and use "
                    "horovod_tpu.ops in-graph collectives directly."
                )
            _state.mesh = Mesh(mesh.devices, (AXIS_NAME,))
        else:
            devs = list(devices) if devices is not None else jax.devices()
            import numpy as np

            _state.mesh = Mesh(np.asarray(devs), (AXIS_NAME,))
        _state.config = EngineConfig.from_env()
        _state.local_topology = None
        if mesh is not None:
            _state.last_init_args = (None, mesh)
        else:
            # Record the MATERIALIZED list, not the caller's argument: a
            # one-shot iterable is already exhausted by the list() above.
            _state.last_init_args = (
                tuple(devs) if devices is not None else None, None)
        _post_host_card(_state)
        _state.initialized = True
        _state.shut_down = False
    # Pin the rank identity stamped on event-log records / state dumps
    # (outside the lock: rank() re-enters _require_init's read path).
    from horovod_tpu import metrics as metrics_mod
    metrics_mod.set_rank(rank())
    atexit.register(shutdown)


def shutdown() -> None:
    """Shut the framework down.  Analogue of ``hvd.shutdown()``
    (reference horovod/common/__init__.py atexit hook → operations.cc:2046).

    Drains the eager engine (all outstanding handles complete or error) and
    releases global state; idempotent.
    """
    with _state.lock:
        if not _state.initialized or _state.shut_down:
            return
        engine, _state.engine = _state.engine, None
        timeline, _state.timeline = _state.timeline, None
        profiling, _state.profiler_active = _state.profiler_active, False
        _state.shut_down = True
        _state.initialized = False
        _state.mesh = None
        _state.local_topology = None
    if profiling:
        # A start_timeline(profiler_dir=...) window left open at shutdown
        # must still finalize the XLA profile (a dangling trace would make
        # the next start_trace raise).
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
    if engine is not None:
        engine.shutdown()
    if timeline is not None:
        timeline.close()
    from horovod_tpu import metrics as metrics_mod
    metrics_mod.set_rank(None)


def is_initialized() -> bool:
    return _state.initialized


def _require_init() -> _State:
    if not _state.initialized:
        raise NotInitializedError(
            "horovod_tpu has not been initialized; use horovod_tpu.init()."
        )
    return _state


def mesh() -> Mesh:
    """The world mesh (single axis ``"hvd"``, one entry per chip)."""
    return _require_init().mesh


def config() -> EngineConfig:
    return _require_init().config


def size() -> int:
    """Total number of chips in the world — the Horovod world size
    (reference operations.cc:2063-2067)."""
    return _require_init().mesh.devices.size


def local_size() -> int:
    """Chips driven from this HOST — all its processes together
    (reference operations.cc:2069-2073: the per-host communicator's size).
    One-process-per-chip gangs see the host's process count; a single
    controller sees its own device count.  Topology resolution order is
    documented in the module docstring."""
    return _local_topology(_require_init())[1]


def rank() -> int:
    """Global index of this process's first device
    (reference operations.cc:2051-2055; see module docstring for mapping)."""
    st = _require_init()
    for i, d in enumerate(st.mesh.devices.flat):
        if d.process_index == jax.process_index():
            return i
    return 0


def local_rank() -> int:
    """Index of this process's first chip among the host's chips
    (reference operations.cc:2057-2061: rank in the per-host communicator).
    {0..nproc-1} under the one-process-per-chip model the torch frontend
    uses — so reference-style per-host logic ("first process on host",
    data staggering, per-host caching) ports unchanged; 0 for a single
    controller process (device pinning is owned by the TPU runtime)."""
    return _local_topology(_require_init())[0]


def cross_size() -> int:
    """Number of hosts (the reference's cross-communicator size,
    operations.cc:1558-1590)."""
    _require_init()
    return jax.process_count()


def cross_rank() -> int:
    """This host's index (reference cross-communicator rank)."""
    _require_init()
    return jax.process_index()


def mpi_threads_supported() -> bool:
    """Parity shim (reference operations.cc:2089-2095).  There is no MPI in
    the TPU runtime; multi-controller coordination is always thread-safe."""
    _require_init()
    return True


def axis_rank():
    """Per-chip rank inside compiled SPMD code: ``lax.axis_index("hvd")``."""
    return jax.lax.axis_index(AXIS_NAME)


# ---------------------------------------------------------------------------
# Rank-major helpers: build / inspect the eager representation.
# ---------------------------------------------------------------------------


def rank_sharding() -> NamedSharding:
    """Sharding that splits axis 0 over ranks (eager rank-major layout)."""
    return NamedSharding(mesh(), P(AXIS_NAME))


def replicated_sharding() -> NamedSharding:
    return NamedSharding(mesh(), P())


def from_per_rank(values) -> jax.Array:
    """Stack one-per-rank host values into a rank-major sharded array.

    The single-controller analogue of "each MPI process holds its tensor":
    ``values`` is a sequence of ``size()`` equal-shaped arrays; the result has
    shape ``[size(), *shape]`` with shard *i* resident on chip *i*.
    """
    import jax.numpy as jnp

    n = size()
    if len(values) != n:
        raise ValueError(f"expected {n} per-rank values, got {len(values)}")
    stacked = jnp.stack([jnp.asarray(v) for v in values])
    return jax.device_put(stacked, rank_sharding())


def per_rank(fn) -> jax.Array:
    """Build a rank-major array from ``fn(rank) -> array``  (test helper for
    the reference's rank-dependent tensors, test/test_tensorflow.py:56-86)."""
    return from_per_rank([fn(r) for r in range(size())])
