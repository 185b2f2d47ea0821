"""The eager engine: async named-tensor collectives with fusion cycles.

TPU-native re-design of the reference's background coordination engine
(reference: horovod/common/operations.cc — ``BackgroundThreadLoop``
:1493-1764, ``RunLoopOnce`` :1795-2007, ``PerformOperation`` :734-1420).

What the reference engine does, and where it went on TPU:

* **Negotiation** (rank-0 gathers requests, matches readiness): exists
  because each MPI process schedules ops in nondeterministic order.  Under a
  single JAX controller, one Python thread observes *every* enqueue, so
  readiness matching is a queue.  In multi-controller jobs the user program
  is identical on every host, so op *order* agrees, but flush *timing* does
  not — therefore fusion grouping there is restricted to caller-delimited
  groups (see ``_fuse_key``), which are identical across hosts by
  construction.  The queue-until-cycle behaviour (and its observability via
  the Timeline NEGOTIATE phase) is retained.
* **Tensor fusion** (memcpy into a 64 MiB buffer, one collective): becomes
  same-dtype bucketing into ONE concatenated psum per bucket, compiled by
  XLA (see :mod:`horovod_tpu.ops.fusion`); ``HOROVOD_FUSION_THRESHOLD`` and
  ``HOROVOD_CYCLE_TIME`` keep their meaning.
* **Execution** (NCCL/MPI calls on a private stream): becomes dispatch of a
  cached jitted ``shard_map`` program; XLA owns streams, buffers and the ICI
  wire.  Async handles map onto JAX's async dispatch — a dispatched op IS a
  future.
* **Stall check** (operations.cc:1424-1470): a watchdog thread warns about
  tensors enqueued but never synchronized.

Eager tensors use the **rank-major** representation (see
:mod:`horovod_tpu.basics`): a logical per-rank tensor of shape ``S`` is one
``jax.Array`` of shape ``[size, *S]`` sharded over axis 0.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import sys
import threading
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu import basics, metrics as metrics_mod
from horovod_tpu import timeline as timeline_mod
from horovod_tpu.basics import AXIS_NAME, HorovodInternalError
from horovod_tpu.ops import collective_ops
from horovod_tpu.ops.collective_ops import Average, Sum, _ReduceOp
from horovod_tpu.ops.compression import Compression, TopKCompressor
from horovod_tpu.ops.handle_manager import HandleManager


@dataclasses.dataclass
class _PendingOp:
    kind: str                      # 'allreduce' | 'allgather' | 'broadcast' | 'sparse'
    handle: int
    tensor: jax.Array              # rank-major stacked input
    name: str
    op: _ReduceOp = Sum
    compression: Any = Compression.none
    root_rank: int = 0
    sizes: tuple[int, ...] | None = None   # ragged allgather per-rank dim-0 sizes
    topk: TopKCompressor | None = None
    group_id: int | None = None            # caller-delimited fusion group
    process_set: Any = None                # ProcessSet restricting the op
    no_fuse: bool = False                  # never share a fusion bucket
    # May a JOINED rank satisfy this op with identity (zero) inputs?
    # True for ordinary data allreduces (hvd.join semantics); False for
    # rendezvous ops like barrier, whose whole point is that every rank
    # actually arrives.
    join_identity: bool = True
    enqueued_at: float = 0.0


def _per_rank_nbytes(stacked: jax.Array) -> int:
    n = stacked.shape[0]
    return (int(stacked.size) // max(n, 1)) * stacked.dtype.itemsize


def _op_end_args(p: _PendingOp) -> dict:
    """dtype/per-rank shape for an op END event (reference
    timeline.cc:170-188 attaches them via TensorShape::DebugString), making
    each trace track diagnosable without cross-referencing code."""
    return {"dtype": str(p.tensor.dtype), "shape": list(p.tensor.shape[1:])}


class EagerEngine:
    """Background engine: queue → cycle tick → fuse → dispatch.

    One instance per :func:`horovod_tpu.init`; created lazily on first eager
    op (the reference spawns its thread inside ``InitializeHorovodOnce``,
    operations.cc:2011-2029).
    """

    # `stats` is intentionally undeclared: it is mixed-lock by design
    # (incremented under whichever lock the touching path already
    # holds — see the comment above its assignment).
    _GUARDED_BY_LOCK = {
        "_lock": ("_queue", "_join_active", "_join_result"),
        "_flush_lock": ("_submitted", "_dispatch_cache"),
    }
    # These run entirely under _flush_lock taken by flush()'s caller
    # chain; they contain no `with` of their own.
    _LOCK_HOLDER_METHODS = {
        "_flush_lock": ("_flush_via_controller", "_allreduce_group_fn",
                        "_dispatch_allreduce_group", "_dispatch_single"),
    }

    def __init__(self, mesh, cfg, timeline=None):
        self.mesh = mesh
        self.config = cfg
        self.handles = HandleManager()
        self.timeline = timeline
        self._axis: Any = AXIS_NAME
        if cfg.hierarchical_allreduce:
            # HOROVOD_HIERARCHICAL_ALLREDUCE: dispatch over a 2-D
            # (dcn, ici) mesh so XLA nests the reduction — fast ICI within
            # the local group, DCN across groups (the reference's
            # ReduceScatter→cross-MPI→AllGather pipeline,
            # operations.cc:1070-1223, expressed as mesh structure).
            local = cfg.hierarchy_local_size or jax.local_device_count()
            total = int(mesh.devices.size)
            if local > 1 and total % local == 0 and total // local > 1:
                from jax.sharding import Mesh

                self.mesh = Mesh(
                    mesh.devices.reshape(total // local, local),
                    ("dcn", "ici"),
                )
                self._axis = ("dcn", "ici")
            else:
                print(
                    "WARNING: HOROVOD_HIERARCHICAL_ALLREDUCE=1 ignored: "
                    f"world of {total} devices does not factor into "
                    f"(cross, local={local}) groups with both extents > 1; "
                    "dispatching over the flat 1-D mesh.  Set "
                    "HOROVOD_TPU_HIERARCHY_LOCAL_SIZE to a divisor of the "
                    "world size to choose the inner extent.",
                    file=sys.stderr,
                )
        self._lock = threading.Lock()
        self._flush_lock = threading.Lock()
        self._queue: list[_PendingOp] = []
        self._dispatch_cache: dict[tuple, Any] = {}
        # CPU-simulation only (same rationale as make_train_step's
        # throttle): XLA CPU collectives are matched by arrival order on
        # shared in-process/Gloo transport, so multiple collective launches
        # in flight can execute in different orders on different ranks and
        # pair mismatched messages ("received data size doesn't match").
        # Blocking per dispatch caps in-flight depth at 1; TPU's ordered
        # stream needs no throttle and keeps the async pipeline.
        # HOROVOD_TPU_SERIALIZE_DISPATCH overrides: "off" tests the
        # TPU-production pipelined path on the single-process virtual mesh
        # (one controller ⇒ one launch covers all ranks, so CPU arrival
        # order cannot diverge); "on" forces depth-1 on any backend.
        if cfg.serialize_dispatch == "on":
            self._serialize_dispatch = True
        elif cfg.serialize_dispatch == "off":
            self._serialize_dispatch = False
        else:
            self._serialize_dispatch = jax.default_backend() == "cpu"
        self._shutdown = threading.Event()
        self._tick = threading.Event()
        self.controller = self._maybe_native_controller(cfg)
        if self.controller is not None and self.timeline is not None:
            # Per-rank NEGOTIATE ticks on rank 0's timeline
            # (reference timeline.cc:98-132); drained after every tick.
            self.controller.enable_tick_trace()
        self._submitted: dict[str, _PendingOp] = {}
        # hvd.join state: while active, batches with names this rank never
        # submitted are filled with zero phantoms (_join_fill); the
        # all-joined response's last rank lands in _join_result.
        self._join_active = False
        self._join_result: int | None = None
        self.autotuner = None
        if cfg.autotune:
            if self.controller is not None:
                # Control-plane autotune: rank 0 OWNS the tuner (it owns
                # batching — BuildBatches runs only there), and every move
                # is installed into the native controller, which applies it
                # to the next tick's bucketing and piggybacks the values on
                # the response so all ranks observe the move in the same
                # tick (reference-shaped: rank-0 tunes, renegotiates
                # through the control plane).
                if jax.process_index() == 0:
                    from horovod_tpu.autotune import Autotuner

                    self.autotuner = Autotuner(
                        cfg,
                        warmup_samples=cfg.autotune_warmup_samples,
                        window_flushes=cfg.autotune_steady_state_samples,
                        log_path=cfg.autotune_log,
                        on_move=self.controller.set_tuned,
                    )
                    # No init-time SetTuned: the controller already holds
                    # the construction threshold, and pre-seeding would mark
                    # untouched defaults as "tuned", silently overriding any
                    # per-rank env differences before the first real move.
            elif jax.process_count() > 1:
                # Multi-controller WITHOUT the native controller: per-host
                # tuners scored on host-local noise would move to different
                # thresholds at different times, split the same group into
                # different buckets per host, and deadlock the
                # differently-fused collectives (see _fuse_key).
                print(
                    "WARNING: HOROVOD_AUTOTUNE=1 ignored: multi-host "
                    "autotuning requires the native controller "
                    "(HOROVOD_TPU_NATIVE_CONTROLLER=on), where rank 0 "
                    "tunes and renegotiates the threshold through the "
                    "control plane; independent per-host tuning would "
                    "diverge bucket plans across hosts.",
                    file=sys.stderr,
                )
            else:
                from horovod_tpu.autotune import Autotuner

                self.autotuner = Autotuner(
                    cfg,
                    warmup_samples=cfg.autotune_warmup_samples,
                    window_flushes=cfg.autotune_steady_state_samples,
                    log_path=cfg.autotune_log,
                )
        # Observability counters (hvd.engine_stats()): updated under the
        # engine's own locks on their paths (enqueue under _lock, dispatch
        # under _flush_lock); reads are snapshots, not a barrier.  Must
        # exist before the cycle thread starts flushing.  Every key is
        # pre-seeded so the key set never grows after __init__ — an
        # unlocked dict() snapshot in engine_stats() would otherwise race
        # a cycle-thread first-insertion and can raise "dictionary changed
        # size during iteration".
        self.stats: dict[str, int] = collections.Counter({
            "ops_enqueued": 0, "batches_dispatched": 0, "tensors_fused": 0,
            "allreduce_bytes": 0, "errors": 0, "stall_warnings": 0,
        })
        # Last-N negotiate waits (enqueue → dispatch) for the straggler
        # detector's rolling window; deque appends are atomic, so the
        # flush thread writes and engine_stats() snapshots lock-free.
        self.recent_negotiate_s: collections.deque[float] = (
            collections.deque(maxlen=256))
        self._cycle_thread = threading.Thread(
            target=self._cycle_loop, name="horovod_tpu-engine", daemon=True
        )
        self._cycle_thread.start()
        self._stall_thread: threading.Thread | None = None
        if cfg.stall_check_enabled:
            self._stall_thread = threading.Thread(
                target=self._stall_loop, name="horovod_tpu-stall-check", daemon=True
            )
            self._stall_thread.start()

    def _mark_error(self, handle: int, err: Exception) -> None:
        """Every handle failure goes through here so ``stats["errors"]``
        counts controller-path rejections (duplicate names, negotiation
        errors, shutdown orphans) the same as dispatch failures."""
        self.handles.mark_error(handle, err)
        self.stats["errors"] += 1

    def _maybe_native_controller(self, cfg):
        """Bring up the native coordination engine (native/src/controller.cc)
        when configured.  ``auto`` → multi-controller jobs only (where true
        negotiation is required for cross-host agreement on op order and
        fusion — the job the reference's C++ coordinator does,
        operations.cc:1795-2007); ``on`` forces it (tests / soak);
        ``off``/unavailable → pure-Python coordination."""
        mode = (cfg.native_controller or "auto").lower()
        if mode in ("off", "0", "false", "no"):
            return None
        nproc = jax.process_count()
        if mode == "auto" and nproc == 1:
            return None
        from horovod_tpu import native

        if mode != "auto":
            native.load_library()   # "on": a failed build raises, with g++'s words
        elif not native.available():
            return None
        spec = cfg.controller_transport
        if spec is None:
            if nproc > 1:
                if mode != "auto":
                    raise RuntimeError(
                        "HOROVOD_TPU_NATIVE_CONTROLLER=on on a multi-host "
                        "job requires HOROVOD_TPU_CONTROLLER_TRANSPORT "
                        "(e.g. tcp:<rank0-host>:<port>)"
                    )
                # auto multi-host with no transport configured: fall back to
                # Python coordination (caller-delimited fusion groups only).
                print(
                    "WARNING: horovod_tpu eager collectives on a multi-host "
                    "job without HOROVOD_TPU_CONTROLLER_TRANSPORT: falling "
                    "back to Python coordination.  Only caller-delimited "
                    "groups (grouped_allreduce_eager) will fuse, and "
                    "cross-host agreement relies on identical program "
                    "order; set HOROVOD_TPU_CONTROLLER_TRANSPORT="
                    "tcp:<rank0-host>:<port> to enable true negotiation.",
                    file=sys.stderr,
                )
                return None
            import os as _os

            spec = f"local:engine-{_os.getpid()}"
        return native.NativeController(
            rank=jax.process_index(),
            size=nproc,
            transport_spec=spec,
            fusion_threshold_bytes=cfg.fusion_threshold_bytes,
            stall_warning_s=cfg.stall_warning_time_s,
        )

    # ------------------------------------------------------------------ queue

    def enqueue(self, pending: _PendingOp) -> int:
        """Analogue of EnqueueTensorAllreduce/Allgather/Broadcast
        (reference operations.cc:2099-2215): push into the shared queue under
        the table mutex; the cycle thread picks it up."""
        self.enqueue_many([pending])
        return pending.handle

    def enqueue_many(self, pendings: list[_PendingOp]) -> None:
        """Enqueue a caller-delimited group ATOMICALLY (one lock
        acquisition), so no cycle-thread flush can observe a partial group.

        This is what makes grouped fusion deterministic: with the whole
        group entering the queue at once and ``_fuse_key`` isolating it by
        ``group_id``, every flush sees the same bucket composition for the
        same call — and therefore the same jitted-program signatures.
        Per-op enqueue would let the tick cut the group at a wall-clock-
        dependent point, compiling a fresh program arity per cut (compile
        churn measured at ~240 ms per novel signature on the CPU sim).
        """
        now = time.monotonic()
        for p in pendings:
            p.enqueued_at = now
            if self.timeline:
                self.timeline.start(
                    p.name, timeline_mod.NEGOTIATE + "_" + p.kind.upper()
                )
                if self.controller is None:
                    # Single controller: one thread observes every enqueue,
                    # so all ranks' readiness arrives at once — one tick
                    # covers the reference's per-rank tick events
                    # (timeline.cc:98-132).
                    self.timeline.instant(p.name, "NEGOTIATE_TICK_ALL")
        with self._lock:
            if self._shutdown.is_set():
                raise HorovodInternalError(
                    "horovod_tpu engine has been shut down")
            self._queue.extend(pendings)
            self.stats["ops_enqueued"] += len(pendings)

    def _fuse_key(self, p: _PendingOp):
        """Fusability key for :func:`fusion.plan_buckets` — the eager
        analogue of the reference's same-type/same-device merge predicate
        (operations.cc:1916-1943).

        In multi-controller jobs, fusion decided by host-local flush timing
        would let different hosts dispatch differently-fused collectives and
        deadlock; there, only *caller-delimited* groups (grouped_allreduce's
        ``group_id``, identical across hosts because the user program is)
        may fuse.  Single-controller keeps timing-based fusion — one thread
        observes every enqueue, so any grouping is consistent.
        """
        if p.kind != "allreduce":
            return ("solo", p.handle)
        if p.op is collective_ops.Adasum or p.no_fuse:
            # Adasum's inner products are per-tensor; no_fuse callers
            # (e.g. int8 error feedback, whose residual must reproduce the
            # wire's exact block quantization) opt out explicitly.
            return ("solo", p.handle)
        ps = p.process_set.ranks if p.process_set is not None else None
        base = ("ar", p.op.name, p.compression, str(p.tensor.dtype), ps)
        if p.group_id is not None:
            # Caller-delimited groups are isolated whenever fusion is
            # planned HERE (single host, or multi-host without the native
            # controller — the controller path negotiates its own merge,
            # see _controller_group): members enter the queue atomically
            # (enqueue_many), so bucket composition — and with it the
            # jitted dispatch-program signature — is identical on every
            # call instead of varying with where the cycle tick happened
            # to cut the queue.
            return base + (("grp", p.group_id),)
        if jax.process_count() > 1:
            return base + (("solo", p.handle),)
        return base

    def flush(self) -> None:
        """Drain the queue now: group, fuse, dispatch.

        The analogue of one ``RunLoopOnce`` tick (operations.cc:1795-2007).
        With the native controller, requests are negotiated (gather → match
        → fuse → bcast, native/src/controller.cc) and dispatch follows the
        returned batch order; without it, negotiation is a no-op under the
        single controller (see module docstring) and fusion is planned
        locally.  Serialized under ``_flush_lock`` so concurrent callers
        (cycle thread, poll, synchronize) cannot interleave dispatch order.
        """
        from horovod_tpu.ops import fusion

        tune_sample = None
        with self._flush_lock:
            with self._lock:
                batch, self._queue = self._queue, []
            if self.controller is not None:
                # Controller path: the returned sample (rank 0 with
                # autotune only) is its dispatched allreduce traffic.
                tune_sample = self._flush_via_controller(batch)
            elif batch:
                for p in batch:
                    self._end_negotiate(p)
                buckets = fusion.plan_buckets(
                    batch,
                    self.config.fusion_threshold_bytes,
                    nbytes=lambda p: _per_rank_nbytes(p.tensor),
                    key=self._fuse_key,
                )
                ar_bytes, sample_out = 0, None
                for bucket in buckets:
                    group = [batch[i] for i in bucket]
                    if group[0].kind == "allreduce":
                        out, nb = self._dispatch_allreduce_group(group)
                        if out is not None:
                            ar_bytes += nb
                            sample_out = out
                    else:
                        assert len(group) == 1
                        self._dispatch_single(group[0])
                if self.autotuner is not None and ar_bytes:
                    tune_sample = (ar_bytes, sample_out)
        # Score OUTSIDE the flush lock: closing a window blocks on device
        # completion of the probe, and holding the lock through that would
        # stall every concurrent synchronize()/poll() flush.
        if tune_sample is not None and self.autotuner is not None:
            self.autotuner.observe(*tune_sample)

    _KIND_CODES = {"allreduce": 0, "allgather": 1, "broadcast": 2,
                   "sparse": 3, "alltoall": 4, "reducescatter": 5}

    def _controller_group(self, p: _PendingOp) -> int:
        """Encode fusability (reduce op, compression) into the controller's
        int64 ``group`` so negotiation never merges requests that need
        different compiled programs.

        The id must be a pure function of the key — NOT encounter order,
        which differs across ranks when flush timing differs, and would let
        the controller fuse a Sum with a Min (dispatched with group[0]'s op
        → silently wrong numerics).

        Caller-delimited group ids ARE included: cross-group merging would
        be *correct* (the batch order is globally agreed), but it makes
        bucket composition depend on what other traffic shared the
        negotiation tick — and under XLA every novel composition is a
        fresh compiled dispatch program (docs/tensor-fusion.md
        "Determinism and compile churn").  Group ids come from a
        per-process counter, identical across ranks exactly when the user
        program is — the same contract grouped fusion already relies on in
        the controller-less multi-host mode.  A divergent program cannot
        deadlock on it: the first-arriving rank's token wins at the
        coordinator and the batch it broadcasts is what every rank
        dispatches."""
        if p.kind != "allreduce":
            return -1
        comp = getattr(p.compression, "__name__", None) or type(
            p.compression
        ).__name__
        ps = p.process_set.ranks if p.process_set is not None else ()
        token = f"{p.op.name}:{comp}:{ps}".encode()
        if p.group_id is not None:
            token += b":grp:" + str(p.group_id).encode()
        if p.no_fuse:
            # Only the same-named request from the other ranks may join
            # this batch — names are identical across ranks, so the batch
            # stays exactly one tensor everywhere.
            token += b":" + p.name.encode()
        import hashlib

        return int.from_bytes(hashlib.sha1(token).digest()[:7], "big")

    @staticmethod
    def _op_code(p: _PendingOp) -> int:
        """Dispatch-program code for join support (types.h OpCode): a
        joined rank can fabricate identity inputs only for the plain
        Sum/Average allreduce program — everything else is kOpOther and
        the controller errors it if it can only complete via joins."""
        from horovod_tpu import native

        if (p.kind == "allreduce" and p.process_set is None
                and p.compression is Compression.none
                and p.join_identity):
            if p.op is Sum:
                return native.OP_PLAIN_SUM
            if p.op is Average:
                return native.OP_PLAIN_AVERAGE
        return native.OP_OTHER

    def _join_fill(self, b, ops: list[_PendingOp]) -> list[_PendingOp] | None:
        """Fill a batch this JOINED rank only partially (or never)
        submitted: phantom ops with identity (zero) inputs stand in for
        the missing names, so this rank launches the SAME compiled
        collective as its active peers — the XLA collective is global
        across processes, and a joined rank that skipped the launch would
        hang the gang (the join op of Horovod ≥0.21 feeds zero tensors the
        same way).  Returns None when the batch is not join-eligible
        (then the caller's silent-skip fallback applies)."""
        from horovod_tpu import native

        if (not self._join_active or b.kind != native.KIND_ALLREDUCE
                or b.op_code not in (native.OP_PLAIN_SUM,
                                     native.OP_PLAIN_AVERAGE)):
            return None
        import numpy as _np

        dtype = _np.dtype(native.DTYPE_NAMES.get(b.dtype, "float32"))
        op = (Average if b.op_code == native.OP_PLAIN_AVERAGE else Sum)
        n = self.mesh.devices.size
        by_name = {p.name: p for p in ops}
        return [
            by_name.get(name) or _PendingOp(
                kind="allreduce", handle=-1,
                tensor=jnp.zeros((n, *shape), dtype=dtype), name=name, op=op,
            )
            for name, shape in zip(b.names, b.shapes)
        ]

    def _flush_via_controller(self, batch: list[_PendingOp]):
        """Submit new requests, run one negotiation tick, dispatch the
        globally-agreed batches (names → this process's pending ops).

        Returns ``(allreduce_bytes, sample_output)`` when this rank runs
        the autotuner (rank 0) and the tick dispatched allreduce traffic;
        None otherwise."""
        for p in batch:
            if p.name in self._submitted:
                # The reference rejects duplicate in-flight names at enqueue
                # (operations.cc:2124-2134).
                self._end_negotiate(p)
                self._mark_error(
                    p.handle,
                    RuntimeError(f"Duplicate tensor name in flight: {p.name}"),
                )
                continue
            try:
                self.controller.submit(
                    self._KIND_CODES[p.kind],
                    str(p.tensor.dtype),
                    p.name,
                    tuple(p.tensor.shape[1:]),
                    root_rank=p.root_rank,
                    group=self._controller_group(p),
                    op_code=self._op_code(p),
                )
            except Exception as e:
                # Per-op containment, like the non-controller dispatch path:
                # a rejected request fails ITS handle, not the whole flush.
                self._end_negotiate(p)
                self._mark_error(p.handle, e)
                continue
            self._submitted[p.name] = p
        try:
            # hvdlint: disable=HVD008 -- negotiated dispatch IS the flush lock's critical section; serializing it is the lock's purpose (see flush docstring)
            bl = self.controller.tick()
        except Exception as e:
            # A broken control plane strands every outstanding op; fail
            # their handles so waiters unblock instead of hanging.  Typed
            # HorovodInternalError (environmental, not a caller mistake)
            # so elastic.run can recover by reinit + replay.
            err = HorovodInternalError(f"control plane failed: {e}")
            err.__cause__ = e
            for p in self._submitted.values():
                self._end_negotiate(p)
                self._mark_error(p.handle, err)
            self._submitted.clear()
            raise err
        if self.timeline:
            for tname, trank in self.controller.drain_ticks():
                self.timeline.instant(tname, f"NEGOTIATE_TICK_r{trank}")
        # Control-plane autotune: apply rank-0's tuned knobs, piggybacked on
        # every response, so the whole gang's config moves in the same tick
        # (bucketing itself is already rank-0-owned via BuildBatches).  The
        # tuner OWNER skips the apply: its tuner writes config directly in
        # _move_to, and a response built just before a move landed would
        # briefly roll its config back.
        if self.autotuner is None:
            if bl.tuned_threshold_bytes is not None:
                self.config.fusion_threshold_bytes = bl.tuned_threshold_bytes
            if bl.tuned_cycle_ms is not None:
                self.config.cycle_time_ms = bl.tuned_cycle_ms
        if bl.last_joined >= 0:
            with self._lock:
                self._join_result = bl.last_joined
        ar_bytes, sample_out = 0, None
        for b in bl.batches:
            ops = [
                self._submitted.pop(n) for n in b.names if n in self._submitted
            ]
            if len(ops) != len(b.names) and not b.error:
                full = self._join_fill(b, ops)
                if full is not None:
                    for p in ops:
                        self._end_negotiate(p)
                    out, nb = self._dispatch_allreduce_group(full)
                    if out is not None and ops:
                        ar_bytes += nb
                        sample_out = out
                    continue
            if not ops:
                continue
            for p in ops:
                self._end_negotiate(p)
            if b.error:
                err = RuntimeError(b.error)
                for p in ops:
                    self._mark_error(p.handle, err)
            elif ops[0].kind == "allreduce":
                out, nb = self._dispatch_allreduce_group(ops)
                if out is not None:
                    ar_bytes += nb
                    sample_out = out
            else:
                for p in ops:
                    self._dispatch_single(p)
        if bl.shutdown:
            # Orphaned ops (submitted but never matched before the shutdown
            # response) must error, not hang their waiters — parity with the
            # reference's SHUT_DOWN_ERROR callbacks (operations.cc:278-283).
            err = HorovodInternalError(
                "horovod_tpu has been shut down; collective was not "
                "completed by all ranks"
            )
            for p in self._submitted.values():
                self._end_negotiate(p)
                self._mark_error(p.handle, err)
            self._submitted.clear()
            self._shutdown.set()
        if self.autotuner is not None and ar_bytes:
            return (ar_bytes, sample_out)
        return None

    def _end_negotiate(self, p: _PendingOp) -> None:
        # Queue-time histogram: enqueue → the flush deciding to run the
        # op, the same span the timeline's NEGOTIATE phase draws — but
        # scrapeable with no timeline attached.
        if p.enqueued_at:
            wait = time.monotonic() - p.enqueued_at
            metrics_mod.DEFAULT.histogram("hvd.negotiate_s").observe(wait)
            self.recent_negotiate_s.append(wait)
        if self.timeline:
            self.timeline.end(
                p.name, timeline_mod.NEGOTIATE + "_" + p.kind.upper()
            )

    def join(self) -> int:
        """Declare this rank out of data (the ``hvd.join()`` API Horovod
        grew in 0.21 for uneven datasets): block until EVERY rank has
        joined, meanwhile participating in the gang's remaining plain
        Sum/Average allreduces with identity (zero) inputs so active ranks
        never stall.  Returns the last rank to join — a root guaranteed to
        have processed all its data.

        Needs the native controller (multi-process gangs).  In a
        single-controller world every rank is driven by this process, so
        all "join" simultaneously: returns ``size - 1`` immediately.
        """
        if self.controller is None:
            if jax.process_count() > 1:
                raise RuntimeError(
                    "hvd.join() needs the native controller "
                    "(HOROVOD_TPU_NATIVE_CONTROLLER=on + a controller "
                    "transport); Python-degraded coordination cannot "
                    "negotiate joined ranks"
                )
            self.flush()
            return self.mesh.devices.size - 1
        self.flush()                     # drain this rank's own queue first
        with self._lock:
            self._join_result = None
            self._join_active = True
        try:
            self.controller.submit_join()
            while True:
                self.flush()
                with self._lock:
                    r = self._join_result
                if r is not None:
                    return r
                if self._shutdown.is_set():
                    raise HorovodInternalError(
                        "engine shut down while waiting in hvd.join()"
                    )
                time.sleep(max(self.config.cycle_time_ms, 0.5) / 1000.0)
        finally:
            with self._lock:
                self._join_active = False
                self._join_result = None

    def _cycle_loop(self) -> None:
        """Background tick every ``HOROVOD_CYCLE_TIME`` ms
        (reference operations.cc:1795 tick + :1661-1685 knob).  The period
        is re-read every iteration: the autotuner mutates it mid-run."""
        while not self._shutdown.is_set():
            period = max(self.config.cycle_time_ms, 0.1) / 1000.0
            self._tick.wait(timeout=period)
            self._tick.clear()
            try:
                self.flush()
                tl = self.timeline
                if tl is not None and tl.mark_cycles:
                    # hvd.start_timeline(mark_cycles=True) parity: one
                    # instant per engine tick on a dedicated track.
                    tl.instant("_engine", "CYCLE_START")
            except Exception:  # pragma: no cover - defensive: keep ticking
                import traceback

                traceback.print_exc(file=sys.stderr)

    def _stall_loop(self) -> None:
        """Warn about tensors stuck in the queue — parity with
        CheckForStalledTensors (reference operations.cc:1424-1470)."""
        warn_after = self.config.stall_warning_time_s
        while not self._shutdown.is_set():
            self._shutdown.wait(timeout=min(warn_after / 4.0, 15.0))
            if self._shutdown.is_set():
                return
            now = time.monotonic()
            with self._lock:
                stalled = [
                    p.name for p in self._queue if now - p.enqueued_at > warn_after
                ]
            if self.controller is not None:
                # Rank-0's native table knows which ranks are missing
                # (reference stall message lists them, operations.cc:1455).
                report = self.controller.stall_report()
                if report:
                    stalled.append(report)
            if stalled:
                self.stats["stall_warnings"] += 1
                print(
                    "WARNING: One or more tensors were submitted to be "
                    "reduced, gathered or broadcasted by subset of ranks and "
                    f"are waiting for remainder of ranks for more than {int(warn_after)} "
                    "seconds. Stalled ops: " + ", ".join(sorted(stalled)),
                    file=sys.stderr,
                )

    def shutdown(self) -> None:
        """Coordinated shutdown: flush outstanding work, propagate the
        shutdown through the control plane, stop threads
        (reference operations.cc:1699-1729)."""
        try:
            self.flush()
            if self.controller is not None:
                # One more negotiated tick so every rank sees the shutdown
                # response (reference :1881-1884, 1906).
                self.controller.request_shutdown()
                self.flush()
        finally:
            self._shutdown.set()
            self._tick.set()
            if self._cycle_thread.is_alive():
                self._cycle_thread.join(timeout=5)
            if self._stall_thread is not None and self._stall_thread.is_alive():
                self._stall_thread.join(timeout=5)
            if self.controller is not None:
                self.controller.close()

    # --------------------------------------------------------------- dispatch

    def _shard_map(self, fn, out_specs=P()):
        # check_vma=False: outputs of these dispatch programs
        # are replicated by construction (psum / all_gather semantics),
        # which the varying-manual-axes inference cannot always prove.
        return jax.jit(
            jax.shard_map(
                fn,
                mesh=self.mesh,
                in_specs=P(self._axis),
                out_specs=out_specs,
                check_vma=False,
            )
        )

    def _allreduce_group_fn(self, op: _ReduceOp, compression,
                            process_set=None) -> Any:
        """One jitted program: concat per-rank flats → ONE collective →
        split.  This is the Horovod fusion buffer, compiled
        (reference operations.cc:999-1053 memcpys become XLA layout ops)."""
        ps_key = process_set.ranks if process_set is not None else None
        key = ("ar", op.name, compression, ps_key)
        fn = self._dispatch_cache.get(key)
        if fn is None:

            def fused(xs):
                flats = [x.reshape(-1) for x in xs]
                buf = jnp.concatenate(flats) if len(flats) > 1 else flats[0]
                red = collective_ops.allreduce(
                    buf, op=op, axis_name=self._axis, compression=compression,
                    process_set=process_set,
                )
                outs, off = [], 0
                for x in xs:
                    n = int(x.size)
                    outs.append(lax.slice(red, (off,), (off + n,)))
                    off += n
                return tuple(outs)

            # Process-set results differ per rank (non-members keep their
            # input), so they come back rank-major instead of replicated.
            fn = self._shard_map(
                fused,
                out_specs=P(self._axis) if process_set is not None else P(),
            )
            self._dispatch_cache[key] = fn
        return fn

    def _dispatch_allreduce_group(self, group: list[_PendingOp]):
        """Dispatch one fused bucket; returns ``(last_output_or_None,
        bucket_bytes)`` — the output feeds the autotuner's completion
        probe, the per-rank payload bytes feed stats and the autotune
        sample (computed once here so the two meters cannot diverge)."""
        names = [p.name for p in group]
        nbytes = sum(_per_rank_nbytes(p.tensor) for p in group)
        # Snapshot: start_timeline() may attach a timeline while we're in
        # the try block, and emitting E events whose B never happened would
        # break the trace's B/E balance.
        tl = self.timeline
        if tl:
            for n in names:
                tl.start(n, "ALLREDUCE", {"fused_with": len(group) - 1})
                tl.start(n, timeline_mod.DISPATCH)
        try:
            ps = group[0].process_set
            fn = self._allreduce_group_fn(group[0].op, group[0].compression, ps)
            outs = fn(tuple(p.tensor.reshape(p.tensor.shape[0], -1) for p in group))
            if self._serialize_dispatch:
                jax.block_until_ready(outs)
            for p, out in zip(group, outs):
                if p.handle < 0:
                    continue  # joined-rank phantom: output discarded
                shape = p.tensor.shape if ps is not None else p.tensor.shape[1:]
                self.handles.mark_dispatched(p.handle, out.reshape(shape))
            self.stats["batches_dispatched"] += 1
            if len(group) > 1:
                self.stats["tensors_fused"] += len(group)
            self.stats["allreduce_bytes"] += nbytes
            metrics_mod.DEFAULT.counter("hvd.allreduce_bytes").inc(nbytes)
            return outs[-1], nbytes
        except Exception as e:
            for p in group:
                if p.handle >= 0:
                    self._mark_error(p.handle, e)
            return None, nbytes
        finally:
            if tl:
                for n, p in zip(names, group):
                    tl.end(n, timeline_mod.DISPATCH)
                    tl.end(n, "ALLREDUCE", _op_end_args(p))

    def _mark_single(self, p: _PendingOp, out) -> None:
        if self._serialize_dispatch:
            jax.block_until_ready(out)
        self.handles.mark_dispatched(p.handle, out)

    def _dispatch_single(self, p: _PendingOp) -> None:
        tl = self.timeline   # snapshot; see _dispatch_allreduce_group
        if tl:
            tl.start(p.name, p.kind.upper())
        try:
            if p.kind == "broadcast":
                ps = p.process_set
                ps_key = ps.ranks if ps is not None else None
                key = ("bc", int(p.root_rank), ps_key)
                fn = self._dispatch_cache.get(key)
                if fn is None:
                    root = int(p.root_rank)

                    def bc(x):
                        out = collective_ops.broadcast(
                            x[0], root, axis_name=self._axis, process_set=ps
                        )
                        # Rank-major output keeps the leading rank axis so
                        # the stacked global shape is [size, *shape].
                        return out[None] if ps is not None else out

                    # With a process set the output differs per rank
                    # (members get root's value, others keep their own), so
                    # it stays rank-major instead of collapsing to one copy.
                    fn = self._shard_map(
                        bc, out_specs=P(self._axis) if ps is not None else P()
                    )
                    self._dispatch_cache[key] = fn
                self._mark_single(p, fn(p.tensor))
            elif p.kind == "allgather":
                fn = self._dispatch_cache.get("ag")
                if fn is None:

                    def ag(x):
                        return lax.all_gather(x[0], self._axis, tiled=True)

                    fn = self._shard_map(ag)
                    self._dispatch_cache["ag"] = fn
                gathered = fn(p.tensor)  # [size * padded_d0, rest]
                if p.sizes is not None or p.process_set is not None:
                    # One slice loop covers both the ragged case (per-rank
                    # first dims) and the process-set case (member blocks
                    # only): a fixed first dim is just sizes == (pad,)*n.
                    pad = p.tensor.shape[1]
                    sizes = p.sizes or (pad,) * p.tensor.shape[0]
                    member_ranks = (
                        range(p.tensor.shape[0]) if p.process_set is None
                        else p.process_set.ranks
                    )
                    gathered = jnp.concatenate(
                        [
                            lax.slice_in_dim(
                                gathered, r * pad, r * pad + sizes[r], axis=0
                            )
                            for r in member_ranks
                        ],
                        axis=0,
                    )
                self._mark_single(p, gathered)
            elif p.kind == "alltoall":
                fn = self._dispatch_cache.get("a2a")
                if fn is None:

                    def a2a(x):
                        # Per-rank block [1, m, ...] → split row into n
                        # chunks, exchange, concat: rank r's output row is
                        # chunk r of every rank (Horovod ≥0.20 hvd.alltoall
                        # semantics, equal splits).
                        out = lax.all_to_all(
                            x[0], self._axis, split_axis=0, concat_axis=0,
                            tiled=True,
                        )
                        return out[None]

                    fn = self._shard_map(a2a, out_specs=P(self._axis))
                    self._dispatch_cache["a2a"] = fn
                self._mark_single(p, fn(p.tensor))
            elif p.kind == "reducescatter":
                key = ("rs", p.op.name)
                fn = self._dispatch_cache.get(key)
                if fn is None:
                    rs_op = p.op

                    def rs(x):
                        # Per-rank row [1, m, ...] → this rank's reduced
                        # shard [1, m/n, ...] (Horovod ≥0.21
                        # hvd.reducescatter semantics); the numerics live
                        # in collective_ops.reducescatter — the
                        # ncclReduceScatter leg of the reference's
                        # hierarchical allreduce, operations.cc:1135-1158.
                        return collective_ops.reducescatter(
                            x[0], op=rs_op, axis_name=self._axis
                        )[None]

                    fn = self._shard_map(rs, out_specs=P(self._axis))
                    self._dispatch_cache[key] = fn
                self._mark_single(p, fn(p.tensor))
            elif p.kind == "sparse":
                topk = p.topk
                key = ("sp", topk.ratio, topk.k, p.op.name)
                fn = self._dispatch_cache.get(key)
                if fn is None:
                    avg = p.op is Average

                    def sp(x):
                        return topk.sparse_allreduce(
                            x[0], average=avg, axis_name=self._axis
                        )

                    fn = self._shard_map(sp)
                    self._dispatch_cache[key] = fn
                self._mark_single(p, fn(p.tensor))
            else:  # pragma: no cover
                raise ValueError(f"unknown op kind {p.kind}")
            self.stats["batches_dispatched"] += 1
        except Exception as e:
            self._mark_error(p.handle, e)
        finally:
            if tl:
                tl.end(p.name, p.kind.upper(), _op_end_args(p))


# ---------------------------------------------------------------------------
# Module-level eager API (the reference's horovod/torch/mpi_ops.py surface).
# ---------------------------------------------------------------------------

_group_counter = itertools.count()
_name_counter = threading.Lock()
_name_seq = 0


def _auto_name(prefix: str) -> str:
    global _name_seq
    with _name_counter:
        _name_seq += 1
        return f"{prefix}.noname.{_name_seq}"


def _engine() -> EagerEngine:
    st = basics._require_init()
    with st.lock:
        if st.engine is None:
            if st.timeline is None:
                # A start_timeline() call before the first eager op may
                # already have installed one — never clobber it with the
                # (possibly unset) env config.
                st.timeline = timeline_mod.maybe_create(
                    st.config.timeline_file
                )
            st.engine = EagerEngine(st.mesh, st.config, st.timeline)
        return st.engine


def _as_rank_major(tensor, kind: str) -> jax.Array:
    t = jnp.asarray(tensor)
    n = basics.size()
    if t.ndim == 0 or t.shape[0] != n:
        raise ValueError(
            f"eager {kind} expects a rank-major array of shape [size={n}, ...]; "
            f"got shape {t.shape}.  Build one with horovod_tpu.from_per_rank / "
            "per_rank, or use a replicated value with hvd.broadcast semantics."
        )
    if not isinstance(t, jax.Array) or t.sharding != basics.rank_sharding():
        t = jax.device_put(t, basics.rank_sharding())
    return t


def allreduce_async(
    tensor,
    average: bool | None = None,
    name: str | None = None,
    *,
    op: _ReduceOp = Sum,
    compression=Compression.none,
    group_id: int | None = None,
    process_set=None,
    no_fuse: bool = False,
    join_identity: bool = True,
) -> int:
    """Async all-reduce of a rank-major tensor; returns a handle
    (reference horovod/torch/mpi_ops.py:156-176).  ``process_set``
    restricts the reduction to member ranks; non-member rows pass through
    unchanged (Horovod ≥0.22 API).  ``no_fuse=True`` keeps this op out of
    every fusion bucket (for callers whose local math must reproduce the
    wire's per-tensor form exactly, e.g. int8 error feedback)."""
    eng, pending = _prepare_allreduce(
        tensor, average, name, op=op, compression=compression,
        group_id=group_id, process_set=process_set, no_fuse=no_fuse,
        join_identity=join_identity,
    )
    eng.enqueue(pending)
    return pending.handle


def _prepare_allreduce(tensor, average, name, *, op, compression, group_id,
                       process_set, no_fuse, join_identity=True):
    """Build (engine, ready-to-enqueue _PendingOp) — shared by the per-op
    async path and the atomic grouped path."""
    if average is not None:
        op = Average if average else Sum
    eng = _engine()
    t = _as_rank_major(tensor, "allreduce")
    name = name or _auto_name("allreduce")
    h = eng.handles.allocate(name)
    return eng, _PendingOp(
        kind="allreduce",
        handle=h,
        tensor=t,
        name=name,
        op=op,
        compression=compression,
        group_id=group_id,
        process_set=process_set,
        no_fuse=no_fuse,
        join_identity=join_identity,
    )


def allreduce(tensor, average: bool | None = None, name: str | None = None,
              *, op: _ReduceOp = Sum, compression=Compression.none,
              process_set=None):
    """Blocking all-reduce (reference horovod/torch/mpi_ops.py:60-109).
    Returns the reduced tensor, fully replicated over the mesh.  With a
    ``process_set`` the result differs per rank (non-members keep their
    input), so it comes back rank-major ``[size, ...]``."""
    return synchronize(
        allreduce_async(tensor, average, name, op=op, compression=compression,
                        process_set=process_set)
    )


def sparse_allreduce_async(
    tensor, name: str | None = None, *, average: bool = False,
    ratio: float = 0.01, k: int | None = None,
) -> int:
    """Fork-parity top-k sparse allreduce (reference
    horovod/torch/__init__.py:46-83), compiled: top_k → all_gather →
    scatter-add in one program."""
    eng = _engine()
    t = _as_rank_major(tensor, "sparse_allreduce")
    name = name or _auto_name("sparse_allreduce")
    h = eng.handles.allocate(name)
    eng.enqueue(
        _PendingOp(
            kind="sparse",
            handle=h,
            tensor=t,
            name=name,
            op=Average if average else Sum,
            topk=TopKCompressor(ratio=ratio, k=k),
        )
    )
    return h


def sparse_allreduce(tensor, name: str | None = None, *, average: bool = False,
                     ratio: float = 0.01, k: int | None = None):
    return synchronize(
        sparse_allreduce_async(tensor, name, average=average, ratio=ratio, k=k)
    )


def allgather_async(tensors, name: str | None = None, *,
                    process_set=None, sizes=None) -> int:
    """Async allgather; ``tensors`` is rank-major or a list of per-rank
    tensors whose first dims may differ (reference allgather-with-unequal-
    first-dims, operations.cc:841-901 — size negotiation happens host-side
    here since the controller sees every rank's shape).

    ``sizes``: for RANK-MAJOR input ``[size, pad, ...]``, the per-rank
    true first dims (each ≤ pad) from
    :func:`negotiate_gather_sizes` — the engine then returns the ragged
    concatenation directly (one slicing implementation for the list,
    torch, and keras frontends).  The list form negotiates its own.

    Cost note: the ragged slice/concat are device ops whose compiled
    forms cache per (pad, sizes) composition, so a hot loop whose
    per-rank sizes VARY every step pays a small fresh compile each
    step.  That trade favors the actual ragged users — object/metric collectives, negotiated
    per call anyway; a per-step ragged hot loop should pad to a fixed
    shape instead (docs/tensor-fusion.md "Determinism and compile
    churn")."""
    eng = _engine()
    if isinstance(tensors, (list, tuple)):
        if sizes is not None:
            raise ValueError(
                "sizes= applies to rank-major input only (the per-rank "
                "list form derives sizes from the tensors themselves)"
            )
        n = basics.size()
        if len(tensors) != n:
            raise ValueError(f"expected {n} per-rank tensors, got {len(tensors)}")
        ts = [jnp.asarray(t) for t in tensors]
        rests = {t.shape[1:] for t in ts}
        if len(rests) > 1:
            raise ValueError(
                "allgather: per-rank tensors must agree on all dims except "
                f"dim 0; got trailing shapes {sorted(map(str, rests))}"
            )
        dtypes = {t.dtype for t in ts}
        if len(dtypes) > 1:
            raise ValueError(
                f"allgather: per-rank tensors must share a dtype; got {dtypes}"
            )
        sizes = tuple(int(t.shape[0]) for t in ts)
        pad = max(sizes)
        padded = [
            jnp.pad(t, [(0, pad - t.shape[0])] + [(0, 0)] * (t.ndim - 1))
            for t in ts
        ]
        t = jax.device_put(jnp.stack(padded), basics.rank_sharding())
        if len(set(sizes)) == 1:
            sizes = None
    else:
        t = _as_rank_major(tensors, "allgather")
        if sizes is not None:
            sizes = tuple(int(s) for s in sizes)
            if t.ndim < 2:
                raise ValueError(
                    "ragged allgather needs rank-major [size, pad, ...] "
                    f"input; got shape {t.shape}"
                )
            if len(sizes) != t.shape[0]:
                raise ValueError(
                    f"sizes must have one entry per rank ({t.shape[0]}); "
                    f"got {len(sizes)}"
                )
            pad = int(t.shape[1])
            if any(not 0 <= s <= pad for s in sizes):
                raise ValueError(
                    f"sizes must lie in [0, padded dim {pad}]; got {sizes}"
                )
            if len(set(sizes)) == 1 and sizes[0] == pad:
                sizes = None    # not actually ragged: plain gather
    if process_set is not None and process_set.ranks[-1] >= basics.size():
        raise ValueError(
            f"process set {process_set.ranks} exceeds world size "
            f"{basics.size()}"
        )
    name = name or _auto_name("allgather")
    h = eng.handles.allocate(name)
    eng.enqueue(
        _PendingOp(
            kind="allgather",
            handle=h,
            tensor=t,
            name=name,
            sizes=sizes,
            process_set=process_set,
        )
    )
    return h


def allgather(tensors, name: str | None = None, *, process_set=None,
              sizes=None):
    """Blocking allgather.  With a ``process_set``, the result is the
    concatenation of MEMBER ranks' slices only (set order)."""
    return synchronize(allgather_async(tensors, name,
                                       process_set=process_set,
                                       sizes=sizes))


MAX_GATHER_NDIM = 8


def negotiate_gather_sizes(shape: Sequence[int], dtype_str: str,
                           name: str | None = None) -> list[int]:
    """Exchange (ndim, dtype, shape) across ranks THROUGH the engine — not
    an out-of-band host collective, so it serializes with every queued
    engine op (no cross-host op-order divergence) — and return the
    per-rank dim-0 sizes for a ragged allgather (the reference's
    unequal-first-dim negotiation, operations.cc:841-901).

    Frontend-agnostic: callers pass the local shape and a dtype STRING
    (consistent within a frontend: every rank runs the same one).  Raises
    the same clean errors for ndim/dtype/trailing-dim mismatch on every
    rank.  Used by the torch and keras frontends."""
    return negotiate_gather_sizes_many([shape], [dtype_str], name)[0]


def negotiate_gather_sizes_many(
    shapes: Sequence[Sequence[int]], dtype_strs: Sequence[str],
    name: str | None = None,
) -> list[list[int]]:
    """Batched :func:`negotiate_gather_sizes`: K members' digests ride ONE
    engine allgather (one control-plane round-trip however many tensors a
    grouped call carries), validated member-by-member with the same
    symmetric errors.

    The digest is prefixed by a member-count header that goes over its
    OWN fixed-width exchange first: the wide digest's wire width is a
    function of K, so ranks disagreeing on K (mismatched grouped-call
    lists) would hit an opaque engine shape error — or deadlock — before
    any validation could run.  The [1] header cannot mismatch in shape,
    so a K disagreement raises the same "group member count differs"
    error on every rank with both exchanges fully drained (no engine
    desync for subsequent ops).  Cost: one extra tiny control round-trip
    per grouped negotiation (skipped single-process)."""
    import zlib

    k = len(shapes)
    n_header = basics.size()
    if n_header > 1:
        hdr = np.asarray([[k]], np.int32)
        hg = jax.make_array_from_process_local_data(
            basics.rank_sharding(), hdr)
        hh = allgather_async(
            hg, name=None if name is None else f"{name}.shapes.k")
        ks = np.asarray(jax.device_get(synchronize(hh))).reshape(n_header)
        for r in range(n_header):
            if int(ks[r]) != k:
                raise ValueError(
                    f"allgather: group member count differs on rank {r}: "
                    f"rank {r} negotiates {int(ks[r])} member(s) vs "
                    f"local {k} — every rank must pass the same-length "
                    f"tensor list to a grouped allgather")
    digest = np.zeros((k, 2 + MAX_GATHER_NDIM), np.int32)
    crcs = []
    for i, (shape, dtype_str) in enumerate(zip(shapes, dtype_strs)):
        ndim = len(shape)
        if ndim < 1:
            raise ValueError("allgather expects a tensor with >= 1 dim")
        if ndim > MAX_GATHER_NDIM:
            raise ValueError(
                f"allgather supports up to {MAX_GATHER_NDIM} dims, "
                f"got {ndim}"
            )
        # int32 end-to-end: jax's default x64-truncation would silently
        # fold int64 digests and break the cross-rank comparison.  Dims
        # that don't fit int32 would wrap silently, so reject up front.
        if any(d > 0x7FFFFFFF for d in shape):
            raise ValueError(
                "allgather: tensor dims must fit in int32 for the "
                f"cross-rank shape negotiation; got shape {tuple(shape)}"
            )
        digest[i, 0] = ndim
        # crc32, not hash(): Python's str hash is per-process randomized.
        crc = zlib.crc32(dtype_str.encode()) & 0x7FFFFFFF
        crcs.append(crc)
        digest[i, 1] = crc
        digest[i, 2:2 + ndim] = list(shape)
    n = basics.size()
    flat = digest.reshape(1, -1)
    if n == 1:
        g = jax.device_put(flat, basics.rank_sharding())
    else:
        g = jax.make_array_from_process_local_data(
            basics.rank_sharding(), flat
        )
    h = allgather_async(g, name=None if name is None else f"{name}.shapes")
    all_digest = np.asarray(
        jax.device_get(synchronize(h))
    ).reshape(n, k, 2 + MAX_GATHER_NDIM)
    out: list[list[int]] = []
    for i, shape in enumerate(shapes):
        ndim = len(shape)
        member = f" (group member {i})" if k > 1 else ""
        for r in range(n):
            if (all_digest[r, i, 0] != ndim
                    or all_digest[r, i, 1] != crcs[i]):
                raise ValueError(
                    "allgather: per-rank tensors must share ndim and "
                    f"dtype; rank {r} disagrees{member} "
                    f"({all_digest[r, i, :2].tolist()} vs "
                    f"{[ndim, crcs[i]]})"
                )
            if list(all_digest[r, i, 3:2 + ndim]) != list(shape[1:]):
                raise ValueError(
                    "allgather: per-rank tensors must agree on all dims "
                    f"except dim 0; rank {r} has trailing{member} "
                    f"{all_digest[r, i, 3:2 + ndim].tolist()} vs local "
                    f"{list(shape[1:])}"
                )
        out.append([int(all_digest[r, i, 2]) for r in range(n)])
    return out


def negotiate_alltoall_splits(splits: Sequence[int], dim0: int,
                              name: str | None = None) -> np.ndarray:
    """Exchange per-rank alltoall split rows THROUGH the engine (so the
    negotiation serializes with every queued op, like
    :func:`negotiate_gather_sizes`) and return the full [n, n] matrix —
    ``S[r, j]`` = rows rank r sends to rank j.  Every rank derives the
    same padding (``S.max()``) and its own receive column from it.

    Validation that depends on a rank's OWN values (row length,
    negativity, sum == its dim 0) happens AFTER the exchange, against
    the gathered matrix, so a bad rank raises the same error on every
    rank instead of deadlocking the others in the negotiation (the
    :func:`negotiate_gather_sizes` discipline)."""
    n = basics.size()
    row = np.asarray(list(splits), np.int64)
    if row.shape != (n,):
        # A wrong-LENGTH row can't be exchanged at the fixed wire shape
        # at all — this is a local programming error, same on any rank
        # that makes it.
        raise ValueError(
            f"alltoall splits must have one entry per rank "
            f"({n}), got shape {row.shape}")
    rec = np.concatenate([
        np.clip(row, -0x80000000, 0x7FFFFFFF),
        [min(dim0, 0x7FFFFFFF)],
    ]).astype(np.int32)[None]
    if n == 1:
        g = jax.device_put(rec, basics.rank_sharding())
    else:
        g = jax.make_array_from_process_local_data(
            basics.rank_sharding(), rec)
    h = allgather_async(g, name=None if name is None else f"{name}.splits")
    allrec = np.asarray(
        jax.device_get(synchronize(h))).reshape(n, n + 1)
    mat, dims = allrec[:, :n].astype(np.int64), allrec[:, n]
    for r in range(n):
        if (mat[r] < 0).any():
            raise ValueError(
                f"alltoall splits must be non-negative; rank {r} sent "
                f"{mat[r].tolist()}")
        if mat[r].sum() != dims[r]:
            raise ValueError(
                f"alltoall splits sum {int(mat[r].sum())} != tensor "
                f"dim 0 {int(dims[r])} on rank {r}")
    return mat.astype(np.int32)


def alltoall_async(tensor, name: str | None = None) -> int:
    """Async all-to-all (the hvd.alltoall API Horovod grew in 0.20, equal
    splits): rank r's row of the rank-major input is split into ``size``
    chunks; its output row is chunk r from every rank.  The result is
    RANK-MAJOR ``[size, m, ...]`` — per-rank values differ by design."""
    eng = _engine()
    t = _as_rank_major(tensor, "alltoall")
    n = basics.size()
    if t.ndim < 2 or t.shape[1] % n != 0:
        # Report the PER-RANK shape: callers (esp. the torch surface)
        # passed a per-rank tensor and never saw the rank-major wrapper.
        raise ValueError(
            "alltoall expects each rank's dim 0 to be divisible by "
            f"size={n}; got per-rank shape {t.shape[1:]}"
        )
    name = name or _auto_name("alltoall")
    h = eng.handles.allocate(name)
    eng.enqueue(
        _PendingOp(kind="alltoall", handle=h, tensor=t, name=name)
    )
    return h


def alltoall(tensor, name: str | None = None):
    return synchronize(alltoall_async(tensor, name))


def barrier(name: str | None = None) -> None:
    """Process-level barrier (the hvd.barrier API Horovod grew in 0.23):
    returns only after every rank has entered it.  Implemented as a
    1-element Sum allreduce drained through the engine, so it also
    serializes with every eager op enqueued before it — reaching the
    barrier means every prior collective on every rank has been matched
    and dispatched."""
    n = basics.size()
    # this process contributes one row per mesh device it owns: [1, 1]
    # in the one-process-per-chip world, [n, 1] single-controller
    mine = sum(1 for d in basics.mesh().devices.flat
               if d.process_index == jax.process_index())
    rows = np.ones((mine, 1), np.float32)
    if mine == n:
        g = jax.device_put(rows, basics.rank_sharding())
    else:
        g = jax.make_array_from_process_local_data(
            basics.rank_sharding(), rows)
    out = synchronize(allreduce_async(
        g, op=Sum, name=name or _auto_name("barrier"),
        # a rendezvous must not be satisfiable by a joined rank's zero
        # phantom (hvd.join would quietly turn the barrier into n-1
        # arrivals); OP_OTHER classification makes the controller error
        # it cleanly instead.  no_fuse keeps its dispatch self-contained.
        no_fuse=True, join_identity=False))
    total = float(np.asarray(jax.device_get(out))[0])
    if total != float(n):          # engine invariant, not user error
        raise HorovodInternalError(
            f"barrier saw contribution sum {total} != world size {n}")


def reducescatter_async(tensor, name: str | None = None, *,
                        op: _ReduceOp = Average) -> int:
    """Async reduce-scatter (the hvd.reducescatter API Horovod grew in
    0.21): the rank-major input is reduced with ``op`` (Sum/Average —
    default Average, matching Horovod's signature) and each rank keeps
    shard r of the result along dim 0.  The result is RANK-MAJOR
    ``[size, m/size, ...]`` — per-rank shards differ by design.  Dim 0 of
    each rank's tensor must be divisible by ``size`` (equal shards, like
    ``alltoall``)."""
    eng = _engine()
    t = _as_rank_major(tensor, "reducescatter")
    n = basics.size()
    if op not in (Sum, Average):
        raise ValueError(f"reducescatter supports Sum/Average, not {op}")
    if t.ndim < 2 or t.shape[1] % n != 0:
        raise ValueError(
            "reducescatter expects each rank's dim 0 to be divisible by "
            f"size={n}; got per-rank shape {t.shape[1:]}"
        )
    name = name or _auto_name("reducescatter")
    h = eng.handles.allocate(name)
    eng.enqueue(
        _PendingOp(kind="reducescatter", handle=h, tensor=t, name=name,
                   op=op)
    )
    return h


def reducescatter(tensor, name: str | None = None, *,
                  op: _ReduceOp = Average):
    return synchronize(reducescatter_async(tensor, name, op=op))


def join() -> int:
    """``hvd.join()`` (Horovod ≥0.21): this rank is out of data — block
    until every rank joins, contributing zeros to the gang's remaining
    plain Sum/Average allreduces meanwhile.  Returns the last rank to
    join.  See ``EagerEngine.join`` for the mechanics."""
    return _engine().join()


def broadcast_async(tensor, root_rank: int, name: str | None = None, *,
                    process_set=None) -> int:
    """Async broadcast of rank ``root_rank``'s slice to all
    (reference horovod/torch/mpi_ops.py:318-405).  With a ``process_set``
    the output is rank-major: members carry the root's value, non-members
    their own input."""
    eng = _engine()
    t = _as_rank_major(tensor, "broadcast")
    if not 0 <= root_rank < basics.size():
        raise ValueError(f"root_rank {root_rank} outside [0, {basics.size()})")
    if process_set is not None and not process_set.included(root_rank):
        raise ValueError(
            f"broadcast root_rank {root_rank} is not in {process_set!r}"
        )
    name = name or _auto_name("broadcast")
    h = eng.handles.allocate(name)
    eng.enqueue(
        _PendingOp(
            kind="broadcast",
            handle=h,
            tensor=t,
            name=name,
            root_rank=root_rank,
            process_set=process_set,
        )
    )
    return h


def broadcast(tensor, root_rank: int, name: str | None = None, *,
              process_set=None):
    return synchronize(broadcast_async(tensor, root_rank, name,
                                       process_set=process_set))


def poll(handle: int) -> bool:
    """Non-blocking completion probe (reference torch/mpi_ops.py:406-419)."""
    eng = _engine()
    eng.flush()
    return eng.handles.poll(handle)


def engine_stats() -> dict:
    """Snapshot of the engine's observability counters.

    Keys: ``ops_enqueued``, ``batches_dispatched`` (one compiled collective
    launch each), ``tensors_fused`` (ops that rode a multi-tensor fused
    bucket — the Tensor Fusion win meter), ``allreduce_bytes`` (per-rank
    payload), ``errors`` (failed handles, dispatch or negotiation),
    ``stall_warnings`` (stall-checker firings).
    Values are monotonic since ``init()``; before the engine's first eager
    op this reports ``{}``.  A snapshot, not a barrier: in-flight ops may
    not be counted yet.  ``recent_negotiate_s`` is the last-N negotiate
    waits (enqueue → dispatch, seconds) — the straggler detector's
    rolling-window feed.
    """
    eng = basics._state.engine
    if eng is None:
        return {}
    out: dict = dict(eng.stats)
    out["recent_negotiate_s"] = list(eng.recent_negotiate_s)
    return out


def take_handle_post(handle: int):
    """Detach the handle's post payload; None if absent/released."""
    return _engine().handles.take_post(handle)


def update_handle_post(handle: int, **items) -> None:
    """Merge keys into a dict post payload, atomically under the manager
    lock."""
    _engine().handles.update_post(handle, items)


def release(handle: int) -> None:
    """Drop a handle without waiting — frees its manager entry (and any
    post payload).  No-op if already released.  For error-path cleanup
    where blocking on the result is pointless."""
    _engine().handles.release(handle)


def synchronize(handle: int):
    """Block until the op completes; returns its output
    (reference torch/mpi_ops.py:422-438)."""
    eng = _engine()
    if eng.timeline is not None:
        tname = eng.handles.name(handle)
        if tname is not None:
            # Flush BEFORE opening the span so this tensor's own
            # NEGOTIATE-end / DISPATCH / op events precede it; the span is
            # an async event (matched by handle id, not the B/E stack), so
            # a concurrent cycle-thread dispatch cannot mis-nest it either.
            eng.flush()
            eng.timeline.async_start(
                tname, timeline_mod.WAIT_FOR_OUTPUT, handle
            )
            try:
                return eng.handles.wait(handle, lambda: None)
            finally:
                eng.timeline.async_end(
                    tname, timeline_mod.WAIT_FOR_OUTPUT, handle
                )
    return eng.handles.wait(handle, eng.flush)


def grouped_allreduce_eager(
    tensors: Sequence, average: bool | None = None, names: list[str] | None = None,
    *, op: _ReduceOp = Sum, compression=Compression.none,
) -> list:
    """Enqueue many allreduces in one call; the engine fuses them into
    buckets (the reference achieves this implicitly when many grads arrive in
    one cycle — test/test_torch.py:175-224 ``..._async_fused``).

    The call delimits a fusion group: members enter the engine queue
    atomically and, under Python-planned fusion (single host or
    controller-less multi-host), fuse only with each other
    (``EagerEngine._fuse_key``) — bucket composition and the compiled
    dispatch-program signatures are then deterministic for a given call
    shape, across hosts AND across repeated calls (no cycle-tick-dependent
    compile churn).  The native-controller path instead merges by
    negotiated fusability (globally consistent, timing-dependent —
    docs/tensor-fusion.md "Determinism and compile churn")."""
    if names is not None and len(names) != len(tensors):
        raise ValueError(
            f"names has {len(names)} entries for {len(tensors)} tensors"
        )
    gid = next(_group_counter)
    eng = None
    pendings = []
    for i, t in enumerate(tensors):
        eng, p = _prepare_allreduce(
            t, average, (names[i] if names else None),
            op=op, compression=compression, group_id=gid,
            process_set=None, no_fuse=False,
        )
        pendings.append(p)
    if eng is not None:
        eng.enqueue_many(pendings)
    return [synchronize(p.handle) for p in pendings]
