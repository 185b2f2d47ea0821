"""Horovod Timeline — Chrome-tracing profiler for the eager engine.

Parity with the reference timeline (reference: horovod/common/timeline.h/.cc,
docs/timeline.md): a ``chrome://tracing`` JSON file written when
``HOROVOD_TIMELINE=<path>`` is set, in which every named tensor is modeled as
its own "process" (pid) whose track shows the phases of its collective:

  NEGOTIATE_ALLREDUCE / NEGOTIATE_ALLGATHER / NEGOTIATE_BROADCAST
      reference timeline.cc:98-132 — time between enqueue and the engine
      deciding to run the op (here: time in the fusion queue until the cycle
      flush picks the tensor up).
  NEGOTIATE_TICK_r<k> / NEGOTIATE_TICK_ALL
      per-rank readiness instants inside the NEGOTIATE span (reference
      timeline.cc:98-132; single-controller jobs see all ranks at once).
  ALLREDUCE / ALLGATHER / BROADCAST  top-level op span (``fused_with: N``
      annotates tensor-fusion grouping)
  DISPATCH / WAIT_FOR_OUTPUT
      TPU-native activity vocabulary replacing the reference's
      MEMCPY_IN_FUSION_BUFFER / NCCL_ALLREDUCE etc. (operations.h:29-46):
      XLA owns the memcpys and the wire, so what the host can observe is
      dispatch (trace/compile/launch) and the wait on the device future
      in ``synchronize``.

Device-side detail (per-HLO timing, ICI traffic) belongs to the JAX/XLA
profiler; :func:`trace_annotation` bridges engine phases into it so both
timelines line up in TensorBoard.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import TextIO

import jax

NEGOTIATE = "NEGOTIATE"
DISPATCH = "DISPATCH"
WAIT_FOR_OUTPUT = "WAIT_FOR_OUTPUT"


class Timeline:
    """Thread-safe Chrome-trace writer (reference timeline.cc:24-188).

    Events are buffered and flushed at most every second (reference
    timeline.cc flush cadence) or on close.
    """

    def __init__(self, path: str, mark_cycles: bool = False) -> None:
        self._lock = threading.Lock()
        self._path = path
        self.mark_cycles = mark_cycles
        self._file: TextIO = open(path, "w")
        self._file.write("[\n")
        self._start = time.perf_counter()
        self._pids: dict[str, int] = {}
        self._next_pid = 1
        self._buffer: list[str] = []
        self._last_flush = time.monotonic()
        self._closed = False

    def _ts_us(self) -> float:
        return (time.perf_counter() - self._start) * 1e6

    def _pid(self, tensor_name: str) -> int:
        pid = self._pids.get(tensor_name)
        if pid is None:
            pid = self._next_pid
            self._next_pid += 1
            self._pids[tensor_name] = pid
            # Tensor-as-process metadata event (reference timeline.cc:51-67).
            self._emit(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": pid,
                    "args": {"name": tensor_name},
                }
            )
            self._emit(
                {"name": "process_sort_index", "ph": "M", "pid": pid,
                 "args": {"sort_index": pid}}
            )
        return pid

    def _emit(self, event: dict) -> None:
        self._buffer.append(json.dumps(event))
        now = time.monotonic()
        if now - self._last_flush > 1.0:
            self._flush_locked()
            self._last_flush = now

    def _flush_locked(self) -> None:
        if self._buffer:
            self._file.write(",\n".join(self._buffer) + ",\n")
            self._buffer.clear()
            self._file.flush()

    def start(self, tensor_name: str, activity: str, args: dict | None = None) -> None:
        with self._lock:
            if self._closed:
                return
            self._emit(
                {"name": activity, "ph": "B", "ts": self._ts_us(),
                 "pid": self._pid(tensor_name), "tid": 0,
                 **({"args": args} if args else {})}
            )

    def end(self, tensor_name: str, activity: str, args: dict | None = None) -> None:
        with self._lock:
            if self._closed:
                return
            self._emit(
                {"name": activity, "ph": "E", "ts": self._ts_us(),
                 "pid": self._pid(tensor_name), "tid": 0,
                 **({"args": args} if args else {})}
            )

    def instant(self, tensor_name: str, activity: str) -> None:
        """Negotiation-tick / scheduler-event instant (reference
        timeline.cc:118-126).  Emitted as a true Chrome instant event —
        ``ph: "i"`` with thread scope — not the zero-width complete
        event (``ph: "X", dur: 0``) earlier versions wrote, which
        chrome://tracing renders as an invisible sliver instead of the
        instant marker."""
        with self._lock:
            if self._closed:
                return
            self._emit(
                {"name": activity, "ph": "i", "ts": self._ts_us(),
                 "pid": self._pid(tensor_name), "tid": 0, "s": "t"}
            )

    def counter(self, tensor_name: str, activity: str,
                values: dict) -> None:
        """Chrome counter event (ph 'C'): a stacked time series on the
        track — the serving scheduler emits queue depth / slot occupancy
        / free-block counts (``SCHED``), cumulative lifecycle totals
        (``LIFECYCLE``: preemptions / timeouts / cancellations /
        rejections / retries / failures) and, with the prefix cache on,
        cumulative reuse totals (``PREFIX``: hits / blocks_reused /
        tokens_skipped / evictions) per step through this, and
        speculative decoding its per-round acceptance counts.
        ``values`` maps series name → number."""
        with self._lock:
            if self._closed:
                return
            self._emit(
                {"name": activity, "ph": "C", "ts": self._ts_us(),
                 "pid": self._pid(tensor_name), "args": values}
            )

    def async_start(self, tensor_name: str, activity: str, aid: int) -> None:
        """Begin an *async* span (Chrome ph 'b'): unlike B/E duration events
        these are matched by id, not the per-(pid,tid) stack, so spans that
        overlap other activities on the same track cannot mis-nest."""
        with self._lock:
            if self._closed:
                return
            self._emit(
                {"name": activity, "ph": "b", "cat": activity,
                 "id": aid, "ts": self._ts_us(),
                 "pid": self._pid(tensor_name), "tid": 0}
            )

    def async_end(self, tensor_name: str, activity: str, aid: int) -> None:
        with self._lock:
            if self._closed:
                return
            self._emit(
                {"name": activity, "ph": "e", "cat": activity,
                 "id": aid, "ts": self._ts_us(),
                 "pid": self._pid(tensor_name), "tid": 0}
            )

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._flush_locked()
            # Chrome tracing tolerates a trailing comma with a closing ']'
            # written on a fresh line; emit a terminator event for strictness.
            self._file.write(json.dumps({"name": "done", "ph": "i", "ts": self._ts_us(), "pid": 0, "s": "g"}))
            self._file.write("\n]\n")
            self._file.close()


def trace_annotation(name: str):
    """A host span on the JAX/XLA profiler's trace, the clock the device's
    operations are on (``jax.profiler.TraceAnnotation``; costs under a
    microsecond and records nothing while no trace is being taken).

    The reference points users at chrome://tracing only; on TPU the XLA
    profiler is the richer source.  Callers, all host code and all with
    constant names: the phases of ``ServeEngine.step``
    (:class:`horovod_tpu.profiler.PhaseSpans`, ``serve.step[.<phase>]``),
    the sections of ``LocalReplica``'s pump loop (``replica.pump.*``) and
    ``RouterServer.route`` (``router.*``).

    A span is the record on the trace's clock and nothing else: it names
    the device's idle gaps and keeps no duration outside a profiler
    session.  What the phases of a step cost is kept by the profiler's
    own clock reads at the same boundaries, one row a step on every
    engine (:class:`horovod_tpu.profiler.StepLog`).
    """
    return jax.profiler.TraceAnnotation(name)


def maybe_create(path: str | None,
                 mark_cycles: bool = False) -> Timeline | None:
    """Create a timeline if configured.  Rank-0-only in multi-host jobs
    (reference operations.cc:1614-1618 gates on is_coordinator) —
    UNLESS ``path`` contains a ``{rank}`` template, in which case EVERY
    rank writes its own file (``trace_{rank}.json`` →
    ``trace_0.json`` ...), the per-rank inputs
    ``tools/timeline_summary.py --merge`` stitches into one fleet
    trace."""
    if not path:
        return None
    if "{rank}" in path:
        path = path.replace("{rank}", str(jax.process_index()))
    elif jax.process_index() != 0:
        return None
    dirname = os.path.dirname(path)
    if dirname:
        os.makedirs(dirname, exist_ok=True)
    return Timeline(path, mark_cycles=mark_cycles)


def start_timeline(path: str, mark_cycles: bool = False,
                   profiler_dir: str | None = None) -> None:
    """Start recording a timeline mid-run — the ``hvd.start_timeline``
    API the Horovod project added in 0.20 (the reference generation could
    only enable it via env var at init).

    ``mark_cycles=True`` adds an instant event per engine cycle tick, the
    same knob as upstream.  Rank-0 only in multi-host jobs (no-op
    elsewhere); raises if a timeline is already active.

    ``profiler_dir`` additionally captures a ``jax.profiler.trace`` for
    the same window (SURVEY §5's TPU mapping of timeline.cc:24-188): the
    engine's NEGOTIATE/DISPATCH phases land in the Chrome trace while the
    device-side detail (per-HLO timing, ICI traffic) lands in the XLA
    profile, and the ``trace_annotation`` bridge names line up across the
    two in TensorBoard.  Stopped by ``stop_timeline``; rank-0 only, like
    the timeline itself.
    """
    from horovod_tpu import basics

    st = basics._require_init()
    with st.lock:
        if st.timeline is not None:
            raise ValueError(
                "a timeline is already active; call stop_timeline() first"
            )
        tl = maybe_create(path, mark_cycles=mark_cycles)
        if tl is not None and profiler_dir:
            # Before st.timeline is assigned: a start_trace failure (e.g. a
            # user-started profiler session already active) must not leave
            # a half-open timeline that start_timeline retries reject.
            try:
                jax.profiler.start_trace(profiler_dir)
            except Exception:
                tl.close()
                raise
            st.profiler_active = True
        st.timeline = tl
        if st.engine is not None and tl is not None:
            st.engine.timeline = tl
            if st.engine.controller is not None:
                st.engine.controller.enable_tick_trace()


def stop_timeline() -> None:
    """Stop the active timeline and finalize its file (``hvd.stop_timeline``
    parity).  Idempotent when none is active."""
    from horovod_tpu import basics

    st = basics._require_init()
    with st.lock:
        tl, st.timeline = st.timeline, None
        profiling, st.profiler_active = st.profiler_active, False
        if st.engine is not None:
            st.engine.timeline = None
            if st.engine.controller is not None and tl is not None:
                # The drain site is gated on an active timeline; without
                # this the rank-0 tick buffer would grow with no consumer.
                st.engine.controller.enable_tick_trace(False)
    if profiling:
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # pragma: no cover - depends on jax state
            # A profiler failure (xplane write error, trace already
            # stopped by user code) must not lose the Chrome trace below.
            import warnings

            warnings.warn(
                f"jax profiler stop failed ({type(e).__name__}: {e}); "
                "the timeline file is still finalized",
                RuntimeWarning,
                stacklevel=2,
            )
    if tl is not None:
        tl.close()
