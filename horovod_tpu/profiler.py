"""Phase spans and the step log of the serving engine.

Continuous-batching schedulers hide host-side stalls inside "decode
time": admission bookkeeping, chunked-prefill dispatch, the blocking
token readback, and per-request postprocessing all happen between two
device ticks, and a whole-step latency histogram cannot say which one
got slower.  vLLM and SGLang both ship per-phase step timing for
exactly this reason.  Here one set of call sites in
:meth:`ServeEngine.step <horovod_tpu.serving_scheduler.ServeEngine.step>`
(``begin`` → ``mark`` / ``sub`` / ``add`` / ``counts`` → ``end``) keeps
two records of every step, on every engine:

* **Spans**, the record on the device trace's clock.  Every phase is a
  ``jax.profiler.TraceAnnotation`` (through
  :func:`horovod_tpu.timeline.trace_annotation`): ``serve.step`` around
  the step, ``serve.step.<phase>`` tiling it, ``serve.step.<sub-phase>``
  nested inside their parent.  They land on the host plane of jax's
  profiler trace, beside the device's ``XLA Ops``, so an idle gap of the
  device can be pinned on the phase the host was in.  While no trace is
  being taken an annotation costs under a microsecond and records
  nothing.
* **One row a step**, the record of durations and counts, on
  ``time.monotonic`` (the clock of :class:`horovod_tpu.metrics.Trace`
  stamps): :data:`ROW_FIELDS` in a bounded :class:`StepLog`, a clock
  read a boundary and one row write a step.  ``report()`` summarises the
  last ``HVD_TPU_PROFILE_WINDOW`` rows (``metrics_snapshot()["profile"]``
  and the monitor's ``/profile``), and :func:`step_logs` hands the logs
  of the last few engines of this process, with their registries, to a
  reader that outlives them.

The engine holds one of two classes (``profile`` / ``HVD_TPU_PROFILE``
decides which), and they differ by histograms and events, not by
whether anything is kept:

* :class:`PhaseSpans` — the default: the spans and the rows.
* :class:`TickProfiler` — the same, and every row also feeds the
  ``serve.phase.*_s`` histograms of the engine's
  :class:`~horovod_tpu.metrics.MetricsRegistry` and one
  ``serve.profile_tick`` structured event when the registry has a JSONL
  sink (replayed by ``tools/profile_report.py``).

Design rules (pinned by ``tests/test_profiler.py``):

* **One vocabulary.**  :data:`TILING` (:data:`PHASES`, :data:`SPEC_PHASES`
  and ``unmask``) and :data:`SUB_PHASES` name the phases in the row, in ``/profile``, in the
  ``serve.phase.*_s`` histograms and (behind ``serve.step.``) in the
  trace, letter for letter.
* **Host code only.**  Nothing here touches a traced value or sits
  inside a jitted function, so ``compile_cache_sizes()`` is unchanged.
* **One clock.**  Every boundary reads ``time.monotonic``.
* **Phases tile the step.**  ``begin(step)`` opens the step in its
  first phase and ``mark(phase)`` is the boundary at which ``phase``
  starts and the phase before it ends, so a row's top-level phases sum
  to its ``ended - began`` by construction (in the trace: up to the few
  statements between ``step()``'s entry and ``begin``).
  :data:`SUB_PHASES` are intervals *inside* their parent (``sub()``,
  and for the cost-model pair ``add()``) and are excluded from the
  coverage arithmetic.
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time

import numpy as np

from horovod_tpu import metrics as metrics_mod
from horovod_tpu.timeline import trace_annotation

#: Top-level phases in ``step()`` order.  They TILE the tick — each is
#: measured boundary-to-boundary, so their sum equals the tick wall time.
#: Every engine produces exactly these (the three of the decode tick
#: only on steps in which a row decodes); schema consumers (replay)
#: may rely on their presence in ``report()``.
PHASES = ("expire", "admit", "decode_dispatch", "device_sync",
          "sample_postprocess", "bookkeeping")

#: Extra top-level phases that fire only on spec-enabled engines
#: (``draft`` before dispatch, ``verify`` in place of part of
#: ``sample_postprocess``).  They tile the tick exactly like
#: :data:`PHASES` but are surfaced in ``report()`` only once observed,
#: so non-spec engines keep the PR-7 report schema byte-for-byte.
SPEC_PHASES = ("draft", "verify")

#: Nested sub-phases (explicit intervals inside a parent phase).  They
#: overlap their parent, so coverage math skips them.  The ``admit``
#: pair are spans (``sub()``); the ``device_sync`` pair is the
#: device-telemetry split of the readback wait: cost-model-predicted
#: device compute vs host stall (only where the engine runs with
#: ``device_telemetry``, 0 elsewhere) — an estimate, so an ``add()`` of
#: seconds into the row and never an interval on the trace.
SUB_PHASES = ("admit.cache_acquire", "admit.prefill_dispatch",
              "device_sync.compute_est", "device_sync.host_stall")

#: The tiling phases of every kind in ``step()`` order: a row's
#: durations of these sum to its ``ended - began``.  ``unmask`` is the
#: extra phase of an engine whose model decodes a block a row (generation
#: by diffusion over blocks): the dispatch of the unmask program in front
#: of the block tick.  What is not of :data:`PHASES` joins ``report()``
#: once observed.
TILING = ("expire", "admit", "draft", "unmask", "decode_dispatch",
          "device_sync", "verify", "sample_postprocess", "bookkeeping")
assert set(TILING) == set(PHASES + SPEC_PHASES + ("unmask",))

#: What ``_step`` counted (``counts()``): chunk programs dispatched, the
#: rows they carried (a program prefills a window of each of its rows), the
#: rows of the dispatched tick (0 with none), tokens handed to results
#: and rows whose first token this was.
COUNTS = ("chunks", "chunk_rows", "tick_rows", "tokens", "first_tokens")

#: Counters of the engine's registry that a row carries as they stood at
#: the step's end: the model's own, reckoned from the programs each step
#: dispatched (0 throughout where the engine's model keeps none).  They
#: are cumulative, so a reader takes their change over the rows it
#: picked and not over the engine's life, warm-up included.
CARRIED = ("attn.blocks_visited", "attn.blocks_live",
           "dsa.mask_queries", "dsa.queries")

#: One row of a :class:`StepLog`: the step's index, when it began and
#: ended (``time.monotonic`` seconds), each phase's and sub-phase's
#: seconds (0 where the step had none), the counts and the carried
#: counters.  A step's tokens are out at the start of its
#: ``bookkeeping`` phase: ``ended - bookkeeping``.
ROW_FIELDS = (("step", "began", "ended") + TILING + SUB_PHASES + COUNTS
              + CARRIED)
_COL = {name: i for i, name in enumerate(ROW_FIELDS)}
_BEGAN, _ENDED = _COL["began"], _COL["ended"]

#: The span around one ``step()``; its phases are ``serve.step.<phase>``,
#: the names built once so that the hot path concatenates nothing.
STEP_SPAN = "serve.step"
_SPAN_NAMES = {p: f"{STEP_SPAN}.{p}" for p in TILING + SUB_PHASES}

#: Rows a :class:`StepLog` holds before it overwrites its oldest.  What
#: needs more than ``report()``'s window is a reader of one whole run:
#: the benchmark's longest take 2,529-2,725 steps an engine, warm-up,
#: lead-in and probes included (``kexaone_mixedq`` in its 141 s drain,
#: ``mistral7b_chat`` in its 60 s; PERF.md section 6, PR 35).  Three
#: times that, 3 min of a chat engine's 22 ms steps:
#: 8,192 rows x 25 fields x 8 B = 1.6 MB an engine, touched as written.
STEP_LOG_ROWS = 8_192

#: How many engines' logs :func:`step_logs` keeps: the newest engine's
#: and that of the one before it (the engine a supervisor just cloned;
#: every benchmark run builds one engine a process).
_KEPT_LOGS = 2

_DEFAULT_WINDOW = 256


def _env_window() -> int:
    raw = os.environ.get("HVD_TPU_PROFILE_WINDOW", "")
    try:
        return int(raw) if raw else _DEFAULT_WINDOW
    except ValueError:
        return _DEFAULT_WINDOW


class StepLog:
    """The rows of one engine's steps, :data:`ROW_FIELDS` wide, in a
    preallocated ring of :data:`STEP_LOG_ROWS`; ``metrics`` is the
    engine's registry, whose cumulative counters stay readable here
    after the engine is gone.

    The engine thread appends; any thread reads a copy."""

    _GUARDED_BY_LOCK = ("_buf", "_n")

    def __init__(self, metrics: "metrics_mod.MetricsRegistry"):
        self.metrics = metrics
        self._lock = threading.Lock()
        self._buf = np.zeros((STEP_LOG_ROWS, len(ROW_FIELDS)), np.float64)
        self._n = 0

    def append(self, row: list) -> None:
        with self._lock:
            self._buf[self._n % len(self._buf)] = row
            self._n += 1

    @property
    def written(self) -> int:
        """Rows appended since the engine was built."""
        with self._lock:
            return self._n

    @property
    def dropped(self) -> int:
        """Rows overwritten by later ones."""
        return max(self.written - len(self._buf), 0)

    def rows(self, last: int | None = None) -> np.ndarray:
        """A copy of the kept rows, oldest first (the newest ``last``)."""
        with self._lock:
            n, cap = self._n, len(self._buf)
            k = min(n, cap) if last is None else min(n, cap, last)
            return self._buf[np.arange(n - k, n) % cap]


_LOGS: collections.deque[StepLog] = collections.deque(maxlen=_KEPT_LOGS)
_LOGS_LOCK = threading.Lock()


def step_logs() -> list[StepLog]:
    """The logs of the last :data:`_KEPT_LOGS` engines this process
    built, oldest first: how a reader gets at an engine's rows and
    registry after the engine was closed."""
    with _LOGS_LOCK:
        return list(_LOGS)


class PhaseSpans:
    """The phases of one ``step()`` as spans on the profiler trace and
    as one row of the engine's :class:`StepLog`.

    The engine thread drives ``begin(step)`` → ``mark(phase)`` /
    ``with sub(sub_phase)`` → ``end()`` once per ``step()``, ``end()`` in
    a ``finally`` so that an exception out of the step leaves no span
    open and still a whole row.  The row being built is engine-thread
    private (one ``step()`` at a time); only the log crosses threads
    (the monitor thread calls ``report()`` on scrape)."""

    def __init__(self, metrics: "metrics_mod.MetricsRegistry",
                 window: int | None = None):
        window = _env_window() if window is None else window
        if window < 1:
            raise ValueError(f"profile window must be >= 1, got {window}")
        self.window = window
        self.log = StepLog(metrics)
        with _LOGS_LOCK:
            _LOGS.append(self.log)
        self._step_span = None
        self._phase_span = None
        self._row: list = [0.0] * len(ROW_FIELDS)
        self._phase = _COL[TILING[0]]
        self._t_last = 0.0
        # (column, counter) of the CARRIED the registry has, bound at
        # the first step: the model registered its own by then
        self._carried: list | None = None

    # -- hot path (engine thread) ------------------------------------------

    def begin(self, step: int) -> None:
        """Open the tick in its first phase, on a fresh row."""
        if self._carried is None:
            metrics = self.log.metrics
            have = metrics.snapshot()["counters"]
            self._carried = [(_COL[name], metrics.counter(name))
                             for name in CARRIED if name in have]
        now = time.monotonic()
        row = self._row = [0.0] * len(ROW_FIELDS)
        row[0] = step
        row[_BEGAN] = self._t_last = now
        self._step_span = trace_annotation(STEP_SPAN)
        self._step_span.__enter__()
        self._open(TILING[0])

    def mark(self, phase: str) -> None:
        """The boundary at which ``phase`` starts: the phase open until
        here ends and is charged with the time since the boundary
        before."""
        self._phase_span.__exit__(None, None, None)
        self._charge()
        self._open(phase)

    @contextlib.contextmanager
    def sub(self, phase: str):
        """Context manager around a nested sub-phase; the parent phase
        stays open and still covers it."""
        t0 = time.monotonic()
        with trace_annotation(_SPAN_NAMES[phase]):
            yield
        self._row[_COL[phase]] += time.monotonic() - t0

    def add(self, phase: str, t0: float, t1: float) -> None:
        """Attribute an explicit ``[t0, t1]`` interval (of any one
        clock) to a nested sub-phase WITHOUT moving the tiling boundary
        (the parent phase still covers it)."""
        self._row[_COL[phase]] += t1 - t0

    def counts(self, **counts: int) -> None:
        """What the step counted, by :data:`COUNTS` name."""
        row = self._row
        for name, value in counts.items():
            row[_COL[name]] = value

    def end(self) -> list:
        """Close the open phase and the tick; the row joins the log."""
        self._phase_span.__exit__(None, None, None)
        self._step_span.__exit__(None, None, None)
        self._phase_span = self._step_span = None
        row = self._row
        for col, counter in self._carried:
            row[col] = counter.value
        row[_ENDED] = self._charge()
        self.log.append(row)
        return row

    def _open(self, phase: str) -> None:
        self._phase = _COL[phase]
        self._phase_span = trace_annotation(_SPAN_NAMES[phase])
        self._phase_span.__enter__()

    def _charge(self) -> float:
        now = time.monotonic()
        self._row[self._phase] += now - self._t_last
        self._t_last = now
        return now

    # -- reporting (any thread) --------------------------------------------

    def report(self) -> dict:
        """Rolling-window per-phase summary over the last ``window``
        rows: for each phase its sample count (the rows that had it),
        total/mean/max seconds and share of tick time, plus the tick
        totals and ``coverage`` — the fraction of windowed tick wall
        time the top-level phases account for (≈ 1.0 by the tiling
        construction).  The same schema ``tools/profile_report.py``
        renders and diffs."""
        rows = self.log.rows(self.window)
        n = len(rows)
        ticks = rows[:, _ENDED] - rows[:, _BEGAN]
        tick_total = float(ticks.sum())
        phases: dict[str, dict] = {}
        tiled = 0.0
        # The other tiling phases join the report only once a tick of
        # the window had them — other engines keep the fixed PHASES schema.
        seen = tuple(p for p in TILING
                     if p not in PHASES and rows[:, _COL[p]].any())
        for phase in PHASES + seen + SUB_PHASES:
            vals = rows[:, _COL[phase]]
            count = int(np.count_nonzero(vals))
            total = float(vals.sum())
            phases[phase] = {
                "count": count,
                "total_s": total,
                "mean_s": total / count if count else 0.0,
                "max_s": float(vals.max()) if n else 0.0,
                "pct_of_tick": (100.0 * total / tick_total
                                if tick_total else 0.0),
            }
            if phase not in SUB_PHASES:
                tiled += total
        return {
            "window": self.window,
            "n": n,
            "ticks": self.log.written,
            "tick": {
                "count": n,
                "total_s": tick_total,
                "mean_s": tick_total / n if n else 0.0,
                "max_s": float(ticks.max()) if n else 0.0,
            },
            "phases": phases,
            "coverage": tiled / tick_total if tick_total else 1.0,
        }


class TickProfiler(PhaseSpans):
    """:class:`PhaseSpans` whose every row also feeds the
    ``serve.phase.*_s`` histograms and one ``serve.profile_tick``
    event: what a deployment reads of the phases from ``/metrics`` and
    replays from the event log."""

    def __init__(self, metrics: "metrics_mod.MetricsRegistry",
                 window: int | None = None):
        super().__init__(metrics, window)
        self.metrics = metrics
        # Pre-bound histograms, registered by LITERAL name (the HVD005
        # contract) so the snapshot is schema-stable from tick 0 and the
        # hot path never does a registry lookup.
        hists = {
            "expire": metrics.histogram("serve.phase.expire_s"),
            "admit": metrics.histogram("serve.phase.admit_s"),
            "admit.cache_acquire":
                metrics.histogram("serve.phase.admit_cache_acquire_s"),
            "admit.prefill_dispatch":
                metrics.histogram("serve.phase.admit_prefill_dispatch_s"),
            "draft": metrics.histogram("serve.phase.draft_s"),
            "unmask": metrics.histogram("serve.phase.unmask_s"),
            "decode_dispatch":
                metrics.histogram("serve.phase.decode_dispatch_s"),
            "device_sync": metrics.histogram("serve.phase.device_sync_s"),
            "device_sync.compute_est": metrics.histogram(
                "serve.phase.device_sync_compute_est_s"),
            "device_sync.host_stall": metrics.histogram(
                "serve.phase.device_sync_host_stall_s"),
            "verify": metrics.histogram("serve.phase.verify_s"),
            "sample_postprocess":
                metrics.histogram("serve.phase.sample_postprocess_s"),
            "bookkeeping": metrics.histogram("serve.phase.bookkeeping_s"),
        }
        assert set(hists) == set(TILING + SUB_PHASES)
        self._tick_hist = metrics.histogram("serve.phase.tick_s")
        self._hists = [(p, _COL[p], hists[p]) for p in TILING + SUB_PHASES]

    def end(self) -> list:
        """Close the tick; the phases the row had feed their
        histograms, and one ``serve.profile_tick`` event is emitted."""
        row = super().end()
        tick_s = row[_ENDED] - row[_BEGAN]
        phases = {}
        for phase, col, hist in self._hists:
            dt = row[col]
            if dt:
                hist.observe(dt)
                phases[phase] = dt
        self._tick_hist.observe(tick_s)
        self.metrics.event("serve.profile_tick", step=row[0],
                           tick_s=tick_s, phases=phases)
        return row
