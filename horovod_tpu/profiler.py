"""Phase spans and the per-tick phase profiler of the serving engine.

Continuous-batching schedulers hide host-side stalls inside "decode
time": admission bookkeeping, chunked-prefill dispatch, the blocking
token readback, and per-request postprocessing all happen between two
device ticks, and a whole-step latency histogram cannot say which one
got slower.  vLLM and SGLang both ship per-phase step timing for
exactly this reason.  Here it comes in two levels, driven by the one
set of call sites in :meth:`ServeEngine.step
<horovod_tpu.serving_scheduler.ServeEngine.step>`; the engine always
holds one of the two (``profile`` / ``HVD_TPU_PROFILE`` decides which):

* :class:`PhaseSpans` — the default.  Every phase is a
  ``jax.profiler.TraceAnnotation`` (through
  :func:`horovod_tpu.timeline.trace_annotation`): ``serve.step`` around
  the tick, ``serve.step.<phase>`` tiling it, ``serve.step.<sub-phase>``
  nested inside their parent.  They land on the host plane of jax's
  profiler trace, the clock the device's ``XLA Ops`` are on, so an idle
  gap of the device can be pinned on the phase the host was in.  While
  no trace is being taken an annotation costs under a microsecond and
  records nothing: the xplane is the only span record.
* :class:`TickProfiler` — the same spans plus host clocks
  (``time.perf_counter``): per-phase histograms in the engine's
  :class:`~horovod_tpu.metrics.MetricsRegistry` (``serve.phase.*_s``),
  one ``serve.profile_tick`` structured event per tick when the registry
  has a JSONL sink (replayed by ``tools/profile_report.py``), and
  ``report()`` over a rolling window of the last
  ``HVD_TPU_PROFILE_WINDOW`` ticks — the payload of
  ``metrics_snapshot()["profile"]`` and the monitor's ``/profile``
  endpoint.

Design rules (pinned by ``tests/test_profiler.py``):

* **One vocabulary.**  :data:`PHASES`, :data:`SPEC_PHASES` and
  :data:`SUB_PHASES` name the phases in ``/profile``, in the
  ``serve.phase.*_s`` histograms and (behind ``serve.step.``) in the
  trace, letter for letter.
* **Host code only.**  Neither level touches a traced value or sits
  inside a jitted function, so ``compile_cache_sizes()`` is unchanged.
* **Phases tile the tick.**  ``begin(step)`` opens the tick in its
  first phase and ``mark(phase)`` is the boundary at which ``phase``
  starts and the phase before it ends, so the top-level phases sum to
  the tick's wall time by construction (in the trace: up to the few
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
#: device compute vs host stall (only emitted when the engine runs with
#: ``device_telemetry``) — an estimate, so a host-clock ``add()`` of
#: :class:`TickProfiler` alone and never an interval on the trace.
SUB_PHASES = ("admit.cache_acquire", "admit.prefill_dispatch",
              "device_sync.compute_est", "device_sync.host_stall")

#: The span around one ``step()``; its phases are ``serve.step.<phase>``,
#: the names built once so that the hot path concatenates nothing.
STEP_SPAN = "serve.step"
_SPAN_NAMES = {p: f"{STEP_SPAN}.{p}"
               for p in PHASES + SPEC_PHASES + SUB_PHASES}

_DEFAULT_WINDOW = 256


def _env_window() -> int:
    raw = os.environ.get("HVD_TPU_PROFILE_WINDOW", "")
    try:
        return int(raw) if raw else _DEFAULT_WINDOW
    except ValueError:
        return _DEFAULT_WINDOW


class PhaseSpans:
    """The phases of one ``step()`` as spans on the profiler trace.

    The engine thread drives ``begin(step)`` → ``mark(phase)`` /
    ``with sub(sub_phase)`` → ``end()`` once per ``step()``, ``end()`` in
    a ``finally`` so that an exception out of the step leaves no span
    open.  All state is engine-thread private (one ``step()`` at a
    time); nothing is kept once a span has closed."""

    def __init__(self) -> None:
        self._step_span = None
        self._phase_span = None

    def begin(self, step: int) -> None:
        """Open the tick in its first phase."""
        self._step_span = trace_annotation(STEP_SPAN)
        self._step_span.__enter__()
        self._open(PHASES[0])

    def mark(self, phase: str) -> None:
        """The boundary at which ``phase`` starts: the phase open until
        here ends."""
        self._phase_span.__exit__(None, None, None)
        self._open(phase)

    def sub(self, phase: str):
        """Context manager around a nested sub-phase; the parent phase
        stays open and still covers it."""
        return trace_annotation(_SPAN_NAMES[phase])

    def add(self, phase: str, t0: float, t1: float) -> None:
        """A cost-model interval has no place on the trace: only
        :class:`TickProfiler` keeps it."""

    def end(self) -> None:
        """Close the open phase and the tick."""
        self._phase_span.__exit__(None, None, None)
        self._step_span.__exit__(None, None, None)
        self._phase_span = self._step_span = None

    def report(self) -> dict | None:
        """Spans alone keep no numbers: ``None``."""
        return None

    def _open(self, phase: str) -> None:
        self._phase_span = trace_annotation(_SPAN_NAMES[phase])
        self._phase_span.__enter__()


class TickProfiler(PhaseSpans):
    """:class:`PhaseSpans` plus host clocks: what each phase cost, per
    tick and over a rolling window.

    The monitor thread calls ``report()`` on scrape.  Only the rolling
    window crosses threads — the per-tick scratch state is engine-thread
    private by construction (one ``step()`` at a time)."""

    _GUARDED_BY_LOCK = ("_ring", "_n_ticks")

    def __init__(self, metrics: "metrics_mod.MetricsRegistry",
                 window: int | None = None):
        super().__init__()
        window = _env_window() if window is None else window
        if window < 1:
            raise ValueError(f"profile window must be >= 1, got {window}")
        self.window = window
        self.metrics = metrics
        self._lock = threading.Lock()
        self._ring: collections.deque[dict] = collections.deque(
            maxlen=window)
        self._n_ticks = 0
        # engine-thread scratch (never read off-thread)
        self._cur: dict[str, float] = {}
        self._t0 = 0.0
        self._t_last = 0.0
        self._phase = PHASES[0]
        self._step = -1
        # Pre-bound histograms, registered by LITERAL name (the HVD005
        # contract) so the snapshot is schema-stable from tick 0 and the
        # hot path never does a registry lookup.
        self._hists = {
            "expire": metrics.histogram("serve.phase.expire_s"),
            "admit": metrics.histogram("serve.phase.admit_s"),
            "admit.cache_acquire":
                metrics.histogram("serve.phase.admit_cache_acquire_s"),
            "admit.prefill_dispatch":
                metrics.histogram("serve.phase.admit_prefill_dispatch_s"),
            "draft": metrics.histogram("serve.phase.draft_s"),
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
            "tick": metrics.histogram("serve.phase.tick_s"),
        }
        assert set(self._hists) == (set(PHASES) | set(SPEC_PHASES)
                                    | set(SUB_PHASES) | {"tick"})

    # -- hot path (engine thread) ------------------------------------------

    def begin(self, step: int) -> None:
        """Open a tick: resets the scratch dict and both clocks."""
        self._step = step
        self._cur = {}
        self._phase = PHASES[0]
        self._t0 = self._t_last = time.perf_counter()
        super().begin(step)

    def mark(self, phase: str) -> None:
        """The boundary at which ``phase`` starts: the phase open until
        here is charged with the time since the previous boundary."""
        super().mark(phase)
        self._charge()
        self._phase = phase

    @contextlib.contextmanager
    def sub(self, phase: str):
        t0 = time.perf_counter()
        with super().sub(phase):
            yield
        self.add(phase, t0, time.perf_counter())

    def add(self, phase: str, t0: float, t1: float) -> None:
        """Attribute an explicit ``[t0, t1]`` ``perf_counter`` interval
        to a nested sub-phase WITHOUT moving the tiling boundary (the
        parent phase still covers it)."""
        self._cur[phase] = self._cur.get(phase, 0.0) + (t1 - t0)

    def end(self) -> None:
        """Close the tick: the open phase is charged, every phase feeds
        its histogram, the tick joins the rolling window, and one
        ``serve.profile_tick`` event is emitted."""
        super().end()
        self._charge()
        cur = self._cur
        cur["tick"] = self._t_last - self._t0
        for phase, dt in cur.items():
            h = self._hists.get(phase)
            if h is not None:
                h.observe(dt)
        with self._lock:
            self._ring.append(cur)
            self._n_ticks += 1
        self.metrics.event(
            "serve.profile_tick", step=self._step, tick_s=cur["tick"],
            phases={k: v for k, v in cur.items() if k != "tick"})

    def _charge(self) -> None:
        now = time.perf_counter()
        t0, self._t_last = self._t_last, now
        self._cur[self._phase] = self._cur.get(self._phase, 0.0) + (now - t0)

    # -- reporting (any thread) --------------------------------------------

    def report(self) -> dict:
        """Rolling-window per-phase summary: for each phase its sample
        count, total/mean/max seconds and share of tick time, plus the
        tick totals and ``coverage`` — the fraction of windowed tick
        wall time the top-level phases account for (≈ 1.0 by the tiling
        construction).  The same schema ``tools/profile_report.py``
        renders and diffs."""
        with self._lock:
            items = list(self._ring)
            n_ticks = self._n_ticks
        n = len(items)
        ticks = [it.get("tick", 0.0) for it in items]
        tick_total = sum(ticks)
        phases: dict[str, dict] = {}
        tiled = 0.0
        # Spec phases (and any future mark names) join the report only
        # once a tick actually recorded them — non-spec engines keep
        # the fixed PHASES schema.
        extra = sorted({k for it in items for k in it}
                       - set(PHASES) - set(SUB_PHASES) - {"tick"})
        for phase in PHASES + tuple(extra) + SUB_PHASES:
            vals = [it[phase] for it in items if phase in it]
            total = sum(vals)
            phases[phase] = {
                "count": len(vals),
                "total_s": total,
                "mean_s": total / len(vals) if vals else 0.0,
                "max_s": max(vals) if vals else 0.0,
                "pct_of_tick": (100.0 * total / tick_total
                                if tick_total else 0.0),
            }
            if phase not in SUB_PHASES:
                tiled += total
        return {
            "window": self.window,
            "n": n,
            "ticks": n_ticks,
            "tick": {
                "count": n,
                "total_s": tick_total,
                "mean_s": tick_total / n if n else 0.0,
                "max_s": max(ticks, default=0.0),
            },
            "phases": phases,
            "coverage": tiled / tick_total if tick_total else 1.0,
        }
