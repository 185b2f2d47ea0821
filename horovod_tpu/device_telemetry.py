"""Device-side observability for the serving engine: XLA cost model,
compile ledger, HBM accounting, transfer stamps, and live serving MFU.

Every observability plane so far stops at the host: the profiler tiles
the tick into phases but ``device_sync`` is one opaque mark, so nothing
says whether that wait was the device doing useful FLOPs or the host
stalled on a dispatch bubble.  :class:`DeviceTelemetry` opens that box
with four instruments, all derived from surfaces jax already exposes:

* **Static cost model** — at jit-pin time, :meth:`capture` runs
  ``jitfn.lower(*avals).compile().cost_analysis()`` per pinned program
  (``tick`` / ``chunk`` / ``set_row`` / ``spec_tick``), recording FLOPs
  and bytes-accessed *per dispatch*.  Ahead-of-time lowering never
  touches the jit call cache, so ``compile_cache_sizes()`` is identical
  telemetry-on vs off (pinned by tests/test_device_telemetry.py) and
  the retrace sentry stays silent.
* **Compile ledger** — each capture times its compile wall time
  (``device.compile_s`` histogram, ``device.compiles`` counter), and
  :meth:`on_retrace` charges the sentry's mid-serve cache growths with
  the captured per-program compile cost — retraces become seconds, not
  just a count.
* **HBM accounting** — :meth:`on_step` polls
  ``device.memory_stats()`` at the ``HVD_TPU_DEVICE_POLL_S`` cadence
  (``device.bytes_in_use`` / ``device.peak_bytes_in_use`` /
  ``device.hbm_used_fraction`` gauges where the backend provides them;
  CPU returns None and the gauges are simply never minted), reconciled
  in :meth:`report` against the engine's model-side byte accounting
  (params + paged KV pool) to expose framework overhead.
* **Transfer + dispatch split** — the engine stamps ``device_put`` /
  readback bytes per tick (``device.h2d_bytes`` / ``device.d2h_bytes``)
  and :meth:`on_sync` splits the measured ``device_sync`` wait into a
  cost-model-predicted device-compute share vs host stall, feeding the
  ``device_sync.compute_est`` / ``device_sync.host_stall`` nested
  profiler intervals and the ``device.overlap_headroom_pct`` gauge —
  the ceiling ROADMAP item 3's double-buffering work is judged against.

The live MFU (``serve.mfu``) divides achieved cost-model FLOPs/s by a
per-platform peak table (per chip, scaled by the engine's ``tp_size``);
on platforms the table doesn't know — every CPU rehearsal — the
``device.peak_flops_known`` gauge reads 0 and the MFU gauge is ABSENT,
never a dishonest zero.  ``HVD_TPU_PEAK_FLOPS`` overrides the per-chip
peak for hardware the table hasn't met.

Replay: one ``device.capture`` event per program plus one
``device.tick`` event per step land in the structured event log;
:func:`report_from_events` rebuilds the same report schema from those
records alone (no wall clock — a DETERMINISM_SURFACES row lets hvdlint
HVD010 police that), so ``tools/device_report.py`` renders and diffs a
crashed run identically to a live ``/device`` scrape.

Only :mod:`horovod_tpu.metrics` is imported at module level; jax loads
lazily inside the capture/poll paths so the replay-side consumers
(``tools/device_report.py``) stay import-light.
"""

from __future__ import annotations

import collections
import os
import time
import warnings
from typing import Any

from horovod_tpu import metrics as metrics_mod

#: The pinned jit programs the engine captures, in capture order
#: (``spec_tick`` only on spec engines).
PROGRAMS = ("sample", "tick", "chunk", "set_row", "spec_tick")

#: Dense per-chip peak FLOP/s by accelerator generation (bf16/fp32 as
#: served — published TPU peak matmul numbers), matched as lowercase
#: substrings of ``device_kind``.  Order matters: first match wins, so
#: longer/more specific keys come first.  CPUs (and any unmatched kind)
#: have NO honest peak — MFU is then not emitted at all.
PEAK_FLOPS_TABLE: tuple[tuple[str, float], ...] = (
    ("v6e", 918e12), ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12), ("v5 lite", 197e12), ("v5litepod", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

_DEFAULT_WINDOW = 256
_DEFAULT_POLL_S = 1.0


def _env_poll_s() -> float:
    raw = os.environ.get("HVD_TPU_DEVICE_POLL_S", "")
    try:
        return float(raw) if raw else _DEFAULT_POLL_S
    except ValueError:
        return _DEFAULT_POLL_S


def _env_peak_flops() -> float | None:
    """Per-chip peak override for hardware the table hasn't met."""
    raw = os.environ.get("HVD_TPU_PEAK_FLOPS", "")
    try:
        return float(raw) if raw else None
    except ValueError:
        warnings.warn(
            f"HVD_TPU_PEAK_FLOPS={raw!r} is not a float; ignoring",
            RuntimeWarning, stacklevel=2)
        return None


def lookup_peak_flops(device_kind: str) -> float | None:
    """Table lookup by device-kind substring; None = honest unknown."""
    kind = device_kind.lower()
    for key, peak in PEAK_FLOPS_TABLE:
        if key in kind:
            return peak
    return None


class DeviceTelemetry:
    """Per-engine device observability plane.

    The engine thread drives :meth:`dispatch` / :meth:`on_sync` /
    :meth:`on_step` once per program call / readback / step; the
    monitor thread calls :meth:`report` on scrape.  Only the rolling
    window ring crosses threads (same discipline as the profiler), and
    every hot-path call is gated at the engine by one ``is not None``
    test, so telemetry off costs nothing."""

    _GUARDED_BY_LOCK = ()  # ring mutations are engine-thread-only;
    # report() reads a consistent-enough copy (plain-dict snapshots).

    def __init__(self, registry: "metrics_mod.MetricsRegistry",
                 *, n_devices: int = 1, window: int | None = None,
                 poll_s: float | None = None,
                 peak_flops: float | None = None):
        self.metrics = registry
        self.n_devices = max(int(n_devices), 1)
        self.window = _DEFAULT_WINDOW if window is None else int(window)
        if self.window < 1:
            raise ValueError(
                f"device window must be >= 1, got {self.window}")
        self.poll_s = _env_poll_s() if poll_s is None else float(poll_s)
        self.platform, self.device_kind = self._identify()
        per_chip = (peak_flops if peak_flops is not None
                    else _env_peak_flops())
        self.peak_source = "arg" if peak_flops is not None else (
            "env" if per_chip is not None else "table")
        if per_chip is None:
            per_chip = lookup_peak_flops(self.device_kind)
        if per_chip is None:
            self.peak_source = None
        self.peak_flops = (per_chip * self.n_devices
                           if per_chip is not None else None)
        self.peak_flops_known = self.peak_flops is not None
        #: per-program cost-model rows: flops / bytes_accessed /
        #: compile_s / dispatches (cumulative).
        self.programs: dict[str, dict] = {}
        # Model-side device bytes for HBM reconciliation (the engine
        # sets these from its own exact accounting).
        self.param_bytes = 0
        self.kv_total_bytes = 0
        # Cumulative odometers (also mirrored to registry counters).
        self.total_flops = 0.0
        self.total_h2d = 0
        self.total_d2h = 0
        self.dispatch_totals: dict[str, int] = {}
        self.retraces = 0
        self.retrace_compile_est_s = 0.0
        # Rolling window: explicit popleft keeps O(1) running sums.
        self._ring: collections.deque[dict] = collections.deque()
        self._sums = {"dt_s": 0.0, "flops": 0.0, "bytes_accessed": 0.0,
                      "h2d_bytes": 0.0, "d2h_bytes": 0.0, "sync_s": 0.0,
                      "compute_est_s": 0.0, "host_stall_s": 0.0}
        self._ticks = 0
        # engine-thread scratch for the tick being accumulated
        self._pend = self._fresh_pend()
        self._last_step_ts: float | None = None
        self._last_poll_ts: float | None = None
        self.last_memory: dict | None = None
        # Instruments by LITERAL name (the HVD005 contract).  The
        # conditional gauges (serve.mfu, device.bytes_in_use, ...) are
        # minted only when their value is honestly known — an absent
        # gauge beats a fabricated zero.
        self._c_compiles = registry.counter("device.compiles")
        self._h_compile_s = registry.histogram("device.compile_s")
        self._c_flops = registry.counter("device.model_flops")
        self._c_h2d = registry.counter("device.h2d_bytes")
        self._c_d2h = registry.counter("device.d2h_bytes")
        self._g_headroom = registry.gauge("device.overlap_headroom_pct")
        registry.gauge("device.peak_flops_known").set(
            1 if self.peak_flops_known else 0)

    @staticmethod
    def _identify() -> tuple[str, str]:
        try:
            import jax
            d = jax.devices()[0]
            return d.platform, getattr(d, "device_kind", d.platform)
        except Exception as exc:  # noqa: BLE001 — telemetry never kills serving
            warnings.warn(f"device identification failed ({exc!r}); "
                          "telemetry continues with unknown platform",
                          RuntimeWarning, stacklevel=2)
            return "unknown", "unknown"

    def _fresh_pend(self) -> dict:
        return {"dispatches": {}, "flops": 0.0, "bytes_accessed": 0.0,
                "h2d_bytes": 0, "d2h_bytes": 0, "sync_s": 0.0,
                "compute_est_s": 0.0, "host_stall_s": 0.0}

    # -- cost model + compile ledger (engine init) ------------------------

    def set_model_bytes(self, *, param_bytes: int,
                        kv_total_bytes: int) -> None:
        """Exact model-side device bytes, for HBM reconciliation."""
        self.param_bytes = int(param_bytes)
        self.kv_total_bytes = int(kv_total_bytes)

    def capture(self, name: str, jitfn: Any, *avals: Any) -> dict:
        """AOT-compile one pinned program from abstract avals and record
        its cost model.  ``jax.jit(...).lower()`` does NOT mint a jit
        call-cache entry, so capturing leaves ``compile_cache_sizes()``
        untouched.  The timed compile is the ledger sample — the same
        program's first real call pays the same cost again through the
        jit cache, and every sentry-detected retrace re-pays it.
        Capture failures degrade to a zeroed row (telemetry must never
        break serving)."""
        t0 = time.perf_counter()
        entry = {"flops": 0.0, "bytes_accessed": 0.0, "compile_s": 0.0,
                 "dispatches": 0}
        try:
            compiled = jitfn.lower(*avals).compile()
            entry["compile_s"] = time.perf_counter() - t0
            cost = compiled.cost_analysis() or {}   # None: no cost model
            entry["flops"] = float(cost.get("flops", 0.0) or 0.0)
            entry["bytes_accessed"] = float(
                cost.get("bytes accessed", 0.0) or 0.0)
        except Exception as exc:  # noqa: BLE001 — degrade, don't break serving
            entry["error"] = repr(exc)
            warnings.warn(
                f"device cost capture failed for {name!r} ({exc!r}); "
                "telemetry continues without its cost model",
                RuntimeWarning, stacklevel=2)
        self.programs[name] = entry
        self._c_compiles.inc()
        self._h_compile_s.observe(entry["compile_s"])
        self.metrics.event(
            "device.capture", program=name, flops=entry["flops"],
            bytes_accessed=entry["bytes_accessed"],
            compile_s=entry["compile_s"], platform=self.platform,
            device_kind=self.device_kind, n_devices=self.n_devices,
            peak_flops=self.peak_flops,
            peak_flops_known=self.peak_flops_known)
        return entry

    def on_retrace(self, grew: dict) -> None:
        """Charge sentry-detected mid-serve cache growth with the
        captured compile cost of each regrown program — the ledger's
        answer to "how much did that retrace cost us"."""
        for prog, (before, after) in grew.items():
            n = after - max(before, 1)
            if n <= 0:
                continue
            self.retraces += n
            self._c_compiles.inc(n)
            est = self.programs.get(prog, {}).get("compile_s", 0.0)
            self.retrace_compile_est_s += est * n

    # -- hot path (engine thread) ------------------------------------------

    def dispatch(self, name: str, h2d_bytes: int = 0) -> None:
        """One dispatch of a pinned program, with its host->device
        argument bytes (the arrays the engine materializes per call —
        persistent donated state transfers nothing)."""
        p = self._pend
        p["dispatches"][name] = p["dispatches"].get(name, 0) + 1
        self.dispatch_totals[name] = (
            self.dispatch_totals.get(name, 0) + 1)
        entry = self.programs.get(name)
        if entry is not None:
            p["flops"] += entry["flops"]
            p["bytes_accessed"] += entry["bytes_accessed"]
        p["h2d_bytes"] += h2d_bytes

    def on_sync(self, names, t0: float, t1: float,
                d2h_bytes: int = 0) -> tuple[float, float]:
        """Split one measured ``device_sync`` readback wait ``[t0, t1]``
        into (device-compute estimate, host stall) using the cost
        model's predicted device time for the programs ``names`` that
        the wait was for (the engine's read waits for the tick in
        flight and the step's chunks; each counted whole, so the
        estimate is an upper bound where a program began before the
        wait did) — predicted = flops / peak.  With no honest peak (CPU
        rehearsals) the split degenerates to all-compute: we cannot
        prove any stall, so none is claimed.  Returns
        ``(compute_est_s, host_stall_s)``."""
        sync_s = max(t1 - t0, 0.0)
        est = sync_s
        if self.peak_flops:
            flops = sum(self.programs[n]["flops"] for n in names
                        if n in self.programs)
            if flops > 0.0:
                est = min(flops / self.peak_flops, sync_s)
        stall = sync_s - est
        p = self._pend
        p["d2h_bytes"] += d2h_bytes
        p["sync_s"] += sync_s
        p["compute_est_s"] += est
        p["host_stall_s"] += stall
        return est, stall

    def on_step(self, step: int) -> None:
        """Close the step's pending record: fold it into the rolling
        window, refresh the gauges/counters, poll HBM at the configured
        cadence, and emit one ``device.tick`` event."""
        now = time.perf_counter()
        dt = (now - self._last_step_ts
              if self._last_step_ts is not None else 0.0)
        self._last_step_ts = now
        p = self._pend
        self._pend = self._fresh_pend()
        rec = {"step": step, "dt_s": dt, "flops": p["flops"],
               "bytes_accessed": p["bytes_accessed"],
               "h2d_bytes": p["h2d_bytes"], "d2h_bytes": p["d2h_bytes"],
               "sync_s": p["sync_s"],
               "compute_est_s": p["compute_est_s"],
               "host_stall_s": p["host_stall_s"],
               "dispatches": p["dispatches"]}
        if len(self._ring) >= self.window:
            old = self._ring.popleft()
            for k in self._sums:
                self._sums[k] -= old[k]
        self._ring.append(rec)
        for k in self._sums:
            self._sums[k] += rec[k]
        self._ticks += 1
        self.total_flops += p["flops"]
        self.total_h2d += p["h2d_bytes"]
        self.total_d2h += p["d2h_bytes"]
        if p["flops"]:
            self._c_flops.inc(int(p["flops"]))
        if p["h2d_bytes"]:
            self._c_h2d.inc(p["h2d_bytes"])
        if p["d2h_bytes"]:
            self._c_d2h.inc(p["d2h_bytes"])
        win = self._sums
        if win["dt_s"] > 0.0:
            self._g_headroom.set(
                100.0 * win["compute_est_s"] / win["dt_s"])
            if self.peak_flops:
                # Minted only here: no honest peak, no MFU gauge.
                self.metrics.gauge("serve.mfu").set(
                    win["flops"] / win["dt_s"] / self.peak_flops)
        if win["bytes_accessed"] > 0.0:
            self.metrics.gauge("serve.arithmetic_intensity").set(
                win["flops"] / win["bytes_accessed"])
        if (self._last_poll_ts is None
                or now - self._last_poll_ts >= self.poll_s):
            self._last_poll_ts = now
            self.poll_memory()
        self.metrics.event("device.tick", **rec)

    def poll_memory(self) -> dict | None:
        """One ``memory_stats()`` poll.  Backends without it (CPU)
        return None: the gauges are never minted and ``last_memory``
        records the honest absence."""
        stats = None
        try:
            import jax
            stats = jax.devices()[0].memory_stats()
        except Exception:  # noqa: BLE001 — poll failures are absence, not errors
            stats = None
        if not stats:
            self.last_memory = {"available": False}
            return None
        mem = {"available": True,
               "bytes_in_use": int(stats.get("bytes_in_use", 0)),
               "peak_bytes_in_use": int(
                   stats.get("peak_bytes_in_use", 0)),
               "bytes_limit": int(stats.get("bytes_limit", 0))}
        self.last_memory = mem
        self.metrics.gauge("device.bytes_in_use").set(
            mem["bytes_in_use"])
        self.metrics.gauge("device.peak_bytes_in_use").set(
            mem["peak_bytes_in_use"])
        if mem["bytes_limit"] > 0:
            frac = mem["bytes_in_use"] / mem["bytes_limit"]
            self.metrics.gauge("device.hbm_used_fraction").set(frac)
        self.metrics.event("device.memory", **mem)
        return mem

    # -- reporting (any thread) --------------------------------------------

    def report(self) -> dict:
        """The ``/device`` payload: platform + peak provenance, the
        per-program cost table, the compile ledger, the rolling-window
        achieved numbers (MFU only when the peak is honest), and the
        HBM reconciliation when the backend reports memory."""
        ring = list(self._ring)
        return build_report(
            platform=self.platform, device_kind=self.device_kind,
            n_devices=self.n_devices, peak_flops=self.peak_flops,
            peak_flops_known=self.peak_flops_known,
            peak_source=self.peak_source,
            programs={k: dict(v, dispatches=self.dispatch_totals.get(
                k, 0)) for k, v in self.programs.items()},
            compiles=int(self._c_compiles.value),
            compile_total_s=float(self._h_compile_s.sum),
            retraces=self.retraces,
            retrace_compile_est_s=self.retrace_compile_est_s,
            ticks=self._ticks, window=self.window, ring=ring,
            memory=self.last_memory, param_bytes=self.param_bytes,
            kv_total_bytes=self.kv_total_bytes)


def build_report(*, platform: str, device_kind: str, n_devices: int,
                 peak_flops: float | None, peak_flops_known: bool,
                 peak_source: str | None, programs: dict, compiles: int,
                 compile_total_s: float, retraces: int,
                 retrace_compile_est_s: float, ticks: int, window: int,
                 ring: list, memory: dict | None, param_bytes: int,
                 kv_total_bytes: int) -> dict:
    """Assemble the report schema from already-collected records — the
    shared shape of the live :meth:`DeviceTelemetry.report` and the
    event-log replay (:func:`report_from_events`), so the two are
    field-for-field comparable.  Pure arithmetic over its inputs: no
    clocks, no entropy (the HVD010 contract for the replay path)."""
    sums = {k: 0.0 for k in ("dt_s", "flops", "bytes_accessed",
                             "h2d_bytes", "d2h_bytes", "sync_s",
                             "compute_est_s", "host_stall_s")}
    dispatches: dict[str, int] = {}
    for rec in ring:
        for k in sums:
            sums[k] += rec.get(k, 0.0)
        for prog, n in (rec.get("dispatches") or {}).items():
            dispatches[prog] = dispatches.get(prog, 0) + int(n)
    dt = sums["dt_s"]
    win: dict[str, Any] = {
        "n": len(ring),
        "elapsed_s": dt,
        "flops": sums["flops"],
        "bytes_accessed": sums["bytes_accessed"],
        "h2d_bytes": int(sums["h2d_bytes"]),
        "d2h_bytes": int(sums["d2h_bytes"]),
        "sync_s": sums["sync_s"],
        "compute_est_s": sums["compute_est_s"],
        "host_stall_s": sums["host_stall_s"],
        "dispatches": dict(sorted(dispatches.items())),
        "flops_per_s": sums["flops"] / dt if dt else 0.0,
        "overlap_headroom_pct": (100.0 * sums["compute_est_s"] / dt
                                 if dt else 0.0),
        "arithmetic_intensity": (
            sums["flops"] / sums["bytes_accessed"]
            if sums["bytes_accessed"] else 0.0),
        # honest: no peak, no MFU — the key is present (schema-stable)
        # but null, and the gauge side never mints at all.
        "mfu": (sums["flops"] / dt / peak_flops
                if peak_flops and dt else None),
    }
    out: dict[str, Any] = {
        "platform": platform,
        "device_kind": device_kind,
        "n_devices": n_devices,
        "peak_flops": peak_flops,
        "peak_flops_known": peak_flops_known,
        "peak_flops_source": peak_source,
        "programs": {k: dict(v) for k, v in sorted(programs.items())},
        "compiles": compiles,
        "compile_total_s": compile_total_s,
        "retraces": retraces,
        "retrace_compile_est_s": retrace_compile_est_s,
        "ticks": ticks,
        "window": window,
        "win": win,
        "memory": memory,
    }
    if memory and memory.get("available"):
        model = param_bytes + kv_total_bytes
        out["reconciliation"] = {
            "param_bytes": param_bytes,
            "kv_total_bytes": kv_total_bytes,
            "model_bytes": model,
            "hbm_bytes_in_use": memory["bytes_in_use"],
            "framework_overhead_bytes":
                memory["bytes_in_use"] - model,
        }
    return out


def report_from_events(events: list[dict],
                       window: int | None = None) -> dict:
    """Rebuild the ``/device`` report schema from ``device.capture`` /
    ``device.tick`` / ``device.memory`` event-log records — the replay
    path (``tools/device_report.py``).  Reads ONLY recorded fields:
    wall clocks or fresh polls here would make a replayed report
    disagree with the live one it must match (hvdlint HVD010 polices
    this via its DETERMINISM_SURFACES row)."""
    captures = [e for e in events if e.get("kind") == "device.capture"]
    ticks = [e for e in events if e.get("kind") == "device.tick"]
    mems = [e for e in events if e.get("kind") == "device.memory"]
    programs: dict[str, dict] = {}
    for e in captures:          # last capture per program wins
        programs[str(e.get("program"))] = {
            "flops": float(e.get("flops", 0.0)),
            "bytes_accessed": float(e.get("bytes_accessed", 0.0)),
            "compile_s": float(e.get("compile_s", 0.0)),
            "dispatches": 0,
        }
    for e in ticks:
        for prog, n in (e.get("dispatches") or {}).items():
            if prog in programs:
                programs[prog]["dispatches"] += int(n)
    head = captures[-1] if captures else {}
    peak = head.get("peak_flops")
    n_ticks = len(ticks)
    win_n = n_ticks if window is None else min(window, n_ticks)
    ring = [{k: e.get(k, 0.0) for k in
             ("step", "dt_s", "flops", "bytes_accessed", "h2d_bytes",
              "d2h_bytes", "sync_s", "compute_est_s", "host_stall_s")}
            | {"dispatches": e.get("dispatches") or {}}
            for e in ticks[-win_n:]] if win_n else []
    memory = None
    if mems:
        m = mems[-1]
        memory = {"available": True,
                  "bytes_in_use": int(m.get("bytes_in_use", 0)),
                  "peak_bytes_in_use": int(
                      m.get("peak_bytes_in_use", 0)),
                  "bytes_limit": int(m.get("bytes_limit", 0))}
    return build_report(
        platform=str(head.get("platform", "unknown")),
        device_kind=str(head.get("device_kind", "unknown")),
        n_devices=int(head.get("n_devices", 1)),
        peak_flops=peak,
        peak_flops_known=bool(head.get("peak_flops_known", False)),
        peak_source="replay" if peak is not None else None,
        programs=programs,
        compiles=len(captures),
        compile_total_s=sum(p["compile_s"] for p in programs.values()),
        retraces=0, retrace_compile_est_s=0.0,
        ticks=n_ticks, window=window if window is not None else win_n,
        ring=ring, memory=memory, param_bytes=0, kv_total_bytes=0)


def maybe_telemetry(registry: "metrics_mod.MetricsRegistry",
                    *, n_devices: int = 1) -> DeviceTelemetry | None:
    """Env factory: a plane when ``HVD_TPU_DEVICE_TELEMETRY=1``, else
    None (the engine's ``device_telemetry=None`` default routes here)."""
    if os.environ.get("HVD_TPU_DEVICE_TELEMETRY", "") != "1":
        return None
    return DeviceTelemetry(registry, n_devices=n_devices)
