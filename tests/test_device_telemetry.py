"""Device telemetry plane tests (horovod_tpu/device_telemetry.py +
the ServeEngine integration + tools/device_report.py).

The acceptance criteria, pinned:

1. *Cost model on every pinned program*: at engine init the plane
   AOT-captures FLOPs / bytes-accessed / compile time for ``tick`` /
   ``chunk`` / ``set_row`` (and ``spec_tick`` on a spec engine), and
   the captured tick FLOPs lands in an analytically sane band around
   2 x param-count per token.
2. *Free and harmless*: telemetry on vs off produces BIT-IDENTICAL
   greedy tokens, ``compile_cache_sizes()`` is unchanged (AOT lowering
   mints no jit call-cache entries), and the retrace sentry stays
   silent.
3. *Honest MFU*: with a pinned peak the ``serve.mfu`` gauge and the
   report's ``win.mfu`` equal achieved-FLOPs/s divided by peak exactly;
   with NO honest peak (every CPU rehearsal) the gauge is ABSENT —
   never a fabricated zero — and ``win.mfu`` is null.
4. *CPU graceful degradation*: ``memory_stats()`` is None on CPU, so
   the report says ``{"available": false}`` and no HBM gauge is minted.
5. *Serving surface*: ``/device`` over a real socket (engine monitor
   404s with telemetry off; router aggregates the fleet), snapshot and
   state-dump embedding, event-log replay equivalence, and the
   ``--compare`` gate tripping on an injected MFU drop.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import device_telemetry as dt_mod
from horovod_tpu import metrics as metrics_mod
from horovod_tpu.alerts import ALERT_RULES, AlertManager, rule_names
from horovod_tpu.device_telemetry import (
    DeviceTelemetry, PROGRAMS, build_report, lookup_peak_flops,
    maybe_telemetry, report_from_events)
from horovod_tpu.metrics import MetricsRegistry
from horovod_tpu.models import llama
from horovod_tpu.monitor import MonitorServer
from horovod_tpu.router import RouterServer
from horovod_tpu.serving import OK, Request
from horovod_tpu.serving_scheduler import ServeEngine
from horovod_tpu.timeseries import MetricsSampler

pytestmark = pytest.mark.device


@pytest.fixture(scope="module")
def world():
    cfg = llama.llama_tiny(attn_impl="dense", dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(5))
    return cfg, params


def _reqs(n=4, pl=3, new=4, **kw):
    rng = np.random.default_rng(2)
    return [Request(prompt=[int(t) for t in
                            rng.integers(1, 250, pl + (i % 3))],
                    max_new_tokens=new, **kw)
            for i in range(n)]


def _engine(world, **kw):
    cfg, params = world
    kw.setdefault("metrics", MetricsRegistry(event_log=None))
    kw.setdefault("monitor", False)
    return ServeEngine(params, cfg, n_slots=2, max_len=32, chunk=8, **kw)


class Clock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


# ---------------------------------------------------------------------------
# Unit surfaces: peak table, cost normalization, env knobs.
# ---------------------------------------------------------------------------


def test_peak_table_lookup_and_override(monkeypatch):
    assert lookup_peak_flops("TPU v5p") == 459e12
    assert lookup_peak_flops("TPU v5 lite") == 197e12
    assert lookup_peak_flops("TPU v4") == 275e12
    assert lookup_peak_flops("cpu") is None          # honest unknown
    # explicit arg beats everything; env beats the table; n_devices
    # scales the per-chip number to the mesh.
    reg = MetricsRegistry(event_log=None)
    t = DeviceTelemetry(reg, n_devices=4, peak_flops=1e12)
    assert t.peak_flops == 4e12 and t.peak_source == "arg"
    monkeypatch.setenv("HVD_TPU_PEAK_FLOPS", "2e12")
    t = DeviceTelemetry(MetricsRegistry(event_log=None))
    assert t.peak_flops == 2e12 and t.peak_source == "env"
    monkeypatch.setenv("HVD_TPU_PEAK_FLOPS", "not-a-float")
    with pytest.warns(RuntimeWarning, match="HVD_TPU_PEAK_FLOPS"):
        t = DeviceTelemetry(MetricsRegistry(event_log=None))
    assert t.peak_flops is None                      # CPU: no table hit
    assert t.peak_source is None and not t.peak_flops_known


def test_cost_analysis_is_one_dict_on_the_pinned_jax():
    # The capture reads compiled.cost_analysis() as one dict (or None when
    # the backend has no cost model); older jax returned a list of them.
    import jax
    import jax.numpy as jnp

    cost = jax.jit(lambda x: x @ x).lower(jnp.ones((8, 8))).compile() \
        .cost_analysis()
    assert isinstance(cost, dict) and cost["flops"] > 0


def test_poll_and_window_knobs(monkeypatch):
    monkeypatch.setenv("HVD_TPU_DEVICE_POLL_S", "0.25")
    assert DeviceTelemetry(MetricsRegistry(event_log=None)).poll_s == 0.25
    monkeypatch.setenv("HVD_TPU_DEVICE_POLL_S", "junk")
    assert DeviceTelemetry(MetricsRegistry(event_log=None)).poll_s == 1.0
    with pytest.raises(ValueError):
        DeviceTelemetry(MetricsRegistry(event_log=None), window=0)


def test_env_factory_and_engine_knob(world, monkeypatch):
    monkeypatch.delenv("HVD_TPU_DEVICE_TELEMETRY", raising=False)
    assert maybe_telemetry(MetricsRegistry(event_log=None)) is None
    assert _engine(world).device is None
    monkeypatch.setenv("HVD_TPU_DEVICE_TELEMETRY", "1")
    eng = _engine(world)
    assert isinstance(eng.device, DeviceTelemetry)
    # explicit argument beats the env
    assert _engine(world, device_telemetry=False).device is None


# ---------------------------------------------------------------------------
# Acceptance 1: cost capture on every pinned program.
# ---------------------------------------------------------------------------


def test_cost_capture_all_four_programs(world):
    eng = _engine(world, spec=True, device_telemetry=True)
    out = eng.run(_reqs(4))
    assert all(r.status == OK for r in out)
    rep = eng.metrics_snapshot()["device"]
    assert set(rep["programs"]) == set(PROGRAMS)
    for name in PROGRAMS:
        row = rep["programs"][name]
        assert "error" not in row
        assert row["flops"] > 0.0
        assert row["bytes_accessed"] > 0.0
        assert row["compile_s"] > 0.0
    # the programs that served this workload were counted per dispatch
    assert rep["programs"]["chunk"]["dispatches"] > 0
    assert rep["programs"]["set_row"]["dispatches"] > 0
    assert rep["programs"]["spec_tick"]["dispatches"] > 0
    # spec engines never call plain tick, nor the sampling program in
    # front of it: captured, zero dispatches
    assert rep["programs"]["tick"]["dispatches"] == 0
    assert rep["programs"]["sample"]["dispatches"] == 0
    # compile ledger: one timed AOT compile per captured program
    assert rep["compiles"] == len(PROGRAMS)
    assert rep["compile_total_s"] > 0.0
    assert eng.metrics.counter("device.compiles").value == len(PROGRAMS)
    assert eng.metrics.histogram("device.compile_s").count == \
        len(PROGRAMS)


def test_captured_tick_flops_in_analytic_band(world):
    # Hand-computed sanity band: a dense decode step is matmul-
    # dominated, ~2 FLOPs per parameter per token, batch = n_slots.
    # The XLA cost model adds attention/normalization on top, so pin
    # the captured number between 1x and 10x the matmul floor.
    cfg, params = world
    eng = _engine(world, device_telemetry=True)
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree.leaves(params))
    floor = 2.0 * n_params                  # one token through the net
    tick_flops = eng.device.programs["tick"]["flops"]
    assert floor <= tick_flops <= 10.0 * floor * eng.n_slots


# ---------------------------------------------------------------------------
# Acceptance 2: free and harmless.
# ---------------------------------------------------------------------------


def test_telemetry_on_off_parity(world):
    reqs = _reqs(6)
    off = _engine(world)
    out_off = off.run(reqs)
    on = _engine(world, device_telemetry=True)
    out_on = on.run(reqs)
    assert [list(a) for a in out_on] == [list(b) for b in out_off]
    assert all(r.status == OK for r in out_on)
    # AOT capture minted NO jit call-cache entries: one signature per
    # program, same as off — and the sentry never fired.
    assert on.compile_cache_sizes() == off.compile_cache_sizes() == \
        {"sample": 1, "tick": 1, "chunk": 1, "set_row": 1}
    assert on.metrics.counter("serve.retrace").value == 0
    snap = on.metrics_snapshot()
    assert "device" in snap
    assert "device" not in off.metrics_snapshot()
    # transfer stamps accumulated on the on-engine only
    assert snap["counters"]["device.h2d_bytes"] > 0
    assert snap["counters"]["device.d2h_bytes"] > 0
    assert snap["device"]["win"]["h2d_bytes"] > 0
    assert snap["device"]["ticks"] == on.step_index
    # state_dump carries the human-readable device line
    assert "device:" in on.state_dump()
    assert "device:" not in off.state_dump()


def test_retrace_charged_with_compile_cost(world):
    eng = _engine(world, device_telemetry=True)
    out = eng.run(_reqs(3))
    assert all(r.status == OK for r in out)
    assert eng.device.retraces == 0
    compiles0 = eng.metrics.counter("device.compiles").value
    # the profiler suite's deliberately unpinned call: a python int
    # where the engine always passes a device scalar
    eng.pcache = eng._set_row(
        eng.pcache, 0, jnp.asarray(eng._trash_row),
        jnp.asarray(0, jnp.int32))
    eng.step()
    assert eng.metrics.counter("serve.retrace").value == 1
    assert eng.device.retraces == 1
    # the ledger charged the regrown program's captured compile cost
    assert eng.device.retrace_compile_est_s == pytest.approx(
        eng.device.programs["set_row"]["compile_s"])
    assert eng.metrics.counter("device.compiles").value == compiles0 + 1
    rep = eng.device.report()
    assert rep["retraces"] == 1
    assert rep["retrace_compile_est_s"] > 0.0


# ---------------------------------------------------------------------------
# Acceptance 3: honest MFU arithmetic.
# ---------------------------------------------------------------------------


def test_mfu_arithmetic_with_pinned_peak(world):
    cfg, params = world
    reg = MetricsRegistry(event_log=None)
    peak = 1e15                               # pinned: MFU is honest
    dtel = DeviceTelemetry(reg, peak_flops=peak)
    eng = ServeEngine(params, cfg, n_slots=2, max_len=32, chunk=8,
                      metrics=reg, monitor=False, device_telemetry=dtel)
    out = eng.run(_reqs(5))
    assert all(r.status == OK for r in out)
    rep = eng.device.report()
    assert rep["peak_flops"] == peak
    assert rep["peak_flops_source"] == "arg" and rep["peak_flops_known"]
    w = rep["win"]
    assert w["n"] > 0 and w["elapsed_s"] > 0.0 and w["flops"] > 0.0
    # MFU is exactly achieved FLOPs/s over peak, and the live gauge
    # carries the same number the report computes
    assert w["mfu"] == pytest.approx(
        w["flops"] / w["elapsed_s"] / peak, rel=1e-12)
    assert w["flops_per_s"] == pytest.approx(
        w["flops"] / w["elapsed_s"], rel=1e-12)
    snap = reg.snapshot()
    assert snap["gauges"]["serve.mfu"] == pytest.approx(w["mfu"])
    assert snap["gauges"]["device.peak_flops_known"] == 1
    assert snap["gauges"]["serve.arithmetic_intensity"] == \
        pytest.approx(w["flops"] / w["bytes_accessed"])
    # with a peak, the sync split can prove a stall; the two halves
    # tile the measured sync wait exactly
    assert w["compute_est_s"] + w["host_stall_s"] == pytest.approx(
        w["sync_s"], rel=1e-9)
    assert w["host_stall_s"] >= 0.0
    assert 0.0 <= w["overlap_headroom_pct"] <= 100.0 + 1e-9


def test_sync_split_degenerates_without_peak():
    # no honest peak: we cannot prove any stall, so none is claimed
    t = DeviceTelemetry(MetricsRegistry(event_log=None))
    assert not t.peak_flops_known
    t.programs["tick"] = {"flops": 1e9, "bytes_accessed": 1.0,
                          "compile_s": 0.0, "dispatches": 0}
    est, stall = t.on_sync(("tick",), 0.0, 0.5)
    assert est == 0.5 and stall == 0.0
    # with a peak the predicted device time caps at the measured wait
    t2 = DeviceTelemetry(MetricsRegistry(event_log=None),
                         peak_flops=1e10)
    t2.programs["tick"] = {"flops": 1e9, "bytes_accessed": 1.0,
                           "compile_s": 0.0, "dispatches": 0}
    est, stall = t2.on_sync(("tick",), 0.0, 0.5)
    assert est == pytest.approx(0.1) and stall == pytest.approx(0.4)
    est, stall = t2.on_sync(("tick",), 0.0, 0.01)   # wait < prediction
    assert est == pytest.approx(0.01) and stall == 0.0


# ---------------------------------------------------------------------------
# Acceptance 4: CPU graceful degradation — absent, never zero.
# ---------------------------------------------------------------------------


def test_cpu_degradation_absent_not_zero(world):
    eng = _engine(world, device_telemetry=True)
    out = eng.run(_reqs(4))
    assert all(r.status == OK for r in out)
    rep = eng.metrics_snapshot()["device"]
    # CPU backend: no memory_stats, no honest peak
    assert rep["memory"] == {"available": False}
    assert rep["peak_flops"] is None and not rep["peak_flops_known"]
    assert rep["win"]["mfu"] is None
    assert "reconciliation" not in rep
    gauges = eng.metrics.snapshot()["gauges"]
    # the honest-absence contract: no gauge is EVER a fabricated zero
    assert "serve.mfu" not in gauges
    assert "device.bytes_in_use" not in gauges
    assert "device.peak_bytes_in_use" not in gauges
    assert "device.hbm_used_fraction" not in gauges
    assert gauges["device.peak_flops_known"] == 0
    # headroom IS known (it divides measured quantities)
    assert "device.overlap_headroom_pct" in gauges
    assert eng.device.poll_memory() is None


def test_report_reconciles_hbm_when_available():
    # build_report with a synthetic memory block: the reconciliation
    # section appears and framework overhead is the exact residue
    rep = build_report(
        platform="tpu", device_kind="TPU v4", n_devices=1,
        peak_flops=275e12, peak_flops_known=True, peak_source="table",
        programs={}, compiles=0, compile_total_s=0.0, retraces=0,
        retrace_compile_est_s=0.0, ticks=0, window=256, ring=[],
        memory={"available": True, "bytes_in_use": 1000,
                "peak_bytes_in_use": 1200, "bytes_limit": 2000},
        param_bytes=600, kv_total_bytes=300)
    rec = rep["reconciliation"]
    assert rec["model_bytes"] == 900
    assert rec["framework_overhead_bytes"] == 100
    assert rep["win"]["mfu"] is None         # no ticks: no dishonest 0


# ---------------------------------------------------------------------------
# Acceptance 5: the serving surface.
# ---------------------------------------------------------------------------


def test_device_endpoint_over_socket(world):
    import urllib.request
    eng = _engine(world, device_telemetry=True)
    mon = MonitorServer(eng.metrics, eng, port=0).start()
    try:
        eng.run(_reqs(3))
        url = f"http://{mon.host}:{mon.port}/device"
        with urllib.request.urlopen(url, timeout=5) as r:
            assert r.status == 200
            assert r.headers["Content-Type"] == "application/json"
            rep = json.loads(r.read())
        assert rep["ticks"] == eng.device.report()["ticks"]
        assert set(rep["programs"]) == {"sample", "tick", "chunk",
                                        "set_row"}
    finally:
        mon.stop()
    # telemetry off: /device 404s with the turn-it-on hint
    off = _engine(world)
    mon = MonitorServer(off.metrics, off, port=0).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                f"http://{mon.host}:{mon.port}/device", timeout=5)
        assert exc.value.code == 404
        assert b"HVD_TPU_DEVICE_TELEMETRY" in exc.value.read()
    finally:
        mon.stop()


def test_router_fleet_device_view(world):
    import urllib.request
    cfg, params = world
    engines = [ServeEngine(params, cfg, n_slots=2, max_len=32, chunk=8,
                           device_telemetry=(i == 0))
               for i in range(2)]
    router = RouterServer(engines, policy="round_robin").start()
    try:
        rep = router.device_report()
        assert len(rep["replicas"]) == 1
        assert rep["without_telemetry"] == [
            n for n in sorted(r["name"]
                              for r in router.replicas_report())
            if n not in rep["replicas"]]
        assert rep["summary"]["n_reporting"] == 1
        (one,) = rep["replicas"].values()
        assert set(one["programs"]) == {"sample", "tick", "chunk",
                                        "set_row"}
        # and over the wire
        url = f"http://{router.host}:{router.port}/device"
        with urllib.request.urlopen(url, timeout=10) as r:
            assert json.loads(r.read())["summary"]["n_reporting"] == 1
    finally:
        router.stop()


def test_event_log_replay_matches_live_report(world, tmp_path):
    from tools.device_report import compare_reports, load_report, render
    log = str(tmp_path / "events.jsonl")
    reg = MetricsRegistry(event_log=metrics_mod.EventLog(log))
    eng = _engine(world, metrics=reg, device_telemetry=True)
    eng.run(_reqs(4))
    live = eng.device.report()
    replay = load_report(log)
    # the replay rebuilds the same schema from the event log alone
    assert replay["platform"] == live["platform"]
    assert replay["ticks"] == live["ticks"]
    assert set(replay["programs"]) == set(live["programs"])
    for name, row in live["programs"].items():
        rrow = replay["programs"][name]
        assert rrow["flops"] == row["flops"]
        assert rrow["bytes_accessed"] == row["bytes_accessed"]
        assert rrow["dispatches"] == row["dispatches"]
    for k in ("n", "flops", "h2d_bytes", "d2h_bytes"):
        assert replay["win"][k] == live["win"][k]
    for k in ("elapsed_s", "sync_s", "compute_est_s", "host_stall_s"):
        assert replay["win"][k] == pytest.approx(live["win"][k],
                                                 rel=1e-9)
    assert replay["win"]["mfu"] is None is live["win"]["mfu"]
    # --window replays only the tail
    tail = report_from_events(
        [json.loads(ln) for ln in open(log)], window=2)
    assert tail["win"]["n"] == 2
    # render never crashes, names every program, says honest things
    text = render(replay)
    for name in live["programs"]:
        assert name in text
    assert "unknown (no MFU)" in text
    assert "no memory_stats" in text
    # a saved report and a full snapshot dump both round-trip
    saved = tmp_path / "rep.json"
    saved.write_text(json.dumps(live))
    assert load_report(str(saved))["ticks"] == live["ticks"]
    snap = tmp_path / "snap.json"
    snap.write_text(json.dumps(eng.metrics_snapshot()))
    assert load_report(str(snap))["ticks"] == live["ticks"]
    # same-vs-same is clean (the MFU axis honestly skipped: no peak)
    rows = compare_reports(live, replay)
    assert not any(r["regressed"] for r in rows)
    assert "mfu" not in {r["metric"] for r in rows}


def _report_with(peak, flops, stall_s=0.001):
    ring = [{"step": i, "dt_s": 0.01, "flops": flops,
             "bytes_accessed": 2 * flops, "h2d_bytes": 64,
             "d2h_bytes": 8, "sync_s": 0.004 + stall_s,
             "compute_est_s": 0.004, "host_stall_s": stall_s,
             "dispatches": {"tick": 1}} for i in range(10)]
    return build_report(
        platform="tpu", device_kind="TPU v4", n_devices=1,
        peak_flops=peak, peak_flops_known=peak is not None,
        peak_source="arg" if peak else None, programs={}, compiles=3,
        compile_total_s=1.0, retraces=0, retrace_compile_est_s=0.0,
        ticks=10, window=256, ring=ring, memory=None, param_bytes=0,
        kv_total_bytes=0)


def test_compare_trips_on_mfu_regression(tmp_path):
    from tools.device_report import compare_reports, main
    old = _report_with(1e12, 1e9)
    good = _report_with(1e12, 0.99e9)          # -1 %: inside threshold
    bad = _report_with(1e12, 0.5e9)            # -50 %: a real MFU drop
    assert old["win"]["mfu"] == pytest.approx(1e9 / 0.01 / 1e12)
    assert not any(r["regressed"] for r in compare_reports(old, good))
    rows = compare_reports(old, bad, threshold_pct=10.0)
    flagged = {r["metric"] for r in rows if r["regressed"]}
    assert "mfu" in flagged and "flops_per_s" in flagged
    # one side without an honest peak: the MFU axis is unjudgeable
    rows = compare_reports(_report_with(None, 1e9), bad)
    assert "mfu" not in {r["metric"] for r in rows}
    # host stall regresses on growth past threshold AND the ms floor
    # (headroom is compute_est/dt, untouched by a pure stall change)
    worse = _report_with(1e12, 1e9, stall_s=0.003)
    rows = compare_reports(old, worse)
    assert {r["metric"] for r in rows if r["regressed"]} == \
        {"host_stall_ms_per_tick"}
    # the CLI gate: exit 1 on the doctored drop, 0 on same-vs-same
    po, pb = tmp_path / "old.json", tmp_path / "bad.json"
    po.write_text(json.dumps(old))
    pb.write_text(json.dumps(bad))
    assert main(["--compare", str(po), str(po)]) == 0
    assert main(["--compare", str(po), str(pb)]) == 1


# ---------------------------------------------------------------------------
# Profiler nesting: the device_sync split rides the phase report.
# ---------------------------------------------------------------------------


def test_sync_split_feeds_nested_profiler_phases(world):
    from tools.profile_report import render
    eng = _engine(world, profile=True, device_telemetry=True)
    out = eng.run(_reqs(4))
    assert all(r.status == OK for r in out)
    rep = eng.prof.report()
    # the split covers the readback interval INSIDE device_sync: its
    # halves sum to the telemetry window's measured sync time exactly,
    # and never exceed the enclosing phase (which also holds the
    # dispatch bookkeeping around the readback)
    assert rep["phases"]["device_sync.compute_est"]["count"] > 0
    split = (rep["phases"]["device_sync.compute_est"]["total_s"]
             + rep["phases"]["device_sync.host_stall"]["total_s"])
    assert split == pytest.approx(
        eng.device.report()["win"]["sync_s"], rel=1e-6)
    assert 0.0 < split <= rep["phases"]["device_sync"]["total_s"]
    # CPU: no honest peak, so no stall is ever claimed
    assert rep["phases"]["device_sync.host_stall"]["total_s"] == 0.0
    # nested intervals stay OUT of the coverage base: still ~100 %
    assert 0.9 <= rep["coverage"] <= 1.0 + 1e-9
    # both renderers indent the split under its parent
    text = render(rep)
    assert text.index("device_sync ") < text.index(
        "  device_sync.compute_est")
    assert "  device_sync.host_stall" in text


# ---------------------------------------------------------------------------
# The HBM exhaustion alert rule.
# ---------------------------------------------------------------------------


def test_device_hbm_exhaustion_rule_fires_and_resolves():
    assert "device_hbm_exhaustion" in rule_names()
    rules = [r for r in ALERT_RULES
             if r["name"] == "device_hbm_exhaustion"]
    reg = MetricsRegistry(event_log=None)
    clk = Clock(0.0)
    s = MetricsSampler(reg, sample_s=1.0, clock=clk)
    # 0.1 scale: window 3 s, pending 1 s, clear 6 s
    am = AlertManager(s, rules=rules, registry=reg, time_scale=0.1,
                      clock=clk)
    g = reg.gauge("device.hbm_used_fraction")

    def step(v: float) -> None:
        clk.t += 1.0
        g.set(v)
        s.tick()
        am.tick()

    for _ in range(4):
        step(0.5)                      # healthy fraction
    assert am.firing() == []
    for _ in range(5):                 # windowed mean crosses 0.92,
        step(0.97)                     # then sustains past pending_s
    assert am.firing() == ["device_hbm_exhaustion"]
    for _ in range(10):
        step(0.5)                      # drained; clear_s elapses
    st = am.states()["device_hbm_exhaustion"]
    assert st["fired"] == 1 and st["resolved"] == 1
    assert am.firing() == []
