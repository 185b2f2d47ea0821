"""Health-plane tests: the time-series sampler, SLO burn-rate alert
rules, the capacity advisor, and the health-report tool.

Everything here drives virtual clocks through the public seams
(``MetricsSampler.ingest`` / ``tick(now)``, ``AlertManager`` with an
injected ``clock``) — no sleeps, no threads, no engines.  Degraded
inputs (torn snapshots, counter resets, missing ranks, quiet windows)
get explicit coverage because the alert evaluator's contract is
"no-data holds state, never flaps".
"""

from __future__ import annotations

import bisect
import importlib.util
import json
import os

import pytest

from horovod_tpu import alerts as alerts_mod
from horovod_tpu import metrics as metrics_mod
from horovod_tpu import timeseries as timeseries_mod
from horovod_tpu.alerts import (
    ALERT_RULES, AlertManager, CapacityAdvisor, rule_names)
from horovod_tpu.metrics import EventLog, MetricsRegistry
from horovod_tpu.monitor import merge_snapshots
from horovod_tpu.timeseries import MetricsSampler, merge_series

pytestmark = pytest.mark.alerts


@pytest.fixture(scope="module")
def health_mod():
    spec = importlib.util.spec_from_file_location(
        "health_report",
        os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "health_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Clock:
    """Mutable virtual clock passed as ``clock=``."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def _rules(*names: str) -> list[dict]:
    picked = [r for r in ALERT_RULES if r["name"] in names]
    assert len(picked) == len(names)
    return picked


# ---------------------------------------------------------------------------
# MetricsSampler: tiers, rates, percentiles, degraded inputs.
# ---------------------------------------------------------------------------


def test_sampler_counter_rates_and_aligned_tiers():
    reg = MetricsRegistry(event_log=None)
    clk = Clock(0.0)
    s = MetricsSampler(reg, sample_s=1.0, clock=clk)
    c = reg.counter("serve.requests_completed")
    for _ in range(25):
        c.inc(2)
        clk.t += 1.0
        assert s.tick()
    # First sample only establishes the baseline; every later point
    # carries the 2/s rate.
    pts = s.window("serve.requests_completed", 30.0, now=clk.t)
    assert len(pts) == 24
    assert all(p["rate"] == pytest.approx(2.0) for p in pts)
    r = s.counter_rate("serve.requests_completed", 10.0, now=clk.t)
    assert r["n"] == 11 and r["rate"] == pytest.approx(2.0)
    # The 10s tier holds flushed buckets on aligned timestamps with
    # the deltas summed.
    rep = s.report()
    ten = rep["tiers"]["10s"]["series"]["serve.requests_completed"]
    assert ten["kind"] == "counter"
    assert all(p["t"] % 10.0 == 0.0 for p in ten["points"])
    assert any(p["delta"] == pytest.approx(20.0) for p in ten["points"])
    assert rep["sample_s"] == 1.0 and rep["now"] == clk.t
    snap = reg.snapshot()["counters"]
    assert snap["ts.samples"] == 25
    assert reg.snapshot()["gauges"]["ts.series"] >= 1


def test_sampler_counter_reset_clamps_at_zero():
    s = MetricsSampler(MetricsRegistry(event_log=None), sample_s=1.0,
                      clock=Clock(0.0))
    s.ingest(1.0, {"counters": {"supervisor.respawns": 100.0}})
    s.ingest(2.0, {"counters": {"supervisor.respawns": 10.0}})  # reset
    s.ingest(3.0, {"counters": {"supervisor.respawns": 13.0}})
    pts = s.window("supervisor.respawns", 10.0, now=3.0)
    # The respawn reset yields a zero-rate sample, never a negative
    # one; counting resumes from the post-reset baseline.
    assert [p["delta"] for p in pts] == [0.0, 3.0]
    assert all(p["rate"] >= 0.0 for p in pts)


def test_sampler_gauge_envelope_and_slope():
    s = MetricsSampler(MetricsRegistry(event_log=None), sample_s=1.0,
                      clock=Clock(0.0))
    for i in range(10):
        s.ingest(float(i), {"gauges": {"kv.free_blocks": 100.0 - 10.0 * i}})
    st = s.gauge_stats("kv.free_blocks", 20.0, now=9.0)
    assert st["n"] == 10
    assert st["last"] == 10.0 and st["min"] == 10.0 and st["max"] == 100.0
    assert s.slope_per_s("kv.free_blocks", 20.0, now=9.0) == \
        pytest.approx(-10.0)
    # Fewer than 3 points -> no slope.
    assert s.slope_per_s("kv.free_blocks", 0.5, now=9.0) is None


def test_sampler_hist_deltas_keep_percentiles_exact():
    reg = MetricsRegistry(event_log=None)
    clk = Clock(0.0)
    s = MetricsSampler(reg, sample_s=1.0, clock=clk)
    h = reg.histogram("serve.ttft_s")
    clk.t = 1.0
    s.tick()                                   # histogram baseline
    for v in (0.004, 0.005, 0.006, 0.2):
        h.observe(v)
    clk.t = 2.0
    s.tick()
    win = s.hist_window("serve.ttft_s", 5.0, now=2.0)
    assert win["count"] == 4
    # All observations landed in this one window, so the summed deltas
    # ARE the live bucket counts.
    assert win["buckets"] == reg.snapshot()["histograms"][
        "serve.ttft_s"]["buckets"]
    # Exact at bucket resolution: the windowed p99 lands inside the
    # bucket that holds the 0.2 observation.
    p99 = s.hist_percentile("serve.ttft_s", 5.0, 0.99, now=2.0)
    i = bisect.bisect_left(win["bounds"], 0.2)
    lo = win["bounds"][i - 1] if i > 0 else 0.0
    hi = win["bounds"][i] if i < len(win["bounds"]) else win["bounds"][-1]
    assert lo <= p99 <= hi


def test_sampler_hist_end_offset_separates_baseline_window():
    reg = MetricsRegistry(event_log=None)
    clk = Clock(0.0)
    s = MetricsSampler(reg, sample_s=1.0, clock=clk)
    h = reg.histogram("serve.ttft_s")
    clk.t = 1.0
    s.tick()
    for _ in range(50):
        h.observe(0.002)
    clk.t = 2.0
    s.tick()
    for _ in range(50):
        h.observe(0.3)
    clk.t = 3.0
    s.tick()
    # The drift rule's two windows: recent vs the window just before.
    cur = s.hist_percentile("serve.ttft_s", 0.5, 0.99, now=3.0)
    base = s.hist_percentile("serve.ttft_s", 0.5, 0.99, now=3.0,
                             end_offset_s=1.0)
    assert base < 0.01 < cur
    assert cur / base > 2.0


def test_sampler_tolerates_torn_and_partial_snapshots():
    s = MetricsSampler(MetricsRegistry(event_log=None), sample_s=1.0,
                      clock=Clock(0.0))
    assert s.ingest(1.0, {"gauges": {"g": 1.0}})
    assert not s.ingest(1.5, {"gauges": {"g": 2.0}})   # inside sample_s
    assert not s.ingest(3.0, "torn")                    # not a dict
    # Malformed histogram entries and non-numeric values skip, never
    # raise; the good parts of the same snapshot still land.
    assert s.ingest(3.5, {"histograms": {"h1": "torn",
                                         "h2": {"count": 3},
                                         "h3": {"buckets": [1],
                                                "bounds": "x"}},
                          "counters": {"c": "nan?"},
                          "gauges": {"g": 4.0, "g2": None}})
    assert set(s.report()["tiers"]["raw"]["series"]) == {"g"}
    # A bounds change (histogram re-registered across a respawn)
    # re-baselines instead of emitting garbage deltas.
    s.ingest(5.0, {"histograms": {"h4": {"count": 1, "sum": 1.0,
                                         "buckets": [1, 0],
                                         "bounds": [1.0]}}})
    s.ingest(6.0, {"histograms": {"h4": {"count": 2, "sum": 2.0,
                                         "buckets": [1, 1, 0],
                                         "bounds": [1.0, 2.0]}}})
    s.ingest(7.0, {"histograms": {"h4": {"count": 3, "sum": 3.0,
                                         "buckets": [1, 2, 0],
                                         "bounds": [1.0, 2.0]}}})
    pts = [p for p in s.window("h4", 10.0, now=7.0) if "buckets" in p]
    assert len(pts) == 1 and pts[0]["buckets"] == [0, 1, 0]


def test_merge_series_sums_ranks_and_degrades_on_missing_rank():
    def feed(s, upto):
        for i in range(upto):
            t = float(i + 1)
            s.ingest(t, {"counters": {"c": 2.0 * t},
                         "gauges": {"g": 10.0 + t}})
    s0 = MetricsSampler(MetricsRegistry(event_log=None), sample_s=1.0)
    s1 = MetricsSampler(MetricsRegistry(event_log=None), sample_s=1.0)
    feed(s0, 5)
    feed(s1, 3)                        # rank 1 died after t=3
    merged = merge_series([s0.report(), "torn", s1.report()],
                          ranks=[0, 1])
    assert merged["ranks"] == [0, 1]
    raw = merged["tiers"]["raw"]["series"]
    by_t = {p["t"]: p for p in raw["c"]["points"]}
    # Both ranks present: rates sum.  Rank 1 missing: merge from the
    # rank that has the bucket — degraded coverage, not an error.
    assert by_t[2.0]["ranks"] == 2
    assert by_t[2.0]["rate"] == pytest.approx(4.0)
    assert by_t[5.0]["ranks"] == 1
    assert by_t[5.0]["rate"] == pytest.approx(2.0)
    g2 = {p["t"]: p for p in raw["g"]["points"]}[2.0]
    assert g2["min"] == g2["max"] == g2["mean"] == pytest.approx(12.0)
    assert g2["n"] == 2


def test_merge_snapshots_carries_timeseries_section():
    s0 = MetricsSampler(MetricsRegistry(event_log=None), sample_s=1.0)
    s0.ingest(1.0, {"gauges": {"serve.goodput": 1.0}})
    snaps = [{"counters": {}, "gauges": {}, "histograms": {},
              "timeseries": s0.report()},
             {"counters": {}, "gauges": {}, "histograms": {}}]
    merged = merge_snapshots(snaps)
    assert "timeseries" in merged
    assert "serve.goodput" in \
        merged["timeseries"]["tiers"]["raw"]["series"]


# ---------------------------------------------------------------------------
# AlertManager: rule kinds, state machine, hysteresis, no-data holds.
# ---------------------------------------------------------------------------


def _burn_setup(event_log=None, time_scale=0.1):
    reg = MetricsRegistry(event_log=event_log)
    clk = Clock(0.0)
    s = MetricsSampler(reg, sample_s=1.0, clock=clk)
    am = AlertManager(s, rules=_rules("goodput_burn_fast"),
                      registry=reg, time_scale=time_scale, clock=clk)
    g = reg.gauge("serve.goodput")

    def step(v: float) -> None:
        clk.t += 1.0
        g.set(v)
        s.tick()
        am.tick()

    return reg, am, step


def test_goodput_burn_fast_fires_and_resolves_with_hysteresis():
    # time_scale 0.1: short 3 s, long 30 s, clear 6 s, pending 0.
    reg, am, step = _burn_setup()
    for _ in range(5):
        step(1.0)
    assert am.firing() == []
    for _ in range(4):
        step(0.5)                      # burn 50x once both windows sag
    assert am.firing() == ["goodput_burn_fast"]
    st = am.states()["goodput_burn_fast"]
    assert st["fired"] == 1 and st["ever_true"] and not st["no_data"]
    # Recovery: the clear_s hysteresis holds the alert while the short
    # window still remembers the dip...
    for _ in range(3):
        step(1.0)
    assert am.firing() == ["goodput_burn_fast"]
    # ...then sustained health resolves it exactly once (dedup).
    for _ in range(12):
        step(1.0)
    assert am.firing() == []
    st = am.states()["goodput_burn_fast"]
    assert st["fired"] == 1 and st["resolved"] == 1
    assert [tr["event"] for tr in am.report()["history"]] == \
        ["fire", "resolve"]
    counters = reg.snapshot()["counters"]
    assert counters["alert.fired"] == 1
    assert counters["alert.resolved"] == 1
    assert counters["alert.evals"] > 0


def test_goodput_burn_slow_needs_both_windows():
    # The multi-window pair: a blip that sags the short window but not
    # the long one must NOT trip the slow burn (condition is min of
    # the two burns).
    reg = MetricsRegistry(event_log=None)
    clk = Clock(0.0)
    s = MetricsSampler(reg, sample_s=1.0, clock=clk)
    am = AlertManager(s, rules=_rules("goodput_burn_slow"),
                      registry=reg, time_scale=0.01, clock=clk)
    g = reg.gauge("serve.goodput")
    # 0.01 scale: short 3 s, long 18 s, pending 0.6 s.
    for i in range(18):
        clk.t += 1.0
        g.set(0.9 if 12 <= i < 15 else 1.0)   # 3 s blip in an 18 s run
        s.tick()
        am.tick()
    st = am.states()["goodput_burn_slow"]
    # Short-window burn exceeded 2x during the blip, long-window burn
    # stayed under it -> never even pending->fired.
    assert st["fired"] == 0
    assert am.firing() == []


def test_threshold_pending_cancel_fire_and_no_data_holds_state():
    # straggler_skew at 0.1 scale: window 6 s, pending 3 s, clear 6 s.
    reg = MetricsRegistry(event_log=None)
    clk = Clock(0.0)
    s = MetricsSampler(reg, sample_s=1.0, clock=clk)
    am = AlertManager(s, rules=_rules("straggler_skew"),
                      registry=reg, time_scale=0.1, clock=clk)
    g = reg.gauge("hvd.step_skew_s")

    def step(v: float) -> None:
        clk.t += 1.0
        g.set(v)
        s.tick()
        am.tick()

    for _ in range(3):
        step(0.0)
    step(5.0)                          # windowed mean crosses 1 s
    assert am.states()["straggler_skew"]["state"] == "pending"
    step(0.0)                          # mean back under -> cancel
    assert am.states()["straggler_skew"]["state"] == "ok"
    assert am.states()["straggler_skew"]["fired"] == 0
    for _ in range(4):                 # sustained past pending_s
        step(5.0)
    assert am.firing() == ["straggler_skew"]
    # No data in the window (sampler quiet, e.g. a torn scrape gap):
    # the rule HOLDS firing instead of flapping to ok.
    clk.t += 50.0
    am.evaluate(clk.t)
    st = am.states()["straggler_skew"]
    assert st["state"] == "firing" and st["no_data"]
    # Fresh healthy samples with clear_s long elapsed -> resolve.
    step(0.0)
    assert am.firing() == []
    events = [tr["event"] for tr in am.report()["history"]]
    assert events == ["pending", "cancel", "pending", "fire", "resolve"]


def test_ttft_p99_drift_fires_on_doubling_then_resolves():
    # 0.1 scale: recent 6 s, baseline 60 s, pending 3 s, clear 12 s.
    reg = MetricsRegistry(event_log=None)
    clk = Clock(0.0)
    s = MetricsSampler(reg, sample_s=1.0, clock=clk)
    am = AlertManager(s, rules=_rules("ttft_p99_drift"),
                      registry=reg, time_scale=0.1, clock=clk)
    h = reg.histogram("serve.ttft_s")

    def step(v: float) -> None:
        clk.t += 1.0
        for _ in range(20):
            h.observe(v)
        s.tick()
        am.tick()

    for _ in range(11):
        step(0.002)                    # healthy baseline era
    assert am.firing() == []
    for _ in range(7):
        step(0.3)                      # 150x the baseline p99
    assert am.firing() == ["ttft_p99_drift"]
    for _ in range(30):
        step(0.002)                    # back to healthy
    st = am.states()["ttft_p99_drift"]
    assert st["fired"] == 1 and st["resolved"] == 1
    assert am.firing() == []


def test_kv_exhaustion_slope_projects_time_to_zero():
    # 0.1 scale: window 12 s, horizon 30 s, clear 6 s, pending 0.
    reg = MetricsRegistry(event_log=None)
    clk = Clock(0.0)
    s = MetricsSampler(reg, sample_s=1.0, clock=clk)
    am = AlertManager(s, rules=_rules("kv_exhaustion"),
                      registry=reg, time_scale=0.1, clock=clk)
    g = reg.gauge("kv.free_blocks")
    v = 400.0

    def step(dv: float) -> None:
        nonlocal v
        clk.t += 1.0
        v += dv
        g.set(v)
        s.tick()
        am.tick()

    for _ in range(4):
        step(-20.0)                    # draining 20 blocks/s
    st = am.states()["kv_exhaustion"]
    assert am.firing() == ["kv_exhaustion"]
    assert st["value"] <= 30.0         # projected time-to-zero
    for _ in range(20):
        step(0.0)                      # drain stopped; slope flattens
    st = am.states()["kv_exhaustion"]
    assert st["fired"] == 1 and st["resolved"] == 1
    assert am.firing() == []


def test_replica_death_and_replica_flap_delta_rules():
    # 0.1 scale: death window 6 s / clear 6 s (min_delta 1); flap
    # window 30 s / clear 30 s (min_delta 3).
    reg = MetricsRegistry(event_log=None)
    clk = Clock(0.0)
    s = MetricsSampler(reg, sample_s=1.0, clock=clk)
    am = AlertManager(s, rules=_rules("replica_death", "replica_flap"),
                      registry=reg, time_scale=0.1, clock=clk)
    deaths = reg.counter("router.replica_deaths")
    respawns = reg.counter("supervisor.respawns")

    def step() -> None:
        clk.t += 1.0
        s.tick()
        am.tick()

    for _ in range(3):
        step()
    assert am.firing() == []
    deaths.inc()
    respawns.inc()
    step()
    # One death pages immediately; one respawn is not yet a flap.
    assert am.firing() == ["replica_death"]
    respawns.inc()
    step()
    respawns.inc()
    step()
    assert am.firing() == ["replica_death", "replica_flap"]
    for _ in range(70):                # both windows drain + clear
        step()
    assert am.firing() == []
    st = am.states()
    assert st["replica_death"]["fired"] == 1
    assert st["replica_death"]["resolved"] == 1
    assert st["replica_flap"]["fired"] == 1
    assert st["replica_flap"]["resolved"] == 1


def test_alert_report_shape_and_rule_table():
    reg, am, step = _burn_setup()
    for _ in range(5):
        step(1.0)
    rep = am.report()
    assert rep["firing"] == [] and rep["pending"] == []
    assert rep["time_scale"] == 0.1
    (rule,) = rep["rules"]
    assert rule["name"] == "goodput_burn_fast"
    assert rule["state"] == "ok" and rule["fired"] == 0
    json.dumps(rep)                    # the /alerts payload serializes
    # The docs table renders every canonical rule from the same
    # literal the linter extracts.
    table = alerts_mod.render_alert_table()
    for name in rule_names():
        assert f"`{name}`" in table
    assert len(ALERT_RULES) == len(set(rule_names()))


# ---------------------------------------------------------------------------
# CapacityAdvisor.
# ---------------------------------------------------------------------------


def _advised(gauges_by_t, counters_by_t=None, knee=None, **kw):
    reg = MetricsRegistry(event_log=None)
    clk = Clock(0.0)
    s = MetricsSampler(reg, sample_s=1.0, clock=clk)
    for t in sorted(gauges_by_t):
        snap = {"gauges": gauges_by_t[t]}
        if counters_by_t:
            snap["counters"] = counters_by_t.get(t, {})
        s.ingest(float(t), snap)
        clk.t = float(t)
    adv = CapacityAdvisor(s, registry=reg, load_report=knee,
                          window_s=10.0, clock=clk, **kw)
    return reg, adv


def test_advisor_holds_without_goodput_samples():
    reg, adv = _advised({})
    rec = adv.recommend()
    assert rec["action"] == "hold" and rec["n"] == 0
    assert "no goodput samples" in rec["reason"]
    assert reg.snapshot()["counters"]["advisor.recommendations"] == 1


def test_advisor_scales_up_sized_by_knee_demand():
    knee = {"serve_load_knee_goodput_rps": 2.0}
    gauges = {i: {"serve.goodput": 0.9,
                  "router.replicas_healthy": 2.0,
                  "serve.queue_depth": 2.0 * i}       # growing backlog
              for i in range(1, 7)}
    counters = {i: {"serve.requests_completed": 8.0 * i}
                for i in range(1, 7)}
    reg, adv = _advised(gauges, counters, knee=knee)
    rec = adv.recommend()
    # Demand-sized: ceil(8 rps / (2 * 0.8 headroom)) = 5 replicas
    # needed, 2 healthy -> +3.
    assert rec["action"] == "scale_up" and rec["n"] == 3
    assert "queue growing" in rec["reason"]
    assert rec["evidence"]["knee_goodput_rps"] == 2.0
    assert rec["evidence"]["replicas_healthy"] == 2
    assert reg.snapshot()["gauges"]["advisor.target_delta"] == 3
    assert adv.report()["last"] == rec


@pytest.mark.parametrize("configured", ["missing_path", "nothing"])
def test_advisor_scale_up_defaults_to_one_without_knee(
        tmp_path, monkeypatch, configured):
    # A report that merely lies in the working directory is not looked
    # up: the knee is what the advisor was given, or nothing.
    (tmp_path / "serve_load_report.json").write_text(
        json.dumps({"serve_load_knee_goodput_rps": 4.0}))
    monkeypatch.chdir(tmp_path)
    gauges = {i: {"serve.goodput": 0.5,
                  "router.replicas_healthy": 1.0,
                  "serve.queue_depth": 3.0 * i}
              for i in range(1, 7)}
    _, adv = _advised(gauges, knee=(str(tmp_path / "missing.json")
                                    if configured == "missing_path"
                                    else None))
    assert adv.load_knee() is None
    rec = adv.recommend()
    assert rec["action"] == "scale_up" and rec["n"] == 1
    assert rec["evidence"]["knee_goodput_rps"] is None


def test_advisor_scales_down_when_fleet_fits_fewer_replicas():
    knee = {"serve_load_knee_goodput_rps": 2.0}
    gauges = {i: {"serve.goodput": 1.0,
                  "router.replicas_healthy": 3.0,
                  "serve.queue_depth": 5.0}           # flat queue
              for i in range(1, 7)}
    counters = {i: {"serve.requests_completed": 0.5 * i}   # 0.5 rps
                for i in range(1, 7)}
    reg, adv = _advised(gauges, counters, knee=knee)
    rec = adv.recommend()
    # Trigger: 0.5 rps < knee * low_util * (n-1) = 2 * 0.3 * 2 = 1.2.
    # Demand-sized: ceil(0.5 / (2 * 0.8 headroom)) = 1 replica needed,
    # 3 healthy -> -2 (one survivor floor keeps it from -3).
    assert rec["action"] == "scale_down" and rec["n"] == 2
    assert "fits 1 replica" in rec["reason"]
    assert rec["evidence"]["headroom"] == 0.8
    assert reg.snapshot()["gauges"]["advisor.target_delta"] == -2


def test_advisor_holds_inside_the_envelope():
    knee = {"serve_load_knee_goodput_rps": 2.0}
    gauges = {i: {"serve.goodput": 1.0,
                  "router.replicas_healthy": 3.0,
                  "serve.queue_depth": 5.0}
              for i in range(1, 7)}
    counters = {i: {"serve.requests_completed": 3.0 * i}   # 3 rps
                for i in range(1, 7)}
    _, adv = _advised(gauges, counters, knee=knee)
    rec = adv.recommend()
    assert rec["action"] == "hold"
    assert rec["reason"] == "within envelope"


def test_advisor_knee_from_path_and_firing_alerts_escalate(tmp_path):
    report = tmp_path / "serve_load_report.json"
    report.write_text(json.dumps({"serve_load_knee_goodput_rps": 4.0}))
    reg = MetricsRegistry(event_log=None)
    clk = Clock(0.0)
    s = MetricsSampler(reg, sample_s=1.0, clock=clk)
    am = AlertManager(s, rules=_rules("goodput_burn_fast"),
                      registry=reg, time_scale=0.1, clock=clk)
    g = reg.gauge("serve.goodput")
    for _ in range(8):
        clk.t += 1.0
        g.set(0.5)                     # burning from the start
        s.tick()
        am.tick()
    adv = CapacityAdvisor(s, alerts=am, registry=reg,
                          load_report=str(report), window_s=10.0,
                          clock=clk)
    assert adv.load_knee() == {"serve_load_knee_goodput_rps": 4.0}
    rec = adv.recommend()
    # Sagging + alerts firing is enough even with a flat queue.
    assert rec["action"] == "scale_up"
    assert "alerts firing: goodput_burn_fast" in rec["reason"]
    assert rec["evidence"]["firing"] == ["goodput_burn_fast"]


# ---------------------------------------------------------------------------
# Env contracts.
# ---------------------------------------------------------------------------


def test_maybe_sampler_and_maybe_alerts_env_gates(monkeypatch):
    reg = MetricsRegistry(event_log=None)
    monkeypatch.setenv("HVD_TPU_SAMPLE_S", "0")
    assert timeseries_mod.maybe_sampler(reg) is None
    monkeypatch.setenv("HVD_TPU_SAMPLE_S", "0.25")
    s = timeseries_mod.maybe_sampler(reg)
    assert s is not None and s.sample_s == 0.25
    assert timeseries_mod.maybe_sampler(metrics_mod.NULL) is None
    monkeypatch.setenv("HVD_TPU_ALERTS", "0")
    assert alerts_mod.maybe_alerts(s) is None
    monkeypatch.delenv("HVD_TPU_ALERTS")
    am = alerts_mod.maybe_alerts(s, reg)
    assert am is not None and am.rules == tuple(ALERT_RULES)
    assert alerts_mod.maybe_alerts(None) is None


# ---------------------------------------------------------------------------
# tools/health_report.py: live scrape == event-log replay.
# ---------------------------------------------------------------------------


def test_health_report_live_scrape_matches_event_log_replay(
        health_mod, tmp_path):
    path = str(tmp_path / "events.jsonl")
    reg, am, step = _burn_setup(event_log=EventLog(path))
    for _ in range(5):
        step(1.0)
    for _ in range(4):
        step(0.5)
    for _ in range(15):
        step(1.0)                      # fire, then resolve
    live = health_mod.build_report(
        health_mod.timeline_from_alerts(am.report()),
        source="live", alerts=am.report())
    replay = health_mod.build_report(
        health_mod.timeline_from_events(health_mod.read_events(path)),
        source="replay")
    # The acceptance contract: identical transition sequences from the
    # live /alerts payload and the event-log replay.
    key = health_mod.timeline_key(live["timeline"])
    assert key == health_mod.timeline_key(replay["timeline"])
    assert key == [("goodput_burn_fast", "fire", "firing"),
                   ("goodput_burn_fast", "resolve", "ok")]
    assert live["fired"] == replay["fired"] == ["goodput_burn_fast"]
    assert live["ok"] and replay["ok"]
    # Replay rows carry the event-log wall timestamp.
    assert all(isinstance(r["t"], float) for r in replay["timeline"])


def test_health_report_cli_renders_and_gates_regressions(
        health_mod, tmp_path, capsys):
    healed = str(tmp_path / "healed.jsonl")
    reg, am, step = _burn_setup(event_log=EventLog(healed))
    for v in [1.0] * 5 + [0.5] * 4 + [1.0] * 15:
        step(v)
    burning = str(tmp_path / "burning.jsonl")
    reg2, am2, step2 = _burn_setup(event_log=EventLog(burning))
    for v in [1.0] * 5 + [0.5] * 4:
        step2(v)                       # fires, never resolves
    old_json = str(tmp_path / "old.json")
    new_json = str(tmp_path / "new.json")
    assert health_mod.main(["--events", healed, "--out", old_json]) == 0
    assert "resolve" in capsys.readouterr().out
    assert health_mod.main(["--events", burning, "--out", new_json,
                            "--json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["firing"] == ["goodput_burn_fast"]
    assert out["unresolved"] == ["goodput_burn_fast"]
    # The --compare gate: healed -> burning is a regression; a report
    # compared against itself is not.
    assert health_mod.main(["--compare", old_json, new_json]) == 1
    assert "REGRESSION" in capsys.readouterr().out
    assert health_mod.main(["--compare", new_json, new_json]) == 0
    assert health_mod.main(["--compare", old_json, old_json]) == 0


# ---------------------------------------------------------------------------
# CapacityAdvisor at fleet scale: demand sizing for hundreds of
# replicas, and the actuator's step/max clamps on its advice.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("healthy,rate_rps,expect_up", [
    (50, 120.0, 25),     # ceil(120 / (2 * 0.8)) = 75 needed -> +25
    (200, 400.0, 50),    # ceil(400 / 1.6) = 250 needed -> +50
    (500, 960.0, 100),   # ceil(960 / 1.6) = 600 needed -> +100
])
def test_advisor_demand_sizing_scales_to_fleet_size(
        healthy, rate_rps, expect_up):
    knee = {"serve_load_knee_goodput_rps": 2.0}
    gauges = {i: {"serve.goodput": 0.9,
                  "router.replicas_healthy": float(healthy),
                  "serve.queue_depth": 2.0 * i}      # growing backlog
              for i in range(1, 7)}
    counters = {i: {"serve.requests_completed": rate_rps * i}
                for i in range(1, 7)}
    _, adv = _advised(gauges, counters, knee=knee)
    rec = adv.recommend()
    assert rec["action"] == "scale_up" and rec["n"] == expect_up
    assert rec["evidence"]["replicas_healthy"] == healthy


@pytest.mark.parametrize("healthy,rate_rps,expect_down", [
    (50, 10.0, 43),      # ceil(10 / 1.6) = 7 needed -> -43
    (200, 40.0, 175),    # ceil(40 / 1.6) = 25 needed -> -175
    (500, 100.0, 437),   # ceil(100 / 1.6) = 63 needed -> -437
])
def test_advisor_demand_shrink_scales_to_fleet_size(
        healthy, rate_rps, expect_down):
    knee = {"serve_load_knee_goodput_rps": 2.0}
    gauges = {i: {"serve.goodput": 1.0,
                  "router.replicas_healthy": float(healthy),
                  "serve.queue_depth": 5.0}          # flat queue
              for i in range(1, 7)}
    counters = {i: {"serve.requests_completed": rate_rps * i}
                for i in range(1, 7)}
    _, adv = _advised(gauges, counters, knee=knee)
    rec = adv.recommend()
    assert rec["action"] == "scale_down" and rec["n"] == expect_down


def test_autoscaler_step_cap_then_max_bound_clamp_advice():
    """A +50 recommendation against a 200-replica SimFleet: the step
    cap admits 8 per action, and max_replicas truncates even that —
    the advisor sizes demand, the actuator rations it."""
    from horovod_tpu.simfleet import SimFleet

    fleet = SimFleet(200, seed=0, max_replicas=204)
    try:
        d = fleet.autoscaler.actuate({"action": "scale_up", "n": 50,
                                      "reason": "demand"})
        # min(200 + min(50, step=8), max_replicas=204) -> 204.
        assert d["action"] == "scale_up"
        assert len(fleet.router.replicas) == 204
        fleet.clock.advance(3.0)            # past the cooldown guard
        d2 = fleet.autoscaler.actuate({"action": "scale_up", "n": 50,
                                       "reason": "demand"})
        assert d2["action"] == "hold" and "max_replicas" in d2["why"]
        assert len(fleet.router.replicas) == 204
    finally:
        fleet.close()


def test_autoscaler_step_cap_alone_rations_big_advice():
    from horovod_tpu.simfleet import SimFleet

    fleet = SimFleet(50, seed=0, max_replicas=200)
    try:
        d = fleet.autoscaler.actuate({"action": "scale_up", "n": 50,
                                      "reason": "demand"})
        assert d["action"] == "scale_up"
        assert len(fleet.router.replicas) == 58     # 50 + step cap 8
    finally:
        fleet.close()
