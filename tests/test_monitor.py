"""Cross-rank observability layer (horovod_tpu/monitor.py).

Four pillars, pinned:

1. *Exact merge*: ``merge_snapshots`` of per-shard histogram snapshots
   is BIT-IDENTICAL to one histogram fed the union of observations
   (dyadic-rational samples make float sums order-independent, so ``==``
   is meaningful); counters sum, gauges keep per-rank values.
2. *Live exporter*: ``/metrics`` scraped over a real localhost socket
   DURING a running ``ServeEngine`` loop parses as Prometheus 0.0.4 and
   agrees with ``metrics_snapshot()``; ``/healthz`` flips to 503 when
   the no-progress watchdog would fire.
3. *Straggler detection*: the skew math on synthetic multi-rank
   reports, plus the allgathered ``check()`` path (single-process
   degenerate) feeding ``hvd.step_skew_s`` and the ``monitor.straggler``
   event.
4. *SLO goodput windows*: windowed good fraction over terminal traces,
   per-request ``slo_s`` overrides, and the engine integration
   (``serve.goodput`` gauge, ``slo_report()`` in ``metrics_snapshot()``).

The multiprocess half of pillar 2's acceptance —
``aggregate_snapshots()`` returning the same fleet view on every rank —
lives in tests/test_multiprocess.py (slow tier).
"""

from __future__ import annotations

import json
import re
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import metrics as metrics_mod
from horovod_tpu import monitor as monitor_mod
from horovod_tpu.metrics import EventLog, MetricsRegistry, Trace
from horovod_tpu.models import llama
from horovod_tpu.monitor import (
    MonitorServer, SLOWindow, StragglerDetector, aggregate_snapshots,
    maybe_start_monitor, merge_snapshots,
)
from horovod_tpu.serving import OK, Request
from horovod_tpu.serving_scheduler import ServeEngine

pytestmark = pytest.mark.monitor


# ---------------------------------------------------------------------------
# Pillar 2 helpers: scrape + a strict-enough 0.0.4 parser.
# ---------------------------------------------------------------------------


def _get(server: MonitorServer, path: str):
    url = f"http://{server.host}:{server.port}{path}"
    try:
        with urllib.request.urlopen(url, timeout=5) as r:
            return r.status, r.headers.get("Content-Type", ""), \
                r.read().decode()
    except urllib.error.HTTPError as e:        # 4xx/5xx still carry bodies
        return e.code, e.headers.get("Content-Type", ""), e.read().decode()


_SAMPLE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^{}]*\})? (-?[0-9.eE+\-]+|NaN)$")


def parse_prometheus(text: str) -> dict[str, list[tuple[str, float]]]:
    """Parse 0.0.4 exposition text; raises on any malformed line, on a
    sample with no preceding # TYPE, or on a # HELP not followed by its
    # TYPE.  Returns base-metric-name -> [(labels, value)]."""
    assert text.endswith("\n")
    typed: dict[str, str] = {}
    samples: dict[str, list[tuple[str, float]]] = {}
    pending_help: str | None = None
    for ln in text.splitlines():
        if not ln:
            continue
        if ln.startswith("# HELP "):
            assert pending_help is None, f"HELP twice in a row: {ln}"
            pending_help = ln.split(" ", 3)[2]
            continue
        if ln.startswith("# TYPE "):
            _, _, name, kind = ln.split(" ", 3)
            assert kind in ("counter", "gauge", "histogram"), ln
            typed[name] = kind
            if pending_help is not None:
                assert pending_help == name, (
                    f"HELP for {pending_help} not followed by its TYPE")
                pending_help = None
            continue
        assert pending_help is None, "sample between HELP and TYPE"
        m = _SAMPLE.match(ln)
        assert m, f"malformed sample line: {ln!r}"
        name = m.group(1)
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        assert name in typed or base in typed, f"untyped sample {name!r}"
        samples.setdefault(name, []).append(
            (m.group(2) or "", float(m.group(3))))
    return samples


# ---------------------------------------------------------------------------
# Pillar 1: exact merge.
# ---------------------------------------------------------------------------


def _dyadic_values(rng: np.random.Generator, n: int) -> list[float]:
    # k/256 with k in [1, 2^16): exactly representable, and sums of any
    # subset in any order are exact in float64 — merge `sum` fields can
    # be compared with == instead of approx.
    return [float(k) / 256.0 for k in rng.integers(1, 2 ** 16, n)]


@pytest.mark.parametrize("seed", [0, 1, 7])
def test_merged_histogram_bit_identical_to_union(seed):
    """THE merge invariant: per-shard snapshots merged == one histogram
    over the union of observations, field for field, bit for bit."""
    rng = np.random.default_rng(seed)
    n_ranks = int(rng.integers(2, 5))
    shards = [_dyadic_values(rng, int(rng.integers(0, 200)))
              for _ in range(n_ranks)]

    regs = [MetricsRegistry(event_log=None) for _ in range(n_ranks)]
    union = MetricsRegistry(event_log=None)
    for reg, vals in zip(regs, shards):
        for v in vals:
            reg.histogram("serve.e2e_s").observe(v)
    # union fed shard-major (any order works: bucket counts are ints,
    # dyadic sums are exact)
    for vals in shards:
        for v in vals:
            union.histogram("serve.e2e_s").observe(v)

    merged = merge_snapshots([r.snapshot() for r in regs])
    expect = union.snapshot()["histograms"]["serve.e2e_s"]
    got = merged["histograms"]["serve.e2e_s"]
    assert got == expect                       # bit-identical, every field
    assert merged["ranks"] == list(range(n_ranks))


def test_merge_counters_sum_gauges_per_rank():
    a, b = MetricsRegistry(event_log=None), MetricsRegistry(event_log=None)
    a.counter("serve.steps").inc(3)
    b.counter("serve.steps").inc(4)
    a.counter("only.on.a").inc(1)
    a.gauge("serve.queue_depth").set(2.0)
    b.gauge("serve.queue_depth").set(6.0)
    merged = merge_snapshots([a.snapshot(), b.snapshot()], ranks=[0, 3])
    assert merged["counters"]["serve.steps"] == 7
    assert merged["counters"]["only.on.a"] == 1
    g = merged["gauges"]["serve.queue_depth"]
    assert g["per_rank"] == {0: 2.0, 3: 6.0}
    assert g["min"] == 2.0 and g["max"] == 6.0 and g["mean"] == 4.0
    assert merged["ranks"] == [0, 3]
    json.dumps(merged)                         # fleet view is JSON-clean


def test_merge_empty_and_partial_histograms():
    a, b = MetricsRegistry(event_log=None), MetricsRegistry(event_log=None)
    a.histogram("h")                           # registered, never observed
    b.histogram("h").observe(0.5)
    merged = merge_snapshots([a.snapshot(), b.snapshot()])
    h = merged["histograms"]["h"]
    assert h["count"] == 1 and h["min"] == h["max"] == 0.5
    # registered everywhere but never observed -> zeroed summary
    e1, e2 = MetricsRegistry(event_log=None), MetricsRegistry(event_log=None)
    e1.histogram("z")
    e2.histogram("z")
    z = merge_snapshots([e1.snapshot(), e2.snapshot()])["histograms"]["z"]
    assert z["count"] == 0 and z["p99"] == 0.0 and z["min"] == 0.0
    # no histograms anywhere -> none in the fleet view
    merged0 = merge_snapshots([MetricsRegistry(event_log=None).snapshot()
                               for _ in range(2)])
    assert merged0["histograms"] == {}


def test_merge_rejects_bounds_mismatch_and_old_schema():
    a, b = MetricsRegistry(event_log=None), MetricsRegistry(event_log=None)
    a.histogram("h", bounds=(1.0, 2.0)).observe(1.5)
    b.histogram("h", bounds=(1.0, 4.0)).observe(1.5)
    with pytest.raises(ValueError, match="bounds differ"):
        merge_snapshots([a.snapshot(), b.snapshot()])
    snap = a.snapshot()
    del snap["histograms"]["h"]["buckets"]
    with pytest.raises(ValueError, match="buckets"):
        merge_snapshots([snap])
    with pytest.raises(ValueError, match="rank ids"):
        merge_snapshots([a.snapshot()], ranks=[0, 1])


def test_aggregate_snapshots_single_process():
    """Engine-plane aggregation degenerates cleanly pre-gang: one local
    snapshot, merged, with the aggregation odometer bumped."""
    reg = MetricsRegistry(event_log=None)
    reg.counter("serve.steps").inc(5)
    reg.histogram("serve.e2e_s").observe(0.25)
    fleet = aggregate_snapshots(reg)
    assert fleet["counters"]["serve.steps"] == 5
    assert fleet["histograms"]["serve.e2e_s"]["count"] == 1
    assert len(fleet["ranks"]) == jax.process_count()
    assert reg.counter("monitor.aggregations").value == 1


# ---------------------------------------------------------------------------
# Prometheus polish (satellite): HELP lines + label escaping.
# ---------------------------------------------------------------------------


def test_prometheus_help_lines_and_escaping():
    reg = MetricsRegistry(event_log=None)
    reg.counter("monitor.scrapes").inc(2)
    reg.histogram("serve.ttft_s").observe(0.1)
    text = reg.to_prometheus()
    assert ("# HELP monitor_scrapes "
            + metrics_mod.METRIC_HELP["monitor.scrapes"]) in text
    # HELP immediately precedes its TYPE (the 0.0.4 grouping rule)
    assert "# HELP serve_ttft_s " in text
    i_help = text.index("# HELP serve_ttft_s")
    i_type = text.index("# TYPE serve_ttft_s")
    assert i_help < i_type
    parse_prometheus(text)
    assert metrics_mod.escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    # unknown names simply get no HELP line — never a crash
    reg2 = MetricsRegistry(event_log=None)
    reg2.counter("no.help.entry").inc()
    assert "# HELP no_help_entry" not in reg2.to_prometheus()
    parse_prometheus(reg2.to_prometheus())


# ---------------------------------------------------------------------------
# Pillar 2: the live exporter.
# ---------------------------------------------------------------------------


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


def test_exporter_endpoints(world):
    cfg, params = world
    reg = MetricsRegistry(event_log=None)
    eng = ServeEngine(params, cfg, n_slots=2, max_len=32, chunk=8,
                      metrics=reg, monitor=False)
    mon = MonitorServer(reg, eng, port=0).start()
    try:
        assert mon.port > 0
        code, ctype, text = _get(mon, "/metrics")
        assert code == 200 and ctype.startswith("text/plain")
        assert "version=0.0.4" in ctype
        parse_prometheus(text)
        code, ctype, body = _get(mon, "/snapshot")
        assert code == 200 and ctype == "application/json"
        snap = json.loads(body)
        # Engine attached → the engine's view, SLO + memory reports
        # embedded, the step rows' report ("profile", on every engine)
        # plus the env-default health plane.
        assert set(snap) == {"counters", "gauges", "histograms", "slo",
                             "memory", "profile", "timeseries", "alerts",
                             "advice"}
        assert snap["counters"]["monitor.scrapes"] >= 1
        assert snap["slo"]["goodput"] == eng.slo.goodput()
        assert snap["memory"]["kv"]["block_bytes"] == eng._block_bytes
        # profiling off → /profile still answers, from the step rows
        code, ctype, body = _get(mon, "/profile")
        assert code == 200 and ctype == "application/json"
        assert json.loads(body)["ticks"] == eng.step_index
        code, _, body = _get(mon, "/healthz")
        hz = json.loads(body)
        assert code == 200 and hz["ok"] is True
        assert hz["rank"] == hvd.rank() and hz["pid"] > 0
        assert hz["watchdog_steps"] == eng.watchdog_steps
        code, _, body = _get(mon, "/state")
        assert code == 200
        assert body.startswith(f"rank={hvd.rank()} pid=")
        code, _, _ = _get(mon, "/nope")
        assert code == 404
        # the watchdog-imminent flip: /healthz goes 503 before the
        # engine raise, so an orchestrator can restart the rank
        eng._idle_steps = eng.watchdog_steps
        code, _, body = _get(mon, "/healthz")
        assert code == 503 and json.loads(body)["ok"] is False
    finally:
        mon.stop()


def test_exporter_no_engine_paths():
    reg = MetricsRegistry(event_log=None)
    mon = MonitorServer(reg, port=0).start()
    try:
        code, _, _ = _get(mon, "/state")
        assert code == 404                     # no engine attached
        # No engine -> no health plane either; each 404 carries a hint.
        code, _, body = _get(mon, "/timeseries")
        assert code == 404 and "HVD_TPU_SAMPLE_S" in body
        code, _, body = _get(mon, "/alerts")
        assert code == 404 and "HVD_TPU_ALERTS" in body
        code, _, _ = _get(mon, "/advice")
        assert code == 404
        code, _, body = _get(mon, "/healthz")
        assert code == 200 and json.loads(body)["ok"] is True
        assert reg.counter("monitor.scrapes").value == 5
    finally:
        mon.stop()


def test_exporter_health_plane_endpoints(world):
    """/timeseries, /alerts, /advice serve the sampler/alert/advisor
    payloads, and the per-endpoint scrape self-observation rides
    private generation cells — scraping must never invalidate the
    Prometheus render cache."""
    from horovod_tpu.alerts import AlertManager, rule_names
    from horovod_tpu.timeseries import MetricsSampler

    cfg, params = world
    reg = MetricsRegistry(event_log=None)
    sampler = MetricsSampler(reg, sample_s=1e-9)   # sample every step
    eng = ServeEngine(params, cfg, n_slots=2, max_len=32, chunk=8,
                      metrics=reg, monitor=False, sampler=sampler,
                      alerts=AlertManager(sampler, registry=reg))
    assert all(r.status == OK for r in eng.run(_reqs()))
    mon = MonitorServer(reg, eng, port=0).start()
    try:
        code, ctype, body = _get(mon, "/timeseries")
        assert code == 200 and ctype == "application/json"
        ts = json.loads(body)
        assert set(ts["tiers"]) == {"raw", "10s", "60s"}
        assert "serve.requests_completed" in ts["tiers"]["raw"]["series"]
        code, _, body = _get(mon, "/alerts")
        assert code == 200
        alerts = json.loads(body)
        assert [r["name"] for r in alerts["rules"]] == list(rule_names())
        # A healthy all-OK run never burns goodput (kv_exhaustion MAY
        # trip here: production-shaped windows over a sub-second run
        # see the allocation ramp as a drain slope).
        assert "goodput_burn_fast" not in alerts["firing"]
        assert "replica_death" not in alerts["firing"]
        code, _, body = _get(mon, "/advice")
        assert code == 200
        advice = json.loads(body)
        assert advice["last"]["action"] in {"hold", "scale_up",
                                            "scale_down"}
        # /snapshot embeds the same sections for merge_snapshots.
        snap = json.loads(_get(mon, "/snapshot")[2])
        assert "timeseries" in snap and "alerts" in snap
        # Scrapes self-observe per endpoint...
        assert any(k.startswith("monitor.scrape_s.")
                   for k in snap["histograms"])
        assert snap["counters"].get("monitor.scrape_errors.alerts",
                                    0) == 0
        # ...without touching the shared render generation: two
        # back-to-back /metrics scrapes serve the identical cached
        # text and leave the generation untouched.
        gen = reg._gen.n
        text1 = _get(mon, "/metrics")[2]
        text2 = _get(mon, "/metrics")[2]
        assert text1 == text2
        assert reg._gen.n == gen
    finally:
        mon.stop()


def test_exporter_live_scrape_during_serve(world):
    """The end-to-end acceptance: scrape /metrics over a real socket
    WHILE the engine serves; every scrape parses as 0.0.4, and the final
    scrape agrees with metrics_snapshot()."""
    cfg, params = world
    reg = MetricsRegistry(event_log=None)
    eng = ServeEngine(params, cfg, n_slots=2, max_len=32, chunk=8,
                      metrics=reg, monitor=0)   # port 0 = ephemeral
    assert eng.monitor is not None and eng.monitor.port > 0
    scrapes: list[str] = []
    stop = threading.Event()

    def _scraper():
        while not stop.is_set():
            _, _, text = _get(eng.monitor, "/metrics")
            scrapes.append(text)
            stop.wait(0.002)

    t = threading.Thread(target=_scraper, daemon=True)
    t.start()
    try:
        out = eng.run(_reqs(6))
    finally:
        stop.set()
        t.join(timeout=10)
    assert all(r.status == OK for r in out)
    assert scrapes, "no scrape completed during the serve loop"
    for text in scrapes:
        parse_prometheus(text)
    # final scrape vs the engine's own snapshot: identical registry state
    _, _, final = _get(eng.monitor, "/metrics")
    samples = parse_prometheus(final)
    snap = eng.metrics_snapshot()
    assert samples["serve_steps"][0][1] == snap["counters"]["serve.steps"]
    assert (samples["serve_e2e_s_count"][0][1]
            == snap["histograms"]["serve.e2e_s"]["count"] == 6)
    assert samples["serve_goodput"][0][1] == snap["gauges"]["serve.goodput"]
    eng.monitor.stop()


def test_maybe_start_monitor_env(monkeypatch):
    monkeypatch.delenv("HVD_TPU_MONITOR_PORT", raising=False)
    assert maybe_start_monitor(MetricsRegistry(event_log=None)) is None
    monkeypatch.setenv("HVD_TPU_MONITOR_PORT", "not-a-port")
    with pytest.warns(RuntimeWarning, match="not an int"):
        assert maybe_start_monitor(MetricsRegistry(event_log=None)) is None
    # pick a base so base + rank lands on a free ephemeral-range port
    probe = MonitorServer(MetricsRegistry(event_log=None), port=0)
    free = probe.port
    probe.stop()
    monkeypatch.setenv("HVD_TPU_MONITOR_PORT",
                       str(free - metrics_mod.current_rank()))
    mon = maybe_start_monitor(MetricsRegistry(event_log=None))
    try:
        assert mon is not None and mon.port == free
        code, _, _ = _get(mon, "/metrics")
        assert code == 200
    finally:
        if mon is not None:
            mon.stop()


# ---------------------------------------------------------------------------
# Pillar 3: straggler detection.
# ---------------------------------------------------------------------------


def test_straggler_evaluate_synthetic():
    reports = [
        {"rank": 0, "step_mean_s": 0.10},
        {"rank": 1, "step_mean_s": 0.11},
        {"rank": 2, "step_mean_s": 0.95},      # the laggard
        {"rank": 3, "step_mean_s": 0.10},
    ]
    v = StragglerDetector._evaluate(reports)
    assert v["slowest_rank"] == 2
    assert v["median_step_s"] == pytest.approx(0.105)
    assert v["skew_s"] == pytest.approx(0.95 - 0.105)


def test_straggler_check_single_process(tmp_path):
    """The gathered path, degenerate gang of one: skew 0, gauge set;
    warn_s below zero forces the straggler event so its payload is
    pinned without needing a real laggard."""
    log = EventLog(str(tmp_path / "ev.jsonl"))
    reg = MetricsRegistry(event_log=log)
    det = StragglerDetector(reg, window=8, warn_s=-1.0)
    for dt in (0.01, 0.02, 0.03):
        det.record_step(dt)
    v = det.check()
    assert v["skew_s"] == pytest.approx(0.0)
    assert v["slowest_rank"] == hvd.rank()
    assert len(v["reports"]) == jax.process_count()
    assert reg.gauge("hvd.step_skew_s").value == pytest.approx(0.0)
    assert reg.histogram("hvd.step_s").count == 3
    log.close()
    events = EventLog.read(log.path)
    ev = [e for e in events if e["kind"] == "monitor.straggler"]
    assert len(ev) == 1
    assert ev[0]["straggler_rank"] == hvd.rank()
    assert ev[0]["rank"] == metrics_mod.current_rank()   # attribution stamp


def test_straggler_pulls_negotiate_deltas():
    reg = MetricsRegistry(event_log=None)
    det = StragglerDetector(reg, window=8, warn_s=10.0)
    reg.histogram("hvd.negotiate_s").observe(0.2)
    reg.histogram("hvd.negotiate_s").observe(0.4)
    r = det.report()
    assert r["negotiate_mean_s"] == pytest.approx(0.3)
    # deltas, not totals: a second report with no new waits adds nothing
    n_before = len(det._negotiates)
    det.report()
    assert len(det._negotiates) == n_before


def test_engine_negotiate_waits_surface_in_stats():
    """The eager engine's recent negotiate waits ride engine_stats() —
    the straggler window's feed."""
    x = hvd.allreduce(hvd.per_rank(lambda r: jnp.ones(4) * r))
    jax.block_until_ready(x)
    stats = hvd.engine_stats()
    assert "recent_negotiate_s" in stats
    assert len(stats["recent_negotiate_s"]) >= 1
    assert all(w >= 0.0 for w in stats["recent_negotiate_s"])


# ---------------------------------------------------------------------------
# Pillar 4: SLO goodput windows.
# ---------------------------------------------------------------------------


def _terminal_trace(rid, e2e, status=OK, n_tokens=3):
    tr = Trace(rid=rid, enqueue_ts=100.0, enqueue_step=0)
    tr.first_token_ts = 100.0 + e2e / 2
    tr.terminal_ts = 100.0 + e2e
    tr.status = status
    tr.n_tokens = n_tokens
    return tr


def test_slo_window_goodput_and_overrides():
    w = SLOWindow(window=4, slo_e2e_s=1.0)
    assert w.goodput() == 1.0                  # empty window: no evidence
    w.add(_terminal_trace(0, e2e=0.5))         # good
    w.add(_terminal_trace(1, e2e=2.0))         # breaches window default
    w.add(_terminal_trace(2, e2e=0.5, status="TIMEOUT"))   # not OK
    w.add(_terminal_trace(3, e2e=2.0), slo_s=5.0)          # per-req slack
    assert w.goodput() == pytest.approx(2 / 4)
    # ring semantics: a 5th add evicts the oldest (the good one)
    w.add(_terminal_trace(4, e2e=9.0))
    assert w.goodput() == pytest.approx(1 / 4)
    rep = w.report()
    assert rep["n"] == 4 and rep["window"] == 4
    assert rep["statuses"]["TIMEOUT"] == 1
    assert rep["e2e_s"]["p50"] == pytest.approx(2.0)
    with pytest.raises(ValueError):
        SLOWindow(window=0)


def test_slo_window_no_target_counts_completion():
    w = SLOWindow(window=8)                    # no default target
    w.add(_terminal_trace(0, e2e=100.0))       # slow but OK -> good
    w.add(_terminal_trace(1, e2e=0.1, status="FAILED"))
    assert w.slo_e2e_s is None
    assert w.goodput() == pytest.approx(0.5)


def test_engine_slo_integration(world):
    """serve.goodput + slo_report() through a real serve loop: generous
    targets -> 1.0; an impossible per-request target drags the window
    below 1.0 while the request still completes OK."""
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=2, max_len=32, chunk=8,
                      metrics=MetricsRegistry(event_log=None),
                      monitor=False, slo_window=16)
    out = eng.run(_reqs(4, new=3, slo_s=1000.0))
    assert all(r.status == OK for r in out)
    snap = eng.metrics_snapshot()
    assert snap["slo"]["goodput"] == 1.0
    assert snap["gauges"]["serve.goodput"] == 1.0
    assert snap["slo"]["n"] == 4
    assert snap["slo"]["e2e_s"]["p99"] > 0.0
    # an unmeetable SLO: completes OK, counts bad
    out2 = eng.run(_reqs(2, new=3, slo_s=1e-9))
    assert all(r.status == OK for r in out2)
    rep = eng.slo_report()
    assert rep["n"] == 6
    assert rep["goodput"] == pytest.approx(4 / 6)
    assert eng.metrics.gauge("serve.goodput").value == pytest.approx(4 / 6)
    with pytest.raises(ValueError, match="slo_s"):
        eng.submit(Request(prompt=[1], max_new_tokens=1, slo_s=0.0))


def test_engine_monitor_arg_validation(world):
    cfg, params = world
    with pytest.raises(ValueError, match="monitor"):
        ServeEngine(params, cfg, n_slots=2, max_len=32, chunk=8,
                    metrics=MetricsRegistry(event_log=None),
                    monitor=True)              # True is not a port


# ---------------------------------------------------------------------------
# Satellite: rank/pid stamping + interleaved multi-rank log reading.
# ---------------------------------------------------------------------------


def test_event_log_rank_pid_stamped(tmp_path):
    import os as _os
    log = EventLog(str(tmp_path / "ev.jsonl"))
    log.emit("serve.submit", rid=1)
    log.emit("custom", rank=99)                # caller override wins
    log.close()
    a, b = EventLog.read(log.path)
    assert a["rank"] == metrics_mod.current_rank()
    assert a["pid"] == _os.getpid()
    assert b["rank"] == 99


def test_event_log_interleaved_multi_rank_fuzz(tmp_path):
    """Reader robustness on a merged multi-rank log: whole lines from
    different ranks interleaved in random order, with torn fragments
    injected between them — every intact record survives with its rank
    attribution, every torn line is dropped."""
    rng = np.random.default_rng(42)
    path = str(tmp_path / "merged.jsonl")
    lines, expect = [], {0: 0, 1: 0, 2: 0}
    for rank in expect:
        metrics_mod.set_rank(rank)
        solo = EventLog(str(tmp_path / f"r{rank}.jsonl"))
        for i in range(20):
            solo.emit("serve.submit", rid=i)
        solo.close()
        with open(solo.path) as f:
            new = f.read().splitlines()
        lines += new
        expect[rank] = len(new)
    metrics_mod.set_rank(None)
    rng.shuffle(lines)
    with open(path, "w") as f:
        for i, ln in enumerate(lines):
            f.write(ln + "\n")
            if i % 7 == 3:                     # torn fragment mid-log
                f.write(ln[:int(rng.integers(1, len(ln)))] + "\n")
    events = EventLog.read(path)
    by_rank: dict[int, int] = {}
    for e in events:
        by_rank[e["rank"]] = by_rank.get(e["rank"], 0) + 1
    assert by_rank == expect
    # the stray fragments vanished silently: every survivor is complete
    assert all({"ts", "kind", "rank", "pid", "rid"} <= set(e)
               for e in events)
