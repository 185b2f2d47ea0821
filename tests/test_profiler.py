"""Serving profiler, retrace sentry, and memory accounting
(horovod_tpu/profiler.py + the ServeEngine integration).

The acceptance criteria, pinned:

1. *Free and harmless*: profiling on vs off produces BIT-IDENTICAL
   engine outputs, and ``compile_cache_sizes()`` stays at one signature
   per program (the chunk program: one a width, which its count reads as
   1) — the profiler never touches a traced value.
2. *Phases tile the tick*: the top-level phase totals sum to the
   profiler's measured tick wall time (coverage ~ 1.0), and that tick
   total is within 10 % of an independently measured wall time for the
   same steps.
3. *Retrace sentry*: a deliberately unpinned jit call (a python int
   where the engine always passes a device scalar) grows a program's
   cache — the sentry bumps ``serve.retrace`` on the next step and
   raises under the fatal knob.
4. *Memory accounting*: ``kv.*`` byte gauges track the BlockPool
   exactly (blocks x block_bytes) across admit / release-to-cache /
   evict / preempt, and ``block_bytes`` matches the KV array's real
   dtype/shape arithmetic.
5. *Serving surface*: ``/profile`` over a real socket, snapshot and
   state-dump embedding, event-log replay via tools/profile_report.
6. *The phases are spans on the profiler trace*, profiling on or off:
   under ``jax.profiler``'s trace every ``step()`` is one ``serve.step``
   tiled by ``serve.step.<phase>``, the sub-phases nest inside
   ``admit``, an exception out of the step leaves nothing open, and
   taking the trace changes neither tokens nor compile counts.
7. *One row a step, on every engine*: the default class keeps
   ``ROW_FIELDS`` a step in a bounded ``StepLog`` (tiling phases sum to
   ``ended - began``, the counts follow the schedule, the ring counts
   what it dropped), ``step_logs()`` outlives the engine, and
   ``TickProfiler`` adds only the histograms and the event.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics as metrics_mod
from horovod_tpu import profiler as profiler_mod
from horovod_tpu.metrics import MetricsRegistry
from horovod_tpu.models import llama
from horovod_tpu.monitor import MonitorServer
from horovod_tpu.profiler import (CARRIED, COUNTS, PHASES, ROW_FIELDS,
                                  SPEC_PHASES, SUB_PHASES, TILING,
                                  PhaseSpans, TickProfiler)
from horovod_tpu.serving import OK, Request
from horovod_tpu.serving_scheduler import ServeEngine

pytestmark = pytest.mark.profile


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


# ---------------------------------------------------------------------------
# TickProfiler unit behavior.
# ---------------------------------------------------------------------------


def test_profiler_marks_tile_the_tick():
    reg = MetricsRegistry(event_log=None)
    prof = TickProfiler(reg, window=8)
    for step in range(3):
        prof.begin(step)                 # opens "expire"
        prof.mark("admit")
        with prof.sub("admit.cache_acquire"):
            pass
        prof.mark("bookkeeping")
        prof.end()
    rep = prof.report()
    assert rep["n"] == rep["ticks"] == 3 and rep["window"] == 8
    # tiling: per tick, the sum of top-level phases IS the tick time
    tiled = sum(rep["phases"][p]["total_s"] for p in PHASES)
    assert tiled == pytest.approx(rep["tick"]["total_s"], rel=1e-9)
    assert rep["coverage"] == pytest.approx(1.0, rel=1e-9)
    # sub-phases are reported but excluded from the coverage base
    assert rep["phases"]["admit.cache_acquire"]["count"] == 3
    assert rep["phases"]["admit.prefill_dispatch"]["count"] == 0
    # every phase fed its histogram by literal name
    assert reg.histogram("serve.phase.expire_s").count == 3
    assert reg.histogram("serve.phase.tick_s").count == 3
    assert reg.histogram("serve.phase.admit_cache_acquire_s").count == 3


def test_profiler_window_semantics(monkeypatch):
    reg = MetricsRegistry(event_log=None)
    with pytest.raises(ValueError):
        TickProfiler(reg, window=0)
    # env default + tolerant parse of garbage
    monkeypatch.setenv("HVD_TPU_PROFILE_WINDOW", "3")
    prof = TickProfiler(reg)
    assert prof.window == 3
    monkeypatch.setenv("HVD_TPU_PROFILE_WINDOW", "not-a-number")
    assert TickProfiler(reg).window == 256
    # the ring keeps only the last `window` ticks; `ticks` keeps counting
    for step in range(5):
        prof.begin(step)
        prof.end()
    rep = prof.report()
    assert rep["n"] == 3 and rep["ticks"] == 5


# ---------------------------------------------------------------------------
# Acceptance 1 + 2: bit-identical outputs, no new signatures, coverage.
# ---------------------------------------------------------------------------


def test_profile_on_off_parity_and_phase_sum(world):
    reqs = _reqs(6)
    off = _engine(world, prefix_cache=True)
    out_off = off.run(reqs)
    on = _engine(world, profile=True, prefix_cache=True)
    t0 = time.perf_counter()
    out_on = on.run(reqs)
    wall = time.perf_counter() - t0
    assert [list(a) for a in out_on] == [list(b) for b in out_off]
    assert all(r.status == OK for r in out_on)
    # one jit signature per program, profiling on — and no retraces seen
    assert on.compile_cache_sizes() == {"sample": 1, "tick": 1, "chunk": 1,
                                        "set_row": 1}
    assert on.metrics.counter("serve.retrace").value == 0
    snap = on.metrics_snapshot()
    rep = snap["profile"]
    # the default engine reports the same steps in the same schema
    rep_off = off.metrics_snapshot()["profile"]
    assert rep_off["ticks"] == rep["ticks"] == on.step_index
    assert set(rep_off["phases"]) == set(rep["phases"])
    assert rep_off["coverage"] == pytest.approx(1.0, rel=1e-6)
    # phase sum within 10 % of measured wall step time (the tiling
    # construction makes it exact vs the profiler's own tick clock;
    # vs the OUTER wall clock only the between-step run() overhead
    # separates them)
    tiled = sum(rep["phases"][p]["total_s"] for p in PHASES)
    assert tiled == pytest.approx(rep["tick"]["total_s"], rel=1e-6)
    assert 0.9 <= rep["coverage"] <= 1.0 + 1e-9
    assert rep["tick"]["total_s"] == pytest.approx(wall, rel=0.10)
    # every phase + sub-phase is present in the report schema
    assert set(rep["phases"]) == set(PHASES) | set(SUB_PHASES)
    # cache-acquire sub-phase actually sampled (prefix cache was on)
    assert rep["phases"]["admit.cache_acquire"]["count"] > 0
    # state_dump carries the human-readable phase line
    assert "profile (mean ms over last" in on.state_dump()
    assert "kv bytes:" in on.state_dump()


def test_profile_env_knob(world, monkeypatch):
    monkeypatch.setenv("HVD_TPU_PROFILE", "1")
    eng = _engine(world)
    assert type(eng.prof) is TickProfiler and eng.prof.report()["n"] == 0
    monkeypatch.delenv("HVD_TPU_PROFILE")
    # off: the spans and the rows, no histograms
    off = _engine(world)
    assert type(off.prof) is PhaseSpans and off.prof.report()["n"] == 0
    assert "serve.phase.tick_s" not in off.metrics.snapshot()["histograms"]
    # explicit argument beats the env
    monkeypatch.setenv("HVD_TPU_PROFILE", "1")
    assert type(_engine(world, profile=False).prof) is PhaseSpans


# ---------------------------------------------------------------------------
# Acceptance 3: the retrace sentry.
# ---------------------------------------------------------------------------


def test_retrace_sentry_fires_on_unpinned_jit(world):
    eng = _engine(world)
    out = eng.run(_reqs(3))
    assert all(r.status == OK for r in out)
    assert eng.metrics.counter("serve.retrace").value == 0
    # A deliberately unpinned call: the engine always passes the slot as
    # a device int32 scalar; a python int is a new (weak-typed)
    # signature, exactly the class of leak HVD001 lints for statically.
    eng.pcache = eng._set_row(
        eng.pcache, 0, jnp.asarray(eng._trash_row),
        jnp.asarray(0, jnp.int32))
    assert eng.compile_cache_sizes()["set_row"] == 2
    eng.step()
    assert eng.metrics.counter("serve.retrace").value == 1
    # one-shot: the sentry baselines the new size, no double count
    eng.step()
    assert eng.metrics.counter("serve.retrace").value == 1


def test_retrace_sentry_fatal(world, monkeypatch):
    monkeypatch.setenv("HVD_TPU_RETRACE_FATAL", "1")
    eng = _engine(world)
    out = eng.run(_reqs(2))          # first compiles are NOT retraces
    assert all(r.status == OK for r in out)
    eng.pcache = eng._set_row(
        eng.pcache, 1, jnp.asarray(eng._trash_row),
        jnp.asarray(0, jnp.int32))
    with pytest.raises(RuntimeError, match="retrace sentry"):
        eng.step()


# ---------------------------------------------------------------------------
# Acceptance 4: KV/host memory accounting.
# ---------------------------------------------------------------------------


def test_block_bytes_matches_cache_shape(world):
    eng = _engine(world)
    k = eng.pcache.k
    expect = (2 * k.dtype.itemsize
              * k.shape[0] * k.shape[2] * k.shape[3] * k.shape[4])
    assert eng._block_bytes == expect
    mem = eng.memory_report()
    assert mem["kv"]["block_bytes"] == expect
    assert mem["kv"]["total_bytes"] == expect * k.shape[1]
    assert eng.metrics.gauge("kv.block_bytes").value == expect


def _assert_kv_gauges_match_pool(eng):
    bb = eng._block_bytes
    g = eng.metrics.gauge
    assert g("kv.free_blocks").value == eng.pool.free_count()
    assert g("kv.free_bytes").value == eng.pool.free_count() * bb
    assert g("kv.referenced_blocks").value == eng.pool.ref_count()
    assert g("kv.referenced_bytes").value == eng.pool.ref_count() * bb
    assert g("kv.cached_blocks").value == eng.pool.cached_count()
    assert g("kv.cached_bytes").value == eng.pool.cached_count() * bb


def test_kv_gauges_track_pool_through_lifecycle(world):
    # Overcommitted pool + preemption + prefix cache: admit, release-
    # to-cache, evict, and preempt all happen, and after EVERY step the
    # byte gauges are exactly blocks x block_bytes per pool state.
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, chunk=4,
                      block_size=4, n_blocks=6, preempt_after=2,
                      prefix_cache=True,
                      metrics=MetricsRegistry(event_log=None),
                      monitor=False)
    shared = [5, 17, 42, 7, 9, 11, 13, 2]           # two full blocks
    reqs = [Request(prompt=shared, max_new_tokens=8),        # 4 blocks
            Request(prompt=[7, 8, 1, 3], max_new_tokens=6),  # starves
            Request(prompt=shared, max_new_tokens=8),        # prefix hit
            Request(prompt=shared, max_new_tokens=4)]
    for r in reqs:
        eng.submit(r)
    saw_cached = False
    steps = 0
    while eng.pending() and steps < 300:
        eng.step()
        steps += 1
        _assert_kv_gauges_match_pool(eng)
        saw_cached = saw_cached or eng.pool.cached_count() > 0
    assert not eng.pending()
    assert eng.counters["preemptions"] >= 1, \
        "workload did not exercise preemption"
    assert saw_cached, "nothing was ever released to the prefix cache"
    mem = eng.memory_report()
    assert mem["kv"]["free_bytes"] == \
        eng.pool.free_count() * eng._block_bytes
    assert mem["host"]["registry_bytes"] > 0
    assert mem["host"]["trace_ring_bytes"] > 0
    assert mem["host"]["prefix_index_bytes"] > 0
    assert eng.prefix.approx_footprint_bytes() == \
        mem["host"]["prefix_index_bytes"]


def test_event_log_bytes_accounted(world, tmp_path):
    log = str(tmp_path / "events.jsonl")
    reg = MetricsRegistry(event_log=metrics_mod.EventLog(log))
    eng = _engine(world, metrics=reg, profile=True)
    eng.run(_reqs(2))
    mem = eng.memory_report()
    assert mem["host"]["event_log_bytes"] == os.path.getsize(log) > 0


# ---------------------------------------------------------------------------
# Acceptance 5: the serving surface — /profile, replay, compare.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("profile", [False, True],
                         ids=["spans", "profiler"])
def test_profile_endpoint_over_socket(world, profile):
    import urllib.request
    eng = _engine(world, profile=profile)
    mon = MonitorServer(eng.metrics, eng, port=0).start()
    try:
        eng.run(_reqs(3))
        url = f"http://{mon.host}:{mon.port}/profile"
        with urllib.request.urlopen(url, timeout=5) as r:
            assert r.status == 200
            assert r.headers["Content-Type"] == "application/json"
            rep = json.loads(r.read())
        assert rep["n"] > 0
        assert set(rep["phases"]) == set(PHASES) | set(SUB_PHASES)
        # the scrape is the same report the engine computes
        assert rep["ticks"] == eng.prof.report()["ticks"]
    finally:
        mon.stop()


def test_event_log_replay_matches_live_report(world, tmp_path):
    from tools.profile_report import (
        compare_reports, load_report, render, report_from_events,
    )
    log = str(tmp_path / "events.jsonl")
    reg = MetricsRegistry(event_log=metrics_mod.EventLog(log))
    eng = _engine(world, metrics=reg, profile=True)
    eng.run(_reqs(4))
    live = eng.prof.report()
    replay = load_report(log)
    assert replay["n"] == live["n"]
    for p in PHASES:
        assert replay["phases"][p]["total_s"] == pytest.approx(
            live["phases"][p]["total_s"], rel=1e-9)
    assert replay["coverage"] == pytest.approx(live["coverage"],
                                               rel=1e-6)
    # --window replays only the tail
    tail = report_from_events(
        [json.loads(ln) for ln in open(log)], window=2)
    assert tail["n"] == 2
    # render never crashes and names every phase
    text = render(replay)
    for p in PHASES:
        assert p in text
    # a saved report round-trips through load_report, as does a full
    # metrics_snapshot() dump (its "profile" key)
    saved = tmp_path / "rep.json"
    saved.write_text(json.dumps(live))
    assert load_report(str(saved))["n"] == live["n"]
    snap = tmp_path / "snap.json"
    snap.write_text(json.dumps(eng.metrics_snapshot()))
    assert load_report(str(snap))["n"] == live["n"]
    # the regression gate: same-vs-same is clean, a doctored 2x admit
    # regression past threshold+floor is flagged
    assert not any(r["regressed"]
                   for r in compare_reports(live, replay))
    worse = json.loads(json.dumps(live))
    worse["phases"]["admit"]["mean_s"] = \
        live["phases"]["admit"]["mean_s"] * 2 + 1.0
    rows = compare_reports(live, worse, threshold_pct=10, floor_ms=0.05)
    flagged = {r["phase"] for r in rows if r["regressed"]}
    assert flagged == {"admit"}
    # the absolute floor silences sub-floor percent blowups
    tiny_old = {"phases": {"x": {"mean_s": 1e-9}}}
    tiny_new = {"phases": {"x": {"mean_s": 9e-9}}}
    assert not any(r["regressed"]
                   for r in compare_reports(tiny_old, tiny_new))


def test_profiler_overhead_and_registry_cache(world):
    # The rendered-exposition cache: unchanged registry -> the SAME
    # string object (no re-render); any instrument write invalidates;
    # and the monitor's own scrape counter does NOT invalidate (its
    # generation cell is private), so back-to-back scrapes are cheap.
    reg = MetricsRegistry(event_log=None)
    reg.counter("serve.steps").inc()
    a = reg.to_prometheus()
    assert reg.to_prometheus() is a
    reg.counter("serve.steps").inc()
    b = reg.to_prometheus()
    assert b is not a
    mon = MonitorServer(reg, port=0)
    mon._scrapes.inc()
    assert reg.to_prometheus() is b
    assert reg.snapshot()["counters"]["monitor.scrapes"] == 1
    mon._httpd.server_close()


# ---------------------------------------------------------------------------
# Acceptance 6: the phases as spans on jax's profiler trace.
# ---------------------------------------------------------------------------

BOTH = pytest.mark.parametrize("profile", [False, True],
                               ids=["spans", "profiler"])


def _step_spans(tr):
    """The traced thread's line and its ``serve.step`` spans."""
    line = tr.line_with("serve.step")
    return line, [e for e in line if e[0] == "serve.step"]


@BOTH
def test_trace_phases_tile_every_step(world, host_trace, profile):
    eng = _engine(world, profile=profile, prefix_cache=True)
    eng.run(_reqs(2))                    # compiles outside the trace
    with host_trace() as tr:
        out = eng.run(_reqs(5))
    assert all(r.status == OK for r in out)
    line, steps = _step_spans(tr)
    assert len(steps) >= 5
    assert not any(e[0] == "serve.step"
                   for s in steps for e in tr.children(line, s))
    full, spare = 0, []
    for s in steps:
        kids = tr.children(line, s)
        names = [k[0] for k in kids]
        # the decode tick's three phases only where a row decoded
        want = PHASES if len(kids) > 3 else (PHASES[:2] + PHASES[-1:])
        assert names == ["serve.step." + p for p in want]
        full += len(kids) > 3
        # boundary to boundary: what the phases leave uncovered is the
        # few statements around begin(), mark() and end()
        covered = sum(k[2] - k[1] for k in kids)
        spare.append(max(0.05 * (s[2] - s[1]), 1e5)
                     - ((s[2] - s[1]) - covered))
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
    assert full >= 5
    # ... in the step as it usually goes, not in every one: where the
    # machine takes the thread off its core between two annotations, that
    # one step reads the wait as time without a name
    assert np.median(spare) > 0


@BOTH
def test_trace_sub_phases_nest_in_admit(world, host_trace, profile):
    eng = _engine(world, profile=profile, prefix_cache=True)
    eng.run(_reqs(2))
    with host_trace() as tr:
        eng.run(_reqs(4))
    line, steps = _step_spans(tr)
    subs = {"serve.step.admit.cache_acquire": 0,
            "serve.step.admit.prefill_dispatch": 0}
    for e in line:
        if e[0] in subs:
            subs[e[0]] += 1
            admit, = [a for a in line if a[0] == "serve.step.admit"
                      and a[1] <= e[1] and e[2] <= a[2]]
            assert e in tr.children(line, admit)
    # one window dispatch span a step; one lookup per admitted request
    assert subs["serve.step.admit.prefill_dispatch"] == len(steps)
    assert subs["serve.step.admit.cache_acquire"] >= 4
    # the cost-model pair is an estimate: never a span
    assert not any("compute_est" in e[0] or "host_stall" in e[0]
                   for e in line)


def test_trace_changes_neither_tokens_nor_compiles(world, host_trace):
    reqs = _reqs(5)
    plain = _engine(world, prefix_cache=True)
    want = [list(r) for r in plain.run(reqs)]
    sizes = plain.compile_cache_sizes()
    assert sizes == {"sample": 1, "tick": 1, "chunk": 1, "set_row": 1}
    for profile in (False, True):
        eng = _engine(world, profile=profile, prefix_cache=True)
        with host_trace() as tr:
            got = eng.run(reqs)
        assert [list(r) for r in got] == want
        assert eng.compile_cache_sizes() == sizes
        assert eng.metrics.counter("serve.retrace").value == 0
        assert tr.line_with("serve.step")


@BOTH
def test_exception_out_of_step_leaves_no_span_open(world, host_trace,
                                                   monkeypatch, profile):
    monkeypatch.setenv("HVD_TPU_RETRACE_FATAL", "1")
    eng = _engine(world, profile=profile)
    eng.run(_reqs(2))
    eng.pcache = eng._set_row(           # unpinned: the sentry will raise
        eng.pcache, 1, jnp.asarray(eng._trash_row),
        jnp.asarray(0, jnp.int32))
    eng.submit(_reqs(1)[0])
    with host_trace() as tr:
        with pytest.raises(RuntimeError, match="retrace sentry"):
            eng.step()
        assert eng.prof._phase_span is None and eng.prof._step_span is None
        while eng.pending():
            eng.step()
    line, steps = _step_spans(tr)
    assert len(steps) >= 2
    # the step that raised closed in the phase it was in, and the next
    # step's span starts after it: not nested in it
    raised = tr.children(line, steps[0])
    assert raised[-1][0] == "serve.step.bookkeeping"
    assert raised[-1][2] <= steps[0][2] <= steps[1][1]
    # the aborted tick still left a whole row (step_index did not advance)
    assert eng.prof.report()["ticks"] == eng.step_index + 1
    rows = eng.prof.log.rows()
    aborted = dict(zip(ROW_FIELDS, rows[-1 - (len(steps) - 1)]))
    assert aborted["ended"] > aborted["began"] and aborted["bookkeeping"] > 0
    assert sum(aborted[p] for p in TILING) == pytest.approx(
        aborted["ended"] - aborted["began"], abs=1e-9)


# ---------------------------------------------------------------------------
# Acceptance 7: one row a step, on every engine.
# ---------------------------------------------------------------------------


def _as_dicts(log):
    return [dict(zip(ROW_FIELDS, r)) for r in log.rows()]


def test_row_fields_are_the_vocabulary():
    assert ROW_FIELDS[:3] == ("step", "began", "ended")
    assert set(TILING) == set(PHASES) | set(SPEC_PHASES) | {"unmask"}
    assert ROW_FIELDS == (("step", "began", "ended") + TILING + SUB_PHASES
                          + COUNTS + CARRIED)
    assert COUNTS == ("chunks", "chunk_rows", "tick_rows", "tokens",
                      "first_tokens")
    assert len(set(ROW_FIELDS)) == len(ROW_FIELDS)


def test_default_class_rows_tile_the_step():
    prof = PhaseSpans(MetricsRegistry(event_log=None), window=8)
    for step in range(3):
        prof.begin(step)
        prof.mark("admit")
        with prof.sub("admit.prefill_dispatch"):
            pass
        prof.mark("decode_dispatch")
        prof.mark("device_sync")
        prof.add("device_sync.compute_est", 1.0, 1.5)
        prof.mark("sample_postprocess")
        prof.mark("bookkeeping")
        prof.counts(chunks=2, tick_rows=step, tokens=step)
        prof.end()
    rows = _as_dicts(prof.log)
    assert [r["step"] for r in rows] == [0, 1, 2]
    for a, b in zip(rows, rows[1:]):
        assert a["ended"] <= b["began"]
    for i, r in enumerate(rows):
        # to well under a microsecond: differences of one clock's reads
        assert sum(r[p] for p in TILING) == pytest.approx(
            r["ended"] - r["began"], abs=1e-9)
        assert r["draft"] == r["verify"] == 0.0
        assert 0 < r["admit.prefill_dispatch"] <= r["admit"]
        assert r["device_sync.compute_est"] == 0.5
        assert (r["chunks"], r["tick_rows"], r["tokens"]) == (2, i, i)
        assert r["first_tokens"] == 0
        assert all(r[c] == 0 for c in CARRIED)      # none in this registry
    rep = prof.report()
    assert rep["n"] == rep["ticks"] == 3
    assert rep["coverage"] == pytest.approx(1.0, rel=1e-9)
    assert set(rep["phases"]) == set(PHASES) | set(SUB_PHASES)
    assert rep["phases"]["device_sync.compute_est"]["total_s"] == 1.5
    # the default class feeds no histogram
    assert not prof.log.metrics.snapshot()["histograms"]


def test_step_log_is_bounded_and_counts_what_it_dropped(monkeypatch):
    monkeypatch.setattr(profiler_mod, "STEP_LOG_ROWS", 4)
    prof = PhaseSpans(MetricsRegistry(event_log=None))
    assert prof.log.dropped == 0 and len(prof.log.rows()) == 0
    for step in range(6):
        prof.begin(step)
        prof.end()
    log = prof.log
    assert (log.written, log.dropped) == (6, 2)
    assert [r[0] for r in log.rows()] == [2, 3, 4, 5]      # oldest first
    assert [r[0] for r in log.rows(last=2)] == [4, 5]
    began = log.rows()[:, ROW_FIELDS.index("began")]
    assert list(began) == sorted(began)
    # the window of report() is not the ring's bound
    assert prof.report()["n"] == 4 and prof.report()["ticks"] == 6
    # a copy: a later step does not move what a reader holds
    kept = log.rows()
    prof.begin(6)
    prof.end()
    assert [r[0] for r in kept] == [2, 3, 4, 5]


def test_step_logs_outlive_their_engines_and_are_bounded(world):
    eng = _engine(world)
    out = eng.run(_reqs(3))
    n_tokens = sum(len(r) for r in out)
    log, steps = eng.prof.log, eng.step_index
    assert profiler_mod.step_logs()[-1] is log
    eng.params = eng.pcache = None
    del eng
    assert profiler_mod.step_logs()[-1] is log
    assert log.written == steps and log.dropped == 0
    counters = log.metrics.snapshot()["counters"]
    assert counters["serve.steps"] == steps
    assert counters["serve.tokens_emitted"] == n_tokens
    # the last two, oldest first
    made = [PhaseSpans(MetricsRegistry(event_log=None)).log
            for _ in range(3)]
    kept = profiler_mod.step_logs()
    assert len(kept) == 2 and log not in kept and made[0] not in kept
    assert all(a is b for a, b in zip(kept, made[1:]))


@pytest.mark.parametrize("profile", [False, True],
                         ids=["spans", "profiler"])
def test_rows_follow_a_known_schedule(world, profile):
    # one request of 11 prompt tokens (two windows of 8) and 3 to serve:
    # step 0 admits it and dispatches window 0; step 1 dispatches the last
    # window and the row joins that step's tick (its first token); steps 2
    # and 3 tick once each; the third token retires it.
    eng = _engine(world, profile=profile)
    rid = eng.submit(Request(prompt=list(range(1, 12)), max_new_tokens=3))
    emitted = eng.metrics.counter("serve.tokens_emitted")
    walked = [eng.metrics.counter(c) for c in CARRIED[:2]]
    seen, carried = [], []
    while eng.pending():
        eng.step()
        seen.append(emitted.value)
        carried.append(tuple(c.value for c in walked))
    assert len(eng.results[rid]) == 3
    rows = _as_dicts(eng.prof.log)
    got = [tuple(int(r[c]) for c in COUNTS) for r in rows]
    # (chunk programs, the rows they carried, ...): one row prefills alone
    assert got == [(1, 1, 0, 0, 0), (1, 1, 1, 1, 1), (0, 0, 1, 1, 0),
                   (0, 0, 1, 1, 0)]
    # the counter moves when a token is emitted, not when its request ends
    assert seen == [0, 1, 2, 3]
    assert [int(r["step"]) for r in rows] == [0, 1, 2, 3]
    # the model's counters as they stood at each step's end: every step
    # dispatched a program, so both grew in every row
    assert [(r["attn.blocks_visited"], r["attn.blocks_live"])
            for r in rows] == carried
    assert all(a[0] < b[0] and a[1] < b[1]
               for a, b in zip([(0, 0)] + carried, carried))
    assert all(r["dsa.queries"] == 0 for r in rows)     # not this model's
    assert "dsa.queries" not in eng.metrics.snapshot()["counters"]
    for r in rows:
        assert sum(r[p] for p in TILING) == pytest.approx(
            r["ended"] - r["began"], abs=1e-9)
        ticked = r["tick_rows"] > 0
        assert (r["device_sync"] > 0) == (r["decode_dispatch"] > 0) == ticked
        assert 0 < r["admit.prefill_dispatch"] <= r["admit"]
    assert eng.compile_cache_sizes() == {"sample": 1, "tick": 1, "chunk": 1,
                                         "set_row": 1}


def test_spec_rows_carry_draft_and_verify_and_every_token(world):
    eng = _engine(world, spec=True, draft_k=3)
    out = eng.run(_reqs(3, new=8))
    assert all(r.status == OK for r in out)
    rows = _as_dicts(eng.prof.log)
    ticking = [r for r in rows if r["tick_rows"] > 0]
    assert ticking and all(r["draft"] > 0 and r["verify"] > 0
                           and r["sample_postprocess"] == 0
                           for r in ticking)
    assert sum(r["tokens"] for r in rows) == sum(len(r) for r in out)
    assert sum(r["first_tokens"] for r in rows) == len(out)
    # spec phases join the report once a row of the window had them
    rep = eng.prof.report()
    assert set(rep["phases"]) == (set(PHASES) | set(SPEC_PHASES)
                                  | set(SUB_PHASES))
    assert rep["phases"]["sample_postprocess"]["count"] == 0


def test_tick_profiler_adds_only_histograms_and_the_event(world, tmp_path):
    log = str(tmp_path / "events.jsonl")
    reg = MetricsRegistry(event_log=metrics_mod.EventLog(log))
    eng = _engine(world, metrics=reg, profile=True, prefix_cache=True)
    eng.run(_reqs(4))
    rows = _as_dicts(eng.prof.log)
    hists = reg.snapshot()["histograms"]
    assert hists["serve.phase.tick_s"]["count"] == len(rows)
    assert hists["serve.phase.tick_s"]["sum"] == pytest.approx(
        sum(r["ended"] - r["began"] for r in rows), rel=1e-9)
    for phase, name in (("device_sync", "serve.phase.device_sync_s"),
                        ("admit.cache_acquire",
                         "serve.phase.admit_cache_acquire_s")):
        had = [r[phase] for r in rows if r[phase] > 0]
        assert hists[name]["count"] == len(had) > 0
        assert hists[name]["sum"] == pytest.approx(sum(had), rel=1e-9)
    events = [json.loads(ln) for ln in open(log)]
    ticks = [e for e in events if e["kind"] == "serve.profile_tick"]
    assert [e["step"] for e in ticks] == [r["step"] for r in rows]
    for e, r in zip(ticks, rows):
        assert e["phases"] == {p: r[p] for p in TILING + SUB_PHASES
                               if r[p] > 0}


def test_clone_engine_asks_the_class(world):
    from horovod_tpu.supervisor import clone_engine
    assert type(clone_engine(_engine(world)).prof) is PhaseSpans
    assert type(clone_engine(_engine(world, profile=True)).prof) \
        is TickProfiler
