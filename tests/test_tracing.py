"""Causal distributed tracing (horovod_tpu/tracing.py).

Three oracles pin the plane, all deterministic — no unseeded entropy
anywhere in an assertion path:

1. *Sampling is pure*: the head-sample decision and every trace/span
   id are pure functions of (seed, key) — replay the same request,
   get the same tree bit-for-bit, which is what keeps HVD010 and the
   simfleet/chaos determinism oracles green with tracing on.
2. *One request, one tree*: a request served through a 2-replica
   router with one injected replica death reconstructs as ONE span
   tree spanning both replicas — the failover replay a CHILD of the
   attempt it replaced — whose critical path tiles the
   client-observed e2e within 1 ms (the acceptance bar).
3. *Damage degrades, never throws*: torn-away parents, crash-orphaned
   opens, and cross-incarnation journal rejoins reconstruct as
   labeled partial trees; the report/compare/perf-gate tools keep
   their exit-code contracts on top.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import pytest

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from horovod_tpu import tracing
from horovod_tpu.faults import FaultRegistry
from horovod_tpu.loadgen import (
    DEFAULT_TENANTS, FixedRate, RequestMix, VirtualClock, build_schedule,
    run_open_loop, summarize_rung,
)
from horovod_tpu.metrics import EventLog, MetricsRegistry
from horovod_tpu.models import llama
from horovod_tpu.router import RouterServer
from horovod_tpu.serving import OK, Request
from horovod_tpu.serving_scheduler import ServeEngine

pytestmark = pytest.mark.trace


@pytest.fixture(scope="module")
def world():
    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(11))
    return cfg, params


def _engine(params, cfg, reg, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("chunk", 8)
    return ServeEngine(params, cfg, metrics=reg, **kw)


def _walk(root):
    stack = [root]
    while stack:
        node = stack.pop()
        stack.extend(node["children"])
        yield node


# -- identity plane: pure, seeded, no engine ---------------------------------


def test_sampling_is_pure_and_clamped():
    # shortcuts: <= 0 never samples, >= 1 always
    assert not tracing.sampled("k", 0.0, 0)
    assert not tracing.sampled("k", -1.0, 0)
    assert tracing.sampled("k", 1.0, 0)
    assert tracing.sampled("k", 2.0, 0)
    keys = [f"router:{i}" for i in range(2000)]
    picks = [k for k in keys if tracing.sampled(k, 0.3, 7)]
    # pure function of (seed, key): bit-identical on replay, different
    # under a different seed, and rate-accurate at the fraction
    assert picks == [k for k in keys if tracing.sampled(k, 0.3, 7)]
    assert picks != [k for k in keys if tracing.sampled(k, 0.3, 8)]
    assert 0.25 < len(picks) / len(keys) < 0.35

    tid = tracing.trace_id_for("router:5", 7)
    assert tid == tracing.trace_id_for("router:5", 7)
    assert tid != tracing.trace_id_for("router:5", 8)
    assert len(tid) == 32 and int(tid, 16) >= 0
    sid = tracing.child_span_id(tid, "", "client")
    assert sid == tracing.child_span_id(tid, "", "client")
    assert len(sid) == 16
    # seq disambiguates same-named siblings (failover attempt chains)
    assert sid != tracing.child_span_id(tid, "", "client", seq=1)

    # root(): None when unsampled — the no-allocation fast path
    assert tracing.TraceContext.root("k", "client", 0.0, 0) is None
    ctx = tracing.TraceContext.root("k", "client", 1.0, 0)
    assert ctx.trace_id == tracing.trace_id_for("k", 0)
    assert ctx.span_id == tracing.child_span_id(ctx.trace_id, "", "client")


def test_trace_context_header_and_dict_round_trips():
    ctx = tracing.TraceContext.root("rt", "client", 1.0, 3)
    hdr = ctx.to_header()
    assert hdr == f"00-{ctx.trace_id}-{ctx.span_id}-01"
    back = tracing.TraceContext.from_header(hdr)
    assert (back.trace_id, back.span_id) == (ctx.trace_id, ctx.span_id)
    # malformed / unsampled-flag headers degrade to None, never throw
    for bad in (None, "", "junk", "00-zz-yy-01", "00-abc-01",
                f"00-{ctx.trace_id}-{ctx.span_id}-00"):
        assert tracing.TraceContext.from_header(bad) is None
    d = ctx.to_dict()
    back = tracing.TraceContext.from_dict(d)
    assert (back.trace_id, back.span_id) == (ctx.trace_id, ctx.span_id)
    for bad in (None, {}, {"trace_id": 5}, {"trace_id": "a"}, "nope"):
        assert tracing.TraceContext.from_dict(bad) is None
    ch = ctx.child("router.request")
    assert ch.trace_id == ctx.trace_id
    assert ch.span_id == tracing.child_span_id(
        ctx.trace_id, ctx.span_id, "router.request")


def test_histogram_exemplars_in_snapshot_and_prometheus():
    reg = MetricsRegistry(event_log=None)
    h = reg.histogram("router.e2e_s")
    h.observe(0.01)                     # untraced: no exemplar machinery
    assert "exemplars" not in reg.snapshot()["histograms"]["router.e2e_s"]
    h.observe(0.02, exemplar="deadbeefdeadbeef")
    snap = reg.snapshot()["histograms"]["router.e2e_s"]
    assert any(e == {"trace_id": "deadbeefdeadbeef", "value": 0.02}
               for e in snap["exemplars"].values())
    text = reg.to_prometheus()
    assert '# {trace_id="deadbeefdeadbeef"} 0.02' in text


# -- the engine plane: one request, post-hoc span emission -------------------


def test_engine_request_tree_critical_path_and_tick_nesting(
        world, tmp_path):
    cfg, params = world
    path = str(tmp_path / "events.jsonl")
    reg = MetricsRegistry(event_log=EventLog(path))
    eng = _engine(params, cfg, reg, chunk=4, max_len=32)
    eng._trace_fraction = 1.0           # engine-origin head sampling
    out = eng.run([Request(prompt=[2, 3, 5, 7, 11], max_new_tokens=4)])
    assert out[0].ok

    records = EventLog.read(path)
    forest = tracing.build_forest(records)
    assert len(forest) == 1
    (roots,) = forest.values()
    assert len(roots) == 1
    root = roots[0]
    assert root["name"] == "serve.request"
    assert not root["unclosed"] and not root["orphan"]
    assert root["attrs"]["status"] == OK
    by_name = {c["name"]: c for c in root["children"]}
    assert {"serve.queue", "serve.prefill", "serve.decode"} <= set(by_name)
    prefill = by_name["serve.prefill"]
    # chunk spans emitted BEFORE the prefill close still join under it
    # (the parent id is derived, not allocated): 5 tokens at chunk=4
    # is two prefill windows
    assert prefill["attrs"]["chunks"] == 2
    chunks = [c for c in prefill["children"]
              if c["name"] == "serve.prefill_chunk"]
    assert len(chunks) == 2
    assert sorted(c["attrs"]["seq"] for c in chunks) == [0, 1]
    decode = by_name["serve.decode"]
    assert decode["attrs"]["n_tokens"] == 4
    assert decode["attrs"]["admit_step"] <= decode["attrs"]["terminal_step"]

    # critical path tiles the request interval EXACTLY
    path_ents = tracing.critical_path(root)
    assert sum(e["self_s"] for e in path_ents) == pytest.approx(
        root["t1"] - root["t0"], abs=1e-9)
    agg = tracing.aggregate_critical_paths(roots)
    assert agg["n_traces"] == 1
    assert sum(s["share"] for s in agg["by_name"].values()) \
        == pytest.approx(1.0)

    # registry side: sampled/spans counters and the e2e exemplar
    snap = reg.snapshot()
    assert snap["counters"]["trace.sampled"] == 1
    assert snap["counters"]["trace.spans"] >= 5
    ex = snap["histograms"]["serve.e2e_s"]["exemplars"]
    assert any(e["trace_id"] == root["trace_id"] for e in ex.values())

    # a profiler tick whose step falls in the decode span's step range
    # nests as a synthetic serve.tick child at reconstruction
    sys.path.insert(0, str(REPO_ROOT / "tools"))
    import trace_report
    tick = {"kind": "serve.profile_tick",
            "step": decode["attrs"]["admit_step"],
            "mono_s": decode["t1"], "tick_s": decode["t1"] - decode["t0"]}
    report = trace_report.build_report(records + [tick])
    assert report["n_ticks_nested"] >= 1
    assert report["n_traces"] == 1 and report["orphans"] == 0


# -- THE acceptance bar: one tree across a replica death ---------------------


def test_failover_trace_is_one_tree_spanning_replicas(world, tmp_path):
    """A sampled request served through a 2-replica router with one
    injected replica death yields ONE reconstructed trace tree
    spanning both replicas — the failover replay a child span of the
    original attempt — whose critical path tiles the client-observed
    e2e within 1 ms."""
    cfg, params = world
    log = EventLog(str(tmp_path / "events.jsonl"))
    engines = [_engine(params, cfg, MetricsRegistry(event_log=log))
               for _ in range(2)]
    fr = FaultRegistry()
    router = RouterServer(engines, policy="round_robin", faults=fr,
                          registry=MetricsRegistry(event_log=log))
    # replica0 dies before its SECOND engine step: the request is
    # admitted (its serve.request span_open is durable) and mid-flight
    fr.inject("serve.router", key="replica0", on_hit=2, permanent=True)
    try:
        ctx = tracing.TraceContext.root("acceptance", "client", 1.0, 0)
        req = Request(prompt=[2, 3, 5, 7], max_new_tokens=6)
        req.trace_ctx = ctx
        send_ts = time.monotonic()
        rid = router.route(req)
        res = router.result(rid, timeout=120)
        done_ts = time.monotonic()
        assert res is not None and res.status == OK
        snap = router.metrics.snapshot()
        assert snap["counters"]["router.failovers"] >= 1
        assert snap["counters"]["router.replica_deaths"] == 1
        # the client-side span closes the root of the tree
        router.tracer.span(ctx, "client", send_ts, done_ts,
                           status=res.status)
        # the p99-linkable exemplar on router.e2e_s names this trace
        e2e = snap["histograms"]["router.e2e_s"]
        assert any(e["trace_id"] == ctx.trace_id
                   for e in e2e["exemplars"].values())
    finally:
        router.stop()
        fr.clear()

    records = EventLog.read(str(tmp_path / "events.jsonl"))
    forest = tracing.build_forest(records)
    assert list(forest) == [ctx.trace_id]       # ONE trace
    roots = forest[ctx.trace_id]
    main = [r for r in roots if not r["orphan"]]
    assert len(main) == 1 and main[0]["name"] == "client"
    nodes = list(_walk(main[0]))

    rreq = [n for n in nodes if n["name"] == "router.request"]
    assert len(rreq) == 1 and rreq[0]["parent_id"] == ctx.span_id
    assert rreq[0]["attrs"]["failovers"] >= 1

    attempts = [n for n in nodes if n["name"] == "replica.attempt"]
    assert len(attempts) == 2
    first = next(a for a in attempts
                 if a["parent_id"] == rreq[0]["span_id"])
    assert first["attrs"]["replica"] == "replica0"
    assert first["attrs"]["status"] == "failover"
    # the replay is a CHILD of the attempt it replaced
    second = next(a for a in attempts if a is not first)
    assert second["parent_id"] == first["span_id"]
    assert second in first["children"]
    assert second["attrs"]["replica"] == "replica1"
    assert second["attrs"]["status"] == OK

    # both replicas' engines appear in the SAME tree: the dead one's
    # serve.request survives as an [unclosed] node (span_open only),
    # the survivor's closed with the full queue/prefill/decode split
    serves = [n for n in nodes if n["name"] == "serve.request"]
    assert len(serves) == 2
    dead = next(s for s in serves if s["unclosed"])
    live = next(s for s in serves if not s["unclosed"])
    assert dead["parent_id"] == first["span_id"]
    assert live["parent_id"] == second["span_id"]
    assert {"serve.queue", "serve.prefill", "serve.decode"} <= {
        c["name"] for c in live["children"]}

    # critical path tiles the client-observed e2e within 1 ms
    cp = tracing.critical_path(main[0])
    assert abs(sum(e["self_s"] for e in cp) - (done_ts - send_ts)) < 1e-3


# -- damage: partial trees, labeled, never a throw ---------------------------


def test_degraded_trees_orphan_unclosed_and_duplicate_close():
    tid = tracing.trace_id_for("deg", 0)
    root = tracing.child_span_id(tid, "", "client")
    mid = tracing.child_span_id(tid, root, "router.request")
    leaf = tracing.child_span_id(tid, mid, "replica.attempt")
    recs = [
        # the client root's record is torn away entirely; the router
        # span only ever opened (crash ate the close); the attempt
        # closed from another (pid, rank) incarnation
        {"kind": tracing.SPAN_OPEN_KIND, "trace_id": tid, "span_id": mid,
         "parent_id": root, "name": "router.request", "t0": 10.0,
         "pid": 1111, "rank": 0},
        {"kind": tracing.SPAN_KIND, "trace_id": tid, "span_id": leaf,
         "parent_id": mid, "name": "replica.attempt", "t0": 10.2,
         "t1": 10.6, "attrs": {"rid": 7}, "pid": 2222, "rank": 1},
        {"kind": "serve.submit", "rid": 1},         # non-span noise
        {"kind": tracing.SPAN_KIND, "trace_id": tid},       # torn span
        {"kind": tracing.SPAN_KIND, "trace_id": tid, "span_id": leaf,
         "parent_id": mid, "name": "replica.attempt", "t0": 10.2,
         "t1": 10.7, "pid": 2222, "rank": 1},       # replay duplicate
    ]
    forest = tracing.build_forest(recs)
    (roots,) = forest.values()
    assert len(roots) == 1
    node = roots[0]
    assert node["orphan"] and node["unclosed"]
    assert [c["span_id"] for c in node["children"]] == [leaf]
    # duplicate closes (journal-replay re-derivation) keep the last
    assert node["children"][0]["t1"] == 10.7
    # effective end falls back to the deepest descendant close, and
    # the critical path still tiles the recoverable interval
    assert tracing.span_end(node) == 10.7
    cp = tracing.critical_path(node)
    assert sum(e["self_s"] for e in cp) == pytest.approx(0.7)

    sys.path.insert(0, str(REPO_ROOT / "tools"))
    import trace_report
    text = "\n".join(trace_report.render_tree(node))
    assert "[orphan]" in text and "[unclosed]" in text
    report = trace_report.build_report(recs)
    assert report["orphans"] == 1 and report["unclosed"] == 1
    # an open record arriving AFTER the close must not reopen the span
    reopened = tracing.build_forest(recs + [
        {"kind": tracing.SPAN_OPEN_KIND, "trace_id": tid,
         "span_id": leaf, "parent_id": mid, "name": "replica.attempt",
         "t0": 10.2}])
    (roots2,) = reopened.values()
    assert not roots2[0]["children"][0]["unclosed"]


def test_journal_replay_rejoins_original_trace(world, tmp_path):
    """Crash recovery: the accept record carries the dead
    incarnation's router.request span, so the replay's span
    reconstructs as its CHILD — one trace across (pid, rid)
    incarnations, rendered as a labeled partial tree (the original's
    records died with the process)."""
    cfg, params = world
    tid = tracing.trace_id_for("incarnation-1", 0)
    dead_root = tracing.child_span_id(tid, "", "client")
    dead_span = tracing.child_span_id(tid, dead_root, "router.request")
    jpath = str(tmp_path / "journal.jsonl")
    jl = EventLog(jpath)
    jl.emit("router.accept", rid=0, key="crash-1",
            req={"prompt": [5, 6, 7], "max_new_tokens": 3},
            trace={"trace_id": tid, "span_id": dead_span})
    jl.close()

    epath = str(tmp_path / "events.jsonl")
    log = EventLog(epath)
    router = RouterServer(
        [_engine(params, cfg, MetricsRegistry(event_log=log))],
        policy="round_robin", journal=jpath,
        registry=MetricsRegistry(event_log=log))
    try:
        assert router.replay_journal() == 1
        # the keyed duplicate parks on the replay's outcome
        rid = router.route(Request(prompt=[5, 6, 7], max_new_tokens=3),
                           idempotency_key="crash-1")
        res = router.result(rid, timeout=120)
        assert res is not None and res.status == OK
    finally:
        router.stop()

    forest = tracing.build_forest(EventLog.read(epath))
    roots = forest[tid]
    replayed = [r for r in roots if r["name"] == "router.request"]
    assert len(replayed) == 1
    node = replayed[0]
    assert node["orphan"]                       # parent died unrecorded
    assert node["parent_id"] == dead_span
    assert node["span_id"] == tracing.child_span_id(
        tid, dead_span, "router.request")
    assert any(n["name"] == "serve.request" and not n["unclosed"]
               for n in _walk(node))


# -- tools: trace_report + the folded perf gate ------------------------------


def _synthetic_spans(tid_key, decode_s):
    tid = tracing.trace_id_for(tid_key, 0)
    root = tracing.child_span_id(tid, "", "serve.request")
    dec = tracing.child_span_id(tid, root, "serve.decode")
    return [
        {"kind": tracing.SPAN_KIND, "trace_id": tid, "span_id": root,
         "parent_id": None, "name": "serve.request", "t0": 0.0,
         "t1": 0.2 + decode_s, "attrs": {}},
        {"kind": tracing.SPAN_KIND, "trace_id": tid, "span_id": dec,
         "parent_id": root, "name": "serve.decode", "t0": 0.2,
         "t1": 0.2 + decode_s, "attrs": {}},
    ]


def test_trace_report_cli_render_and_compare_gate(tmp_path, capsys):
    sys.path.insert(0, str(REPO_ROOT / "tools"))
    import trace_report
    src = tmp_path / "events.jsonl"
    with open(src, "w") as f:
        for rec in _synthetic_spans("a", 0.3) + _synthetic_spans("b", 0.1):
            f.write(json.dumps(rec) + "\n")
        f.write('{"kind": "trace.sp')            # torn tail line
    assert trace_report.main([str(src), "--critical-path"]) == 0
    out = capsys.readouterr().out
    assert "2 traces" in out and "serve.decode" in out
    assert "fleet critical-path breakdown" in out

    # --json round-trips into the --compare gate; decode's share and
    # the mean critical seconds both grew => exit 1 with rows flagged
    old = {k: v for k, v in trace_report.build_report(
        trace_report.load_records([str(src)])).items() if k != "_forest"}
    new = json.loads(json.dumps(old))
    new["mean_critical_s"] = old["mean_critical_s"] * 2.0
    by = new["critical_path"]["by_name"]
    by["serve.decode"]["share"] = min(
        by["serve.decode"]["share"] + 0.4, 1.0)
    o_p, n_p = tmp_path / "old.json", tmp_path / "new.json"
    o_p.write_text(json.dumps(old))
    n_p.write_text(json.dumps(new))
    assert trace_report.main(["--compare", str(o_p), str(o_p)]) == 0
    capsys.readouterr()
    assert trace_report.main(["--compare", str(o_p), str(n_p)]) == 1
    out = capsys.readouterr().out
    assert "REGRESSED" in out
    rows = trace_report.compare_reports(old, new)
    flagged = {r["metric"] for r in rows if r["regressed"]}
    assert "mean_critical_ms" in flagged
    assert "share:serve.decode" in flagged

    # perfetto export: one lane per trace + span args, valid JSON
    perf = tmp_path / "perfetto.json"
    rep = trace_report.build_report(trace_report.load_records([str(src)]))
    n = trace_report.export_perfetto(rep, str(perf))
    events = json.loads(perf.read_text())["traceEvents"]
    assert len(events) == n
    assert {e["name"] for e in events if e["ph"] == "X"} == {
        "serve.request", "serve.decode"}


def test_loadgen_stamps_trace_ids_and_rung_exemplars(world, monkeypatch):
    cfg, params = world
    monkeypatch.setenv("HVD_TPU_TRACE_SAMPLE", "1")
    monkeypatch.setenv("HVD_TPU_TRACE_SEED", "3")
    router = RouterServer(
        [_engine(params, cfg, MetricsRegistry(event_log=None))],
        policy="round_robin")
    try:
        mix = RequestMix(DEFAULT_TENANTS, seed=2, vocab_hi=60)
        sched = build_schedule(FixedRate(20.0), mix, 0.25, seed=2)
        records = run_open_loop(router, sched, clock=VirtualClock(),
                                timeout_s=120.0)
        assert records
        assert all(isinstance(r["trace_id"], str) and r["trace_id"]
                   for r in records)
        # client-origin roots: the id is a pure function of the seeded
        # schedule, so a replay stamps the identical ids
        for idx, (a, r) in enumerate(zip(sched, records)):
            assert r["trace_id"] == tracing.trace_id_for(
                f"client:{idx}:{a.t!r}:{a.tenant}", 3)
        # the client spans reached the live ring (the /traces payload)
        ring = router.tracer.recent()
        assert sum(s["name"] == "client" for s in ring) == len(records)
    finally:
        router.stop()

    rung = summarize_rung(records, offered_rps=20.0, duration_s=0.25)
    ex = rung["exemplar_trace_ids"]
    assert 1 <= len(ex) <= 3
    # exemplars are the SLOWEST sampled requests, slowest first
    ranked = sorted((r for r in records if r["e2e_s"] is not None),
                    key=lambda r: r["e2e_s"], reverse=True)
    assert ex == [r["trace_id"] for r in ranked[:len(ex)]]

    # tools/load_report.py surfaces them under the knee attribution
    sys.path.insert(0, str(REPO_ROOT / "tools"))
    import load_report
    fake = {"rungs": [rung], "knee_index": 0,
            "knee_exemplar_trace_ids": ex}
    text = load_report.render(fake)
    assert "knee exemplar traces" in text
    assert ex[0] in text
