"""Multi-replica serving router (horovod_tpu/router.py).

Three oracles pin the router, all step-counted / socket-real, no
sleeps in any assertion path:

1. *Placement is pure*: every routing policy is a function of
   (candidates, request, context) — unit-tested against synthetic
   contexts with no engine behind them, and prefix_affinity must
   concentrate a shared-prefix workload onto one replica while
   round_robin provably spreads it.
2. *Failover is invisible*: killing a replica mid-stream (the
   ``serve.router`` fault site) re-enqueues its in-flight requests to
   survivors and every output stays bit-identical to the solo
   ``llama.generate`` run — greedy replay from the full prompt hides
   the death point by construction.
3. *The wire is honest*: shed → 429, junk body → 400, everything else
   → 200 with a terminal ``status`` field; real OS processes hammering
   one router over real sockets read byte-identical token payloads.
"""

from __future__ import annotations

import http.server
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.faults import FaultRegistry
from horovod_tpu.models import llama
from horovod_tpu.prefix_cache import chunk_path_digests
from horovod_tpu.router import (
    HttpReplica, LeastLoadedPolicy, PrefixAffinityPolicy, ReplicaHandle,
    RoundRobinPolicy, RouterServer, RoutingContext, ShadowPrefixIndex,
    request_from_json, request_to_json, resolve_routing_policy,
)
from horovod_tpu.serving import (FAILED, OK, REJECTED, Request,
                                 RequestResult)
from horovod_tpu.serving_scheduler import ServeEngine

pytestmark = pytest.mark.router

HERE = os.path.dirname(os.path.abspath(__file__))
ROUTER_WORKER = os.path.join(HERE, "multiprocess_router_worker.py")


@pytest.fixture(scope="module")
def world():
    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(11))
    return cfg, params


def _engines(params, cfg, n, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("chunk", 8)
    kw.setdefault("prefix_cache", True)
    return [ServeEngine(params, cfg, **kw) for _ in range(n)]


def _solo(params, cfg, prompt, n_new, max_len=64):
    return np.asarray(llama.generate(
        params, jnp.asarray([prompt], jnp.int32), cfg,
        max_new_tokens=n_new, max_len=max_len,
    ))[0]


# -- shadow index + policies: no engine, no socket ---------------------------


def test_shadow_prefix_index_matching():
    idx = ShadowPrefixIndex(block_size=4)
    toks = list(range(10, 23))                      # 3 full blocks + tail
    idx.observe(toks)
    assert len(idx) == 3
    assert idx.match_tokens(toks) == 12             # whole cached stem
    assert idx.match_tokens(toks[:9]) == 8          # partial block drops
    # A diverging 2nd block stops the contiguous match after block 1.
    assert idx.match_tokens(toks[:4] + [99] * 8) == 4
    assert idx.match_tokens([99, 98, 97, 96]) == 0
    # load() merges a replica's own key_digest() summary and adopts its
    # block size on a cold shadow.
    cold = ShadowPrefixIndex()
    assert cold.match_tokens(toks) == 0
    cold.load({"block_size": 4,
               "paths": chunk_path_digests(toks, 4)})
    assert cold.block_size == 4
    assert cold.match_tokens(toks) == 12
    assert cold.approx_footprint_bytes() > 0


def test_shadow_prefix_index_fifo_bound():
    idx = ShadowPrefixIndex(block_size=2, max_paths=4)
    for i in range(8):
        idx.observe([i * 10, i * 10 + 1])           # 8 distinct digests
    assert len(idx) == 4                            # oldest 4 evicted
    assert idx.match_tokens([0, 1]) == 0
    assert idx.match_tokens([70, 71]) == 2


def _ctx(inflight, shadows=None, views=None, imbalance=4.0):
    return RoutingContext(views or {}, shadows or {}, inflight,
                          imbalance)


def test_round_robin_and_least_loaded_policies():
    rr = RoundRobinPolicy()
    names = [rr.choose(["a", "b", "c"], None, _ctx({}))[0]
             for _ in range(5)]
    assert names == ["a", "b", "c", "a", "b"]
    ll = LeastLoadedPolicy()
    assert ll.choose(["a", "b"], None, _ctx({"a": 3, "b": 1}))[0] == "b"
    # Equal queues: the SLO-missing replica is effectively fuller.
    views = {"a": {"goodput": 0.4}, "b": {"goodput": 0.9}}
    assert ll.choose(["a", "b"], None,
                     _ctx({"a": 2, "b": 2}, views=views))[0] == "b"


def test_prefix_affinity_policy_and_imbalance_fallback():
    stem = list(range(10, 27))                      # 17 tokens, 2 blocks
    hot, cold = ShadowPrefixIndex(8), ShadowPrefixIndex(8)
    hot.observe(stem)
    shadows = {"hot": hot, "cold": cold}
    pol = PrefixAffinityPolicy()
    req = Request(prompt=stem + [99], max_new_tokens=2)

    name, info = pol.choose(["hot", "cold"], req,
                            _ctx({"hot": 0, "cold": 0}, shadows))
    assert name == "hot"
    assert info == {"affinity_hit_tokens": 16, "fallback": False}
    # No match anywhere: least-loaded, hit length 0.
    name, info = pol.choose(["hot", "cold"],
                            Request(prompt=[99, 98], max_new_tokens=2),
                            _ctx({"hot": 2, "cold": 0}, shadows))
    assert name == "cold" and info["affinity_hit_tokens"] == 0
    # Affinity choice 5 requests deeper than the emptiest replica with
    # imbalance=4: locality loses to load, flagged as a fallback.
    name, info = pol.choose(["hot", "cold"], req,
                            _ctx({"hot": 5, "cold": 0}, shadows))
    assert name == "cold" and info["fallback"] is True


def test_resolve_routing_policy(monkeypatch):
    assert resolve_routing_policy("round_robin").name == "round_robin"
    inst = LeastLoadedPolicy()
    assert resolve_routing_policy(inst) is inst
    monkeypatch.setenv("HVD_TPU_ROUTER_POLICY", "least_loaded")
    assert resolve_routing_policy(None).name == "least_loaded"
    monkeypatch.delenv("HVD_TPU_ROUTER_POLICY")
    assert resolve_routing_policy(None).name == "prefix_affinity"
    with pytest.raises(ValueError, match="unknown routing policy"):
        resolve_routing_policy("best_effort")


def test_request_json_roundtrip():
    req = Request(prompt=[1, 2, 3], max_new_tokens=5, priority=2,
                  slo_s=1.5)
    back = request_from_json(request_to_json(req))
    assert back.prompt == [1, 2, 3] and back.max_new_tokens == 5
    assert back.priority == 2 and back.slo_s == 1.5
    with pytest.raises(ValueError, match="list of token ids"):
        request_from_json({"prompt": "abc", "max_new_tokens": 2})
    with pytest.raises(ValueError, match="max_new_tokens"):
        request_from_json({"prompt": [1], "max_new_tokens": "2"})
    with pytest.raises(ValueError, match="JSON object"):
        request_from_json([1, 2])
    # explicit null priority is absent-priority, not a crash
    assert request_from_json({"prompt": [1], "max_new_tokens": 1,
                              "priority": None}).priority == 0


def test_request_json_lifecycle_field_validation():
    """Every optional lifecycle field is type-checked at the door: junk
    must be a ValueError (HTTP 400) HERE, not a TypeError later inside
    a replica pump's submit/step arithmetic — where the router would
    read the crash as a replica death and replay the poisoned request
    onto each survivor in turn."""
    ok = request_from_json({"prompt": [1], "max_new_tokens": 2,
                            "deadline_s": 1.5, "slo_s": 2,
                            "max_queue_steps": 3, "eos_id": 7})
    assert ok.deadline_s == 1.5 and ok.slo_s == 2
    assert ok.max_queue_steps == 3 and ok.eos_id == 7
    for field, junk in [("deadline_s", "soon"), ("deadline_s", True),
                        ("slo_s", [1]), ("max_queue_steps", 2.5),
                        ("max_queue_steps", "many"), ("eos_id", "eos"),
                        ("priority", "high")]:
        with pytest.raises(ValueError, match=field):
            request_from_json({"prompt": [1], "max_new_tokens": 2,
                               field: junk})


# -- routing through real engines --------------------------------------------


def test_affinity_concentrates_shared_prefix(world):
    """The headline behavior: a shared-prefix workload lands on ONE
    replica under prefix_affinity (fleet cache hits) while round_robin
    provably spreads it — and the tokens are identical either way."""
    cfg, params = world
    stem = list(range(2, 19))                       # 2 full blocks of 8
    reqs = [Request(prompt=stem + [40 + i], max_new_tokens=4)
            for i in range(4)]
    solo = {i: _solo(params, cfg, r.prompt, 4) for i, r in
            enumerate(reqs)}

    outs = {}
    for policy in ("round_robin", "prefix_affinity"):
        router = RouterServer(_engines(params, cfg, 2), policy=policy)
        try:
            rids = [router.route(r) for r in reqs]
            res = [router.result(rid, timeout=60) for rid in rids]
            assert all(r.status == OK for r in res)
            for i, r in enumerate(res):
                np.testing.assert_array_equal(
                    np.asarray(list(r), np.int64),
                    solo[i].astype(np.int64))
            outs[policy] = {rep["name"]: rep["routed"]
                            for rep in router.replicas_report()}
            snap = router.metrics.snapshot()
            assert snap["counters"][f"router.routed.{policy}"] == 4
            if policy == "prefix_affinity":
                hist = snap["histograms"]["router.affinity_hit_tokens"]
                assert hist["count"] == 4
                assert hist["max"] == 16.0      # warmed shadow matched
        finally:
            router.stop()
    assert sorted(outs["round_robin"].values()) == [2, 2]
    assert sorted(outs["prefix_affinity"].values()) == [0, 4]


def test_admission_shed_and_rejected_passthrough(world):
    cfg, params = world
    router = RouterServer(_engines(params, cfg, 1),
                          policy="round_robin", min_goodput=2.0)
    try:
        rid = router.route(Request(prompt=[3, 5], max_new_tokens=2))
        res = router.result(rid, timeout=10)
        assert res.status == REJECTED and list(res) == []
        code, body = router.handle_generate(
            Request(prompt=[3, 5], max_new_tokens=2))
        assert code == 429 and body["shed"] == "goodput"
        snap = router.metrics.snapshot()
        assert snap["counters"]["router.sheds"] == 2
        assert snap["counters"]["router.requests"] == 2
    finally:
        router.stop()

    # An engine-level REJECTED (empty prompt) rides back through the
    # router as a terminal result — not a failover, not an exception.
    router = RouterServer(_engines(params, cfg, 1),
                          policy="round_robin")
    try:
        rid = router.route(Request(prompt=[], max_new_tokens=2))
        res = router.result(rid, timeout=30)
        assert res.status == REJECTED
        assert router.metrics.snapshot()["counters"]["router.failovers"] \
            == 0
    finally:
        router.stop()


def test_failover_outputs_bit_identical(world):
    """Kill a replica mid-stream via the ``serve.router`` fault site:
    its in-flight requests re-enqueue to the survivor and every token
    stream is bit-identical to the solo run — the failover acceptance
    bar."""
    cfg, params = world
    fr = FaultRegistry()
    router = RouterServer(_engines(params, cfg, 2),
                          policy="round_robin", faults=fr)
    fr.inject("serve.router", key="replica0", on_hit=3, permanent=True)
    try:
        reqs = [Request(prompt=[2 + i, 3 + i, 5 + i, 7 + i],
                        max_new_tokens=6) for i in range(4)]
        rids = [router.route(r) for r in reqs]
        res = [router.result(rid, timeout=60) for rid in rids]
        assert all(r.status == OK for r in res)
        for req, r in zip(reqs, res):
            np.testing.assert_array_equal(
                np.asarray(list(r), np.int64),
                _solo(params, cfg, req.prompt, 6).astype(np.int64))
        snap = router.metrics.snapshot()
        assert snap["counters"]["router.replica_deaths"] == 1
        assert snap["counters"]["router.failovers"] >= 1
        assert snap["gauges"]["router.replicas_healthy"] == 1
        report = {rep["name"]: rep for rep in router.replicas_report()}
        assert not report["replica0"]["healthy"]
        assert report["replica1"]["healthy"]
        # With the whole fleet dead, routing fails terminally (and
        # /healthz goes 503) instead of hanging a client forever.
        fr.inject("serve.router", key="replica1", on_hit=1,
                  permanent=True)
        rid = router.route(Request(prompt=[9, 8, 7], max_new_tokens=4))
        res = router.result(rid, timeout=60)
        assert res.status == FAILED
        assert "no healthy replicas" in str(res.error)
        code, body = router.health()
        assert code == 503 and body["healthy"] == 0
    finally:
        router.stop()
        fr.clear()


# -- hardening: poison requests, ticket hygiene, probe debounce --------------


class _EchoReplica(ReplicaHandle):
    """Completes every submission instantly with OK(prompt) — a replica
    with no engine behind it, for router-bookkeeping tests."""

    def __init__(self, name: str = "echo"):
        self.name = name

    def submit(self, req, done_cb):
        done_cb(RequestResult(list(req.prompt), OK))

    def probe(self):
        return {"healthy": True, "inflight": 0, "queue_depth": 0,
                "goodput": 1.0, "free_kv_frac": 1.0, "prefix": None}


class _CrashingReplica(_EchoReplica):
    """Signals death-in-flight (the ``None`` failover signal) for every
    submission while always probing healthy — the worst case of a
    poison request that kills whatever pump it lands on."""

    def submit(self, req, done_cb):
        done_cb(None)


def test_malformed_lifecycle_request_rejected_not_fatal(world):
    """A programmatic caller can hand the router a Request whose
    deadline_s is a string (bypassing request_from_json); the engine's
    submit-side arithmetic raises TypeError, which the pump maps to a
    terminal REJECTED — not a replica death followed by a poison
    replay across the fleet."""
    cfg, params = world
    router = RouterServer(_engines(params, cfg, 1),
                          policy="round_robin")
    try:
        rid = router.route(Request(prompt=[2, 3], max_new_tokens=2,
                                   deadline_s="soon"))
        res = router.result(rid, timeout=30)
        assert res.status == REJECTED
        snap = router.metrics.snapshot()
        assert snap["counters"]["router.replica_deaths"] == 0
        assert snap["counters"]["router.failovers"] == 0
        # The replica survived and still serves.
        rid = router.route(Request(prompt=[2, 3], max_new_tokens=2))
        assert router.result(rid, timeout=60).status == OK
    finally:
        router.stop()


def test_failover_cap_stops_poison_cascade():
    """A request that kills every replica it lands on is replayed at
    most max_failovers times, then fails terminally — it must not
    bounce around the fleet forever."""
    router = RouterServer(
        [_CrashingReplica("a"), _CrashingReplica("b")],
        policy="round_robin", max_failovers=3)
    try:
        rid = router.route(Request(prompt=[1, 2], max_new_tokens=2))
        res = router.result(rid, timeout=10)
        assert res.status == FAILED
        assert "failed over 3 times" in str(res.error)
        snap = router.metrics.snapshot()
        assert snap["counters"]["router.failovers"] == 3
        assert snap["gauges"]["router.inflight"] == 0
    finally:
        router.stop()


def test_ticket_reaping_bounds_the_table():
    router = RouterServer([_EchoReplica()], policy="round_robin",
                          ticket_ttl_s=0.0)
    try:
        code, body = router.handle_generate(
            Request(prompt=[4, 2], max_new_tokens=1))
        assert code == 200 and body["tokens"] == [4, 2]
        # The HTTP reply is a ticket's last reader: popped with it.
        assert router.memory_report()["tickets"] == 0
        rid = router.route(Request(prompt=[7], max_new_tokens=1))
        assert router.result(rid, timeout=10).status == OK
        assert router.memory_report()["tickets"] == 1
        router.poll_now()       # the poller reaps done tickets past TTL
        assert router.memory_report()["tickets"] == 0
        with pytest.raises(KeyError, match="unknown router rid"):
            router.result(rid)
    finally:
        router.stop()


def test_probe_debounce_and_http_revival():
    """An HTTP-style (can_revive) replica needs probe_fails CONSECUTIVE
    failed probes to leave the candidate set — one blip must not
    permanently shrink the fleet — and healthy probes bring it back."""

    class _Flaky(_EchoReplica):
        can_revive = True
        healthy = True

        def probe(self):
            return dict(super().probe(), healthy=self.healthy)

    flaky = _Flaky("flaky")
    router = RouterServer([flaky, _EchoReplica()],
                          policy="round_robin", probe_fails=3)
    try:
        def healthy_gauge():
            return router.metrics.snapshot()["gauges"][
                "router.replicas_healthy"]

        flaky.healthy = False
        router.poll_now()
        router.poll_now()
        assert healthy_gauge() == 2         # two blips: still routable
        flaky.healthy = True
        router.poll_now()                   # healthy probe resets count
        flaky.healthy = False
        router.poll_now()
        router.poll_now()
        assert healthy_gauge() == 2
        router.poll_now()                   # third consecutive: dead
        assert healthy_gauge() == 1
        report = {r["name"]: r for r in router.replicas_report()}
        assert not report["flaky"]["healthy"]
        flaky.healthy = True
        router.poll_now()                   # HTTP replicas rejoin
        assert healthy_gauge() == 2
        snap = router.metrics.snapshot()
        assert snap["counters"]["router.replica_deaths"] == 1
        assert snap["counters"]["router.replica_revives"] == 1
    finally:
        router.stop()


def test_http_replica_timeout_is_terminal_not_failover():
    """A socket timeout means slow-but-alive: the submission must fail
    terminally rather than fire the None failover signal (replaying
    elsewhere would silently run the decode twice).  A refused
    connection is a dead backend and still signals failover."""

    class _Slow(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            time.sleep(0.8)
            try:
                body = b'{"tokens": [], "status": "OK"}'
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            except Exception:
                pass                        # client already gave up

        def log_message(self, fmt, *args):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), _Slow)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        slow = HttpReplica(
            "slow", f"http://127.0.0.1:{srv.server_address[1]}",
            timeout_s=0.2)
        got: list = []
        ev = threading.Event()
        slow.submit(Request(prompt=[1], max_new_tokens=1),
                    lambda r: (got.append(r), ev.set()))
        assert ev.wait(10)
        assert got[0] is not None and got[0].status == FAILED
    finally:
        srv.shutdown()
        srv.server_close()

    refused = HttpReplica("refused", "http://127.0.0.1:9",
                          timeout_s=0.5)
    got2: list = []
    ev2 = threading.Event()
    refused.submit(Request(prompt=[1], max_new_tokens=1),
                   lambda r: (got2.append(r), ev2.set()))
    assert ev2.wait(10)
    assert got2[0] is None

    # Deadline-carrying requests stretch the wire budget past their own
    # deadline, so an engine-side TIMEOUT reply beats the socket.
    rep = HttpReplica("r", "http://example.invalid", timeout_s=30.0)
    assert rep._request_timeout_s(
        Request(prompt=[1], max_new_tokens=1)) == 30.0
    assert rep._request_timeout_s(
        Request(prompt=[1], max_new_tokens=1, deadline_s=45.0)) == 75.0


def test_memory_report_counts_shadow_indexes(world):
    cfg, params = world
    router = RouterServer(_engines(params, cfg, 2),
                          policy="prefix_affinity")
    try:
        rid = router.route(Request(prompt=list(range(2, 19)),
                                   max_new_tokens=2))
        assert router.result(rid, timeout=60).status == OK
        mem = router.memory_report()
        assert mem["approx_footprint_bytes"] == sum(
            mem["shadow_index_bytes"].values())
        assert set(mem["shadow_index_bytes"]) == {"replica0", "replica1"}
        assert router.metrics.snapshot()["gauges"][
            "router.shadow_index_bytes"] == mem["approx_footprint_bytes"]
    finally:
        router.stop()


def test_poller_merges_replica_digests(world):
    """poll_now() pulls each replica's key_digest() summary into its
    shadow — the authoritative feed: a prompt served OUTSIDE the
    router (warmed directly on the engine) still attracts affinity."""
    cfg, params = world
    engines = _engines(params, cfg, 2)
    stem = list(range(2, 19))
    engines[1].run([Request(prompt=stem + [77], max_new_tokens=2)])
    router = RouterServer(engines, policy="prefix_affinity")
    try:
        router.poll_now()
        rid = router.route(Request(prompt=stem + [88],
                                   max_new_tokens=2))
        assert router.result(rid, timeout=60).status == OK
        report = {rep["name"]: rep for rep in router.replicas_report()}
        assert report["replica1"]["routed"] == 1
        assert report["replica0"]["routed"] == 0
        view = report["replica1"]["view"]
        assert view["healthy"] and view["free_kv_frac"] > 0
    finally:
        router.stop()


# -- the HTTP front door ------------------------------------------------------


def test_http_front_door(world):
    cfg, params = world
    router = RouterServer(_engines(params, cfg, 1),
                          policy="round_robin").start()
    base = f"http://{router.host}:{router.port}"
    try:
        body = json.dumps({"prompt": [5, 17, 42],
                           "max_new_tokens": 4}).encode()
        req = urllib.request.Request(
            base + "/v1/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            out = json.loads(r.read())
        assert out["status"] == OK and out["replica"] == "replica0"
        np.testing.assert_array_equal(
            np.asarray(out["tokens"], np.int64),
            _solo(params, cfg, [5, 17, 42], 4).astype(np.int64))

        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            assert json.loads(r.read())["ok"]
        with urllib.request.urlopen(base + "/replicas", timeout=10) as r:
            assert json.loads(r.read())[0]["routed"] == 1
        with urllib.request.urlopen(base + "/snapshot", timeout=10) as r:
            snap = json.loads(r.read())
        assert snap["counters"]["router.requests"] == 1
        assert snap["replicas"][0]["name"] == "replica0"
        with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
            text = r.read().decode()
        assert "router_requests 1" in text
        assert "# HELP router_sheds" in text
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(base + "/nope", timeout=10)
        assert e.value.code == 404
    finally:
        router.stop()


# -- the pump and route() as spans on jax's profiler trace -------------------

PUMP_SPANS = {"replica.pump.submit", "replica.pump.callbacks",
              "replica.pump.view", "replica.pump.wait", "serve.step"}


@pytest.mark.parametrize("profile", [False, True],
                         ids=["spans", "profiler"])
def test_trace_pump_thread_has_no_unnamed_stretch(world, host_trace,
                                                  profile):
    cfg, params = world
    eng, = _engines(params, cfg, 1, profile=profile)
    eng.run([Request(prompt=[5, 17, 42], max_new_tokens=2)])   # compile
    router = RouterServer([eng], policy="round_robin")
    try:
        with host_trace() as tr:
            time.sleep(0.02)             # a few rounds of the idle pump
            rids = [router.route(Request(prompt=[3, 5, 7 + i],
                                         max_new_tokens=6))
                    for i in range(4)]
            out = [router.result(r, timeout=60) for r in rids]
    finally:
        router.stop()
    assert all(r.status == OK for r in out)
    line = tr.line_with("serve.step")
    top = tr.children(line, ("", line[0][1], max(e[2] for e in line)))
    # while requests are pending the pump's line is its four sections
    # and the engine's step, end to end: no stretch of it without a name
    first = next(i for i, e in enumerate(top) if e[0] == "serve.step")
    last = max(i for i, e in enumerate(top) if e[0] == "serve.step")
    busy = top[first - 1:last + 3]       # submit ... callbacks, view
    assert {e[0] for e in busy} <= PUMP_SPANS
    assert {"replica.pump.submit", "replica.pump.callbacks",
            "replica.pump.view", "serve.step"} <= {e[0] for e in busy}
    assert [e[0] for e in busy[-2:]] == ["replica.pump.callbacks",
                                        "replica.pump.view"]
    # (between each two sections as the round usually goes: one gap may
    # be the thread's wait for a core, which is not the pump's)
    gaps: dict = {}
    for a, b in zip(busy, busy[1:]):
        gaps.setdefault((a[0], b[0]), []).append(b[1] - a[2])
    assert max(np.median(g) for g in gaps.values()) < 1e6   # 1 ms
    # the idle pump waits under a name too
    assert any(e[0] == "replica.pump.wait" for e in top)


@pytest.mark.parametrize("door", ["route", "handle_generate"])
def test_trace_route_encloses_admission_and_place(world, host_trace, door):
    cfg, params = world
    router = RouterServer(_engines(params, cfg, 1), policy="round_robin")
    req = Request(prompt=[3, 5, 7], max_new_tokens=2)
    try:
        with host_trace() as tr:
            if door == "route":
                res = router.result(router.route(req), timeout=60)
                assert res.status == OK
            else:
                code, body = router.handle_generate(req)
                assert code == 200 and body["status"] == OK
    finally:
        router.stop()
    line = tr.line_with("router.route")
    route, = [e for e in line if e[0] == "router.route"]
    assert [e[0] for e in tr.children(line, route)] == [
        "router.admission", "router.place", "router.submit"]
    # the caller's thread, not the pump's
    assert line is not tr.line_with("serve.step")


def test_multiprocess_router_real_sockets(world):
    """Real OS processes, real sockets: stdlib-only clients hammer one
    router concurrently and read byte-identical token payloads
    (greedy determinism end to end through the HTTP plane)."""
    cfg, params = world
    router = RouterServer(_engines(params, cfg, 2),
                          policy="prefix_affinity").start()
    try:
        outs = []
        procs = []
        for wid in range(2):
            env = dict(os.environ)
            env["ROUTER_URL"] = f"http://{router.host}:{router.port}"
            env["ROUTER_WORKER_ID"] = str(wid)
            procs.append(subprocess.Popen(
                [sys.executable, ROUTER_WORKER], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        for p in procs:
            outs.append(p.communicate(timeout=180)[0])
        payloads = []
        for i, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"worker {i} rc={p.returncode}:\n{out}"
            assert "WORKER_OK" in out, f"worker {i} no OK line:\n{out}"
            payloads.append(out.split("WORKER_OK ", 1)[1].splitlines()[0])
        assert payloads[0] == payloads[1], (
            "token payloads differ across workers:\n"
            + "\n---\n".join(payloads))
        tokens = json.loads(payloads[0])["results"][0]["tokens"]
        want = _solo(params, cfg, list(range(2, 19)) + [40], 4)
        np.testing.assert_array_equal(np.asarray(tokens, np.int64),
                                      want.astype(np.int64))
    finally:
        router.stop()
