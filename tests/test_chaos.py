"""Self-healing fleet (horovod_tpu/supervisor.py, chaos.py, and the
router's crash-durability layer).

Four oracles pin the stack:

1. *Storms are replayable*: a :class:`ChaosSchedule` is a pure
   function of its seed — same seed, same rules, same kills — and the
   first ``len(STORM_SITES)`` rules provably cover every storm site.
2. *The journal is exactly-once*: every accepted request either
   reaches a journaled terminal or is replayed by the next router
   incarnation (drain-timeout included), duplicate idempotency keys
   read one result without re-running, and a torn WAL tail costs at
   most the half-written line.
3. *Respawn is budgeted*: the supervisor retries a dead replica only
   after exponential backoff, a firing ``serve.supervisor`` fault
   burns real budget, and the circuit-breaker makes a replica that
   keeps dying permanent-dead instead of hot-looping.
4. *Healing is invisible*: a respawned local replica serves
   bit-identical tokens (greedy determinism through clone_engine),
   and a full seeded campaign — engine-site storm plus a replica
   kill — passes every invariant oracle.
"""

from __future__ import annotations

import json
import os
import signal
import socket
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

from horovod_tpu.chaos import (
    KILL_SITE, STORM_SITES, ChaosRule, ChaosSchedule, compare_campaigns,
    run_campaign,
)
from horovod_tpu.faults import FaultRegistry
from horovod_tpu.metrics import EventLog
from horovod_tpu.models import llama
from horovod_tpu.router import (
    HttpReplica, ReplicaHandle, RouterServer, load_journal,
    request_to_json,
)
from horovod_tpu.serving import FAILED, OK, Request, RequestResult
from horovod_tpu.serving_scheduler import ServeEngine
from horovod_tpu.supervisor import ReplicaSupervisor

pytestmark = pytest.mark.chaos

HERE = os.path.dirname(os.path.abspath(__file__))
SUP_WORKER = os.path.join(HERE, "multiprocess_supervisor_worker.py")


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


class _BlackHole(ReplicaHandle):
    """A replica that accepts submissions and never answers — the
    deterministic way to hold a request in flight forever."""

    name = "hole"
    block_size = 8

    def __init__(self):
        self.cbs = []

    def submit(self, req, done_cb):
        self.cbs.append(done_cb)

    def probe(self):
        return {"healthy": True, "inflight": len(self.cbs),
                "queue_depth": 0, "goodput": 1.0, "free_kv_frac": 1.0}


# -- schedules and the regression gate: no engine, no jax compute ------------


def test_chaos_schedule_deterministic_and_covering():
    names = ["replica0", "replica1", "replica2"]
    a = ChaosSchedule.generate(7, replica_names=names)
    b = ChaosSchedule.generate(7, replica_names=names)
    assert a.to_json() == b.to_json()           # seed IS the schedule
    assert ChaosSchedule.generate(8, replica_names=names).to_json() \
        != a.to_json()
    # Coverage guarantee: the first len(sites) rules cycle every site.
    assert {r.site for r in a.rules} == set(STORM_SITES)
    assert set(a.sites()) == set(STORM_SITES) | {KILL_SITE}
    for k in a.kills:
        assert k.site == KILL_SITE and k.key in names
        assert 2 <= k.on_hit <= 8 and k.count == 1
    # A rule arms as a real registry fault at its scheduled hit.
    fr = FaultRegistry()
    ChaosRule(site="serve.tick", on_hit=2).arm(fr)
    fr.check("serve.tick")
    with pytest.raises(Exception):
        fr.check("serve.tick")
    assert fr.log == [("serve.tick", None, 2)]


def test_compare_campaigns_gate():
    old = {"oracles": {"bit_identical": True, "healed": True},
           "ok": True, "ok_fraction": 1.0}
    same = {"oracles": {"bit_identical": True, "healed": True},
            "ok": True, "ok_fraction": 0.95}
    ok, problems = compare_campaigns(old, same)
    assert ok and not problems                  # within threshold
    broken = {"oracles": {"bit_identical": True, "healed": False},
              "ok": False, "ok_fraction": 0.5}
    ok, problems = compare_campaigns(old, broken)
    assert not ok
    assert any("healed" in p for p in problems)
    assert any("ok_fraction" in p for p in problems)
    # Soak reports gate on min_ok_fraction.
    ok, problems = compare_campaigns({"min_ok_fraction": 1.0, "ok": True},
                                     {"min_ok_fraction": 0.7, "ok": True})
    assert not ok and "min_ok_fraction" in problems[0]


# -- the request journal -----------------------------------------------------


def test_torn_journal_line_tolerated(tmp_path):
    path = str(tmp_path / "journal.jsonl")
    log = EventLog(path)
    log.emit("router.accept", rid=0, key="k0",
             req={"prompt": [2, 3, 4], "max_new_tokens": 2})
    log.emit("router.accept", rid=1, key=None,
             req={"prompt": [5, 6], "max_new_tokens": 2})
    log.emit("router.terminal", rid=1, key=None, status=OK,
             tokens=[9], error=None)
    log.close()
    with open(path, "a") as f:
        f.write('{"kind": "router.acc')        # crash mid-append
    incomplete, terms = load_journal(path)
    assert [r["key"] for r in incomplete] == ["k0"]
    assert terms == {}                          # unkeyed terminal: no dedup
    # A terminal for k0 retires it; several crashed accepts of one key
    # collapse to a single replay.
    log = EventLog(path)
    log.emit("router.accept", rid=7, key="dup",
             req={"prompt": [2], "max_new_tokens": 1})
    log.emit("router.accept", rid=8, key="dup",
             req={"prompt": [2], "max_new_tokens": 1})
    log.emit("router.terminal", rid=0, key="k0", status=OK,
             tokens=[1, 2], error=None)
    log.close()
    incomplete, terms = load_journal(path)
    assert [r["key"] for r in incomplete] == ["dup"]
    assert terms["k0"]["tokens"] == [1, 2]


def test_journal_accept_terminal_roundtrip_and_drain(world, tmp_path):
    cfg, params = world
    path = str(tmp_path / "journal.jsonl")
    router = RouterServer(_engines(params, cfg, 1), policy="round_robin",
                          journal=path)
    rid = router.route(Request(prompt=[5, 17, 42], max_new_tokens=4),
                       idempotency_key="req-A")
    res = router.result(rid, timeout=120)
    assert res is not None and res.status == OK
    want = _solo(params, cfg, [5, 17, 42], 4).astype(np.int64)
    np.testing.assert_array_equal(np.asarray(list(res), np.int64), want)
    # stop() drains: a request routed moments before shutdown still
    # finishes (and lands its terminal record) inside the drain window.
    rid2 = router.route(Request(prompt=[5, 17, 42, 7], max_new_tokens=4))
    router.stop(drain_s=60.0)
    res2 = router.result(rid2, timeout=0)
    assert res2 is not None and res2.status == OK
    incomplete, terms = load_journal(path)
    assert incomplete == []                     # every accept paired
    assert list(terms) == ["req-A"]
    assert terms["req-A"]["tokens"] == [int(t) for t in res]
    assert router.metrics.snapshot()["counters"][
        "router.journal_appends"] == 4          # 2 accepts + 2 terminals


def test_journal_dedup_terminal_inflight_and_restart(world, tmp_path):
    cfg, params = world
    path = str(tmp_path / "journal.jsonl")
    req = Request(prompt=[3, 9, 27, 81], max_new_tokens=4)
    router = RouterServer(_engines(params, cfg, 1), policy="round_robin",
                          journal=path)
    try:
        rid1 = router.route(req, idempotency_key="pay-once")
        res1 = router.result(rid1, timeout=120)
        assert res1.status == OK
        # Terminal dedup: the duplicate answers from the journal map
        # without a second run.
        rid2 = router.route(req, idempotency_key="pay-once")
        res2 = router.result(rid2, timeout=10)
        assert list(res2) == list(res1)
        counters = router.metrics.snapshot()["counters"]
        assert counters["router.journal_dedups"] == 1
        assert counters["router.routed.round_robin"] == 1
    finally:
        router.stop()

    # Restart: the journaled terminal survives the process boundary —
    # the duplicate never touches the fresh replica.
    router = RouterServer(_engines(params, cfg, 1), policy="round_robin",
                          journal=path)
    try:
        rid3 = router.route(req, idempotency_key="pay-once")
        res3 = router.result(rid3, timeout=10)
        assert list(res3) == list(res1)
        counters = router.metrics.snapshot()["counters"]
        assert counters["router.journal_dedups"] == 1
        assert counters["router.routed.round_robin"] == 0
    finally:
        router.stop()

    # In-flight dedup: while the original is live, a duplicate parks on
    # its outcome instead of running twice (black hole makes the
    # in-flight window deterministic).
    hole = _BlackHole()
    router = RouterServer([hole], journal=str(tmp_path / "j2.jsonl"))
    try:
        rid_a = router.route(req, idempotency_key="k-live")
        rid_b = router.route(req, idempotency_key="k-live")
        assert len(hole.cbs) == 1               # one submission only
        assert router.result(rid_b, timeout=0) is None
        hole.cbs[0](RequestResult([11, 12, 13], OK))
        res_a = router.result(rid_a, timeout=10)
        res_b = router.result(rid_b, timeout=10)
        assert list(res_a) == list(res_b) == [11, 12, 13]
        assert router.metrics.snapshot()["counters"][
            "router.journal_dedups"] == 1
    finally:
        router.stop()


def test_journal_write_fault_degrades_not_fails(world, tmp_path):
    cfg, params = world
    fr = FaultRegistry()
    fr.inject("router.journal", on_hit=1, key="router.accept")
    router = RouterServer(_engines(params, cfg, 1), policy="round_robin",
                          journal=str(tmp_path / "journal.jsonl"),
                          faults=fr)
    try:
        rid = router.route(Request(prompt=[5, 17, 42], max_new_tokens=4),
                           idempotency_key="k")
        res = router.result(rid, timeout=120)
        # Durability degraded — the accept append was lost — but the
        # request itself still served, bit-identically.
        assert res.status == OK
        want = _solo(params, cfg, [5, 17, 42], 4).astype(np.int64)
        np.testing.assert_array_equal(
            np.asarray(list(res), np.int64), want)
        counters = router.metrics.snapshot()["counters"]
        assert counters["router.journal_errors"] == 1
        assert counters["router.journal_appends"] == 1  # the terminal
        assert fr.log == [("router.journal", "router.accept", 1)]
    finally:
        router.stop()


def test_drain_timeout_fails_open_and_replays_next_incarnation(
        world, tmp_path):
    cfg, params = world
    path = str(tmp_path / "journal.jsonl")
    req = Request(prompt=[5, 17, 42], max_new_tokens=4)
    hole = _BlackHole()
    router = RouterServer([hole], journal=path)
    rid = router.route(req, idempotency_key="lost-boy")
    router.stop(drain_s=0.05)                   # hole never answers
    res = router.result(rid, timeout=0)
    assert res is not None and res.status == FAILED
    assert "shut down" in str(res.error)
    # The abandoned request's accept stayed unpaired — the next
    # incarnation owes it a replay.
    incomplete, terms = load_journal(path)
    assert [r["key"] for r in incomplete] == ["lost-boy"]
    assert terms == {}

    router = RouterServer(_engines(params, cfg, 1), policy="round_robin",
                          journal=path)
    try:
        assert router.replay_journal() == 1
        # The client's retry parks on (or dedups against) the replay
        # and reads the exact tokens the lost incarnation owed it.
        rid2 = router.route(req, idempotency_key="lost-boy")
        res2 = router.result(rid2, timeout=120)
        assert res2.status == OK
        want = _solo(params, cfg, [5, 17, 42], 4).astype(np.int64)
        np.testing.assert_array_equal(
            np.asarray(list(res2), np.int64), want)
        counters = router.metrics.snapshot()["counters"]
        assert counters["router.journal_replays"] == 1
        assert counters["router.journal_dedups"] == 1
        assert router.replay_journal() == 0     # replay is one-shot
    finally:
        router.stop()
    incomplete, _terms = load_journal(path)
    assert incomplete == []                     # debt paid


def test_stop_releases_parked_idempotency_waiters(tmp_path):
    # A duplicate parked on an in-flight key has replica=None and no
    # accept record of its own; stop() must fail it explicitly or its
    # handle_generate thread waits on done forever.
    path = str(tmp_path / "journal.jsonl")
    req = Request(prompt=[5, 17, 42], max_new_tokens=4)
    hole = _BlackHole()
    router = RouterServer([hole], journal=path)
    rid_orig = router.route(req, idempotency_key="stuck")
    rid_dup = router.route(req, idempotency_key="stuck")
    assert len(hole.cbs) == 1                   # dup parked, not routed
    router.stop(drain_s=0.05)
    for rid in (rid_orig, rid_dup):
        res = router.result(rid, timeout=0)     # no wait: both released
        assert res is not None and res.status == FAILED
        assert "shut down" in str(res.error)
    assert router._journal_waiters == {}
    assert router._journal_inflight == {}
    # Only the original's accept is owed a replay.
    incomplete, terms = load_journal(path)
    assert [r["key"] for r in incomplete] == ["stuck"]
    assert terms == {}


def test_stop_releases_http_handler_threads(tmp_path):
    # handle_generate claims its ticket; were the claim at entry, the
    # ticket would be invisible to stop()'s undrained scan and both
    # the original's and the parked duplicate's handler threads would
    # block on done.wait() forever.
    req = Request(prompt=[5, 17, 42], max_new_tokens=4)
    hole = _BlackHole()
    router = RouterServer([hole], journal=str(tmp_path / "j.jsonl"))
    out = []
    threads = [threading.Thread(
        target=lambda: out.append(router.handle_generate(req, "k")))
        for _ in range(2)]
    for t in threads:
        t.start()
    deadline = time.time() + 10
    while len(hole.cbs) < 1 or len(router._journal_waiters.get("k", [])) < 1:
        assert time.time() < deadline, "requests never reached the router"
        time.sleep(0.01)
    router.stop(drain_s=0.05)
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive(), "handler thread still blocked after stop()"
    assert sorted(body["status"] for _code, body in out) == [FAILED, FAILED]
    with router._lock:
        assert router._tickets == {}            # both claimed on reply


def test_unkeyed_replay_converges_across_restarts(world, tmp_path):
    cfg, params = world
    path = str(tmp_path / "journal.jsonl")
    # Incarnation 1 crashed with an unkeyed accept on the books (pid
    # forged so its ident can't collide with this process's replay —
    # real incarnations are distinct processes).
    log = EventLog(path)
    log.emit("router.accept", pid=424242, rid=0, key=None,
             req={"prompt": [5, 17, 42], "max_new_tokens": 4})
    log.close()
    # Incarnation 2 replays it once; the router.replayed marker retires
    # the ORIGINAL accept, so the replay's own accept/terminal pair is
    # the only record of the request from here on.
    router = RouterServer(_engines(params, cfg, 1), policy="round_robin",
                          journal=path)
    try:
        assert router.replay_journal() == 1
        deadline = time.time() + 120
        while time.time() < deadline:
            if not load_journal(path)[0]:
                break
            time.sleep(0.05)
        incomplete, _ = load_journal(path)
        assert incomplete == []
    finally:
        router.stop()
    # Incarnation 3 owes nothing — without the marker the original
    # accept would re-run here (and on every restart forever).
    router = RouterServer(_engines(params, cfg, 1), policy="round_robin",
                          journal=path)
    try:
        assert router.replay_journal() == 0
        assert router.metrics.snapshot()["counters"][
            "router.journal_replays"] == 0
    finally:
        router.stop()


def test_journal_keys_lru_bound_and_startup_compaction(tmp_path):
    path = str(tmp_path / "journal.jsonl")
    hole = _BlackHole()
    router = RouterServer([hole], journal=path, journal_keys=2)

    def run(key, tokens):
        rid = router.route(Request(prompt=[2, 3], max_new_tokens=1),
                           idempotency_key=key)
        hole.cbs[-1](RequestResult(tokens, OK))
        return router.result(rid, timeout=10)

    run("k1", [1])
    run("k2", [2])
    run("k3", [3])
    with router._lock:
        assert list(router._journal_results) == ["k2", "k3"]  # k1 evicted
    # An evicted key's duplicate re-runs (at-least-once past the bound);
    # a kept key still dedups without touching the replica.
    n_subs = len(hole.cbs)
    run("k1", [1])
    assert len(hole.cbs) == n_subs + 1
    rid = router.route(Request(prompt=[2, 3], max_new_tokens=1),
                       idempotency_key="k3")
    assert len(hole.cbs) == n_subs + 1
    assert list(router.result(rid, timeout=10)) == [3]
    with router._lock:
        # The k3 dedup hit refreshed its recency past k1's re-run.
        assert list(router._journal_results) == ["k1", "k3"]
    router.stop()

    # Startup compaction: the WAL shrinks to what recovery needs — the
    # newest journal_keys keyed terminals, no paired accepts.
    router = RouterServer([_BlackHole()], journal=path, journal_keys=2)
    try:
        with open(path) as f:
            recs = [json.loads(ln) for ln in f if ln.strip()]
        assert [r["kind"] for r in recs] == ["router.terminal"] * 2
        assert sorted(r["key"] for r in recs) == ["k1", "k3"]
        with router._lock:
            assert sorted(router._journal_results) == ["k1", "k3"]
    finally:
        router.stop()


# -- the supervisor ----------------------------------------------------------


def test_supervisor_backoff_budget_circuit_breaker(world):
    cfg, params = world
    router = RouterServer(_engines(params, cfg, 2), policy="round_robin")
    clk = [0.0]
    boom = []

    def bad_factory():
        boom.append(1)
        raise RuntimeError("factory exploded")

    sup = ReplicaSupervisor(router, max_restarts=2, backoff_s=1.0,
                            factories={"replica0": bad_factory},
                            clock=lambda: clk[0])
    try:
        with router._lock:
            router._dead.add("replica0")
        assert not sup.degraded()
        assert sup.tick() == 0                  # attempt 1: factory dies
        assert len(boom) == 1
        assert sup.tick() == 0                  # inside backoff: no try
        assert len(boom) == 1
        clk[0] = 1.5
        assert sup.tick() == 0                  # attempt 2 at t>=1.0
        assert len(boom) == 2
        clk[0] = 10.0                           # past backoff 1.5+2.0
        sup.tick()                              # budget gone: break open
        st = sup.state()["replica0"]
        assert st["restarts"] == 2 and st["permanent_dead"]
        assert [h["ok"] for h in st["history"]] == [False, False]
        assert "factory exploded" in st["history"][0]["error"]
        clk[0] = 100.0
        sup.tick()                              # permanent-dead: no retry
        assert len(boom) == 2
        counters = router.metrics.snapshot()["counters"]
        assert counters["supervisor.respawn_failures"] == 2
        assert counters["supervisor.permanent_deaths"] == 1
        assert counters["supervisor.respawns"] == 0
        assert sup.degraded()
        _code, health = router.health()
        assert health["degraded"]
        dump = router.state_dump()
        assert "supervisor replica0" in dump
        assert "PERMANENT-DEAD" in dump
    finally:
        router.stop()


def test_supervisor_fault_site_burns_budget(world):
    cfg, params = world
    fr = FaultRegistry()
    # The chaos hook: a firing serve.supervisor rule fails one respawn
    # attempt — consuming budget and advancing backoff, like any
    # crashing factory.
    fr.inject("serve.supervisor", on_hit=1, key="replica0")
    router = RouterServer(_engines(params, cfg, 2),
                          policy="round_robin", faults=fr)
    clk = [0.0]
    sup = ReplicaSupervisor(router, max_restarts=3, backoff_s=1.0,
                            factories={"replica0": lambda: None},
                            clock=lambda: clk[0])
    try:
        with router._lock:
            router._dead.add("replica0")
        assert sup.tick() == 0                  # fault fires, burns try 1
        assert fr.log == [("serve.supervisor", "replica0", 1)]
        clk[0] = 2.0
        # Attempt 2 succeeds; a None factory is an out-of-band respawn
        # (the handle revives through probes), so nothing rejoins here.
        assert sup.tick() == 0
        st = sup.state()["replica0"]
        assert [h["ok"] for h in st["history"]] == [False, True]
        counters = router.metrics.snapshot()["counters"]
        assert counters["supervisor.respawn_failures"] == 1
        assert counters["supervisor.respawns"] == 1
    finally:
        router.stop()


def test_supervisor_warm_continues_past_bad_prompt():
    hole = _BlackHole()
    router = RouterServer([hole])
    sup = ReplicaSupervisor(router, warm_prefixes=4)
    try:
        bad, good = tuple(range(8)), tuple(range(100, 108))
        for p in (good, bad):                   # bad is newer → tried first
            sup._observe_route("hole", Request(prompt=list(p),
                                               max_new_tokens=1))
            with router._lock:
                router._shadows["hole"].observe(list(p))

        class _Eng:
            prefix = object()                   # enables warm-up
            ran: list = []

            def run(self, reqs):
                if tuple(reqs[0].prompt) == bad:
                    raise RuntimeError("poisoned warm prompt")
                self.ran.append(tuple(reqs[0].prompt))

        eng = _Eng()
        sup._warm(eng, "hole")
        # One bad prompt must not cold-start the rest of the warm set.
        assert eng.ran == [good]
        assert router.metrics.snapshot()["counters"][
            "supervisor.warm_prefixes"] == 1
    finally:
        router.stop()


def test_supervisor_respawns_local_replica_bit_identical(world):
    cfg, params = world
    fr = FaultRegistry()
    # Kill replica0's pump mid-stream (the PR 9 failover trigger) —
    # this time the supervisor must bring it BACK.
    fr.inject("serve.router", on_hit=3, key="replica0")
    router = RouterServer(_engines(params, cfg, 2, faults=fr),
                          policy="round_robin", faults=fr)
    sup = ReplicaSupervisor(router, max_restarts=3, backoff_s=0.0,
                            warm_prefixes=4)
    try:
        stem = list(range(10, 26))              # two full 8-blocks
        reqs = [Request(prompt=stem + [40 + i], max_new_tokens=4)
                for i in range(4)]
        rids = [router.route(r) for r in reqs]
        deadline = time.monotonic() + 120
        for rid, req in zip(rids, reqs):
            while True:
                res = router.result(rid, timeout=0.05)
                if res is not None:
                    break
                router.poll_now()               # probes + supervisor
                assert time.monotonic() < deadline, "fleet stalled"
            # Failover replay hid the death: every request OK and
            # bit-identical to the solo oracle.
            assert res.status == OK
            want = _solo(params, cfg, req.prompt, 4).astype(np.int64)
            np.testing.assert_array_equal(
                np.asarray(list(res), np.int64), want)
        while True:
            router.poll_now()
            _code, health = router.health()
            if health["healthy"] == 2:
                break
            assert time.monotonic() < deadline, "replica0 never healed"
        st = sup.state()["replica0"]
        assert st["restarts"] == 1 and not st["permanent_dead"]
        assert [h["ok"] for h in st["history"]] == [True]
        counters = router.metrics.snapshot()["counters"]
        assert counters["supervisor.respawns"] == 1
        assert counters["router.failovers"] >= 1
        # Warm respawn: the shared stem was hot in replica0's shadow
        # index, so the fresh engine rejoined pre-warmed.
        assert counters["supervisor.warm_prefixes"] >= 1
        assert health["degraded"]               # healed, but on budget
        # The respawned replica serves — and its tokens match the
        # oracle (clone_engine preserved the exact engine config).
        extra = Request(prompt=stem + [77], max_new_tokens=4)
        rid = router.route(extra)
        res = router.result(rid, timeout=120)
        assert res.status == OK
        want = _solo(params, cfg, extra.prompt, 4).astype(np.int64)
        np.testing.assert_array_equal(
            np.asarray(list(res), np.int64), want)
    finally:
        router.stop()


# -- the campaign smoke + the wire -------------------------------------------


def test_chaos_campaign_smoke(world):
    """One seeded storm — every STORM_SITE armed plus a replica kill —
    must pass every invariant oracle (the module-docstring contract)."""
    cfg, params = world
    report = run_campaign(params, cfg, seed=3)
    assert report["ok"], report
    assert all(report["oracles"].values()), report["oracles"]
    assert len(report["sites_fired"]) >= 3
    assert report["kills_fired"] >= 1
    assert report["respawns"] >= 1
    assert report["ok_fraction"] > 0.0
    # The schedule in the report replays the campaign: same seed in,
    # same rules out.
    again = ChaosSchedule.generate(
        3, replica_names=[f"replica{i}" for i in range(3)])
    assert report["schedule"] == again.to_json()


def test_chaos_campaign_alert_oracle(world, no_compile_cache):
    """The health-plane acceptance campaign: a consecutive-prefill
    fault rule exhausts retry budgets (FAILED requests -> goodput
    dip) on a single-replica fleet with one kill.  replica_death and
    goodput_burn_fast must FIRE during the storm and RESOLVE after
    heal + recovery traffic — by the alerts_covered oracle and by
    name.

    Without the compilation cache: ``replica_death`` is a counter's
    delta, which needs a sample from before the kill, and the campaign
    takes its first sample while its first wave waits for the fleet's
    programs to compile.  Served from the cache the wave is over, kill
    included, before the sampler's first poll (ROADMAP.md, D14)."""
    cfg, params = world
    report = run_campaign(
        params, cfg, seed=7, n_replicas=1, n_kills=1,
        extra_rules=[ChaosRule("serve.prefill", on_hit=2, count=12)],
        alert_oracle=True, recovery_waves=8,
        alert_time_scale=0.005, alert_drain_s=30.0)
    assert report["ok"], report
    assert report["oracles"]["alerts_covered"], report["alerts"]
    fired = set(report["alerts"]["fired"])
    assert "replica_death" in fired
    assert "goodput_burn_fast" in fired
    assert fired <= set(report["alerts"]["resolved"])
    assert not report["alerts"]["still_firing"]
    # The storm really failed requests — that is what burned goodput.
    assert report["ok_fraction"] < 1.0
    # The event log carries the transitions for health_report replay.
    kinds = {e["kind"] for e in EventLog.read(report["event_log"])}
    assert "alert.fire" in kinds and "alert.resolve" in kinds
    assert report["alerts"]["transitions"] >= 4


def test_http_idempotency_and_state_endpoint(world, tmp_path):
    cfg, params = world
    router = RouterServer(_engines(params, cfg, 1),
                          policy="round_robin",
                          journal=str(tmp_path / "journal.jsonl")).start()
    base = f"http://{router.host}:{router.port}"
    try:
        body = json.dumps({"prompt": [5, 17, 42], "max_new_tokens": 4,
                           "idempotency_key": "wire-key"}).encode()

        def _post():
            req = urllib.request.Request(
                base + "/v1/generate", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                return json.loads(r.read())

        first, second = _post(), _post()
        assert first["status"] == OK and second["status"] == OK
        assert first["tokens"] == second["tokens"]
        assert router.metrics.snapshot()["counters"][
            "router.journal_dedups"] == 1
        with pytest.raises(urllib.error.HTTPError) as e:
            req = urllib.request.Request(
                base + "/v1/generate",
                data=json.dumps({"prompt": [1],
                                 "idempotency_key": 7}).encode(),
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=10)
        assert e.value.code == 400              # key must be a string
        with urllib.request.urlopen(base + "/state", timeout=10) as r:
            dump = r.read().decode()
        assert "RouterServer" in dump
        assert "journal:" in dump and "replica0" in dump
    finally:
        router.stop()


# -- the gang: a real SIGKILL, a real respawn --------------------------------


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_healthy(url: str, deadline: float) -> None:
    while True:
        try:
            with urllib.request.urlopen(url + "/healthz", timeout=2) as r:
                if json.loads(r.read()).get("ok"):
                    return
        except OSError:
            pass
        assert time.monotonic() < deadline, f"{url} never came up"
        time.sleep(0.5)


@pytest.mark.slow
def test_multiprocess_supervisor_sigkill_respawn(world):
    """The whole self-healing story against a real OS process: SIGKILL
    a remote replica mid-stream, watch failover keep every payload
    byte-identical, and watch the supervisor relaunch the worker and
    the probe path return it to routing."""
    cfg, params = world
    port = _free_port()
    url = f"http://127.0.0.1:{port}"
    env = dict(os.environ)
    env["REPLICA_PORT"] = str(port)
    env.setdefault("JAX_PLATFORMS", "cpu")
    procs: list[subprocess.Popen] = []

    def launch_worker() -> subprocess.Popen:
        p = subprocess.Popen([sys.executable, SUP_WORKER], env=env,
                             stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT, text=True)
        procs.append(p)
        return p

    def respawn_worker():
        # Out-of-band respawn: relaunch the process and return None —
        # the HttpReplica handle itself is still valid and rejoins
        # when its probes turn healthy.  Guard against double-launch
        # while a previous relaunch is still booting on the port.
        if procs and procs[-1].poll() is None:
            return None
        launch_worker()
        return None

    launch_worker()
    deadline = time.monotonic() + 300
    _wait_healthy(url, deadline)

    remote = HttpReplica("w", url, monitor_url=url, block_size=8,
                         timeout_s=120.0)
    router = RouterServer(_engines(params, cfg, 1) + [remote],
                          policy="round_robin", probe_fails=1,
                          max_failovers=5).start()
    sup = ReplicaSupervisor(router, max_restarts=5, backoff_s=15.0,
                            factories={"w": respawn_worker})
    try:
        stem = list(range(2, 19))
        reqs = [Request(prompt=stem + [30 + i], max_new_tokens=4)
                for i in range(6)]
        rids = [router.route(r) for r in reqs]
        time.sleep(0.2)                         # let submissions hit the wire
        procs[-1].kill()                        # SIGKILL, mid-stream
        for rid, req in zip(rids, reqs):
            res = router.result(rid, timeout=180)
            assert res is not None and res.status == OK
            want = _solo(params, cfg, req.prompt, 4).astype(np.int64)
            np.testing.assert_array_equal(
                np.asarray(list(res), np.int64), want)
        # Heal: the poller marks w dead, ticks the supervisor, the
        # relaunched worker boots, probes revive it.
        while True:
            _code, health = router.health()
            if health["healthy"] == 2:
                break
            assert time.monotonic() < deadline, (
                f"w never rejoined: {router.state_dump()}")
            time.sleep(0.5)
        st = sup.state()["w"]
        assert st["restarts"] >= 1 and not st["permanent_dead"]
        assert sup.degraded() and health["degraded"]
        assert "supervisor w" in router.state_dump()
        post = Request(prompt=stem + [50], max_new_tokens=4)
        rid = router.route(post)
        res = router.result(rid, timeout=180)
        assert res.status == OK
        want = _solo(params, cfg, post.prompt, 4).astype(np.int64)
        np.testing.assert_array_equal(
            np.asarray(list(res), np.int64), want)
    finally:
        router.stop()
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGKILL)
            try:
                p.communicate(timeout=10)
            except subprocess.TimeoutExpired:
                pass
