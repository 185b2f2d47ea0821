"""Fleet-scale simulation tests: SimReplica latency/chaos modeling,
the virtual-time fleet driver, and the chaos-at-scale campaign with
its invariant oracles — hundreds of simulated replicas driven through
the REAL router / supervisor / autoscaler / alert control plane.

The acceptance test at the bottom is the tier-1 bar from the roadmap:
200+ replicas × 100k+ virtual requests, crash storm + partition wave
+ straggler epidemic + KV-exhaustion ramp + scripted epoch bumps,
every oracle green, in well under a minute of wall clock.
"""

from __future__ import annotations

import time

import pytest

from horovod_tpu.router import RouterServer
from horovod_tpu.serving import OK, REJECTED, Request
from horovod_tpu.simfleet import (
    PhaseProfile, SimClock, SimFleet, SimReplica, crash_storm,
    measure_poll_scaling, run_sim_campaign, sim_tokens)

pytestmark = pytest.mark.sim


def _req(prompt_len=8, new=4, **kw):
    return Request(prompt=list(range(1, prompt_len + 1)),
                   max_new_tokens=new, **kw)


# ---------------------------------------------------------------------------
# SimReplica: the latency model behind the real handle interface.
# ---------------------------------------------------------------------------


def test_sim_replica_serves_deterministic_tokens():
    clk = SimClock()
    r = SimReplica("s0", clk, seed=3)
    got = []
    req = _req()
    r.submit(req, got.append)
    assert got == []                      # queued, not served yet
    clk.advance(10.0)
    assert r.advance_to(clk()) == 1
    assert got[0].status == OK
    assert list(got[0]) == sim_tokens(req)
    # A twin replica (same seed, different name) replays the same
    # request to the same bits — the failover-replay contract.
    clk2 = SimClock()
    twin = SimReplica("s1", clk2, seed=3)
    got2 = []
    twin.submit(req, got2.append)
    clk2.advance(10.0)
    twin.advance_to(clk2())
    assert list(got2[0]) == list(got[0])


def test_sim_replica_jitter_is_seeded_per_replica():
    def finish_time(name, seed):
        clk = SimClock()
        r = SimReplica(name, clk, seed=seed)
        r.submit(_req(), lambda res: None)
        return r._running[0][0]

    assert finish_time("a", 1) == finish_time("a", 1)
    assert finish_time("a", 1) != finish_time("b", 1)


def test_sim_replica_poison_and_dead_on_arrival():
    clk = SimClock()
    r = SimReplica("s0", clk, seed=0)
    got = []
    r.submit(Request(prompt=[], max_new_tokens=4), got.append)
    assert got and got[0].status == REJECTED   # poison: load-shed
    r.kill()
    r.submit(_req(), got.append)
    assert got[1] is None                      # dead: failover signal
    r.kill()                                   # idempotent


def test_sim_replica_kill_fails_over_everything_aboard():
    clk = SimClock()
    r = SimReplica("s0", clk, seed=0, n_slots=2)
    got = []
    for _ in range(5):                      # 2 running + 3 queued
        r.submit(_req(), got.append)
    assert got == []
    r.kill()
    assert got == [None] * 5


def test_sim_replica_kv_pressure_and_leak():
    clk = SimClock()
    # 4 blocks of 16 tokens: one 33-token request takes 3 blocks, so
    # a second one must wait for the first to free them.
    r = SimReplica("s0", clk, seed=0, n_slots=4, kv_blocks=4,
                   tokens_per_block=16)
    got = []
    r.submit(_req(prompt_len=30, new=3), got.append)
    r.submit(_req(prompt_len=30, new=3), got.append)
    assert len(r._running) == 1 and len(r._queue) == 1
    clk.advance(10.0)
    r.advance_to(clk())                     # first frees, second admits
    assert len(got) == 1 and len(r._running) == 1
    clk.advance(10.0)
    r.advance_to(clk())
    assert len(got) == 2
    # A leak swallows capacity until healed.
    assert r.leak_kv(0.9) == 3
    r.submit(_req(prompt_len=30, new=3), got.append)
    clk.advance(10.0)
    r.advance_to(clk())
    assert len(got) == 2                    # starved by the leak
    r.heal_kv()
    r.advance_to(clk())
    clk.advance(10.0)
    r.advance_to(clk())
    assert len(got) == 3


def test_sim_replica_straggler_and_slow_start():
    clk = SimClock()
    fast = SimReplica("f", clk, seed=0, jitter=0.0)
    slow = SimReplica("s", clk, seed=0, jitter=0.0)
    slow.set_slow(8.0)
    fast.submit(_req(), lambda r: None)
    slow.submit(_req(), lambda r: None)
    assert slow._running[0][0] == pytest.approx(
        8.0 * fast._running[0][0])
    assert slow.probe()["goodput"] == pytest.approx(1 / 8.0)
    warm = SimReplica("w", clk, seed=0, jitter=0.0, slow_start_s=5.0)
    warm.submit(_req(), lambda r: None)
    assert warm._running[0][0] == pytest.approx(
        3.0 * fast._running[0][0])          # default 3x while warming


# ---------------------------------------------------------------------------
# The clock seam and the poller's fleet instrumentation.
# ---------------------------------------------------------------------------


def test_router_default_clock_is_wall():
    clk = SimClock()
    router = RouterServer([SimReplica("s0", clk, seed=0)])
    try:
        assert router.clock is time.monotonic
    finally:
        router.stop()


def test_partition_marks_dead_then_revives_without_respawn():
    clk = SimClock()
    reps = [SimReplica(f"s{i}", clk, seed=0) for i in range(3)]
    router = RouterServer(reps, probe_fails=2, clock=clk)
    try:
        reps[0].partition(5.0)
        for _ in range(2):                  # debounce: two failed probes
            router.poll_now()
            clk.advance(1.0)
        assert router.health()[1]["healthy"] == 2
        clk.advance(5.0)                    # heal window passes
        router.poll_now()                   # can_revive: probe revival
        assert router.health()[1]["healthy"] == 3
        assert router.metrics.counter(
            "router.replica_revives").value == 1
    finally:
        router.stop()


def test_poll_pass_metrics():
    clk = SimClock()
    reps = [SimReplica(f"s{i}", clk, seed=0) for i in range(5)]
    router = RouterServer(reps, clock=clk)
    try:
        router.poll_now()
        assert router.metrics.gauge("router.fleet_size").value == 5
        hist = router.metrics.histogram("router.poll_s").snapshot()
        assert hist["count"] == 1 and hist["max"] < 1.0
    finally:
        router.stop()


def test_shadow_byte_ceiling_evicts():
    clk = SimClock()
    reps = [SimReplica(f"s{i}", clk, seed=0) for i in range(4)]
    router = RouterServer(reps, shadow_max_bytes=4096, clock=clk)
    try:
        for i in range(64):                 # distinct 2-block prompts
            prompt = [i * 100 + j for j in range(33)]
            router.route(Request(prompt=prompt, max_new_tokens=2))
        for r in reps:
            r.advance_to(clk.advance(10.0))
        router.poll_now()
        assert router._shadow_bytes() <= 4096
        assert router.metrics.counter(
            "router.shadow_evictions").value > 0
    finally:
        router.stop()


def test_shadow_ceiling_disabled_when_nonpositive():
    clk = SimClock()
    router = RouterServer([SimReplica("s0", clk, seed=0)],
                          shadow_max_bytes=0, clock=clk)
    try:
        assert router._enforce_shadow_bound(10 ** 9) == 10 ** 9
        assert router.metrics.counter(
            "router.shadow_evictions").value == 0
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# SimFleet driver: real control plane on virtual time.
# ---------------------------------------------------------------------------


def test_fleet_crash_storm_failover_keeps_every_request():
    fleet = SimFleet(8, seed=5)
    try:
        arrivals = []
        t = 0.0
        for i in range(200):
            t += 0.01
            arrivals.append(type("A", (), {
                "t": t, "req": _req(prompt_len=6 + i % 4)})())
        stats = fleet.run(arrivals,
                          events=crash_storm(5, n_kills=3, t0=0.3,
                                             t1=1.5),
                          settle_s=5.0, max_virtual_s=120.0)
        assert stats["delivered"] == stats["submitted"] == 200
        assert stats["mismatches"] == 0
        assert fleet.router.metrics.counter(
            "supervisor.respawns").value >= 1
        assert fleet.router.memory_report()["tickets"] == 0
    finally:
        fleet.close()


def test_campaign_report_is_deterministic():
    kw = dict(n_replicas=25, n_requests=2000, poll_scaling=False)
    drop = ("wall_s", "poll_scaling")
    a = run_sim_campaign(seed=11, **kw)
    b = run_sim_campaign(seed=11, **kw)
    assert {k: v for k, v in a.items() if k not in drop} \
        == {k: v for k, v in b.items() if k not in drop}
    assert a["ok"], a["oracles"]
    c = run_sim_campaign(seed=12, **kw)
    assert c["ok"], c["oracles"]
    assert {k: v for k, v in c.items() if k not in drop} \
        != {k: v for k, v in a.items() if k not in drop}


def test_poll_scaling_measure_shape():
    m = measure_poll_scaling(n_small=5, n_big=20, polls=4)
    assert m["poll_s_small"] > 0 and m["poll_s_big"] > 0
    assert m["per_replica_ratio"] > 0


# ---------------------------------------------------------------------------
# The acceptance bar: fleet scale, all oracles.
# ---------------------------------------------------------------------------


def test_fleet_scale_campaign_under_chaos_all_oracles_green():
    """≥200 simulated replicas × ≥100k virtual requests through the
    real RouterServer + supervisor + autoscaler + AlertManager under
    virtual time, with a crash storm, a partition wave, a straggler
    epidemic, a KV-exhaustion ramp, and two scripted epoch bumps —
    every invariant oracle must hold."""
    report = run_sim_campaign(seed=0, n_replicas=200,
                              n_requests=100000)
    assert report["n_replicas"] >= 200
    assert report["n_requests"] >= 100000
    failed = {k: v for k, v in report["oracles"].items() if not v}
    assert not failed, (failed, report)
    assert report["ok"]
    # The chaos actually happened: kills respawned, failovers
    # replayed, alerts fired AND resolved, the shadow ceiling bit,
    # and membership epoch advanced through both scripted actions.
    assert report["respawns"] >= 10
    assert report["failovers"] >= 10
    assert report["alerts"]["fired"] and not report["alerts"]["unresolved"]
    assert report["shadow_evictions"] > 0
    assert report["epoch"] >= 2
    assert report["journal_dedups"] >= report["keyed"] > 0
