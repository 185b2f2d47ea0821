"""Request lifecycle & fault tolerance of the ServeEngine.

Every test is step-counted — fault schedules, backoffs, queue budgets,
and preemption triggers are all functions of the engine step index, so
there is NOT ONE sleep in this file and every run is bit-reproducible.
The two hard engine invariants stay pinned through every lifecycle
transition:

1. *Bit-parity*: every request that terminates ``OK`` — including one
   preempted mid-decode and resumed via replay, or one that survived a
   transient injected fault — emits exactly the tokens its solo
   ``llama.generate`` run emits; every non-``OK`` result's tokens-so-far
   are a prefix of that solo run.
2. *Fixed signature*: preempt / requeue / cancel / timeout / fail all
   ride the existing compiled programs —
   ``compile_cache_sizes()`` never moves (its ``"chunk": 1`` is "one
   signature a width" of the chunk program, whose rows a fault leaves
   one by one).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import faults as faults_mod
from horovod_tpu.faults import (
    FaultRegistry, PermanentFault, TransientFault,
)
from horovod_tpu.models import llama
from horovod_tpu.serving import (
    CANCELLED, FAILED, OK, REJECTED, TIMEOUT, Request, RequestResult,
)
from horovod_tpu.serving_scheduler import ServeEngine

pytestmark = pytest.mark.faults


@pytest.fixture(scope="module")
def world():
    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(11))
    return cfg, params


def _solo(params, cfg, prompt, n_new, max_len):
    return np.asarray(llama.generate(
        params, jnp.asarray([prompt], jnp.int32), cfg,
        max_new_tokens=n_new, max_len=max_len,
    ))[0]


def _assert_solo_prefix(params, cfg, req, res, max_len):
    """OK results equal the solo run; partial results are a prefix of
    it (greedy determinism — tokens-so-far never diverge)."""
    want = _solo(params, cfg, req.prompt, req.max_new_tokens, max_len)
    got = np.asarray(list(res), np.int64)
    if res.status == OK:
        np.testing.assert_array_equal(got, want.astype(np.int64))
    else:
        assert len(got) <= len(want)
        np.testing.assert_array_equal(got, want[:len(got)].astype(np.int64))


# -- the registry itself -----------------------------------------------------


def test_fault_registry_schedules():
    reg = FaultRegistry()
    rule = reg.inject("serve.tick", on_hit=3, count=2)
    perm = reg.inject("serve.tick", on_hit=7, permanent=True, key=42)
    for _ in range(2):
        reg.check("serve.tick", key=1)       # hits 1, 2: quiet
    with pytest.raises(TransientFault):
        reg.check("serve.tick", key=1)       # hit 3 fires
    with pytest.raises(TransientFault):
        reg.check("serve.tick", key=1)       # hit 4 fires (count=2)
    reg.check("serve.tick", key=1)           # hit 5: transient cleared
    assert rule.fired == 2 and rule.seen == 5
    # the keyed permanent rule counts only key=42 hits
    assert perm.seen == 0
    for _ in range(6):
        reg.check("serve.tick", key=42)
    for _ in range(3):                       # fires on EVERY hit >= 7
        with pytest.raises(PermanentFault):
            reg.check("serve.tick", key=42)
    assert perm.fired == 3
    assert reg.hits("serve.tick") == 14
    assert len(reg.log) == 5
    reg.clear()
    assert reg.hits("serve.tick") == 0 and not reg.rules
    with pytest.raises(ValueError):
        reg.inject("x", on_hit=0)


# -- deadlines, queue budgets, cancellation ----------------------------------


def test_deadline_times_out_queued_request(world):
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=1, max_len=16, chunk=4)
    occupant = eng.submit(Request(prompt=[5, 17, 42], max_new_tokens=8))
    doomed = eng.submit(Request(prompt=[7, 8], max_new_tokens=4,
                                deadline_s=0.0))
    finished = eng.step()
    assert finished[doomed].status == TIMEOUT
    assert list(finished[doomed]) == []
    assert eng.counters["timeouts"] == 1
    while eng.pending():
        eng.step()
    assert eng.results[occupant].status == OK
    _assert_solo_prefix(params, cfg, Request(prompt=[5, 17, 42],
                                             max_new_tokens=8),
                        eng.results[occupant], 16)


def test_deadline_times_out_inflight_request(world):
    cfg, params = world
    req = Request(prompt=[5, 17, 42], max_new_tokens=10, deadline_s=60.0)
    eng = ServeEngine(params, cfg, n_slots=1, max_len=16, chunk=4)
    rid = eng.submit(req)
    for _ in range(4):
        eng.step()                           # decoding, tokens emitted
    assert eng._slots[0].state == "decode"
    # expire the deadline without sleeping (white-box: the absolute
    # monotonic deadline lives on the slot once admitted)
    eng._slots[0].deadline = time.monotonic() - 1.0
    finished = eng.step()
    res = finished[rid]
    assert res.status == TIMEOUT and len(res) > 0
    _assert_solo_prefix(params, cfg, req, res, 16)
    assert not eng.pending()
    assert eng.free_block_count() == eng.pcache.k.shape[1] - 1


def test_max_queue_steps_rejects(world):
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=1, max_len=16, chunk=4)
    occ_req = Request(prompt=[5, 17, 42], max_new_tokens=8)
    occupant = eng.submit(occ_req)
    shed = eng.submit(Request(prompt=[9, 9], max_new_tokens=4,
                              max_queue_steps=2))
    statuses = {}
    while eng.pending():
        statuses.update(eng.step())
    assert statuses[shed].status == REJECTED
    assert list(statuses[shed]) == []
    assert eng.counters["rejections"] == 1
    assert statuses[occupant].status == OK
    _assert_solo_prefix(params, cfg, occ_req, eng.results[occupant], 16)
    # rejected after exactly its budget of queued steps (0 and 1): the
    # reject fires at the top of step 2
    reject = [e for e in eng.events if e.kind == "reject"][0]
    assert reject.step == 2 and reject.slot == -1


def test_malformed_submit_rejects_without_raising(world):
    """Empty prompt / zero budget are client-data errors, not caller
    bugs: ``submit`` returns a rid whose result is already terminal
    ``REJECTED`` (with a trace), so a router or HTTP front end gets a
    status to forward instead of an exception to translate — and the
    engine serves on, untouched."""
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=1, max_len=16, chunk=4)
    r1 = eng.submit(Request(prompt=[], max_new_tokens=4))
    r2 = eng.submit(Request(prompt=[7, 8], max_new_tokens=0))
    for rid in (r1, r2):
        res = eng.results[rid]
        assert res.status == REJECTED and list(res) == []
        assert res.trace is not None
        assert res.trace.rid == rid
        assert res.trace.status == REJECTED
    assert eng.counters["rejections"] == 2
    assert not eng.pending()                 # nothing left enqueued
    req = Request(prompt=[5, 17, 42], max_new_tokens=4)
    out = eng.run([req])[0]
    assert out.status == OK
    _assert_solo_prefix(params, cfg, req, out, 16)


def test_cancel_in_every_state(world):
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=1, max_len=32, chunk=4)
    run_req = Request(prompt=[5, 17, 42], max_new_tokens=6)
    running = eng.submit(run_req)
    queued = eng.submit(Request(prompt=[7], max_new_tokens=3))
    # 1) queued: cancelled before ever touching a slot
    assert eng.cancel(queued)
    assert eng.results[queued].status == CANCELLED
    assert list(eng.results[queued]) == []
    assert not eng.cancel(queued)            # already terminal
    assert not eng.cancel(999)               # unknown rid
    # 2) decoding: tokens-so-far survive the cancel
    for _ in range(3):
        eng.step()
    assert eng.cancel(running)
    res = eng.results[running]
    assert res.status == CANCELLED and len(res) > 0
    _assert_solo_prefix(params, cfg, run_req, res, 32)
    # 3) mid-prefill: a multi-window prompt cancelled between windows
    long_req = Request(prompt=list(range(1, 15)), max_new_tokens=4)
    mid = eng.submit(long_req)
    eng.step()                               # window 1 of 4 ran
    assert eng._slots[0].state == "prefill"
    assert eng.cancel(mid)
    assert eng.results[mid].status == CANCELLED
    assert list(eng.results[mid]) == []
    assert eng.counters["cancellations"] == 3
    # every block came home and the engine still serves
    assert eng.free_block_count() == eng.pcache.k.shape[1] - 1
    after = eng.run([Request(prompt=[3, 1], max_new_tokens=4)])[0]
    assert after.status == OK
    _assert_solo_prefix(params, cfg, Request(prompt=[3, 1],
                                             max_new_tokens=4), after, 32)


# -- preemption with replay --------------------------------------------------


def test_preemption_replay_bit_parity(world):
    """The acceptance pin: a row preempted mid-decode for a starved
    queue head resumes via replay and emits tokens bit-identical to its
    uninterrupted run — with zero new jit signatures."""
    cfg, params = world
    # 5 allocatable blocks: victim needs 4, head needs 3 → head starves
    # until the victim (the only decoding row) is preempted for it.
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, chunk=4,
                      block_size=4, n_blocks=6, preempt_after=2)
    victim = Request(prompt=[5, 17, 42], max_new_tokens=13)   # 4 blocks
    head = Request(prompt=[7, 8], max_new_tokens=6)           # 3 blocks
    out = eng.run([victim, head])
    assert eng.counters["preemptions"] >= 1
    kinds = [e.kind for e in eng.events]
    assert "preempt" in kinds
    # the victim was admitted at least twice: original + replay
    admits = [e for e in eng.events if e.kind == "admit"
              and e.request_id == 0]
    assert len(admits) >= 2
    for req, res in zip([victim, head], out):
        assert res.status == OK
        _assert_solo_prefix(params, cfg, req, res, 16)
    assert eng.compile_cache_sizes() == {
        "sample": 1, "tick": 1, "chunk": 1, "set_row": 1}
    assert eng.free_block_count() == 5


def test_preemption_under_churn_parity(world):
    """Many requests through an overcommitted pool with an aggressive
    preemption trigger: ping-ponging preemptions still terminate and
    every result stays solo-exact."""
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, chunk=4,
                      block_size=4, n_blocks=6, preempt_after=1)
    reqs = [
        Request(prompt=[5, 17, 42], max_new_tokens=12),
        Request(prompt=[7], max_new_tokens=10),
        Request(prompt=[9, 1, 2, 3], max_new_tokens=8),
        Request(prompt=[100, 101], max_new_tokens=11),
        Request(prompt=[200, 3, 1], max_new_tokens=5),
    ]
    out = eng.run(reqs)
    assert eng.counters["preemptions"] >= 1
    for req, res in zip(reqs, out):
        assert res.status == OK
        _assert_solo_prefix(params, cfg, req, res, 16)
    assert eng.compile_cache_sizes() == {
        "sample": 1, "tick": 1, "chunk": 1, "set_row": 1}
    assert eng.free_block_count() == 5


# -- poison-request quarantine -----------------------------------------------


def test_permanent_prefill_fault_fails_only_that_request(world):
    """The acceptance pin: an injected permanent fault in one request's
    prefill yields FAILED for that request only — concurrent rows finish
    solo-exact and the engine keeps serving afterward."""
    cfg, params = world
    reg = FaultRegistry()
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, chunk=4,
                      faults=reg)
    reqs = [Request(prompt=[5, 17, 42], max_new_tokens=6),
            Request(prompt=[7, 8, 9, 10, 11], max_new_tokens=5),
            Request(prompt=[100, 101], max_new_tokens=4)]
    ids = [eng.submit(r) for r in reqs]
    reg.inject("serve.prefill", key=ids[1], permanent=True)
    while eng.pending():
        eng.step()
    poisoned = eng.results[ids[1]]
    assert poisoned.status == FAILED
    assert isinstance(poisoned.error, PermanentFault)
    assert list(poisoned) == []              # died before any token
    assert eng.counters["failures"] == 1
    for i in (0, 2):
        assert eng.results[ids[i]].status == OK
        _assert_solo_prefix(params, cfg, reqs[i], eng.results[ids[i]], 16)
    # the engine keeps serving: fresh request, full parity, no retrace
    late = Request(prompt=[42], max_new_tokens=5)
    res = eng.run([late])[0]
    assert res.status == OK
    _assert_solo_prefix(params, cfg, late, res, 16)
    assert eng.compile_cache_sizes() == {
        "sample": 1, "tick": 1, "chunk": 1, "set_row": 1}
    assert eng.free_block_count() == eng.pcache.k.shape[1] - 1


def test_permanent_tick_fault_keeps_tokens_so_far(world):
    cfg, params = world
    reg = FaultRegistry()
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, chunk=4,
                      faults=reg)
    reqs = [Request(prompt=[5, 17, 42], max_new_tokens=8),
            Request(prompt=[7, 8], max_new_tokens=6)]
    ids = [eng.submit(r) for r in reqs]
    # rid 0's 4th decode readback dies permanently
    reg.inject("serve.tick", key=ids[0], on_hit=4, permanent=True)
    while eng.pending():
        eng.step()
    dead = eng.results[ids[0]]
    assert dead.status == FAILED
    assert isinstance(dead.error, PermanentFault)
    assert len(dead) == 3                    # emitted before the poison
    _assert_solo_prefix(params, cfg, reqs[0], dead, 16)
    ok = eng.results[ids[1]]
    assert ok.status == OK
    _assert_solo_prefix(params, cfg, reqs[1], ok, 16)
    assert eng.free_block_count() == eng.pcache.k.shape[1] - 1


class _Poisoned:
    """What the sampling program hands back when a program in front of it
    failed on the device: the error surfaces where the host asks for the
    tokens, not where the program was dispatched."""

    def __init__(self, exc):
        self.exc = exc

    def copy_to_host_async(self):
        pass

    def is_ready(self):
        return True

    def __array__(self, *args, **kwargs):
        raise self.exc


def test_a_fault_of_tick_n_surfaces_at_step_n_plus_1_and_is_its_rows(world):
    """A step returns with its tick in flight, so tick N's fault is met by
    step N+1's read.  It is charged to the rows that decoded in tick N; a row
    that joined with step N+1 (its token lost with the read, its tick already
    dispatched) replays uncharged, like a preempted one."""
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=3, max_len=16, chunk=4)
    reqs = [Request(prompt=[5, 17, 42], max_new_tokens=8),
            Request(prompt=[7, 8], max_new_tokens=7),
            Request(prompt=[9, 1, 2, 3, 4, 5], max_new_tokens=4)]
    ids = [eng.submit(r) for r in reqs]
    inner, calls = eng._sample, []

    def sample(last_logits, counters):
        tok, counters = inner(last_logits, counters)
        calls.append(eng.step_index)
        if len(calls) == 2:             # the read behind tick 0
            tok = _Poisoned(RuntimeError("tick 0 died on the device"))
        return tok, counters

    sample._cache_size = inner._cache_size
    eng._sample = sample
    # step 0: rows 0 and 1 end their one-window prompts and decode in tick
    # 0; the third request is half-way through its prompt.  Nothing is wrong
    # yet as far as the host can know.
    assert eng.step() == {}
    assert [len(s.out) for s in eng._slots] == [1, 1, 0]
    assert eng.counters["retries"] == 0
    # step 1: the third row joins the tick; the read meets tick 0's fault
    assert eng.step() == {}
    assert calls == [0, 1]
    at_1 = [(e.kind, e.request_id) for e in eng.events if e.step == 1]
    assert at_1 == [("retry", ids[0]), ("retry", ids[1]),
                    ("preempt", ids[2])]
    assert eng.counters["retries"] == 2
    assert eng.counters["preemptions"] == 1
    assert all(s.state == "free" for s in eng._slots)
    assert sorted((e.rid, e.retries, len(e.prior)) for e in eng._queue) \
        == [(ids[0], 1, 1), (ids[1], 1, 1), (ids[2], 0, 0)]
    while eng.pending():
        eng.step()
    for rid, req in zip(ids, reqs):
        res = eng.results[rid]
        assert res.status == OK, (rid, res.status, res.error)
        _assert_solo_prefix(params, cfg, req, res, 16)
        assert res.trace.retries == (1 if rid != ids[2] else 0)
    assert eng.counters["retries"] == 2 and eng.counters["failures"] == 0
    assert eng.compile_cache_sizes() == {
        "sample": 1, "tick": 1, "chunk": 1, "set_row": 1}
    assert eng.free_block_count() == eng.pcache.k.shape[1] - 1


def test_an_error_in_the_models_own_counters_is_no_rows_fault(
        world, monkeypatch):
    """The model's host-side count of a step's programs runs between the
    tick's dispatch and its readback, and outside the tick's fault handling:
    an error there leaves ``step()`` as it did from the step's end, and no
    row is quarantined or retried for it."""
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, chunk=4)
    rid = eng.submit(Request(prompt=[5, 17, 42], max_new_tokens=4))
    publish = llama.publish_paged_metrics

    def broken(metrics, cfg, pcache, stats_host=None, row_blocks=(),
               programs=()):
        if any(p.rows > 1 for p in programs):       # a tick's
            raise ZeroDivisionError("the count")
        publish(metrics, cfg, pcache, stats_host, row_blocks, programs)

    monkeypatch.setattr(llama, "publish_paged_metrics", broken)
    with pytest.raises(ZeroDivisionError, match="the count"):
        while eng.pending():
            eng.step()
    assert rid not in eng.results and eng.counters["retries"] == 0
    assert not [e for e in eng.events if e.kind in ("retry", "fail")]


def test_transient_faults_retry_to_parity(world):
    """Transient faults at every engine site (admit, prefill window,
    decode readback) retry within bounds and the request still ends OK
    with solo-exact tokens; the retry counter and events record it."""
    cfg, params = world
    reg = FaultRegistry()
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, chunk=4,
                      faults=reg)
    reqs = [Request(prompt=[5, 17, 42], max_new_tokens=6),
            Request(prompt=[7, 8, 9, 10, 11], max_new_tokens=5)]
    ids = [eng.submit(r) for r in reqs]
    reg.inject("serve.admit", key=ids[0])                  # 1st attempt
    reg.inject("serve.prefill", key=ids[1], on_hit=1)      # 1st window
    reg.inject("serve.tick", key=ids[0], on_hit=2)         # 2nd readback
    while eng.pending():
        eng.step()
    assert eng.counters["retries"] == 3
    assert [e.kind for e in eng.events].count("retry") == 3
    for rid, req in zip(ids, reqs):
        res = eng.results[rid]
        assert res.status == OK, (rid, res.status, res.error)
        _assert_solo_prefix(params, cfg, req, res, 16)
    assert eng.compile_cache_sizes() == {
        "sample": 1, "tick": 1, "chunk": 1, "set_row": 1}


def test_transient_fault_exhausts_retries_to_failed(world):
    cfg, params = world
    reg = FaultRegistry()
    eng = ServeEngine(params, cfg, n_slots=1, max_len=16, chunk=4,
                      faults=reg, max_retries=1)
    rid = eng.submit(Request(prompt=[5, 17, 42], max_new_tokens=4))
    # fires on every prefill attempt within the retry budget
    reg.inject("serve.prefill", key=rid, on_hit=1, count=10)
    while eng.pending():
        eng.step()
    res = eng.results[rid]
    assert res.status == FAILED
    assert isinstance(res.error, TransientFault)
    assert eng.counters["retries"] == 1      # bounded by max_retries
    assert eng.free_block_count() == eng.pcache.k.shape[1] - 1


# -- watchdog ----------------------------------------------------------------


def test_no_progress_watchdog_raises_with_dump(world):
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=1, max_len=16, chunk=4,
                      watchdog_steps=5)
    eng.submit(Request(prompt=[5, 17, 42], max_new_tokens=4))
    # simulate a block leak: the queue head can never admit and nothing
    # is decoding, so no step makes progress
    eng._free_blocks.clear()
    with pytest.raises(RuntimeError, match="no scheduling progress"):
        for _ in range(10):
            eng.step()
    msg = str(eng.state_dump())
    assert "queued rid=0" in msg and "free_blocks=0" in msg


# -- the data.producer site --------------------------------------------------


def test_data_producer_fault_surfaces_in_consumer():
    """An injected producer-thread fault propagates into the iterating
    consumer (the loader's existing exception channel) instead of
    wedging the prefetch queue."""
    from horovod_tpu.data import ShardedLoader

    x = np.arange(64, dtype=np.float32).reshape(32, 2)
    try:
        faults_mod.inject("data.producer", on_hit=2)
        loader = ShardedLoader((x,), 2, shuffle=False, device_put=False)
        it = iter(loader)
        next(it)                             # batch 0 fine
        with pytest.raises(TransientFault):
            for _ in it:
                pass
    finally:
        faults_mod.clear()
    # with the registry cleared the same loader drains fully
    assert len(list(iter(loader))) == 2
