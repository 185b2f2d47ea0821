"""models/state_space_moe.py behind ``ServeEngine``, at a tiny size on the CPU
with seeded weights (the helpers and the tiny configuration are
``tests/toy_state_space_moe.py``'s): the engine's logits against the
reference, cache-free generation, the snapshot budget's rules (a prefix learnt
at its second bearer and hit from its third, a hit rounded down to the deepest
block that holds a snapshot, entries and blocks evicted apart and an evicted
entry never restored, an entry that follows the block that stays at
``insert``, no entry for a match that the admission itself evicted),
preemption with replay, speculation, a cloned engine, the router,
cancellation and the counters."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from toy_state_space_moe import (ATOL, N_LAYERS, TINY,  # noqa: E402
                                 reference_logits, tiny, tokens)

from horovod_tpu import metrics as metrics_mod  # noqa: E402
from horovod_tpu import supervisor  # noqa: E402
from horovod_tpu.models import state_space_moe as sm  # noqa: E402
from horovod_tpu.router import LocalReplica, RouterServer  # noqa: E402
from horovod_tpu.serving import Request  # noqa: E402
from horovod_tpu.serving_scheduler import ServeEngine  # noqa: E402

N_NEW = 9


def _engine(mc, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("chunk", 8)
    return ServeEngine(params, mc, monitor=False, sampler=False,
                       metrics=metrics_mod.MetricsRegistry(event_log=None),
                       **kw)


def _requests(prompts, n_new=N_NEW):
    return [Request(prompt=p, max_new_tokens=n_new) for p in prompts]


def _counters(eng):
    return eng.metrics.snapshot()["counters"]


@pytest.fixture(scope="module")
def served():
    """The tiny model, four prompts of which three share their first two
    blocks (a system prompt), and each prompt's solo tokens with no cache."""
    _, mc, params = tiny()
    system = tokens(16, seed=3)
    prompts = [system + tokens(11, seed=4), tokens(7, seed=5),
               system + tokens(5, seed=6), system + tokens(3, seed=7)]
    want = [sm.generate(params, mc, p, N_NEW, pad_to=48) for p in prompts]
    return mc, params, prompts, want


def test_engine_prefill_and_decode_agree_with_the_reference_on_logits(served):
    """One request through ``ServeEngine`` a step at a time: the logits the
    engine holds for the row after its prefill and after each tick are the
    reference's full pass over the prompt and the tokens served."""
    mc, params, prompts, want = served
    eng = _engine(mc, params, n_slots=1)
    rid = eng.submit(Request(prompt=prompts[2], max_new_tokens=N_NEW))
    seen = {}
    while eng.pending():
        eng.step()
        s = eng._slots[0]
        if s.request_id == rid and s.out is not None and s.budget > 0 \
                and int(eng.pcache.length[0]) >= len(prompts[2]):
            seen[int(eng.pcache.length[0])] = np.asarray(eng.last_logits[0])
    out = list(eng.results[rid])
    assert out == want[2]
    full = reference_logits(dict(TINY), prompts[2] + out)
    assert len(seen) >= N_NEW - 1
    for length, logits in seen.items():
        np.testing.assert_allclose(logits, full[length - 1], atol=ATOL,
                                   rtol=0)


def test_engine_run_equals_cache_free_generate(served):
    mc, params, prompts, want = served
    eng = _engine(mc, params)
    assert eng.model is sm and eng.snaps.n == 3
    out = eng.run(_requests(prompts))
    assert [r.status for r in out] == ["OK"] * 4
    assert [list(r) for r in out] == want
    assert eng.compile_cache_sizes() == \
        {"sample": 1, "tick": 1, "chunk": 1, "set_row": 1}
    snap = eng.metrics.snapshot()
    # float32: 1 attention layer of 2 key heads of 8; 3 state-space layers
    # of 8 x 8 x 8 and 3 convolution inputs of 80
    assert snap["gauges"]["kv.bytes_per_token"] == 2 * 1 * 2 * 8 * 4
    assert snap["gauges"]["state.bytes_per_slot"] == \
        3 * (8 * 8 * 8 + 3 * 80) * 4
    assert snap["gauges"]["kv.snapshot_block_bytes"] == \
        snap["gauges"]["state.bytes_per_slot"]
    assert snap["counters"]["ssm.state_restores"] == 0
    # with no prefix cache nothing can hit: no entry is asked for
    assert snap["counters"]["ssm.snapshots_written"] == 0
    assert eng.memory_report()["kv"]["pools"].keys() == {"k", "v"}
    # the state every program moved: a tick's decoding rows, a chunk's one
    slot = snap["gauges"]["state.bytes_per_slot"]
    n_chunks = sum(-(-len(p) // 8) for p in prompts)
    assert snap["counters"]["ssm.state_bytes_moved"] == \
        2 * slot * (n_chunks + 4 * N_NEW)


def test_a_prefix_is_learnt_at_its_second_bearer_and_hit_from_its_third(
        served):
    """The first bearer of the system prompt leaves a snapshot at its own
    prompt's last full block only.  The second matches the system prompt's
    two blocks, finds no snapshot there, recomputes them and is given an
    entry for the block that is indexed; as soon as its prefill has passed
    that block's end the third is admitted on a hit.  Every request serves
    its solo tokens; zeroed snapshots would not."""
    mc, params, prompts, want = served
    eng = _engine(mc, params, prefix_cache=True)
    assert list(eng.run(_requests(prompts[:1]))[0]) == want[0]
    c = _counters(eng)
    assert c["ssm.snapshots_written"] == 1 and c["ssm.state_restores"] == 0
    index = eng.prefix.path_blocks(prompts[0])
    assert len(index) == 3 and eng.snaps.entry(index[2]) is not None
    assert eng.snaps.entry(index[1]) is None
    rid2 = eng.submit(_requests(prompts[2:3])[0])
    eng.step()                              # its first chunk: one block
    assert eng.snaps.wanted(index[1]) and eng.snaps.entry(index[1]) is None
    c = _counters(eng)
    assert (c["prefix.blocks_matched"], c["prefix.blocks_restored"]) == (2, 0)
    assert eng.prefix_counters["hits"] == 0
    eng.step()                              # its second: the block's end
    assert eng.snaps.entry(index[1]) is not None
    rid3 = eng.submit(_requests(prompts[3:])[0])
    while eng.pending():
        eng.step()
    assert list(eng.results[rid2]) == want[2]
    assert list(eng.results[rid3]) == want[3]
    c = _counters(eng)
    assert (c["prefix.blocks_matched"], c["prefix.blocks_restored"]) == (4, 2)
    assert eng.prefix_counters["hits"] == 1
    assert eng.prefix_counters["tokens_skipped"] == 16
    assert c["ssm.state_restores"] == 1
    # the second bearer's own last full block is the system prompt's end,
    # for which the entry was already on its way: it asked for one only
    assert c["ssm.snapshots_written"] == 2
    assert eng.metrics.snapshot()["gauges"]["ssm.snapshots_live"] == 2
    eng._check_block_invariants()

    broken = _engine(mc, params, prefix_cache=True)
    broken.run(_requests(prompts[:1]) + _requests(prompts[2:3]))
    broken.pcache = broken.pcache._replace(
        snap_ssm=jnp.zeros_like(broken.pcache.snap_ssm),
        snap_conv=jnp.zeros_like(broken.pcache.snap_conv))
    wrong = broken.run(_requests(prompts[3:]))
    assert broken.prefix_counters["hits"] == 1
    assert list(wrong[0]) != want[3]


def test_a_hit_is_rounded_down_to_the_deepest_block_that_holds_a_snapshot():
    _, mc, params = tiny()
    long = tokens(40, seed=11)
    eng = _engine(mc, params, n_slots=1, max_len=64, n_blocks=30,
                  prefix_cache=True)       # blocks enough: none is evicted
    solo = lambda p: sm.generate(params, mc, p, N_NEW, pad_to=64)  # noqa: E731
    assert list(eng.run(_requests([long]))[0]) == solo(long)
    index = eng.prefix.path_blocks(long)
    assert len(index) == 5
    assert [eng.snaps.entry(b) is not None for b in index] == \
        [False] * 4 + [True]
    # three blocks matched, none holds a snapshot: rounded down to nothing,
    # and learnt at the third
    p3 = long[:24] + tokens(5, seed=12)
    assert list(eng.run(_requests([p3]))[0]) == solo(p3)
    c = _counters(eng)
    assert (c["prefix.blocks_matched"], c["prefix.blocks_restored"]) == (3, 0)
    assert eng.snaps.entry(index[2]) is not None
    # four matched, the third the deepest with a snapshot: three restored,
    # the fourth released and recomputed
    p4 = long[:32] + tokens(5, seed=13)
    assert list(eng.run(_requests([p4]))[0]) == solo(p4)
    c = _counters(eng)
    assert (c["prefix.blocks_matched"], c["prefix.blocks_restored"]) == (7, 3)
    assert c["prefix.tokens_skipped"] == 24
    assert c["ssm.state_restores"] == 1
    # the budget of 3 is full: blocks 5, 3 and now 4 of the long prompt's
    # path were wanted, each by the request that left the path there
    assert eng.snaps.held_count() == 3
    assert [eng.snaps.entry(b) is not None for b in index] == \
        [False, False, True, True, True]
    eng._check_block_invariants()


def test_entries_and_blocks_are_evicted_apart_and_none_is_restored_after():
    _, mc, params = tiny(snapshots=1)
    solo = lambda p: sm.generate(params, mc, p, N_NEW, pad_to=48)  # noqa: E731
    eng = _engine(mc, params, n_slots=1, prefix_cache=True)
    a, b = tokens(19, seed=21), tokens(19, seed=22)
    assert list(eng.run(_requests([a]))[0]) == solo(a)
    blocks_a = eng.prefix.path_blocks(a)
    held = blocks_a[1]                  # a's last full block
    assert eng.snaps.entry(held) is not None
    # the one entry goes to the next prompt: a's block stays indexed
    assert list(eng.run(_requests([b]))[0]) == solo(b)
    assert eng.snaps.entry(held) is None and held in eng.prefix
    assert _counters(eng)["ssm.snapshots_evicted"] == 1
    # a again, longer: its blocks match, none holds a snapshot, nothing is
    # restored, and the tokens are right all the same
    a2 = a + tokens(4, seed=23)
    assert list(eng.run(_requests([a2]))[0]) == solo(a2)
    c = _counters(eng)
    assert c["ssm.state_restores"] == 0 and c["prefix.blocks_restored"] == 0
    assert c["prefix.blocks_matched"] == 2
    # ... and a block that is evicted gives its entry up
    holder = next(blk for blk in list(eng.prefix._nodes)
                  if eng.snaps.entry(blk) is not None)
    eng.prefix.evict(eng.prefix.indexed_blocks())
    assert eng.prefix.indexed_blocks() == 0 and holder not in eng.prefix
    assert eng.snaps.held_count() == 0
    eng._check_block_invariants()


def test_a_match_evicted_at_its_own_admission_is_granted_no_entry():
    """A pool so short that the admission which matched two unheld blocks
    evicts them to make room, and is handed them back at other indices: the
    entry it is given is for a block of its own at that block's index, never
    for the id the index matched (which now holds other tokens), and what
    each held entry keeps is the state after its own block's tokens."""
    _, mc, params = tiny(snapshots=1)
    solo = lambda p: sm.generate(params, mc, p, N_NEW, pad_to=48)  # noqa: E731
    eng = _engine(mc, params, n_slots=1, prefix_cache=True)
    a, b = tokens(19, seed=21), tokens(19, seed=22)
    eng.run(_requests([a]))
    eng.run(_requests([b]))             # takes the one entry: a's is unheld
    old = eng.prefix.path_blocks(a)
    assert len(old) == 2 and eng.snaps.entry(old[1]) is None
    a2 = a + tokens(4, seed=23)
    rid = eng.submit(_requests([a2])[0])
    before = eng.prefix_counters["evictions"]
    eng.step()
    row = eng._slots[0]
    assert eng.prefix_counters["evictions"] > before
    assert old[1] not in eng.prefix and old[1] in row.blocks
    assert row.blocks.index(old[1]) != 1    # back at another index
    c = _counters(eng)
    assert (c["prefix.blocks_matched"], c["prefix.blocks_restored"]) == (2, 0)
    assert [(i, eng.snaps.pending_block(e)) for i, e in row.snap_pending] \
        == [(1, row.blocks[1])]
    eng._check_block_invariants()
    while eng.pending():
        eng.step()
    out = list(eng.results[rid])
    assert out == solo(a2)
    path = eng.prefix.path_blocks(a2 + out)
    held = [i for i, blk in enumerate(path)
            if eng.snaps.entry(blk) is not None]
    assert held == [1]
    fresh = _engine(mc, params, n_slots=1, prefix_cache=True)
    fresh.run(_requests([a2[:17]], n_new=1))    # its last full block: 1
    want = fresh.snaps.entry(fresh.prefix.path_blocks(a2[:17] + [0] * 8)[1])
    got = eng.snaps.entry(path[1])
    np.testing.assert_allclose(np.asarray(eng.pcache.snap_ssm[:, got]),
                               np.asarray(fresh.pcache.snap_ssm[:, want]),
                               atol=ATOL)
    eng._check_block_invariants()


def test_at_insert_an_entry_follows_the_block_that_stays():
    """One prompt of exactly two blocks, twice, under a budget of two.  The
    second bearer's match is capped a token short, so it recomputes the
    prompt's last block into a copy of its own and is given, for that copy,
    the entry the first bearer's block held (nothing was ever restored from
    it).  When it retires its copy is the duplicate, and the entry moves
    back to the block that stays.  A third request then hits there."""
    _, mc, params = tiny(snapshots=2)
    p = tokens(16, seed=31)
    eng = _engine(mc, params, n_slots=1, prefix_cache=True)
    eng.run(_requests([p], n_new=2))
    first, stays = eng.prefix.path_blocks(p + [0])
    assert eng.snaps.entry(stays) is not None
    eng.submit(Request(prompt=p, max_new_tokens=2))
    eng.step()                              # its first chunk: one block
    own = eng._slots[0].blocks[1]
    assert own != stays and eng._slots[0].n_hit == 0
    # the matched block's entry (on evidence) is written; the copy's is to be
    assert eng.snaps.entry(first) is not None
    assert [eng.snaps.pending_block(e) for _, e in
            eng._slots[0].snap_pending] == [own]
    eng.step()
    assert eng.snaps.entry(own) is not None and eng.snaps.entry(stays) is None
    while eng.pending():
        eng.step()
    assert eng.prefix.path_blocks(p + [0])[1] == stays
    assert eng.snaps.entry(stays) is not None and eng.snaps.entry(own) is None
    p3 = p + tokens(4, seed=32)
    out = eng.run(_requests([p3]))
    assert list(out[0]) == sm.generate(params, mc, p3, N_NEW, pad_to=48)
    assert _counters(eng)["ssm.state_restores"] == 1
    eng._check_block_invariants()


def test_a_batch_over_one_prefix_computes_it_twice_under_a_budget(served):
    """The three bearers of the system prompt handed over at once, under a
    budget of two entries.  The first prefills alone while the others are
    held (it is admitted to write what they would); it has no evidence that
    the prefix is shared, so its one entry is at its own prompt's end.  The
    second matches the two blocks, finds no snapshot, recomputes them and is
    given the entry for the block that is indexed; the third is held for
    that entry and restored from it once it is committed."""
    _, mc, params = tiny(snapshots=2)
    _, _, prompts, want = served
    eng = _engine(mc, params, n_slots=3, prefix_cache=True)
    rids = [eng.submit(r) for r in _requests(
        [prompts[0], prompts[2], prompts[3]])]
    admitted_at = {}
    while eng.pending():
        eng.step()
        eng._check_block_invariants()
        for s in eng._slots:
            admitted_at.setdefault(s.request_id, (eng.step_index, s.n_hit))
    # two chunks for the first bearer's two blocks, two for the second's
    assert [admitted_at[r] for r in rids] == [(1, 0), (3, 0), (5, 2)]
    assert [list(eng.results[r]) for r in rids] == [want[0], want[2], want[3]]
    c = _counters(eng)
    assert c["prefix.admissions_held"] == 2 and c["prefix.held_steps"] == 6
    assert c["prefix.tokens_skipped"] == 16 and c["ssm.state_restores"] == 1
    assert (c["prefix.blocks_matched"], c["prefix.blocks_restored"]) == (4, 2)
    assert c["ssm.snapshots_evicted"] == 0


def test_preemption_and_replay_serve_the_same_tokens(served):
    mc, params, prompts, want = served
    # a decoding row taken off its slot: its blocks are released to the
    # cache, and it is replayed from its prompt plus what it emitted through
    # a hit rounded down to its prompt's last full block, where its snapshot
    # is
    eng = _engine(mc, params, n_slots=1, prefix_cache=True)
    rid = eng.submit(_requests(prompts[:1])[0])
    while len(eng._slots[0].out) < 6:
        eng.step()
    eng._preempt_row(0)
    while eng.pending():
        eng.step()
    assert list(eng.results[rid]) == want[0]
    c = _counters(eng)
    assert eng.counters["preemptions"] == 1 and c["ssm.state_restores"] == 1
    # 4 full blocks matched (27 tokens and 6 served), the third restored
    assert (c["prefix.blocks_matched"], c["prefix.blocks_restored"]) == (4, 3)
    eng._check_block_invariants()
    # 7 blocks cannot hold both long requests: the second starves, the first
    # is preempted, and the cache gives way before a row does: whatever is
    # left to hit, both serve their solo tokens; and with no cache to replay
    # through, from position 0
    for prefix_cache in (True, False):
        eng = _engine(mc, params, n_blocks=7, preempt_after=2,
                      prefix_cache=prefix_cache)
        out = eng.run(_requests([prompts[0], prompts[2]]))
        assert [list(r) for r in out] == [want[0], want[2]]
        assert eng.counters["preemptions"] >= 1
        eng._check_block_invariants()
    assert _counters(eng)["ssm.state_restores"] == 0


@pytest.mark.parametrize("prefix_cache", [False, True])
def test_speculation_on_and_off_serve_the_same_tokens(served, prefix_cache):
    mc, params, prompts, want = served
    # prompts that repeat themselves, so that drafts are proposed (and some
    # accepted, some not): the round has a state to advance by as many
    loops = [p[:20] + p[14:20] * 2 for p in prompts[:3]]
    solo = [sm.generate(params, mc, p, N_NEW, pad_to=48) for p in loops]
    outs = {}
    for spec in (False, True):
        eng = _engine(mc, params, spec=spec, draft_k=3,
                      prefix_cache=prefix_cache)
        outs[spec] = [list(r) for r in eng.run(_requests(loops))]
        if spec:
            assert eng.spec_counters["rounds"] > 0
            assert eng.spec_counters["proposed"] > 0
            assert eng.compile_cache_sizes() == {
                "sample": 0, "tick": 0, "chunk": 1, "set_row": 1,
                "spec_tick": 1}
    assert outs[True] == outs[False] == solo


def test_a_cloned_engine_serves_the_same_tokens(served):
    """``supervisor.clone_engine`` after the engine has ticked: same registry,
    fresh state and a fresh budget; the clone's device counters start at zero
    under counters that do not."""
    mc, params, prompts, want = served
    eng = _engine(mc, params, prefix_cache=True)
    assert [list(r) for r in eng.run(_requests(prompts))] == want
    before = dict(_counters(eng))
    clone = supervisor.clone_engine(eng)
    assert clone.metrics is eng.metrics
    assert clone.snaps is not eng.snaps and clone.snaps.held_count() == 0
    assert [list(r) for r in clone.run(_requests(prompts))] == want
    after = _counters(clone)
    for name in ("moe.choices_total", "moe.choices_held",
                 "ssm.state_restores", "ssm.snapshots_written",
                 "attn.keys_visible", "ssm.state_bytes_moved"):
        assert after[name] == 2 * before[name], name
    assert clone.compile_cache_sizes() == {"sample": 1, "tick": 1, "chunk": 1,
                                           "set_row": 1}


def test_router_over_a_local_replica_serves_the_same_tokens(served):
    mc, params, prompts, want = served
    router = RouterServer([LocalReplica(
        _engine(mc, params, prefix_cache=True), "r0")])
    try:
        rids = [router.route(r) for r in _requests(prompts)]
        got = [router.result(rid, timeout=120) for rid in rids]
    finally:
        router.stop(drain_s=0.0)
    assert [r.status for r in got] == ["OK"] * 4
    assert [list(r) for r in got] == want


def test_cancel_mid_prefill_frees_every_block_and_entry(served):
    mc, params, prompts, want = served
    eng = _engine(mc, params, prefix_cache=True)
    rid = eng.submit(Request(prompt=prompts[0], max_new_tokens=N_NEW))
    eng.step()
    assert eng.snaps.pending_count() == 1
    assert eng.cancel(rid)
    while eng.pending():
        eng.step()
    assert eng.results[rid].status == "CANCELLED"
    # the block its one dispatched chunk filled stays indexed, the rest free
    assert eng.cached_block_count() == 1
    assert eng.free_block_count() == eng.pool.n_blocks - 2
    assert eng.snaps.pending_count() == 0 and eng.snaps.held_count() == 0
    # the slot's stale state is not the next row's: mapped at 0 it is zeros
    assert [list(r) for r in eng.run(_requests(prompts))] == want
    eng._check_block_invariants()


def test_tensor_parallel_serving_is_refused_clearly(served):
    mc, params, _, _ = served
    with pytest.raises(NotImplementedError, match="tensor-parallel"):
        _engine(mc, params, tp_size=2)


def test_counters_equal_what_the_run_did(served):
    """One request, no cache: every prompt and served token (a tick feeds the
    token it emits) is a counted token of every layer's experts."""
    mc, params, prompts, _ = served
    eng = _engine(mc, params)
    eng.run(_requests(prompts[2:3]))
    n = len(prompts[2]) + N_NEW
    c = _counters(eng)
    assert c["moe.choices_total"] == n * mc.top_k * N_LAYERS
    assert 0 < c["moe.choices_held"] < c["moe.choices_total"]
    assert c["attn.keys_visible"] == sum(p + 1 for p in range(n))
    gauges = eng.metrics.snapshot()["gauges"]
    load = [gauges[f"moe.held_load.{e}"] for e in range(4)]
    assert sum(load) == c["moe.choices_held"]
    assert gauges["moe.load_max"] == max(load)
    assert 0 < gauges["moe.experts_touched"] <= N_LAYERS * 4
    assert c["attn.blocks_visited"] == c["attn.blocks_in_table"] \
        > c["attn.blocks_live"] > 0
    assert c["moe.choices_in_place"] > 0
