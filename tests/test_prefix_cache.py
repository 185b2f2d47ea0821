"""Shared-prefix KV cache: radix index, refcounted blocks, COW.

Pins the subsystem's acceptance contract from three sides:

1. *Parity*: with ``prefix_cache=True`` every request's tokens are
   bit-identical to its cache-off solo ``llama.generate`` run —
   including requests whose prefill was partly (or almost entirely)
   skipped by a radix hit, COW-divergent continuations of a shared
   prefix, and requests replayed after a preemption.
2. *Fixed signature*: cache hits change block-table data, never shapes
   — ``compile_cache_sizes()`` stays ``{"sample": 1, "tick": 1, "chunk": 1,
   "set_row": 1}`` through every admission (``chunk``: one signature a
   width of the chunk program, none added after construction).
3. *Accounting*: a drained engine holds zero live references and every
   block is either free or parked zero-ref in a structurally sound
   radix index; the ``HVD_TPU_VERIFY_BLOCKS`` walker checks the same
   after every step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics as metrics_mod
from horovod_tpu.faults import FaultRegistry, PermanentFault
from horovod_tpu.models import llama
from horovod_tpu.models.llama import BlockPool
from horovod_tpu.prefix_cache import RadixPrefixCache, chunk_path_digests
from horovod_tpu.serving import FAILED, OK, Request
from horovod_tpu.serving_scheduler import ServeEngine

pytestmark = pytest.mark.prefix


@pytest.fixture(scope="module")
def world():
    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(11))
    return cfg, params


def _solo(params, cfg, prompt, n_new, max_len):
    return np.asarray(llama.generate(
        params, jnp.asarray([prompt], jnp.int32), cfg,
        max_new_tokens=n_new, max_len=max_len,
    ))[0].astype(np.int64)


def _assert_drained_consistent(eng):
    assert eng.pool.ref_count() == 0
    assert (eng.free_block_count() + eng.cached_block_count()
            == eng.pcache.k.shape[1] - 1)
    if eng.prefix is not None:
        eng.prefix.check_consistency()
    assert eng.compile_cache_sizes() == {
        "sample": 1, "tick": 1, "chunk": 1, "set_row": 1}


# -- the pool ----------------------------------------------------------------


def test_block_pool_states():
    pool = BlockPool(6)                      # blocks 1..5, 0 is trash
    assert pool.free_count() == 5
    # classic allocation order: low ids first
    a, b = pool.alloc(), pool.alloc()
    assert (a, b) == (1, 2)
    pool.incref(a)
    pool.incref(b)
    pool.incref(b)                           # b shared by two rows
    assert pool.refcount(b) == 2 and pool.ref_count() == 2
    pool.decref(b)
    assert pool.refcount(b) == 1
    # unindexed blocks free at zero refs
    pool.decref(a)
    assert pool.refcount(a) == 0 and pool.free_count() == 4
    # indexed blocks park in LRU at zero refs instead
    pool.mark_indexed(b)
    pool.decref(b)
    assert pool.free_count() == 4 and pool.cached_count() == 1
    assert pool.lru_blocks() == [b]
    # re-referencing a cached block pins it (leaves the LRU)
    pool.incref(b)
    assert pool.cached_count() == 0
    with pytest.raises(RuntimeError):
        pool.drop_indexed(b)                 # live refs: not evictable
    pool.decref(b)
    pool.drop_indexed(b)                     # eviction → free list
    assert pool.free_count() == 5 and pool.cached_count() == 0
    with pytest.raises(ValueError):
        BlockPool(1)                         # only the trash block


def test_radix_insert_acquire_and_cow_cap():
    pool = BlockPool(10)
    cache = RadixPrefixCache(pool, block_size=2)
    toks = [5, 6, 7, 8, 9]
    blocks = [pool.alloc() for _ in range(3)]
    for b in blocks:
        pool.incref(b)
    # frontier 5 → only the two FULL blocks index; the partial third
    # stays private and frees on release
    assert cache.insert(toks, blocks, frontier=5) == 2
    cache.release(reversed(blocks))
    assert pool.cached_count() == 2 and pool.free_count() == 7
    # exact-path acquire is capped one token short of the prompt (COW:
    # the write-frontier block must be private) — [5,6,7,8] matches
    # only its first block even though both are indexed
    hit = cache.acquire([5, 6, 7, 8])
    assert hit == blocks[:1]
    assert pool.refcount(blocks[0]) == 1     # pinned against eviction
    assert cache.stats["hits"] == 1
    assert cache.stats["tokens_skipped"] == 2
    cache.release(hit)
    # a longer prompt walks both blocks; a diverging one stops early
    assert cache.path_blocks([5, 6, 7, 8, 1, 2]) == blocks[:2]
    assert cache.path_blocks([5, 6, 99, 8]) == blocks[:1]
    # duplicate path insert keeps the incumbent block
    dup = [pool.alloc() for _ in range(2)]
    for b in dup:
        pool.incref(b)
    assert cache.insert([5, 6, 7, 8], dup, frontier=4) == 0
    cache.release(reversed(dup))             # unindexed → straight free
    assert pool.free_count() == 7 and pool.cached_count() == 2
    cache.check_consistency()


def test_radix_evict_lru_leaf_first():
    pool = BlockPool(10)
    cache = RadixPrefixCache(pool, block_size=1)
    # two chains sharing a root token: [1,2,3] then [1,9]
    for path in ([1, 2, 3], [1, 9]):
        blocks = [pool.alloc() for _ in path]
        for b in blocks:
            pool.incref(b)
        cache.insert(path, blocks, frontier=len(path))
        cache.release(reversed(blocks))
    assert pool.cached_count() == 4          # [1] is shared: 3+2-1 nodes
    # one eviction takes the LRU *leaf*, never the shared [1] root
    assert cache.evict(1) == 1
    assert cache.path_blocks([1]) != []
    cache.check_consistency()
    # draining evicts everything, interior nodes last
    assert cache.evict(99) == 3
    assert pool.cached_count() == 0 and pool.free_count() == 9
    # pinned blocks are not evictable
    blocks = [pool.alloc()]
    pool.incref(blocks[0])
    cache.insert([4], blocks, frontier=1)
    assert cache.evict(1) == 0               # still referenced
    cache.release(blocks)
    assert cache.evict(1) == 1


def test_key_digest_summary_and_concurrent_walk_fallback(monkeypatch):
    """key_digest() is scraped from the monitor's HTTP thread while the
    engine mutates the tree: a mid-walk mutation (RuntimeError) must
    retry, then fall back to the last complete summary — never crash
    the scrape."""
    pool = BlockPool(8)
    cache = RadixPrefixCache(pool, block_size=2)
    toks = [5, 6, 7, 8]
    blocks = [pool.alloc() for _ in range(2)]
    for b in blocks:
        pool.incref(b)
    cache.insert(toks, blocks, frontier=4)
    cache.release(reversed(blocks))
    summary = cache.key_digest()
    assert summary["block_size"] == 2 and summary["n_paths"] == 2
    assert not summary["truncated"]
    assert set(summary["paths"]) == set(chunk_path_digests(toks, 2))

    # A second walk hashes nothing again: each node keeps its path's hash.
    import horovod_tpu.prefix_cache as prefix_cache_mod
    hashed = []
    with monkeypatch.context() as m:
        m.setattr(prefix_cache_mod, "_update_chunk",
                  lambda h, chunk: hashed.append(chunk))
        assert cache.key_digest() == summary and hashed == []

    # One mutation mid-walk: the retry succeeds transparently.
    real_walk = cache._key_digest_walk
    calls = {"n": 0}

    def flaky(max_paths):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("dictionary changed size during iteration")
        return real_walk(max_paths)

    monkeypatch.setattr(cache, "_key_digest_walk", flaky)
    assert cache.key_digest() == summary and calls["n"] == 2

    # A tree that never holds still: serve the last complete summary.
    def boom(max_paths):
        raise RuntimeError("dictionary changed size during iteration")

    monkeypatch.setattr(cache, "_key_digest_walk", boom)
    assert cache.key_digest() == summary

    # No complete walk ever: an empty-but-schema-stable summary.
    cold = RadixPrefixCache(BlockPool(4), block_size=2)
    monkeypatch.setattr(cold, "_key_digest_walk", boom)
    empty = cold.key_digest()
    assert empty["n_paths"] == 0 and empty["paths"] == []
    assert not empty["truncated"]


def _row(pool, n):
    blocks = [pool.alloc() for _ in range(n)]
    for b in blocks:
        pool.incref(b)
    return blocks


def test_unwritten_nodes_are_waited_for_not_hit():
    """A row admitted to write a path reserves it: ``acquire`` stops there
    and gives nothing, the nodes are not advertised, and each becomes a hit
    when it is flipped; a row that leaves first takes the rest with it."""
    pool = BlockPool(12)
    reg = metrics_mod.MetricsRegistry(event_log=None)
    cache = RadixPrefixCache(pool, block_size=2, metrics=reg)
    toks = [5, 6, 7, 8, 9, 10, 11]
    writer = _row(pool, 4)
    nodes = cache.reserve(toks, writer)
    assert [n.block for n in nodes] == writer[:3]
    assert cache.indexed_blocks() == 0 and cache.key_digest()["n_paths"] == 0
    assert cache.path_blocks(toks) == []
    cache.check_consistency()
    # a second bearer of the first two blocks: nothing now, more later
    assert cache.acquire([5, 6, 7, 8, 1]) is None
    assert cache.awaited is nodes[0] and cache.on_its_way(nodes[0])
    assert cache.stats["hits"] == 0 and cache.stats["misses"] == 0
    # ... nor does it reserve anything below a node that is another row's
    late = _row(pool, 3)
    assert cache.reserve([5, 6, 7, 8, 1, 2], late) == [None] * 3
    cache.release(late)
    # the first block's chunk is dispatched: a hit, and the walk stops at
    # the second
    cache.written(nodes[0], writer[0])
    assert not cache.on_its_way(nodes[0]) and writer[0] in cache
    assert cache.key_digest()["paths"] == chunk_path_digests(toks, 2)[:1]
    assert cache.acquire([5, 6, 7, 8, 1]) is None
    assert cache.awaited is nodes[1]
    # a prompt that leaves the path there is served what there is
    assert cache.acquire([5, 6, 1, 2]) == writer[:1]
    cache.release(writer[:1])
    assert reg.counter("prefix.blocks_indexed_live").value == 1
    assert cache.stats["inserted_blocks"] == 1
    cache.check_consistency()
    # the writer is freed with two blocks unwritten: they leave the tree,
    # the written one parks, and the waiting prompt gets its one block
    cache.forget(nodes[1:])
    cache.release(reversed(writer))
    assert not cache.on_its_way(nodes[1]) and nodes[2].parent is None
    assert pool.cached_count() == 1 and pool.free_count() == 10
    cache.check_consistency()
    assert cache.acquire([5, 6, 7, 8, 1]) == writer[:1]
    cache.release(writer[:1])
    cache.check_consistency()


def test_a_retiring_row_takes_an_unwritten_node_over():
    """A prompt that ends on a block boundary is admitted beside the row
    that is to write that block (its match is capped short of it) and writes
    a copy of its own.  If it retires first, the node is its copy's, and the
    reserved block is the duplicate when its chunk comes."""
    pool = BlockPool(12)
    cache = RadixPrefixCache(pool, block_size=2)
    slow = _row(pool, 3)
    nodes = cache.reserve([5, 6, 7, 8, 9], slow)
    cache.written(nodes[0], slow[0])
    assert cache.acquire([5, 6, 7, 8]) == slow[:1]      # capped: not held
    quick = slow[:1] + _row(pool, 2)
    assert cache.reserve([5, 6, 7, 8], quick) == [None, None]
    assert cache.insert([5, 6, 7, 8, 1, 2], quick, frontier=6) == 2
    assert cache.path_blocks([5, 6, 7, 8, 1, 2]) == quick
    cache.release(reversed(quick))
    cache.check_consistency()
    cache.written(nodes[1], slow[1])        # the duplicate: nothing changes
    assert cache.path_blocks([5, 6, 7, 8]) == quick[:2]
    cache.forget(nodes)                     # nothing of it is unwritten
    cache.release(reversed(slow))
    assert pool.cached_count() == 3 and pool.free_count() == 8
    cache.check_consistency()


# -- engine integration ------------------------------------------------------


def _shared_prefix_requests():
    sys_prompt = [5, 17, 42, 9, 3, 8, 11, 2]
    return [
        Request(prompt=sys_prompt + [7], max_new_tokens=5),
        Request(prompt=sys_prompt + [30, 31], max_new_tokens=4),
        Request(prompt=sys_prompt + [7], max_new_tokens=5),
        Request(prompt=[100, 101], max_new_tokens=6),   # cold prompt
        Request(prompt=sys_prompt, max_new_tokens=3),   # boundary COW
    ]


def test_engine_parity_and_hits_with_cache(world):
    """The acceptance pin: a shared-prefix workload served twice through
    one cache-on engine is bit-identical to the solo runs, reports hits
    (the second pass on every warm prompt), and never adds a jit
    signature."""
    cfg, params = world
    reqs = _shared_prefix_requests()
    eng = ServeEngine(params, cfg, n_slots=2, max_len=24, chunk=4,
                      prefix_cache=True)
    for _pass in range(2):
        out = eng.run(reqs)
        for req, res in zip(reqs, out):
            assert res.status == OK
            np.testing.assert_array_equal(
                np.asarray(list(res), np.int64),
                _solo(params, cfg, req.prompt, req.max_new_tokens, 24))
        _assert_drained_consistent(eng)
    # pass 2 hits every request whose prompt spans >= 1 full block;
    # request 3's 2-token prompt can't (cap = (2-1)//4 = 0 blocks)
    assert eng.prefix_counters["hits"] >= 4
    assert eng.prefix_counters["tokens_skipped"] > 0
    hit_rids = {e.request_id for e in eng.events if e.kind == "hit"}
    assert len(hit_rids) >= 4


def test_cow_divergent_continuations_share_blocks(world):
    """Two in-flight requests over one cached prefix: their rows map
    the SAME physical blocks (refcount 2) while each appends into its
    own private tail — and both finish solo-exact."""
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=2, max_len=24, chunk=4,
                      prefix_cache=True)
    sys_prompt = [5, 17, 42, 9, 3, 8, 11, 2]
    warm = Request(prompt=sys_prompt + [1], max_new_tokens=3)
    assert eng.run([warm])[0].status == OK   # indexes the prefix
    a = Request(prompt=sys_prompt + [7, 13], max_new_tokens=5)
    b = Request(prompt=sys_prompt + [60], max_new_tokens=5)
    ra, rb = eng.submit(a), eng.submit(b)
    shared_seen = False
    for _ in range(64):
        if not eng.pending():
            break
        eng.step()
        sa = next((s for s in eng._slots if s.request_id == ra), None)
        sb = next((s for s in eng._slots if s.request_id == rb), None)
        if sa is not None and sb is not None and sa.n_hit and sb.n_hit:
            common = set(sa.blocks[:sa.n_hit]) & set(sb.blocks[:sb.n_hit])
            for blk in common:
                assert eng.pool.refcount(blk) == 2
                shared_seen = True
            # divergent tails are disjoint private blocks
            assert not (set(sa.blocks[sa.n_hit:])
                        & set(sb.blocks[sb.n_hit:]))
    assert shared_seen, "prefix blocks were never physically shared"
    for req, rid in ((a, ra), (b, rb)):
        res = eng.results[rid]
        assert res.status == OK
        np.testing.assert_array_equal(
            np.asarray(list(res), np.int64),
            _solo(params, cfg, req.prompt, req.max_new_tokens, 24))
    _assert_drained_consistent(eng)


def test_preempt_replay_with_cache_reports_hits(world):
    """Preemption on an overcommitted pool with the cache on: the
    victim's blocks release-to-cache, its replay re-admits through a
    PREFIX hit, and the resumed output stays bit-identical."""
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, chunk=4,
                      block_size=4, n_blocks=6, preempt_after=2,
                      prefix_cache=True)
    victim = Request(prompt=[5, 17, 42], max_new_tokens=13)
    head = Request(prompt=[7, 8], max_new_tokens=6)
    out = eng.run([victim, head])
    assert eng.counters["preemptions"] >= 1
    kinds = [(e.kind, e.request_id) for e in eng.events]
    pre = kinds.index(("preempt", 0))
    assert ("hit", 0) in kinds[pre:], \
        "replay admission did not hit the released-to-cache blocks"
    assert eng.prefix_counters["hits"] >= 1
    for req, res in zip([victim, head], out):
        assert res.status == OK
        np.testing.assert_array_equal(
            np.asarray(list(res), np.int64),
            _solo(params, cfg, req.prompt, req.max_new_tokens, 16))
    _assert_drained_consistent(eng)


def test_cache_fault_quarantines_one_request(world):
    """A permanent ``serve.cache`` fault fails ONLY the implicated
    request; concurrent sharers of the same prefix finish solo-exact
    and the radix index / shared blocks survive intact."""
    cfg, params = world
    reqs = _shared_prefix_requests()[:3]     # three prefix sharers
    reg = FaultRegistry()
    eng = ServeEngine(params, cfg, n_slots=2, max_len=24, chunk=4,
                      faults=reg, prefix_cache=True)
    ids = [eng.submit(r) for r in reqs]
    reg.inject("serve.cache", on_hit=1, permanent=True, key=ids[1])
    while eng.pending():
        eng.step()
    assert eng.results[ids[1]].status == FAILED
    assert isinstance(eng.results[ids[1]].error, PermanentFault)
    for i in (0, 2):
        res = eng.results[ids[i]]
        assert res.status == OK
        np.testing.assert_array_equal(
            np.asarray(list(res), np.int64),
            _solo(params, cfg, reqs[i].prompt,
                  reqs[i].max_new_tokens, 24))
    _assert_drained_consistent(eng)
    # the surviving index still serves: a fourth sharer hits
    hits0 = eng.prefix_counters["hits"]
    res = eng.run([reqs[0]])[0]
    assert res.status == OK
    assert eng.prefix_counters["hits"] > hits0


def test_transient_cache_fault_retries_then_hits(world):
    """A transient ``serve.cache`` fault delays admission by the
    backoff, then the retried lookup succeeds normally."""
    cfg, params = world
    reg = FaultRegistry()
    eng = ServeEngine(params, cfg, n_slots=2, max_len=24, chunk=4,
                      faults=reg, prefix_cache=True)
    req = Request(prompt=[5, 17, 42, 9, 3], max_new_tokens=4)
    rid0 = eng.run([req])                    # warm the index
    assert rid0[0].status == OK
    rid = eng.submit(req)
    reg.inject("serve.cache", on_hit=1, key=rid)
    while eng.pending():
        eng.step()
    res = eng.results[rid]
    assert res.status == OK
    assert eng.counters["retries"] >= 1
    np.testing.assert_array_equal(
        np.asarray(list(res), np.int64),
        _solo(params, cfg, req.prompt, req.max_new_tokens, 24))
    _assert_drained_consistent(eng)


def test_invariant_walker_runs_and_catches_corruption(world, monkeypatch):
    """``HVD_TPU_VERIFY_BLOCKS=1`` walks the tables every step without
    tripping on a healthy engine — and a deliberately corrupted slot
    bookkeeping trips it immediately."""
    cfg, params = world
    monkeypatch.setenv("HVD_TPU_VERIFY_BLOCKS", "1")
    eng = ServeEngine(params, cfg, n_slots=2, max_len=24, chunk=4,
                      prefix_cache=True)
    assert eng._verify_blocks
    out = eng.run(_shared_prefix_requests())
    assert all(r.status == OK for r in out)
    # corrupt: claim a live row over blocks the table does not map
    s = eng._slots[0]
    s.state, s.blocks, s.n_blocks = "decode", [3], 1
    with pytest.raises(AssertionError):
        eng._check_block_invariants()


def test_timeline_prefix_counters(world, tmp_path):
    """The PREFIX counter series reaches the Chrome trace (cache on
    only) with exactly the documented series names, and the final
    totals match the engine's counters."""
    import json

    from horovod_tpu import timeline as timeline_mod
    cfg, params = world
    path = str(tmp_path / "prefix_timeline.json")
    tl = timeline_mod.Timeline(path)
    eng = ServeEngine(params, cfg, n_slots=2, max_len=24, chunk=4,
                      timeline=tl, prefix_cache=True)
    eng.run(_shared_prefix_requests())
    eng.run(_shared_prefix_requests())       # warm pass → hits
    tl.close()
    with open(path) as f:
        trace = json.load(f)
    prefix_events = [ev for ev in trace
                     if ev.get("ph") == "C" and ev["name"] == "PREFIX"]
    assert prefix_events
    assert set(prefix_events[-1]["args"]) == {
        "hits", "blocks_reused", "tokens_skipped", "evictions"}
    assert prefix_events[-1]["args"] == eng.prefix_counters
    assert prefix_events[-1]["args"]["hits"] > 0


# -- registered at dispatch, waited for at admission -------------------------

SHARED = [5, 17, 42, 9, 3, 8, 11, 2]            # two blocks of four


def _batch(n):
    return [Request(prompt=SHARED + [20 + i, 40 + i, 60 + i][:1 + i % 3],
                    max_new_tokens=4) for i in range(n)]


def _engine(params, cfg, n_slots, **kw):
    return ServeEngine(params, cfg, n_slots=n_slots, max_len=24, chunk=4,
                       prefix_cache=True,
                       metrics=metrics_mod.MetricsRegistry(event_log=None),
                       **kw)


def _assert_solo(params, cfg, reqs, results):
    for req, res in zip(reqs, results):
        assert res.status == OK
        np.testing.assert_array_equal(
            np.asarray(list(res), np.int64),
            _solo(params, cfg, req.prompt, req.max_new_tokens, 24))


def _prefix_counters(eng):
    c = eng.metrics.snapshot()["counters"]
    return {k[len("prefix."):]: v for k, v in c.items()
            if k.startswith("prefix.")}


def test_a_batch_over_one_prefix_prefills_it_once(world, monkeypatch):
    """N requests over one prefix of two blocks handed over at once to N
    slots: one prefills the prefix, the others are passed over until its
    second chunk is dispatched and then hit both blocks while it still
    runs; every request's tokens are its cache-off run's."""
    cfg, params = world
    monkeypatch.setenv("HVD_TPU_VERIFY_BLOCKS", "1")
    n = 4
    reqs = _batch(n)
    eng = _engine(params, cfg, n)
    ids = [eng.submit(r) for r in reqs]
    eng.step()
    assert [s.request_id for s in eng._slots] == [ids[0], -1, -1, -1]
    eng.step()                              # the second shared block's chunk
    assert eng._slots[0].state == "prefill" and len(eng.events) == 1
    eng.step()
    assert [s.n_hit for s in eng._slots] == [0, 2, 2, 2]
    assert eng.pool.refcount(eng._slots[0].blocks[1]) == n
    while eng.pending():
        eng.step()
    _assert_solo(params, cfg, reqs, [eng.results[i] for i in ids])
    c = _prefix_counters(eng)
    assert c["tokens_skipped"] == (n - 1) * len(SHARED)
    assert c["admissions_held"] == n - 1 and c["held_steps"] == 2 * (n - 1)
    assert c["blocks_indexed_live"] == 2 and c["hits"] == n - 1
    _assert_drained_consistent(eng)


@pytest.mark.parametrize("how", ["cancel", "fail", "preempt"])
def test_the_bearer_leaves_before_it_has_written_the_prefix(world, how,
                                                            monkeypatch):
    """The row the others wait for goes after its first chunk, with the
    second shared block unwritten: cancelled, failed by a ``serve.prefill``
    fault, or taken off its slot for replay.  The node they waited on goes
    with it, so the next step admits the first of them on the one block that
    was written, the others wait for that one, and nothing leaks."""
    cfg, params = world
    monkeypatch.setenv("HVD_TPU_VERIFY_BLOCKS", "1")
    reg = FaultRegistry()
    reqs = _batch(3)
    eng = _engine(params, cfg, 3, faults=reg)
    ids = [eng.submit(r) for r in reqs]
    if how == "fail":
        reg.inject("serve.prefill", on_hit=2, permanent=True, key=ids[0])
    eng.step()
    assert eng._slots[0].request_id == ids[0]
    if how == "cancel":
        assert eng.cancel(ids[0])
    elif how == "preempt":
        eng._preempt_row(0)
    else:
        eng.step()                          # the fault takes its second chunk
        assert eng.results[ids[0]].status == FAILED
    assert all(e.held_on is not None and not eng.prefix.on_its_way(e.held_on)
               for e in eng._queue if e.rid != ids[0])
    eng.step()
    bearer = next(s for s in eng._slots if s.request_id == ids[1])
    assert bearer.n_hit == 1                # the block the first one wrote
    assert ids[2] not in [s.request_id for s in eng._slots]
    while eng.pending():
        eng.step()
    served = [i for i in range(3) if how == "preempt" or i > 0]
    _assert_solo(params, cfg, [reqs[i] for i in served],
                 [eng.results[ids[i]] for i in served])
    c = _prefix_counters(eng)
    # the replayed bearer waits in its turn; a candidate counts once
    assert c["admissions_held"] == (3 if how == "preempt" else 2)
    assert c["tokens_skipped"] == 4 + 8 + (8 if how == "preempt" else 0)
    _assert_drained_consistent(eng)


def test_a_failed_row_leaves_the_blocks_its_chunks_filled_as_hits(world):
    """A row that fails in decode registers nothing at its retirement, but
    its prompt's blocks were indexed as their chunks were dispatched, each
    without a fault: they stay, and the next bearer of the prompt hits them
    and serves its cache-off tokens."""
    cfg, params = world
    reg = FaultRegistry()
    eng = _engine(params, cfg, 2, faults=reg)
    doomed = Request(prompt=SHARED + [7, 13], max_new_tokens=8)
    rid = eng.submit(doomed)
    reg.inject("serve.tick", on_hit=2, permanent=True, key=rid)
    while eng.pending():
        eng.step()
    assert eng.results[rid].status == FAILED
    assert eng.prefix.path_blocks(doomed.prompt) != [] \
        and eng.prefix.indexed_blocks() == 2    # none of the answer's
    assert eng.cached_block_count() == 2
    nxt = Request(prompt=SHARED + [7, 60], max_new_tokens=5)
    out = eng.run([nxt])
    _assert_solo(params, cfg, [nxt], out)
    assert eng.prefix_counters["tokens_skipped"] == len(SHARED)
    _assert_drained_consistent(eng)
