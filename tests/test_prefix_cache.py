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
