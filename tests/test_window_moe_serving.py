"""models/window_moe.py behind ``ServeEngine``: logits against the reference
(benchmark/reference/kexaone.py), the tokens of the cache-free program, a
prefix hit that restores the ring, preemption with replay, the verify round, a
cloned engine, the router over a replica, a cancel, and the counters."""

import jax.numpy as jnp
import numpy as np
import pytest

from toy_window_moe import (ATOL, N_MOE, N_NEW, TINY, _counters, _engine,
                            _requests, reference_logits, tiny, tokens)

from horovod_tpu import supervisor
from horovod_tpu.models import window_moe as wm
from horovod_tpu.router import LocalReplica, RouterServer
from horovod_tpu.serving import Request


@pytest.fixture(scope="module")
def served():
    """The tiny model, four prompts of which three share their first two
    blocks (a system prompt), and each prompt's solo tokens with no cache."""
    _, mc, params = tiny()
    system = tokens(16, seed=3)
    prompts = [system + tokens(5, seed=4), tokens(7, seed=5),
               system + tokens(11, seed=6), system + tokens(3, seed=7)]
    want = [wm.generate(params, mc, p, N_NEW, pad_to=48) for p in prompts]
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
    assert eng.model is wm
    out = eng.run(_requests(prompts))
    assert [r.status for r in out] == ["OK"] * 4
    assert [list(r) for r in out] == want
    assert eng.compile_cache_sizes() == \
        {"sample": 1, "tick": 1, "chunk": 1, "set_row": 1}
    snap = eng.metrics.snapshot()
    # float32: 1 full layer and 4 sliding ones of 2 key heads of 8
    assert snap["gauges"]["kv.bytes_per_token"] == 2 * 1 * 2 * 8 * 4
    assert snap["gauges"]["state.bytes_per_slot"] == 2 * 4 * 6 * 2 * 8 * 4
    assert snap["gauges"]["kv.snapshot_block_bytes"] == \
        snap["gauges"]["state.bytes_per_slot"]
    assert snap["counters"]["window.state_restores"] == 0
    assert eng.memory_report()["kv"]["pools"].keys() == {"k", "v", "snap"}


def test_a_prefix_hit_serves_the_cold_tokens_and_restores_the_ring(served):
    """Admitted on a hit, a request prefills only its own part; its tokens
    are its solo cache-off run's bit for bit, which they are not when the
    snapshots it restores from are zeroed."""
    mc, params, prompts, want = served
    eng = _engine(mc, params, prefix_cache=True)
    first = eng.run(_requests(prompts[:1]))
    assert list(first[0]) == want[0]
    assert _counters(eng)["window.state_restores"] == 0
    hit = eng.run(_requests(prompts[2:]))
    assert [list(r) for r in hit] == want[2:]
    assert eng.prefix_counters["hits"] == 2
    assert eng.prefix_counters["tokens_skipped"] == 32
    assert _counters(eng)["window.state_restores"] == 2
    assert eng.compile_cache_sizes() == \
        {"sample": 1, "tick": 1, "chunk": 1, "set_row": 1}

    broken = _engine(mc, params, prefix_cache=True)
    assert list(broken.run(_requests(prompts[:1]))[0]) == want[0]
    broken.pcache = broken.pcache._replace(
        snap=jnp.zeros_like(broken.pcache.snap))
    wrong = broken.run(_requests(prompts[2:]))
    assert broken.prefix_counters["hits"] == 2
    assert [list(r) for r in wrong] != want[2:]


def test_preemption_and_replay_serve_the_same_tokens(served):
    mc, params, prompts, want = served
    # 7 blocks cannot hold both long requests: the second starves, the first
    # is preempted, its blocks (and their snapshots) released to the cache,
    # and replayed through a hit on them from its prompt plus what it emitted
    eng = _engine(mc, params, n_blocks=7, preempt_after=2, prefix_cache=True)
    out = eng.run(_requests([prompts[0], prompts[2]]))
    assert [list(r) for r in out] == [want[0], want[2]]
    assert eng.counters["preemptions"] >= 1
    assert _counters(eng)["window.state_restores"] >= 1
    # and with no cache to replay through: prefill from position 0
    eng = _engine(mc, params, n_blocks=7, preempt_after=2)
    out = eng.run(_requests([prompts[0], prompts[2]]))
    assert [list(r) for r in out] == [want[0], want[2]]
    assert eng.counters["preemptions"] >= 1
    assert _counters(eng)["window.state_restores"] == 0


@pytest.mark.parametrize("prefix_cache", [False, True])
def test_speculation_on_and_off_serve_the_same_tokens(served, prefix_cache):
    mc, params, prompts, want = served
    # prompts that repeat themselves, so that drafts are proposed (and some
    # accepted, some not): the round has a ring to pick
    loops = [p + p[-6:] * 2 for p in prompts[:3]]
    solo = [wm.generate(params, mc, p, N_NEW, pad_to=48) for p in loops]
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
    fresh state; the clone's device counters start at zero under counters
    that do not."""
    mc, params, prompts, want = served
    eng = _engine(mc, params, prefix_cache=True)
    assert [list(r) for r in eng.run(_requests(prompts))] == want
    before = dict(_counters(eng))
    clone = supervisor.clone_engine(eng)
    assert clone.metrics is eng.metrics
    assert [list(r) for r in clone.run(_requests(prompts))] == want
    after = _counters(clone)
    for name in ("moe.choices_total", "moe.choices_held",
                 "window.state_restores", "window.snapshots_written",
                 "attn.keys_visible"):
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


def test_cancel_mid_prefill_frees_every_block_and_the_slot_serves_on(served):
    mc, params, prompts, want = served
    eng = _engine(mc, params, prefix_cache=True)
    rid = eng.submit(Request(prompt=prompts[2], max_new_tokens=N_NEW))
    eng.step()
    assert eng.cancel(rid)
    while eng.pending():
        eng.step()
    assert eng.results[rid].status == "CANCELLED"
    # the block its one dispatched chunk filled stays indexed, the rest free
    assert eng.cached_block_count() == 1
    assert eng.free_block_count() == eng.pool.n_blocks - 2
    # the slot's stale ring is not the next row's: mapped at 0 it is zeros
    assert [list(r) for r in eng.run(_requests(prompts))] == want


def test_tensor_parallel_serving_is_refused_clearly(served):
    mc, params, _, _ = served
    with pytest.raises(NotImplementedError, match="tensor-parallel"):
        _engine(mc, params, tp_size=2)


def test_counters_equal_what_the_run_did(served):
    """One request, no cache: every prompt and served token (a tick feeds the
    token it emits) is a counted token of every expert layer; the blocks that
    filled hold snapshots."""
    mc, params, prompts, _ = served
    eng = _engine(mc, params)
    eng.run(_requests(prompts[2:3]))
    n = len(prompts[2]) + N_NEW
    c = _counters(eng)
    assert c["moe.choices_total"] == n * mc.top_k * N_MOE
    assert 0 < c["moe.choices_held"] < c["moe.choices_total"]
    assert c["window.snapshots_written"] == n // 8
    assert c["attn.keys_visible"] == sum(
        (p + 1) + 4 * min(p + 1, mc.window) for p in range(n))
    gauges = eng.metrics.snapshot()["gauges"]
    load = [gauges[f"moe.held_load.{e}"] for e in range(8)]
    assert sum(load) == c["moe.choices_held"]
    assert gauges["moe.load_max"] == max(load)
    assert 0 < gauges["moe.experts_touched"] <= N_MOE * 8
    # a table of 48 positions is one key tile: no walk can read less
    assert c["attn.blocks_visited"] == c["attn.blocks_in_table"] \
        > c["attn.blocks_live"] > 0
    assert c["moe.choices_in_place"] > 0


def test_a_rows_window_bytes_are_a_ring_and_a_snapshot_a_block(served):
    """What a live row holds for its sliding layers is its ring, fixed, and
    one snapshot a block its table maps: read off ``kv.window_bytes_live``
    with one row decoding at two lengths, and no key or value of a sliding
    layer is kept anywhere else (the pools are the full layer's)."""
    mc, params, _, _ = served
    ring = 2 * 4 * mc.window * 2 * 8 * 4        # k and v, 4 sliding layers
    read = {}
    for n_prompt in (5, 29):
        eng = _engine(mc, params)
        eng.submit(Request(prompt=tokens(n_prompt, seed=9),
                           max_new_tokens=N_NEW))
        while eng.pending():
            eng.step()
            g = eng.metrics.snapshot()["gauges"]
            if g["serve.decoding"] and g["kv.tokens_live"] > n_prompt:
                read[n_prompt] = (g["kv.window_bytes_live"],
                                  g["kv.full_bytes_live"],
                                  g["kv.tokens_live"])
    blocks = {n: -(-(n + N_NEW) // 8) for n in (5, 29)}      # reserved whole
    assert blocks == {5: 2, 29: 5}
    for n, (window_bytes, full_bytes, live) in read.items():
        assert window_bytes == ring + blocks[n] * ring
        assert full_bytes == blocks[n] * 8 * (2 * 1 * 2 * 8 * 4)
        assert n < live <= n + N_NEW
    eng_pool = wm.paged_pool_bytes(eng.pcache)
    assert eng.pcache.k.shape[0] == mc.n_of(wm.FULL) == 1
    assert eng_pool["snap"] == ring
    # six times the tokens, the same ring: 3 more snapshots is all it costs
    assert read[29][0] - read[5][0] == 3 * ring
