"""A model with a recurrent state per sequence behind ``ServeEngine``:
``models/shortconv_moe.py`` at a tiny size on the CPU.  Every holder of the
engine's invariants has to carry the convolution's state by the snapshot rule
alone: a prefix hit, release to cache at retirement, preemption with replay,
the verify round, a cloned engine, the router over a replica; and no program
gains a signature."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from horovod_tpu import metrics as metrics_mod  # noqa: E402
from horovod_tpu import supervisor  # noqa: E402
from horovod_tpu.models import shortconv_moe as sm  # noqa: E402
from horovod_tpu.router import LocalReplica, RouterServer  # noqa: E402
from horovod_tpu.serving import Request  # noqa: E402
from horovod_tpu.serving_scheduler import ServeEngine  # noqa: E402

N_NEW = 9


def tokens(n, seed):
    return np.random.default_rng(seed).integers(1, 64, n).tolist()


def _engine(mc, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("chunk", 8)
    return ServeEngine(params, mc, monitor=False, sampler=False,
                       metrics=metrics_mod.MetricsRegistry(event_log=None),
                       **kw)


def _requests(prompts):
    return [Request(prompt=p, max_new_tokens=N_NEW) for p in prompts]


@pytest.fixture(scope="module")
def served():
    """The tiny model, four prompts of which three share their first two
    blocks (a template), and each prompt's solo tokens with no cache."""
    import toy_shortconv_moe as t

    _, mc, params = t.tiny()
    template = tokens(16, seed=3)
    prompts = [template + tokens(5, seed=4), tokens(7, seed=5),
               template + tokens(11, seed=6), template + tokens(3, seed=7)]
    want = [sm.generate(params, mc, p, N_NEW, pad_to=48) for p in prompts]
    return mc, params, prompts, want


def _counters(eng):
    return eng.metrics.snapshot()["counters"]


def test_engine_run_equals_cache_free_generate(served):
    mc, params, prompts, want = served
    eng = _engine(mc, params)
    assert eng.model is sm
    out = eng.run(_requests(prompts))
    assert [r.status for r in out] == ["OK"] * 4
    assert [list(r) for r in out] == want
    assert eng.compile_cache_sizes() == \
        {"sample": 1, "tick": 1, "chunk": 1, "set_row": 1}
    snap = eng.metrics.snapshot()
    assert snap["gauges"]["kv.bytes_per_token"] == 2 * 2 * 2 * 8 * 4
    assert snap["gauges"]["state.bytes_per_slot"] == 5 * 2 * 32 * 4
    assert snap["counters"]["conv.state_restores"] == 0
    assert eng.memory_report()["kv"]["pools"].keys() == {"k", "v", "snap"}


def test_a_prefix_hit_serves_the_solo_tokens_and_restores_the_state(served):
    """Admitted on a hit, a request prefills only its own part; its tokens
    are its solo cache-off run's bit for bit, which they are not when the
    snapshots it restores from are zeroed."""
    mc, params, prompts, want = served
    eng = _engine(mc, params, prefix_cache=True)
    first = eng.run(_requests(prompts[:1]))
    assert list(first[0]) == want[0]
    assert _counters(eng)["conv.state_restores"] == 0
    hit = eng.run(_requests(prompts[2:]))
    assert [list(r) for r in hit] == want[2:]
    assert eng.prefix_counters["hits"] == 2
    assert eng.prefix_counters["tokens_skipped"] == 32
    assert _counters(eng)["conv.state_restores"] == 2
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
    assert _counters(eng)["conv.state_restores"] >= 1
    # and with no cache to replay through: prefill from position 0
    eng = _engine(mc, params, n_blocks=7, preempt_after=2)
    out = eng.run(_requests([prompts[0], prompts[2]]))
    assert [list(r) for r in out] == [want[0], want[2]]
    assert eng.counters["preemptions"] >= 1
    assert _counters(eng)["conv.state_restores"] == 0


@pytest.mark.parametrize("prefix_cache", [False, True])
def test_speculation_on_and_off_serve_the_same_tokens(served, prefix_cache):
    mc, params, prompts, want = served
    # prompts that repeat themselves, so that drafts are proposed (and some
    # accepted, some not): the round has a state to pick
    loops = [p + p[-6:] * 2 for p in prompts[:3]]
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
    """``supervisor.clone_engine``: same registry, fresh state; the clone's
    device counters start at zero under counters that do not."""
    mc, params, prompts, want = served
    eng = _engine(mc, params, prefix_cache=True)
    assert [list(r) for r in eng.run(_requests(prompts))] == want
    before = _counters(eng)["moe.choices_total"]
    clone = supervisor.clone_engine(eng)
    assert clone.metrics is eng.metrics
    assert [list(r) for r in clone.run(_requests(prompts))] == want
    assert _counters(clone)["moe.choices_total"] == 2 * before
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
    # the slot's stale state is not the next row's: mapped at 0 it is zeros
    assert [list(r) for r in eng.run(_requests(prompts))] == want


def test_a_batch_over_one_template_prefills_it_once(served):
    """The template's three bearers handed over at once to three slots: one
    prefills the template's two blocks while the other two are held, and
    these are admitted on a hit as soon as the second chunk is dispatched,
    their convolution state restored from the snapshot that chunk wrote."""
    mc, params, prompts, want = served
    eng = _engine(mc, params, n_slots=3, prefix_cache=True)
    out = eng.run(_requests([prompts[0], prompts[2], prompts[3]]))
    assert [list(r) for r in out] == [want[0], want[2], want[3]]
    c = _counters(eng)
    assert c["prefix.tokens_skipped"] == 2 * 16
    assert c["prefix.admissions_held"] == 2 and c["prefix.held_steps"] == 4
    assert c["conv.state_restores"] == 2
    # the first bearer's three full blocks, indexed as their chunks went
    assert c["prefix.blocks_indexed_live"] == 2 + sum(
        (len(p) - 16) // 8 for p in (prompts[0], prompts[2], prompts[3]))
    eng._check_block_invariants()


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
    assert c["moe.choices_total"] == n * mc.top_k * 5
    assert c["conv.snapshots_written"] == n // 8
    assert c["attn.keys_visible"] == 2 * n * (n + 1) // 2
    gauges = eng.metrics.snapshot()["gauges"]
    assert sum(gauges[f"moe.held_load.{e}"] for e in range(8)) == \
        c["moe.choices_total"]
    assert 0 < gauges["moe.experts_touched"] <= 5 * 2
    # a table of 48 positions is one key tile: no walk can read less
    assert c["attn.blocks_visited"] == c["attn.blocks_in_table"] \
        > c["attn.blocks_live"] > 0


@pytest.mark.parametrize("threshold", [0, 4, 256])
def test_choices_in_place_are_those_of_the_programs_of_few_rows(
        monkeypatch, served, threshold):
    """Chunks of 8 tokens a row (one row, or the two that prefill together in
    one program: 16 rows of tokens) and ticks of 2: with the threshold between
    them the ticks alone compute their experts in place
    (:func:`latent_moe.rows_in_place`), at 256 (the module's own) every
    program and at 0 none; ``moe.choices_in_place`` is reckoned from the
    dispatched programs' rows, and the tokens and ``moe.choices_total`` are
    the same under each."""
    from horovod_tpu.models import latent_moe as lm

    mc, params, prompts, want = served
    monkeypatch.setattr(lm, "IN_PLACE_ROWS", threshold)
    programs, publish = [], sm.publish_paged_metrics

    def spy(metrics, cfg, pcache, stats_host=None, row_blocks=(),
            programs_=()):
        programs.extend(programs_)
        return publish(metrics, cfg, pcache, stats_host, row_blocks, programs_)

    monkeypatch.setattr(sm, "publish_paged_metrics", spy)
    eng = _engine(mc, params)
    out = eng.run(_requests(prompts[:2]))
    assert [list(r) for r in out] == want[:2]
    assert {(p.rows, p.t) for p in programs} == {(1, 8), (2, 8), (2, 1)}
    in_place = [p for p in programs if p.rows * p.t <= threshold]
    assert {p.t for p in in_place} == {0: set(), 4: {1}, 256: {1, 8}}[
        threshold]
    c = _counters(eng)
    assert c["moe.choices_in_place"] == mc.top_k * 5 * sum(
        p.rows * p.t for p in in_place)
    assert c["moe.choices_in_place"] == lm.choices_in_place(mc, programs)
    assert c["moe.layers_batched"] <= 5 * len(in_place)
    assert (c["moe.layers_batched"] > 0) == (threshold == 256)
    assert c["moe.choices_total"] == mc.top_k * 5 * sum(
        len(p) + N_NEW for p in prompts[:2])
