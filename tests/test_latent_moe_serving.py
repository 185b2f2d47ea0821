"""models/latent_moe.py behind ``ServeEngine`` and the router, over the model
interface: the tokens of the cache-free program on either path of the
selection and under each form of the experts, a prefix hit, preemption with
replay, the verify round, a cancel, and the counters against what the
reference counts (benchmark/reference/dots3.py)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from toy_latent_moe import (REACHES, TINY, _dispatched, _engine, _forms_run,
                            reference_choices, tiny, tokens)

from horovod_tpu import metrics as metrics_mod
from horovod_tpu.models import latent_moe as lm
from horovod_tpu.models import llama
from horovod_tpu.router import LocalReplica, RouterServer
from horovod_tpu.serving import Request
from horovod_tpu.serving_scheduler import ServeEngine


@pytest.fixture(scope="module")
def served():
    cfg, mc, params = tiny()
    prompts = [tokens(19, seed=4), tokens(7, seed=5), tokens(26, seed=6)]
    want = [lm.generate(params, mc, p, 9, pad_to=48) for p in prompts]
    return mc, params, prompts, want


def test_engine_run_equals_cache_free_generate(served):
    mc, params, prompts, want = served
    eng = _engine(mc, params)
    out = eng.run([Request(prompt=p, max_new_tokens=9) for p in prompts])
    assert [r.status for r in out] == ["OK"] * 3
    assert [list(r) for r in out] == want
    assert eng.compile_cache_sizes() == \
        {"sample": 1, "tick": 1, "chunk": 1, "set_row": 1}


@pytest.mark.parametrize("path", sorted(REACHES))
def test_engine_serves_the_same_tokens_on_either_path(monkeypatch, served,
                                                      path):
    """Chunks of 16 may take the mask path (see above); the tokens are the
    cache-free program's whichever path the reach sends them down, and
    ``dsa.mask_queries`` / ``dsa.queries`` are what :func:`lm.mask_reach`
    says of the programs that were dispatched."""
    mc, params, prompts, want = served
    monkeypatch.setattr(lm, "MASK_REACH_TOPKS", REACHES[path])
    programs = _dispatched(monkeypatch)
    eng = _engine(mc, params, chunk=16)
    out = eng.run([Request(prompt=p, max_new_tokens=9) for p in prompts])
    assert [list(r) for r in out] == want
    assert eng.compile_cache_sizes() == \
        {"sample": 1, "tick": 1, "chunk": 1, "set_row": 1}
    c = eng.metrics_snapshot()["counters"]
    reach = {"mask": 48, "list": 0, "mask_then_list": 24}[path]
    chunks = [p for p in programs if p.t == 16]
    ticks = [p for p in programs if p.t == 1]
    assert len(chunks) == 2 + 1 + 2 and len(chunks) + len(ticks) \
        == len(programs)
    assert c["dsa.queries"] == 2 * (16 * len(chunks) + 2 * len(ticks))
    assert c["dsa.mask_queries"] == 2 * 16 * sum(
        1 for p in chunks if p.longest + p.t <= reach)
    assert c["dsa.mask_queries"] == {"mask": 160, "list": 0,
                                     "mask_then_list": 96}[path]
    assert c["dsa.mask_queries"] == sum(
        p.rows * p.t * 2 for p in programs
        if p.longest + p.t <= lm.mask_reach(p.t, 48, 6))


def test_ticks_alone_count_no_query_under_the_mask(monkeypatch, served):
    """Once the prompts are in, the steps dispatch ticks only: two rows of
    one token over a table of 48 list 6 rows each, the list path, whatever
    the rows hold."""
    mc, params, prompts, want = served
    programs = _dispatched(monkeypatch)
    eng = _engine(mc, params, chunk=16)
    rid = eng.submit(Request(prompt=prompts[0], max_new_tokens=9))
    while not programs or programs[-1].t > 1:       # until the first tick
        eng.step()
    counters = lambda: eng.metrics_snapshot()["counters"]  # noqa: E731
    before, n = counters(), len(programs)
    assert before["dsa.mask_queries"] == before["dsa.queries"] - 2 * 2 > 0
    while eng.pending():
        eng.step()
    assert list(eng.results[rid]) == want[0]
    assert {p.t for p in programs[n:]} == {1} and len(programs) > n
    after = counters()
    assert after["dsa.mask_queries"] == before["dsa.mask_queries"]
    assert after["dsa.queries"] - before["dsa.queries"] \
        == 2 * 2 * (len(programs) - n)


@pytest.mark.parametrize("threshold", [0, 8, 256])
def test_choices_in_place_are_those_of_the_programs_of_few_rows(
        monkeypatch, served, threshold):
    """Chunks of 16 rows and ticks of 2: with the threshold between them the
    ticks alone compute their experts in place, at 256 (the module's own)
    every program does and at 0 none; ``moe.choices_in_place`` says so from
    the dispatched programs' rows, the tokens and ``moe.choices_total`` are
    the same under each, and the device's count of the layers that took
    every expert at once stays within the layers in place."""
    mc, params, prompts, want = served
    monkeypatch.setattr(lm, "IN_PLACE_ROWS", threshold)
    programs = _dispatched(monkeypatch)
    forms = _forms_run(monkeypatch)
    eng = _engine(mc, params, chunk=16)
    out = eng.run([Request(prompt=p, max_new_tokens=9) for p in prompts])
    assert [list(r) for r in out] == want
    # 4 expert layers a program, traced once each: set_row has none
    assert sorted(set(forms)) == sorted(
        ("_experts_in_place" if rows <= threshold else "_experts_in_tiles",
         rows) for rows in (2, 16))
    c = eng.metrics_snapshot()["counters"]
    assert {(p.rows, p.t) for p in programs} == {(1, 16), (2, 1)}
    ticks = sum(1 for p in programs if p.t == 1)
    chunks = len(programs) - ticks
    assert c["moe.choices_in_place"] == 4 * 4 * (
        2 * ticks * (2 <= threshold) + 16 * chunks * (16 <= threshold))
    assert c["moe.choices_in_place"] == lm.choices_in_place(mc, programs)
    assert c["moe.layers_batched"] <= 4 * (
        ticks * (2 <= threshold) + chunks * (16 <= threshold))
    # 16 rows x top-4 over 8 held of 16 experts touch most of them
    if threshold != 8:
        assert (c["moe.layers_batched"] > 0) == (threshold == 256)
    assert c["moe.choices_total"] == 4 * 4 * sum(
        len(p) + 9 for p in prompts)


def test_prefix_cache_hit_serves_the_same_tokens(served):
    mc, params, prompts, want = served
    eng = _engine(mc, params, prefix_cache=True)
    first = eng.run([Request(prompt=prompts[2], max_new_tokens=9)])
    again = eng.run([Request(prompt=prompts[2], max_new_tokens=9)])
    assert list(first[0]) == list(again[0]) == want[2]
    # the second run mapped the first's blocks in all three pools at once
    assert eng.prefix_counters["hits"] >= 1
    assert eng.prefix_counters["tokens_skipped"] >= 16


def test_preemption_and_replay_serve_the_same_tokens(served):
    mc, params, prompts, want = served
    # 7 blocks cannot hold both long requests: the second starves, the first
    # is preempted, requeued and replayed from its prompt plus what it emitted
    eng = _engine(mc, params, n_blocks=7, preempt_after=2)
    out = eng.run([Request(prompt=prompts[0], max_new_tokens=9),
                   Request(prompt=prompts[2], max_new_tokens=9)])
    assert [list(r) for r in out] == [want[0], want[2]]
    assert eng.counters["preemptions"] >= 1


def test_speculative_round_serves_the_same_tokens(served):
    mc, params, prompts, want = served
    eng = _engine(mc, params, spec=True, draft_k=3)
    out = eng.run([Request(prompt=p, max_new_tokens=9) for p in prompts])
    assert [list(r) for r in out] == want
    assert eng.spec_counters["rounds"] > 0
    assert eng.compile_cache_sizes()["tick"] == 0       # the wide tick only


def test_router_over_a_local_replica_serves_the_same_tokens(served):
    mc, params, prompts, want = served
    router = RouterServer([LocalReplica(_engine(mc, params), "r0")])
    try:
        rids = [router.route(Request(prompt=p, max_new_tokens=9))
                for p in prompts]
        got = [router.result(rid, timeout=120) for rid in rids]
    finally:
        router.stop(drain_s=0.0)
    assert [r.status for r in got] == ["OK"] * 3
    assert [list(r) for r in got] == want


def test_cancel_frees_every_block(served):
    mc, params, prompts, _ = served
    eng = _engine(mc, params)
    rid = eng.submit(Request(prompt=prompts[2], max_new_tokens=9))
    eng.step()
    assert eng.cancel(rid)
    while eng.pending():
        eng.step()
    assert eng.results[rid].status == "CANCELLED"
    assert eng.free_block_count() == eng.pool.n_blocks - 1


def test_tensor_parallel_serving_is_refused_clearly(served):
    mc, params, _, _ = served
    with pytest.raises(NotImplementedError, match="tensor-parallel"):
        _engine(mc, params, tp_size=2)


def test_counters_equal_what_the_reference_counts(served):
    """One request, no prefix cache: the engine decodes the prompt and each
    token it emits, so the counters are the reference's choices over prompt
    plus output."""
    mc, params, prompts, want = served
    cfg = dict(TINY)
    eng = _engine(mc, params)
    out = eng.run([Request(prompt=prompts[0], max_new_tokens=9)])
    seq = prompts[0] + list(out[0])
    choices = reference_choices(cfg, seq)
    experts = np.stack([np.asarray(a["experts"]) for a in choices
                        if a["experts"] is not None])           # [4, T, k]
    snap = eng.metrics_snapshot()
    c, g = snap["counters"], snap["gauges"]
    assert c["moe.choices_total"] == experts.size
    assert c["moe.choices_held"] == int((experts < 8).sum())
    for e in range(8):
        assert g[f"moe.held_load.{e}"] == int((experts == e).sum())
    n = len(seq)
    assert c["dsa.keys_visible"] == 2 * sum(t + 1 for t in range(n))
    assert c["dsa.keys_selected"] == 2 * sum(min(t + 1, 6) for t in range(n))
    selected = [np.asarray(a["selected"]) for a in choices
                if a["selected"] is not None]
    assert c["dsa.keys_selected"] == sum(int((s >= 0).sum()) for s in selected)
    assert 0 < g["moe.experts_touched"] <= 4 * 8
    # per pool, and their sum
    pools = eng.memory_report()["kv"]["pools"]
    assert set(pools) == {"latent", "index", "window"}
    assert g["kv.block_bytes"] == sum(p["block_bytes"] for p in pools.values())
    assert g["kv.latent_block_bytes"] == pools["latent"]["block_bytes"]
    assert g["kv.window_block_bytes"] == pools["window"]["block_bytes"]
    assert "pools=" in eng.state_dump()


def test_counters_carry_past_a_word():
    """A running sum is two int32 words; the carry is exact."""
    stats = jnp.zeros((2, lm.LOAD0 + 2), jnp.int32)
    add = jnp.zeros((lm.LOAD0 + 2,), jnp.int32).at[lm.KEYS_VISIBLE].set(
        2**30 + 12345)
    for _ in range(5):
        stats = lm._add_stats(stats, add, None)
    assert lm.read_counters(np.asarray(stats))["keys_visible"] \
        == 5 * (2**30 + 12345)


def test_a_llama_engine_lowers_to_the_same_programs_as_before_the_interface():
    """The engine reaches ``models.llama`` through the model interface; for a
    ``LlamaConfig`` its tick and chunk are, letter for letter, the programs
    that named ``llama`` directly (the chunk, since it carries rows, the
    rows entry over a program of one)."""
    cfg = llama.llama_tiny()
    params = llama.init_params(cfg, jax.random.key(0))
    eng = ServeEngine(params, cfg, n_slots=2, max_len=32, chunk=8,
                      monitor=False, sampler=False,
                      metrics=metrics_mod.MetricsRegistry(event_log=None))
    assert eng.model is llama

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def _tick(params, pcache, last_logits, active):
        tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        logits, pcache = llama.decode_chunk_paged(
            params, tok[:, None], cfg, pcache, advance=active)
        return logits[:, 0], pcache     # the host reads `_sample`'s tokens

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def _chunk(params, pcache, last_logits, toks, slots, new_len, sel):
        logits, pcache = llama.decode_chunk_paged_rows(
            params, toks, cfg, pcache, slots, new_length=new_len, sel=sel)
        return pcache, last_logits.at[slots].set(logits, mode="drop")

    progs = eng.pinned_programs()
    for name, before in (("tick", _tick), ("chunk", _chunk)):
        fn, *avals = progs[name]
        assert fn.lower(*avals).as_text() == before.lower(*avals).as_text()
    assert set(eng.memory_report()["kv"]["pools"]) == {"k", "v"}
