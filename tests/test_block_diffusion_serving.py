"""``ServeEngine`` over a model that generates by diffusion over blocks
(``horovod_tpu.models.block_diffusion_moe``) on the CPU at a toy size, in
float32: every request's tokens, committed blocks and unmask order against the
plain reference's sampler (``benchmark/reference/sdar.py``), with mixed rows in
one tick (rows that commit beside rows that denoise beside rows that prefill),
prompts that leave a tail, budgets that cut a block, a prefix hit, preemption
with replay, a retirement, a cancel, a deadline and a fault in the middle of a
block, and what the engine refuses."""

import time

import jax
import numpy as np
import pytest

from horovod_tpu import faults as faults_mod
from horovod_tpu import metrics as metrics_mod
from horovod_tpu import profiler
from horovod_tpu.serving import Request
from horovod_tpu.serving_scheduler import DECODE, ServeEngine

from toy_block_diffusion import (DYNAMIC, SOME, STATIC, model_config, ref,
                                 sampler, toy)

PAD = 64


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def world():
    return toy(4)


def _engine(world, s, **kw):
    cfg, _, params = world
    kw.setdefault("n_slots", 3)
    kw.setdefault("prefix_cache", True)
    return ServeEngine(params, model_config(cfg, s), max_len=PAD, chunk=8,
                       metrics=metrics_mod.MetricsRegistry(event_log=None),
                       **kw)


def _want(world, s, prompt, n_out):
    cfg, w, _ = world
    return ref.sample(cfg, w, s, prompt, n_out, pad_to=PAD)


def _check(world, s, req, res):
    want = _want(world, s, req.prompt, req.max_new_tokens)
    assert res.status == "OK"
    assert list(res) == want["tokens"]
    assert res.blocks == want["blocks"]
    assert res.unmask_steps == want["steps"]


def _requests(rng, shapes):
    return [Request(prompt=rng.integers(1, 60, n).tolist(), max_new_tokens=k)
            for n, k in shapes]


#: prompts that leave a tail of 2, 3, 0, 1, 0 and 3 in their first block
#: (one shorter than a block: no prefill at all), budgets that cut the last
SHAPES = ((10, 7), (3, 9), (16, 4), (21, 13), (8, 1), (19, 6))


@pytest.mark.parametrize("steps,remasking", [
    (2, STATIC), (2, DYNAMIC), (4, DYNAMIC), (1, STATIC)])
def test_every_request_is_the_references_sampler(world, steps, remasking):
    s = sampler(steps, remasking, SOME)
    eng = _engine(world, s)
    reqs = _requests(np.random.default_rng(steps), SHAPES)
    res = eng.run(reqs)
    for req, r in zip(reqs, res):
        _check(world, s, req, r)
    # one signature a program over a run in which ticks held rows that
    # commit beside rows that denoise beside rows that prefill
    assert eng.compile_cache_sizes() == {
        "unmask": 1, "tick": 1, "chunk": 1, "set_row": 1}
    c = eng.metrics.snapshot()["counters"]
    g = eng.metrics.snapshot()["gauges"]
    n_blocks = sum(len(r.blocks) for r in res)
    assert c["diffusion.commit_forwards"] == n_blocks \
        == g["diffusion.blocks_committed.device"]
    generated = sum(1 for r in res for when in r.unmask_steps
                    for w in when if w >= 0)
    assert c["diffusion.tokens_unmasked"] == generated \
        == c["diffusion.unmasked_by_threshold"] \
        + c["diffusion.unmasked_by_schedule"]
    assert c["diffusion.blocks_redone"] == 0
    if remasking == STATIC:
        assert c["diffusion.unmasked_by_threshold"] == 0
    elif steps > 1:     # the threshold fired in some blocks and not others
        assert c["diffusion.unmasked_by_threshold"] > 0
        assert c["diffusion.unmasked_by_schedule"] > 0
    assert c["serve.tokens_emitted"] == sum(len(r) for r in res)
    assert c["moe.choices_total"] == c["moe.choices_held"] > 0
    assert c["attn.blocks_visited"] >= c["attn.blocks_live"] > 0
    assert g["moe.experts_touched"] > 0 and g["moe.load_max"] > 0


def test_the_step_log_counts_blocks_and_the_unmask_phase(world):
    s = sampler(2, STATIC)
    eng = _engine(world, s)
    reqs = _requests(np.random.default_rng(3), ((9, 6), (6, 3)))
    res = eng.run(reqs)
    rows = eng.prof.log.rows()
    col = {n: rows[:, profiler.ROW_FIELDS.index(n)] for n in
           ("tokens", "tick_rows", "first_tokens", "unmask",
            "decode_dispatch")}
    assert col["tokens"].sum() == sum(len(r) for r in res)
    # a step emits nothing or a block's generated tokens, a row
    assert set(col["tokens"]) <= {0, 1, 2, 3, 4, 5, 6, 7, 8}
    assert col["first_tokens"].sum() == 2
    ticking = col["tick_rows"] > 0
    assert ticking.any() and (col["unmask"][ticking] > 0).all()
    assert not col["unmask"][~ticking].any()
    assert "unmask" in eng.prof.report()["phases"]
    # first block of 4 less the tail of 1 / 2, then blocks of 4
    tr = [r.trace for r in res]
    assert all(t.first_token_ts is not None and t.tpot_s is not None
               for t in tr)
    assert "block=" in eng.state_dump()


def test_a_prefix_hit_skips_whole_pages_only(world):
    s = sampler(2, STATIC)
    eng = _engine(world, s)
    rng = np.random.default_rng(11)
    head = rng.integers(1, 60, 16).tolist()             # two pages of 8
    reqs = [Request(prompt=head + rng.integers(1, 60, n).tolist(),
                    max_new_tokens=6) for n in (5, 7)]
    first = eng.run(reqs[:1])
    second = eng.run(reqs[1:])
    _check(world, s, reqs[0], first[0])
    _check(world, s, reqs[1], second[0])
    assert first[0].trace.prefix_tokens_skipped == 0
    assert second[0].trace.prefix_tokens_skipped == 16
    # the prompt's tail (21 mod 4 = 1, 23 mod 4 = 3) was prefilled by
    # neither, and a prompt that ends on a page is all hit but its last page
    exact = Request(prompt=head, max_new_tokens=5)
    again = eng.run([exact])
    _check(world, s, exact, again[0])
    assert again[0].trace.prefix_tokens_skipped == 8


def test_preemption_replays_committed_blocks_and_redoes_the_one_in_flight(
        world):
    s = sampler(2, STATIC)
    # 9 blocks of 8 for rows that need 4 each: the third waits, and with
    # preempt_after=1 the youngest decoding row is preempted for it
    eng = _engine(world, s, n_blocks=10, preempt_after=1,
                  prefix_cache=False)
    reqs = _requests(np.random.default_rng(5), ((10, 20), (7, 22), (9, 21)))
    res = eng.run(reqs)
    assert eng.counters["preemptions"] > 0
    for req, r in zip(reqs, res):
        _check(world, s, req, r)
    c = eng.metrics.snapshot()["counters"]
    assert c["diffusion.blocks_redone"] > 0
    assert c["diffusion.commit_forwards"] == sum(len(r.blocks) for r in res)
    assert eng.compile_cache_sizes()["tick"] == 1


def _step_until(eng, cond, limit=200):
    for _ in range(limit):
        eng.step()
        if cond():
            return
    raise AssertionError("the condition never held")


def test_cancel_deadline_and_eos_in_the_middle_of_a_block(world):
    s = sampler(4, STATIC)      # four denoise ticks a block: easy to land in
    eng = _engine(world, s)
    rng = np.random.default_rng(9)
    prompt = rng.integers(1, 60, 9).tolist()
    want = _want(world, s, prompt, 12)
    mid = lambda slot: (slot.state == DECODE and not slot.fresh   # noqa: E731
                        and 0 < slot.block.count(63) < 4)
    # cancel: tokens so far are the committed blocks' alone
    rid = eng.submit(Request(prompt=prompt, max_new_tokens=12))
    _step_until(eng, lambda: mid(eng._slots[0]) and eng._slots[0].out)
    held = list(eng._slots[0].out)
    assert "denoise_steps=" in eng.state_dump()
    assert eng.cancel(rid)
    got = eng.results[rid]
    assert got.status == "CANCELLED" and list(got) == held
    assert held == want["tokens"][:len(held)] and len(held) % 4 == 3
    assert got.blocks == want["blocks"][:len(got.blocks)]
    # a deadline that runs out in the middle of a block
    rid = eng.submit(Request(prompt=prompt, max_new_tokens=12,
                             deadline_s=3600.0))
    _step_until(eng, lambda: mid(eng._slots[0]))
    eng._slots[0].deadline = time.monotonic() - 1.0
    eng.step()
    assert eng.results[rid].status == "TIMEOUT"
    assert list(eng.results[rid]) == want["tokens"][:len(eng.results[rid])]
    # eos inside a block cuts there, and the slot serves the next request
    eos = want["tokens"][5]
    cut = want["tokens"].index(eos) + 1
    res = eng.run([Request(prompt=prompt, max_new_tokens=12, eos_id=eos),
                   Request(prompt=prompt, max_new_tokens=12)])
    assert res[0].status == "OK" and list(res[0]) == want["tokens"][:cut]
    assert list(res[1]) == want["tokens"]
    assert not eng.pending()


def test_a_fault_in_a_block_tick_replays_to_the_same_tokens(world):
    s = sampler(2, STATIC)
    reg = faults_mod.FaultRegistry()
    eng = _engine(world, s, faults=reg)
    reqs = _requests(np.random.default_rng(13), ((10, 9), (6, 7)))
    reg.inject("serve.tick", on_hit=5, count=1, key=0)
    res = eng.run(reqs)
    assert eng.counters["retries"] == 1
    for req, r in zip(reqs, res):
        _check(world, s, req, r)


def test_what_the_engine_refuses(world):
    cfg, _, params = world
    mc = model_config(cfg, sampler(2))
    kw = dict(n_slots=2, max_len=PAD,
              metrics=metrics_mod.MetricsRegistry(event_log=None))
    with pytest.raises(ValueError, match="diffusion over blocks"):
        ServeEngine(params, mc, chunk=8, spec=True, **kw)
    with pytest.raises(NotImplementedError, match="diffusion over blocks"):
        ServeEngine(params, mc, chunk=8, tp_size=2, **kw)
    with pytest.raises(ValueError, match="multiples of its block_length"):
        ServeEngine(params, mc, chunk=6, **kw)
    with pytest.raises(ValueError, match="multiples of its block_length"):
        ServeEngine(params, mc, chunk=8, block_size=2, **kw)
    eng = ServeEngine(params, mc, chunk=8, **kw)
    with pytest.raises(ValueError, match="diffusion over blocks"):
        eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=4,
                           temperature=0.7))
    # the last block is written whole: 57 + 6 = 63 tokens are 64 positions,
    # 58 + 6 would be 64 tokens and still fit, 59 + 6 would not
    eng.submit(Request(prompt=[1] * 58, max_new_tokens=6))
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.submit(Request(prompt=[1] * 59, max_new_tokens=6))
