"""Continuous-batching decode engine (horovod_tpu/serving_scheduler.py).

Three oracles pin the engine:

1. *Bit-parity*: every request served through the recycled slot pool —
   including requests admitted mid-flight into a just-recycled slot —
   emits exactly the tokens solo ``llama.generate`` emits for it.  The
   paged cache's write-before-read invariant (masked garbage past each
   row's length, trash-block scatter for idle rows) is what makes this
   hold; any leak across rows or stale read breaks it immediately.
2. *No re-trace*: each device program (tick / prefill chunk / table
   write) compiles exactly once for the life of the engine, pinned by
   the jit cache-entry counts — admission and recycling change table
   *data*, never shapes.
3. *Throughput*: on a staggered workload the engine beats fixed-batch
   ``generate`` (slot recycling backfills the drain; chunked prefill
   hides admission), the ``serve_vs_static_ratio > 1`` acceptance bar.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import timeline as timeline_mod
from horovod_tpu.models import llama
from horovod_tpu.serving import REJECTED, Request
from horovod_tpu.serving_scheduler import ServeEngine


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


def _assert_parity(params, cfg, reqs, results, max_len):
    assert len(results) == len(reqs)
    for req, got in zip(reqs, results):
        want = _solo(params, cfg, req.prompt, req.max_new_tokens, max_len)
        np.testing.assert_array_equal(np.asarray(got), want)


def _mixed_requests():
    return [
        Request(prompt=[5, 17, 42], max_new_tokens=4),
        Request(prompt=[7], max_new_tokens=6),
        Request(prompt=[9, 1, 2, 3, 4, 5], max_new_tokens=3),
        Request(prompt=[100, 101], max_new_tokens=5),
        Request(prompt=[200, 3, 1], max_new_tokens=2),
        Request(prompt=[11, 12, 13, 14], max_new_tokens=4),
        Request(prompt=[42], max_new_tokens=5),
    ]


def test_engine_matches_solo_generate(world):
    """Queue deeper than the pool, mixed lengths/budgets: every result
    is bit-identical to its solo run (recycled slots, recycled blocks,
    interleaved prefill and decode)."""
    cfg, params = world
    reqs = _mixed_requests()
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, chunk=4)
    _assert_parity(params, cfg, reqs, eng.run(reqs), 16)


def test_midflight_admission_parity(world):
    """Requests submitted while other rows are mid-decode land in
    recycled slots and still match solo generate — the strongest
    write-before-read check: the new row's blocks held another
    request's K/V moments earlier."""
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, chunk=4)
    first = _mixed_requests()[:3]
    ids = [eng.submit(r) for r in first]
    for _ in range(3):                    # mid-flight: rows decoding
        eng.step()
    late = [Request(prompt=[33, 44, 55, 66, 77], max_new_tokens=4),
            Request(prompt=[8, 9], max_new_tokens=6)]
    ids += [eng.submit(r) for r in late]
    while eng.pending():
        eng.step()
    results = [eng.results[i] for i in ids]
    _assert_parity(params, cfg, first + late, results, 16)


def test_no_retrace_across_admissions(world):
    """The fixed-signature pin: one jit cache entry per program, and the
    counts stay constant across admissions, recycles, and a full second
    workload on the same engine."""
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, chunk=4)
    eng.run(_mixed_requests())
    sizes = eng.compile_cache_sizes()
    assert sizes == {"tick": 1, "chunk": 1, "set_row": 1}
    eng.run([Request(prompt=[1, 2, 3, 4, 5, 6, 7], max_new_tokens=6),
             Request(prompt=[250], max_new_tokens=3)])
    assert eng.compile_cache_sizes() == sizes
    assert len([e for e in eng.events if e.kind == "admit"]) == 9
    assert len([e for e in eng.events if e.kind == "recycle"]) == 9


def test_overcommitted_block_pool(world):
    """A pool too small to back every slot at max_len: admission waits
    on the free list, parity holds, and retirement returns every
    block."""
    cfg, params = world
    # full backing would be 2 slots * 4 blocks + trash = 9 blocks
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, chunk=4,
                      n_blocks=6)
    total_free = eng.free_block_count()
    assert total_free == 5                # block 0 is trash
    reqs = _mixed_requests()
    _assert_parity(params, cfg, reqs, eng.run(reqs), 16)
    assert eng.free_block_count() == total_free


def test_eos_retires_slot_early(world):
    cfg, params = world
    prompt = [5, 17, 42]
    solo = _solo(params, cfg, prompt, 8, 16)
    eos = int(solo[2])                    # force a stop at token 3
    eng = ServeEngine(params, cfg, n_slots=1, max_len=16, chunk=4)
    out = eng.run([Request(prompt=prompt, max_new_tokens=8,
                           eos_id=eos)])[0]
    np.testing.assert_array_equal(np.asarray(out), solo[:3])
    assert not eng.pending()
    assert eng.free_block_count() == eng.pcache.k.shape[1] - 1


def test_chunked_prefill_interleaves_with_decode(world):
    """A long prompt admitted while another row decodes: its prefill
    runs one window per step (never stalling the ticking row for more
    than a window) and both rows keep solo parity."""
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=2, max_len=32, chunk=4)
    short = Request(prompt=[3, 1], max_new_tokens=10)
    i0 = eng.submit(short)
    eng.step()
    eng.step()                            # short row is now decoding
    long = Request(prompt=list(range(10, 29)), max_new_tokens=5)  # 19 toks
    i1 = eng.submit(long)
    windows = -(-len(long.prompt) // eng.chunk)
    admit_step = eng.step_index
    while eng.pending():
        eng.step()
    decode_evts = [e for e in eng.events
                   if e.kind == "recycle" and e.request_id == i1]
    # one prefill window per step; the final window's step also runs the
    # first decode tick: retire = admit + (windows - 1) + (budget - 1)
    assert decode_evts[0].step == admit_step + windows + long.max_new_tokens - 2
    _assert_parity(params, cfg, [short, long],
                   [eng.results[i0], eng.results[i1]], 32)


def test_scheduler_events_and_timeline(world, tmp_path):
    """Admit/recycle land in ``events`` in causal order and in the
    Chrome trace as instants, with per-step 'C'-phase counters."""
    cfg, params = world
    path = str(tmp_path / "serve_timeline.json")
    tl = timeline_mod.Timeline(path)
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, chunk=4,
                      timeline=tl)
    reqs = _mixed_requests()[:4]
    eng.run(reqs)
    tl.close()
    kinds = [e.kind for e in eng.events]
    assert kinds.count("admit") == 4 and kinds.count("recycle") == 4
    by_rid = {}
    for e in eng.events:
        by_rid.setdefault(e.request_id, []).append(e)
    for rid, evts in by_rid.items():
        assert [e.kind for e in evts] == ["admit", "recycle"]
        assert evts[0].step <= evts[1].step
    with open(path) as f:
        trace = json.load(f)
    names = [ev["name"] for ev in trace]
    assert names.count("ADMIT") == 4 and names.count("RECYCLE") == 4
    counters = [ev for ev in trace if ev.get("ph") == "C"]
    assert counters, "expected per-step counter events"
    assert set(counters[0]["args"]) == {
        "queued", "decoding", "prefilling", "free_blocks"}
    # The lifecycle totals ride their own counter series; a clean run
    # reports every series at zero on every step.
    lifecycle = [ev for ev in counters if ev["name"] == "LIFECYCLE"]
    assert lifecycle, "expected per-step LIFECYCLE counter events"
    assert set(lifecycle[0]["args"]) == {
        "preemptions", "timeouts", "cancellations", "rejections",
        "retries", "failures"}
    assert all(v == 0 for v in lifecycle[-1]["args"].values())


def test_submit_validation(world):
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=1, max_len=16, chunk=6,
                      block_size=4)
    # Malformed-but-harmless requests REJECT instead of raising — a
    # router/HTTP client sees a terminal status, not a torn connection.
    rid = eng.submit(Request(prompt=[], max_new_tokens=2))
    assert eng.results[rid].status == REJECTED
    rid = eng.submit(Request(prompt=[1], max_new_tokens=0))
    assert eng.results[rid].status == REJECTED
    with pytest.raises(ValueError, match="greedy-only"):
        eng.submit(Request(prompt=[1], max_new_tokens=2,
                           temperature=0.7))
    with pytest.raises(ValueError, match="exceeds max_len"):
        eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=14))
    # prompt 13 (+2 new = 15 <= 16) pads to 3 prefill windows of 6 = 18
    with pytest.raises(ValueError, match="prefill"):
        eng.submit(Request(prompt=list(range(1, 14)), max_new_tokens=2))
    with pytest.raises(ValueError, match="trash block"):
        ServeEngine(params, cfg, n_slots=1, max_len=16, chunk=4,
                    n_blocks=3)


@pytest.mark.slow
def test_randomized_soak_parity(world):
    """Soak: random prompts/budgets/submission times over a small pool;
    every emitted sequence must still match its solo run."""
    cfg, params = world
    rng = np.random.default_rng(7)
    eng = ServeEngine(params, cfg, n_slots=3, max_len=24, chunk=4,
                      n_blocks=12)
    reqs, ids = [], []
    for _ in range(24):
        L = int(rng.integers(1, 12))
        budget = int(rng.integers(1, 24 - L + 1))
        reqs.append(Request(
            prompt=rng.integers(1, cfg.vocab_size, size=L).tolist(),
            max_new_tokens=budget))
    pending = list(reqs)
    while pending or eng.pending():
        for _ in range(int(rng.integers(0, 3))):
            if pending:
                ids.append(eng.submit(pending.pop(0)))
        eng.step()
    results = [eng.results[i] for i in ids]
    _assert_parity(params, cfg, reqs, results, 24)
    assert eng.compile_cache_sizes() == {
        "tick": 1, "chunk": 1, "set_row": 1}
