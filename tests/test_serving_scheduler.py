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
    """The fixed-signature pin: one jit cache entry per program (for
    ``chunk``, whose program carries rows, 1 reads "one signature a width":
    ``ServeEngine.chunk_widths``, all compiled by the constructor), and the
    counts stay constant across admissions, recycles, and a full second
    workload on the same engine."""
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, chunk=4)
    eng.run(_mixed_requests())
    sizes = eng.compile_cache_sizes()
    assert sizes == {"sample": 1, "tick": 1, "chunk": 1, "set_row": 1}
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


# -- a step leaves its tick in flight -----------------------------------------
#
# The step's tokens come from the sampling program in front of the tick;
# nothing the tick returns is read in the step that dispatched it.  The
# oracle is the order the engine had before: the same engine, made to wait
# after every step for all that the step dispatched.

FAMILIES = ("llama", "latent_moe", "shortconv_moe", "window_moe")
MODEL_COUNTERS = ("moe.", "dsa.", "attn.", "conv.", "window.")


def _family(name):
    import importlib
    mod = importlib.import_module(f"horovod_tpu.models.{name}")
    mc = (llama.llama_tiny(dtype=jnp.float32) if name == "llama"
          else getattr(mod, f"{name}_tiny")())
    return mod, mc, mod.init_params(mc, jax.random.key(0))


def _toks(n, seed, vocab):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def _drive(eng, batch, lock_step):
    """Serve ``batch`` to the end; what each step handed back, in order."""
    ids = [eng.submit(r) for r in batch]
    steps = []
    while eng.pending():
        out = eng.step()
        if lock_step:
            jax.block_until_ready((eng.pcache, eng.last_logits))
        steps.append({rid: (r.status, list(r)) for rid, r in out.items()})
    return [eng.results[i] for i in ids], steps


def _model_counters(eng):
    return {k: v for k, v in eng.metrics.snapshot()["counters"].items()
            if k.startswith(MODEL_COUNTERS)}


@pytest.fixture(scope="module", params=FAMILIES)
def in_flight_and_lock_step(request):
    """Two engines of one family over the same two batches: a pool of 7
    blocks under three slots of up to 6 (the head starves: a preemption),
    prompts of one to four chunks of which two share a template of two
    blocks, then the first prompt again (a hit up to its last block) with
    its own third token as ``eos``, beside a new tail on the template."""
    from horovod_tpu import metrics as metrics_mod
    mod, mc, params = _family(request.param)
    v = mc.vocab_size
    template = _toks(16, 3, v)
    first = [Request(prompt=template + _toks(3, 4, v), max_new_tokens=9),
             Request(prompt=_toks(7, 5, v), max_new_tokens=6),
             Request(prompt=_toks(26, 6, v), max_new_tokens=9),
             Request(prompt=template + _toks(11, 7, v), max_new_tokens=5)]
    seen = {}
    for lock_step in (True, False):
        eng = ServeEngine(
            params, mc, n_slots=3, max_len=48, chunk=8, n_blocks=8,
            preempt_after=2, prefix_cache=True, monitor=False,
            sampler=False,
            metrics=metrics_mod.MetricsRegistry(event_log=None))
        out1, steps1 = _drive(eng, first, lock_step)
        after1 = _model_counters(eng)
        second = [Request(prompt=first[0].prompt, max_new_tokens=9,
                          eos_id=list(out1[0])[2]),
                  Request(prompt=template + _toks(6, 8, v),
                          max_new_tokens=4),
                  Request(prompt=_toks(5, 9, v), max_new_tokens=3)]
        out2, steps2 = _drive(eng, second, lock_step)
        seen[lock_step] = dict(
            eng=eng, out=out1 + out2, steps=steps1 + steps2,
            counters=[after1, _model_counters(eng)],
            events=[(e.kind, e.step, e.slot, e.request_id)
                    for e in eng.events])
    return mod, seen[False], seen[True]


def test_tokens_equal_the_lock_step_order(in_flight_and_lock_step):
    _, flight, lock = in_flight_and_lock_step
    assert all(r.status == "OK" for r in flight["out"])
    assert [list(r) for r in flight["out"]] == \
        [list(r) for r in lock["out"]]
    # step N hands out token N: each step returns the same results, and
    # every decision of the scheduler falls in the same step
    assert flight["steps"] == lock["steps"]
    assert flight["events"] == lock["events"]
    kinds = {e[0] for e in flight["events"]}
    assert {"preempt", "hit", "recycle"} <= kinds
    # a row ended by its eos before its budget, the others by budget
    ended_early = flight["out"][4]
    assert 1 <= len(ended_early) <= 3 < 9
    assert list(ended_early) == list(flight["out"][0])[:len(ended_early)]
    assert [len(r) for r in flight["out"][:4]] == [9, 6, 9, 5]
    assert flight["eng"].compile_cache_sizes() == {
        "sample": 1, "tick": 1, "chunk": 1, "set_row": 1}


def test_counters_after_a_drain_hold_the_last_tick(in_flight_and_lock_step):
    mod, flight, lock = in_flight_and_lock_step
    # after each drain, not only at the end of the engine's life
    assert flight["counters"] == lock["counters"]
    assert any(flight["counters"][1].values())
    eng = flight["eng"]
    stats = mod.paged_counters(eng.pcache)
    if stats is None:               # the model keeps none on the device
        return
    # the registry holds the device's own totals, the tick that ran
    # behind the last token included
    device = mod.read_counters(np.asarray(stats))
    held = flight["counters"][1]
    assert held["moe.choices_total"] == device["choices_total"] > 0
    mirrored = {prefix + key: total for key, total in device.items()
                for prefix in MODEL_COUNTERS
                if prefix + key in held and not key.endswith("_live")}
    assert len(mirrored) >= 3
    assert {name: held[name] for name in mirrored} == mirrored


def test_step_reads_nothing_of_its_own_tick(world):
    cfg, params = world
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, chunk=4)
    inner, made = eng._tick, []

    def tick(*args):
        made.append(inner(*args))
        return made[-1]

    tick._cache_size = inner._cache_size        # the retrace sentry's
    eng._tick = tick
    for r in _mixed_requests()[:3]:
        eng.submit(r)
    handed = 0
    while eng.pending():
        n_ticks = len(made)
        handed += sum(len(r) for r in eng.step().values())
        for out in made[n_ticks:]:
            # dispatched by this step, and not brought to the host by it
            assert all(leaf._npy_value is None
                       for leaf in jax.tree.leaves(out))
    assert len(made) >= 6 and handed == 4 + 6 + 3


def test_step_rows_tile_and_host_bound_is_bounded(world):
    from horovod_tpu import metrics as metrics_mod
    from horovod_tpu.profiler import ROW_FIELDS, TILING
    cfg, params = world
    reg = metrics_mod.MetricsRegistry(event_log=None)
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, chunk=4,
                      metrics=reg)
    eng.run(_mixed_requests())
    rows = eng.prof.log.rows()
    col = {name: i for i, name in enumerate(ROW_FIELDS)}
    assert len(rows) == eng.step_index
    tiled = rows[:, [col[p] for p in TILING]].sum(axis=1)
    np.testing.assert_allclose(
        tiled, rows[:, col["ended"]] - rows[:, col["began"]],
        rtol=0, atol=1e-9)
    ticking = int((rows[:, col["tick_rows"]] > 0).sum())
    # a ticking step waits in device_sync for its tokens, and hands out
    # one token a row
    assert (rows[:, col["device_sync"]] > 0).sum() == ticking
    np.testing.assert_array_equal(rows[:, col["tokens"]],
                                  rows[:, col["tick_rows"]])
    counters = reg.snapshot()["counters"]
    assert 0 <= counters["serve.step.host_bound"] <= ticking \
        <= counters["serve.steps"] == eng.step_index


def _held_world(params, cfg, second_new=4, **kw):
    """An engine of three slots and, handed over at once, two bearers of one
    prefix of two blocks with a request of another prefix behind them."""
    shared = [5, 17, 42, 9, 3, 8, 11, 2]
    reqs = [Request(prompt=shared + [21], max_new_tokens=4),
            Request(prompt=shared + [22, 23], max_new_tokens=second_new),
            Request(prompt=[90, 91, 92, 93, 94, 95], max_new_tokens=4)]
    eng = ServeEngine(params, cfg, n_slots=3, max_len=24, chunk=4,
                      prefix_cache=True, **kw)
    return eng, reqs, [eng.submit(r) for r in reqs]


def test_a_held_candidate_is_skipped_not_a_head_of_line(world):
    """The second bearer of a prefix the first is still prefilling is passed
    over; the request behind it, of another prefix, is admitted in the same
    step, and every request is served its solo tokens."""
    cfg, params = world
    eng, reqs, ids = _held_world(params, cfg)
    eng.step()
    assert [s.request_id for s in eng._slots] == [ids[0], ids[2], -1]
    assert [e.rid for e in eng._queue] == [ids[1]]
    assert eng._queue[0].held_steps == 1 and eng._queue[0].held_on is not None
    assert "held_on=block" in eng.state_dump()
    eng.step()
    eng.step()
    assert eng._slots[2].request_id == ids[1] and eng._slots[2].n_hit == 2
    while eng.pending():
        eng.step()
    _assert_parity(params, cfg, reqs, [eng.results[i] for i in ids], 24)


def test_a_held_candidate_is_not_block_starved(world):
    """A pool that backs the first bearer and two blocks more, and a trigger
    that preempts at the first starved step: while the second bearer is held
    it is not starved (it asks for no block), so the first is left to write
    the prefix.  Once that is a hit the second is one block short of the
    three it still needs, and the trigger is its to pull."""
    cfg, params = world
    eng, reqs, ids = _held_world(params, cfg, second_new=8, n_blocks=7,
                                 preempt_after=1)
    eng.cancel(ids[2])
    for _ in range(2):
        eng.step()
        assert eng._starve_steps == 0 and eng.counters["preemptions"] == 0
    assert eng._queue[0].held_steps == 2
    eng.step()
    assert eng._starve_steps == 1 or eng.counters["preemptions"] == 1
    while eng.pending():
        eng.step()
    _assert_parity(params, cfg, reqs[:2], [eng.results[i] for i in ids[:2]],
                   24)


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
        "sample": 1, "tick": 1, "chunk": 1, "set_row": 1}
