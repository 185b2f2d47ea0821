"""The scheduler's side of a chunk program of several rows: the rows that
prefill in a step go out in whole groups of the wide width and the rest a row
a program (no served program carries a row that is not there), the widths
follow what the engine can see (the model's rows entry, its slots, the token
budget, the device's memory), both are compiled before the constructor
returns, a faulting request leaves its group, and the tokens are a one-row
engine's."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics as metrics_mod
from horovod_tpu import profiler
from horovod_tpu import serving_scheduler as sched
from horovod_tpu.faults import FaultRegistry
from horovod_tpu.models import latent_moe, llama, shortconv_moe, window_moe
from horovod_tpu.serving import OK, Request
from horovod_tpu.serving_scheduler import ServeEngine

CHUNK, MAX_LEN = 8, 48
MODELS = {
    "llama": (llama, lambda: llama.llama_tiny(dtype=jnp.float32)),
    "shortconv_moe": (shortconv_moe, shortconv_moe.shortconv_moe_tiny),
    "window_moe": (window_moe, window_moe.window_moe_tiny),
}


@pytest.fixture(scope="module")
def world():
    cfg = llama.llama_tiny(dtype=jnp.float32)
    return cfg, llama.init_params(cfg, jax.random.key(5))


def _engine(cfg, params, n_slots, **kw):
    kw.setdefault("metrics", metrics_mod.MetricsRegistry(event_log=None))
    return ServeEngine(params, cfg, n_slots=n_slots, max_len=MAX_LEN,
                       chunk=CHUNK, monitor=False, sampler=False, **kw)


def _requests(n, vocab=64, seed=0, lo=3, hi=CHUNK, n_out=4):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(1, vocab, int(rng.integers(
        lo, hi + 1))).tolist(), max_new_tokens=n_out) for _ in range(n)]


def _column(eng, name):
    return eng.prof.log.rows()[:, profiler.ROW_FIELDS.index(name)].tolist()


@pytest.fixture(scope="module")
def seven_slots(world, ):
    """One engine of seven slots under a budget of three windows a program
    (widths 3 and 1) for the cases that count a step's programs; each case
    drains it."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(sched, "_CHUNK_TOKENS", 3 * CHUNK)
        return _engine(*world, 7)


@pytest.mark.parametrize("n, programs", [(1, 1), (2, 2), (3, 1), (4, 2),
                                         (6, 2), (7, 3)])
def test_prefilling_rows_go_out_in_whole_wide_groups_and_singles(
        seven_slots, n, programs):
    """n rows that prefill in one step are dispatched in programs of 3 rows
    while three are left, then a row a program: 7 = 3 + 3 + 1, 2 = 1 + 1.
    The step's row says so (``chunks`` programs, ``chunk_rows`` rows), and
    the registry."""
    eng = seven_slots
    assert eng.chunk_widths == (3, 1)
    reg = eng.metrics
    before = (reg.counter("serve.chunk.programs").value,
              reg.counter("serve.chunk.rows").value)
    written = eng.prof.log.written
    for r in _requests(n, seed=n):
        eng.submit(r)
    eng.step()                  # every prompt is one window: all prefill here
    row = eng.prof.log.rows()[-1]
    assert eng.prof.log.written == written + 1
    assert row[profiler.ROW_FIELDS.index("chunks")] == programs
    assert row[profiler.ROW_FIELDS.index("chunk_rows")] == n
    while eng.pending():
        eng.step()
    assert reg.counter("serve.chunk.programs").value - before[0] == programs
    assert reg.counter("serve.chunk.rows").value - before[1] == n
    assert reg.snapshot()["gauges"]["serve.chunk.max_rows"] == 3
    assert eng.compile_cache_sizes()["chunk"] == 1


@pytest.mark.parametrize("model", sorted(MODELS))
def test_greedy_outputs_equal_a_one_row_engines(model, monkeypatch):
    """Prompts of one to three windows, some sharing a prefix of two blocks,
    through four slots: the tokens are those of an engine that dispatches one
    row a program, and rows did share programs."""
    mod, make = MODELS[model]
    cfg = make()
    params = mod.init_params(cfg, jax.random.key(2))
    shared = _requests(1, seed=9, lo=16, hi=16)[0].prompt
    reqs = _requests(7, seed=1, lo=2, hi=22)
    for r in reqs[::3]:
        r.prompt = shared + r.prompt[:5]
    wide = _engine(cfg, params, 4, prefix_cache=True)
    got = wide.run(reqs)
    monkeypatch.setattr(sched, "_CHUNK_TOKENS", CHUNK)
    narrow = _engine(cfg, params, 4, prefix_cache=True)
    want = narrow.run(reqs)
    assert wide.chunk_widths == (4, 1) and narrow.chunk_widths == (1,)
    assert all(r.status == OK for r in got + want)
    assert [list(r) for r in got] == [list(r) for r in want]
    assert sum(_column(wide, "chunk_rows")) == sum(_column(narrow, "chunks"))
    assert sum(_column(wide, "chunks")) < sum(_column(narrow, "chunks"))
    assert wide.prefix_counters["hits"] == narrow.prefix_counters["hits"] > 0
    for eng in (wide, narrow):
        assert eng.compile_cache_sizes()["chunk"] == 1
        assert eng.metrics.counter("serve.retrace").value == 0


def test_a_faulting_request_leaves_its_group_and_the_others_go_on(
        world, monkeypatch):
    """``serve.prefill`` is a request's own site: the request it fires for is
    left out before any dispatch (and retries after its back-off), the three
    others of the step go out as 2 + 1."""
    cfg, params = world
    faults = FaultRegistry()
    monkeypatch.setattr(sched, "_CHUNK_TOKENS", 2 * CHUNK)
    eng = _engine(cfg, params, 5, faults=faults)
    assert eng.chunk_widths == (2, 1)
    reqs = _requests(4, seed=3)
    ids = [eng.submit(r) for r in reqs]
    rule = faults.inject("serve.prefill", key=ids[1])
    eng.step()
    assert rule.fired == 1
    assert _column(eng, "chunks")[-1] == 2
    assert _column(eng, "chunk_rows")[-1] == 3
    assert [(e.kind, e.request_id) for e in eng.events
            if e.kind == "retry"] == [("retry", ids[1])]
    while eng.pending():
        eng.step()
    solo = _engine(cfg, params, 1)
    for rid, r in zip(ids, reqs):
        assert eng.results[rid].status == OK
        assert list(eng.results[rid]) == list(solo.run([r])[0])
    assert eng.counters["retries"] == 1


def test_an_exception_out_of_a_program_is_charged_to_its_rows(
        world, monkeypatch):
    """As the tick charges its decoding rows: the rows of the program that
    raised retry, the step's other program and its row are untouched."""
    cfg, params = world
    monkeypatch.setattr(sched, "_CHUNK_TOKENS", 2 * CHUNK)
    eng = _engine(cfg, params, 3)
    assert eng.chunk_widths == (2, 1)
    inner, calls = eng._chunk, []

    def chunk(params, pcache, last_logits, toks, *rest):
        calls.append(toks.shape[0])
        if len(calls) == 1:
            raise RuntimeError("the program of two rows")
        return inner(params, pcache, last_logits, toks, *rest)

    chunk._cache_size = inner._cache_size
    eng._chunk = chunk
    reqs = _requests(3, seed=4)
    ids = [eng.submit(r) for r in reqs]
    eng.step()
    assert calls == [2, 1]
    assert sorted(e.request_id for e in eng.events
                  if e.kind == "retry") == ids[:2]
    assert _column(eng, "chunks")[-1] == 1
    assert _column(eng, "chunk_rows")[-1] == 1
    while eng.pending():
        eng.step()
    assert all(eng.results[rid].status == OK for rid in ids)
    solo = _engine(cfg, params, 1)
    assert ([list(eng.results[rid]) for rid in ids]
            == [list(solo.run([r])[0]) for r in reqs])


def test_a_width_that_does_not_fit_is_dropped(world, monkeypatch):
    """On a device that reports its memory the wide width is halved until its
    compiled scratch fits beside what the engine holds and a tick in flight;
    the one-row program is kept whatever.  Eight rows then go out as 4 + 4,
    and nothing compiles for them.  Where not even the one-row program's
    scratch fits twice, no wide program is compiled at all."""
    cfg, params = world
    free = _engine(cfg, params, 8)          # a CPU reports no limit
    assert free._device_room() is None and free.chunk_widths == (8, 1)
    tick, one, four, eight = (free._scratch_bytes(*p) for p in (
        ("tick",), ("chunk", 1), ("chunk", 4), ("chunk", 8)))
    assert 0 < 2 * one <= eight - 1 and four <= eight - 1 and tick > 0
    assert free._chunk._cache_size() == 2   # a scratch read adds no signature
    room = {"left": tick + eight - 1}
    monkeypatch.setattr(ServeEngine, "_device_room",
                        lambda self: room["left"])
    eng = _engine(cfg, params, 8)
    assert eng.chunk_widths == (4, 1)
    assert eng.metrics.snapshot()["gauges"]["serve.chunk.max_rows"] == 4
    signatures = eng._chunk._cache_size()
    assert signatures == 2
    out = eng.run(_requests(8, seed=6))
    assert all(r.status == OK for r in out)
    assert _column(eng, "chunks")[0] == 2
    assert _column(eng, "chunk_rows")[0] == 8
    assert eng._chunk._cache_size() == signatures
    assert eng.compile_cache_sizes() == {"sample": 1, "tick": 1, "chunk": 1,
                                         "set_row": 1}
    room["left"] = tick + 2 * one - 1
    eng = _engine(cfg, params, 8)
    assert eng.chunk_widths == (1,) and eng._chunk._cache_size() == 1
    assert eng.metrics.snapshot()["gauges"]["serve.chunk.max_rows"] == 1
    assert [list(r) for r in eng.run(_requests(8, seed=6))] \
        == [list(r) for r in free.run(_requests(8, seed=6))]


def test_every_width_is_compiled_before_the_constructor_returns(world):
    """One signature a width from construction on, none after: traffic that
    reaches every width compiles nothing, and ``compile_cache_sizes`` reads
    one signature a width as 1."""
    cfg, params = world
    eng = _engine(cfg, params, 5)
    assert eng.chunk_widths == (5, 1)
    assert eng._chunk._cache_size() == 2
    assert eng.compile_cache_sizes()["chunk"] == 1
    # the runs that compiled them were over rows that are not there
    jax.tree.map(np.testing.assert_array_equal,
                 jax.tree.map(np.asarray, eng.pcache),
                 jax.tree.map(np.asarray, llama.init_paged_cache(
                     cfg, 5, MAX_LEN, block_size=CHUNK)))
    np.testing.assert_array_equal(np.asarray(eng.last_logits), 0)
    out = eng.run(_requests(12, seed=7, lo=2, hi=20))
    assert all(r.status == OK for r in out)
    # a step of five rows is the wide program, one of fewer a row a program
    assert {(int(c), int(r)) for c, r in zip(
        _column(eng, "chunks"), _column(eng, "chunk_rows"))} >= {
            (1, 5), (1, 1), (2, 2)}
    assert eng._chunk._cache_size() == 2
    assert eng.compile_cache_sizes() == {"sample": 1, "tick": 1, "chunk": 1,
                                         "set_row": 1}
    assert eng.metrics.counter("serve.retrace").value == 0


@pytest.mark.parametrize("n_slots, budget, widths", [
    (1, 2048, (1,)), (3, 2048, (3, 1)), (8, 2048, (8, 1)),
    (8, 4 * CHUNK, (4, 1)), (8, 2 * CHUNK - 1, (1,))])
def test_widths_follow_the_slots_and_the_token_budget(
        world, monkeypatch, n_slots, budget, widths):
    monkeypatch.setattr(sched, "_CHUNK_TOKENS", budget)
    assert _engine(*world, n_slots).chunk_widths == widths


def test_a_model_without_the_rows_entry_keeps_one_row_a_program():
    """The engine learns it from the model, never from a name: the second
    model has no ``decode_chunk_paged_rows``, so its chunk is one row wide and
    compiled by its first use, as ever."""
    cfg = latent_moe.latent_moe_tiny()
    assert not hasattr(latent_moe, "decode_chunk_paged_rows")
    eng = _engine(cfg, latent_moe.init_params(cfg, jax.random.key(0)), 3)
    assert eng.chunk_widths == (1,)
    assert eng.compile_cache_sizes()["chunk"] == 0
    out = eng.run(_requests(3, seed=8))
    assert all(r.status == OK for r in out)
    assert _column(eng, "chunks")[0] == _column(eng, "chunk_rows")[0] == 3
    assert eng.compile_cache_sizes()["chunk"] == 1
