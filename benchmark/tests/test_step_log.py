"""The readers of the program's step log (``benchmark/step_log_stats.py`` and
its files under ``layer_metrics/``): each over hand-made rows whose answer is
known, ``None`` on a program without ``profiler.step_logs``, on a log that
wrapped inside the window and on one that does not overlap it, the identities
the rows have to keep, the new ``BENCHMARK.json`` entries against the accepted
ones, and the chat and longdoc cells end to end at toy size with and without a
program that keeps rows."""

import json
import math
import os
import types

import numpy as np
import pytest

import rehearse
from benchmark import lib, step_log_stats
from horovod_tpu import profiler
from horovod_tpu.metrics import MetricsRegistry

LATENCY = ("step_host_ms", "step_sync_wait_ms", "between_steps_ms",
           "chunk_dispatch_ms", "step_longest_ms", "step_longest_sync_pct",
           "itl_p90_emit_ms", "attn_walk_over_live")
BATCH = ("step_host_ms.batch", "step_sync_wait_ms.batch",
         "between_steps_ms.batch", "chunk_dispatch_ms.batch",
         "attn_walk_over_live.batch")
NEW = LATENCY + BATCH + ("dsa_mask_query_pct.dots3",)
BATCH_CELLS = ["mistral7b_longdoc", "dots3_longdoc32k", "lfm2_batchgen",
               "kexaone_mixedq"]

T0 = 1000.0          # the window opens here, on the rows' clock


def _row(began, gap_before=0.0, **fields):
    """One hand-made row: phases in seconds, counts as given; ``ended`` is
    ``began`` plus the tiling phases."""
    row = dict.fromkeys(profiler.ROW_FIELDS, 0.0)
    row.update(fields)
    row["began"] = began + gap_before
    row["ended"] = row["began"] + sum(row[p] for p in profiler.TILING)
    return row


def _schedule():
    """Six steps: a chunk-only step, four ticking steps (the third gives two
    tokens a row, the last holds a first token) and a chunk-only one after a
    pause.  Times in seconds, chosen to be read back by eye."""
    rows, t = [], T0 + 0.010
    plan = [
        # gap before, phases, counts
        (0.000, dict(expire=.001, admit=.004, bookkeeping=.001),
         dict(chunks=2)),
        (0.002, dict(expire=.001, admit=.001, decode_dispatch=.002,
                     device_sync=.016, sample_postprocess=.001,
                     bookkeeping=.001),
         dict(tick_rows=2, tokens=2, first_tokens=2)),
        (0.003, dict(expire=.001, admit=.001, decode_dispatch=.002,
                     device_sync=.014, sample_postprocess=.001,
                     bookkeeping=.001),
         dict(tick_rows=2, tokens=2)),
        (0.001, dict(expire=.001, admit=.001, decode_dispatch=.002,
                     device_sync=.030, sample_postprocess=.001,
                     bookkeeping=.003),
         dict(tick_rows=2, tokens=4)),
        (0.002, dict(expire=.001, admit=.005, decode_dispatch=.002,
                     device_sync=.016, sample_postprocess=.001,
                     bookkeeping=.001),
         dict(tick_rows=3, tokens=3, first_tokens=1, chunks=1)),
        (0.500, dict(expire=.001, admit=.002, bookkeeping=.001),
         dict(chunks=1)),
    ]
    for step, (gap, phases, counts) in enumerate(plan):
        sub = {"admit.prefill_dispatch": 0.75 * phases["admit"]
               if counts.get("chunks") else 0.0}
        r = _row(t, gap, step=step, **phases, **sub, **counts)
        rows.append(r)
        t = r["ended"]
    return rows


def _log(rows, counters=()):
    """A log of ``rows``; each of ``counters`` (name, where it stood at the
    first row's end, by how much each later row moved it) in its carried
    column."""
    log = profiler.StepLog(MetricsRegistry(event_log=None))
    for i, r in enumerate(rows):
        r = dict(r, **{name: stood + i * step
                       for name, stood, step in counters})
        log.append([r[f] for f in profiler.ROW_FIELDS])
    return log


def _served():
    """A step of warm-up before the window opens, then ``_schedule()``."""
    return [_row(T0 - 5.0, step=-1, expire=.001, bookkeeping=.001),
            *_schedule()]


# what the warm-up left (a walk of 4.0, every query a mask's) is not the
# window's: its six rows move the counters by 300 / 462 and 6,000 / 1,500
COUNTERS = (("attn.blocks_live", 1000, 50), ("attn.blocks_visited", 4000, 77),
            ("dsa.queries", 500, 1000), ("dsa.mask_queries", 500, 250))


@pytest.fixture
def program(monkeypatch):
    """``program(log, ...)`` puts hand-made logs in ``step_logs()``'s
    place."""
    def install(*logs):
        fake = types.SimpleNamespace(
            ROW_FIELDS=profiler.ROW_FIELDS, TILING=profiler.TILING,
            step_logs=lambda: list(logs))
        monkeypatch.setattr(step_log_stats, "_profiler", lambda: fake)
    return install


def _read(metric, rec):
    return lib.load_module("layer_metrics", metric).read(rec)


REC = {"window": (T0, T0 + 1.0)}

# by hand from _schedule(): the ticking steps' host time is 6, 6, 8, 10 ms and
# their waits 16, 14, 30, 16; the gaps between them 3, 1, 2; 3 chunks took
# 0.75 x (4 + 5 + 2) ms; the longest step is the third ticking one, 38 ms, of
# which 30 in the wait.
EXPECTED = {
    "step_host_ms": 7.5, "step_sync_wait_ms": 19.0, "between_steps_ms": 2.0,
    "chunk_dispatch_ms": 0.75 * 11.0 / 4.0, "step_longest_ms": 38.0,
    "step_longest_sync_pct": 100.0 * 30.0 / 38.0,
    "attn_walk_over_live": 1.54, "dsa_mask_query_pct.dots3": 25.0}


@pytest.mark.parametrize("metric", NEW)
def test_each_reader_over_hand_made_rows(program, metric):
    program(_log(_served(), COUNTERS))
    got = _read(metric, REC)
    base = metric[:-len(".batch")] if metric.endswith(".batch") else metric
    if base == "itl_p90_emit_ms":
        rows = _schedule()
        out = [r["ended"] - r["bookkeeping"] for r in rows[1:5]]
        # tokens other than first ones: 2 a gap of out[1]-out[0], 4 of half
        # the next gap (two tokens a row), 2 of the last (a first token left
        # out of three)
        gaps = ([out[1] - out[0]] * 2 + [(out[2] - out[1]) / 2] * 4
                + [out[3] - out[2]] * 2)
        assert got == pytest.approx(1e3 * np.quantile(gaps, 0.9), rel=1e-9)
        # 23, 23, 18.5 x 4, 30, 30 ms
        assert got == pytest.approx(30.0, rel=1e-6)
    else:
        assert got == pytest.approx(EXPECTED[base], rel=1e-9)


def test_counters_grow_from_the_row_before_the_window(program):
    program(_log(_served(), COUNTERS))
    rows = step_log_stats.window_rows(REC)
    assert len(rows) == 6 and int(rows.before[0]) == -1
    assert (rows.grew("attn.blocks_live"), rows.grew("dsa.queries")) == (
        300.0, 6000.0)
    # a window over the last three rows: the same walk, from the third's end
    late = {"window": (rows["began"][3], T0 + 1.0)}
    assert step_log_stats.window_rows(late).grew("attn.blocks_visited") == 231
    assert _read("attn_walk_over_live", late) == pytest.approx(1.54)
    # the engine's first row inside the window: nothing stood before it
    program(_log(_schedule(), COUNTERS))
    assert _read("attn_walk_over_live", REC) == pytest.approx(
        (4000 + 5 * 77) / (1000 + 5 * 50))
    # rows whose model keeps neither counter
    program(_log(_served()))
    assert _read("attn_walk_over_live", REC) is None
    assert _read("dsa_mask_query_pct.dots3", REC) is None


def test_the_chunk_count_is_the_windows(program):
    # 4 chunks in the window, the fifth row's among them
    program(_log(_schedule()))
    rows = step_log_stats.window_rows(REC)
    assert len(rows) == 6 and rows["chunks"].sum() == 4
    assert [int(s) for s in rows["step"]] == list(range(6))
    assert len(rows.ticking()) == 4


def test_host_and_wait_are_the_ticking_steps_wall_and_the_rows_tile(program):
    rows = _schedule()
    program(_log(rows))
    ticking = [r for r in rows if r["tick_rows"]]
    wall_ms = 1e3 * np.mean([r["ended"] - r["began"] for r in ticking])
    assert (_read("step_host_ms", REC) + _read("step_sync_wait_ms", REC)
            == pytest.approx(wall_ms, abs=1e-3))         # to the microsecond
    # every row's wall and the gap to the next one, over a window that the
    # rows fill: its length
    lo, hi = rows[0]["began"], rows[-1]["ended"]
    tiled = sum((b["began"] - a["ended"]) + (a["ended"] - a["began"])
                for a, b in zip(rows, rows[1:])) + (hi - rows[-1]["began"])
    assert tiled == pytest.approx(hi - lo, rel=1e-9)
    got = step_log_stats.window_rows({"window": (lo, hi)})
    span = got["ended"][-1] - got["began"][0]
    assert abs(span - (hi - lo)) <= 0.01 * (hi - lo)


@pytest.mark.parametrize("metric", NEW)
def test_none_on_a_program_without_step_logs(monkeypatch, metric):
    monkeypatch.setattr(step_log_stats, "_profiler", lambda: None)
    assert _read(metric, REC) is None


def test_a_profiler_module_without_step_logs_is_no_program(monkeypatch):
    monkeypatch.delattr(profiler, "step_logs")
    assert step_log_stats._profiler() is None
    assert step_log_stats.window_rows(REC) is None


@pytest.mark.parametrize("metric", NEW)
def test_none_where_no_log_overlaps_the_window(program, metric):
    program(_log(_schedule(), COUNTERS))
    assert _read(metric, {"window": (T0 + 50.0, T0 + 60.0)}) is None
    program()                                    # no engine was ever built
    assert _read(metric, REC) is None


def test_none_on_a_log_that_wrapped_inside_the_window(program, monkeypatch):
    monkeypatch.setattr(profiler, "STEP_LOG_ROWS", 4)
    log = _log(_schedule(), COUNTERS)
    assert log.dropped == 2
    program(log)
    assert all(_read(m, REC) is None for m in NEW)
    # the same log read over a window that opens after its oldest kept row
    # lost nothing inside it
    late = {"window": (log.rows()[1, 1], T0 + 1.0)}
    assert step_log_stats.window_rows(late) is not None
    assert _read("step_longest_ms", late) == pytest.approx(38.0)


def test_the_log_with_most_of_the_window_is_read(program):
    warm = _log([_row(T0 - 5.0, step=0, expire=.001, bookkeeping=.001)])
    other = _log(_schedule()[:2])
    main = _log(_served(), COUNTERS)
    for logs in ((warm, other, main), (main, other, warm)):
        program(*logs)
        assert len(step_log_stats.window_rows(REC)) == 6
        assert _read("attn_walk_over_live", REC) == pytest.approx(1.54)
    program(warm, other)                    # no counters in those rows
    assert _read("attn_walk_over_live", REC) is None
    assert _read("step_longest_ms", REC) == pytest.approx(22.0)


def test_every_new_entry_has_its_file_and_accepted_cells():
    spec = lib.benchmark_spec()
    entries = {m["name"]: m for m in spec["per_layer"]}
    assert len(spec["per_layer"]) == 81
    assert [m["name"] for m in spec["per_layer"][-14:]] == [
        "step_host_ms", "step_host_ms.batch", "step_sync_wait_ms",
        "step_sync_wait_ms.batch", "between_steps_ms",
        "between_steps_ms.batch", "chunk_dispatch_ms",
        "chunk_dispatch_ms.batch", "step_longest_ms", "step_longest_sync_pct",
        "itl_p90_emit_ms", "attn_walk_over_live", "attn_walk_over_live.batch",
        "dsa_mask_query_pct.dots3"]
    layers = {m["layer"] for m in spec["per_layer"][:-14]}
    for name in NEW:
        m = entries[name]
        assert lib.has_module("layer_metrics", name)
        assert m["layer"] in layers
        assert m["source"] in ("program_span", "program_counter")
        reported_in = lib.metric_cells(
            lib.find(spec["end_to_end"], m["moves"], "metric"), spec)
        assert set(m["workloads"]) <= set(reported_in), name
    assert all(entries[n]["workloads"] == ["mistral7b_chat"] for n in LATENCY)
    assert all(entries[n]["workloads"] == BATCH_CELLS for n in BATCH[:-1])
    assert entries["attn_walk_over_live.batch"]["workloads"] == [
        "mistral7b_longdoc", "lfm2_batchgen", "kexaone_mixedq"]
    assert entries["dsa_mask_query_pct.dots3"]["workloads"] == [
        "dots3_longdoc32k"]


# -- the cells end to end, at toy size --------------------------------------


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearse.make_copy(str(tmp_path_factory.mktemp("bench_steplog")))


def _ok(rc, last, out, err):
    assert rc == 0, (out[-2000:], err[-2000:])
    assert last["correct"] is True and last["failed"] == 0, out[-2000:]
    return last


@pytest.mark.parametrize("cell,names", [
    ("mistral7b_chat", LATENCY),
    ("mistral7b_longdoc", BATCH)])
def test_a_traced_cell_prints_its_new_metrics(copy, cell, names):
    last = _ok(*rehearse.run_in_copy(copy, cell, trace=1))
    got = last["metrics"]
    for name in names:
        assert math.isfinite(got[name]["value"]), name
    dot = ".batch" if cell == "mistral7b_longdoc" else ""
    assert got["attn_walk_over_live" + dot]["value"] >= 1.0
    assert got["step_sync_wait_ms" + dot]["value"] > 0.0
    assert got["step_host_ms" + dot]["value"] > 0.0


def test_a_program_without_rows_prints_none_of_them_and_is_correct(copy):
    parent = "import horovod_tpu.profiler as _p; del _p.step_logs"
    last = _ok(*rehearse.run_in_copy(copy, "mistral7b_chat", trace=1,
                                     extra=parent))
    assert not set(NEW) & set(last["metrics"])
    assert {"rows_per_tick", "tick_dev_ms"} <= set(last["metrics"])
