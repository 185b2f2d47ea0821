"""Collects ``benchmark/tests/test_step_log.py`` under tier-1: the same test
functions, parametrisations and module fixtures; the one test that pins the
length of ``BENCHMARK.json``'s ``per_layer`` at PR 35's 81 entries is taken on
those 81 (held, by their digest, to be the entries PR 35 left: the cut sees
the accepted spec and nothing edited), and what later PRs appended (a cell's
own metrics: files and entries only) is checked beside it.  Run directly
(``python -m pytest benchmark/tests``) the accepted case fails on
``len(...) == 81`` until a ``benchmark`` issue edits that line (PERF.md
section 7)."""

import hashlib
import json
import os
import sys
import types

sys.path.append(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "tests"))    # `import rehearse`

import pytest  # noqa: E402

from benchmark import lib  # noqa: E402
from benchmark.tests import test_step_log as accepted  # noqa: E402
from benchmark.tests.test_step_log import *  # noqa: E402,F401,F403

ACCEPTED_ENTRIES = 81
#: sha-256 of ``json.dumps(per_layer[:81], sort_keys=True)`` at PR 37's commit
ACCEPTED_DIGEST = \
    "93369594d82581a81e0de054f52bc44d62b7765c7c3e83b810910a1f2e9c3481"


#: cells that later PRs appended to accepted metrics' lists of cells (the one
#: edit of an accepted entry the contract allows): PR 48's, to the train
#: step's and the device's four
JOINED_LATER = ("mellum2_pretrain8k",)


def _as_accepted(entry: dict) -> dict:
    """An accepted entry without the cells that joined its list later, which
    have to stand at the list's end."""
    cells = entry.get("workloads", [])
    kept = [c for c in cells if c not in JOINED_LATER]
    assert cells[:len(kept)] == kept, entry["name"]
    return dict(entry, workloads=kept) if "workloads" in entry else entry


def test_every_new_entry_has_its_file_and_accepted_cells(monkeypatch):  # noqa: F811,E501
    spec = lib.benchmark_spec()
    first = [_as_accepted(m) for m in spec["per_layer"][:ACCEPTED_ENTRIES]]
    assert hashlib.sha256(json.dumps(first, sort_keys=True).encode()
                          ).hexdigest() == ACCEPTED_DIGEST
    assert [m["name"] for m, was in zip(spec["per_layer"], first)
            if m != was] == ["step_ms", "mfu_pct", "device_idle_pct.train",
                             "hbm_peak_gb.train"]
    monkeypatch.setattr(accepted.lib, "benchmark_spec", lambda: dict(
        spec, per_layer=first))
    accepted.test_every_new_entry_has_its_file_and_accepted_cells()
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"][ACCEPTED_ENTRIES:]:
        assert lib.has_module("layer_metrics", m["name"]), m["name"]
        # a cell's own metrics, and PR 39's one more reader of the step rows
        # in the cells of `chunk_dispatch_ms.batch`
        assert set(m["workloads"]) <= cells and (
            len(m["workloads"]) == 1
            or (m["name"], m["workloads"]) == (
                "rows_per_chunk.batch", accepted.BATCH_CELLS))
        assert set(m["workloads"]) <= set(lib.metric_cells(
            lib.find(spec["end_to_end"], m["moves"], "metric"), spec))


def _rows_per_chunk(rec):
    return lib.load_module("layer_metrics", "rows_per_chunk.batch").read(rec)


def test_rows_per_chunk_over_hand_made_rows(program):  # noqa: F811
    """PR 39's reader: the window's ``chunk_rows`` over its ``chunks``; the
    warm-up's program of eight rows, before the window, is left out."""
    rows = accepted._served()
    rows[0]["chunks"], rows[0]["chunk_rows"] = 1, 8
    for r, carried in zip(rows[1:], (5, 0, 0, 0, 1, 1)):    # 2 + 1 + 1 programs
        r["chunk_rows"] = carried
    program(accepted._log(rows))
    assert _rows_per_chunk(accepted.REC) == 7 / 4


@pytest.mark.parametrize("case", ["no_column", "no_chunk", "no_step_logs"])
def test_rows_per_chunk_is_none_where_there_is_nothing_to_read(
        program, monkeypatch, case):  # noqa: F811
    """A program whose step rows have no ``chunk_rows`` column (the parent of
    PR 39), a window that dispatched no chunk, a program without step logs:
    ``None``, never a number and never an exception."""
    if case == "no_step_logs":
        monkeypatch.setattr(accepted.step_log_stats, "_profiler",
                            lambda: None)
    elif case == "no_chunk":
        rows = [dict(r, chunks=0) for r in accepted._served()]
        program(accepted._log(rows))
    else:
        log = accepted._log(accepted._served())
        fields = tuple(f for f in accepted.profiler.ROW_FIELDS
                       if f != "chunk_rows")
        keep = [accepted.profiler.ROW_FIELDS.index(f) for f in fields]
        old = types.SimpleNamespace(rows=lambda: log.rows()[:, keep],
                                    dropped=0)
        monkeypatch.setattr(
            accepted.step_log_stats, "_profiler",
            lambda: types.SimpleNamespace(
                ROW_FIELDS=fields, TILING=accepted.profiler.TILING,
                step_logs=lambda: [old]))
    assert _rows_per_chunk(accepted.REC) is None
