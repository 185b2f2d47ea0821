"""The arithmetic of the metrics read from the program's own step log: one
row a ``ServeEngine.step`` (``horovod_tpu.profiler.ROW_FIELDS``: when the step
began and ended on ``time.monotonic``, the clock of ``rec["window"]``, what
each phase of it took, what it dispatched and emitted), kept by every engine
and reached through ``profiler.step_logs()``, which outlives the engine.

The rows are those of the whole measured window, in a traced run and in an
untraced one alike (the traced slice is 3-60 s of it).  The model's counters
that a row carries (``profiler.CARRIED``) stand there as they stood at the
step's end, and a reader takes their change over the window's rows: warm-up,
lead-in and probes are left out, as from every other metric here.

Every reader returns ``None``, never a number, on a program without
``profiler.step_logs`` (every commit before PR 35), when no log has a row in
the window, and when the log overwrote rows that may have lain in it.
"""

from __future__ import annotations

import numpy as np


def _profiler():
    try:
        from horovod_tpu import profiler
    except ImportError:
        return None
    return profiler if hasattr(profiler, "step_logs") else None


class Rows:
    """Some rows of one log, a column by its ``ROW_FIELDS`` name; ``tiling``
    names the phases that tile a step, ``before`` is the row that ended
    before the first of these began (zeros where that is the engine's
    first)."""

    def __init__(self, fields: tuple, tiling: tuple, rows: np.ndarray,
                 before: np.ndarray):
        self.fields, self.tiling = tuple(fields), tuple(tiling)
        self.rows, self.before = rows, before

    def __len__(self) -> int:
        return len(self.rows)

    def __getitem__(self, name: str) -> np.ndarray:
        return self.rows[:, self.fields.index(name)]

    def where(self, keep: np.ndarray) -> "Rows":
        return Rows(self.fields, self.tiling, self.rows[keep], self.before)

    def grew(self, name: str) -> float:
        """By how much the cumulative column ``name`` grew over these rows."""
        col = self.fields.index(name)
        return float(self.rows[-1, col] - self.before[col])

    def ticking(self) -> "Rows":
        """The rows of the steps that ran a decode tick."""
        return self.where(self["tick_rows"] > 0)


def window_rows(rec: dict) -> Rows | None:
    """The rows that began inside ``rec["window"]``, of the log that has the
    most of them."""
    prof = _profiler()
    if prof is None:
        return None
    lo, hi = rec["window"]
    began = prof.ROW_FIELDS.index("began")
    best, best_wrapped = None, False
    for log in prof.step_logs():
        rows = log.rows()
        inside = np.flatnonzero(
            (rows[:, began] >= lo) & (rows[:, began] <= hi))
        if len(inside) > (0 if best is None else len(best)):
            first = inside[0]
            before = rows[first - 1] if first else np.zeros(rows.shape[1])
            best = Rows(prof.ROW_FIELDS, prof.TILING, rows[inside], before)
            # the rows a log overwrote lay before the oldest it kept: inside
            # the window if that one is
            best_wrapped = log.dropped > 0 and rows[0, began] >= lo
    return None if best_wrapped else best


def _ticking(rec: dict) -> Rows | None:
    """The window's ticking rows, or ``None`` where there is none."""
    rows = window_rows(rec)
    ticking = None if rows is None else rows.ticking()
    return ticking if ticking is not None and len(ticking) else None


def step_host_ms(rec: dict):
    """Mean over the window's ticking steps of the step's phases other than
    ``device_sync``: the host's own time a step, from inside."""
    t = _ticking(rec)
    if t is None:
        return None
    host = sum(t[p] for p in t.tiling if p != "device_sync")
    return 1e3 * float(np.mean(host))


def step_sync_wait_ms(rec: dict):
    """Mean ``device_sync`` a ticking step: how long the host waited for the
    device's tokens.  Near 0 means the host sets the pace."""
    t = _ticking(rec)
    return None if t is None else 1e3 * float(np.mean(t["device_sync"]))


def between_steps_ms(rec: dict):
    """Mean of the next row's ``began`` less this row's ``ended`` over the
    pairs of consecutive rows that both tick: the pump's sections between two
    steps, as the engine sees them (an idle engine's wait is in no pair)."""
    rows = window_rows(rec)
    if rows is None or len(rows) < 2:
        return None
    ticks = rows["tick_rows"] > 0
    pair = ticks[:-1] & ticks[1:]
    if not pair.any():
        return None
    gaps = rows["began"][1:] - rows["ended"][:-1]
    return 1e3 * float(np.mean(gaps[pair]))


def chunk_dispatch_ms(rec: dict):
    """Host time to hand one chunk program over: the window's
    ``admit.prefill_dispatch`` seconds over its chunks.  A dispatch that
    blocks shows here."""
    rows = window_rows(rec)
    if rows is None or not rows["chunks"].sum():
        return None
    return 1e3 * float(rows["admit.prefill_dispatch"].sum()
                       / rows["chunks"].sum())


def _longest(rec: dict):
    rows = window_rows(rec)
    if rows is None or not len(rows):
        return None
    wall = rows["ended"] - rows["began"]
    i = int(np.argmax(wall))
    return float(wall[i]), float(rows["device_sync"][i])


def step_longest_ms(rec: dict):
    """The longest step of the window: a stalled run names itself."""
    found = _longest(rec)
    return None if found is None else 1e3 * found[0]


def step_longest_sync_pct(rec: dict):
    """The share of the window's longest step inside ``device_sync``: whether
    the wait for the device or the host's own code held it."""
    found = _longest(rec)
    return None if found is None else 100.0 * found[1] / found[0]


def itl_p90_emit_ms(rec: dict):
    """90th percentile, pooled over the window's tokens other than first
    tokens, of the gap between tokens: from the moment the emitting step
    before handed its tokens out (the start of its ``bookkeeping``) to the
    moment this one did, over the tokens a row this step gave.  One token a
    row a step reads as ``itl_p90_ms`` weighted by rows; K tokens a row read
    as K gaps of a K-th each."""
    rows = window_rows(rec)
    if rows is None:
        return None
    e = rows.where(rows["tokens"] > 0)
    if len(e) < 2:
        return None
    out = e["ended"] - e["bookkeeping"]
    per_row = (e["tokens"] / e["tick_rows"])[1:]
    later = (e["tokens"] - e["first_tokens"])[1:].astype(np.int64)
    if not later.sum():
        return None
    gaps = (out[1:] - out[:-1]) / per_row
    return 1e3 * float(np.quantile(np.repeat(gaps, later), 0.9))


def _counter_ratio(rec: dict, num: str, den: str):
    """The growth of the carried counter ``num`` over that of ``den``, over
    the window's rows; ``None`` where ``den`` did not grow (the engine's model
    keeps no such counter)."""
    rows = window_rows(rec)
    if rows is None or not {num, den} <= set(rows.fields):
        return None
    return rows.grew(num) / rows.grew(den) if rows.grew(den) else None


def attn_walk_over_live(rec: dict):
    """``attn.blocks_visited / attn.blocks_live`` over the window's steps:
    the table entries the paged attention walk read over those the read rows'
    own positions span.  1.0 is a walk that reads what the live rows hold and
    nothing else: whole tiles, a group's bound and the one tile of a slot
    that does not decode all count above it."""
    return _counter_ratio(rec, "attn.blocks_visited", "attn.blocks_live")


def dsa_mask_query_pct(rec: dict):
    """``dsa.mask_queries / dsa.queries`` over the window's steps: the share
    of the full layers' queries whose selection was kept as a mask."""
    ratio = _counter_ratio(rec, "dsa.mask_queries", "dsa.queries")
    return None if ratio is None else 100.0 * ratio
