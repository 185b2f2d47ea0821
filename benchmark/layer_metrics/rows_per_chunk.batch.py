"""``rows_per_chunk.batch``: the rows the window's prefill chunk programs
carried over those programs, by the step rows (``chunk_rows`` over ``chunks``):
how many rows shared one read of the weights.  1.0 is one row a program.
``None`` on a program whose step rows have no ``chunk_rows`` column (every
commit before PR 39), and where the window dispatched no chunk."""

from benchmark import step_log_stats


def read(rec: dict):
    rows = step_log_stats.window_rows(rec)
    if (rows is None or "chunk_rows" not in rows.fields
            or not rows["chunks"].sum()):
        return None
    return float(rows["chunk_rows"].sum() / rows["chunks"].sum())
