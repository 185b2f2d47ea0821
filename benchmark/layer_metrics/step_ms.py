"""``step_ms``: the window over its steps.  Host clock."""


def read(rec: dict):
    return 1e3 * rec["window_s"] / rec["steps"]
