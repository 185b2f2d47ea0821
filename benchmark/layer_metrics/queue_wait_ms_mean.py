"""``queue_wait_ms_mean``: the engine enqueued the request -> a slot admitted
it (``Trace.enqueue_ts`` -> ``Trace.admit_ts``, the program's own stamps),
mean over the window's answered requests: the part of the time to the first
token spent waiting in the scheduler's queue."""

from benchmark import lib


def stamped(rec: dict) -> list:
    """The window's answered requests that carry all three stamps."""
    return [r for r in rec["requests"]
            if r["in_window"] and r["ok"] and None not in (
                r["enqueue"], r["admit"], r["first_token"])]


def read(rec: dict):
    reqs = stamped(rec)
    if not reqs:
        return None
    return lib.mean(1e3 * (r["admit"] - r["enqueue"]) for r in reqs)
