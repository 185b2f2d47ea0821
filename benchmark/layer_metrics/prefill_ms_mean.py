"""``prefill_ms_mean``: a slot admitted the request -> its first token
(``Trace.admit_ts`` -> ``Trace.first_token_ts``), mean over the same requests
as ``queue_wait_ms_mean``: the prefill chunks and the first decode tick, with
the ticks of the other rows in between."""

from benchmark import lib

stamped = lib.load_module("layer_metrics", "queue_wait_ms_mean").stamped


def read(rec: dict):
    reqs = stamped(rec)
    if not reqs:
        return None
    return lib.mean(1e3 * (r["first_token"] - r["admit"]) for r in reqs)
