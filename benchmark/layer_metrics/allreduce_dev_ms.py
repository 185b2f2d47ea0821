"""``allreduce_dev_ms``: device time of the gradient exchange per step on the
first chip — the union of the intervals of every collective operation
(``all-reduce``, or the ``reduce-scatter`` and ``all-gather`` XLA may make
of it; asynchronous ones from start to done) over the runs of the train-step
program in the traced window."""

from benchmark import trace_reduce

PREFIXES = ("all-reduce", "reduce-scatter", "all-gather",
            "collective-permute", "all-to-all")


def read(rec: dict):
    tr = rec.get("trace")
    if not tr or tr["n_devices"] < 2 or not tr["programs"]:
        return None
    spans = [iv for op, ivs in tr["op_intervals"].items()
             if op.startswith(PREFIXES) for iv in ivs]
    if not spans:
        return None
    step = max(tr["programs"].values(), key=lambda p: p["total_s"])
    return 1e3 * trace_reduce.time_in(spans, step["runs"]) / step["count"]
