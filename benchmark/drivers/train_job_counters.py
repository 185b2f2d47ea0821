"""Driver ``train_job_counters``: ``train_job``'s loop and window (the same
code: its ``_loop`` and ``SPANS``), for a family whose step counts while it
computes.  The record gains ``counters``, the job's own totals over the
window's steps, read from the device once after the drain, and, in a traced
run, ``traced_counters`` (the same totals over the traced steps: the rows a
traced kernel call had are theirs, not the window's) and ``kernels``: the
count and summed device seconds of every traced
operation whose name holds one of the family's ``KERNELS`` (kept as
``kernel_names``), with ``kernel_ops``, what one call of each computes,
counted from the shapes by the family."""

from __future__ import annotations

import math
import time

from benchmark import capture, lib

_train_job = lib.load_module("drivers", "train_job")
SPANS = _train_job.SPANS
_loop = _train_job._loop


def kernel_times(reduced: dict, names: list) -> dict:
    """``{kernel: {"count", "total_s"}}`` of a reduced trace: an operation
    belongs to the longest of ``names`` that its own name holds."""
    out = {n: {"count": 0, "total_s": 0.0} for n in names}
    for op, intervals in reduced["op_intervals"].items():
        held = [n for n in names if n in op]
        if held:
            k = out[max(held, key=len)]
            k["count"] += len(intervals)
            k["total_s"] += sum(b - a for a, b in intervals) / 1e9
    return out


def run(ctx) -> dict:
    mix = ctx.mix
    job = ctx.family.build(ctx)
    ctx.say("job built")
    readings = job.first_steps(int(mix["check_steps"]))
    ctx.say(f"first steps: losses {readings['losses']}")
    depth = int(mix["in_flight"])
    for _ in range(int(mix["warm_steps"])):
        job.step().block_until_ready()
    reduced = kernels = traced_counters = None
    if ctx.trace:
        job.take_counters()             # the traced steps' own, from zero
        trace = capture.WindowTrace(SPANS)
        trace.start()
        _loop(job, float(mix["trace_s"]), depth)
        trace.stop()
        reduced = trace.reduce()
        traced_counters = job.take_counters()
        kernels = kernel_times(reduced, [
            n for names in ctx.family.KERNELS.values() for n in names])
        ctx.say("trace taken and reduced")
    job.take_counters()                 # the window's totals start at zero

    setup_s = time.monotonic() - ctx.t0
    steps, window_s, loss = _loop(job, ctx.seconds, depth)
    last = float(loss)
    counters = job.take_counters()
    ctx.say(f"window: {steps} steps in {window_s:.3f} s, last loss {last}")

    rec = {"setup_s": setup_s, "window_s": window_s, "steps": steps,
           "items": steps * job.items_per_step, "chips": ctx.chips,
           "attempted": steps, "failed": 0 if math.isfinite(last) else steps,
           "memory_peak_bytes": ctx.memory_peak_bytes() + job.scratch_bytes,
           "flops_per_item": ctx.family.flops_per_item(ctx.config),
           "counters": counters, "traced_counters": traced_counters,
           "kernels": kernels,
           "kernel_names": ctx.family.KERNELS,
           "kernel_ops": ctx.family.kernel_ops(ctx.config, mix),
           "trace": reduced}
    extra = job.signatures() - 1
    job.free()
    t0 = time.monotonic()
    rec["checks"] = ctx.family.check(ctx, readings)
    rec["checks"].append({"name": "programs_compiled_in_window",
                          "value": extra, "limit": 0, "ok": extra == 0})
    ctx.say(f"reference and comparison took {time.monotonic() - t0:.1f} s")
    return rec
