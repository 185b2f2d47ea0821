"""Driver ``train_job``: a training job's steady state.  Set-up builds the
family's job (the compiled step with its state), drives it through the first
steps that the check compares, and hands the same object to the window; the
window dispatches steps for ``--seconds`` with a bounded number in flight
and no host read, and ends by waiting for the last step's outputs.

A traced run first traces ``trace_s`` seconds of the same loop (the device's
busy and idle time, the operations, the gaps), stops the profiler, and then
measures its window untraced, so that its host-clock numbers are not the
profiler's."""

from __future__ import annotations

import collections
import math
import time

from benchmark import capture

SPANS = ("train.dispatch", "train.wait", "train.drain")


def _loop(job, seconds: float, depth: int) -> tuple:
    """Dispatch steps for ``seconds``, at most ``depth`` in flight, then wait
    for the last one.  Returns (steps, seconds taken, last loss)."""
    pending: collections.deque = collections.deque()
    steps = 0
    t_start = time.monotonic()
    while True:
        with capture.span("train.dispatch"):
            loss = job.step()
        pending.append(loss)
        steps += 1
        if len(pending) > depth:
            with capture.span("train.wait"):
                pending.popleft().block_until_ready()
        if time.monotonic() - t_start >= seconds:
            break
    with capture.span("train.drain"):
        loss.block_until_ready()
    return steps, time.monotonic() - t_start, loss


def run(ctx) -> dict:
    mix = ctx.mix
    job = ctx.family.build(ctx)
    ctx.say("job built")
    readings = job.first_steps(int(mix["check_steps"]))
    ctx.say(f"first steps: losses {readings['losses']}")
    depth = int(mix["in_flight"])
    for _ in range(int(mix["warm_steps"])):
        job.step().block_until_ready()
    reduced = None
    if ctx.trace:
        trace = capture.WindowTrace(SPANS)
        trace.start()
        _loop(job, float(mix["trace_s"]), depth)
        trace.stop()
        reduced = trace.reduce()
        ctx.say("trace taken and reduced")

    setup_s = time.monotonic() - ctx.t0
    steps, window_s, loss = _loop(job, ctx.seconds, depth)
    last = float(loss)
    ctx.say(f"window: {steps} steps in {window_s:.3f} s, last loss {last}")

    rec = {"setup_s": setup_s, "window_s": window_s, "steps": steps,
           "items": steps * job.items_per_step, "chips": ctx.chips,
           "attempted": steps, "failed": 0 if math.isfinite(last) else steps,
           "memory_peak_bytes": ctx.memory_peak_bytes() + job.scratch_bytes,
           "flops_per_item": ctx.family.flops_per_item(ctx.config),
           "trace": reduced}
    extra = job.signatures() - 1
    job.free()
    t0 = time.monotonic()
    rec["checks"] = ctx.family.check(ctx, readings)
    rec["checks"].append({"name": "programs_compiled_in_window",
                          "value": extra, "limit": 0, "ok": extra == 0})
    ctx.say(f"reference and comparison took {time.monotonic() - t0:.1f} s")
    return rec
