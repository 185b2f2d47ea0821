"""Driver ``offline_batch_late_trace``: ``offline_batch`` (a fixed batch
handed to the router at once and drained; the measured window is the drain
itself, from the first submit to the last completion), with the traced slice
placed ``trace_after_s`` seconds into the window and not at its start.

A cell whose first wave fills every slot prefills for longer than a trace may
last before its first tick runs (every prefilling row gets a chunk a step: 64
chunks a step until the shortest prompts are in), so a trace that starts with
the window holds no tick at all, and one long enough to reach them fills the
profiler's buffer.  The traced slice here starts once the first wave decodes:
ticks at full width beside the chunks of later admissions.  An untraced run is
``offline_batch``'s, call for call.  ``rec["trace_started"]`` is when the
trace began, on the clock of the steps' stamps, for the readers that pair a
traced program's runs with the steps that dispatched them."""

from __future__ import annotations

import time

import numpy as np

from benchmark import capture, serve_records, traffic_gen


def run(ctx) -> dict:
    mix = ctx.mix
    served = ctx.family.build(ctx)
    ctx.say("engine built and warm")
    rng = np.random.default_rng([ctx.seed, 1])
    n = max(round(ctx.seconds * float(mix["requests_per_window_second"])), 1)
    systems = traffic_gen.draw_system_prompts(mix, served.vocab, rng)
    batch = traffic_gen.plan(mix, n, served.vocab, rng, systems, timed=False)
    ctx.say(f"batch: {n} requests, {traffic_gen.token_count(batch)} tokens, "
            f"digest {traffic_gen.digest(batch)[:16]}")

    trace = (capture.WindowTrace(serve_records.spans(ctx))
             if ctx.trace else None)
    t_start = time.monotonic()
    sender = serve_records.Sender(served, batch, t_start)
    sender.start()
    trace_started = None
    if trace is not None:
        time.sleep(float(mix["trace_after_s"]))
        trace_started = time.monotonic()
        trace.start()
        time.sleep(float(mix["trace_s"]))
        trace.stop()
    sender.join()
    if sender.error is not None:
        raise sender.error
    window = (t_start, float("inf"))
    records, finished = serve_records.collect(
        served, sender.sent, window, float(mix["drain_timeout_s"]))
    ends = [r["terminal"] for r in records if r["terminal"] is not None]
    t_end = max(ends) if ends else time.monotonic()
    ctx.say(f"the batch drained in {t_end - t_start:.3f} s")
    rec = {"setup_s": t_start - ctx.t0, "window": (t_start, t_end),
           "requests": records, "attempted": len(records),
           "failed": sum(1 for r in records if not r["ok"]),
           "trace_started": trace_started,
           "trace": trace.reduce() if trace is not None else None}
    return serve_records.close(ctx, served, rec, finished)
