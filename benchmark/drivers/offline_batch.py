"""Driver ``offline_batch``: a fixed batch handed to the router at once and
drained.  The batch holds ``round(seconds x requests_per_window_second)``
requests, that constant set once on the chip so that the code of that day
drains it in about ``--seconds``; the measured window is the drain itself,
from the first submit to the last completion, so every token of the batch
counts and there are no window edges."""

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
    if trace is not None:
        trace.start()
    t_start = time.monotonic()
    sender = serve_records.Sender(served, batch, t_start)
    sender.start()
    if trace is not None:
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
           "trace": trace.reduce() if trace is not None else None}
    return serve_records.close(ctx, served, rec, finished)
