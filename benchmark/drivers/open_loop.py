"""Driver ``open_loop``: requests arrive on a schedule whatever the server
does.  A lead-in of the same traffic (not counted, part of set-up) fills the
slots to their steady occupancy; the window's requests are those due in the
``--seconds`` after it; then nothing more arrives and the run waits for
every request sent."""

from __future__ import annotations

import time

import numpy as np

from benchmark import capture, serve_records, traffic_gen


def run(ctx) -> dict:
    mix = ctx.mix
    served = ctx.family.build(ctx)
    ctx.say("engine built and warm")
    rng = np.random.default_rng([ctx.seed, 1])
    rate = float(mix["arrivals"]["rate_rps"])
    lead_s = float(mix["lead_in_s"])
    systems = traffic_gen.draw_system_prompts(mix, served.vocab, rng)
    lead = traffic_gen.plan(mix, max(int(rate * lead_s), 1), served.vocab,
                            rng, systems)          # all due before the window
    win = traffic_gen.plan(mix, max(round(rate * ctx.seconds), 1),
                           served.vocab, rng, systems)
    for p in win:
        p.due += lead_s
    ctx.say(f"schedule: {len(lead)} lead-in + {len(win)} requests at "
            f"{rate} /s, digest {traffic_gen.digest(lead + win)[:16]}")

    t_base = time.monotonic() + 0.05
    window = (t_base + lead_s, t_base + lead_s + ctx.seconds)
    sender = serve_records.Sender(served, lead + win, t_base)
    sender.start()
    trace = capture.WindowTrace(serve_records.spans(ctx)) if ctx.trace else None
    if trace is not None:
        time.sleep(max(window[0] - time.monotonic(), 0.0))
        trace.start()
        time.sleep(float(mix["trace_s"]))
        trace.stop()
    sender.join()
    if sender.error is not None:
        raise sender.error
    records, finished = serve_records.collect(
        served, sender.sent, window, float(mix["drain_timeout_s"]))
    ctx.say(f"all {len(records)} requests answered "
            f"{time.monotonic() - window[1]:.1f} s after the window closed")
    due = [r for r in records if r["in_window"]]
    rec = {"setup_s": window[0] - ctx.t0, "window": window,
           "requests": records, "attempted": len(due),
           "failed": sum(1 for r in due if not r["ok"]),
           "trace": trace.reduce() if trace is not None else None}
    return serve_records.close(ctx, served, rec, finished)
