"""The sweep the chat cell's rate is set from: one process, one set-up, a few
offered rates of the cell's own traffic, each for the same number of seconds.
A rate is **sustained** when every request due is answered and the number of
requests in the system at the end of its rung is no larger than at one third
of it (a queue that grows all through a rung is over capacity).

    python3 benchmark/sweep.py <workload> <seed> <seconds per rate> <rate> [<rate> ...]

Not part of a benchmark run; the result is written into the traffic file."""

from __future__ import annotations

import copy
import json
import os
import sys
import time
import types

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import lib, run, serve_records, traffic_gen  # noqa: E402


def depth_at(records: list, t: float) -> int:
    """Requests sent by ``t`` and not yet finished at ``t``."""
    return sum(1 for r in records if r["sent"] <= t
               and (r["terminal"] is None or r["terminal"] > t))


def main(argv: list) -> int:
    workload, seed, seconds = argv[0], int(argv[1]), float(argv[2])
    rates = [float(r) for r in argv[3:]]
    _, cell, config, mix = run.load_cell(workload)
    run.check_device(int(cell["chips"]))
    run.compile_cache()
    family = lib.load_module("families", config["family"])
    ctx = types.SimpleNamespace(config=config, mix=mix, seed=seed,
                                chips=int(cell["chips"]), say=run.say)
    served = family.build(ctx)
    rng = np.random.default_rng([seed, 1])
    systems = traffic_gen.draw_system_prompts(mix, served.vocab, rng)
    for rate in rates:
        m = copy.deepcopy(mix)
        m["arrivals"]["rate_rps"] = rate
        planned = traffic_gen.plan(m, max(round(rate * seconds), 1),
                                   served.vocab, rng, systems)
        t_base = time.monotonic() + 0.05
        sender = serve_records.Sender(served, planned, t_base)
        sender.start()
        sender.join()
        if sender.error is not None:
            raise sender.error
        records, _ = serve_records.collect(
            served, sender.sent, (t_base, t_base + seconds),
            float(mix["drain_timeout_s"]))
        ok = sum(1 for r in records if r["ok"])
        third = depth_at(records, t_base + seconds / 3.0)
        end = depth_at(records, t_base + seconds)
        ttft = [r["first_token"] - r["due"] for r in records
                if r["ok"] and r["first_token"] is not None]
        drained = max((r["terminal"] or 0.0) for r in records) - t_base
        print("SWEEP", json.dumps({
            "rate_rps": rate, "sent": len(records), "answered": ok,
            "depth_at_third": third, "depth_at_end": end,
            "sustained": bool(ok == len(records) and end <= third),
            "ttft_mean_ms": 1e3 * lib.mean(ttft) if ttft else None,
            "ttft_p90_ms": 1e3 * lib.quantile(ttft, 0.9) if ttft else None,
            "drained_s": drained}), flush=True)
    served.close()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
