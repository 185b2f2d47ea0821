"""What the two serving drivers share: sending planned requests on their
schedule from one thread, collecting results and traces, and closing the
run with the check.  One record per request::

    {"due": s, "sent": s, "in_window": bool, "prompt_len": n, "n_out": n,
     "own_len": n, "ok": bool, "first_token": s | None, "terminal": s | None,
     "enqueue": s | None, "admit": s | None, "recv": s | None,
     "prefill_chunks": n, "prefix_skipped": n}

All times are ``time.monotonic`` seconds of this process, the clock the
program's own ``Trace`` stamps use.
"""

from __future__ import annotations

import threading
import time


class Sender(threading.Thread):
    """Sends each planned request when it is due (``t_base + due``), never
    early; how late it ran is in the records (``sent - due``)."""

    def __init__(self, served, planned: list, t_base: float):
        super().__init__(name="bench-loadgen", daemon=True)
        self.served, self.planned, self.t_base = served, planned, t_base
        self.sent: list = []          # (planned, due_abs, sent_ts, rid)
        self.error: BaseException | None = None

    def run(self) -> None:
        try:
            for p in self.planned:
                due = self.t_base + p.due
                wait = due - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                t = time.monotonic()
                rid = self.served.route(p.prompt, p.n_out)
                self.sent.append((p, due, t, rid))
        except BaseException as e:      # reported by the driver's thread
            self.error = e


def spans(ctx) -> tuple:
    """The host spans the family puts on the trace's clock."""
    return tuple(getattr(ctx.family, "SPANS", ()))


def collect(served, sent: list, window: tuple, timeout_s: float) -> tuple:
    """Wait for every request sent; returns the records and, for the check,
    the finished requests ``(prompt, tokens)`` that were due in the window."""
    records, finished = [], []
    deadline = time.monotonic() + timeout_s
    for p, due, t_sent, rid in sent:
        res, tr = served.collect(rid, max(deadline - time.monotonic(), 0.0))
        tr = tr or {}
        router = tr.get("router") or {}
        in_window = window[0] <= due < window[1]
        ok = (res is not None and res.status == "OK"
              and len(res) == p.n_out)
        records.append({
            "due": due, "sent": t_sent, "in_window": in_window,
            "prompt_len": len(p.prompt), "n_out": p.n_out,
            "own_len": p.own_len, "ok": ok,
            "first_token": tr.get("first_token_ts"),
            "terminal": tr.get("terminal_ts"),
            "enqueue": tr.get("enqueue_ts"), "admit": tr.get("admit_ts"),
            "recv": router.get("recv_ts"),
            "prefill_chunks": tr.get("prefill_chunks", 0),
            "prefix_skipped": tr.get("prefix_tokens_skipped", 0)})
        if ok and in_window:
            finished.append((p.prompt, list(res)))
    return records, finished


def close(ctx, served, rec: dict, finished: list) -> dict:
    """Read the peak, check that nothing compiled in the window, free the
    engine, and only then run the reference."""
    rec["memory_peak_bytes"] = ctx.memory_peak_bytes()
    counts = served.compile_counts()
    rec["compiled_in_window"] = sum(max(v - 1, 0) for v in counts.values())
    rec["steps"] = list(served.steps)
    rec["weight_bytes"] = ctx.family.weight_bytes(ctx.config)
    rec["kv_bytes_per_token"] = ctx.family.kv_bytes_per_token(ctx.config)
    served.close()
    t0 = time.monotonic()
    rec["checks"] = ctx.family.check(ctx, finished)
    rec["checks"].append({"name": "programs_compiled_in_window",
                          "value": rec["compiled_in_window"], "limit": 0,
                          "ok": rec["compiled_in_window"] == 0})
    ctx.say(f"reference and comparison took {time.monotonic() - t0:.1f} s")
    return rec
