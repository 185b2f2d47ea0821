"""The arithmetic of the serving metrics, over the records the serving
drivers keep (``serve_records``): per request stamps, per engine step stamps
``(began, returned, rows decoded, rows prefilling, requests finished)``, and
the reduced trace.  Each metric's own file names the function it reads."""

from __future__ import annotations

from benchmark import lib


def _due_ok(rec: dict) -> list:
    return [r for r in rec["requests"]
            if r["in_window"] and r["ok"] and r["first_token"] is not None]


def ttft_ms(rec: dict) -> list:
    """First token minus the time the request was due (not sent), for every
    request due in the window that was answered."""
    return [1e3 * (r["first_token"] - r["due"]) for r in _due_ok(rec)]


def token_gaps_ms(rec: dict) -> list:
    """The gap between tokens: for every step that returns in the window and
    starts with at least one row decoding (rows decoded in the step before,
    less the requests it finished), the time from that step's return to this
    one's.  Every decoding row gets one token a step."""
    lo, hi = rec["window"]
    steps = rec["steps"]
    gaps = []
    for prev, cur in zip(steps, steps[1:]):
        if prev[2] - prev[4] > 0 and lo <= cur[1] <= hi:
            gaps.append(1e3 * (cur[1] - prev[1]))
    return gaps


def tail_mean(values: list, share: float) -> float:
    xs = sorted(values)
    k = max(int(round(len(xs) * share)), 1)
    return lib.mean(xs[-k:])


def tokens_per_s(rec: dict) -> float:
    """Prompt tokens plus output tokens of the whole batch over the time from
    the first submit to the last completion."""
    lo, hi = rec["window"]
    toks = sum(r["prompt_len"] + r["n_out"] for r in rec["requests"]
               if r["ok"])
    return toks / (hi - lo)


def gen_late_ms(rec: dict) -> list:
    return [1e3 * (r["sent"] - r["due"]) for r in rec["requests"]
            if r["in_window"]]


def route_ms(rec: dict) -> list:
    """``route()`` received the request -> the engine enqueued it."""
    return [1e3 * (r["enqueue"] - r["recv"]) for r in rec["requests"]
            if r["in_window"] and r["enqueue"] is not None
            and r["recv"] is not None]


def prefix_skip_pct(rec: dict) -> float | None:
    reqs = [r for r in rec["requests"] if r["in_window"] and r["ok"]]
    if not reqs:
        return None
    return 100.0 * sum(r["prefix_skipped"] for r in reqs) / sum(
        r["prompt_len"] for r in reqs)


def _window_steps(rec: dict) -> list:
    lo, hi = rec["window"]
    return [s for s in rec["steps"] if lo <= s[1] <= hi]


def rows_per_tick(rec: dict) -> float | None:
    ticks = [s[2] for s in _window_steps(rec) if s[2] > 0]
    return lib.mean(ticks) if ticks else None


def _program(rec: dict, suffix: str) -> dict | None:
    tr = rec.get("trace")
    if not tr:
        return None
    for name, p in tr["programs"].items():
        if name.endswith(suffix) and p["count"]:
            return p
    return None


def program_ms(rec: dict, suffix: str) -> float | None:
    """Mean device time of one run of the program, from the trace."""
    p = _program(rec, suffix)
    return 1e3 * p["total_s"] / p["count"] if p else None


def sched_host_ms(rec: dict) -> float | None:
    """Per engine step in the traced window: the step's wall time (the
    ``engine.step`` spans) less the device time of the programs it ran."""
    tr = rec.get("trace")
    if not tr or not tr.get("span_totals", {}).get("engine.step"):
        return None
    n, wall = tr["span_totals"]["engine.step"]
    dev = sum(p["total_s"] for p in tr["programs"].values())
    return 1e3 * (wall - dev) / n


def live_tokens_per_tick(rec: dict) -> float | None:
    """Mean over the window's decode ticks of the positions the decoding
    rows attend to: a request decoding from its first token to its last
    holds its prompt plus the tokens it has so far (half of them on
    average), and takes part in one tick per token."""
    lo, hi = rec["window"]
    n_ticks = sum(1 for s in _window_steps(rec) if s[2] > 0)
    if not n_ticks:
        return None
    total = 0.0
    for r in rec["requests"]:
        if not r["ok"] or r["first_token"] is None or r["terminal"] is None:
            continue
        span = max(r["terminal"] - r["first_token"], 1e-9)
        inside = max(min(r["terminal"], hi) - max(r["first_token"], lo), 0.0)
        total += r["n_out"] * (inside / span) * (
            r["prompt_len"] + 0.5 * r["n_out"])
    return total / n_ticks


def tick_roofline_pct(rec: dict) -> float | None:
    """The least time a decode tick could take on this chip (its bytes —
    the weights once and the live keys and values — over the memory's peak
    rate; a tick of a few rows is bound by bytes, not operations) over the
    tick program's device time."""
    ms = program_ms(rec, "_tick")
    live = live_tokens_per_tick(rec)
    if ms is None or live is None:
        return None
    nbytes = rec["weight_bytes"] + live * rec["kv_bytes_per_token"]
    peak = lib.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return lib.share_of_peak(nbytes / (ms / 1e3), peak, "tick_roofline")

