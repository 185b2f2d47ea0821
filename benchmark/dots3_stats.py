"""The arithmetic of the ``dots3_serve`` family's per-layer metrics, over the
records the serving drivers keep.  A step's stamp is ``llama_serve``'s five
fields and then ``(experts touched by its tick, moe.choices_total,
moe.choices_held, dsa.keys_visible, dsa.keys_selected, load of each held
expert)``, the counters as the engine last read them from the device (it
reads them with a tick's tokens, so they move only in steps that tick).

The trace covers the first seconds of the window and the stamps all of it, so
what is compared with a traced program's time is taken from the stamps of the
same steps: the trace starts with the window, and its ``n`` runs of the tick
program are the window's first ``n`` ticking steps (the last may be cut by
the trace's end and is left out)."""

from __future__ import annotations

from benchmark import lib, serve_stats

CONFIG = "dots3-note-prev.json"
TOUCHED, TOTAL, HELD, VISIBLE, SELECTED, LOAD0 = 5, 6, 7, 8, 9, 10


def _family():
    return lib.load_module("families", "dots3_serve")


def _config() -> dict:
    return lib.load_json("configs", CONFIG)


def _counted(rec: dict) -> list:
    """The stamps that carry this family's counters (none on a program that
    has no such counters)."""
    return [s for s in rec.get("steps", ()) if len(s) > LOAD0]


def window_delta(rec: dict, field: int):
    """How far a running counter moved over the window's steps."""
    lo, hi = rec["window"]
    steps = [s for s in _counted(rec) if lo <= s[1] <= hi]
    return steps[-1][field] - steps[0][field] if len(steps) > 1 else None


def moe_held_share_pct(rec: dict):
    total, held = window_delta(rec, TOTAL), window_delta(rec, HELD)
    return 100.0 * held / total if total else None


def dsa_selected_pct(rec: dict):
    seen, kept = window_delta(rec, VISIBLE), window_delta(rec, SELECTED)
    return 100.0 * kept / seen if seen else None


def moe_load_max_over_mean(rec: dict):
    """The busiest held expert's token-choices over the held experts' mean,
    over the window."""
    lo, hi = rec["window"]
    steps = [s for s in _counted(rec) if lo <= s[1] <= hi]
    if len(steps) < 2:
        return None
    load = [b - a for a, b in zip(steps[0][LOAD0:], steps[-1][LOAD0:])]
    return max(load) / lib.mean(load) if sum(load) else None


def traced_ticks(rec: dict) -> list:
    """The stamps of the window's ticking steps whose tick ran whole inside
    the trace, in order."""
    p = serve_stats._program(rec, "_tick")
    if p is None:
        return []
    lo, _ = rec["window"]
    ticking = [s for s in _counted(rec) if s[1] >= lo and s[2] > 0]
    return ticking[:max(p["count"] - 1, 0)]


def _context_at(rec: dict, t: float) -> float:
    """The positions the rows decoding at host time ``t`` attend to, summed:
    a request holds its prompt plus the tokens it has so far."""
    total = 0.0
    for r in rec["requests"]:
        if not r["ok"] or r["first_token"] is None or r["terminal"] is None:
            continue
        if r["first_token"] <= t <= r["terminal"]:
            span = max(r["terminal"] - r["first_token"], 1e-9)
            total += r["prompt_len"] + r["n_out"] * (t - r["first_token"]) / span
    return total


def tick_roofline_pct(rec: dict):
    """The least time the traced ticks could take on this chip (their bytes
    over the memory's peak rate; a tick of a few rows is bound by bytes) over
    the tick program's device time."""
    ticks = traced_ticks(rec)
    ms = serve_stats.program_ms(rec, "_tick")
    if not ticks or ms is None:
        return None
    fam, cfg = _family(), _config()
    nbytes = lib.mean(
        fam.tick_bytes(cfg, rows=b[2], live_tokens=_context_at(rec, b[1]),
                       experts_touched=b[TOUCHED]) for b in ticks)
    peak = lib.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return lib.share_of_peak(nbytes / (ms / 1e3), peak, "tick_roofline.dots3")


def chunk_mfu_pct(rec: dict):
    """The operations of the chunk programs that ran between the first and
    the last whole tick of the trace (what the counters gained there, less the
    ticks' own part) over those programs' device time, as a share of the
    chip's peak."""
    ticks = traced_ticks(rec)
    p = serve_stats._program(rec, "_chunk")
    t = serve_stats._program(rec, "_tick")
    if len(ticks) < 2 or p is None or t is None:
        return None
    fam, cfg = _family(), _config()
    sizes = fam._sizes(cfg)
    first, last = ticks[0], ticks[-1]
    gained = {f: last[f] - first[f] for f in (TOTAL, HELD, VISIBLE, SELECTED)}
    between = ticks[1:]
    rows = sum(b[2] for b in between)
    contexts = [_context_at(rec, b[1]) for b in between]
    per_token = sizes["k"] * (sizes["n"] - sizes["dense"])
    tokens = gained[TOTAL] / per_token - rows
    share = gained[HELD] / gained[TOTAL] if gained[TOTAL] else 0.0
    flops = fam.chunk_flops(
        cfg, tokens=tokens,
        keys_visible=gained[VISIBLE] - sum(contexts) * sizes["full"],
        keys_selected=gained[SELECTED] - sizes["full"] * sum(
            min(c / max(b[2], 1), sizes["topk"]) * b[2]
            for c, b in zip(contexts, between)),
        choices_held=share * tokens * per_token)
    # the chunk runs between the end of the first whole tick and the end of
    # the last, on the trace's clock
    t_runs = sorted(t["runs"])
    start, end = t_runs[0][1], t_runs[len(ticks) - 1][1]
    secs = sum(b - a for a, b in p["runs"] if a >= start and b <= end) / 1e9
    if tokens <= 0 or secs <= 0:
        return None
    peak = lib.peaks(rec["device_kind"])["bf16_flops_per_s"]
    return lib.share_of_peak(flops / secs, peak, "chunk_mfu_pct.dots3")
