"""The arithmetic of the ``kexaone_serve`` family's per-layer metrics, over the
records the serving drivers keep.  A step's stamp is ``llama_serve``'s five
fields and then ``(experts touched by its tick, moe.choices_total,
moe.choices_held, window.state_restores, attn.keys_visible, load of each held
expert, kv.tokens_live, kv.full_bytes_live, kv.window_bytes_live)``:
``dots3_serve``'s layout up to the loads, so what ``dots3_stats`` reads of a
stamp by position (the stamps that carry counters, a counter's gain over the
window, the share of the choices held, the traced ticks, the rows' contexts)
is read here by the same code; the three gauges stand last.  On a program
whose stamps carry no such counters every reader returns ``None``."""

from __future__ import annotations

from benchmark import dots3_stats, lib, serve_stats

CONFIG = "k-exaone-236b-a23b.json"
TOUCHED, TOTAL, HELD, RESTORES, VISIBLE, LOAD0 = 5, 6, 7, 8, 9, 10
TOKENS_LIVE, FULL_LIVE, WINDOW_LIVE = -3, -2, -1
assert (LOAD0, TOUCHED, TOTAL, HELD) == (
    dots3_stats.LOAD0, dots3_stats.TOUCHED, dots3_stats.TOTAL,
    dots3_stats.HELD)

moe_held_share_pct = dots3_stats.moe_held_share_pct
traced_ticks = dots3_stats.traced_ticks


def _family():
    return lib.load_module("families", "kexaone_serve")


def _config() -> dict:
    return lib.load_json("configs", CONFIG)


def _window_steps(rec: dict) -> list:
    lo, hi = rec["window"]
    return [s for s in dots3_stats._counted(rec) if lo <= s[1] <= hi]


def moe_load_max_over_mean(rec: dict):
    """The busiest held expert's token-choices over the held experts' mean,
    over the window."""
    steps = _window_steps(rec)
    if len(steps) < 2:
        return None
    load = [b - a for a, b in zip(steps[0][LOAD0:TOKENS_LIVE],
                                  steps[-1][LOAD0:TOKENS_LIVE])]
    return max(load) / lib.mean(load) if sum(load) else None


def kv_bytes_per_live_token(rec: dict):
    """Over the window's ticking steps, the bytes the live rows hold (the
    pools' blocks their tables map, their rings, those blocks' snapshots)
    over the positions they hold: every layer paged would be the sum of both
    kinds' bytes a position, 32,768, and more for the blocks' unfilled
    ends."""
    ticks = [s for s in _window_steps(rec) if s[2] > 0 and s[TOKENS_LIVE] > 0]
    if not ticks:
        return None
    return (sum(s[FULL_LIVE] + s[WINDOW_LIVE] for s in ticks)
            / sum(s[TOKENS_LIVE] for s in ticks))


def tick_roofline_pct(rec: dict):
    """The least time the traced ticks could take on this chip (their bytes
    over the memory's peak rate; a tick of 64 rows does 4 rows an expert and
    is bound by bytes) over the tick program's device time."""
    ticks = traced_ticks(rec)
    ms = serve_stats.program_ms(rec, "_tick")
    if not ticks or ms is None:
        return None
    fam, cfg = _family(), _config()
    nbytes = lib.mean(
        fam.tick_bytes(cfg, rows=b[2],
                       live_tokens=dots3_stats._context_at(rec, b[1]),
                       experts_touched=b[TOUCHED]) for b in ticks)
    peak = lib.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return lib.share_of_peak(nbytes / (ms / 1e3), peak,
                             "tick_roofline.kexaone")


def chunk_mfu_pct(rec: dict):
    """The operations of the chunk programs that ran between the first and
    the last whole tick of the trace (what the counters gained there, less
    the ticks' own part) over those programs' device time, as a share of the
    chip's peak."""
    ticks = traced_ticks(rec)
    p = serve_stats._program(rec, "_chunk")
    t = serve_stats._program(rec, "_tick")
    if len(ticks) < 2 or p is None or t is None:
        return None
    fam, cfg = _family(), _config()
    sizes = fam._sizes(cfg)
    first, last, between = ticks[0], ticks[-1], ticks[1:]
    gained = {f: last[f] - first[f] for f in (TOTAL, HELD, VISIBLE)}
    per_token = sizes["k"] * (sizes["n"] - sizes["dense"])
    rows = sum(b[2] for b in between)
    tokens = gained[TOTAL] / per_token - rows
    # a decoding row's query sees its context in a full layer and the window
    # (its context, while that is shorter) in a sliding one
    ticks_saw = sum(
        sizes["full"] * c + sizes["sliding"] * min(c, b[2] * sizes["window"])
        for c, b in ((dots3_stats._context_at(rec, b[1]), b)
                     for b in between))
    share = gained[HELD] / gained[TOTAL] if gained[TOTAL] else 0.0
    # the chunks run between the end of the first whole tick and the end of
    # the last, on the trace's clock
    t_runs = sorted(t["runs"])
    start, end = t_runs[0][1], t_runs[len(ticks) - 1][1]
    secs = sum(b - a for a, b in p["runs"] if a >= start and b <= end) / 1e9
    if tokens <= 0 or secs <= 0:
        return None
    flops = fam.chunk_flops(
        cfg, tokens=tokens, keys_visible=max(gained[VISIBLE] - ticks_saw, 0.0),
        choices_held=share * tokens * per_token)
    peak = lib.peaks(rec["device_kind"])["bf16_flops_per_s"]
    return lib.share_of_peak(flops / secs, peak, "chunk_mfu_pct.kexaone")
