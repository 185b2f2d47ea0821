"""The arithmetic of the ``lfm2_serve`` family's per-layer metrics, over the
records the serving drivers keep.  A step's stamp is ``llama_serve``'s five
fields and then ``(experts touched by its tick, moe.choices_total,
conv.state_restores, conv.snapshots_written, attn.keys_visible, load of each
expert)``: ``dots3_serve``'s layout with this family's counters, so what
``dots3_stats`` reads of a stamp by position (the stamps that carry counters,
a counter's gain over the window, the traced ticks, the rows' contexts, the
busiest expert) is read here by the same code.  On a program whose stamps
carry no such counters every reader returns ``None``."""

from __future__ import annotations

from benchmark import dots3_stats, lib, serve_stats

CONFIG = "lfm2-8b-a1b.json"
TOUCHED, TOTAL, RESTORES, SNAPSHOTS, VISIBLE, LOAD0 = 5, 6, 7, 8, 9, 10
assert LOAD0 == dots3_stats.LOAD0 and TOUCHED == dots3_stats.TOUCHED

moe_load_max_over_mean = dots3_stats.moe_load_max_over_mean
traced_ticks = dots3_stats.traced_ticks


def _family():
    return lib.load_module("families", "lfm2_serve")


def _config() -> dict:
    return lib.load_json("configs", CONFIG)


def moe_experts_touched_pct(rec: dict):
    """Mean over the window's decode ticks of the experts their rows touched,
    as a share of every expert of every expert layer."""
    lo, hi = rec["window"]
    ticks = [s[TOUCHED] for s in dots3_stats._counted(rec)
             if lo <= s[1] <= hi and s[2] > 0]
    if not ticks:
        return None
    sizes = _family()._sizes(_config())
    return 100.0 * lib.mean(ticks) / (
        (sizes["n"] - sizes["dense"]) * sizes["e"])


def sched_host_ms(rec: dict):
    """Per ticking step of the traced window, on the trace's clock: from the
    first tick's start to the last whole tick's end, the wall time less the
    device time of the programs that ran in it, over the ticks.  (The whole
    window's step time less its programs' says nothing here: the first steps
    hand the device a hundred chunks each and return before it has run
    them.)"""
    t = serve_stats._program(rec, "_tick")
    if t is None or t["count"] < 3:
        return None
    runs = sorted(t["runs"])[:-1]           # the last may be cut
    start, end = runs[0][0], runs[-1][1]
    dev = sum(min(b, end) - max(a, start)
              for p in rec["trace"]["programs"].values()
              for a, b in p["runs"] if b > start and a < end)
    return (end - start - dev) / 1e6 / len(runs)


def tick_roofline_pct(rec: dict):
    """The least time the traced ticks could take on this chip (their bytes
    over the memory's peak rate; a tick of 128 rows does 16 rows an expert
    and is bound by bytes) over the tick program's device time."""
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
    return lib.share_of_peak(nbytes / (ms / 1e3), peak, "tick_roofline.lfm2")


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
    per_token = sizes["k"] * (sizes["n"] - sizes["dense"])
    rows = sum(b[2] for b in between)
    tokens = (last[TOTAL] - first[TOTAL]) / per_token - rows
    contexts = sum(dots3_stats._context_at(rec, b[1]) for b in between)
    visible = (last[VISIBLE] - first[VISIBLE]) - sizes["attn"] * contexts
    # the chunks run between the end of the first whole tick and the end of
    # the last, on the trace's clock
    t_runs = sorted(t["runs"])
    start, end = t_runs[0][1], t_runs[len(ticks) - 1][1]
    secs = sum(b - a for a, b in p["runs"] if a >= start and b <= end) / 1e9
    if tokens <= 0 or secs <= 0:
        return None
    flops = fam.chunk_flops(cfg, tokens=tokens,
                            keys_visible=max(visible, 0.0),
                            choices=tokens * per_token)
    peak = lib.peaks(rec["device_kind"])["bf16_flops_per_s"]
    return lib.share_of_peak(flops / secs, peak, "chunk_mfu_pct.lfm2")
