"""The arithmetic of the ``granite_serve`` family's per-layer metrics, over the
records the serving drivers keep.  A step's stamp is ``llama_serve``'s five
fields and then ``(experts touched by its tick, moe.choices_total,
moe.choices_held, ssm.state_restores, attn.keys_visible, load of each held
expert, ssm.snapshots_written, ssm.snapshots_evicted, ssm.state_bytes_moved,
prefix.blocks_matched, prefix.blocks_restored, ssm.snapshots_live)``:
``dots3_serve``'s layout up to the loads, so what ``dots3_stats`` reads of a
stamp by position (the stamps that carry counters, a counter's gain over the
window, the share of the choices held, the traced ticks, the rows' contexts)
is read here by the same code; the snapshot budget's five counters and its
gauge stand last.  On a program whose stamps carry no such counters every
reader returns ``None``."""

from __future__ import annotations

from benchmark import dots3_stats, lib, serve_stats

CONFIG = "granite-4.0-h-small.json"
TOUCHED, TOTAL, HELD, RESTORES, VISIBLE, LOAD0 = 5, 6, 7, 8, 9, 10
WRITTEN, EVICTED, STATE_MOVED, MATCHED, RESTORED, LIVE = -6, -5, -4, -3, -2, -1
assert (LOAD0, TOUCHED, TOTAL, HELD) == (
    dots3_stats.LOAD0, dots3_stats.TOUCHED, dots3_stats.TOTAL,
    dots3_stats.HELD)

moe_held_share_pct = dots3_stats.moe_held_share_pct


def traced_ticks(rec: dict) -> list:
    """The stamps of the ticking steps that began once the trace had
    (``rec["trace_started"]``: the cell's driver places the trace after the
    first wave's prefill; the window's start where a driver does not say),
    as many as the trace holds whole runs of the tick program, in order.
    Since a step leaves its tick in flight, the tick dispatched just before
    the trace began may run inside it: the pairing of stamps and runs is
    then off by one step, which a mean over hundreds of ticks does not
    see."""
    p = serve_stats._program(rec, "_tick")
    if p is None:
        return []
    start = rec.get("trace_started") or rec["window"][0]
    ticking = [s for s in dots3_stats._counted(rec)
               if s[0] >= start and s[2] > 0]
    return ticking[:max(p["count"] - 1, 0)]


def _family():
    return lib.load_module("families", "granite_serve")


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
    load = [b - a for a, b in zip(steps[0][LOAD0:WRITTEN],
                                  steps[-1][LOAD0:WRITTEN])]
    return max(load) / lib.mean(load) if sum(load) else None


def snapshot_restored_over_matched(rec: dict):
    """Blocks the admissions were restored at over blocks the radix index
    matched for them, over the window: 1.0 where the snapshot budget lost
    nothing, under it by what was matched, found without a snapshot and
    computed again."""
    matched = dots3_stats.window_delta(rec, MATCHED)
    restored = dots3_stats.window_delta(rec, RESTORED)
    return restored / matched if matched else None


def snapshots_evicted_per_request(rec: dict):
    """Snapshot entries taken from a block because none was free, a request
    the window finished."""
    evicted = dots3_stats.window_delta(rec, EVICTED)
    done = sum(1 for r in rec["requests"] if r["in_window"] and r["ok"])
    return evicted / done if evicted is not None and done else None


def _tick_bytes(rec: dict, ticks: list) -> list:
    fam, cfg = _family(), _config()
    return [fam.tick_bytes(cfg, rows=b[2],
                           live_tokens=dots3_stats._context_at(rec, b[1]),
                           experts_touched=b[TOUCHED]) for b in ticks]


def tick_roofline_pct(rec: dict):
    """The least time the traced ticks could take on this chip (their bytes
    over the memory's peak rate: a tick of 64 rows does 9 rows an expert and
    is bound by bytes) over the tick program's device time."""
    ticks = traced_ticks(rec)
    ms = serve_stats.program_ms(rec, "_tick")
    if not ticks or ms is None:
        return None
    peak = lib.peaks(rec["device_kind"])["hbm_bytes_per_s"]
    return lib.share_of_peak(lib.mean(_tick_bytes(rec, ticks)) / (ms / 1e3),
                             peak, "tick_roofline.granite")


def _chunks_between(rec: dict, n_ticks: int):
    """The chunk program's runs between the end of the trace's first whole
    tick and the end of its ``n_ticks``-th, on the trace's clock: ``(count,
    seconds)``, or ``None`` where the trace lacks either program."""
    p = serve_stats._program(rec, "_chunk")
    t = serve_stats._program(rec, "_tick")
    if p is None or t is None:
        return None
    t_runs = sorted(t["runs"])
    start, end = t_runs[0][1], t_runs[n_ticks - 1][1]
    runs = [b - a for a, b in p["runs"] if a >= start and b <= end]
    return len(runs), sum(runs) / 1e9


def ssm_state_share_pct(rec: dict):
    """Of the bytes the traced ticks had to move, the share that was
    recurrent state: what ``ssm.state_bytes_moved`` gained between the first
    and the last whole tick of the trace, less the state of the chunks that
    ran between them (a slot's, read and written), over those ticks'
    reckoned bytes."""
    ticks = traced_ticks(rec)
    if len(ticks) < 2:
        return None
    between = _chunks_between(rec, len(ticks))
    if between is None:
        return None
    fam, cfg = _family(), _config()
    moved = ticks[-1][STATE_MOVED] - ticks[0][STATE_MOVED] \
        - between[0] * 2 * fam.state_bytes_per_slot(cfg)
    total = sum(_tick_bytes(rec, ticks[1:]))
    return 100.0 * moved / total if total and moved >= 0 else None


def chunk_mfu_pct(rec: dict):
    """The operations of the chunk programs that ran between the first and
    the last whole tick of the trace (what the counters gained there, less
    the ticks' own part) over those programs' device time, as a share of the
    chip's peak."""
    ticks = traced_ticks(rec)
    if len(ticks) < 2:
        return None
    between_chunks = _chunks_between(rec, len(ticks))
    if between_chunks is None:
        return None
    fam, cfg = _family(), _config()
    sizes = fam._sizes(cfg)
    first, last, between = ticks[0], ticks[-1], ticks[1:]
    gained = {f: last[f] - first[f] for f in (TOTAL, HELD, VISIBLE)}
    per_token = sizes["k"] * sizes["n"]
    tokens = gained[TOTAL] / per_token - sum(b[2] for b in between)
    # a decoding row's query sees its context in the attention layer
    ticks_saw = sizes["attn"] * sum(
        dots3_stats._context_at(rec, b[1]) for b in between)
    share = gained[HELD] / gained[TOTAL] if gained[TOTAL] else 0.0
    secs = between_chunks[1]
    if tokens <= 0 or secs <= 0:
        return None
    flops = fam.chunk_flops(
        cfg, tokens=tokens, keys_visible=max(gained[VISIBLE] - ticks_saw, 0.0),
        choices_held=share * tokens * per_token)
    peak = lib.peaks(rec["device_kind"])["bf16_flops_per_s"]
    return lib.share_of_peak(flops / secs, peak, "chunk_mfu_pct.granite")
