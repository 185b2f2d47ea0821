"""The arithmetic of the ``sdar_serve`` family's per-layer metrics, over the
records the serving drivers keep.  A step's stamp is ``llama_serve``'s five
fields and then ``(experts touched by its tick, moe.choices_total,
attn.keys_visible, diffusion.denoise_forwards, diffusion.commit_forwards, load
of each expert)``: ``dots3_serve``'s layout with this family's counters, so
what ``dots3_stats`` reads of a stamp by position (the stamps that carry
counters, a counter's gain over the window, the traced ticks, the rows'
contexts, the busiest expert) is read here by the same code.  A tick here is a
**block tick**: ``block_length`` positions a decoding row.  On a program whose
stamps carry no such counters every reader returns ``None``."""

from __future__ import annotations

from benchmark import dots3_stats, lib, serve_stats

CONFIG = "sdar-30b-a3b-chat.json"
TOUCHED, TOTAL, VISIBLE, DENOISE, COMMIT, LOAD0 = 5, 6, 7, 8, 9, 10
assert LOAD0 == dots3_stats.LOAD0 and TOUCHED == dots3_stats.TOUCHED

moe_load_max_over_mean = dots3_stats.moe_load_max_over_mean
traced_ticks = dots3_stats.traced_ticks


def _family():
    return lib.load_module("families", "sdar_serve")


def _config() -> dict:
    return lib.load_json("configs", CONFIG)


def tokens_per_forward(rec: dict):
    """Tokens committed over row-forwards, over the window: ``block_length``
    a committed block, over the forwards of either kind (a denoise forward
    commits nothing, a commit forward only stores).  ``B / (S + 1)`` at the
    schedule's floor; a trained model's confidences raise it."""
    denoise = dots3_stats.window_delta(rec, DENOISE)
    commit = dots3_stats.window_delta(rec, COMMIT)
    if denoise is None or commit is None or not denoise + commit:
        return None
    return _family()._sizes(_config())["block"] * commit / (denoise + commit)


def moe_experts_touched_pct(rec: dict):
    """Mean over the window's block ticks of the experts their rows touched,
    as a share of every expert of every layer."""
    lo, hi = rec["window"]
    ticks = [s[TOUCHED] for s in dots3_stats._counted(rec)
             if lo <= s[1] <= hi and s[2] > 0]
    if not ticks:
        return None
    sizes = _family()._sizes(_config())
    return 100.0 * lib.mean(ticks) / (sizes["n"] * sizes["e"])


def tick_roofline_pct(rec: dict):
    """The least time the traced block ticks could take on this chip (their
    bytes over the memory's peak rate: 512 tokens over weights that are read
    once are far under the ridge) over the tick program's device time."""
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
    return lib.share_of_peak(nbytes / (ms / 1e3), peak, "tick_roofline.sdar")


def chunk_mfu_pct(rec: dict):
    """The operations of the chunk programs that ran between the first and
    the last whole tick of the trace (what the counters gained there, less
    the block ticks' own part: ``block_length`` tokens a decoding row, each
    seeing its row's context and its whole block) over those programs' device
    time, as a share of the chip's peak."""
    ticks = traced_ticks(rec)
    p = serve_stats._program(rec, "_chunk")
    t = serve_stats._program(rec, "_tick")
    if len(ticks) < 2 or p is None or t is None:
        return None
    fam, cfg = _family(), _config()
    sizes = fam._sizes(cfg)
    first, last, between = ticks[0], ticks[-1], ticks[1:]
    per_token = sizes["k"] * sizes["n"]
    block = sizes["block"]
    rows = sum(b[2] for b in between)
    tokens = (last[TOTAL] - first[TOTAL]) / per_token - rows * block
    contexts = sum(dots3_stats._context_at(rec, b[1]) for b in between)
    visible = (last[VISIBLE] - first[VISIBLE]) - sizes["n"] * block * (
        contexts + rows * block)
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
    return lib.share_of_peak(flops / secs, peak, "chunk_mfu_pct.sdar")
