"""Render and diff serving-profiler phase reports in the terminal.

The ``TickProfiler`` (``HVD_TPU_PROFILE=1``) publishes the same
rolling per-phase report three ways; this tool reads any of them:

    python tools/profile_report.py http://127.0.0.1:9400        # live /profile
    python tools/profile_report.py events.jsonl                 # event-log replay
    python tools/profile_report.py profile.json [--json]        # saved report

A URL is scraped at its ``/profile`` endpoint (appended when missing); a
``.jsonl`` source replays the ``serve.profile_tick`` records of the
structured event log into an identical report (so a crashed run's last
window is still renderable); anything else is a saved report JSON — a
prior ``--json`` dump, a raw ``/profile`` body, or a full
``metrics_snapshot()`` (its ``"profile"`` key is used).

Regression gate (per phase):

    python tools/profile_report.py --compare old.json new.json \\
        [--threshold 10] [--floor-ms 0.05]

exits 1 when any phase's mean grew more than ``--threshold`` percent
AND more than ``--floor-ms`` absolute (the floor keeps sub-microsecond
jitter from failing a gate).
"""

from __future__ import annotations

import argparse
import json
import sys
import urllib.request

#: Dotted phase names are sub-phases nested inside a parent — excluded
#: from tick-share/coverage math (mirrors horovod_tpu.profiler.PHASES,
#: re-derived here so the tool stays importable without the package).


def _is_top_level(phase: str) -> bool:
    return "." not in phase


def fetch_report(url: str) -> dict:
    """Scrape a live monitor's ``/profile`` endpoint."""
    if not url.rstrip("/").endswith("/profile"):
        url = url.rstrip("/") + "/profile"
    with urllib.request.urlopen(url, timeout=5) as resp:
        return json.loads(resp.read())


def report_from_events(events: list[dict],
                       window: int | None = None) -> dict:
    """Rebuild the profiler's report schema from ``serve.profile_tick``
    event-log records (the replay path): the last ``window`` ticks, or
    every recorded tick when None."""
    ticks = [e for e in events if e.get("kind") == "serve.profile_tick"]
    if window is not None:
        ticks = ticks[-window:]
    names: list[str] = []
    for e in ticks:
        for p in e.get("phases", {}):
            if p not in names:
                names.append(p)
    tick_vals = [float(e.get("tick_s", 0.0)) for e in ticks]
    tick_total = sum(tick_vals)
    phases: dict[str, dict] = {}
    tiled = 0.0
    for p in names:
        vals = [float(e["phases"][p]) for e in ticks
                if p in e.get("phases", {})]
        total = sum(vals)
        phases[p] = {
            "count": len(vals),
            "total_s": total,
            "mean_s": total / len(vals) if vals else 0.0,
            "max_s": max(vals) if vals else 0.0,
            "pct_of_tick": (100.0 * total / tick_total
                            if tick_total else 0.0),
        }
        if _is_top_level(p):
            tiled += total
    return {
        "window": window if window is not None else len(ticks),
        "n": len(ticks),
        "ticks": len(ticks),
        "tick": {
            "count": len(ticks),
            "total_s": tick_total,
            "mean_s": tick_total / len(ticks) if ticks else 0.0,
            "max_s": max(tick_vals, default=0.0),
        },
        "phases": phases,
        "coverage": tiled / tick_total if tick_total else 1.0,
    }


def load_report(source: str, window: int | None = None) -> dict:
    """Dispatch on the source shape: URL, event-log JSONL, or report
    JSON (accepts a bare report, a ``/profile`` body, or a whole
    ``metrics_snapshot()`` dump)."""
    if source.startswith(("http://", "https://")):
        return fetch_report(source)
    if source.endswith(".jsonl"):
        events = []
        with open(source) as f:
            for line in f:
                try:
                    events.append(json.loads(line))
                except json.JSONDecodeError:
                    pass          # torn tail line of a live/crashed log
        return report_from_events(events, window=window)
    with open(source) as f:
        data = json.load(f)
    if "phases" in data:
        return data
    if "profile" in data:          # a metrics_snapshot() dump
        return data["profile"]
    raise SystemExit(f"{source}: neither a profiler report nor a "
                     f"snapshot with a 'profile' key")


def render(report: dict) -> str:
    """The phase table: count / total / mean / max / share of tick."""
    lines = [
        f"profiler report: {report['n']} ticks in window "
        f"(window={report['window']}, lifetime ticks={report['ticks']})",
        f"{'phase':26s} {'count':>6s} {'total ms':>10s} "
        f"{'mean ms':>9s} {'max ms':>9s} {'% tick':>7s}",
    ]
    phases = report.get("phases", {})
    # Top-level phases by descending total, each followed by its OWN
    # nested sub-phases (device_sync.compute_est under device_sync,
    # admit.* under admit) so the indentation reads as containment.
    order = []
    for p in sorted((p for p in phases if _is_top_level(p)),
                    key=lambda p: -phases[p]["total_s"]):
        order.append(p)
        order.extend(sorted(
            (s for s in phases if s.startswith(p + ".")),
            key=lambda s: -phases[s]["total_s"]))
    order += [s for s in phases if s not in order]   # orphan sub-phases
    for p in order:
        s = phases[p]
        name = ("  " + p if not _is_top_level(p) else p)
        lines.append(
            f"{name:26s} {s['count']:6d} {s['total_s'] * 1e3:10.2f} "
            f"{s['mean_s'] * 1e3:9.3f} {s['max_s'] * 1e3:9.3f} "
            f"{s['pct_of_tick']:6.1f}%")
    t = report["tick"]
    lines.append(
        f"{'tick (wall)':26s} {t['count']:6d} {t['total_s'] * 1e3:10.2f} "
        f"{t['mean_s'] * 1e3:9.3f} {t['max_s'] * 1e3:9.3f} {100.0:6.1f}%")
    lines.append(f"phase coverage of tick time: "
                 f"{report.get('coverage', 0.0) * 100.0:.1f}%")
    return "\n".join(lines)


def compare_reports(old: dict, new: dict, threshold_pct: float = 10.0,
                    floor_ms: float = 0.05) -> list[dict]:
    """Per-phase mean-time diff.  A phase REGRESSED when its mean grew
    more than ``threshold_pct`` percent AND more than ``floor_ms``
    milliseconds (both, so noise on near-zero phases can't gate)."""
    rows = []
    phases = dict(old.get("phases", {}))
    for p in new.get("phases", {}):
        phases.setdefault(p, {"mean_s": 0.0})
    for p in sorted(phases):
        o = old.get("phases", {}).get(p, {}).get("mean_s", 0.0) * 1e3
        n = new.get("phases", {}).get(p, {}).get("mean_s", 0.0) * 1e3
        delta = n - o
        pct = (delta / o * 100.0) if o else (float("inf") if n else 0.0)
        rows.append({
            "phase": p, "old_mean_ms": o, "new_mean_ms": n,
            "delta_ms": delta, "delta_pct": pct,
            "regressed": pct > threshold_pct and delta > floor_ms,
        })
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("source", nargs="?",
                    help="monitor URL, event-log .jsonl, or report JSON")
    ap.add_argument("--compare", nargs=2, metavar=("OLD", "NEW"),
                    help="diff two report sources; exit 1 on regression")
    ap.add_argument("--threshold", type=float, default=10.0,
                    help="regression threshold in percent (default 10)")
    ap.add_argument("--floor-ms", type=float, default=0.05,
                    help="absolute mean-growth floor in ms below which "
                         "a percent regression is ignored")
    ap.add_argument("--window", type=int, default=None,
                    help="for .jsonl replay: use only the last N ticks")
    ap.add_argument("--json", action="store_true",
                    help="dump the report (or the comparison rows) as JSON")
    args = ap.parse_args(argv)

    if bool(args.source) == bool(args.compare):
        ap.error("give exactly one of: a source, or --compare OLD NEW")

    if args.compare:
        old = load_report(args.compare[0], window=args.window)
        new = load_report(args.compare[1], window=args.window)
        rows = compare_reports(new=new, old=old,
                               threshold_pct=args.threshold,
                               floor_ms=args.floor_ms)
        if args.json:
            print(json.dumps(rows, indent=2))
        else:
            print(f"{'phase':26s} {'old ms':>9s} {'new ms':>9s} "
                  f"{'delta':>9s} {'pct':>8s}")
            for r in rows:
                flag = "  << REGRESSED" if r["regressed"] else ""
                print(f"{r['phase']:26s} {r['old_mean_ms']:9.3f} "
                      f"{r['new_mean_ms']:9.3f} {r['delta_ms']:+9.3f} "
                      f"{r['delta_pct']:+7.1f}%{flag}")
        return 1 if any(r["regressed"] for r in rows) else 0

    report = load_report(args.source, window=args.window)
    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True))
        return 0
    if not report.get("n"):
        print("no profiled ticks in source")
        return 1
    print(render(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
