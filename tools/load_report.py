"""Render and diff open-loop saturation-sweep reports in the terminal.

``horovod_tpu.loadgen.measure_saturation`` emits one JSON report per
sweep: the offered-RPS ladder,
per-rung client-observed percentiles, SLO goodput, the goodput knee,
and the per-phase end-to-end latency attribution.  This tool renders
it:

    python tools/load_report.py sweep.json            # saturation table
    python tools/load_report.py sweep.json --json     # normalized dump

Regression gate (the open-loop complement to ``profile_report.py``'s
per-phase tick diff):

    python tools/load_report.py --compare old.json new.json \\
        [--threshold 10] [--floor-ms 0.5]

exits 1 when the goodput knee dropped more than ``--threshold``
percent, when any matching rung's p99 TTFT grew more than
``--threshold`` percent AND more than ``--floor-ms`` absolute, or when
knee attribution coverage fell below 0.95 from a passing baseline.
"""

from __future__ import annotations

import argparse
import json
import sys

#: Attribution phases in causal order (mirrors
#: horovod_tpu.loadgen.ATTR_PHASES, re-declared so the tool stays
#: importable without the package).
ATTR_PHASES = ("ingress_s", "route_s", "replica_queue_s",
               "queue_wait_s", "prefill_s", "decode_s", "finish_s",
               "egress_s")

#: Knee attribution coverage below this is a gate failure when the
#: baseline met it — the acceptance bar for "the report can say where
#: the p99 millisecond lives".
COVERAGE_BAR = 0.95


def load_report(source: str) -> dict:
    """A saved sweep report JSON: a ``measure_saturation`` return
    value, or a dump carrying one under ``serve_load``."""
    with open(source) as f:
        data = json.load(f)
    if "rungs" in data:
        return data
    if "serve_load" in data and "rungs" in data["serve_load"]:
        return data["serve_load"]
    raise SystemExit(f"{source}: not a saturation-sweep report "
                     f"(no 'rungs' key)")


def render(report: dict) -> str:
    """The saturation curve as a rung table plus the knee attribution."""
    rungs = report.get("rungs", [])
    knee_i = report.get("knee_index", 0)
    lines = [
        f"saturation sweep: {report.get('serve_load_requests', 0)} "
        f"requests over {len(rungs)} rungs "
        f"(process={report.get('serve_load_process', '?')}, "
        f"seed={report.get('serve_load_seed', '?')}, "
        f"{report.get('serve_load_duration_s', 0)}s/rung, "
        f"{report.get('serve_load_replicas', '?')} replicas)",
        f"{'offered':>8s} {'n':>5s} {'ok':>5s} {'shed':>5s} "
        f"{'t/o':>5s} {'p50 ttft':>9s} {'p99 ttft':>9s} "
        f"{'p99 tpot':>9s} {'p99 e2e':>9s} {'goodput':>8s}",
    ]
    for i, r in enumerate(rungs):
        mark = "  << knee" if i == knee_i else ""
        lines.append(
            f"{r['offered_rps']:7.1f}r {r['n']:5d} {r['ok_rate']:5.2f} "
            f"{r['shed_rate']:5.2f} {r['timeout_rate']:5.2f} "
            f"{r['p50_ttft_s'] * 1e3:7.1f}ms {r['p99_ttft_s'] * 1e3:7.1f}ms "
            f"{r['p99_tpot_s'] * 1e3:7.1f}ms {r['p99_e2e_s'] * 1e3:7.1f}ms "
            f"{r['goodput_rps']:6.1f}/s{mark}")
    mono = "monotone" if report.get("serve_load_p99_ttft_monotone") \
        else "NOT monotone"
    lines.append(f"p99 TTFT across rungs: {mono}; knee at "
                 f"{report.get('serve_load_knee_rps', 0):.1f} offered rps "
                 f"-> {report.get('serve_load_knee_goodput_rps', 0):.1f} "
                 f"good rps")
    if rungs:
        attr = rungs[knee_i].get("attribution", {})
        phases = attr.get("phases", {})
        mean_e2e = attr.get("mean_e2e_s", 0.0)
        lines.append(f"knee attribution over {attr.get('n', 0)} OK "
                     f"requests (mean e2e {mean_e2e * 1e3:.2f} ms, "
                     f"coverage {attr.get('coverage', 0.0) * 100:.1f}%):")
        for p in ATTR_PHASES:
            v = phases.get(p, 0.0)
            share = (v / mean_e2e * 100.0) if mean_e2e else 0.0
            lines.append(f"  {p:18s} {v * 1e3:9.3f} ms {share:6.1f}%")
        exemplars = (report.get("knee_exemplar_trace_ids")
                     or rungs[knee_i].get("exemplar_trace_ids") or [])
        if exemplars:
            lines.append("knee exemplar traces (slowest sampled "
                         "requests; feed to tools/trace_report.py):")
            for tid in exemplars:
                lines.append(f"  {tid}")
    return "\n".join(lines)


def compare_reports(old: dict, new: dict, threshold_pct: float = 10.0,
                    floor_ms: float = 0.5) -> list[dict]:
    """Sweep-level diff rows.  REGRESSED when: the knee goodput-RPS
    dropped more than ``threshold_pct``; a matching offered-RPS rung's
    p99 TTFT grew more than ``threshold_pct`` percent AND more than
    ``floor_ms`` milliseconds (both, so jitter on fast rungs can't
    gate); or knee attribution coverage fell below ``COVERAGE_BAR``
    from a baseline that met it."""
    rows = []
    o_knee = old.get("serve_load_knee_goodput_rps", 0.0)
    n_knee = new.get("serve_load_knee_goodput_rps", 0.0)
    drop_pct = ((o_knee - n_knee) / o_knee * 100.0) if o_knee else 0.0
    rows.append({
        "metric": "knee_goodput_rps", "old": o_knee, "new": n_knee,
        "delta_pct": -drop_pct,
        "regressed": drop_pct > threshold_pct,
    })
    o_rungs = {r["offered_rps"]: r for r in old.get("rungs", [])}
    for r in new.get("rungs", []):
        o = o_rungs.get(r["offered_rps"])
        if o is None:
            continue
        o_ms = o["p99_ttft_s"] * 1e3
        n_ms = r["p99_ttft_s"] * 1e3
        delta = n_ms - o_ms
        pct = (delta / o_ms * 100.0) if o_ms else \
            (float("inf") if n_ms else 0.0)
        rows.append({
            "metric": f"p99_ttft_ms@{r['offered_rps']:g}rps",
            "old": o_ms, "new": n_ms, "delta_pct": pct,
            "regressed": pct > threshold_pct and delta > floor_ms,
        })
    o_cov = old.get("serve_load_attr_coverage_knee", 0.0)
    n_cov = new.get("serve_load_attr_coverage_knee", 0.0)
    rows.append({
        "metric": "knee_attr_coverage", "old": o_cov, "new": n_cov,
        "delta_pct": ((n_cov - o_cov) / o_cov * 100.0) if o_cov else 0.0,
        "regressed": o_cov >= COVERAGE_BAR and n_cov < COVERAGE_BAR,
    })
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("source", nargs="?",
                    help="saved saturation-sweep report JSON")
    ap.add_argument("--compare", nargs=2, metavar=("OLD", "NEW"),
                    help="diff two sweep reports; exit 1 on regression")
    ap.add_argument("--threshold", type=float, default=10.0,
                    help="regression threshold in percent (default 10)")
    ap.add_argument("--floor-ms", type=float, default=0.5,
                    help="absolute p99-TTFT growth floor in ms below "
                         "which a percent regression is ignored")
    ap.add_argument("--json", action="store_true",
                    help="dump the report (or the comparison rows) as JSON")
    args = ap.parse_args(argv)

    if bool(args.source) == bool(args.compare):
        ap.error("give exactly one of: a source, or --compare OLD NEW")

    if args.compare:
        old = load_report(args.compare[0])
        new = load_report(args.compare[1])
        rows = compare_reports(old=old, new=new,
                               threshold_pct=args.threshold,
                               floor_ms=args.floor_ms)
        if args.json:
            print(json.dumps(rows, indent=2))
        else:
            print(f"{'metric':26s} {'old':>10s} {'new':>10s} {'pct':>8s}")
            for r in rows:
                flag = "  << REGRESSED" if r["regressed"] else ""
                print(f"{r['metric']:26s} {r['old']:10.3f} "
                      f"{r['new']:10.3f} {r['delta_pct']:+7.1f}%{flag}")
        return 1 if any(r["regressed"] for r in rows) else 0

    report = load_report(args.source)
    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True))
        return 0
    print(render(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
