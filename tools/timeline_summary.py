"""Summarize a Horovod-TPU timeline (Chrome-trace JSON) in the terminal.

The timeline models each tensor as a "process" whose pid groups its
events (reference timeline.cc:51-67); chrome://tracing renders it, but a
quick look during a run shouldn't need a browser:

    python tools/timeline_summary.py /tmp/timeline.json [--top 20] [--json]

Multi-rank merge (per-rank traces from a ``{rank}``-templated
``maybe_create`` path): positional order assigns ranks 0, 1, ... —
each event's ``pid`` becomes its rank (the original tensor pid moves to
``tid``), so chrome://tracing shows one process lane per rank; summary
and ``--json`` modes aggregate across the ranks, with tensors prefixed
``r<k>/``.  Per-rank traces use per-process monotonic origins, so the
merge time-aligns them on their first common event (``rank_shifts``)
before stitching:

    python tools/timeline_summary.py --merge r0.json r1.json --out all.json

Prints per-tensor negotiation and execution durations, per-phase totals,
the negotiation tick counts per rank (NEGOTIATE_TICK_r<k> instants —
reference timeline.cc:98-132 parity), aggregated counter (``ph: "C"``)
series — the serving scheduler's SCHED/LIFECYCLE/PREFIX tracks, plus
SPEC (speculative-decode rounds/proposed/accepted, spec engines only):
final values plus the delta and sample count across the trace — and
per-request async spans (the engine's ``REQ`` ``b``/``e`` pairs, one id
per request).  The phases of ``ServeEngine.step`` are not here: they are
spans of jax's profiler trace (``serve.step.<phase>``), on the device's
clock.  ``--json`` dumps the whole summary dict as JSON for scripting.
"""

from __future__ import annotations

import argparse
import collections
import json
import sys


def load_events(path: str) -> list[dict]:
    with open(path) as f:
        text = f.read()
    try:
        data = json.loads(text)
    except json.JSONDecodeError:
        # An in-progress trace: the writer emits ",\n"-terminated events
        # and only close() writes the final "]".  Summarizing mid-run is
        # the tool's point, so complete the array and retry.
        data = json.loads(text.rstrip().rstrip(",") + "]")
    # Chrome trace is either a bare event array or {"traceEvents": [...]}.
    return data["traceEvents"] if isinstance(data, dict) else data


def rank_shifts(traces: list[list[dict]]) -> list[float]:
    """Per-rank timestamp shifts (us, add to ``ts``) aligning traces on
    their first common event.

    Each rank's trace uses its own monotonic origin (the writer stamps
    a per-process clock), so raw merges skew lanes by process start
    time.  Wall clocks can't fix that — they step and drift — but
    monotonic *deltas* are trustworthy, so the merge anchors on the
    earliest event *name* every rank recorded (the one whose latest
    first-occurrence across ranks is smallest) and shifts each rank so
    its first occurrence of that anchor lands at the same instant (the
    minimum across ranks).  No common event → zero shifts (nothing to
    anchor on beats a wrong anchor)."""
    firsts: list[dict[str, float]] = []
    for events in traces:
        first: dict[str, float] = {}
        for e in events:
            if e.get("ph") == "M" or "ts" not in e:
                continue
            name = e.get("name", "")
            if name not in first or e["ts"] < first[name]:
                first[name] = e["ts"]
        firsts.append(first)
    common = set.intersection(*(set(f) for f in firsts)) if firsts else set()
    if not common or len(firsts) < 2:
        return [0.0] * len(traces)
    anchor = min(common, key=lambda n: max(f[n] for f in firsts))
    target = min(f[anchor] for f in firsts)
    return [target - f[anchor] for f in firsts]


def _shifted(e: dict, shift: float) -> dict:
    e = dict(e)
    if shift and "ts" in e:
        e["ts"] = e["ts"] + shift
    return e


def merge_chrome(paths: list[str]) -> list[dict]:
    """Stitch per-rank Chrome traces into ONE: rank k's events get
    ``pid=k`` (one process lane per rank in chrome://tracing) and keep
    their original tensor pid as ``tid``; the per-tensor
    ``process_name`` metadata becomes per-rank ``thread_name`` rows and
    each rank lane is labeled ``rank k``.  Lanes are time-aligned on
    the first common event (:func:`rank_shifts`)."""
    traces = [load_events(p) for p in paths]
    shifts = rank_shifts(traces)
    out: list[dict] = []
    for rank, events in enumerate(traces):
        out.append({"name": "process_name", "ph": "M", "pid": rank,
                    "args": {"name": f"rank {rank}"}})
        out.append({"name": "process_sort_index", "ph": "M", "pid": rank,
                    "args": {"sort_index": rank}})
        for e in events:
            orig_pid = e.get("pid", 0)
            if e.get("ph") == "M":
                if e.get("name") == "process_name":
                    out.append({"name": "thread_name", "ph": "M",
                                "pid": rank, "tid": orig_pid,
                                "args": dict(e.get("args", {}))})
                # drop other process-level metadata (sort indices etc.:
                # they would re-order the rank lanes)
                continue
            e = _shifted(e, shifts[rank])
            e["pid"] = rank
            # The tensor identity lives in the original pid (the writer
            # emits a constant tid 0), so tid must be overwritten, not
            # defaulted, to keep one thread row per tensor in the lane.
            e["tid"] = orig_pid
            out.append(e)
    return out


def merge_for_summary(paths: list[str]) -> list[dict]:
    """Concatenate per-rank traces for :func:`summarize`, keeping pids
    unique per (rank, tensor) — ``summarize`` pairs B/E by (pid, name),
    so colliding tensor pids across ranks would cross-pair.  Tensor
    names gain an ``r<k>/`` prefix; counter/instant/span names stay
    shared so those series aggregate fleet-wide.  Timestamps get the
    same first-common-event alignment as :func:`merge_chrome` so
    cross-rank span/counter aggregation compares like instants."""
    traces = [load_events(p) for p in paths]
    shifts = rank_shifts(traces)
    out: list[dict] = []
    for rank, events in enumerate(traces):
        for e in events:
            e = _shifted(e, shifts[rank])
            e["pid"] = rank * 1_000_000 + e.get("pid", 0)
            if (e.get("ph") == "M" and e.get("name") == "process_name"
                    and e.get("args")):
                e["args"] = {**e["args"],
                             "name": f"r{rank}/{e['args'].get('name', '')}"}
            out.append(e)
    return out


def summarize(events: list[dict]) -> dict:
    tensor_names: dict[int, str] = {}
    # (pid, name) -> B timestamp stack; durations per (pid, phase name).
    open_b: dict[tuple, list] = collections.defaultdict(list)
    durs: dict[tuple, float] = collections.defaultdict(float)
    args_by_pid: dict[int, dict] = {}
    ticks = collections.Counter()
    # counter (ph "C") aggregation: activity -> series -> running stats
    counters: dict[str, dict[str, dict]] = {}
    # async (ph "b"/"e") spans: name -> list of closed durations (us)
    span_durs: dict[str, list] = collections.defaultdict(list)
    span_ids: dict[str, set] = collections.defaultdict(set)

    for e in events:
        ph = e.get("ph")
        pid = e.get("pid", 0)
        name = e.get("name", "")
        if ph == "M" and name == "process_name":
            tensor_names[pid] = e.get("args", {}).get("name", str(pid))
        elif ph == "B":
            open_b[(pid, name)].append(e["ts"])
        elif ph == "E":
            stack = open_b.get((pid, name))
            if stack:
                durs[(pid, name)] += e["ts"] - stack.pop()
            if e.get("args"):
                args_by_pid.setdefault(pid, e["args"])
        elif ph == "i":
            # True instant events (per-rank readiness ticks, mark_cycles
            # engine ticks, scheduler lifecycle marks): counted by name.
            if name != "done":              # skip the close() terminator
                ticks[name] += 1
        elif ph == "X":
            if name.startswith("NEGOTIATE_TICK") or name == "CYCLE_START":
                # Back-compat: older traces wrote instants as zero-width
                # complete events; count them, never tabulate as tensors.
                ticks[name] += 1
            else:
                durs[(pid, name)] += e.get("dur", 0.0)
        elif ph == "C":
            series = counters.setdefault(name, {})
            for k, v in (e.get("args") or {}).items():
                s = series.get(k)
                if s is None:
                    series[k] = {"first": v, "last": v, "min": v,
                                 "max": v, "samples": 1}
                else:
                    s["last"] = v
                    s["min"] = min(s["min"], v)
                    s["max"] = max(s["max"], v)
                    s["samples"] += 1
        elif ph == "b":
            open_b[(pid, name, e.get("id"))].append(e["ts"])
            span_ids[name].add(e.get("id"))
        elif ph == "e":
            stack = open_b.get((pid, name, e.get("id")))
            if stack:
                d = e["ts"] - stack.pop()
                durs[(pid, name)] += d
                span_durs[name].append(d)

    unbalanced = sorted(
        k[1] for k, v in open_b.items() for _ in v   # one entry per open B
    )
    per_tensor: dict[str, dict] = {}
    phase_totals: collections.Counter = collections.Counter()
    for (pid, phase), us in durs.items():
        t = per_tensor.setdefault(
            tensor_names.get(pid, str(pid)), {"phases": {}, "args": {}})
        t["phases"][phase] = t["phases"].get(phase, 0.0) + us
        phase_totals[phase] += us
    for pid, a in args_by_pid.items():
        if tensor_names.get(pid) in per_tensor:
            per_tensor[tensor_names[pid]]["args"] = a
    # finalize counter series: delta over the trace + mean step delta
    for series in counters.values():
        for s in series.values():
            s["delta"] = s["last"] - s["first"]
            steps = max(s["samples"] - 1, 1)
            s["per_step"] = s["delta"] / steps
    spans = {
        name: {
            "count": len(ds),
            "open": len(span_ids[name]) - len(ds),
            "total_us": sum(ds),
            "mean_us": sum(ds) / len(ds) if ds else 0.0,
            "max_us": max(ds) if ds else 0.0,
        }
        for name, ds in span_durs.items()
    }
    return {
        "tensors": per_tensor,
        "phase_totals": dict(phase_totals),
        "ticks": dict(ticks),
        "counters": counters,
        "spans": spans,
        "unbalanced": unbalanced,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", nargs="?",
                    help="one Chrome-trace JSON (omit with --merge)")
    ap.add_argument("--merge", nargs="+", metavar="RANK_TRACE",
                    help="per-rank traces in rank order; summarized "
                         "together (and stitched into --out)")
    ap.add_argument("--out",
                    help="with --merge: write the merged Chrome trace "
                         "(pid=rank, tid=original tensor pid) here")
    ap.add_argument("--top", type=int, default=20,
                    help="show the N tensors with the largest total time")
    ap.add_argument("--json", action="store_true",
                    help="dump the full summary dict as JSON")
    args = ap.parse_args(argv)

    if bool(args.trace) == bool(args.merge):
        ap.error("give exactly one of: a trace path, or --merge")
    if args.out and not args.merge:
        ap.error("--out only makes sense with --merge")

    if args.merge:
        if args.out:
            with open(args.out, "w") as f:
                json.dump(merge_chrome(args.merge), f)
        s = summarize(merge_for_summary(args.merge))
        s["ranks"] = len(args.merge)
    else:
        s = summarize(load_events(args.trace))
    if args.json:
        print(json.dumps(s, indent=2, sort_keys=True))
        return 0
    if not s["tensors"] and not s["counters"]:
        print("no tensor events found")
        return 1

    print(f"{len(s['tensors'])} tensors; phase totals (ms):")
    for phase, us in sorted(s["phase_totals"].items(),
                            key=lambda kv: -kv[1]):
        print(f"  {phase:32s} {us / 1e3:10.2f}")
    if s["ticks"]:
        print("instants:",
              " ".join(f"{k}={v}" for k, v in sorted(s["ticks"].items())))
    for activity, series in sorted(s["counters"].items()):
        print(f"\ncounter {activity} (final / delta over "
              f"{max(v['samples'] for v in series.values())} samples):")
        for k, v in sorted(series.items()):
            print(f"  {k:24s} last {v['last']:10g}  delta {v['delta']:10g}"
                  f"  per-step {v['per_step']:8.3f}")
    if s["spans"]:
        print("\nasync spans:")
        for name, sp in sorted(s["spans"].items()):
            print(f"  {name:24s} n={sp['count']:5d} open={sp['open']:3d} "
                  f"mean {sp['mean_us'] / 1e3:8.2f}ms "
                  f"max {sp['max_us'] / 1e3:8.2f}ms")
    rows = sorted(
        s["tensors"].items(),
        key=lambda kv: -sum(kv[1]["phases"].values()),
    )[: args.top]
    print(f"\ntop {len(rows)} tensors by total time (ms):")
    for name, info in rows:
        total = sum(info["phases"].values()) / 1e3
        neg = sum(us for p, us in info["phases"].items()
                  if p.startswith("NEGOTIATE")) / 1e3
        extra = ""
        if info["args"]:
            extra = f"  {info['args'].get('dtype', '')}{info['args'].get('shape', '')}"
        print(f"  {name:40s} total {total:9.2f}  negotiate {neg:8.2f}{extra}")
    if s["unbalanced"]:
        print(f"\nWARNING: {len(s['unbalanced'])} unbalanced B/E pairs: "
              f"{sorted(set(s['unbalanced']))[:5]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
