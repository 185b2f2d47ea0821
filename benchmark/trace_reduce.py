"""From a profiler trace to numbers: device busy and idle time, time per
program, time per operation, and what the host was doing in each idle gap.

Two steps, so that the arithmetic can be tested on a small recorded trace
without a chip:

* :func:`load_xplane` reads jax's ``.xplane.pb`` into plain lists (the ``raw``
  form below, which is also what ``tests/data/*.json`` holds);
* :func:`reduce` turns ``raw`` into the numbers.

The ``raw`` form, all times in nanoseconds on the trace's one clock::

    {"window": [start, end],               # the span named WINDOW_SPAN
     "devices": {"/device:TPU:0": {"modules": [[name, start, dur], ...],
                                   "ops": [[name, start, dur], ...],
                                   "async": [[name, start, dur], ...]}},
     "host": {"<thread line>": [[name, start, dur], ...]}}

As the profiler of jax 0.9 writes a TPU v5e trace: each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Modules`` holds one event per program
run, named ``jit_<function>(<hash>)``, whose line ``XLA Ops`` holds one event
per operation, named by its HLO text ``%<op> = ...``, and whose line
``Async XLA Ops`` holds the spans of asynchronous operations (copies,
collectives) from start to done.  Host threads are lines of the plane
``/host:CPU``; ``jax.profiler.TraceAnnotation`` spans appear there under
their own names.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys

WINDOW_SPAN = "bench.window"
NO_SPAN = "no_host_span"
BETWEEN_OPS = "between_device_ops"
CONTAINERS = ("while", "conditional", "call")
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_LINES = {"XLA Modules": "modules", "XLA Ops": "ops",
          "Async XLA Ops": "async"}


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``: the operation's
    name without the number XLA appends, stable across compiles."""
    m = re.match(r"^%?([^\s=(]+)", text)
    name = m.group(1) if m else text
    return re.sub(r"\.(\d+|remat\d*|clone)(?=\.|$)", "", name)


def program_name(text: str) -> str:
    """``jit__tick(123)`` -> ``_tick``."""
    m = re.match(r"^(?:jit_)?(.*?)(?:\(\d+\))?$", text)
    return m.group(1) if m else text


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    raw: dict = {"window": None, "devices": {}, "host": {}}
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            dev = raw["devices"].setdefault(
                plane.name, {"modules": [], "ops": [], "async": []})
            for line in plane.lines:
                key = _LINES.get(line.name)
                if key is None:
                    continue
                dev[key] = [[e.name, e.start_ns, e.duration_ns]
                            for e in line.events]
        elif plane.name == "/host:CPU":
            for k, line in enumerate(plane.lines):
                evs = [[e.name, e.start_ns, e.duration_ns]
                       for e in line.events if not e.name.startswith("$")]
                if evs:       # thread names repeat (or are empty): number them
                    raw["host"][f"{line.name}#{k}"] = evs
    for evs in raw["host"].values():
        for name, start, dur in evs:
            if name == WINDOW_SPAN:
                raw["window"] = [start, start + dur]
    if raw["window"] is None:
        # the profiler drops host events once its buffer is full, and a span
        # is recorded when it closes: fall back to the extent of the device's
        # own events, which then is the window
        ev = [e for d in raw["devices"].values() for e in d["ops"]]
        if not ev:
            raise ValueError(f"the trace holds neither a {WINDOW_SPAN!r} "
                             f"span nor a device operation")
        raw["window"] = [min(e[1] for e in ev), max(e[1] + e[2] for e in ev)]
    return raw


def _clip(events: list, lo: float, hi: float) -> list:
    out = []
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((name, a, b))
    return out


def _union(intervals: list) -> list:
    """Sorted, merged ``(a, b)`` intervals."""
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def _gaps(busy: list, lo: float, hi: float) -> list:
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _innermost(events: list) -> tuple:
    """One thread's spans (properly nested) as disjoint segments, each named
    by the innermost span open in it: ``(starts, segments)`` with
    ``segments[i] = (a, b, name, span_length)``, sorted for bisection."""
    segs: list = []
    stack: list = []                      # (end, name, length)
    t = 0.0
    for name, a, b in sorted(events, key=lambda e: (e[1], e[1] - e[2])):
        while stack and stack[-1][0] <= a:
            end, top, length = stack.pop()
            if end > t:
                segs.append((t, end, top, length))
                t = end
        if stack and a > t:
            segs.append((t, a, stack[-1][1], stack[-1][2]))
        t = max(t, a) if stack else a
        stack.append((b, name, b - a))
    while stack:
        end, top, length = stack.pop()
        if end > t:
            segs.append((t, end, top, length))
            t = end
    return [s[0] for s in segs], segs


def _span_at(threads: list, t: float) -> str:
    """The shortest host span that is open at time ``t``, over the threads'
    innermost segments."""
    best, best_dur = NO_SPAN, None
    for starts, segs in threads:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0:
            a, b, name, dur = segs[i]
            if a <= t < b and (best_dur is None or dur < best_dur):
                best, best_dur = name, dur
    return best


def reduce(raw: dict, spans: tuple = ()) -> dict:
    """The numbers of one traced window.

    ``busy_s`` is the time in which an operation ran on a device (the union
    of the ``XLA Ops`` intervals), averaged over the devices; ``programs``
    maps a program to the count, summed seconds and intervals of its runs
    on the first device; ``ops`` maps an operation to its summed seconds on
    the first device and ``op_intervals`` to its intervals (asynchronous
    ones from start to done); ``span_totals`` maps each of ``spans`` to its
    count and summed seconds.
    Idle gaps are attributed to the shortest host span open at the gap's
    middle, among the threads that carry one of ``spans`` (all threads if
    none does), and ``NO_SPAN`` where none is open.
    """
    lo, hi = raw["window"]
    window_s = (hi - lo) / 1e9
    names = sorted(raw["devices"])
    if not names:
        raise ValueError("the trace holds no TPU device plane")
    busy_each, first_busy = [], None
    for n in names:
        ops = _clip(raw["devices"][n]["ops"], lo, hi)
        merged = _union([(a, b) for _, a, b in ops])
        busy_each.append(sum(b - a for a, b in merged) / 1e9)
        if first_busy is None:
            first_busy = merged
    first = raw["devices"][names[0]]
    programs: dict = {}
    for name, a, b in _clip(first["modules"], lo, hi):
        p = programs.setdefault(program_name(name),
                                {"count": 0, "total_s": 0.0, "runs": []})
        p["count"] += 1
        p["total_s"] += (b - a) / 1e9
        p["runs"].append((a, b))
    ops: dict = {}
    op_intervals: dict = {}
    for line in ("ops", "async"):
        for name, a, b in _clip(first[line], lo, hi):
            key = op_name(name)
            op_intervals.setdefault(key, []).append((a, b))
            # a loop or a call spans the operations inside it, which are on
            # the line too: counted, it would count them twice
            if line == "ops" and key not in CONTAINERS:
                ops[key] = ops.get(key, 0.0) + (b - a) / 1e9

    threads = [_clip([e for e in evs if e[0] != WINDOW_SPAN], lo, hi)
               for evs in raw["host"].values()]
    marked = [t for t in threads if any(name in spans for name, _, _ in t)]
    threads = marked or threads
    span_totals: dict = {}
    for t in threads:
        for name, a, b in t:
            if name in spans:
                n, tot = span_totals.get(name, (0, 0.0))
                span_totals[name] = (n + 1, tot + (b - a) / 1e9)
    gaps: dict = {}
    inner = [_innermost(t) for t in threads]
    for a, b in _gaps(first_busy, lo, hi):
        # a pause of under a microsecond between two operations of one
        # program is the device's own, not the host's
        name = (BETWEEN_OPS if b - a < 1000.0
                else _span_at(inner, 0.5 * (a + b)))
        gaps[name] = gaps.get(name, 0.0) + (b - a) / 1e9
    return {
        "window_s": window_s,
        "busy_s": sum(busy_each) / len(busy_each),
        "n_devices": len(names),
        "programs": programs,
        "ops": ops,
        "op_intervals": op_intervals,
        "span_totals": span_totals,
        "device_ops": [[k, v] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[k, v] for k, v in sorted(
            gaps.items(), key=lambda kv: -kv[1])],
    }


def union_seconds(intervals: list) -> float:
    return sum(b - a for a, b in _union(list(intervals))) / 1e9


def time_in(intervals: list, runs: list) -> float:
    """Seconds of ``intervals`` (a union) that fall inside ``runs``."""
    merged = _union(list(intervals))
    total = 0.0
    for ra, rb in runs:
        for a, b in merged:
            lo, hi = max(a, ra), min(b, rb)
            if hi > lo:
                total += hi - lo
    return total / 1e9


def main(argv: list) -> int:
    """``trace_reduce.py <trace dir or .xplane.pb> [raw.json]``: print the
    reduction and, with a second argument, keep the raw form."""
    path = argv[0]
    if os.path.isdir(path):
        path = find_xplane(path)
    raw = load_xplane(path)
    if len(argv) > 1:
        with open(argv[1], "w") as f:
            json.dump(raw, f)
    red = reduce(raw)
    red.pop("op_intervals")
    for p in red["programs"].values():
        p.pop("runs")
    print(json.dumps(red, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
