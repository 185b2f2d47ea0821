"""What every part of the benchmark shares: where its files are, how a file
named in ``BENCHMARK.json`` becomes a module, the table of peaks, and the
few statistics the metric readers use.

Nothing here names a model, a traffic mix or a metric: those are files that
``BENCHMARK.json`` names (``configs/``, ``traffic/``, ``families/``,
``drivers/``, ``end_to_end/``, ``layer_metrics/``, ``reference/``), found by
name, so a later PR adds a cell or a metric by adding files and entries.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def load_json(*rel: str):
    with open(os.path.join(BENCH, *rel)) as f:
        return json.load(f)


def benchmark_spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json "
                     f"(have {[e['name'] for e in entries]})")


def has_module(kind: str, name: str) -> bool:
    return os.path.isfile(os.path.join(BENCH, kind, name + ".py"))


def load_module(kind: str, name: str):
    """The module ``benchmark/<kind>/<name>.py``.  ``name`` may hold dots
    (``tick_dev_ms.tput``), so it is loaded by path, once."""
    if not _NAME.match(name):
        raise SystemExit(f"benchmark: {name!r} is not a name")
    key = "benchmark_" + kind + "__" + re.sub(r"[^A-Za-z0-9_]", "_", name)
    if key in sys.modules:
        return sys.modules[key]
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: {kind}/{name}.py is not there")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_cells(metric: dict, spec: dict, moves: str | None = None) -> list:
    """The cells a metric is reported in: its ``workloads`` key, or every
    cell that reports the end-to-end metric it moves (every cell, for an
    end-to-end metric without the key)."""
    if "workloads" in metric:
        return list(metric["workloads"])
    if moves is None:
        return [w["name"] for w in spec["workloads"]]
    target = find(spec["end_to_end"], moves, "end-to-end metric")
    return metric_cells(target, spec)


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and bytes/s of one chip.  A device that is not in
    ``peaks.json`` is an error, never a default."""
    table = load_json("peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       f"benchmark/peaks.json ({sorted(table)})")
    return table[device_kind]


def share_of_peak(value: float, peak: float, what: str) -> float:
    """``value / peak`` in percent.  A share over 100 % means the operations
    or bytes are counted too high or the time leaves out part of the work:
    that is an error here, never clipped."""
    pct = 100.0 * value / peak
    if pct > 100.0:
        raise ValueError(f"{what}: {pct:.2f} % of the peak — the count is "
                         f"too high or the time too short")
    return pct


def device_idle_pct(rec: dict):
    """The share of the traced window in which no operation ran on the
    device (averaged over the chips used)."""
    tr = rec.get("trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def hbm_peak_gb(rec: dict):
    """The run's ``memory_peak_bytes`` in GB, read when the window closes and
    before the reference runs."""
    return rec["memory_peak_bytes"] / 1e9


def quantile(values, q: float) -> float:
    """Linear-interpolated quantile of a sample (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("quantile of nothing")
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def mean(values) -> float:
    values = list(values)
    if not values:
        raise ValueError("mean of nothing")
    return sum(values) / len(values)
