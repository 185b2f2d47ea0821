"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It runs one cell of ``BENCHMARK.json`` on the machine it is started on,
which has to hold a TPU with as many chips as the cell asks for, and prints
as the last line of standard output one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, in a traced run,
``breakdown``.  With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics.

This file holds no name of a model, a traffic mix or a metric.  The cell
names a configuration (``configs/<config>.json``, whose ``family`` names
``families/<family>.py``) and a traffic mix (``traffic/<mix>.json``, whose
``driver`` names ``drivers/<driver>.py``); each metric is a reader of its own
in ``end_to_end/`` or ``layer_metrics/``.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()          # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import lib  # noqa: E402


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:7.2f}s] {msg}", flush=True)


def check_device(chips: int) -> dict:
    """The accelerator this run measures, as jax reports it.  Anything but a
    TPU with at least ``chips`` chips ends the run with no result line."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" or jax.device_count() < chips:
        print(f"benchmark: the cell needs {chips} TPU chip(s), but jax found "
              f"platform={dev.platform!r} ({dev.device_kind!r}, "
              f"{jax.device_count()} device(s)); not running",
              file=sys.stderr)
        raise SystemExit(3)
    lib.peaks(dev.device_kind)      # an unknown chip is an error up front
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": jax.device_count()}


def compile_cache() -> str:
    """jax's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else the fixed ``<checkout>/.jax_cache``; every program is kept,
    however quick its compile, so a second run compiles nothing."""
    import jax
    from horovod_tpu.utils.env import compile_cache_dir

    path = compile_cache_dir(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips used."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def load_cell(workload: str) -> tuple:
    """``(spec, cell, config, mix)`` of one cell of ``BENCHMARK.json``."""
    spec = lib.benchmark_spec()
    cell = lib.find(spec["workloads"], workload, "workload")
    cfg_entry = lib.find(spec["configs"], cell["config"], "configuration")
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    return spec, cell, config, lib.load_json("traffic",
                                             cell["traffic"] + ".json")


def run_cell(workload: str, seed: int, seconds: float, trace: bool) -> dict:
    """Run one cell and return its result line as a dict."""
    spec, cell, config, mix = load_cell(workload)
    chips = int(cell["chips"])
    device = check_device(chips)
    cache = compile_cache()
    say(f"cell={workload} config={cell['config']} traffic={cell['traffic']} "
        f"chips={chips} seed={seed} seconds={seconds} trace={int(trace)} "
        f"device={device} cache={cache}")

    ctx = types.SimpleNamespace(
        t0=_T0, workload=workload, config=config, mix=mix, seed=int(seed),
        seconds=float(seconds), trace=bool(trace), chips=chips, say=say,
        family=lib.load_module("families", config["family"]),
        memory_peak_bytes=lambda: memory_peak_bytes(chips),
        device_kind=device["kind"])
    rec = lib.load_module("drivers", mix["driver"]).run(ctx)
    rec.setdefault("device_kind", device["kind"])
    rec.setdefault("chips", chips)

    for c in rec["checks"]:
        say(f"check {c['name']}: value={c['value']!r} limit={c['limit']!r} "
            f"{'ok' if c['ok'] else 'NOT CORRECT'}")
    kind, entries = (("layer_metrics", spec["per_layer"]) if trace
                     else ("end_to_end", spec["end_to_end"]))
    metrics = {}
    for m in entries:
        if workload not in lib.metric_cells(m, spec, m.get("moves")):
            continue
        value = lib.load_module(kind, m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device["memory_peak_bytes"] = int(rec["memory_peak_bytes"])
    out = {"correct": bool(rec["checks"]) and all(c["ok"]
                                                  for c in rec["checks"]),
           "attempted": int(rec["attempted"]), "failed": int(rec["failed"]),
           "metrics": metrics, "device": device}
    if trace:
        tr = rec["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"][:10],
                            "idle_gaps": tr["idle_gaps"][:10]}
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    sys.exit(code)
