"""Readings the limits of ``correct`` are set from, taken on the chip at a
cell's own size in one process: for each seed the program's numbers against
the reference (a sound run), and for the first few seeds the control's (the
reference at the nearest lower precision, in the program's place).

    python3 benchmark/limits_probe.py <workload> <seconds> <control seeds> <seed> [<seed> ...]

Prints one line per seed and the largest sound and smallest control reading
of each number.  Not part of a benchmark run."""

from __future__ import annotations

import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import lib, run  # noqa: E402


def main(argv: list) -> int:
    workload, seconds, n_control = argv[0], float(argv[1]), int(argv[2])
    seeds = [int(s) for s in argv[3:]]
    _, cell, config, mix = run.load_cell(workload)
    device = run.check_device(int(cell["chips"]))
    run.compile_cache()
    family = lib.load_module("families", config["family"])
    sound, control = {}, {}
    for k, seed in enumerate(seeds):
        ctx = types.SimpleNamespace(
            t0=time.monotonic(), workload=workload, config=config, mix=mix,
            seed=seed, seconds=seconds, trace=False, chips=int(cell["chips"]),
            say=run.say, family=family, device_kind=device["kind"],
            memory_peak_bytes=lambda: 0)
        got = family.probe(ctx, control=k < n_control)
        for name, v in got["sound"].items():
            sound.setdefault(name, []).append(v)
        for name, v in got.get("control", {}).items():
            control.setdefault(name, []).append(v)
        print("PROBE", json.dumps({"seed": seed, **got}), flush=True)
    for name in sound:
        line = {"number": name, "sound_max": max(sound[name]),
                "sound": sorted(sound[name])}
        if name in control:
            line.update(control_min=min(control[name]),
                        control=sorted(control[name]),
                        ratio=min(control[name]) / max(max(sound[name]),
                                                       1e-30))
        print("LIMIT", json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
