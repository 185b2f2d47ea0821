"""Eager-engine launch-overhead microbench (CPU sim).

The reference's eager cost story is its 5 ms background cycle + per-op
negotiation (reference horovod/common/operations.cc:151-155 — the knobs
`HOROVOD_CYCLE_TIME`/`HOROVOD_FUSION_THRESHOLD` exist because per-op
launch overhead dominates many-small-tensor models).  This measures our
engine's analogue where it is actually indicative — the host-side
dispatch path on the CPU sim, where the collective itself is ~free and
whatever remains IS the engine overhead:

* ops/sec for 1-KiB eager allreduces, posted async in bursts (the
  gradient-hook shape) and drained;
* fused (default 64 MiB threshold: the whole burst merges into one
  dispatch) vs solo (`HOROVOD_FUSION_THRESHOLD=0`: one dispatch per
  tensor) — Tensor Fusion's launch-overhead win in isolation;
* single-process engine vs 2-process native-controller gang (adds TCP
  negotiation per cycle).

Usage:
    python tools/eager_overhead_bench.py                 # orchestrates all arms
    python tools/eager_overhead_bench.py --mode single   # one arm, this process
    python tools/eager_overhead_bench.py --mode worker   # rank of a 2-proc gang

Prints one ``RESULT {json}`` line per arm; the orchestrator ends with
``SUMMARY {json}``.  Smoke-tested by tests/test_bench_helpers.py.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TENSOR_ELEMS = 256          # 256 f32 = 1 KiB, the reference's "small tensor"
BURST = int(os.environ.get("EAGER_OVH_BURST", "32"))   # tensors per burst
ROUNDS = int(os.environ.get("EAGER_OVH_ROUNDS", "8"))  # bursts timed
WARMUP_ROUNDS = 2


def _force_cpu() -> None:
    """Before jax is imported.  Only the worker modes come here: the
    orchestrating parent never touches jax."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if REPO not in sys.path:
        sys.path.insert(0, REPO)


def _measure(tag: str) -> dict:
    """Run the burst loop on the CURRENT engine config; returns the arm
    record.  Must be called after hvd.init().

    Bursts go through ``grouped_allreduce_eager`` — caller-delimited, so
    bucket composition is DETERMINISTIC round to round and each arm
    compiles its dispatch program(s) once in warmup.  Timing-driven flush
    (the raw async-post pattern) varies composition with scheduler jitter,
    and on XLA every novel composition is a fresh compile
    (docs/tensor-fusion.md "Determinism and compile churn") — that would
    measure the compiler, not the launch overhead.  The threshold knob
    still controls bucketing *within* the group: 64 MiB → one fused
    dispatch per burst, 0 → one dispatch per tensor."""
    import jax
    import numpy as np

    import horovod_tpu as hvd

    n = hvd.size()
    rng = np.random.RandomState(0)
    bufs = [
        rng.randn(n, TENSOR_ELEMS).astype(np.float32) for _ in range(BURST)
    ]

    def one_round() -> None:
        outs = hvd.grouped_allreduce_eager(bufs, average=True)
        jax.block_until_ready(outs)

    for _ in range(WARMUP_ROUNDS):
        one_round()
    stats0 = hvd.engine_stats()
    t0 = time.perf_counter()
    for _ in range(ROUNDS):
        one_round()
    dt = time.perf_counter() - t0

    ops = ROUNDS * BURST
    stats = hvd.engine_stats()
    return {
        "arm": tag,
        "ops_per_sec": round(ops / dt, 1),
        "us_per_op": round(dt / ops * 1e6, 1),
        "tensors_fused":
            stats.get("tensors_fused", 0) - stats0.get("tensors_fused", 0),
        "batches_dispatched": stats.get("batches_dispatched", 0)
            - stats0.get("batches_dispatched", 0),
    }


def _run_single(threshold: str) -> None:
    _force_cpu()
    os.environ["HOROVOD_FUSION_THRESHOLD"] = threshold
    os.environ.setdefault("HOROVOD_CYCLE_TIME", "1")
    import horovod_tpu as hvd

    hvd.init()
    tag = "fused" if threshold != "0" else "solo"
    print("RESULT " + json.dumps(_measure(f"single.{tag}")), flush=True)
    hvd.shutdown()


def _run_worker() -> None:
    _force_cpu()
    os.environ.setdefault("HOROVOD_CYCLE_TIME", "1")
    import horovod_tpu as hvd

    hvd.init()
    tag = "fused" if os.environ.get("HOROVOD_FUSION_THRESHOLD") != "0" \
        else "solo"
    rec = _measure(f"gang2.{tag}")
    if hvd.rank() == 0:
        print("RESULT " + json.dumps(rec), flush=True)
    hvd.shutdown()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_arm(args: list[str], env_extra: dict) -> str:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), *args],
        env=env, capture_output=True, text=True, timeout=600,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"arm {args} {env_extra} failed rc={out.returncode}:\n"
            f"{out.stdout}\n{out.stderr}"
        )
    return out.stdout


def _spawn_gang(threshold: str) -> str:
    port = _free_port()
    ctl_port = _free_port()
    env_base = {
        "HOROVOD_TPU_COORDINATOR": f"127.0.0.1:{port}",
        "HOROVOD_TPU_NUM_PROCESSES": "2",
        "HOROVOD_FUSION_THRESHOLD": threshold,
        "HOROVOD_TPU_NATIVE_CONTROLLER": "on",
        "HOROVOD_TPU_CONTROLLER_TRANSPORT": f"tcp:127.0.0.1:{ctl_port}",
    }
    env = [dict(os.environ) for _ in range(2)]
    procs = []
    for pid in range(2):
        env[pid].pop("XLA_FLAGS", None)
        env[pid].update(JAX_PLATFORMS="cpu",
                        HOROVOD_TPU_PROCESS_ID=str(pid), **env_base)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--mode", "worker"],
            env=env[pid], stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        ))
    outs = [p.communicate(timeout=600)[0] for p in procs]
    for pid, p in enumerate(procs):
        if p.returncode != 0:
            raise RuntimeError(
                f"gang rank {pid} rc={p.returncode}:\n{outs[pid]}"
            )
    return "\n".join(outs)


def _collect(text: str) -> list[dict]:
    return [json.loads(line.split("RESULT ", 1)[1])
            for line in text.splitlines() if line.startswith("RESULT ")]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["orchestrate", "single", "worker"],
                    default="orchestrate")
    ap.add_argument("--threshold", default=None)
    args = ap.parse_args()

    if args.mode == "single":
        _run_single(args.threshold or
                    os.environ.get("HOROVOD_FUSION_THRESHOLD", ""))
        return
    if args.mode == "worker":
        _run_worker()
        return

    results: list[dict] = []
    for thr in (str(64 * 1024 * 1024), "0"):
        results += _collect(
            _spawn_arm(["--mode", "single", "--threshold", thr], {})
        )
    for thr in (str(64 * 1024 * 1024), "0"):
        results += _collect(_spawn_gang(thr))
    for r in results:
        print("RESULT " + json.dumps(r), flush=True)

    by = {r["arm"]: r for r in results}
    summary = {
        "tensor_bytes": TENSOR_ELEMS * 4,
        "burst": BURST,
        "fusion_speedup_single":
            round(by["single.fused"]["ops_per_sec"]
                  / by["single.solo"]["ops_per_sec"], 2),
        "fusion_speedup_gang2":
            round(by["gang2.fused"]["ops_per_sec"]
                  / by["gang2.solo"]["ops_per_sec"], 2),
        "controller_cost_us_per_op":
            round(by["gang2.fused"]["us_per_op"]
                  - by["single.fused"]["us_per_op"], 1),
        "arms": by,
    }
    print("SUMMARY " + json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
