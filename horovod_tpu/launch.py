"""``python -m horovod_tpu.launch`` — the multi-process launcher.

The reference launches with plain ``mpirun -np N python train.py``
(reference docs/running.md; no custom launcher).  On TPU there is no MPI;
this is the torchrun-shaped equivalent for the cases that need one process
per host (or per simulated worker): it spawns N copies of the script with
the coordination environment set, prefixes their output by rank, and
propagates the first failure.

    # 2-process CPU simulation of a 2-host job, eager TCP control plane:
    python -m horovod_tpu.launch --nproc 2 -- python train.py --epochs 1

On a real pod slice you usually do NOT need this: one process per host is
started by the platform (GKE/queued resources), and ``hvd.init()`` reads
``HOROVOD_TPU_COORDINATOR/NUM_PROCESSES/PROCESS_ID`` which the platform or
this launcher sets.

On a host with TPU chips a chip belongs to one process at a time, so
``--nproc`` above 1 needs ``--cpu`` there; without it the launcher refuses
at once (see ``_local_tpu_chips``).  The launcher itself never starts a jax
backend (importing this package imports jax, which opens nothing): a parent
that had would hold the chips.
"""

from __future__ import annotations

import argparse
import glob
import os
import signal
import socket
import subprocess
import sys
import threading


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# PCI ids of TPU chips, as jax keeps them (jax/_src/hardware_utils.py).
_GOOGLE_PCI_VENDOR_ID = "0x1ae0"
_TPU_PCI_DEVICE_IDS = frozenset({
    "0x0027",  # v2, v3
    "0x0056",
    "0x005e",  # v4
    "0x0062",  # v5p
    "0x0063",  # v5e
    "0x006f",  # v6e
    "0x0076",  # tpu7x
})


def _local_tpu_chips(sys_pci: str = "/sys/bus/pci/devices") -> int:
    """TPU chips attached to this host, counted from their PCI ids — the way
    jax decides a host has them, without asking jax for its devices."""
    chips = 0
    for vendor in glob.glob(os.path.join(sys_pci, "*", "vendor")):
        with open(vendor) as f:
            if f.read().strip() != _GOOGLE_PCI_VENDOR_ID:
                continue
        with open(os.path.join(os.path.dirname(vendor), "device")) as f:
            chips += f.read().strip() in _TPU_PCI_DEVICE_IDS
    return chips


def _stream(rank: int, pipe, out) -> None:
    for line in iter(pipe.readline, ""):
        out.write(f"[rank {rank}] {line}")
        out.flush()
    pipe.close()


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m horovod_tpu.launch",
        description="Spawn N coordinated worker processes on this host.",
    )
    p.add_argument("--nproc", type=int, required=True,
                   help="worker processes on THIS host")
    p.add_argument("--nnodes", type=int, default=1,
                   help="total hosts in the job (world = nnodes * nproc)")
    p.add_argument("--node-rank", type=int, default=0,
                   help="this host's index in [0, nnodes)")
    p.add_argument("--coordinator", default=None,
                   help="host:port of process 0 (default: 127.0.0.1:auto; "
                        "REQUIRED when nnodes > 1 — every host must name "
                        "node 0's address)")
    p.add_argument("--controller-transport", default=None,
                   help="native control plane, e.g. tcp:<node0>:9876 "
                        "(default: tcp on an auto local port; REQUIRED when "
                        "nnodes > 1)")
    p.add_argument("--cpu", action="store_true",
                   help="pin workers to the CPU backend (simulation)")
    p.add_argument("--restarts", type=int, default=0,
                   help="relaunch the whole gang up to N times after a "
                        "failure (fault tolerance without in-job world "
                        "resize: workers resume via latest_checkpoint() + "
                        "restore_checkpoint() at startup — see "
                        "docs/running.md, 'The launcher')")
    p.add_argument("cmd", nargs=argparse.REMAINDER,
                   help="-- command to run (e.g. -- python train.py)")
    args = p.parse_args(argv)
    cmd = args.cmd
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        p.error("no command given; usage: ... --nproc 2 -- python train.py")
    if args.nproc < 1:
        p.error(f"--nproc must be >= 1, got {args.nproc}")
    if not 0 <= args.node_rank < args.nnodes:
        p.error(f"--node-rank {args.node_rank} outside [0, {args.nnodes})")
    if args.nnodes > 1 and not (args.coordinator and args.controller_transport):
        p.error(
            "nnodes > 1 requires explicit --coordinator and "
            "--controller-transport (auto-picked local ports would differ "
            "per host)"
        )

    chips = 0 if args.cpu or args.nproc == 1 else _local_tpu_chips()
    if chips:
        p.error(
            f"this host has TPU chips ({chips} on its PCI bus) and a chip "
            f"belongs to one process at a time: {args.nproc} workers that "
            f"each open every chip would fail or hang.  Use --nproc 1 (one "
            f"process drives all the chips it is given, the usual way) or "
            f"--cpu"
        )

    if args.restarts < 0:
        p.error(f"--restarts must be >= 0, got {args.restarts}")
    if args.restarts and args.nnodes > 1:
        p.error(
            "--restarts only coordinates a single-host gang; multi-host "
            "restart needs an external supervisor on every node"
        )
    if args.restarts and (args.coordinator or args.controller_transport):
        print(
            "horovod_tpu.launch: warning: --restarts with explicit "
            "--coordinator/--controller-transport rebinds the SAME ports "
            "every attempt; a relaunch can fail to bind while the dead "
            "gang's connections sit in TIME_WAIT.  Prefer auto ports "
            "(omit the flags) for restartable single-host gangs.",
            file=sys.stderr,
        )

    world = args.nnodes * args.nproc
    for attempt in range(args.restarts + 1):
        # Fresh auto ports per attempt: the dead gang's coordinator/
        # controller listeners may linger in TIME_WAIT.
        coordinator = args.coordinator or f"127.0.0.1:{_free_port()}"
        transport = (
            args.controller_transport or f"tcp:127.0.0.1:{_free_port()}"
        )
        rc = _run_gang(args, cmd, world, coordinator, transport)
        if rc == 0 or rc == 130 or attempt == args.restarts:
            return rc
        print(
            f"horovod_tpu.launch: gang failed (rc={rc}); restarting "
            f"({attempt + 1}/{args.restarts}) — workers resume from their "
            "latest checkpoint",
            file=sys.stderr,
        )
    raise AssertionError("unreachable: the loop returns on its last pass")


def _run_gang(args, cmd, world: int, coordinator: str,
              transport: str) -> int:
    procs: list[subprocess.Popen] = []
    streams: list[threading.Thread] = []
    for i in range(args.nproc):
        pid = args.node_rank * args.nproc + i
        env = dict(os.environ)
        env.update(
            HOROVOD_TPU_COORDINATOR=coordinator,
            HOROVOD_TPU_NUM_PROCESSES=str(world),
            HOROVOD_TPU_PROCESS_ID=str(pid),
            HOROVOD_TPU_CONTROLLER_TRANSPORT=transport,
            # Per-host topology (reference MPI_COMM_TYPE_SHARED split,
            # operations.cc:1558-1590): the launcher spawned exactly
            # --nproc workers on this host, so it is the authority.
            HOROVOD_TPU_LOCAL_RANK=str(i),
            HOROVOD_TPU_LOCAL_SIZE=str(args.nproc),
        )
        if args.cpu:
            env["JAX_PLATFORMS"] = "cpu"
            env.pop("XLA_FLAGS", None)
        proc = subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        procs.append(proc)
        t = threading.Thread(
            target=_stream, args=(pid, proc.stdout, sys.stdout), daemon=True
        )
        t.start()
        streams.append(t)

    rc = 0
    first_failed = None
    try:
        # Gang semantics (mpirun/torchrun): the first worker failure tears
        # the rest down — survivors would otherwise block forever inside a
        # collective waiting for the dead rank.  terminate() escalates to
        # kill() after a grace period for workers that trap SIGTERM.
        import time as _time

        live = set(range(len(procs)))
        terminated_at = None
        while live:
            for i in sorted(live):
                code = procs[i].poll()
                if code is None:
                    continue
                live.discard(i)
                if code != 0 and rc == 0 and terminated_at is None:
                    rc, first_failed = code, i
                    print(
                        f"horovod_tpu.launch: worker {i} exited rc={code}; "
                        "terminating the remaining workers",
                        file=sys.stderr,
                    )
                    terminated_at = _time.monotonic()
                    for j in live:
                        if procs[j].poll() is None:
                            procs[j].terminate()
            if live:
                if (terminated_at is not None
                        and _time.monotonic() - terminated_at > 15.0):
                    for j in live:
                        if procs[j].poll() is None:
                            procs[j].kill()
                    terminated_at = float("inf")  # escalate once
                _time.sleep(0.2)
    except KeyboardInterrupt:
        rc = 130
        for proc in procs:
            if proc.poll() is None:
                proc.send_signal(signal.SIGINT)
        for proc in procs:
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
        for t in streams:
            t.join(timeout=5)
    if rc:
        # Report only genuine failures — not survivors the launcher itself
        # SIGTERM/SIGKILLed (negative returncode) or never waited on.
        failed = [i for i, pr in enumerate(procs)
                  if pr.returncode is not None and pr.returncode > 0]
        if first_failed is not None and first_failed not in failed:
            failed.append(first_failed)
        print(f"horovod_tpu.launch: worker(s) {sorted(failed)} failed "
              f"(rc={rc})", file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
