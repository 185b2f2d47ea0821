"""True multi-process end-to-end test: real OS processes, TCP control plane.

The reference's entire CI runs under ``mpirun -np 2`` — real separate
processes (reference: .travis.yml; SURVEY.md §4).  This is the TPU-native
analogue: two Python workers, each driving one CPU device, joined into one
world via ``jax.distributed`` (the data plane) and the native TCP
controller (the eager control plane).  Everything else in the suite runs
single-process on a virtual mesh; only this file proves the multi-host
claims under actual process separation.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = os.path.join(HERE, "multiprocess_worker.py")
MONITOR_WORKER = os.path.join(HERE, "multiprocess_monitor_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_workers(worker: str, nproc: int, env_overrides: dict,
                 *, drop: tuple[str, ...] = (), timeout: int = 300):
    """Spawn ``nproc`` copies of ``worker`` with the coordination env set;
    on timeout, kill survivors and fail with the captured output.  Returns
    the per-worker outputs after asserting rc == 0."""
    coord_port = _free_port()
    procs = []
    for pid in range(nproc):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # each worker drives ONE cpu device
        for var in drop:
            env.pop(var, None)
        env.update(
            JAX_PLATFORMS="cpu",
            HOROVOD_TPU_COORDINATOR=f"127.0.0.1:{coord_port}",
            HOROVOD_TPU_NUM_PROCESSES=str(nproc),
            HOROVOD_TPU_PROCESS_ID=str(pid),
        )
        env.update(env_overrides)
        procs.append(
            subprocess.Popen(
                [sys.executable, worker], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
        )

    outs: list[str | None] = [None] * nproc
    try:
        for i, p in enumerate(procs):
            out, _ = p.communicate(timeout=timeout)
            outs[i] = out
    except subprocess.TimeoutExpired:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for i, p in enumerate(procs):
            if outs[i] is None:
                try:
                    outs[i], _ = p.communicate(timeout=10)
                except Exception:
                    outs[i] = "<output unavailable>"
        pytest.fail(
            "multi-process workers timed out (deadlock?):\n"
            + "\n---\n".join(o or "" for o in outs)
        )

    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed (rc={p.returncode}):\n{out}"
    return outs


@pytest.mark.slow
def test_two_process_end_to_end(tmp_path):
    outs = _run_workers(
        WORKER, 2,
        {
            "HOROVOD_TPU_NATIVE_CONTROLLER": "on",
            "HOROVOD_TPU_CONTROLLER_TRANSPORT": f"tcp:127.0.0.1:{_free_port()}",
            # rank 0 writes the timeline; the worker asserts per-rank ticks
            "HOROVOD_TIMELINE": str(tmp_path / "mp_timeline.json"),
        },
    )
    for i, out in enumerate(outs):
        assert "WORKER_OK" in out, f"worker {i} no OK line:\n{out}"


@pytest.mark.slow
def test_two_process_metric_aggregation():
    """Cross-rank observability acceptance: ``aggregate_snapshots()``
    over the real allgather plane returns the SAME fleet view on every
    rank — byte-identical payloads — with the merged histogram equal to
    the union of both ranks' observations (each worker checks that
    exactly; see multiprocess_monitor_worker.py)."""
    outs = _run_workers(
        MONITOR_WORKER, 2,
        {
            "HOROVOD_TPU_NATIVE_CONTROLLER": "on",
            "HOROVOD_TPU_CONTROLLER_TRANSPORT":
                f"tcp:127.0.0.1:{_free_port()}",
        },
    )
    payloads = []
    for i, out in enumerate(outs):
        assert "WORKER_OK" in out, f"worker {i} no OK line:\n{out}"
        payloads.append(
            out.split("WORKER_OK ", 1)[1].splitlines()[0])
    assert payloads[0] == payloads[1], (
        "fleet views differ across ranks:\n" + "\n---\n".join(payloads))
    fleet = json.loads(payloads[0])["fleet"]
    assert fleet["counters"]["serve.steps"] == 30          # 10 + 20
    assert fleet["histograms"]["serve.e2e_s"]["count"] == 100


@pytest.mark.slow
def test_three_process_process_sets_and_adasum(tmp_path):
    """ProcessSet subset reductions, the Adasum tree, and root-only-read
    checkpoint restore with REAL process boundaries inside and outside
    the member set (3 workers, 1 CPU device each, native TCP
    controller)."""
    outs = _run_workers(
        os.path.join(HERE, "multiprocess_features_worker.py"), 3,
        {
            "HOROVOD_TPU_NATIVE_CONTROLLER": "on",
            "HOROVOD_TPU_CONTROLLER_TRANSPORT": f"tcp:127.0.0.1:{_free_port()}",
            "FEATURES_CKPT_DIR": str(tmp_path / "feat_ck"),
        },
    )
    for i, out in enumerate(outs):
        assert "WORKER_OK" in out, f"worker {i} no OK line:\n{out}"


@pytest.mark.slow
def test_two_process_degraded_python_coordination():
    """Multi-host eager WITHOUT a controller transport: the engine must
    warn, fall back to Python coordination, and caller-delimited fusion
    groups must stay correct and deadlock-free across real processes
    (the degraded mode's cross-host safety claim in eager.py)."""
    from horovod_tpu import native

    if not native.available():
        pytest.skip("libhvdtpu.so unavailable — the fallback under test "
                    "is the no-transport one, not native-unavailability")
    outs = _run_workers(
        os.path.join(HERE, "multiprocess_degraded_worker.py"), 2,
        {"HOROVOD_TPU_NATIVE_CONTROLLER": "auto"},
        drop=("HOROVOD_TPU_CONTROLLER_TRANSPORT",),
    )
    for out in outs:
        assert "DEGRADED_OK" in out, out
        assert "falling back to Python coordination" in out, (
            "expected the degraded-mode warning"
        )


@pytest.mark.slow
def test_launcher_module_runs_two_workers():
    """python -m horovod_tpu.launch --nproc 2 --cpu -- <worker>: the
    reference's ``mpirun -np 2`` launch story (docs/running.md there)."""
    env = dict(os.environ)
    env["HOROVOD_TPU_NATIVE_CONTROLLER"] = "on"
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.launch", "--nproc", "2",
         "--cpu", "--", sys.executable, WORKER],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(HERE),
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.count("WORKER_OK") == 2, r.stdout
    assert "[rank 0]" in r.stdout and "[rank 1]" in r.stdout


def test_launcher_gang_teardown_on_failure(tmp_path):
    """One crashed worker must bring the gang down promptly (survivors
    would otherwise block in a collective forever)."""
    bad = tmp_path / "bad_worker.py"
    bad.write_text(
        "import os, sys, time\n"
        "if os.environ['HOROVOD_TPU_PROCESS_ID'] == '1':\n"
        "    sys.exit(3)\n"
        "time.sleep(300)\n"  # survivor blocks; launcher must kill it
    )
    import time as _t
    t0 = _t.monotonic()
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.launch", "--nproc", "2",
         "--cpu", "--", sys.executable, str(bad)],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(HERE),
    )
    took = _t.monotonic() - t0
    assert r.returncode == 3, (r.returncode, r.stdout, r.stderr)
    assert took < 60, f"gang teardown took {took:.0f}s"
    assert "terminating the remaining workers" in r.stderr


def test_launcher_rejects_bad_multihost_flags():
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.launch", "--nproc", "2",
         "--nnodes", "2", "--", "true"],
        capture_output=True, text=True, timeout=60,
        cwd=os.path.dirname(HERE),
    )
    assert r.returncode == 2
    assert "--coordinator" in r.stderr


def test_launcher_escalates_to_kill_for_sigterm_trappers(tmp_path):
    """A survivor that traps SIGTERM must still be brought down (term→kill
    escalation after the grace period)."""
    bad = tmp_path / "trap_worker.py"
    bad.write_text(
        "import os, signal, sys, time\n"
        "signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
        "if os.environ['HOROVOD_TPU_PROCESS_ID'] == '1':\n"
        "    time.sleep(1); sys.exit(5)\n"
        "time.sleep(300)\n"
    )
    import time as _t
    t0 = _t.monotonic()
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.launch", "--nproc", "2",
         "--cpu", "--", sys.executable, str(bad)],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(HERE),
    )
    took = _t.monotonic() - t0
    assert r.returncode == 5, (r.returncode, r.stderr)
    assert took < 60, f"term->kill escalation took {took:.0f}s"
    assert "worker(s) [1] failed" in r.stderr


def test_launcher_refuses_workers_that_would_share_tpu_chips(
        tmp_path, monkeypatch, capsys):
    """A chip belongs to one process at a time.  On a host with TPU chips,
    --nproc > 1 without --cpu would start workers that each open every
    chip; the launcher says so and exits at once instead of hanging.  Chips
    are told from other devices by PCI vendor and device id."""
    from horovod_tpu import launch

    cards = [("0x1ae0", "0x0063")] * 4      # four v5e chips
    cards += [("0x1ae0", "0x0042"),         # the same vendor's NIC
              ("0x8086", "0x0063")]         # another vendor, same device id
    for i, (vendor, device) in enumerate(cards):
        d = tmp_path / f"0000:00:{i:02x}.0"
        d.mkdir()
        (d / "vendor").write_text(vendor + "\n")
        (d / "device").write_text(device + "\n")
    assert launch._local_tpu_chips(str(tmp_path)) == 4
    assert launch._local_tpu_chips(str(tmp_path / "absent")) == 0

    monkeypatch.setattr(launch, "_local_tpu_chips", lambda: 4)
    for nproc in ("2", "4"):
        with pytest.raises(SystemExit) as exc:
            launch.main(["--nproc", nproc, "--", "true"])
        assert exc.value.code == 2
        err = capsys.readouterr().err
        assert "a chip belongs to one process" in err and "--cpu" in err
    # --cpu and --nproc 1 are untouched by any of this.
    assert launch.main(["--nproc", "2", "--cpu", "--", "true"]) == 0
    assert launch.main(["--nproc", "1", "--", "true"]) == 0


def test_launcher_rejects_nproc_zero():
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.launch", "--nproc", "0",
         "--", "true"],
        capture_output=True, text=True, timeout=60,
        cwd=os.path.dirname(HERE),
    )
    assert r.returncode == 2 and "--nproc" in r.stderr


def test_launcher_restarts_gang_until_success(tmp_path):
    """--restarts N: a gang that fails once and succeeds on relaunch ends
    with rc 0 (the resume-from-checkpoint fault-tolerance recipe);
    with --restarts 0 the same failure is final."""
    flaky = tmp_path / "flaky_worker.py"
    marker = tmp_path / "attempted"
    flaky.write_text(
        "import os, sys\n"
        f"marker = {str(marker)!r}\n"
        "if not os.path.exists(marker):\n"
        "    if os.environ['HOROVOD_TPU_PROCESS_ID'] == '0':\n"
        "        open(marker, 'w').close()\n"
        "    sys.exit(5)\n"
        "print('recovered')\n"
    )
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.launch", "--nproc", "2",
         "--cpu", "--restarts", "2", "--", sys.executable, str(flaky)],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(HERE),
    )
    assert r.returncode == 0, (r.returncode, r.stdout, r.stderr)
    assert "restarting (1/2)" in r.stderr, r.stderr
    assert "recovered" in r.stdout

    marker.unlink()
    r0 = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.launch", "--nproc", "2",
         "--cpu", "--", sys.executable, str(flaky)],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(HERE),
    )
    assert r0.returncode == 5, (r0.returncode, r0.stderr)


def test_launcher_restarts_rejected_multihost():
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.launch", "--nproc", "1",
         "--nnodes", "2", "--node-rank", "0", "--restarts", "1",
         "--coordinator", "h:1", "--controller-transport", "tcp:h:2",
         "--", "true"],
        capture_output=True, text=True, timeout=60,
        cwd=os.path.dirname(HERE),
    )
    assert r.returncode == 2
    assert "external supervisor" in r.stderr


@pytest.mark.slow
def test_torch_adapter_two_processes(tmp_path):
    """horovod_tpu.torch under the reference's exact process model: two OS
    processes, one CPU device each, torch tensors on the wire, hook-based
    DistributedOptimizer keeping ranks identical (+ TorchState elastic
    sync/restore fan-out across the real process boundary)."""
    outs = _run_workers(
        os.path.join(HERE, "multiprocess_torch_worker.py"), 2,
        {
            "HOROVOD_TPU_NATIVE_CONTROLLER": "on",
            "HOROVOD_TPU_CONTROLLER_TRANSPORT": f"tcp:127.0.0.1:{_free_port()}",
            "TORCH_ELASTIC_CKPT": str(tmp_path / "torch_el_ck"),
        },
    )
    for i, out in enumerate(outs):
        assert "TORCH_OK" in out, f"worker {i} no OK line:\n{out}"


def test_torch_adapter_rejects_multi_device_controller():
    """In a single-controller multi-device world the torch adapter must
    refuse with a pointer to the JAX-native API — and leave the world
    SHUT DOWN so that pointer's advice (re-init natively) actually works."""
    import horovod_tpu as hvd
    import horovod_tpu.torch as hvdt

    try:
        with pytest.raises(RuntimeError, match="ONE device per process"):
            hvdt.init()
        assert not hvd.is_initialized()
    finally:
        hvd.init()   # restore the session world for later tests


@pytest.mark.slow
def test_pytorch_mnist_example_via_launcher():
    """The reference's headline torch example, launched the reference way
    (one process per device) — convergence smoke across 2 real processes."""
    env = dict(os.environ)
    env["HOROVOD_TPU_NATIVE_CONTROLLER"] = "on"
    # The example is run as a script (its dir joins sys.path, the repo root
    # does not); an installed package wouldn't need this.
    env["PYTHONPATH"] = os.path.dirname(HERE) + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.launch", "--nproc", "2",
         "--cpu", "--", sys.executable,
         os.path.join(os.path.dirname(HERE), "examples", "pytorch_mnist.py"),
         "--epochs", "1", "--samples", "256", "--batch-size", "16"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(HERE),
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "final loss (rank-averaged):" in r.stdout


@pytest.mark.slow
def test_pytorch_synthetic_benchmark_via_launcher():
    env = dict(os.environ)
    env["HOROVOD_TPU_NATIVE_CONTROLLER"] = "on"
    env["PYTHONPATH"] = os.path.dirname(HERE) + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.launch", "--nproc", "2",
         "--cpu", "--", sys.executable,
         os.path.join(os.path.dirname(HERE), "examples",
                      "pytorch_synthetic_benchmark.py"),
         "--smoke", "--model", "mlp", "--batch-size", "4"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(HERE),
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "Total img/sec on 2 worker(s):" in r.stdout


@pytest.mark.slow
def test_pytorch_imagenet_resume_after_crash(tmp_path):
    """The reference's canonical fault-recovery recipe end-to-end
    (reference examples/pytorch_imagenet_resnet50.py:62-75,134-142):
    launch 1 saves epoch-1's checkpoint on rank 0 then dies abruptly
    (os._exit mid-gang); launch 2 finds the checkpoint, broadcasts
    resume_from_epoch, loads on rank 0, broadcast_parameters +
    broadcast_optimizer_state, and finishes the remaining epoch."""
    env = dict(os.environ)
    env["HOROVOD_TPU_NATIVE_CONTROLLER"] = "on"
    env["PYTHONPATH"] = os.path.dirname(HERE) + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    script = os.path.join(os.path.dirname(HERE), "examples",
                          "pytorch_imagenet_resnet50.py")
    ckpt_dir = str(tmp_path / "ckpts")
    base = [sys.executable, "-m", "horovod_tpu.launch", "--nproc", "2",
            "--cpu", "--", sys.executable, script, "--smoke",
            "--checkpoint-dir", ckpt_dir]

    r1 = subprocess.run(base + ["--crash-after", "1"], env=env,
                        capture_output=True, text=True, timeout=300,
                        cwd=os.path.dirname(HERE))
    assert r1.returncode != 0, "crash injection should fail the gang"
    assert "CRASH-INJECTED after epoch 1" in r1.stdout, r1.stdout + r1.stderr
    assert os.path.exists(os.path.join(ckpt_dir, "checkpoint-1.pt"))

    r2 = subprocess.run(base, env=env, capture_output=True, text=True,
                        timeout=300, cwd=os.path.dirname(HERE))
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "resumed_from 1" in r2.stdout, r2.stdout
    # Only the post-resume epoch ran in launch 2.
    assert "epoch 2:" in r2.stdout and "epoch 1:" not in r2.stdout
    assert os.path.exists(os.path.join(ckpt_dir, "checkpoint-2.pt"))


@pytest.mark.slow
def test_control_plane_autotune_two_processes():
    """HOROVOD_AUTOTUNE over the native controller (the multi-host config
    the r2 engine refused): rank 0 tunes, installs moves via SetTuned, the
    threshold governs rank-0's BuildBatches for the whole gang, and the
    (threshold, cycle) pair piggybacks on every response — the worker
    asserts every rank's config moved IDENTICALLY off the default."""
    outs = _run_workers(
        os.path.join(HERE, "multiprocess_autotune_worker.py"), 2,
        {
            "HOROVOD_TPU_NATIVE_CONTROLLER": "on",
            "HOROVOD_TPU_CONTROLLER_TRANSPORT": f"tcp:127.0.0.1:{_free_port()}",
            "HOROVOD_AUTOTUNE": "1",
            "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "1",
            "HOROVOD_AUTOTUNE_STEADY_STATE_SAMPLES": "4",
        },
        timeout=420,
    )
    finals = set()
    for i, out in enumerate(outs):
        assert "AUTOTUNE_OK" in out, f"worker {i} no OK line:\n{out}"
        line = [l for l in out.splitlines() if l.startswith("AUTOTUNE_OK")][0]
        finals.add(json.loads(line.split(" ", 1)[1])["final_threshold"])
    assert len(finals) == 1, f"ranks converged to different thresholds: {finals}"


@pytest.mark.slow
def test_gang4_ragged_process_sets_restart(tmp_path):
    """nproc=4 over the TCP controller: ragged allgather, two process
    sets spanning real process boundaries, then a mid-run rank-2 kill
    recovered by the launcher's --restarts gang restart — wider and more
    failure-realistic than the reference CI's mpirun -np 2 everything
    (.travis.yml)."""
    env = dict(os.environ)
    # The launcher owns the controller transport (a fresh auto port per
    # restart attempt — launch.py avoids the TIME_WAIT rebind hazard of a
    # fixed port) and pops XLA_FLAGS itself under --cpu.
    env.update(
        HOROVOD_TPU_NATIVE_CONTROLLER="on",
        GANG4_MARKER=str(tmp_path / "gang4.attempted"),
    )
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.launch", "--nproc", "4",
         "--cpu", "--restarts", "2", "--", sys.executable,
         os.path.join(HERE, "multiprocess_gang4_worker.py")],
        env=env, capture_output=True, text=True, timeout=420,
        cwd=os.path.dirname(HERE),
    )
    assert r.returncode == 0, (r.returncode, r.stdout[-4000:], r.stderr[-4000:])
    assert "GANG4-KILL rank 2 dying mid-run" in r.stdout
    assert "restarting (1/2)" in r.stderr, r.stderr[-2000:]
    assert r.stdout.count("GANG4_OK") == 4, r.stdout[-4000:]


@pytest.mark.slow
def test_join_uneven_data_two_processes():
    """hvd.join() (Horovod >=0.21) under real process separation: rank 0
    exhausts its data and joins while rank 1 keeps reducing (zeros
    fabricated from the batch wire), join() returns the last joiner, the
    joined state resets per epoch, and non-plain ops error cleanly."""
    outs = _run_workers(
        os.path.join(HERE, "multiprocess_join_worker.py"), 2,
        {
            "HOROVOD_TPU_NATIVE_CONTROLLER": "on",
            "HOROVOD_TPU_CONTROLLER_TRANSPORT": f"tcp:127.0.0.1:{_free_port()}",
        },
    )
    for i, out in enumerate(outs):
        assert "JOIN_OK" in out, f"worker {i} no OK line:\n{out}"


@pytest.mark.slow
def test_two_controllers_two_devices_each():
    """VERDICT r3 #7: the real pod shape — 2 processes × 2 virtual CPU
    devices each (multi-chip controllers), exercising rank()/local_*,
    make_array_from_process_local_data with multi-row shards, and
    caller-delimited fusion across controllers."""
    outs = _run_workers(
        os.path.join(HERE, "multiprocess_multidev_worker.py"), 2,
        {
            "HOROVOD_TPU_NATIVE_CONTROLLER": "on",
            "HOROVOD_TPU_CONTROLLER_TRANSPORT": f"tcp:127.0.0.1:{_free_port()}",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        },
    )
    for i, out in enumerate(outs):
        assert "MULTIDEV_OK" in out, f"worker {i} no OK line:\n{out}"


@pytest.mark.slow
def test_launcher_local_topology_four_process_single_host(tmp_path):
    """VERDICT r3 #4: a 4-process single-host gang must see local_ranks
    {0,1,2,3} and local_size 4 through BOTH frontends (the reference's
    MPI_COMM_TYPE_SHARED per-host split, operations.cc:1558-1590) — the
    launcher is the topology authority via HOROVOD_TPU_LOCAL_RANK/SIZE."""
    worker = tmp_path / "topo_worker.py"
    worker.write_text(
        "import os, sys\n"
        f"sys.path.insert(0, {os.path.dirname(HERE)!r})\n"
        "import torch\n"
        "import horovod_tpu.torch as hvdt\n"
        "import horovod_tpu as hvd\n"
        "hvdt.init()\n"
        "lr, ls = hvdt.local_rank(), hvdt.local_size()\n"
        "assert (lr, ls) == (hvd.local_rank(), hvd.local_size())\n"
        "assert ls == 4, ls\n"
        "assert lr == int(os.environ['HOROVOD_TPU_PROCESS_ID']), lr\n"
        "seen = hvdt.allgather(torch.tensor([[lr]]), name='topo.lr')\n"
        "assert sorted(seen.flatten().tolist()) == [0, 1, 2, 3], seen\n"
        "hvdt.shutdown()\n"
        "print('TOPO_OK', lr, ls, flush=True)\n"
    )
    env = dict(os.environ)
    env["HOROVOD_TPU_NATIVE_CONTROLLER"] = "on"
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.launch", "--nproc", "4",
         "--cpu", "--", sys.executable, str(worker)],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(HERE),
    )
    assert r.returncode == 0, (r.returncode, r.stdout[-4000:], r.stderr[-4000:])
    assert r.stdout.count("TOPO_OK") == 4, r.stdout[-4000:]


@pytest.mark.slow
def test_elastic_gang_relaunch_resumes(tmp_path):
    """hvd.elastic end to end: durable sync commits every 2 batches, rank 1
    killed at batch 5, launcher --restarts relaunches the gang, and the
    relaunched run resumes from the batch-4 commit (asserted in-worker)
    to the uninterrupted-run final value.  Capability the 0.15.1 reference
    lacks (elastic arrived in Horovod 0.20; SURVEY §2.3)."""
    env = dict(os.environ)
    env.update(
        HOROVOD_TPU_NATIVE_CONTROLLER="on",
        ELASTIC_MARKER=str(tmp_path / "elastic.died"),
        ELASTIC_CKPT=str(tmp_path / "elastic_ck"),
    )
    r = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.launch", "--nproc", "2",
         "--cpu", "--restarts", "2", "--", sys.executable,
         os.path.join(HERE, "multiprocess_elastic_worker.py")],
        env=env, capture_output=True, text=True, timeout=420,
        cwd=os.path.dirname(HERE),
    )
    assert r.returncode == 0, (r.returncode, r.stdout[-4000:], r.stderr[-4000:])
    assert "ELASTIC-KILL rank 1 dying mid-run" in r.stdout
    assert "restarting (1/2)" in r.stderr, r.stderr[-2000:]
    assert "ELASTIC-RESUMED batch=4" in r.stdout, r.stdout[-4000:]
    assert r.stdout.count("ELASTIC_OK") == 2, r.stdout[-4000:]


@pytest.mark.slow
def test_pytorch_elastic_example_via_launcher(tmp_path):
    """The torch-frontend elastic example: run once to completion, then
    re-launch against the same commit dir — the second gang restores
    epoch==epochs and trains nothing (resume-as-no-op, the gang-relaunch
    path in miniature through TorchState)."""
    env = dict(os.environ)
    env["HOROVOD_TPU_NATIVE_CONTROLLER"] = "on"
    env["PYTHONPATH"] = os.path.dirname(HERE) + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    cmd = [sys.executable, "-m", "horovod_tpu.launch", "--nproc", "2",
           "--cpu", "--restarts", "1", "--", sys.executable,
           os.path.join(os.path.dirname(HERE), "examples",
                        "pytorch_elastic.py"),
           "--epochs", "1", "--samples", "256", "--batch-size", "16",
           "--ckpt-dir", str(tmp_path / "ck")]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=300, cwd=os.path.dirname(HERE))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "epoch 0: loss" in r.stdout
    assert (tmp_path / "ck" / "step_1.pt").exists()

    r2 = subprocess.run(cmd, env=env, capture_output=True, text=True,
                        timeout=300, cwd=os.path.dirname(HERE))
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "epoch 0: loss" not in r2.stdout     # resumed past the end


@pytest.mark.slow
def test_keras_frontend_two_ranks():
    """The Keras-3 frontend under real process separation: two ranks run
    ``model.fit`` with DistributedOptimizer — the gradient allreduce rides
    io_callback inside keras's jitted train step through the eager engine
    — plus the broadcast/metric callbacks and value-level ops (the
    reference's ``mpirun -np 2`` keras CI shape)."""
    pytest.importorskip("keras")
    outs = _run_workers(
        os.path.join(HERE, "keras_multiprocess_worker.py"), 2,
        {"KERAS_BACKEND": "jax"}, timeout=600,
    )
    for i, out in enumerate(outs):
        assert "WORKER_OK" in out, f"worker {i} no OK line:\n{out}"


@pytest.mark.slow
def test_keras_elastic_example_via_launcher(tmp_path):
    """The keras-frontend elastic example: run once to completion, then
    re-launch against the same commit dir — the second gang restores
    epoch==epochs and trains nothing (resume-as-no-op through
    KerasState), completing the elastic-triple's launcher drills."""
    pytest.importorskip("keras")
    env = dict(os.environ)
    env["HOROVOD_TPU_NATIVE_CONTROLLER"] = "on"
    env["KERAS_BACKEND"] = "jax"
    env["PYTHONPATH"] = os.path.dirname(HERE) + os.pathsep + env.get(
        "PYTHONPATH", ""
    )
    cmd = [sys.executable, "-m", "horovod_tpu.launch", "--nproc", "2",
           "--cpu", "--restarts", "1", "--", sys.executable,
           os.path.join(os.path.dirname(HERE), "examples",
                        "keras_elastic.py"),
           "--epochs", "1", "--samples", "256", "--batch-size", "16",
           "--ckpt-dir", str(tmp_path / "ck")]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=300, cwd=os.path.dirname(HERE))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "epoch 0: loss" in r.stdout
    assert (tmp_path / "ck" / "step_1.npz").exists()

    r2 = subprocess.run(cmd, env=env, capture_output=True, text=True,
                        timeout=300, cwd=os.path.dirname(HERE))
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "epoch 0: loss" not in r2.stdout     # resumed past the end
