"""Native coordination engine: negotiation, fusion, validation, stall,
shutdown, and the eager-engine integration.

Mirrors the reference's coordinator-protocol behavior (reference:
horovod/common/operations.cc RunLoopOnce :1795-2007, response fusion
:1916-1943, mismatch errors :335-537 — exercised there by
test/test_tensorflow.py:249-320's negative tests under mpirun).  Multi-rank
negotiation is driven by N threads, each owning a rank's controller over an
in-process transport — the single-host analogue of ``mpirun -np N``.
"""

from __future__ import annotations

import os
import threading
import uuid

import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="libhvdtpu.so could not be built"
)

AR = native.KIND_ALLREDUCE
AG = native.KIND_ALLGATHER
BC = native.KIND_BROADCAST


def run_ranks(size, body, *, transport=None, threshold=1 << 20, stall_s=60.0):
    """Spawn one thread per rank, each with its own controller; returns the
    per-rank results of ``body(rank, controller)``."""
    spec = transport or f"local:{uuid.uuid4().hex}"
    results = [None] * size
    errors = []

    def runner(rank):
        try:
            ctrl = native.NativeController(
                rank=rank, size=size, transport_spec=spec,
                fusion_threshold_bytes=threshold, stall_warning_s=stall_s,
            )
            try:
                results[rank] = body(rank, ctrl)
            finally:
                ctrl.close()
        except Exception as e:  # pragma: no cover - surfaced via errors
            errors.append((rank, e))

    threads = [threading.Thread(target=runner, args=(r,)) for r in range(size)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "rank thread hung (negotiation deadlock)"
    assert not errors, f"rank errors: {errors}"
    return results


def drain(ctrl, n_names):
    """Tick until n_names tensor names have been batched; returns batches."""
    out = []
    got = 0
    while got < n_names:
        bl = ctrl.tick()
        for b in bl.batches:
            out.append(b)
            got += len(b.names)
    return out


def test_staleness_is_by_source_digest_not_mtime(tmp_path, monkeypatch):
    """A copied tree keeps no mtimes: a library is trusted only when the
    digest recorded beside it is that of the sources present — not because
    it is newer than them."""
    assert not native._so_stale()             # built and recorded above
    recorded = tmp_path / "libhvdtpu.so.sha256"
    monkeypatch.setattr(native, "_DIGEST_PATH", str(recorded))
    assert native._so_stale()                 # a library with no record
    recorded.write_text(native._source_digest() + "\n")
    assert not native._so_stale()             # ... whatever the mtimes say
    os.utime(native._SO_PATH, (0, 0))
    assert not native._so_stale()
    recorded.write_text("0" * 64 + "\n")      # built from other sources
    assert native._so_stale()


def test_agreement_and_fusion_across_ranks():
    """Ranks submit in different orders; all must agree on one fused order
    (the core coordinator property, reference operations.cc:1795-2007)."""

    def body(rank, ctrl):
        names = ["gr.a", "gr.b", "gr.c"]
        order = names[rank % 3:] + names[:rank % 3]
        for n in order:
            ctrl.submit(AR, "float32", n, (8, 4))
        return drain(ctrl, 3)

    results = run_ranks(4, body)
    assert len(results[0]) == 1  # fused into one batch
    assert sorted(results[0][0].names) == ["gr.a", "gr.b", "gr.c"]
    for r in range(1, 4):
        assert [b.names for b in results[r]] == [b.names for b in results[0]]


def test_fusion_respects_threshold_and_dtype():
    def body(rank, ctrl):
        ctrl.submit(AR, "float32", "t.f32a", (100,))   # 400 B
        ctrl.submit(AR, "float32", "t.f32b", (100,))   # 400 B -> splits
        ctrl.submit(AR, "bfloat16", "t.bf16", (100,))  # dtype change
        return drain(ctrl, 3)

    batches = run_ranks(2, body, threshold=600)[0]
    assert [len(b.names) for b in batches] == [1, 1, 1]

    def body2(rank, ctrl):
        ctrl.submit(AR, "float32", "u.a", (10,))
        ctrl.submit(AR, "float32", "u.b", (10,))
        ctrl.submit(AR, "bfloat16", "u.c", (10,))
        return drain(ctrl, 3)

    batches = run_ranks(2, body2, threshold=1 << 20)[0]
    assert [sorted(b.names) for b in batches] == [["u.a", "u.b"], ["u.c"]]


def test_fusion_respects_group():
    """Different fusion groups (distinct reduce op / compression) never
    merge even with matching dtype."""

    def body(rank, ctrl):
        ctrl.submit(AR, "float32", "g.sum", (4,), group=0)
        ctrl.submit(AR, "float32", "g.min", (4,), group=1)
        return drain(ctrl, 2)

    batches = run_ranks(2, body)[0]
    assert [b.names for b in batches] == [["g.sum"], ["g.min"]]


def test_shape_mismatch_is_error_on_all_ranks():
    """Even-vs-odd-rank shapes → error batch everywhere (reference
    negative test shape, test_tensorflow.py:249-283)."""

    def body(rank, ctrl):
        ctrl.submit(AR, "float32", "bad.shape", (8 if rank % 2 else 4,))
        return drain(ctrl, 1)

    for batches in run_ranks(2, body):
        assert "Mismatched allreduce tensor shapes" in batches[0].error


def test_dtype_mismatch_is_error():
    def body(rank, ctrl):
        ctrl.submit(AR, "float32" if rank == 0 else "int32", "bad.dtype", (4,))
        return drain(ctrl, 1)

    for batches in run_ranks(2, body):
        assert "Mismatched tensor dtypes" in batches[0].error


def test_ragged_allgather_allowed_but_trailing_dims_checked():
    def body(rank, ctrl):
        ctrl.submit(AG, "float32", "ag.ok", (rank + 1, 7))   # ragged dim 0 ok
        ctrl.submit(AG, "float32", "ag.bad", (2, rank + 3))  # trailing differ
        return drain(ctrl, 2)

    for batches in run_ranks(2, body):
        by_name = {b.names[0]: b for b in batches}
        assert by_name["ag.ok"].error == ""
        assert "trailing dims" in by_name["ag.bad"].error


def test_broadcast_root_mismatch_is_error():
    def body(rank, ctrl):
        ctrl.submit(BC, "float32", "bc.bad", (4,), root_rank=rank)
        return drain(ctrl, 1)

    for batches in run_ranks(2, body):
        assert "root_rank" in batches[0].error


def test_duplicate_submit_does_not_release_early():
    """A rank double-submitting a name must not satisfy the all-ranks-seen
    condition for a rank that never submitted; the duplicate surfaces as an
    error once all ranks HAVE reported."""

    def body(rank, ctrl):
        ctrl.submit(AR, "float32", "dup.x", (4,))
        if rank == 0:
            ctrl.submit(AR, "float32", "dup.x", (4,))  # duplicate in flight
        got = list(ctrl.tick().batches)
        while not got:
            got = list(ctrl.tick().batches)
        return got

    for batches in run_ranks(2, body):
        assert "Duplicate tensor name" in batches[0].error


def test_uint32_supported_on_the_wire():
    def body(rank, ctrl):
        ctrl.submit(AR, "uint32", "u32.x", (4,))
        return drain(ctrl, 1)

    assert run_ranks(2, body)[0][0].error == ""


def test_tick_trace_records_per_rank_arrivals():
    """Rank 0's tick trace records each rank's request arrival — the data
    behind the timeline's per-rank NEGOTIATE tick events
    (reference timeline.cc:98-132)."""

    def body(rank, ctrl):
        if rank == 0:
            ctrl.enable_tick_trace()
        ctrl.submit(AR, "float32", "tt.a", (4,))
        drain(ctrl, 1)
        return ctrl.drain_ticks()

    results = run_ranks(3, body)
    assert sorted(r for _, r in results[0]) == [0, 1, 2]
    assert all(n == "tt.a" for n, _ in results[0])
    assert results[1] == [] and results[2] == []  # rank-0-only data


def test_tick_trace_disabled_by_default():
    def body(rank, ctrl):
        ctrl.submit(AR, "float32", "tt.b", (4,))
        drain(ctrl, 1)
        return ctrl.drain_ticks()

    results = run_ranks(2, body)
    assert results[0] == [] and results[1] == []


def test_stall_report_names_missing_ranks():
    """Rank 0's table reports tensors stuck waiting on specific ranks
    (reference CheckForStalledTensors, operations.cc:1424-1470)."""

    def body(rank, ctrl):
        if rank == 0:
            ctrl.submit(AR, "float32", "lonely", (4,))
        ctrl.tick()
        return ctrl.stall_report()

    reports = run_ranks(3, body, stall_s=0.0)
    assert "lonely" in reports[0]
    assert "missing ranks: 1 2" in reports[0]
    assert reports[1] == "" and reports[2] == ""


def test_shutdown_propagates_to_all_ranks():
    def body(rank, ctrl):
        if rank == 1:
            ctrl.request_shutdown()
        bl = ctrl.tick()
        return bl.shutdown

    assert all(run_ranks(3, body))


def test_tcp_transport_agreement():
    """Same negotiation over real sockets (the multi-host control plane)."""

    def body(rank, ctrl):
        ctrl.submit(AR, "float32", "tcp.x", (4,))
        ctrl.submit(AR, "float32", "tcp.y", (4,))
        return drain(ctrl, 2)

    results = run_ranks(2, body, transport="tcp:127.0.0.1:19872")
    assert [b.names for b in results[0]] == [b.names for b in results[1]]


def test_transport_failure_raises_not_shutdown():
    """A dead control plane must surface as an error tick (rc=-1), not a
    benign empty BatchList or a clean shutdown — otherwise outstanding
    collective handles hang forever instead of being failed (the reference
    fails callbacks with an error on engine death, operations.cc:278-283)."""
    spec = "tcp:127.0.0.1:19873"
    closed = threading.Event()
    outcome = {}

    def rank1():
        ctrl = native.NativeController(
            rank=1, size=2, transport_spec=spec,
            fusion_threshold_bytes=1 << 20,
        )
        ctrl.close()  # dies without negotiating shutdown
        closed.set()

    def rank0():
        ctrl = native.NativeController(
            rank=0, size=2, transport_spec=spec,
            fusion_threshold_bytes=1 << 20,
        )
        assert closed.wait(30)
        try:
            bl = ctrl.tick()
            outcome["result"] = ("tick", bl.shutdown, len(bl.batches))
        except RuntimeError as e:
            outcome["result"] = ("raised", str(e))
        finally:
            ctrl.close()

    threads = [threading.Thread(target=rank1), threading.Thread(target=rank0)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "transport-failure test hung"
    assert outcome["result"][0] == "raised", (
        f"expected a transport error, got {outcome['result']}"
    )


# ---------------------------------------------------------------------------
# Eager-engine integration: the native controller drives dispatch.
# ---------------------------------------------------------------------------


@pytest.fixture
def native_engine_world(monkeypatch):
    """Re-init horovod_tpu with the native controller forced on."""
    monkeypatch.setenv("HOROVOD_TPU_NATIVE_CONTROLLER", "on")
    monkeypatch.setenv(
        "HOROVOD_TPU_CONTROLLER_TRANSPORT", f"local:{uuid.uuid4().hex}"
    )
    hvd.shutdown()
    hvd.init()
    yield
    hvd.shutdown()
    monkeypatch.delenv("HOROVOD_TPU_NATIVE_CONTROLLER")
    monkeypatch.delenv("HOROVOD_TPU_CONTROLLER_TRANSPORT")
    hvd.init()


def test_eager_engine_native_dispatch(native_engine_world):
    """Collectives negotiated through the native engine produce the same
    values as the pure-Python path."""
    x = hvd.per_rank(lambda r: jnp.full((3,), float(r)))
    out = hvd.allreduce(x, average=True)
    np.testing.assert_allclose(np.asarray(out), np.full(3, 3.5))

    from horovod_tpu.basics import _state

    assert _state.engine.controller is not None  # native path actually on

    b = hvd.broadcast(hvd.per_rank(lambda r: jnp.asarray([r])), root_rank=5)
    assert np.asarray(b).tolist() == [5]

    g = hvd.allgather([jnp.ones((r % 2 + 1, 2)) * r for r in range(8)])
    assert g.shape == (sum(r % 2 + 1 for r in range(8)), 2)


def test_eager_engine_native_fused_group(native_engine_world):
    outs = hvd.grouped_allreduce_eager(
        [hvd.per_rank(lambda r, i=i: jnp.full((4,), float(r + i)))
         for i in range(5)],
        average=True,
    )
    for i, o in enumerate(outs):
        np.testing.assert_allclose(np.asarray(o), np.full(4, 3.5 + i))


def test_eager_engine_native_grouped_composition_deterministic(
    native_engine_world,
):
    """Caller-delimited groups ride their own negotiation token, so (a)
    concurrent solo traffic never lands in the group's batch and (b)
    repeated identical grouped calls dispatch identical bucket
    compositions — novel compositions are fresh XLA compiles
    (docs/tensor-fusion.md "Determinism and compile churn")."""
    from horovod_tpu.basics import _state
    from horovod_tpu.ops.eager import EagerEngine

    grads = [hvd.per_rank(lambda r, i=i: jnp.full((16,), float(i)))
             for i in range(6)]
    seen = []
    orig = EagerEngine._dispatch_allreduce_group

    def record(self, group):
        seen.append(sorted(p.name for p in group))
        return orig(self, group)

    EagerEngine._dispatch_allreduce_group = record
    try:
        solo = hvd.allreduce_async(
            hvd.per_rank(lambda r: jnp.ones((16,))), name="solo.bystander"
        )
        assert _state.engine.controller is not None  # engine exists now
        first_outs = hvd.grouped_allreduce_eager(
            grads, average=True, names=[f"det.g{i}" for i in range(6)]
        )
        hvd.synchronize(solo)
        group_batches = [g for g in seen if any(n.startswith("det.") for n in g)]
        assert group_batches, "grouped call never dispatched"
        for g in group_batches:   # (a) isolation from the bystander
            assert "solo.bystander" not in g
        for trial in range(3):    # (b) stable composition call-to-call
            seen.clear()
            outs = hvd.grouped_allreduce_eager(
                grads, average=True,
                names=[f"det{trial}.g{i}" for i in range(6)],
            )
            trial_batches = [
                [n.split(".", 1)[1] for n in g]
                for g in seen if any(n.startswith(f"det{trial}.") for n in g)
            ]
            want = [[n.split(".", 1)[1] for n in g] for g in group_batches]
            assert trial_batches == want
        for i, o in enumerate(first_outs):
            np.testing.assert_allclose(np.asarray(o), np.full(16, float(i)))
    finally:
        EagerEngine._dispatch_allreduce_group = orig


def test_eager_engine_duplicate_name_errors(native_engine_world):
    x = hvd.per_rank(lambda r: jnp.ones((2,)))
    h1 = hvd.allreduce_async(x, name="dup")
    h2 = hvd.allreduce_async(x, name="dup")
    hvd.synchronize(h1)
    with pytest.raises(RuntimeError, match="Duplicate tensor name"):
        hvd.synchronize(h2)


def test_eager_engine_native_process_sets_do_not_cross_fuse(
    native_engine_world,
):
    """Regression: the controller's fusion token must separate different
    ProcessSets — cross-fused sets would all dispatch under group[0]'s set
    (wrong numerics, no error)."""
    n = hvd.size()
    a_set = hvd.ProcessSet([0, 1])
    b_set = hvd.ProcessSet([2, 3])
    ta = hvd.per_rank(lambda r: jnp.full((8,), float(r)))
    tb = hvd.per_rank(lambda r: jnp.full((8,), float(10 * r)))
    ha = hvd.allreduce_async(ta, average=True, process_set=a_set)
    hb = hvd.allreduce_async(tb, average=True, process_set=b_set)
    oa = np.asarray(hvd.synchronize(ha))
    ob = np.asarray(hvd.synchronize(hb))
    np.testing.assert_allclose(oa[0], np.full((8,), 0.5))
    np.testing.assert_allclose(oa[4], np.full((8,), 4.0))   # pass-through
    np.testing.assert_allclose(ob[2], np.full((8,), 25.0))
    np.testing.assert_allclose(ob[0], np.full((8,), 0.0))   # pass-through


def test_hostile_frame_length_fails_transport_not_memory():
    """A corrupt/hostile u32 length prefix on the control socket must fail
    rank 0's tick with a transport error — NOT attempt a ~4 GiB
    allocation (transport.cc kMaxFrameBytes bound)."""
    import socket
    import struct

    spec_port = 19874
    spec = f"tcp:127.0.0.1:{spec_port}"
    outcome = {}
    hello_sent = threading.Event()

    def attacker():
        # Pose as rank 1: valid hello, then a frame claiming ~2 GiB.
        deadline = 30
        s = None
        for _ in range(300):
            try:
                s = socket.create_connection(("127.0.0.1", spec_port),
                                             timeout=deadline)
                break
            except OSError:
                import time as _t

                _t.sleep(0.1)
        assert s is not None, "could not reach coordinator"
        s.sendall(struct.pack("<I", 1))               # hello: rank 1
        hello_sent.set()
        s.sendall(struct.pack("<I", 0x7FFFFFF0))      # hostile length
        s.sendall(b"garbage")
        import time as _t

        _t.sleep(2)
        s.close()

    def rank0():
        ctrl = native.NativeController(
            rank=0, size=2, transport_spec=spec,
            fusion_threshold_bytes=1 << 20,
        )
        try:
            assert hello_sent.wait(30)
            ctrl.submit(AR, "float32", "hostile.x", (4,))
            try:
                bl = ctrl.tick()
                outcome["result"] = ("tick", bl.shutdown, len(bl.batches))
            except RuntimeError as e:
                outcome["result"] = ("raised", str(e))
        finally:
            ctrl.close()

    threads = [threading.Thread(target=attacker),
               threading.Thread(target=rank0)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive(), "hostile-frame test hung"
    assert outcome["result"][0] == "raised", (
        f"expected transport error on hostile frame, got {outcome['result']}"
    )


def test_wire_parsers_fuzz_under_sanitizers(tmp_path):
    """Build the wire fuzz harness with ASan+UBSan and run it: random
    bytes, exact round-trips, and single-byte mutations — the 'trivially
    fuzzable' claim of wire.h, made checkable."""
    import shutil
    import subprocess
    import sys as _sys

    gxx = shutil.which("g++")
    if gxx is None:
        pytest.skip("no g++ in PATH")
    src_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "native", "src",
    )
    exe = tmp_path / "wire_fuzz"
    build = subprocess.run(
        [gxx, "-std=c++17", "-O1", "-g", "-fsanitize=address,undefined",
         "-fno-sanitize-recover=all",
         os.path.join(src_dir, "wire_fuzz_main.cc"), "-o", str(exe)],
        capture_output=True, text=True, timeout=180,
    )
    assert build.returncode == 0, build.stderr
    run = subprocess.run(
        [str(exe), "5000", "7"], capture_output=True, text=True, timeout=300,
    )
    assert run.returncode == 0, run.stdout + run.stderr
    assert "wire fuzz OK" in run.stdout


def test_set_tuned_piggyback_and_rebucketing():
    """Control-plane autotune at the controller level: rank 0's SetTuned
    (a) re-buckets the NEXT tick with the new threshold — batching is
    rank-0-owned — and (b) piggybacks (threshold, cycle) on every rank's
    response, sub-millisecond cycle values surviving the micros wire
    exactly.  Non-root SetTuned must be a no-op."""
    f32 = "float32"

    def body(rank, ctrl):
        seen = []
        # Non-root set_tuned must not influence anything.
        if rank == 1:
            ctrl.set_tuned(1, 99.0)
        # Round 1: default threshold (1 MiB) fuses two 1 KiB allreduces.
        ctrl.submit(AR, f32, "a", (256,))
        ctrl.submit(AR, f32, "b", (256,))
        batches = drain(ctrl, 2)
        seen.append(sorted(batches[0].names) if len(batches) == 1 else None)
        # Rank 0 tunes: threshold 1 byte (nothing fuses), cycle 0.057 ms
        # (the llround-sensitive value the fuzz harness flagged).
        if rank == 0:
            ctrl.set_tuned(1, 0.057)
        bl = ctrl.tick()                     # propagation tick
        ctrl.submit(AR, f32, "c", (256,))
        ctrl.submit(AR, f32, "d", (256,))
        batches2 = drain(ctrl, 2)
        seen.append([b.names for b in batches2])
        # The piggyback must reach every rank with exact values.
        bl2 = ctrl.tick()
        seen.append((bl2.tuned_threshold_bytes, bl2.tuned_cycle_ms))
        return seen

    results = run_ranks(2, body)
    for r in results:
        assert r[0] == ["a", "b"], r          # fused under the default
        assert r[1] == [["c"], ["d"]], r      # split after SetTuned(1)
        assert r[2] == (1, 0.057), r          # exact piggyback everywhere


def test_agreement_at_16_ranks_mixed_order_and_stragglers():
    """Control-plane scale: 16 ranks, shuffled submit orders, some ranks
    submitting late relative to their first tick — all must converge on
    identical fused batch sequences.  (The reference CI never exceeded
    mpirun -np 2; this exercises the coordinator's gather/match/fuse at a
    pod-slice-sized worker count on the local transport.)"""
    import random

    names = [f"s16.{i}" for i in range(12)]

    def body(rank, ctrl):
        order = names[:]
        random.Random(rank).shuffle(order)
        late = order[8:]        # stragglers: submitted only after ticking
        for n in order[:8]:
            ctrl.submit(AR, "float32", n, (16,))
        # The partial-readiness tick can legally emit batches (a name every
        # rank's first-8 happens to cover); count them or drain() hangs.
        early = list(ctrl.tick().batches)
        for n in late:
            ctrl.submit(AR, "float32", n, (16,))
        done = sum(len(b.names) for b in early)
        return early + drain(ctrl, len(names) - done)

    results = run_ranks(16, body, threshold=1 << 10)
    seq0 = [b.names for b in results[0]]
    assert sorted(n for b in seq0 for n in b) == sorted(names)
    for r in range(1, 16):
        assert [b.names for b in results[r]] == seq0, f"rank {r} diverged"


def test_tcp_transport_agreement_8_ranks():
    """The socket control plane at 8 workers (one per chip of a v5e-8):
    everyone sees the same batch stream over real TCP."""
    import socket

    with socket.socket() as s:      # OS-assigned port: no collisions with
        s.bind(("127.0.0.1", 0))    # other tests' fixed listeners
        port = s.getsockname()[1]

    def body(rank, ctrl):
        for i in range(4):
            ctrl.submit(AR, "float32", f"tcp8.{i}", (8,))
        return drain(ctrl, 4)

    results = run_ranks(8, body, transport=f"tcp:127.0.0.1:{port}")
    for r in range(1, 8):
        assert [b.names for b in results[r]] == [b.names for b in results[0]]
