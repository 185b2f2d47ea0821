"""Seeded randomized interleaving of the eager engine's async surface.

The directed tests pin each path; this sweep drives the engine the way a
real define-by-run frontend does — many outstanding handles of mixed
kinds/dtypes/shapes, synchronized in arbitrary order — and checks every
result against a numpy oracle.  The reference's engine is exercised the
same way by its async_fused tests (test_torch.py:175-224); here the
interleaving and fusion grouping are randomized (seeded: deterministic in
CI) so negotiation-order bugs that directed tests can't reach get a
chance to surface.

Shapes draw from a small pool so XLA compiles stay bounded.
"""

import numpy as np
import pytest

import jax.numpy as jnp

import horovod_tpu as hvd

SHAPES = [(4,), (2, 3), (8,), (3, 2, 2)]
DTYPES = [np.float32, np.int32]


def _rank_major(n, shape, dtype, rng):
    if dtype == np.int32:
        return rng.integers(-50, 50, size=(n, *shape)).astype(np.int32)
    return rng.standard_normal((n, *shape)).astype(np.float32)


@pytest.mark.parametrize("seed", [7, 21, 63])
def test_engine_random_interleaving(seed):
    n = hvd.size()
    rng = np.random.default_rng(seed)
    pending = []   # (handle, oracle ndarray, kind)

    for i in range(14):
        kind = rng.choice(
            ["allreduce", "allgather", "broadcast", "reducescatter"]
        )
        shape = SHAPES[int(rng.integers(len(SHAPES)))]
        dtype = DTYPES[int(rng.integers(len(DTYPES)))]
        data = _rank_major(n, shape, dtype, rng)
        name = f"fz{seed}.{i}"
        if kind == "allreduce":
            avg = bool(rng.integers(2)) and dtype == np.float32
            h = hvd.allreduce_async(jnp.asarray(data), name=name, average=avg)
            want = data.mean(axis=0) if avg else data.sum(axis=0)
        elif kind == "allgather":
            h = hvd.allgather_async(jnp.asarray(data), name=name)
            want = data.reshape(n * shape[0], *shape[1:])
        elif kind == "reducescatter":
            shape = (2 * n,)           # dim 0 must divide by the mesh
            data = _rank_major(n, shape, np.float32, rng)
            h = hvd.reducescatter_async(jnp.asarray(data), name=name,
                                        op=hvd.Sum)
            want = data.sum(axis=0).reshape(n, 2)   # rank-major shards
        else:
            root = int(rng.integers(n))
            h = hvd.broadcast_async(jnp.asarray(data), root, name=name)
            want = data[root]          # result is the root's tensor
        pending.append((h, want, kind))

        # Randomly drain a prefix of outstanding handles mid-stream, in a
        # shuffled order — the engine must tolerate out-of-order waits
        # while later ops are still being negotiated.
        if rng.integers(3) == 0:       # pending is never empty here
            k = int(rng.integers(1, len(pending) + 1))
            batch, pending = pending[:k], pending[k:]
            order = rng.permutation(len(batch))
            for j in order:
                h, want, kind = batch[j]
                got = np.asarray(hvd.synchronize(h))
                np.testing.assert_allclose(
                    got, want, rtol=1e-5, atol=1e-5,
                    err_msg=f"seed={seed} kind={kind}")

    order = rng.permutation(len(pending))
    for j in order:
        h, want, kind = pending[j]
        got = np.asarray(hvd.synchronize(h))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                   err_msg=f"seed={seed} kind={kind} (tail)")


def test_engine_random_interleaving_tiny_threshold(monkeypatch):
    """Same sweep shape at a 1-byte fusion threshold (every op its own
    bucket) — the planner's other extreme under interleaving."""
    try:
        monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", "1")
        hvd.shutdown()
        hvd.init()                      # snapshots the 1-byte threshold
        test_engine_random_interleaving(5)
    finally:
        # undo() restores any PRE-EXISTING threshold (delenv would discard
        # it and the restoring init below would bake in the default).
        monkeypatch.undo()
        hvd.shutdown()
        hvd.init()


def test_engine_random_interleaving_pipelined_dispatch(monkeypatch):
    """The TPU-production dispatch mode: HOROVOD_TPU_SERIALIZE_DISPATCH=off
    keeps multiple collective launches in flight, covering the dispatch
    false-branches (no block_until_ready per launch) that the 'auto' CPU
    default never takes.  Safe on this harness: a single process drives
    all 8 virtual ranks, so one launch covers every rank and CPU arrival
    order cannot diverge."""
    try:
        monkeypatch.setenv("HOROVOD_TPU_SERIALIZE_DISPATCH", "off")
        hvd.shutdown()
        hvd.init()
        for seed in (9, 27):
            test_engine_random_interleaving(seed)
        from horovod_tpu.basics import _state

        assert _state.engine is not None
        assert _state.engine._serialize_dispatch is False
    finally:
        monkeypatch.undo()
        hvd.shutdown()
        hvd.init()


def test_engine_pipelined_dispatch_native_controller(monkeypatch):
    """Pipelined dispatch × native control plane — the closest this
    harness gets to the real TPU production configuration (async launch
    depth > 1 behind controller-negotiated batches)."""
    import uuid

    from horovod_tpu import native

    if not native.available():
        pytest.skip("libhvdtpu.so unavailable")
    try:
        monkeypatch.setenv("HOROVOD_TPU_SERIALIZE_DISPATCH", "off")
        monkeypatch.setenv("HOROVOD_TPU_NATIVE_CONTROLLER", "on")
        monkeypatch.setenv(
            "HOROVOD_TPU_CONTROLLER_TRANSPORT", f"local:{uuid.uuid4().hex}"
        )
        hvd.shutdown()
        hvd.init()
        test_engine_random_interleaving(31)
        from horovod_tpu.basics import _state

        assert _state.engine.controller is not None
        assert _state.engine._serialize_dispatch is False
    finally:
        monkeypatch.undo()
        hvd.shutdown()
        hvd.init()


@pytest.mark.faults
@pytest.mark.metrics
@pytest.mark.spec
# The spec axis rides two of the four seed combos (cache off on one
# seed, cache on on the other) rather than the full cross-product —
# the spec engine's per-combo cost is a whole extra jit program, and
# the directed spec tests in test_spec_sched.py carry the rest.
@pytest.mark.parametrize("seed,prefix_cache,spec", [
    (3, False, False), (3, True, False),
    (17, False, False), (17, True, False),
    (3, False, True), (17, True, True),
])
def test_serve_engine_fault_schedule_fuzz(seed, prefix_cache, spec,
                                          tmp_path):
    """Randomized request lifecycle sweep of the ServeEngine under an
    overcommitted KV pool: seeded random prompts/budgets, one hard
    deadline, one permanently poisoned request, transient injected
    faults at the admit/prefill sites, mid-flight cancels, and a queue
    budget — all step-counted, no sleeps.  The directed tests in
    test_serving_faults.py pin each path; this sweep interleaves them
    and checks the two global invariants: every result's tokens are a
    prefix of (and for OK, equal to) its solo ``llama.generate`` run,
    and the non-OK statuses land exactly where the schedule says.
    Runs with the shared-prefix cache both off (classic free-list
    accounting) and on (release-to-cache: the same sweep must drain to
    a consistent radix index with zero live references), and with
    self-drafting speculation both off and on — preempt-replay,
    cancels, and faults under a multi-token-per-tick emission stream
    must still land every OK request bit-identical to its solo run.

    The observability layer rides the same sweep: the registry's
    lifecycle counters must grow monotonically step over step, every
    terminal result must carry a finalized trace, and replaying the
    JSONL event log must reproduce ``eng.counters`` exactly."""
    import jax

    from horovod_tpu.faults import FaultRegistry
    from horovod_tpu.metrics import (
        LIFECYCLE_EVENT_COUNTERS, EventLog, MetricsRegistry,
    )
    from horovod_tpu.models import llama
    from horovod_tpu.serving import (
        CANCELLED, FAILED, OK, REJECTED, TIMEOUT, Request,
    )
    from horovod_tpu.serving_scheduler import ServeEngine

    rng = np.random.default_rng(seed)
    cfg = llama.llama_tiny(attn_impl="dense", dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(5))
    max_len = 24

    reqs = []
    for _ in range(8):
        pl = int(rng.integers(2, 10))
        new = int(rng.integers(1, min(10, max_len - pl) + 1))
        reqs.append(Request(
            prompt=[int(t) for t in rng.integers(1, cfg.vocab_size, pl)],
            max_new_tokens=new))

    # Assign one lifecycle role per request, at a shuffled position so
    # the roles land on different submit orders per seed.
    roles = rng.permutation(8)
    dl, perm, tr_admit, tr_prefill, c0, c1, shed = (int(i) for i in roles[:7])
    reqs[dl].deadline_s = 0.0              # expired on arrival
    reqs[shed].max_queue_steps = 2         # load-shed under pressure

    # Overcommitted pool: full backing would be 2*6+1 = 13 blocks; 9
    # forces admission stalls and preemption-with-replay churn.
    reg = FaultRegistry()
    log_path = str(tmp_path / f"events_{seed}_{prefix_cache}_{spec}.jsonl")
    mreg = MetricsRegistry(event_log=EventLog(log_path))
    eng = ServeEngine(params, cfg, n_slots=2, max_len=max_len, chunk=4,
                      block_size=4, n_blocks=9, preempt_after=2,
                      faults=reg, prefix_cache=prefix_cache, metrics=mreg,
                      spec=spec, draft_k=3)
    ids = [eng.submit(r) for r in reqs]
    reg.inject("serve.tick", on_hit=2, permanent=True, key=ids[perm])
    reg.inject("serve.admit", on_hit=1, key=ids[tr_admit])
    reg.inject("serve.prefill", on_hit=1, key=ids[tr_prefill])
    cancel_at = {ids[c0]: int(rng.integers(1, 4)),
                 ids[c1]: int(rng.integers(4, 9))}

    lifecycle = sorted(eng.counters)
    prev = {k: 0 for k in lifecycle}
    step = 0
    while eng.pending() and step < 400:
        for rid, at in cancel_at.items():
            if at == step:
                eng.cancel(rid)
        eng.step()
        step += 1
        # counter monotonicity, sampled every step of the churn: the
        # registry mirrors only ever move up, in lockstep with the
        # engine's own dict
        for k in lifecycle:
            v = mreg.counter("serve." + k).value
            assert v >= prev[k], f"seed={seed} counter serve.{k} went down"
            assert v == eng.counters[k]
            prev[k] = v
    assert not eng.pending(), f"fuzz seed={seed} did not drain"
    # event-log replay reproduces the lifecycle counters exactly, and
    # every terminal result carries a finalized trace
    replayed = {k: 0 for k in lifecycle}
    for ev in EventLog.read(log_path):
        key = LIFECYCLE_EVENT_COUNTERS.get(ev["kind"])
        if key is not None:
            replayed[key] += 1
    assert replayed == dict(eng.counters), f"seed={seed} replay diverged"
    for rid in ids:
        res = eng.results[rid]
        assert res.trace is not None and res.trace.status == res.status
        assert res.trace.n_tokens == len(list(res))
    assert eng.traces == {}

    allowed = {ids[i]: {OK} for i in range(8)}
    allowed[ids[dl]] = {TIMEOUT}
    allowed[ids[perm]] = {FAILED}
    allowed[ids[shed]] = {OK, REJECTED}
    allowed[ids[c0]] = {OK, CANCELLED}
    allowed[ids[c1]] = {OK, CANCELLED}
    statuses = []
    for i, req in enumerate(reqs):
        res = eng.results[ids[i]]
        statuses.append(res.status)
        assert res.status in allowed[ids[i]], (
            f"seed={seed} rid={ids[i]} role-violating status {res.status}")
        want = np.asarray(llama.generate(
            params, jnp.asarray([req.prompt], jnp.int32), cfg,
            max_new_tokens=req.max_new_tokens, max_len=max_len))[0]
        got = np.asarray(list(res), np.int64)
        if res.status == OK:
            np.testing.assert_array_equal(
                got, want.astype(np.int64),
                err_msg=f"seed={seed} rid={ids[i]} OK not solo-identical")
        else:
            assert len(got) <= len(want)
            np.testing.assert_array_equal(
                got, want[:len(got)].astype(np.int64),
                err_msg=f"seed={seed} rid={ids[i]} partial diverged")
    assert statuses[dl] == TIMEOUT and statuses[perm] == FAILED
    # Lifecycle churn must not leak device state: the compiled programs
    # (spec engines swap the 1-wide tick for the K+1-wide verify tick)
    # and the whole block pool survive the sweep intact.
    if spec:
        assert eng.compile_cache_sizes() == \
            {"sample": 0, "tick": 0, "chunk": 1,
                                             "set_row": 1, "spec_tick": 1}
    else:
        assert eng.compile_cache_sizes() == \
            {"sample": 1, "tick": 1, "chunk": 1,
                                             "set_row": 1}
    if prefix_cache:
        # drained: no live references; every block is either free or
        # parked zero-ref in a structurally sound radix index
        assert eng.pool.ref_count() == 0
        assert (eng.free_block_count() + eng.cached_block_count()
                == eng.pcache.k.shape[1] - 1)
        eng.prefix.check_consistency()
    else:
        assert len(eng._free_blocks) == eng.pcache.k.shape[1] - 1


def test_engine_random_interleaving_native_controller(monkeypatch):
    """The chaos sweep through the native C++ controller (gather→match→
    fuse→bcast in controller.cc) instead of the in-process Python
    negotiation — same oracle, different control plane."""
    import uuid

    from horovod_tpu import native

    if not native.available():
        pytest.skip("libhvdtpu.so unavailable")
    try:
        monkeypatch.setenv("HOROVOD_TPU_NATIVE_CONTROLLER", "on")
        monkeypatch.setenv(
            "HOROVOD_TPU_CONTROLLER_TRANSPORT", f"local:{uuid.uuid4().hex}"
        )
        hvd.shutdown()
        hvd.init()
        test_engine_random_interleaving(11)
        from horovod_tpu.basics import _state

        # The engine spins up on the first eager op; verify the sweep
        # really negotiated through the native controller.
        assert _state.engine.controller is not None
        test_engine_random_interleaving(43)
    finally:
        monkeypatch.undo()
        hvd.shutdown()
        hvd.init()
