"""DistributedOptimizer / train-step semantics —
reference test/test_torch.py optimizer tests (:734-1039) re-shaped for the
compiled SPMD path."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd


def _linreg_data(n=64, d=4, seed=0):
    rng = np.random.RandomState(seed)
    w_true = rng.randn(d).astype(np.float32)
    x = rng.randn(n, d).astype(np.float32)
    y = x @ w_true + 0.01 * rng.randn(n).astype(np.float32)
    return x, y, w_true


def _loss_fn(params, batch):
    x, y = batch
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


def test_distributed_matches_single_device_full_batch():
    """DP gradient averaging == full-batch gradient: one distributed step
    must equal one single-device step on the concatenated batch."""
    x, y, _ = _linreg_data()
    params = {"w": jnp.zeros(4), "b": jnp.zeros(())}
    tx = optax.sgd(0.1)

    # single-device reference step
    grads = jax.grad(_loss_fn)(params, (x, y))
    updates, _ = tx.update(grads, tx.init(params), params)
    expected = optax.apply_updates(params, updates)

    # distributed step over 8 shards
    dtx = hvd.DistributedOptimizer(tx)
    step = hvd.make_train_step(_loss_fn, dtx, donate=False)
    opt_state = tx.init(params)
    params2, _, loss = step(params, opt_state, (x, y))
    np.testing.assert_allclose(
        np.asarray(params2["w"]), np.asarray(expected["w"]), rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(params2["b"]), np.asarray(expected["b"]), rtol=1e-5
    )
    assert float(loss) > 0


def test_train_step_converges():
    """End-to-end: distributed SGD recovers the true weights (the MNIST-
    convergence-smoke analogue, reference .travis.yml examples-as-E2E)."""
    x, y, w_true = _linreg_data(n=256)
    params = {"w": jnp.zeros(4), "b": jnp.zeros(())}
    tx = hvd.DistributedOptimizer(optax.sgd(0.1))
    opt_state = tx.init(params)
    step = hvd.make_train_step(_loss_fn, tx, donate=False)
    loss = None
    for _ in range(200):
        params, opt_state, loss = step(params, opt_state, (x, y))
    assert float(loss) < 1e-3
    np.testing.assert_allclose(np.asarray(params["w"]), w_true, atol=0.05)


def test_sparse_mode_full_ratio_matches_dense():
    """Fork's is_sparse path with ratio=1.0 == dense averaging
    (reference torch/__init__.py:141-151)."""
    x, y, _ = _linreg_data()
    params = {"w": jnp.ones(4), "b": jnp.zeros(())}
    base = optax.sgd(0.05)
    dense = hvd.make_train_step(_loss_fn, hvd.DistributedOptimizer(base), donate=False)
    sparse = hvd.make_train_step(
        _loss_fn,
        hvd.DistributedOptimizer(base, is_sparse=True, sparse_ratio=1.0),
        donate=False,
    )
    st = base.init(params)
    p1, _, _ = dense(params, st, (x, y))
    p2, _, _ = sparse(params, st, (x, y))
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]), rtol=1e-5)


def test_local_mode_skips_communication():
    """Fork's ``self.local`` flag (reference torch/__init__.py:115,158):
    gradients stay rank-local, so ranks diverge."""
    x, y, _ = _linreg_data()
    params = {"w": jnp.zeros(4), "b": jnp.zeros(())}
    tx = hvd.DistributedOptimizer(optax.sgd(0.1), local=True)
    opt_state = tx.init(params)

    def step(params, opt_state, batch):
        grads = jax.grad(_loss_fn)(params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        new = optax.apply_updates(params, updates)
        return jax.tree.map(lambda v: v[None], new)  # per-rank row

    f = jax.jit(
        jax.shard_map(
            step,
            mesh=hvd.mesh(),
            in_specs=(P(), P(), P(hvd.AXIS_NAME)),
            out_specs=P(hvd.AXIS_NAME),
            check_vma=False,
        )
    )
    out = f(params, opt_state, (x, y))
    w = np.asarray(out["w"])
    assert w.shape == (8, 4)
    assert not np.allclose(w[0], w[1])  # ranks diverged: no allreduce happened


def test_rank_dependent_loss_no_deadlock():
    """Two-headed net where each rank's loss uses a different head — grads
    for the unused head are zeros, not missing, so averaging just works (the
    situation reference test_torch.py:972-1039 ``test_force_allreduce``
    guards with explicit missing-grad handling)."""
    params = {"head_a": jnp.ones(3), "head_b": jnp.ones(3) * 2}

    def loss_fn(params, batch):
        r = jax.lax.axis_index(hvd.AXIS_NAME)
        la = jnp.sum(params["head_a"] * batch)
        lb = jnp.sum(params["head_b"] * batch)
        return jnp.where(r % 2 == 0, la, lb)

    tx = hvd.DistributedOptimizer(optax.sgd(0.1))
    step = hvd.make_train_step(loss_fn, tx, donate=False)
    batch = jnp.ones((8, 3))
    p, _, _ = step(params, tx.init(params), batch)
    # both heads moved: half the ranks contributed grad 1 for each head
    np.testing.assert_allclose(np.asarray(p["head_a"]), np.ones(3) - 0.05, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(p["head_b"]), 2 * np.ones(3) - 0.05, rtol=1e-6)


def test_allreduce_gradients_compressed():
    x, y, _ = _linreg_data()
    params = {"w": jnp.zeros(4), "b": jnp.zeros(())}
    tx = hvd.DistributedOptimizer(optax.sgd(0.1), compression=hvd.Compression.bf16)
    step = hvd.make_train_step(_loss_fn, tx, donate=False)
    p, _, loss = step(params, tx.init(params), (x, y))
    assert p["w"].dtype == jnp.float32
    assert np.isfinite(np.asarray(p["w"])).all()


def test_broadcast_parameters_replicates():
    params = {"w": jnp.arange(4.0), "nested": {"b": jnp.ones((2, 2))}}
    out = hvd.broadcast_parameters(params, root_rank=0)
    assert len(out["w"].sharding.device_set) == 8
    np.testing.assert_allclose(np.asarray(out["nested"]["b"]), 1.0)


def test_broadcast_optimizer_state_scalars():
    """Scalar/non-array leaves round-trip with their Python types
    (reference torch/__init__.py:302-418 scalar wrapping)."""
    tx = optax.adam(1e-3)
    st = tx.init({"w": jnp.zeros(3)})
    out = hvd.broadcast_optimizer_state(st)
    chex_count = out[0].count
    assert int(chex_count) == 0
    # python scalars survive
    custom = {"lr": 0.5, "epoch": 3, "mu": jnp.ones(2)}
    out2 = hvd.broadcast_optimizer_state(custom)
    assert isinstance(out2["lr"], float) and out2["lr"] == 0.5
    assert isinstance(out2["epoch"], int) and out2["epoch"] == 3
    np.testing.assert_allclose(np.asarray(out2["mu"]), 1.0)


def test_broadcast_object_single_host():
    assert hvd.broadcast_object({"resume_epoch": 7}) == {"resume_epoch": 7}


def test_train_step_cpu_backend_throttles_dispatch_depth(monkeypatch):
    """Pin the CPU-simulation deadlock defense: on the cpu backend the step
    make_train_step returns must wait for each launch (XLA's in-process CPU
    collectives abort their rendezvous when many launches are in flight;
    see distributed_optimizer.py).  On TPU it does not wait — this test
    documents the contract so a refactor cannot silently drop the throttle
    and resurface the 40s rendezvous hang."""
    assert jax.default_backend() == "cpu"  # the whole suite runs CPU-sim
    x, y, _ = _linreg_data()
    params = {"w": jnp.zeros(4), "b": jnp.zeros(())}
    tx = hvd.DistributedOptimizer(optax.sgd(0.1))
    step = hvd.make_train_step(_loss_fn, tx, donate=False)
    waits = []
    real = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready", lambda v: waits.append(v) or real(v))
    out = step(params, tx.init(params), (x, y))
    assert len(waits) == 1 and waits[0] is out.loss


def test_train_step_compiles_once_whatever_the_state_sits_on():
    """README's recipe hands make_train_step a bare ``tx.init(params)``: on
    one device, while the step returns its state on the mesh.  The step puts
    what it is given on the mesh itself, so the second call is the same
    signature as the first and nothing compiles twice — and the placed tree
    is handed on untouched from then on."""
    from horovod_tpu.optim.distributed_optimizer import _on_mesh

    x, y, _ = _linreg_data()
    for broadcast in (False, True):
        params = {"w": jnp.zeros(4), "b": jnp.zeros(())}
        tx = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))
        if broadcast:
            params = hvd.broadcast_parameters(params, root_rank=0)
        opt_state = tx.init(params)
        step = hvd.make_train_step(_loss_fn, tx)
        lowered = step.lower(params, opt_state, (x, y))
        assert "all-reduce" in lowered.compile().as_text()
        for _ in range(3):
            params, opt_state, _ = step(params, opt_state, (x, y))
        assert step._cache_size() == 1
        state = (params, opt_state)
        assert _on_mesh(state, hvd.replicated_sharding()) is state


def test_backward_passes_per_step_accumulates():
    """k=2: first micro-step leaves params untouched, second applies the
    SUM of both accumulated gradients — the reference's autograd hooks
    accumulate .grad over k backward passes (torch/__init__.py:115-165)."""
    tx = hvd.DistributedOptimizer(optax.sgd(0.1), backward_passes_per_step=2)
    params = {"w": jnp.zeros((4,)), "b": jnp.zeros(())}
    opt_state = tx.init(params)
    step = hvd.make_train_step(_loss_fn, tx, donate=False)

    x = hvd.per_rank(lambda r: jnp.ones((2, 4)))
    y_at = lambda c: hvd.per_rank(lambda r: jnp.full((2,), c))
    out1 = step(params, opt_state, (x, y_at(2.0)))
    np.testing.assert_allclose(np.asarray(out1.params["w"]), 0.0)  # held
    out2 = step(out1.params, out1.opt_state, (x, y_at(6.0)))
    assert not np.allclose(np.asarray(out2.params["w"]), 0.0)      # applied

    # Loss is quadratic with identical x, so grad(y=2)+grad(y=6) equals
    # 2·grad(y=4): the sum-accumulated update must match one plain step at
    # doubled learning rate on the mean target.
    ref_tx = hvd.DistributedOptimizer(optax.sgd(0.2))
    ref_step = hvd.make_train_step(_loss_fn, ref_tx, donate=False)
    ref = ref_step(params, ref_tx.init(params), (x, y_at(4.0)))
    np.testing.assert_allclose(
        np.asarray(out2.params["w"]), np.asarray(ref.params["w"]), rtol=1e-6
    )


def test_zero_step_matches_replicated_adam():
    """ZeRO sharded step == replicated DistributedOptimizer step (Adam is
    elementwise), with optimizer state at 1/n per rank."""
    n = hvd.size()
    params = {"w": jnp.arange(10.0) / 10, "b": jnp.ones((3,))}

    zstep, zinit = hvd.make_zero_train_step(_loss_fn_quad, optax.adam(0.1),
                                        donate=False)
    zstate = zinit(params)
    # array leaves shard: global leading dim = n * ceil(13/n)
    mu = jax.tree.leaves(zstate)[1]
    assert mu.shape[0] == n * (-(-13 // n))

    rtx = hvd.DistributedOptimizer(optax.adam(0.1))
    rstep = hvd.make_train_step(_loss_fn_quad, rtx, donate=False)
    rstate = rtx.init(params)

    batch = hvd.per_rank(lambda r: jnp.full((2, 1), float(r + 1)))
    zp, zs, zl = params, zstate, None
    rp, rs = params, rstate
    for _ in range(3):
        zout = zstep(zp, zs, batch)
        zp, zs, zl = zout.params, zout.opt_state, zout.loss
        rout = rstep(rp, rs, batch)
        rp, rs = rout.params, rout.opt_state
        np.testing.assert_allclose(float(zl), float(rout.loss), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(zp), jax.tree.leaves(rp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def _loss_fn_quad(params, batch):
    scale = jnp.mean(batch)
    return scale * (jnp.sum(params["w"] ** 2) + jnp.sum(params["b"] ** 2))


def test_zero_clip_global_norm_matches_replicated():
    """ZeRO's clip_global_norm == optax.clip_by_global_norm on the full
    gradient (shard norms sum to the true global norm)."""
    params = {"w": jnp.arange(10.0), "b": jnp.full((3,), 5.0)}

    zstep, zinit = hvd.make_zero_train_step(
        _loss_fn_quad, optax.sgd(0.1), clip_global_norm=1.0, donate=False
    )
    rtx = hvd.DistributedOptimizer(
        optax.chain(optax.clip_by_global_norm(1.0), optax.sgd(0.1))
    )
    rstep = hvd.make_train_step(_loss_fn_quad, rtx, donate=False)

    batch = hvd.per_rank(lambda r: jnp.full((2, 1), 2.0))
    zout = zstep(params, zinit(params), batch)
    rout = rstep(params, rtx.init(params), batch)
    for a, b in zip(jax.tree.leaves(zout.params), jax.tree.leaves(rout.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_allgather_object_single_host():
    out = hvd.allgather_object({"rank_data": 42})
    assert out == [{"rank_data": 42}]
