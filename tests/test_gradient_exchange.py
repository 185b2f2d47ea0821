"""The plan of the in-graph gradient exchange (``ops/fusion.py``'s
``reduce_in_place``, ``allreduce_gradients``, the step ``make_train_step``
builds), on four of the suite's CPU devices.  What the TPU's compiler makes
of it is in ``tests/test_chip_compile.py``."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import metrics
from horovod_tpu.ops import collective_ops, fusion
from horovod_tpu.optim import distributed_optimizer as dopt

KiB = 1024

#: a gradient tree of mixed sizes and two dtypes, as ``(shape, dtype)`` a
#: leaf: kernels of 256 KiB, 64 KiB and 128 KiB, their vectors, and a
#: bfloat16 pair in the middle
LEAVES = [((256, 256), jnp.float32), ((256,), jnp.float32),
          ((128, 128), jnp.float32), ((128,), jnp.float32),
          ((64, 64), jnp.bfloat16), ((64,), jnp.bfloat16),
          ((3, 3, 64, 56), jnp.float32), ((56,), jnp.float32),
          ((7,), jnp.float32)]

#: the orders in which the tree holds those leaves
ORDERS = {
    "reversed": [8, 7, 6, 5, 4, 3, 2, 1, 0],
    "as_listed": [0, 1, 2, 3, 4, 5, 6, 7, 8],
    "interleaved": [4, 0, 5, 1, 6, 2, 7, 3, 8],
    "bfloat16_last": [0, 1, 2, 3, 6, 7, 8, 4, 5],
}
THRESHOLDS = [0, 4 * KiB, 100 * KiB, 300 * KiB, 64 * KiB * KiB]


@pytest.fixture(scope="module")
def mesh4():
    return Mesh(np.array(jax.devices()[:4]), ("hvd",))


def _tree(order, seed=0):
    """One row of each leaf a device, ``[4, *shape]``, in ``order``."""
    keys = jax.random.split(jax.random.key(seed), len(LEAVES))
    rows = [jax.random.normal(k, (4,) + s, jnp.float32).astype(d)
            for k, (s, d) in zip(keys, LEAVES)]
    return [rows[i] for i in ORDERS[order]]


def _exchange(mesh, fn, rows):
    """``fn`` on each device's row of ``rows``, the results stacked."""
    body = lambda *xs: [y[None] for y in fn([x[0] for x in xs])]  # noqa: E731
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=tuple(P("hvd") for _ in rows),
        out_specs=[P("hvd") for _ in rows], check_vma=False))(*rows)


def _gauges():
    return {k: v for k, v in metrics.DEFAULT.snapshot()["gauges"].items()
            if k.startswith("fusion.")}


@pytest.mark.parametrize("threshold", THRESHOLDS)
@pytest.mark.parametrize("order", sorted(ORDERS))
def test_the_exchange_equals_one_psum_a_leaf_bit_for_bit(
        mesh4, order, threshold):
    rows = _tree(order)

    def in_place(leaves):
        return dopt.allreduce_gradients(
            leaves, fusion_threshold_bytes=threshold)

    def a_psum_a_leaf(leaves):
        return [jax.lax.pmean(x, "hvd") for x in leaves]

    got = _exchange(mesh4, in_place, rows)
    assert _gauges()["fusion.leaves_packed"] == 0
    want = _exchange(mesh4, a_psum_a_leaf, rows)
    for g, w, r in zip(got, want, rows):
        assert g.shape == r.shape and g.dtype == r.dtype
        np.testing.assert_array_equal(np.asarray(g.astype(jnp.float32)),
                                      np.asarray(w.astype(jnp.float32)))


@pytest.mark.parametrize("chained", [False, True])
@pytest.mark.parametrize("threshold", THRESHOLDS)
@pytest.mark.parametrize("order", sorted(ORDERS))
def test_the_buckets_hold_the_planner_s_contract(order, threshold, chained):
    """What the collective is handed, bucket by bucket: every leaf once, as
    it lies and in the tree's order; a bucket is one dtype and at most the
    threshold unless it is one leaf; and each leaf's result comes back in
    its own place."""
    leaves = [x[0] for x in _tree(order)]
    handed = []

    def collective(bucket):
        handed.append(bucket)
        return [-x for x in bucket]

    out = fusion.reduce_in_place(leaves, collective,
                                 threshold_bytes=threshold, chained=chained)
    flat = [x for bucket in handed for x in bucket]
    assert len(flat) == len(leaves)
    for x, leaf, o in zip(flat, leaves, out):
        assert x.shape == leaf.shape and x.dtype == leaf.dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(leaf))
        np.testing.assert_array_equal(np.asarray(o), -np.asarray(leaf))
    for bucket in handed:
        assert len(bucket) == 1 or \
            sum(fusion._nbytes(x) for x in bucket) <= threshold
        assert len({x.dtype for x in bucket}) == 1
    assert [len(b) for b in handed] == \
        [len(b) for b in fusion.plan_buckets(leaves, threshold)]


GAUGE_CASES = {
    # order, threshold, keywords -> buckets, largest bucket, in place, packed
    # [8 7] [6] [5 4] [3 2 1] [0]
    "small_buckets": ("reversed", 100 * KiB, {}, (5, 256 * KiB, 9, 0)),
    "one_leaf_a_bucket": ("reversed", 0, {}, (9, 256 * KiB, 9, 0)),
    # [4] [0] [5] [1 6 2 7 3 8]: the dtype cuts
    "roomy": ("interleaved", 64 * KiB * KiB, {}, (4, 256 * KiB, 9, 0)),
    # [0 1] [2 3] [4 5] [6 7 8]: int8 keeps the packed wire, every bucket of
    # several packed whole
    "a_packed_wire": ("as_listed", 300 * KiB,
                      dict(compression=hvd.Compression.int8),
                      (4, 256 * KiB + 1024, 0, 9)),
}


@pytest.mark.parametrize("case", sorted(GAUGE_CASES))
def test_the_fusion_gauges_read_what_the_plan_did(mesh4, case):
    order, threshold, kw, want = GAUGE_CASES[case]
    _exchange(mesh4, lambda leaves: dopt.allreduce_gradients(
        leaves, fusion_threshold_bytes=threshold, **kw), _tree(order))
    g = _gauges()
    assert (g["fusion.buckets"], g["fusion.bucket_bytes_max"],
            g["fusion.leaves_in_place"], g["fusion.leaves_packed"]) == want


WIRES = {
    "bf16_cast": dict(compression=hvd.Compression.bf16),
    "sum": dict(op=hvd.Sum),
}
OTHER_WIRES = {
    "int8": dict(compression=hvd.Compression.int8),
    "adasum": dict(op=hvd.Adasum),
    "process_set": dict(process_set=hvd.ProcessSet([0, 1])),
    "sparse": dict(sparse=True, sparse_ratio=0.5),
}


@pytest.mark.parametrize("wire", sorted(WIRES) + sorted(OTHER_WIRES))
def test_which_wires_reduce_in_place(mesh4, wire):
    """A Sum or an Average, plain or cast down for the wire, reduces every
    leaf where it lies and gives what the packed plan gives; a wire that
    wants its flat buffer, its pairs, its groups or its all-gathers keeps
    it, to the bit."""
    kw = {**WIRES, **OTHER_WIRES}[wire]
    rows = [x for x in _tree("as_listed", 3) if x.dtype == jnp.float32]

    def exchange(leaves):
        return dopt.allreduce_gradients(
            leaves, fusion_threshold_bytes=100 * KiB, **kw)

    def packed(leaves):
        if wire == "sparse":
            return exchange(leaves)
        return collective_ops.grouped_allreduce(
            leaves, fusion_threshold_bytes=100 * KiB,
            **{"op": hvd.Average, **kw})

    metrics.DEFAULT.gauge("fusion.leaves_packed").set(-1)
    got = _exchange(mesh4, exchange, rows)
    leaves_packed = _gauges()["fusion.leaves_packed"]
    want = _exchange(mesh4, packed, rows)
    if wire == "sparse":                  # no fused exchange at all
        assert leaves_packed == -1
    elif wire in WIRES:
        assert leaves_packed == 0
    else:                       # [0] [1 2 3] [4] [5 6]; adasum a leaf each
        assert leaves_packed == (0 if wire == "adasum" else 5)
    for g, w in zip(got, want):
        if wire == "bf16_cast":           # the same cast, summed in another
            np.testing.assert_allclose(   # company: bfloat16's last place
                np.asarray(g), np.asarray(w), rtol=2e-2, atol=2e-2)
        else:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# --- make_train_step: the step's exchange ------------------------------------

LAYERS = ("first", "second", "third")


def _three_layers():
    k = jax.random.key(1)
    params = {n: {"w": 0.05 * jax.random.normal(jax.random.fold_in(k, i),
                                                (128, 128)),
                  "b": jnp.zeros((128,))} for i, n in enumerate(LAYERS)}

    def loss_fn(p, batch):
        x, y = batch
        for n in LAYERS:
            x = jnp.tanh(x @ p[n]["w"] + p[n]["b"])
        return jnp.mean((x - y) ** 2)

    batch = (jax.random.normal(k, (16, 128)), jnp.ones((16, 128)))
    return params, loss_fn, batch


@pytest.mark.parametrize("jitted_loss", [False, True])
@pytest.mark.parametrize("threshold", [70 * KiB, 140 * KiB])
def test_a_train_step_reduces_its_gradients_in_place(
        mesh4, threshold, jitted_loss):
    """A step built by ``make_train_step`` buckets the tree as it lies (by
    name: first, second, third; a bias before its kernel), packs nothing,
    and is one SGD step on the mean gradient."""
    params, loss_fn, batch = _three_layers()
    if jitted_loss:
        loss_fn = jax.jit(loss_fn)
    tx = hvd.DistributedOptimizer(optax.sgd(0.1),
                                  fusion_threshold_bytes=threshold)
    step = hvd.make_train_step(loss_fn, tx, mesh=mesh4, donate=False)
    out = step(params, tx.init(params), batch)
    assert np.isfinite(float(out.loss))
    # 70 KiB: [first, second's bias] [second's kernel, third's bias] [third's
    # kernel]; 140 KiB: everything but the third layer's kernel, then that
    g = _gauges()
    assert (g["fusion.buckets"], g["fusion.leaves_in_place"],
            g["fusion.leaves_packed"]) == \
        ({70 * KiB: 3, 140 * KiB: 2}[threshold], 6, 0)
    grads = jax.grad(loss_fn)(params, batch)
    want = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
    for g, w in zip(jax.tree.leaves(out.params), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


CONTROLS = {
    "unbroken": None,
    "as_benchmark_tests_test_control_breaks_it":
        lambda grads, **kw: grads,
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_the_step_s_exchange_goes_through_the_module_s_name(
        mesh4, monkeypatch, control):
    """``benchmark/tests/test_control.py`` leaves the exchange out by
    replacing ``distributed_optimizer.allreduce_gradients``: the step looks
    the name up when it is traced, calls it with the gradient tree and
    keywords, and with the replacement exchanges nothing but the loss."""
    params, loss_fn, batch = _three_layers()
    if CONTROLS[control] is not None:
        monkeypatch.setattr(dopt, "allreduce_gradients", CONTROLS[control])
    tx = hvd.DistributedOptimizer(optax.sgd(0.1),
                                  fusion_threshold_bytes=70 * KiB)
    step = hvd.make_train_step(loss_fn, tx, mesh=mesh4, donate=False)
    text = step.lower(params, tx.init(params), batch).as_text()
    collectives = re.findall(r"stablehlo\.all_reduce", text)
    if CONTROLS[control] is None:
        # the loss, and a leaf each (jax lowers a psum of several to an
        # all_reduce each; XLA's combiner makes a bucket's one)
        assert len(collectives) == 1 + 6
        # one that holds the chain behind the backward pass, and one
        # between a bucket and the next
        assert text.count("optimization_barrier") == 1 + 2
    else:
        assert len(collectives) == 1              # the loss alone
        assert "optimization_barrier" not in text
        rows = 4 * [batch[0][:4]], 4 * [batch[1][:4]]
        uneven = (jnp.concatenate([r * (i + 1) for i, r in
                                   enumerate(rows[0])]),
                  jnp.concatenate(rows[1]))
        out = step(params, tx.init(params), uneven)
        # every device kept its own gradient: replicated in name only
        shards = [np.asarray(s.data) for s in
                  out.params["third"]["w"].addressable_shards]
        assert any(not np.array_equal(shards[0], s) for s in shards[1:])


def test_one_device_s_step_holds_no_barrier():
    """An axis of one has nothing to exchange: nothing is chained, so the
    update stays free to fuse with what yields the gradients
    (``resnet50_train``'s program)."""
    params, loss_fn, batch = _three_layers()
    mesh1 = Mesh(np.array(jax.devices()[:1]), ("hvd",))
    tx = hvd.DistributedOptimizer(optax.sgd(0.1),
                                  fusion_threshold_bytes=70 * KiB)
    step = hvd.make_train_step(loss_fn, tx, mesh=mesh1, donate=False)
    text = step.lower(params, tx.init(params), batch).as_text()
    assert "optimization_barrier" not in text
    assert _gauges()["fusion.leaves_packed"] == 0
