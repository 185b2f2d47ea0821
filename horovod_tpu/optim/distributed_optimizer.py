"""DistributedOptimizer — data-parallel gradient averaging, optax-native.

TPU-native re-design of the reference's optimizer wrappers:
* TF graph mode: ``DistributedOptimizer.compute_gradients`` allreduces each
  gradient (reference horovod/tensorflow/__init__.py:135-225).
* PyTorch: per-parameter grad hooks fire ``allreduce_async_`` during
  backward; ``step()`` synchronizes (reference horovod/torch/__init__.py:86-227).
* Fork extras: ``is_sparse`` top-k mode (:141-151, 202-216) and the
  ``local`` no-communication flag (:115, 158).

On TPU the optimizer lives inside ONE compiled SPMD program, so "hook per
gradient + background fusion" collapses into a gradient transformation:
``DistributedOptimizer(tx)`` returns an ``optax.GradientTransformation``
whose ``update`` all-reduces the gradient pytree over the mesh axis (fused
into ≤ threshold buckets, compression applied) before delegating to ``tx``.
Nothing of it overlaps with the backward pass, and on a TPU v5e nothing
should: left to itself XLA merges the psums of a step into one all-reduce
after the last convolution; chained buckets are placed inside the backward
pass, synchronous, and slow the convolutions around them; under the
compiler's options for asynchronous all-reduce less than half of the
exchange is hidden and the step is slower still (``PERF.md`` section 6,
PR 47: the plans tried on four chips, with the schedule each compiled to).
What the plan buys is the packing: :func:`allreduce_gradients` reduces every
leaf where it lies, in buckets chained and held behind the backward pass
(``ops/fusion.py``).  The proof is the schedule compiled for four described
chips (``tests/test_chip_compile.py``, the cases over ``dp4_step``) and the
ledger's ``resnet50_dp4``.

Use inside ``shard_map``/``pjit`` over a mesh with the data axis, or via
:func:`make_train_step`, which builds the canonical step function.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax

from horovod_tpu import basics
from horovod_tpu.basics import AXIS_NAME
from horovod_tpu.ops import collective_ops, fusion
from horovod_tpu.ops.collective_ops import Average, Sum, _ReduceOp
from horovod_tpu.ops.compression import Compression, TopKCompressor


def allreduce_gradients(
    grads: Any,
    *,
    op: _ReduceOp = Average,
    axis_name=AXIS_NAME,
    compression=Compression.none,
    fusion_threshold_bytes: int | None = None,
    sparse: bool = False,
    sparse_ratio: float = 0.01,
    process_set=None,
) -> Any:
    """All-reduce a gradient pytree over the mesh axis, fused.

    The in-graph analogue of the reference's per-gradient
    ``hvd.allreduce(grad, average=True, compression=...)`` loop
    (tensorflow/__init__.py:183-209), with Tensor Fusion applied
    structurally: leaves are bucketed (same dtype, ≤ threshold bytes) and
    each bucket is ONE psum (operations.cc:1916-1943's merge, compiled).

    A plain or cast-compressed Sum / Average reduces every leaf where it
    lies (:func:`fusion.reduce_in_place`: a bucket is one variadic psum,
    nothing is concatenated or cut out again; 16 MiB a bucket where
    ``fusion_threshold_bytes`` is None), the buckets chained behind what
    yields the gradients.  Everything else keeps its packed wire
    (:func:`collective_ops.grouped_allreduce`, 64 MiB a bucket): int8's
    blocks want the flat buffer, Adasum is one collective a leaf, a
    ``process_set`` has its groups; ``sparse`` has its all-gathers.
    """
    leaves, treedef = jax.tree.flatten(grads)
    if sparse and process_set is not None:
        raise ValueError(
            "process_set does not compose with the top-k sparse path; "
            "members-only sparse reduction needs a set-local allgather"
        )
    if sparse:
        topk = TopKCompressor(ratio=sparse_ratio)
        reduced = [
            topk.sparse_allreduce(g, average=op is Average, axis_name=axis_name)
            for g in leaves
        ]
    elif (
        op in (Sum, Average)
        and process_set is None
        and not callable(getattr(compression, "quantized_allreduce", None))
    ):

        def reduce_bucket(leaves):
            wire, ctxs = zip(*map(compression.compress, leaves))
            return [
                compression.decompress(r, ctx) for r, ctx in zip(
                    collective_ops._reduce(list(wire), op, axis_name), ctxs)
            ]

        reduced = fusion.reduce_in_place(
            leaves,
            reduce_bucket,
            threshold_bytes=fusion_threshold_bytes,
            # one member has nothing to exchange: XLA drops its collectives,
            # and a barrier would only keep the update out of the fusions
            # the gradients end in
            chained=collective_ops._axis_size(axis_name) > 1,
        )
    else:
        reduced = collective_ops.grouped_allreduce(
            leaves,
            op=op,
            axis_name=axis_name,
            compression=compression,
            fusion_threshold_bytes=fusion_threshold_bytes,
            process_set=process_set,
        )
    return jax.tree.unflatten(treedef, reduced)


class _StatefulCompressionState(NamedTuple):
    """Optimizer-state wrapper when a stateful compressor is attached:
    ``comp`` holds residuals / warm-started factors, ``inner`` the wrapped
    optax state."""

    comp: Any
    inner: Any


def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    *,
    op: _ReduceOp = Average,
    axis_name=AXIS_NAME,
    compression=Compression.none,
    fusion_threshold_bytes: int | None = None,
    is_sparse: bool = False,
    sparse_ratio: float = 0.01,
    local: bool = False,
    backward_passes_per_step: int = 1,
    process_set=None,
) -> optax.GradientTransformation:
    """Wrap an optax optimizer so updates see globally-averaged gradients.

    Parity table with the reference wrapper kwargs:

    ================  =========================================================
    reference                         here
    ================  =========================================================
    ``compression``    ``compression=`` (none / fp16 / bf16 / int8)
    ``sparse_as_dense``  not needed — JAX gradients are dense pytrees
    fork ``is_sparse``   ``is_sparse=True`` + ``sparse_ratio`` (top-k path)
    fork ``self.local``  ``local=True`` skips communication entirely
    ``device_dense`` …  owned by XLA (no device staging knobs on TPU)
    ``backward_passes_per_step``  same name: accumulate k local steps, then
                       one fused allreduce + update (optax.MultiSteps around
                       the reducing transform, so the collective only runs
                       on the flush step — reference torch/__init__.py:115)
    ================  =========================================================

    Must run inside SPMD code where ``axis_name`` is bound (shard_map/pjit
    over the hvd mesh) — the analogue of "must run under mpirun".

    ``compression`` may also be a *stateful* compressor implementing the
    ``init(grads_template)`` / ``reduce(grads, state, ...)`` protocol —
    :class:`horovod_tpu.ops.powersgd.PowerSGDCompressor` or
    :class:`~horovod_tpu.ops.powersgd.ErrorFeedback` around topk/int8.  Its
    state (residuals, warm-started factors) rides in the optimizer state.
    """
    from horovod_tpu.ops.powersgd import (
        as_stateful_compressor,
        is_stateful_compressor,
    )

    # local=True never touches the wire, so residuals/factors would be dead
    # gradient-sized state — skip the stateful machinery entirely.
    stateful = is_stateful_compressor(compression) and not local
    if stateful:
        compression = as_stateful_compressor(compression)
        if is_sparse:
            raise ValueError(
                "is_sparse picks the top-k collective; a stateful compressor "
                "already defines its own wire — wrap TopKCompressor in "
                "ErrorFeedback instead of combining the two flags."
            )
        if process_set is not None:
            raise ValueError(
                "process_set does not compose with stateful compressors "
                "(PowerSGD / ErrorFeedback): their collectives run over "
                "the full axis — silent full-world mixing would corrupt "
                "member updates"
            )
        if op not in (Sum, Average):
            raise ValueError(
                f"stateful compressors support op=Sum/Average, not {op}"
            )

    def init_fn(params):
        inner = optimizer.init(params)
        if stateful:
            return _StatefulCompressionState(
                comp=compression.init(params), inner=inner
            )
        return inner

    def update_fn(grads, state, params=None, **extra):
        comp, inner = (state.comp, state.inner) if stateful else (None, state)
        if local:
            reduced = grads
        elif stateful:
            reduced, comp = compression.reduce(
                grads, comp, axis_name=axis_name, average=op is Average
            )
        else:
            reduced = allreduce_gradients(
                grads,
                op=op,
                axis_name=axis_name,
                compression=compression,
                fusion_threshold_bytes=fusion_threshold_bytes,
                sparse=is_sparse,
                sparse_ratio=sparse_ratio,
                process_set=process_set,
            )
        updates, inner = optimizer.update(reduced, inner, params, **extra)
        if stateful:
            return updates, _StatefulCompressionState(comp=comp, inner=inner)
        return updates, inner

    tx = optax.GradientTransformation(init_fn, update_fn)
    if backward_passes_per_step > 1:
        # Accumulation OUTSIDE the reducing transform: k local micro-grads
        # accumulate with no communication, and the allreduce inside
        # update_fn runs once per k steps on the accumulated gradient.
        # use_grad_mean=False: accumulate by SUM, matching the reference's
        # autograd hooks which add into .grad over the k backward passes
        # (torch/__init__.py:115-165) — a ported script keeps its
        # learning-rate behavior.
        return optax.MultiSteps(
            tx, every_k_schedule=backward_passes_per_step,
            use_grad_mean=False,
        ).gradient_transformation()
    return tx


class TrainStepResult(NamedTuple):
    params: Any
    opt_state: Any
    loss: jax.Array


class TrainStepAuxResult(NamedTuple):
    """:class:`TrainStepResult` of a step built with ``has_aux``: the same
    three fields and the loss function's counters, summed over the ranks."""
    params: Any
    opt_state: Any
    loss: jax.Array
    aux: Any


def make_train_step(
    loss_fn: Callable[..., jax.Array],
    optimizer: optax.GradientTransformation,
    *,
    mesh: jax.sharding.Mesh | None = None,
    axis_name: str = AXIS_NAME,
    donate: bool = True,
    has_aux: bool = False,
) -> Callable[..., TrainStepResult]:
    """Build the canonical data-parallel train step, compiled over the mesh.

    ``loss_fn(params, batch) -> scalar`` is the user's per-shard loss;
    ``optimizer`` is typically ``DistributedOptimizer(...)``.  The returned
    function takes ``(params, opt_state, batch)`` where ``batch`` leaves are
    rank-major (dim 0 == world size × local batch) and params/opt_state are
    replicated (they are put on the mesh on the way in if they are not, so
    a bare ``optimizer.init(params)`` will do); it returns updated
    replicated params, opt_state, and the globally-averaged loss.

    With ``has_aux`` the loss function returns ``(loss, aux)``, ``aux`` a
    tree of counters (what a model counts while it computes: choices routed,
    blocks visited); they leave the step summed over the ranks, as ``aux``
    of a :class:`TrainStepAuxResult`, and the host reads them when it wants
    to.  Without it the step is traced as it always was.

    This is the whole L5→L2 stack of the reference collapsed into one
    compiled program: examples/tensorflow_mnist.py:85's
    ``opt.minimize(loss)`` → stack §3.2 of SURVEY.md.
    """
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        mesh = basics.mesh()

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn, has_aux=has_aux)(
            params, batch)
        if has_aux:
            loss, aux = loss
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        mean_loss = collective_ops.allreduce(loss, op=Average, axis_name=axis_name)
        if has_aux:
            return TrainStepAuxResult(
                params, opt_state, mean_loss,
                jax.tree.map(lambda a: jax.lax.psum(a, axis_name), aux))
        return TrainStepResult(params, opt_state, mean_loss)

    smapped = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(), P(), P(axis_name)),
        out_specs=(TrainStepAuxResult(P(), P(), P(), P()) if has_aux
                   else TrainStepResult(P(), P(), P())),
        check_vma=False,
    )
    jitted = jax.jit(smapped, donate_argnums=(0, 1) if donate else ())
    replicated = jax.sharding.NamedSharding(mesh, P())
    # CPU-simulation only: XLA's in-process CPU collectives deadlock (40 s
    # rendezvous abort) when many launches of a collective module are in
    # flight at once — the N virtual devices share one thread pool, so deep
    # async dispatch can starve a device thread out of an active rendezvous.
    # Blocking per step caps the in-flight depth at 1; on TPU the async
    # pipeline is left untouched.
    throttle = jax.default_backend() == "cpu"

    def train_step(params, opt_state, batch):
        # The step returns params and state replicated over the mesh.  State
        # that arrives anywhere else (a bare ``tx.init(params)`` sits on one
        # device) is a different signature from what every later call gets
        # back, and jit would compile the whole step a second time — so it
        # is put on the mesh here, which costs nothing once it is there.
        params, opt_state = _on_mesh((params, opt_state), replicated)
        out = jitted(params, opt_state, batch)
        if throttle:
            jax.block_until_ready(out.loss)
        return out

    def lower(params, opt_state, batch):
        params, opt_state = _on_mesh((params, opt_state), replicated)
        return jitted.lower(params, opt_state, batch)

    train_step.lower = lower
    train_step._cache_size = jitted._cache_size
    return train_step


def _on_mesh(tree: Any, sharding: jax.sharding.Sharding) -> Any:
    """``tree`` with every leaf on ``sharding``; the tree itself when it
    already is (the steady state: one comparison per leaf)."""
    if all(getattr(leaf, "sharding", None) == sharding
           for leaf in jax.tree.leaves(tree)):
        return tree
    return jax.device_put(tree, sharding)


# ---------------------------------------------------------------------------
# State broadcast: model init sync and optimizer-state sync.
# ---------------------------------------------------------------------------


def _root_process(root_rank: int) -> int:
    """Process index owning device rank ``root_rank`` on the world mesh —
    the single definition of the rank→process mapping used by every
    any-root broadcast."""
    return list(basics.mesh().devices.flat)[root_rank].process_index


def broadcast_parameters(params: Any, root_rank: int = 0) -> Any:
    """Make every process agree with the root's parameter pytree.

    Parity with reference ``hvd.broadcast_parameters``
    (horovod/torch/__init__.py:270-299) / ``BroadcastGlobalVariablesHook``
    (tensorflow/__init__.py:101-132).

    Single-controller: the controller already holds THE copy, so this
    re-places leaves with replicated sharding over the mesh (the
    device-broadcast XLA would emit) and returns them.  Multi-controller:
    the values of the process owning device ``root_rank`` travel to all
    hosts over DCN — a direct one-to-all when the root lives on process 0,
    else a process allgather + select (the reference supports any
    ``root_rank``, horovod/torch/__init__.py:270-299).
    """
    basics._require_init()
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        return multihost_utils.broadcast_one_to_all(
            params, is_source=jax.process_index() == _root_process(root_rank)
        )
    sharding = basics.replicated_sharding()
    return jax.tree.map(lambda x: jax.device_put(jnp.asarray(x), sharding), params)


def broadcast_optimizer_state(opt_state: Any, root_rank: int = 0) -> Any:
    """Broadcast an optax optimizer state pytree.

    The reference needs 100 lines of scalar→tensor wrapping and recursive
    cast callbacks because torch optimizer state mixes tensors and Python
    scalars (torch/__init__.py:302-418).  optax states are pytrees, so the
    only special case is non-array leaves (step counts as Python ints):
    they are wrapped, broadcast, and cast back.
    """
    basics._require_init()
    import numpy as np

    leaves, treedef = jax.tree.flatten(opt_state)
    py_types = [None if isinstance(l, jax.Array) else type(l) for l in leaves]
    wrapped = [jnp.asarray(l) for l in leaves]
    out = broadcast_parameters(wrapped, root_rank)

    def _restore(t, leaf):
        if t is None:
            return leaf
        if issubclass(t, np.ndarray):
            # np.ndarray(x) is the low-level buffer constructor (treats ints
            # as a shape!); np.asarray is the value-preserving conversion.
            return np.asarray(leaf)
        return t(leaf)

    restored = [_restore(t, leaf) for t, leaf in zip(py_types, out)]
    return jax.tree.unflatten(treedef, restored)


def _mesh_local_rows() -> int:
    """How many rows of the rank-major array this process owns — counted
    on the WORLD MESH, not jax.local_device_count(): a device-subset init
    may exclude some local devices from the mesh."""
    me = jax.process_index()
    return sum(
        1 for d in basics.mesh().devices.flat if d.process_index == me
    )


def _process_first_rows() -> dict[int, int]:
    """process index → first global rank (mesh device-order row) owned by
    that process.  Consults the actual mesh device order, like
    ``_root_process`` — mesh order is NOT guaranteed process-contiguous."""
    first: dict[int, int] = {}
    for r, d in enumerate(basics.mesh().devices.flat):
        first.setdefault(d.process_index, r)
    return first


def _process_rank_major(local) -> jax.Array:
    """This process's payload, tiled to its local device rows of the global
    rank-major array (every local device carries the same bytes)."""
    import numpy as np

    rows = np.broadcast_to(local[None], (_mesh_local_rows(),) + local.shape)
    return jax.make_array_from_process_local_data(basics.rank_sharding(), rows)


def broadcast_object(obj: Any, root_rank: int = 0) -> Any:
    """Broadcast an arbitrary picklable object (the resume-epoch pattern of
    reference examples/keras_imagenet_resnet50.py:66-73).

    ``root_rank`` is a device rank; the object travels from the process
    that owns that device (any root works, like ``broadcast_parameters``).

    The wire goes THROUGH the eager engine, not an out-of-band host
    collective: multi-process XLA collectives are matched by arrival order
    on shared transport pairs, so an out-of-band broadcast racing the
    engine's cycle-thread dispatches can pair with the WRONG collective on
    a peer still draining engine traffic ("received data size doesn't
    match expected size").  Enqueueing serializes it with every queued
    engine op — the same reasoning as the torch frontend's
    shape negotiation (torch.py _negotiate_gather_shapes).
    """
    basics._require_init()
    if jax.process_count() == 1:
        return obj
    import pickle

    import numpy as np

    from horovod_tpu.ops import eager as eager_ops

    is_source = basics.cross_rank() == _root_process(root_rank)
    payload = (np.frombuffer(pickle.dumps(obj), np.uint8) if is_source
               else np.zeros((0,), np.uint8))
    length = np.asarray([payload.size], np.int32)
    h = eager_ops.broadcast_async(
        _process_rank_major(length), root_rank, name="bo.len"
    )
    n = int(np.asarray(jax.device_get(eager_ops.synchronize(h)))[0])
    if not is_source:
        payload = np.zeros((n,), np.uint8)
    h = eager_ops.broadcast_async(
        _process_rank_major(payload), root_rank, name="bo.payload"
    )
    data = jax.device_get(eager_ops.synchronize(h))
    return pickle.loads(bytes(bytearray(np.asarray(data))))


def allgather_object(obj: Any) -> list:
    """Gather one picklable object per PROCESS; every process receives the
    ``cross_size()``-long list ordered by process index.

    The object-level sibling of the eager ``allgather`` (an API later
    Horovod versions grew; natural here for gathering per-host metrics or
    shapes).  Wire format: lengths all-gather, pad to max, bytes
    all-gather, unpickle — all THROUGH the engine queue (see
    :func:`broadcast_object` for why out-of-band host collectives are a
    cross-rank ordering hazard).
    """
    basics._require_init()
    if jax.process_count() == 1:
        return [obj]
    import pickle

    import numpy as np

    from horovod_tpu.ops import eager as eager_ops

    payload = pickle.dumps(obj)
    h = eager_ops.allgather_async(
        _process_rank_major(np.asarray([[len(payload)]], np.int32)),
        name="ao.len",
    )
    lengths = np.asarray(
        jax.device_get(eager_ops.synchronize(h))
    ).reshape(-1)                                       # [size] (per device)
    pad = int(lengths.max())
    buf = np.frombuffer(payload.ljust(pad, b"\0"), np.uint8)
    h = eager_ops.allgather_async(
        _process_rank_major(buf[None]), name="ao.payload"
    )
    data = np.asarray(
        jax.device_get(eager_ops.synchronize(h))
    ).reshape(-1, pad)                                  # [size, pad]
    # One row per participating process, in process-index order, located
    # through the mesh's actual device order (not an assumed contiguity).
    return [
        pickle.loads(bytes(bytearray(data[r]))[: int(lengths[r])])
        for _, r in sorted(_process_first_rows().items())
    ]
