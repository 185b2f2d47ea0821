"""``fit`` — the Keras-frontend training loop.

Parity with the reference's Keras integration core
(reference: horovod/_keras/__init__.py:20-109 ``create_distributed_optimizer``
+ the callback protocol of horovod/_keras/callbacks.py): one call wires up
broadcast-at-start, per-batch distributed stepping, per-epoch metric
averaging, and the LR callbacks.  The distributed optimizer here is the
compiled :func:`horovod_tpu.DistributedOptimizer` (gradients all-reduced
inside the jitted step), so the loop body is one XLA program per batch.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import optax

from horovod_tpu import basics
from horovod_tpu.callbacks import Callback
from horovod_tpu.optim.distributed_optimizer import make_train_step


def make_eval_step(
    metric_fn: Callable[[Any, Any], dict],
    *,
    mesh=None,
    axis_name: str = basics.AXIS_NAME,
) -> Callable[[Any, Any], dict]:
    """Compile a distributed evaluation step.

    ``metric_fn(params, batch) -> {name: scalar}`` computes per-shard
    metrics; the returned function takes replicated ``params`` and a
    rank-major ``batch`` and returns the metrics averaged over the mesh —
    the compiled per-batch analogue of ``MetricAverageCallback``
    (reference horovod/_keras/callbacks.py:33-67 allreduces epoch metrics).
    """
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.ops import collective_ops
    from horovod_tpu.ops.collective_ops import Average

    if mesh is None:
        mesh = basics.mesh()

    def step(params, batch):
        metrics = metric_fn(params, batch)
        return {
            k: collective_ops.allreduce(
                jnp.asarray(v), op=Average, axis_name=axis_name
            )
            for k, v in metrics.items()
        }

    jitted = jax.jit(
        jax.shard_map(
            step, mesh=mesh, in_specs=(P(), P(axis_name)), out_specs=P(),
            check_vma=False,
        )
    )
    if jax.default_backend() != "cpu":
        return jitted

    def throttled(params, batch):
        # CPU-simulation: cap in-flight collective launches at 1 (see
        # make_train_step's comment on the in-process rendezvous limit).
        out = jitted(params, batch)
        jax.block_until_ready(out)
        return out

    return throttled


def fit(
    params: Any,
    optimizer: optax.GradientTransformation,
    loss_fn: Callable[[Any, Any], jax.Array],
    train_loader,
    *,
    epochs: int = 1,
    initial_epoch: int = 0,
    opt_state: Any = None,
    callbacks: Sequence[Callback] = (),
    eval_loader=None,
    eval_metric_fn: Callable[[Any, Any], dict] | None = None,
    verbose: bool = True,
) -> tuple[Any, Any, list[dict]]:
    """Train ``params`` with a compiled distributed step; returns
    ``(params, opt_state, history)``.

    * ``optimizer``: typically ``hvd.DistributedOptimizer(optax...)``.
    * ``train_loader``: yields rank-major batches (see
      :class:`horovod_tpu.data.ShardedLoader`); ``set_epoch`` is called per
      epoch when available (the DistributedSampler convention, reference
      examples/pytorch_mnist.py:50).
    * ``callbacks``: state pytree is ``(params, opt_state)`` — e.g.
      ``BroadcastGlobalVariablesCallback`` syncs both, matching the
      reference's broadcast of variables AND optimizer slots.
    * ``eval_metric_fn(params, batch) -> dict`` metrics are averaged over
      eval batches and merged into the epoch history.
    * ``initial_epoch``: first epoch index to run (the Keras resume
      parameter — reference examples/keras_imagenet_resnet50.py:171 passes
      ``initial_epoch=resume_from_epoch`` after the rank-0 checkpoint
      scan + broadcast); epoch-indexed callbacks (warmup/staircase
      schedules) then see the true epoch number.
    """
    if opt_state is None:
        opt_state = optimizer.init(params)
    step = make_train_step(loss_fn, optimizer)

    state = (params, opt_state)
    for cb in callbacks:
        state = cb.on_train_begin(state)
    params, opt_state = state

    history: list[dict] = []
    for epoch in range(initial_epoch, epochs):
        if hasattr(train_loader, "set_epoch"):
            train_loader.set_epoch(epoch)
        state = (params, opt_state)
        for cb in callbacks:
            state = cb.on_epoch_begin(epoch, state)
        params, opt_state = state

        losses = []
        for i, batch in enumerate(train_loader):
            state = (params, opt_state)
            for cb in callbacks:
                state = cb.on_batch_begin(i, state)
            params, opt_state = state
            out = step(params, opt_state, batch)
            params, opt_state = out.params, out.opt_state
            losses.append(out.loss)

        metrics = {"loss": float(jnp.mean(jnp.stack(losses)))} if losses else {}
        if eval_loader is not None and eval_metric_fn is not None:
            on_cpu = jax.default_backend() == "cpu"
            accum: dict[str, list] = {}
            for batch in eval_loader:
                m = eval_metric_fn(params, batch)
                if on_cpu:
                    # Same CPU-simulation throttle as make_train_step: cap
                    # in-flight collective launches at 1 (see the comment
                    # there on the in-process rendezvous limit).
                    jax.block_until_ready(m)
                for k, v in m.items():
                    accum.setdefault(k, []).append(v)
            for k, vs in accum.items():
                metrics[f"val_{k}"] = float(jnp.mean(jnp.stack(vs)))
        for cb in callbacks:
            metrics = cb.on_epoch_end(epoch, (params, opt_state), metrics)
        metrics = {
            k: float(v) if hasattr(v, "item") else v for k, v in metrics.items()
        }
        history.append(metrics)
        if verbose and basics.rank() == 0:
            line = "  ".join(f"{k}={v:.4f}" for k, v in metrics.items())
            print(f"Epoch {epoch + 1}/{epochs}  {line}")
    return params, opt_state, history
