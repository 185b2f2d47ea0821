"""ZeRO-style sharded optimizer — reduce-scatter → shard update → all-gather.

No reference equivalent (the reference replicates optimizer state on every
worker, like pre-ZeRO data parallelism everywhere); this is the TPU-native
memory-scaling extension.  The dataflow per step, inside one compiled SPMD
program over the ``hvd`` axis:

1. Every rank computes local gradients (standard backward).
2. The flattened gradient vector is ``psum_scatter``-ed: each rank receives
   the *reduced* 1/n-th it owns (half the wire cost of a full allreduce —
   the reduce-scatter leg the reference's hierarchical allreduce uses
   internally, operations.cc:1135-1158, promoted to the whole step).
3. The optimizer update runs on the rank's shard only — optimizer state
   (Adam moments etc.) lives at 1/n per chip.  ZeRO stages 1+2.
4. The updated parameter shard is ``all_gather``-ed back to a full vector.

Works with **elementwise** optax transforms (adam/adamw/sgd/rmsprop/…):
each parameter element's update depends only on its own gradient/state.
Transforms that need global statistics across the whole pytree would see
per-shard statistics — for the common case, gradient clipping, pass
``clip_global_norm=`` instead: the true global norm is one extra ``psum``
of per-shard squared norms, computed on the *reduced* gradient exactly as
``optax.clip_by_global_norm`` would see it in the replicated setup.

Memory per chip: params P (replicated) + reduced grads P/n + opt state
S/n, versus P + P + S for the replicated wrapper — for Adam (S = 2P) on
8 chips, optimizer+gradient memory drops from 3P to ~0.4P.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.flatten_util import ravel_pytree
from jax.sharding import PartitionSpec as P

from horovod_tpu import basics
from horovod_tpu.basics import AXIS_NAME


class ZeroStepResult(NamedTuple):
    params: Any
    opt_state: Any       # sharded: array leaves hold the rank's 1/n slice
    loss: jax.Array


def make_zero_train_step(
    loss_fn: Callable[..., jax.Array],
    optimizer: optax.GradientTransformation,
    *,
    mesh: jax.sharding.Mesh | None = None,
    axis_name: str = AXIS_NAME,
    clip_global_norm: float | None = None,
    donate: bool = True,
) -> tuple[Callable[..., ZeroStepResult], Callable[[Any], Any]]:
    """Build a ZeRO train step; returns ``(step, init_opt_state)``.

    ``step(params, opt_state, batch) -> ZeroStepResult`` with replicated
    params and rank-sharded opt_state; ``batch`` leaves are rank-major.
    ``init_opt_state(params)`` creates the sharded state (each rank
    initializes only its own flat slice).

    The optimizer operates on ONE flat vector shard per rank, so its state
    arrays are ``[ceil(P/n)]`` regardless of the parameter pytree; scalar
    state leaves (step counts) stay replicated.  Programs are built once
    per parameter structure and cached.

    ``donate`` (default True): the input ``params``/``opt_state`` buffers
    are donated to the step — do not reuse them after calling; keep the
    returned ones (pass ``donate=False`` to keep inputs alive, at the cost
    of holding two parameter copies during the step).
    """
    if mesh is None:
        mesh = basics.mesh()
    n = int(mesh.devices.size)
    built: dict = {}

    def _build(params):
        # Cache key from structure + leaf shapes/dtypes only — no data
        # movement on the hot path (ravel_pytree concatenates the whole
        # pytree on device, which must happen once per structure, not once
        # per step).
        key = (
            jax.tree.structure(params),
            tuple((l.shape, jnp.dtype(l.dtype).name)
                  for l in jax.tree.leaves(params)),
        )
        if built.get("key") == key:
            return built
        flat0, unravel = ravel_pytree(params)
        total = int(flat0.shape[0])
        per = -(-total // n)                 # ceil: padded shard length
        pad = per * n - total
        # Optimizer-state layout for one shard: arrays shard over the axis,
        # scalars (e.g. Adam's count) replicate.
        shapes = jax.eval_shape(
            optimizer.init, jax.ShapeDtypeStruct((per,), flat0.dtype)
        )
        opt_specs = jax.tree.map(
            lambda l: P(axis_name) if len(l.shape) else P(), shapes
        )

        def my_slice(flat):
            idx = lax.axis_index(axis_name)
            padded = jnp.pad(flat, (0, pad)) if pad else flat
            return lax.dynamic_slice(padded, (idx * per,), (per,))

        def init_inner(flat):
            return optimizer.init(my_slice(flat))

        init_jitted = jax.jit(
            jax.shard_map(
                init_inner, mesh=mesh, in_specs=P(), out_specs=opt_specs,
                check_vma=False,
            )
        )

        def step_inner(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            gflat, _ = ravel_pytree(grads)
            gflat = (jnp.pad(gflat, (0, pad)) if pad else gflat) / n  # mean
            gshard = lax.psum_scatter(gflat, axis_name, tiled=True)   # [per]
            if clip_global_norm is not None:
                # True global norm from shard pieces: ||g||² = Σ_ranks ||g_r||²
                # (shards are disjoint).  Matches optax.clip_by_global_norm
                # on the replicated full gradient.
                gsq = lax.psum(jnp.sum(gshard.astype(jnp.float32) ** 2),
                               axis_name)
                gnorm = jnp.sqrt(gsq)
                scale = jnp.minimum(1.0, clip_global_norm / (gnorm + 1e-16))
                gshard = gshard * scale.astype(gshard.dtype)
            pshard = my_slice(ravel_pytree(params)[0])
            updates, opt_state = optimizer.update(gshard, opt_state, pshard)
            pshard = optax.apply_updates(pshard, updates)
            pfull = lax.all_gather(pshard, axis_name, tiled=True)[:total]
            return ZeroStepResult(
                unravel(pfull), opt_state, lax.pmean(loss, axis_name)
            )

        step_jitted = jax.jit(
            jax.shard_map(
                step_inner, mesh=mesh,
                in_specs=(P(), opt_specs, P(axis_name)),
                out_specs=ZeroStepResult(P(), opt_specs, P()),
                check_vma=False,
            ),
            # Donate params/opt_state (shapes+shardings match outputs) so
            # the step doesn't hold duplicate replicated-param buffers —
            # the memory headroom is the feature's point.
            donate_argnums=(0, 1) if donate else (),
        )
        built.update(key=key, init=init_jitted, step=step_jitted)
        return built

    def init_opt_state(params):
        b = _build(params)
        return b["init"](ravel_pytree(params)[0])

    def step(params, opt_state, batch):
        b = _build(params)
        out = b["step"](params, opt_state, batch)
        if jax.default_backend() == "cpu":
            # Same CPU-simulation dispatch-depth throttle as make_train_step.
            jax.block_until_ready(out.loss)
        return out

    return step, init_opt_state
