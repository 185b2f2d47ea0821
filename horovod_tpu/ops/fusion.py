"""Tensor Fusion — bucketed collectives.

TPU-native re-design of the reference's fusion buffer
(reference: horovod/common/operations.cc:788-812 lazy 64 MiB buffer alloc,
:999-1053/:1290-1369 memcpy in/out, :1916-1943 response merging ≤ threshold).

On TPU there is no hand-managed fusion buffer.  Tensors are bucketed
(:func:`plan_buckets`: consecutive, same dtype, at most
``HOROVOD_FUSION_THRESHOLD`` bytes) and each bucket is ONE collective, in
one of two forms:

* **packed** (:func:`fused_apply`; the eager engine buckets its pending ops
  with the same planner): a bucket's tensors are flattened and concatenated,
  reduced as one flat vector and cut out again.  The packing is not free
  inside ``jit``: where the collective survives (more than one device) the
  ``concatenate`` and the slices are ops of their own, about 1 ms of
  ResNet-50's 49 ms step on four v5e chips for 102 MB of gradients (ledger,
  PR 46, ``resnet50_dp4``: ``concatenate`` 0.0111 s, ``copy`` 0.0109 s and
  0.015 s more of ``slice-done`` in 1.43 s traced);
* **in place** (:func:`reduce_in_place`, what ``allreduce_gradients`` takes
  for a plain or cast Sum / Average): every tensor goes into its bucket's
  collective as it lies (a variadic collective over the bucket's tensors:
  no ``concatenate``, no slice), and the buckets are chained so that the
  compiler keeps them apart, behind the backward pass.  The proof is the
  compiled schedule, ``tests/test_chip_compile.py`` (the cases over
  ``dp4_step``), and the chip's readings in ``PERF.md`` section 6, PR 47.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from horovod_tpu import metrics
from horovod_tpu.utils.env import DEFAULT_FUSION_THRESHOLD_BYTES

#: A bucket of :func:`reduce_in_place` where the caller names no threshold.
#: Read on four v5e chips with ResNet-50's 102 MB in this placement (chained,
#: behind the backward pass, nothing packed; ``PERF.md``, PR 47): 48.29 ms a
#: step at 8 MiB (15 buckets), 48.29 at 16 MiB (7), 48.40 at the packed
#: plan's 64 MiB (2), and 48.49 for one variadic all-reduce of everything.
IN_PLACE_THRESHOLD_BYTES = 16 * 1024 * 1024


def _nbytes(x: jax.Array) -> int:
    return int(x.size) * x.dtype.itemsize


def plan_buckets(
    tensors: Sequence,
    threshold_bytes: int | None,
    *,
    nbytes=_nbytes,
    key=lambda t: t.dtype,
) -> list[list[int]]:
    """Greedy bucketing of *consecutive* same-key items ≤ threshold.

    Mirrors the response-merging loop of the reference coordinator
    (operations.cc:1916-1943): tensors join a fused response while they share
    a fuse key (by default: dtype) and the running size stays under the
    threshold.  A tensor larger than the threshold gets its own bucket (same
    as the reference, which falls back to an unfused response).

    ``nbytes`` and ``key`` generalize the planner so the eager engine can
    bucket pending ops by (kind, op, compression, dtype) with per-rank sizes
    — one policy, both paths.
    """
    if threshold_bytes is None:
        threshold_bytes = DEFAULT_FUSION_THRESHOLD_BYTES
    buckets: list[list[int]] = []
    cur: list[int] = []
    cur_bytes = 0
    cur_key = None
    for i, t in enumerate(tensors):
        nb = nbytes(t)
        k = key(t)
        if cur and (k != cur_key or cur_bytes + nb > threshold_bytes):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nb
        cur_key = k
        if threshold_bytes <= 0:  # fusion disabled: one tensor per bucket
            buckets.append(cur)
            cur, cur_bytes = [], 0
    if cur:
        buckets.append(cur)
    return buckets


def reduce_in_place(
    tensors: list[jax.Array],
    collective: Callable[[list[jax.Array]], list[jax.Array]],
    *,
    threshold_bytes: int | None = None,
    chained: bool = False,
) -> list[jax.Array]:
    """Apply a collective over lists to ``tensors`` bucket-by-bucket, in the
    list's own order, every tensor as it lies: ``collective`` receives a
    bucket's tensors and returns them reduced, shape for shape (a ``psum``
    over the list is one variadic all-reduce: no ``concatenate``, no slice).
    Returns per-tensor results in input order.

    ``chained`` keeps the buckets apart and behind what yields the tensors.
    Left alone the compiler merges independent all-reduces into one,
    whatever order they are written in; so each bucket's operands pass an
    ``optimization_barrier`` together with the bucket before's result, and
    the two collectives can neither be merged nor change places.  A chain
    alone is scheduled where its operands appear, inside the backward pass,
    and on a TPU v5e that is a loss: an all-reduce there is synchronous
    (``all-reduce-start`` "is not implemented on TPU"; the asynchronous
    fusion the compiler has instead takes single-operand collectives only,
    hid under half of their time and slowed the convolutions it ran beside
    by more), and a synchronous collective between the convolutions costs
    them 0.3-0.8 ms a step of ResNet-50's in evicted prefetches
    (``PERF.md`` section 6, PR 47).  So every tensor first passes one
    barrier, which holds the first bucket until the last gradient is there:
    the chain runs after the backward pass, bucket by bucket, each one's
    results going straight into the update.
    """
    if threshold_bytes is None:
        threshold_bytes = IN_PLACE_THRESHOLD_BYTES
    buckets = plan_buckets(tensors, threshold_bytes)
    chained = chained and len(buckets) > 1
    if chained:
        tensors = list(jax.lax.optimization_barrier(tuple(tensors)))
    out: list[jax.Array | None] = [None] * len(tensors)
    before: list[int] = []
    for bucket in buckets:
        operands = [tensors[i] for i in bucket]
        if chained and before:
            operands, held = jax.lax.optimization_barrier(
                (operands, [out[i] for i in before]))
            for i, r in zip(before, held):
                out[i] = r
        for i, r in zip(bucket, collective(operands), strict=True):
            out[i] = r
        before = bucket
    _publish(tensors, buckets, in_place=True)
    return out  # type: ignore[return-value]


def _publish(tensors: list, buckets: list[list[int]], *,
             in_place: bool) -> None:
    """Set the ``fusion.*`` gauges to what the exchange being traced does."""
    reg = metrics.DEFAULT
    leaves = sum(map(len, buckets))
    packed = 0 if in_place else sum(len(b) for b in buckets if len(b) > 1)
    reg.gauge("fusion.buckets").set(len(buckets))
    reg.gauge("fusion.bucket_bytes_max").set(max(
        (sum(_nbytes(tensors[i]) for i in b) for b in buckets), default=0))
    reg.gauge("fusion.leaves_in_place").set(leaves - packed)
    reg.gauge("fusion.leaves_packed").set(packed)


def fused_apply(
    tensors: list[jax.Array],
    collective: Callable[[jax.Array], jax.Array],
    *,
    threshold_bytes: int | None = None,
) -> list[jax.Array]:
    """Apply a flat-vector collective to ``tensors`` bucket-by-bucket, every
    bucket of several packed whole.

    ``collective`` receives a 1-D array (the fused buffer) and must return a
    same-shaped reduced array.  Returns per-tensor results in input order.
    """
    if not tensors:
        return []
    buckets = plan_buckets(tensors, threshold_bytes)
    out: list[jax.Array | None] = [None] * len(tensors)
    for bucket in buckets:
        if len(bucket) == 1:
            i = bucket[0]
            t = tensors[i]
            out[i] = collective(t.reshape(-1)).reshape(t.shape)
            continue
        flats = [tensors[i].reshape(-1) for i in bucket]
        fused = jnp.concatenate(flats)
        reduced = collective(fused)
        offset = 0
        for i in bucket:
            t = tensors[i]
            out[i] = lax_slice(reduced, offset, t.size).reshape(t.shape)
            offset += t.size
    _publish(tensors, buckets, in_place=False)
    return out  # type: ignore[return-value]


def lax_slice(x: jax.Array, start: int, length: int) -> jax.Array:
    """Static slice helper (keeps shapes static under jit)."""
    return jax.lax.slice(x, (start,), (start + length,))
