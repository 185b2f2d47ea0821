"""Attention engines: dense, blockwise (online-softmax), ring, Ulysses.

Long-context sequence parallelism is absent from the reference (SURVEY.md §5
"Long-context / sequence parallelism: Absent"); the closest primitive is its
ragged allgather (operations.cc:841-901).  This module supplies the TPU-native
long-context stack as a first-class capability:

* :func:`dense_attention` — einsum softmax reference implementation.
* :func:`blockwise_attention` — ``lax.scan`` over KV chunks with the online
  (flash) softmax recurrence: O(L) memory, differentiable, jit-friendly.
* :func:`ring_attention` — sequence-parallel attention over a mesh axis:
  KV blocks rotate around the ring via ``lax.ppermute`` while each shard's
  queries accumulate, overlap-friendly on ICI (the pattern of Liu et al.'s
  Ring Attention, built from the same collective the reference's hierarchical
  allreduce uses for its ring leg).
* :func:`ulysses_attention` — DeepSpeed-Ulysses-style sequence parallelism:
  ``all_to_all`` seq→heads, full local attention, ``all_to_all`` back.

All functions take ``[B, L, H, Dh]`` Q and ``[B, L, KVH, Dh]`` K/V (GQA when
``KVH < H``) and accumulate in float32 regardless of input dtype.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """GQA: expand KV heads to match query heads ([B, L, KVH, D] → [B, L, H, D])."""
    if n_rep == 1:
        return k
    b, l, kvh, d = k.shape
    return jnp.broadcast_to(
        k[:, :, :, None, :], (b, l, kvh, n_rep, d)
    ).reshape(b, l, kvh * n_rep, d)


def dense_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
    q_offset: int | jax.Array = 0, kv_offset: int | jax.Array = 0,
    window: int | None = None,
) -> jax.Array:
    """Reference O(L²)-memory attention (the ground truth for tests).

    ``q_offset``/``kv_offset`` are the global positions of element 0 of the
    q/kv sequence axes — needed for causal masking on sequence shards.
    ``window`` (with ``causal``): query ``t`` sees key ``j`` iff
    ``0 <= t - j < window``.
    """
    b, lq, h, d = q.shape
    kvh = k.shape[2]
    k = _repeat_kv(k, h // kvh)
    v = _repeat_kv(v, h // kvh)
    scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        qpos = q_offset + jnp.arange(lq)[:, None]
        kpos = kv_offset + jnp.arange(k.shape[1])[None, :]
        s = jnp.where(_visible(qpos, kpos, window), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _visible(qpos, kpos, window):
    """The causal mask, narrowed to a band of ``window`` keys if given."""
    if window is None:
        return qpos >= kpos
    return (qpos >= kpos) & (qpos - kpos < window)


class _SoftmaxState(NamedTuple):
    """Online-softmax running state (the flash-attention recurrence)."""

    o: jax.Array      # [B, Lq, H, D] f32 unnormalized output accumulator
    m: jax.Array      # [B, H, Lq]    f32 running row max
    l: jax.Array      # [B, H, Lq]    f32 running row sum


def _init_state(q: jax.Array) -> _SoftmaxState:
    b, lq, h, d = q.shape
    return _SoftmaxState(
        o=jnp.zeros((b, lq, h, d), jnp.float32),
        m=jnp.full((b, h, lq), NEG_INF, jnp.float32),
        l=jnp.zeros((b, h, lq), jnp.float32),
    )


def _block_update(
    state: _SoftmaxState,
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, causal: bool, q_offset=0, kv_offset=0,
    q_positions: jax.Array | None = None,
    kv_positions: jax.Array | None = None,
    kv_valid: jax.Array | None = None,
    window: int | None = None,
) -> _SoftmaxState:
    """Fold one KV block into the running softmax state.

    ``q_positions``/``kv_positions``: optional explicit [Lq]/[Lk] global
    position vectors for non-contiguous sequence layouts (zig-zag ring
    sharding); they override the ``*_offset + arange`` default.
    ``kv_valid``: optional [Lk] bool mask for padded tail keys.
    """
    b, lq, h, d = q.shape
    kvh = k.shape[2]
    r = h // kvh
    lk = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    # GQA via grouped einsum (query head g = kv·r + j ↔ kv head g // r,
    # the _repeat_kv mapping): fold the r query heads onto their KV head
    # instead of materializing the repeat-expanded K/V — in the ring this
    # block runs per rotation step, so the expansion would cost r× the KV
    # traffic every step.  The merged (kvh, r) axes are adjacent and in
    # head order, so the reshape back to [B, H, ...] is a free view.
    qg = q.reshape(b, lq, kvh, r, d)
    s = jnp.einsum("bqkjd,bmkd->bkjqm", qg.astype(jnp.float32),
                   k.astype(jnp.float32)).reshape(b, h, lq, lk) * scale
    if causal:
        qpos = (q_positions if q_positions is not None
                else q_offset + jnp.arange(lq))[:, None]
        kpos = (kv_positions if kv_positions is not None
                else kv_offset + jnp.arange(lk))[None, :]
        s = jnp.where(_visible(qpos, kpos, window), s, NEG_INF)
    if kv_valid is not None:
        s = jnp.where(kv_valid[None, None, None, :], s, NEG_INF)
    m_new = jnp.maximum(state.m, s.max(axis=-1))
    # guard fully-masked rows: keep exp argument finite
    p = jnp.exp(s - m_new[..., None])
    correction = jnp.exp(state.m - m_new)
    l_new = state.l * correction + p.sum(axis=-1)
    o_new = (
        state.o * jnp.transpose(correction, (0, 2, 1))[..., None]
        + jnp.einsum("bkjqm,bmkd->bqkjd",
                     p.reshape(b, kvh, r, lq, lk),
                     v.astype(jnp.float32)).reshape(b, lq, h, d)
    )
    return _SoftmaxState(o_new, m_new, l_new)


def _finalize(state: _SoftmaxState, dtype) -> jax.Array:
    l = jnp.maximum(state.l, 1e-30)
    return (state.o / jnp.transpose(l, (0, 2, 1))[..., None]).astype(dtype)


def blockwise_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
    block_size: int = 512, q_offset=0, kv_offset=0,
    window: int | None = None,
) -> jax.Array:
    """O(L)-memory attention: scan over KV chunks with online softmax.
    ``window`` narrows the causal mask to a band (every chunk is still
    scanned: this is the oracle, not the fast path).

    Single-device analogue of ring attention (one ring step per local KV
    block); also the differentiable fallback the pallas flash kernel's
    backward recomputes through.
    """
    b, lkv, kvh, d = k.shape
    nblocks = max(1, math.ceil(lkv / block_size))
    pad = nblocks * block_size - lkv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(b, nblocks, block_size, kvh, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblocks, block_size, kvh, d).transpose(1, 0, 2, 3, 4)

    def step(state, inputs):
        i, kblk, vblk = inputs
        valid = (i * block_size + jnp.arange(block_size)) < lkv
        new = _block_update(
            state, q, kblk, vblk, causal=causal,
            q_offset=q_offset,
            kv_offset=kv_offset + i * block_size,
            kv_valid=valid if pad else None, window=window,
        )
        return new, None

    idx = jnp.arange(nblocks)
    state, _ = lax.scan(step, _init_state(q), (idx, kb, vb))
    return _finalize(state, q.dtype)


def zigzag_positions(rank, n: int, local_len: int) -> jax.Array:
    """Global positions of rank ``rank``'s local sequence slice under
    zig-zag sharding: the sequence is cut into ``2n`` blocks and rank r
    holds blocks ``r`` (head half) and ``2n-1-r`` (tail half), so every
    rank's causal workload is equal.  ``rank`` may be a traced scalar."""
    block = local_len // 2
    head = rank * block + jnp.arange(block)
    tail = (2 * n - 1 - rank) * block + jnp.arange(block)
    return jnp.concatenate([head, tail])


def _zigzag_order(n: int) -> list[int]:
    """Block layout of the zig-zag shard: ``0, 2n-1, 1, 2n-2, …, n-1, n`` —
    slice r of a contiguous shard over n ranks is blocks ``(r, 2n-1-r)``.
    The single source of truth for :func:`zigzag_shard`/``unshard`` and
    consistent with :func:`zigzag_positions` (tested against each other)."""
    order: list[int] = []
    for r in range(n):
        order.extend([r, 2 * n - 1 - r])
    return order


def _permute_blocks(x: jax.Array, n: int, axis: int, perm: list[int]) -> jax.Array:
    l = x.shape[axis]
    if l % (2 * n):
        raise ValueError(f"sequence length {l} not divisible by 2n={2 * n}")
    block = l // (2 * n)
    xs = jnp.moveaxis(x, axis, 0).reshape(2 * n, block, *[
        s for i, s in enumerate(x.shape) if i != axis
    ])
    xs = xs[jnp.asarray(perm)]
    return jnp.moveaxis(xs.reshape(l, *xs.shape[2:]), 0, axis)


def zigzag_shard(x: jax.Array, n: int, *, axis: int = 1) -> jax.Array:
    """Reorder a global sequence axis so that *contiguous* sharding over an
    ``n``-way mesh axis hands each rank its zig-zag block pair (see
    :func:`_zigzag_order`).  Inverse: :func:`zigzag_unshard`.
    """
    return _permute_blocks(x, n, axis, _zigzag_order(n))


def zigzag_unshard(x: jax.Array, n: int, *, axis: int = 1) -> jax.Array:
    """Inverse permutation of :func:`zigzag_shard`."""
    order = _zigzag_order(n)
    inverse = [0] * len(order)
    for pos, blk in enumerate(order):
        inverse[blk] = pos
    return _permute_blocks(x, n, axis, inverse)


def ring_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, axis_name: str,
    causal: bool = True, zigzag: bool = False,
) -> jax.Array:
    """Sequence-parallel ring attention over ``axis_name``.

    Call inside ``shard_map`` where the sequence axis is sharded: each rank
    holds ``[B, L/n, H, D]`` Q/K/V chunks.  KV rotates around the ring
    (``lax.ppermute``, reference-equivalent of the NCCL ring's neighbor
    exchange) while local queries fold each visiting block into the online
    softmax.  n-1 permutes, O(L/n) memory per chip, compute/comm overlap
    scheduled by XLA.

    Causality across chunks with contiguous sharding: rank r's queries
    attend fully to KV from ranks < r, causally to its own, not at all to
    ranks > r — masked blocks idle early ranks (the classic ring-attention
    load skew).  ``zigzag=True`` removes the skew: inputs must be laid out
    by :func:`zigzag_shard` (rank r holds sequence blocks r and 2n-1-r), so
    every rank does the same causal work per ring step; the output stays in
    zig-zag layout (undo with :func:`zigzag_unshard` after unsharding).
    """
    n = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    lc = q.shape[1]
    if zigzag and lc % 2:
        raise ValueError(f"zigzag ring needs an even local length, got {lc}")
    pos = (lambda r: zigzag_positions(r, n, lc)) if zigzag else (
        lambda r: r * lc + jnp.arange(lc)
    )
    qpos = pos(rank)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, i):
        state, kcur, vcur = carry
        src_rank = (rank - i) % n  # whose chunk we currently hold
        state = _block_update(
            state, q, kcur, vcur, causal=causal,
            q_positions=qpos, kv_positions=pos(src_rank),
        )
        knext = lax.ppermute(kcur, axis_name, perm)
        vnext = lax.ppermute(vcur, axis_name, perm)
        return (state, knext, vnext), None

    # n-1 rotated steps in the scan, last block folded outside it — the
    # final rotation's result would be discarded, and XLA cannot DCE a
    # collective inside the scan body (one full KV exchange saved per call).
    state = _init_state(q)
    # The zero-init state is unvarying over the mesh axis while the
    # updated state varies with this rank's q — under shard_map's
    # varying-axes check (check_vma, on by default) the scan carry types
    # would then mismatch.  Mark the init as varying so callers don't
    # need check_vma=False.
    _pvary = (functools.partial(lax.pcast, to="varying")
              if hasattr(lax, "pcast") else lax.pvary)  # jax < 0.8
    state = jax.tree.map(lambda x: _pvary(x, axis_name), state)
    if n > 1:
        (state, k, v), _ = lax.scan(step, (state, k, v), jnp.arange(n - 1))
    state = _block_update(
        state, q, k, v, causal=causal,
        q_positions=qpos, kv_positions=pos((rank - (n - 1)) % n),
    )
    return _finalize(state, q.dtype)


def ulysses_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, axis_name: str,
    causal: bool = True, impl=None,
) -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed Ulysses pattern).

    Inside ``shard_map`` with sequence sharded: all-to-all re-shards from
    [B, L/n, H, D] (seq-sharded) to [B, L, H/n, D] (head-sharded), runs full
    attention on the n-th of the heads, and all-to-alls back.  Requires
    ``H % n == 0``; one balanced a2a each way rides ICI's full bisection
    bandwidth.

    GQA with fewer KV heads than the axis (``KVH < n``): KV heads are
    expanded to ``n`` before their a2a (``n % KVH == 0`` required), so each
    device carries one (replicated-group) KV head.  The mapping stays
    consistent: device i's query heads [i·H/n, (i+1)·H/n) all belong to
    original KV head ``i // (n/KVH)``, which is exactly what expanded head
    i holds.  Costs (n/KVH)× the KV a2a bytes — still far below the q/o
    legs when H ≫ KVH, and it is what makes 8-way Ulysses possible on
    4-KV-head models at all.
    """
    n = lax.axis_size(axis_name)
    h, kvh = q.shape[2], k.shape[2]
    if h % n or (kvh % n if kvh >= n else n % kvh):
        raise ValueError(
            f"ulysses_attention needs H divisible by the axis size and "
            f"KVH % n == 0 or n % KVH == 0: H={h}, KVH={kvh}, n={n}"
        )
    if kvh < n:
        k = _repeat_kv(k, n // kvh)
        v = _repeat_kv(v, n // kvh)
    # seq-sharded → head-sharded
    qh = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    attend = impl or dense_attention
    oh = attend(qh, kh, vh, causal=causal)
    # head-sharded → seq-sharded
    return lax.all_to_all(oh, axis_name, split_axis=1, concat_axis=2, tiled=True)
