"""Pallas flash-attention kernel (TPU).

The hot op of the flagship transformer, written for the MXU/VMEM model of
/opt/skills/guides/pallas_guide.md: the KV loop is the innermost grid
dimension, the online-softmax state (acc / row-max / row-sum) lives in VMEM
scratch that persists across KV steps, and the normalized output tile is
written once on the last step.  Causally-masked-out KV blocks are skipped
with ``pl.when`` (no wasted MXU work past the diagonal).

Backward: the standard two-pass flash scheme as two more pallas kernels —
the forward saves the per-row log-sum-exp, ``delta = rowsum(dO·O)`` is
computed in XLA, then one kernel accumulates dK/dV over query blocks and one
accumulates dQ over key blocks.  No [L, L] materialization anywhere, and the
training hot path stays at MXU-kernel speed end to end.  Set
``HVD_TPU_FLASH_BWD=blockwise`` to fall back to recomputing gradients
through :func:`horovod_tpu.parallel.attention.blockwise_attention` (the
cross-check oracle the tests compare against).

The kernels are always compiled by Mosaic.  Mosaic has no CPU target, so
the CPU test suite opts into the Pallas interpreter explicitly with
:func:`interpret_mode` (tests/conftest.py); nothing in the package does, so
an interpreted kernel cannot reach a chip quietly.
"""

from __future__ import annotations

import contextlib
import functools
import importlib
import math
import threading

import jax
import jax.numpy as jnp

from horovod_tpu.parallel.attention import blockwise_attention


class _OnFirstUse:
    """A module imported when an attribute of it is first asked for.  Pallas
    takes over a second to import (1.3 s of the 4.8 s that importing the
    serving engine took: PERF.md, PR 39) and every process that imports
    ``horovod_tpu.parallel`` paid it, the ones that never run a kernel
    too."""

    def __init__(self, name: str):
        self._name = name

    def __getattr__(self, attr: str):
        return getattr(importlib.import_module(self._name), attr)

    def preload(self) -> None:
        """Start the import on a thread of its own, for a caller that knows
        a kernel will be asked for: the second of import then passes while
        the process makes its weights, not inside the first trace."""
        threading.Thread(target=importlib.import_module, args=(self._name,),
                         name=f"import {self._name}", daemon=True).start()


pl = _OnFirstUse("jax.experimental.pallas")
pltpu = _OnFirstUse("jax.experimental.pallas.tpu")

NEG_INF = -1e30

_interpret = False


@contextlib.contextmanager
def interpret_mode(on: bool = True):
    """Trace :func:`flash_attention` calls made inside this context for the
    Pallas interpreter instead of Mosaic — for tests on the CPU mesh only.
    The choice is read when ``flash_attention`` is traced and is baked into
    the forward and both backward kernels of that call."""
    global _interpret
    prev, _interpret = _interpret, on
    try:
        yield
    finally:
        _interpret = prev


def interpreted() -> bool:
    """Whether a kernel traced now goes to the Pallas interpreter: the one
    switch every Pallas kernel of the package follows."""
    return _interpret


def _out_vma(*arrays):
    """Varying-mesh-axes set for kernel outputs: the union of the inputs'.

    Under ``shard_map``'s default varying-axes check a ``pallas_call``
    out_shape with no ``vma`` is an error — declaring "varies like the
    inputs" lets the flash kernels run without ``check_vma=False``.
    Outside shard_map every input vma is empty → ``None`` (a plain aval).
    """
    vma: frozenset = frozenset()
    for a in arrays:
        vma = vma | jax.typeof(a).vma
    return vma or None


def _sds(shape, dtype, vma):
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _first_key_block(qi, block_q: int, block_k: int, window: int,
                     lo=jnp.maximum):
    """The first key block that holds a key some query of query block ``qi``
    sees under a band of ``window`` keys (the query's own included)."""
    return lo(qi * block_q - (window - 1), 0) // block_k


def _key_blocks(qi, block_q: int, block_k: int, window: int, nk: int, *,
                lo=jnp.maximum, hi=jnp.minimum) -> tuple:
    """``(first, last)`` key block of query block ``qi``'s band: from the
    band's first to the diagonal's.  ``lo`` / ``hi`` are ``max`` / ``min``
    for python ints (the grids' sizes and the counter) and jax's for a
    traced block index (the index maps)."""
    return (_first_key_block(qi, block_q, block_k, window, lo),
            hi((qi * block_q + block_q - 1) // block_k, nk - 1))


def _query_blocks(ki, block_q: int, block_k: int, window: int, nq: int, *,
                  hi=jnp.minimum) -> tuple:
    """``(first, last)`` query block that sees a key of key block ``ki``
    under a band of ``window`` keys: from the diagonal's to the band's
    last."""
    return ((ki * block_k) // block_q,
            hi((ki * block_k + block_k - 1 + window - 1) // block_q, nq - 1))


def band_steps(seq_len: int, block_q: int, block_k: int,
               window: int | None) -> tuple:
    """``(key steps a query block, query steps a key block)`` of the kernels'
    grids over a sequence of ``seq_len``: every block of the other side
    where there is no band, the most a block's band spans where there is."""
    nq, nk = math.ceil(seq_len / block_q), math.ceil(seq_len / block_k)
    if window is None:
        return nk, nq

    def span(first_last):
        return first_last[1] - first_last[0] + 1

    return (max(span(_key_blocks(i, block_q, block_k, window, nk, lo=max,
                                 hi=min)) for i in range(nq)),
            max(span(_query_blocks(j, block_q, block_k, window, nq, hi=min))
                for j in range(nk)))


def key_blocks_visited(seq_len: int, *, block_q: int = 512,
                       block_k: int = 512, window: int | None = None) -> int:
    """The (query block, key block) pairs one head's causal kernels compute
    over a sequence of ``seq_len``: those at or under the diagonal and, under
    a band of ``window`` keys, not wholly before it.  The forward, the dQ and
    the dK/dV kernel each visit this many."""
    block_q, block_k = min(block_q, max(seq_len, 1)), min(block_k,
                                                         max(seq_len, 1))
    nq, nk = math.ceil(seq_len / block_q), math.ceil(seq_len / block_k)
    total = 0
    for i in range(nq):
        first, last = _key_blocks(i, block_q, block_k, window or seq_len, nk,
                                  lo=max, hi=min)
        total += last - first + 1
    return total


def _kernel_name(which: str, window: int | None) -> str:
    """The name a kernel has in a device trace: ``flash_fwd``, ``flash_dq``,
    ``flash_dkv``, and ``flash_band_*`` under a band."""
    return ("flash_" if window is None else "flash_band_") + which


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                  *, scale: float, causal: bool, block_q: int, block_k: int,
                  seq_len: int, window: int | None = None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    if window is None:
        k_start = ki * block_k
        # Skip blocks entirely above the causal diagonal (no MXU work there).
        live = (not causal) or (k_start <= q_start + block_q - 1)
    else:
        # The grid's key steps start at the band's first block: a step past
        # the diagonal has nothing to do (its blocks are the diagonal's,
        # already resident: no fetch either).
        k_start = (_first_key_block(qi, block_q, block_k, window)
                   + ki) * block_k
        live = k_start <= q_start + block_q - 1

    @pl.when(live)
    def _compute():
        # Operands stay in their storage dtype (bf16 in training): the MXU
        # runs bf16×bf16→f32 at full rate, while upcasting operands first
        # would force f32×f32 matmuls at a fraction of peak.  All
        # accumulation below is f32 via preferred_element_type / scratch.
        q = q_ref[0]                                 # [bq, D]
        k = k_ref[0]                                 # [bk, D]
        v = v_ref[0]                                 # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                    # [bq, bk] f32
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_len                        # padded tail keys
        if causal:
            mask = mask & (qpos >= kpos)
        if window is not None:
            mask = mask & (qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0:1]                       # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                       # [bq, bk]
        corr = jnp.exp(m_prev - m_new)               # [bq, 1]
        l_ref[:, 0:1] = l_ref[:, 0:1] * corr + p.sum(axis=-1, keepdims=True)
        m_ref[:, 0:1] = m_new
        # p rides the MXU in the storage dtype (standard flash practice —
        # the f32 row-sum/max state above carries the precision).
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # Per-row log-sum-exp, saved for the backward kernels.
        lse_ref[0] = m_ref[:, 0:1] + jnp.log(l)


def _flash_forward(q, k, v, *, n_heads: int, n_kv_heads: int, causal: bool,
                   block_q: int, block_k: int, interpret: bool,
                   window: int | None = None) -> jax.Array:
    """q: [B·H, L, D]; k/v: [B·KVH, L, D] — GQA resolved by the KV BlockSpec
    index map (head ``bh`` reads kv head ``bh%H // (H/KVH)``), so each KV
    tile is fetched once per group instead of being materialized H/KVH×."""
    bh, l, d = q.shape
    n_rep = n_heads // n_kv_heads
    nq = math.ceil(l / block_q)
    nk = math.ceil(l / block_k)
    lq_pad, lk_pad = nq * block_q, nk * block_k
    if lq_pad != l:
        q = jnp.pad(q, ((0, 0), (0, lq_pad - l), (0, 0)))
    if lk_pad != l:
        k = jnp.pad(k, ((0, 0), (0, lk_pad - l), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, lk_pad - l), (0, 0)))
    kernel = functools.partial(
        _flash_kernel,
        scale=1.0 / math.sqrt(d),
        causal=causal,
        block_q=block_q,
        block_k=block_k,
        seq_len=l,
        window=window,
    )

    def kv_index(b, i, j):
        batch = b // n_heads
        head = b % n_heads
        if window is not None:      # past the diagonal: hold its block
            first, last = _key_blocks(i, block_q, block_k, window, nk)
            j = jnp.minimum(first + j, last)
        return (batch * n_kv_heads + head // n_rep, j, 0)

    vma = _out_vma(q, k, v)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, band_steps(l, block_q, block_k, window)[0]),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kv_index, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), kv_index, memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _sds((bh, lq_pad, d), q.dtype, vma),
            _sds((bh, lq_pad, 1), jnp.float32, vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),    # acc
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum
        ],
        interpret=interpret,
        name=_kernel_name("fwd", window),
    )(q, k, v)
    return out[:, :l], lse


def _mask_scores(causal, q_start, k_start, block_q, block_k, seq_len,
                 window=None):
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kpos < seq_len
    if causal:
        mask = mask & (qpos >= kpos)
    if window is not None:
        mask = mask & (qpos - kpos < window)
    return mask


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                     acc_ref, *, scale, causal, block_q, block_k, seq_len,
                     window=None):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    if window is None:
        k_start = ki * block_k
        live = (not causal) or (k_start <= q_start + block_q - 1)
    else:       # key steps from the band's first block, as in the forward
        k_start = (_first_key_block(qi, block_q, block_k, window)
                   + ki) * block_k
        live = k_start <= q_start + block_q - 1

    @pl.when(live)
    def _compute():
        # Storage-dtype operands on the MXU, f32 accumulation — see the
        # forward kernel's note.
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        mask = _mask_scores(causal, q_start, k_start, block_q, block_k,
                            seq_len, window)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0]) * scale
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc,
                      *, scale, causal, block_q, block_k, seq_len,
                      window=None, n_q_blocks=None):
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if window is None:
        q_start, k_start = qi * block_q, ki * block_k
        # Skip q blocks entirely above the causal diagonal (p would be all 0).
        live = (not causal) or (q_start + block_q - 1 >= k_start)
    else:
        # The grid's query steps start at the diagonal's block: a step past
        # the band's last block has nothing to do and fetches nothing.
        first, last = _query_blocks(ki, block_q, block_k, window, n_q_blocks)
        q_start, k_start = (first + qi) * block_q, ki * block_k
        live = first + qi <= last

    @pl.when(live)
    def _compute():
        # Storage-dtype operands on the MXU, f32 accumulation — see the
        # forward kernel's note.
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        mask = _mask_scores(causal, q_start, k_start, block_q, block_k,
                            seq_len, window)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0]) * scale
        # Contract the query (sublane) dim of both operands — dK/dV tiles
        # accumulate without any materialized transpose.
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, o, lse, g, *, n_heads, n_kv_heads, causal,
                    block_q, block_k, interpret, window=None):
    """Two-pass flash backward: dQ kernel + dK/dV kernel.

    q/o/g: [B·H, L, D]; k/v: [B·KVH, L, D]; lse: [B·H, Lq_pad, 1].
    dK/dV are computed at query-head resolution (KV tiles read through the
    same GQA index map as the forward) and group-summed to KV heads outside.
    """
    bh, l, d = q.shape
    n_rep = n_heads // n_kv_heads
    nq = math.ceil(l / block_q)
    nk = math.ceil(l / block_k)
    lq_pad, lk_pad = nq * block_q, nk * block_k
    delta = (g.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)  # [BH, L]
    if lq_pad != l:
        q = jnp.pad(q, ((0, 0), (0, lq_pad - l), (0, 0)))
        g = jnp.pad(g, ((0, 0), (0, lq_pad - l), (0, 0)))
        delta = jnp.pad(delta, ((0, 0), (0, lq_pad - l)))
    if lk_pad != l:
        k = jnp.pad(k, ((0, 0), (0, lk_pad - l), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, lk_pad - l), (0, 0)))
    delta = delta[..., None]                                         # [BH, Lq, 1]
    scale = 1.0 / math.sqrt(d)
    vma = _out_vma(q, k, v, g)

    k_steps, q_steps = band_steps(l, block_q, block_k, window)

    def kv_head(b):
        return (b // n_heads) * n_kv_heads + (b % n_heads) // n_rep

    def kv_index(b, i, j):
        if window is not None:
            first, last = _key_blocks(i, block_q, block_k, window, nk)
            j = jnp.minimum(first + j, last)
        return (kv_head(b), j, 0)

    def q_block(j, i):
        """The query block of the dK/dV kernel's step ``i`` at key block
        ``j``: from the diagonal's on under a band, held at the band's last
        past it."""
        if window is None:
            return i
        first, last = _query_blocks(j, block_q, block_k, window, nq)
        return jnp.minimum(first + i, last)

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                          memory_space=pltpu.VMEM)
    r_spec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, block_k, d), kv_index,
                           memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(
            _flash_dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, seq_len=l, window=window,
        ),
        grid=(bh, nq, k_steps),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, r_spec, r_spec],
        out_specs=q_spec,
        out_shape=_sds((bh, lq_pad, d), q.dtype, vma),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name=_kernel_name("dq", window),
    )(q, k, v, g, lse, delta)

    # dK/dV: kv blocks in the second grid dim, q innermost; per-q-head
    # output tiles indexed by the *query* head so GQA groups don't race.
    qk_spec = pl.BlockSpec((1, block_q, d),
                           lambda b, j, i: (b, q_block(j, i), 0),
                           memory_space=pltpu.VMEM)
    rk_spec = pl.BlockSpec((1, block_q, 1),
                           lambda b, j, i: (b, q_block(j, i), 0),
                           memory_space=pltpu.VMEM)
    kvk_spec = pl.BlockSpec(
        (1, block_k, d),
        lambda b, j, i: (kv_head(b), j, 0),
        memory_space=pltpu.VMEM,
    )
    dkv_out_spec = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0),
                                memory_space=pltpu.VMEM)
    dk_h, dv_h = pl.pallas_call(
        functools.partial(
            _flash_dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, seq_len=l, window=window,
            n_q_blocks=nq,
        ),
        grid=(bh, nk, q_steps),
        in_specs=[qk_spec, kvk_spec, kvk_spec, qk_spec, rk_spec, rk_spec],
        out_specs=[dkv_out_spec, dkv_out_spec],
        out_shape=[
            _sds((bh, lk_pad, d), k.dtype, vma),
            _sds((bh, lk_pad, d), v.dtype, vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name=_kernel_name("dkv", window),
    )(q, k, v, g, lse, delta)

    b = bh // n_heads
    dk = dk_h.reshape(b, n_kv_heads, n_rep, lk_pad, d).sum(2)
    dv = dv_h.reshape(b, n_kv_heads, n_rep, lk_pad, d).sum(2)
    return (
        dq[:, :l],
        dk.reshape(b * n_kv_heads, lk_pad, d)[:, :l].astype(k.dtype),
        dv.reshape(b * n_kv_heads, lk_pad, d)[:, :l].astype(v.dtype),
    )


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, n_heads, n_kv_heads, causal, block_q, block_k, bwd_impl,
           interpret, window=None):
    out, _ = _flash_forward(q, k, v, n_heads=n_heads, n_kv_heads=n_kv_heads,
                            causal=causal, block_q=block_q, block_k=block_k,
                            interpret=interpret, window=window)
    return out


def _flash_fwd(q, k, v, n_heads, n_kv_heads, causal, block_q, block_k,
               bwd_impl, interpret, window):
    out, lse = _flash_forward(q, k, v, n_heads=n_heads, n_kv_heads=n_kv_heads,
                              causal=causal, block_q=block_q, block_k=block_k,
                              interpret=interpret, window=window)
    return out, (q, k, v, out, lse)


def _flash_bwd(n_heads, n_kv_heads, causal, block_q, block_k, bwd_impl,
               interpret, window, res, g):
    q, k, v, o, lse = res
    if bwd_impl == "blockwise":
        # Cross-check oracle: recompute gradients through the XLA blockwise
        # scan instead of the pallas kernels.
        b = q.shape[0] // n_heads
        l, d = q.shape[1], q.shape[2]

        def ref(q, k, v):
            qb = q.reshape(b, n_heads, l, d).transpose(0, 2, 1, 3)
            kb = k.reshape(b, n_kv_heads, l, d).transpose(0, 2, 1, 3)
            vb = v.reshape(b, n_kv_heads, l, d).transpose(0, 2, 1, 3)
            out = blockwise_attention(qb, kb, vb, causal=causal,
                                      block_size=block_k, window=window)
            return out.transpose(0, 2, 1, 3).reshape(b * n_heads, l, d)

        _, vjp = jax.vjp(ref, q, k, v)
        return vjp(g)
    return _flash_backward(
        q, k, v, o, lse, g, n_heads=n_heads, n_kv_heads=n_kv_heads,
        causal=causal, block_q=block_q, block_k=block_k, interpret=interpret,
        window=window,
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
    block_q: int = 512, block_k: int = 512, bwd: str | None = None,
    window: int | None = None,
) -> jax.Array:
    """Flash attention for [B, L, H, D] q and [B, L, KVH, D] k/v (GQA ok).

    Forward on the MXU via pallas — KV stays at KVH heads, grouped heads
    share tiles through the BlockSpec index map.  Backward is the two-pass
    pallas scheme (dQ kernel + dK/dV kernel over saved log-sum-exp), O(L)
    memory.  Blocks are clamped to the sequence length.

    ``window``: a band over the causal triangle, query ``t`` sees key ``j``
    iff ``0 <= t - j < window``.  The band is in the three kernels' masks
    and in their grids: a query block steps over the key blocks from its
    band's first to its diagonal's (:func:`key_blocks_visited`), and a block
    wholly outside is neither fetched nor multiplied.  ``None`` traces the
    kernels as they were.

    ``bwd``: ``"pallas"`` (default) or ``"blockwise"`` — the cross-check
    oracle that recomputes gradients through the XLA blockwise scan.  The
    choice is resolved at TRACE time (``HVD_TPU_FLASH_BWD`` env var when
    ``bwd`` is None); under jit it is baked into the compiled program, so
    switching an existing step function requires rebuilding it (fresh jit)
    or passing ``bwd=`` explicitly.
    """
    import os

    bwd_impl = (bwd or os.environ.get("HVD_TPU_FLASH_BWD", "pallas")).lower()
    if bwd_impl not in ("pallas", "blockwise"):
        raise ValueError(f"bwd must be 'pallas' or 'blockwise', got {bwd!r}")
    if not (q.dtype == k.dtype == v.dtype):
        # The kernels run matmuls on the operands' storage dtype (full-rate
        # bf16 MXU); mixed inputs would otherwise die deep inside a
        # dot_general trace.  Cast at the call site — typically the KV
        # cache's dtype is the one to keep.
        raise ValueError(
            f"flash_attention requires q/k/v of one dtype, got "
            f"{q.dtype}/{k.dtype}/{v.dtype}"
        )
    if window is not None and (not causal or window < 1):
        raise ValueError("a window is a band over the causal triangle: it "
                         f"needs causal=True and window >= 1, got {window!r}")
    b, l, h, d = q.shape
    kvh = k.shape[2]
    block_q = min(block_q, max(l, 1))
    block_k = min(block_k, max(l, 1))
    # [B, L, H, D] → [B*H, L, D]
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, l, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kvh, l, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kvh, l, d)
    out = _flash(qt, kt, vt, h, kvh, causal, block_q, block_k, bwd_impl,
                 _interpret, window)
    return out.reshape(b, h, l, d).transpose(0, 2, 1, 3)
