"""The band of ``flash_attention(..., window=)``: the forward, dQ and dK/dV
kernels against dense attention and against the ``blockwise`` oracle, at
windows that are and are not multiples of the blocks, and the count of the
key blocks the kernels' grids visit."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

fa = importlib.import_module("horovod_tpu.parallel.flash_attention")
from horovod_tpu.parallel.attention import blockwise_attention, dense_attention


def _qkv(l, h=4, kvh=2, d=16, b=2, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(kq, (b, l, h, d)),
            jax.random.normal(kk, (b, l, kvh, d)),
            jax.random.normal(kv, (b, l, kvh, d)))


#: (sequence, window, query block, key block): the window a multiple of the
#: blocks, not one, shorter than a block, longer than the sequence; blocks
#: of unlike size; a sequence that is padded to whole blocks
BANDS = [(64, 16, 8, 8), (64, 21, 8, 8), (64, 5, 16, 16), (48, 100, 8, 8),
         (64, 24, 16, 8), (64, 19, 8, 16), (50, 13, 8, 8)]


@pytest.mark.parametrize("l,window,bq,bk", BANDS)
def test_the_banded_forward_is_dense_attention_under_the_band(l, window, bq,
                                                              bk):
    q, k, v = _qkv(l)
    out = fa.flash_attention(q, k, v, window=window, block_q=bq, block_k=bk)
    ref = dense_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    if window < l:      # the band is there: causal attention differs
        assert not np.allclose(out, dense_attention(q, k, v, causal=True),
                               atol=1e-3)


@pytest.mark.parametrize("l,window,bq,bk", BANDS)
@pytest.mark.parametrize("oracle", ["dense", "blockwise"])
def test_the_banded_backward_kernels_match(l, window, bq, bk, oracle):
    """dQ, dK and dV of the two Pallas kernels against the gradient through
    dense attention, and against the ``blockwise`` oracle the kernels'
    custom rule can be switched to."""
    q, k, v = _qkv(l, seed=1)
    w = jax.random.normal(jax.random.key(9), q.shape)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    got = jax.grad(loss(lambda q, k, v: fa.flash_attention(
        q, k, v, window=window, block_q=bq, block_k=bk)),
        argnums=(0, 1, 2))(q, k, v)
    if oracle == "dense":
        want = jax.grad(loss(lambda q, k, v: dense_attention(
            q, k, v, causal=True, window=window)), argnums=(0, 1, 2))(q, k, v)
    else:
        want = jax.grad(loss(lambda q, k, v: fa.flash_attention(
            q, k, v, window=window, block_q=bq, block_k=bk,
            bwd="blockwise")), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name}")


def test_the_blockwise_oracle_takes_the_band():
    q, k, v = _qkv(40)
    np.testing.assert_allclose(
        blockwise_attention(q, k, v, block_size=8, window=11),
        dense_attention(q, k, v, causal=True, window=11), atol=2e-5)


def _pairs_computed(l, bq, bk, window):
    """The (query block, key block) pairs with a visible (query, key)."""
    t, j = np.arange(l)[:, None], np.arange(l)[None, :]
    seen = (j <= t) & ((t - j < window) if window else True)
    nq, nk = -(-l // bq), -(-l // bk)
    return sum(bool(seen[i * bq:(i + 1) * bq, m * bk:(m + 1) * bk].any())
               for i in range(nq) for m in range(nk))


@pytest.mark.parametrize("l,window,bq,bk", BANDS + [(64, None, 8, 8),
                                                    (64, None, 16, 8)])
def test_the_grids_visit_the_blocks_the_band_touches_and_no_other(l, window,
                                                                  bq, bk):
    assert fa.key_blocks_visited(l, block_q=bq, block_k=bk, window=window) \
        == _pairs_computed(l, bq, bk, window)
    k_steps, q_steps = fa.band_steps(l, bq, bk, window)
    assert k_steps <= -(-l // bk) and q_steps <= -(-l // bq)


def test_the_cells_band_visits_a_third_of_the_causal_blocks():
    """8,192 positions, a band of 1,024, blocks of 512: 45 of 136."""
    assert fa.key_blocks_visited(8192, window=1024) == 45
    assert fa.key_blocks_visited(8192) == 136
    assert fa.band_steps(8192, 512, 512, 1024) == (3, 3)
    assert fa.band_steps(8192, 512, 512, None) == (16, 16)


def test_no_window_traces_the_kernels_as_they_were():
    """``window=None`` is the causal kernel of before: the same jaxpr as a
    call that does not name the argument, grids of every key block."""
    q, k, v = _qkv(32)
    a = jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, block_q=8, block_k=8))(q, k, v)
    b = jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, block_q=8, block_k=8, window=None))(q, k, v)
    assert str(a) == str(b)
    c = jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, block_q=8, block_k=8, window=9))(q, k, v)
    assert str(a) != str(c)


@pytest.mark.parametrize("bad", [dict(window=0), dict(window=4, causal=False)])
def test_a_window_needs_the_causal_triangle(bad):
    q, k, v = _qkv(16)
    with pytest.raises(ValueError, match="band over the causal triangle"):
        fa.flash_attention(q, k, v, **bad)
