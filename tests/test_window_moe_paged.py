"""models/window_moe.py's second kind of state: what a slot's ring and a
block's snapshot hold after each kind of program, what ``set_row`` restores,
the verify round, and what an idle row may hold; against the reference
(benchmark/reference/kexaone.py) where a sequence continues."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from toy_window_moe import (ATOL, TINY, _serve_by_hand, reference_logits,
                            tiny, tokens)

from horovod_tpu.models import window_moe as wm


def test_the_ring_holds_the_last_window_whatever_the_programs_were():
    """A slot's ring after 29 tokens is the same whether they came a token,
    four or sixteen at a time, and the rubbish it held before is gone from
    every index a position was written to."""
    _, mc, params = tiny(max_len=128)
    seq = tokens(29, seed=3)
    rings = []
    for chunk in (1, 4, 16):
        _, pc = _serve_by_hand(mc, params, seq, 29, chunk, 8)
        rings.append(np.asarray(pc.ring[:, :, 1]))
        np.testing.assert_array_equal(np.asarray(pc.ring[:, :, 0]), 3.0)
    np.testing.assert_allclose(rings[0], rings[1], atol=1e-5, rtol=0)
    np.testing.assert_allclose(rings[0], rings[2], atol=1e-5, rtol=0)
    assert not (rings[0] == 3.0).any()


@pytest.mark.parametrize("chunk", [1, 5, 16])
def test_a_blocks_snapshot_is_the_ring_at_its_last_position(chunk):
    """Prefill to a block's end one way, and further another: the snapshot of
    each block that filled is the ring a row has that stopped at its end."""
    _, mc, params = tiny(max_len=128)
    bs, seq = 8, tokens(27, seed=4)
    _, pc = _serve_by_hand(mc, params, seq, 27, chunk, bs)
    table = np.asarray(pc.block_table[1])
    for end in (8, 16, 24):
        _, at_end = _serve_by_hand(mc, params, seq[:end], end, 3, bs)
        np.testing.assert_allclose(
            np.asarray(pc.snap[:, :, table[end // bs - 1]]),
            np.asarray(at_end.ring[:, :, 1]), atol=1e-5, rtol=0)
    # the block that has not filled holds none
    np.testing.assert_array_equal(np.asarray(pc.snap[:, :, table[3]]), 0.0)


def test_set_row_restores_the_ring_from_the_block_that_ends_at_the_length():
    _, mc, params = tiny(max_len=128)
    bs, seq = 8, tokens(21, seed=5)
    _, pc = _serve_by_hand(mc, params, seq, 21, 4, bs)
    row = pc.block_table[1]
    set_row = jax.jit(wm.set_row)
    for length, block in ((16, row[1]), (8, row[0])):
        got = set_row(pc, 0, row, length)
        np.testing.assert_array_equal(np.asarray(got.ring[:, :, 0]),
                                      np.asarray(pc.snap[:, :, block]))
        assert int(got.length[0]) == length
        np.testing.assert_array_equal(np.asarray(got.block_table[0]),
                                      np.asarray(row))
    fresh = set_row(pc, 0, row, 0)
    np.testing.assert_array_equal(np.asarray(fresh.ring[:, :, 0]), 0.0)
    c0, c1 = (wm.read_counters(np.asarray(p.stats)) for p in (pc, got))
    assert c1["state_restores"] == c0["state_restores"] + 1
    assert wm.read_counters(np.asarray(fresh.stats))["state_restores"] == \
        c0["state_restores"]
    # continuing from the restored ring is continuing the sequence
    cfg = dict(TINY)
    cont = set_row(pc, 0, row, 16)
    logits, _ = wm.decode_chunk_paged_row(
        params, jnp.asarray([seq[16:21] + [0] * 3], jnp.int32), mc, cont, 0,
        new_length=21)
    np.testing.assert_allclose(np.asarray(logits[0, :5]),
                               reference_logits(cfg, seq)[16:], atol=ATOL,
                               rtol=0)


def test_the_verify_round_leaves_the_ring_as_after_the_accepted_tokens():
    """Drafts of which the first two are right: the round advances by three,
    and the cache is the one that three ticks leave, rings and the snapshot
    of the block that filled included."""
    cfg, mc, params = tiny(max_len=128)
    bs, seq = 8, tokens(13, seed=6)
    _, pc = _serve_by_hand(mc, params, seq, 13, 4, bs, slot=0)
    pc = pc._replace(length=pc.length.at[1].set(0))
    want = wm.generate(params, mc, seq, 4, pad_to=24)
    full = reference_logits(cfg, seq + want)
    last = jnp.asarray(np.stack([full[12], full[12]]))
    drafts = jnp.asarray([[want[1], want[2], 63 - want[3]], [-1, -1, -1]],
                         jnp.int32)
    tok, accept, nxt, got = jax.jit(
        lambda c: wm.spec_verify_paged(params, mc, c, last, drafts,
                                       jnp.asarray([1, 0])))(pc)
    assert int(tok[0]) == want[0] and int(accept[0]) == 2
    assert int(got.length[0]) == 16 and int(got.length[1]) == 0
    np.testing.assert_allclose(np.asarray(nxt[0]), full[15], atol=ATOL,
                               rtol=0)
    ticked = pc
    for t in want[:3]:
        _, ticked = wm.decode_chunk_paged(
            params, jnp.asarray([[t], [7]], jnp.int32), mc, ticked,
            advance=jnp.asarray([1, 0]))
    for a, b in ((got.ring, ticked.ring), (got.snap, ticked.snap)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=0)
    assert wm.read_counters(np.asarray(got.stats))["snapshots_written"] == 2


def test_an_idle_row_that_is_not_finite_spoils_no_live_row():
    """A slot another row left holds whatever that row computed; a tick is
    over every slot, and the idle one's numbers reach no live row."""
    cfg, mc, params = tiny(max_len=128)
    seq = tokens(14, seed=8)
    _, pc = _serve_by_hand(mc, params, seq[:13], 13, 4, 8)
    bad = dict(params, embed=params["embed"].at[7].set(jnp.inf))
    logits, _ = wm.decode_chunk_paged(
        bad, jnp.asarray([[7], [seq[13]]], jnp.int32), mc, pc,
        advance=jnp.asarray([0, 1]))
    np.testing.assert_allclose(np.asarray(logits[1, 0]),
                               reference_logits(cfg, seq)[13], atol=ATOL,
                               rtol=0)
