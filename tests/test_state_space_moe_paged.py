"""models/state_space_moe.py's recurrent state in the paged cache: what a
snapshot holds and ``set_row`` restores, the verify round at every acceptance,
what pads and idle rows leave alone, and the snapshot budget's own
bookkeeping; against the reference (benchmark/reference/granite.py) where a
sequence continues."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from toy_state_space_moe import (ATOL, _serve_by_hand, reference_logits,
                                 tiny, tokens)

from horovod_tpu import metrics as metrics_mod
from horovod_tpu.models import paged
from horovod_tpu.models import state_space_moe as sm


@pytest.mark.parametrize("chunk", [1, 5, 16])
def test_a_snapshot_is_the_state_at_its_blocks_last_position(chunk):
    """Prefill past the ends of three blocks of which two were given an
    entry: each entry is the state a row has that stopped at that end, the
    third entry is as it was, and a block end with no entry wrote nothing."""
    _, mc, params = tiny(max_len=128)
    bs, seq = 8, tokens(27, seed=4)
    _, pc = _serve_by_hand(mc, params, seq, 27, chunk, bs, snaps=[2, 3, 0])
    for end, entry in ((8, 2), (24, 0)):
        _, at_end = _serve_by_hand(mc, params, seq[:end], end, 3, bs)
        np.testing.assert_allclose(np.asarray(pc.snap_ssm[:, entry]),
                                   np.asarray(at_end.ssm[:, 1]), atol=1e-5,
                                   rtol=0)
        np.testing.assert_allclose(np.asarray(pc.snap_conv[:, entry]),
                                   np.asarray(at_end.conv[:, 1]), atol=1e-5,
                                   rtol=0)
    np.testing.assert_array_equal(np.asarray(pc.snap_ssm[:, 1]), 0.0)
    assert sm.read_counters(np.asarray(pc.stats))["snapshots_written"] == 2


def test_set_row_restores_the_state_from_the_entry_it_is_told():
    cfg, mc, params = tiny(max_len=128)
    bs, seq = 8, tokens(21, seed=5)
    _, pc = _serve_by_hand(mc, params, seq, 21, 4, bs, snaps=[1, 2])
    row = pc.block_table[1]
    set_row = jax.jit(sm.set_row)
    none = mc.snapshots
    per = pc.block_table.shape[1]
    for length, snaps, entry in ((16, [none, 2], 2), (8, [1, none], 1)):
        full = jnp.asarray(snaps + [none] * (per - 2), jnp.int32)
        got = set_row(pc, 0, row, length, full)
        np.testing.assert_array_equal(np.asarray(got.ssm[:, 0]),
                                      np.asarray(pc.snap_ssm[:, entry]))
        np.testing.assert_array_equal(np.asarray(got.conv[:, 0]),
                                      np.asarray(pc.snap_conv[:, entry]))
        assert int(got.length[0]) == length
        np.testing.assert_array_equal(np.asarray(got.snap_dest[0]),
                                      np.asarray(full))
    fresh = set_row(pc, 0, row, 0, jnp.full((per,), none, jnp.int32))
    np.testing.assert_array_equal(np.asarray(fresh.ssm[:, 0]), 0.0)
    np.testing.assert_array_equal(np.asarray(fresh.conv[:, 0]), 0.0)
    c0, c1 = (sm.read_counters(np.asarray(p.stats)) for p in (pc, got))
    assert c1["state_restores"] == c0["state_restores"] + 1
    assert sm.read_counters(np.asarray(fresh.stats))["state_restores"] == \
        c0["state_restores"]
    # continuing from the restored state is continuing the sequence
    cont = set_row(pc, 0, row, 16, jnp.asarray([none, 2] + [none] * (per - 2),
                                               jnp.int32))
    logits, _ = sm.decode_chunk_paged_row(
        params, jnp.asarray([seq[16:21] + [0] * 3], jnp.int32), mc, cont, 0,
        new_length=21)
    np.testing.assert_allclose(np.asarray(logits[0, :5]),
                               reference_logits(cfg, seq)[16:], atol=ATOL,
                               rtol=0)


@pytest.mark.parametrize("accepted", [0, 1, 2, 3])
def test_the_verify_round_leaves_the_state_as_after_the_accepted_tokens(
        accepted):
    """Drafts of which the first ``accepted`` are right: the round advances
    by one more, and the cache is the one that many ticks leave."""
    cfg, mc, params = tiny(max_len=128)
    bs, seq = 8, tokens(13, seed=6)
    _, pc = _serve_by_hand(mc, params, seq, 13, 4, bs, slot=0)
    rubbish = (np.asarray(pc.ssm[:, 1]), np.asarray(pc.conv[:, 1]))
    want = sm.generate(params, mc, seq, 4, pad_to=24)
    full = reference_logits(cfg, seq + want)
    last = jnp.asarray(np.stack([full[12], full[12]]))
    draft = [want[i + 1] if i < accepted else 63 - want[i + 1]
             for i in range(3)]
    drafts = jnp.asarray([draft, [-1, -1, -1]], jnp.int32)
    tok, accept, nxt, got = jax.jit(
        lambda c: sm.spec_verify_paged(params, mc, c, last, drafts,
                                       jnp.asarray([1, 0])))(pc)
    assert int(tok[0]) == want[0] and int(accept[0]) == accepted
    assert int(got.length[0]) == 13 + 1 + accepted
    assert int(got.length[1]) == 0
    np.testing.assert_allclose(np.asarray(nxt[0]), full[13 + accepted],
                               atol=ATOL, rtol=0)
    ticked = pc
    for t in want[:1 + accepted]:
        _, ticked = sm.decode_chunk_paged(
            params, jnp.asarray([[t], [7]], jnp.int32), mc, ticked,
            advance=jnp.asarray([1, 0]))
    np.testing.assert_allclose(np.asarray(got.ssm), np.asarray(ticked.ssm),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(got.conv), np.asarray(ticked.conv),
                               atol=1e-5, rtol=0)
    np.testing.assert_array_equal(np.asarray(got.ssm[:, 1]), rubbish[0])
    np.testing.assert_array_equal(np.asarray(got.conv[:, 1]), rubbish[1])


def test_an_idle_row_that_is_not_finite_spoils_no_live_row():
    """A slot another row left holds whatever that row computed; a tick is
    over every slot, and the idle one's numbers reach no live row."""
    cfg, mc, params = tiny(max_len=128)
    seq = tokens(14, seed=8)
    _, pc = _serve_by_hand(mc, params, seq[:13], 13, 4, 8)
    bad = dict(params, embed=params["embed"].at[7].set(jnp.inf))
    logits, _ = sm.decode_chunk_paged(
        bad, jnp.asarray([[7], [seq[13]]], jnp.int32), mc, pc,
        advance=jnp.asarray([0, 1]))
    # (the head is the embedding: the logit of the row that is not finite
    # is not, in every row, and is left out)
    keep = np.arange(64) != 7
    np.testing.assert_allclose(np.asarray(logits[1, 0])[keep],
                               reference_logits(cfg, seq)[13][keep],
                               atol=ATOL, rtol=0)


# ---------------------------------------------------------------------------
# the snapshot budget, by itself
# ---------------------------------------------------------------------------

def test_the_budget_grants_commits_evicts_and_drops():
    reg = metrics_mod.MetricsRegistry(event_log=None)
    b = paged.SnapshotBudget(2, evicted=reg.counter("ssm.snapshots_evicted"),
                             live=reg.gauge("ssm.snapshots_live"))
    assert b.none == 2 and b.entry(7) is None and not b.wanted(7)
    e7 = b.grant(7)
    assert e7 == 0 and b.wanted(7) and b.entry(7) is None     # pending
    e9 = b.grant(9)
    assert b.grant(11) is None          # every entry pending: refused
    b.commit(e7)
    b.commit(e9)
    assert (b.entry(7), b.entry(9)) == (e7, e9)
    assert reg.gauge("ssm.snapshots_live").value == 2
    b.touch(7)                  # restored from: 9, never restored, goes
    e11 = b.grant(11)           # first, though it was committed after 7
    assert e11 == e9 and b.entry(9) is None and b.entry(7) == e7
    assert reg.counter("ssm.snapshots_evicted").value == 1
    b.drop(11)                          # freed while its write is pending
    b.commit(e11)                       # ... the entry goes free, unheld
    assert b.entry(11) is None and b.held_count() == 1
    # ... and among the restored, the least recently restored
    b.commit(b.grant(12))
    b.touch(12)
    b.touch(7)
    assert b.grant(13) is not None and b.entry(12) is None
    assert b.entry(7) == e7
    # asked for without evidence, an entry that was restored from stays
    assert b.grant(14, on_evidence=False) is None and b.entry(7) == e7
    b.drop(13)
    b.commit(b.entry(13) or next(iter(b._pending)))
    b.drop(7)
    assert b.held_count() == 0 and b.pending_count() == 0
    e = b.grant(5)
    b.cancel(e)
    assert not b.wanted(5)
    b.check_consistency()
    # an entry follows the block that stays, unless that one holds its own
    b.commit(b.grant(20))
    b.move(20, 21)
    assert b.entry(20) is None and b.entry(21) is not None
    b.commit(b.grant(22))
    b.move(22, 21)
    assert b.entry(22) is not None
    b.check_consistency()
    with pytest.raises(ValueError):
        paged.SnapshotBudget(0)
