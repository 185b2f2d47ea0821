"""models/latent_moe.py against the benchmark's plain reference: chunked
prefill followed by decoding through the three pools, by hand, on the mask
path and on the list path, with the long computations taken in steps, and two
rows of one program each under its own table."""

import jax.numpy as jnp
import numpy as np
import pytest

from toy_latent_moe import (REACHES, _serve_by_hand, reference_logits, tiny,
                            tokens)

from horovod_tpu.models import latent_moe as lm


def test_chunked_prefill_then_decode_through_the_pools_equals_the_reference():
    cfg, mc, params = tiny()
    seq = tokens(31, seed=1)
    got, pc = _serve_by_hand(mc, params, seq, n_prompt=19, chunk=8)
    np.testing.assert_allclose(got, reference_logits(cfg, seq), atol=1e-4,
                               rtol=0)
    assert int(pc.length[1]) == len(seq) and int(pc.length[0]) == 0
    # the idle row counted for nothing: every counted token was slot 1's
    c = lm.read_counters(np.asarray(pc.stats))
    assert c["choices_total"] == len(seq) * mc.top_k * 4


def test_long_computations_taken_in_steps_equal_the_reference(monkeypatch):
    """At real sizes the indexer scores a few blocks of keys at a time and no
    further than the rows reach, the top-k sorts the shortest width that
    holds the visible keys, the selected latents are gathered a block of
    queries at a time, and a program of more than 256 rows sorts its choices
    into tiles of 128: here the same code with steps small enough for the
    tiny preset to take several (chunks of 8 rows in tiles of 4, ticks in
    place)."""
    monkeypatch.setattr(lm, "INDEX_STEP_KEYS", 8)
    monkeypatch.setattr(lm, "QUERY_BLOCK", 4)
    monkeypatch.setattr(lm, "TILE_ROWS", 4)
    monkeypatch.setattr(lm, "IN_PLACE_ROWS", 4)
    cfg, mc, params = tiny()
    seq = tokens(70, seed=7)
    got, _ = _serve_by_hand(mc, params, seq, n_prompt=61, chunk=8,
                            max_len=96)
    np.testing.assert_allclose(got, reference_logits(cfg, seq), atol=1e-4,
                               rtol=0)


@pytest.mark.parametrize("path", sorted(REACHES))
def test_chunked_prefill_equals_the_reference_on_either_path(monkeypatch,
                                                             path):
    """A chunk of 16 tokens over a table of 48 lists 96 rows where the table
    holds 48, so it may keep the selection as a mask; top-6 is far below the
    context, so a mask that was ignored would fail.  Tiles of one block and
    blocks of 8 queries make the mask path take several of each."""
    monkeypatch.setattr(lm, "MASK_REACH_TOPKS", REACHES[path])
    monkeypatch.setattr(lm, "MASK_KEY_TILE", 16)
    monkeypatch.setattr(lm, "QUERY_BLOCK", 8)
    if path == "list":                  # and is not even compiled in
        monkeypatch.delattr(lm, "_attend_mask")
    cfg, mc, params = tiny()
    seq = tokens(47, seed=8)
    got, _ = _serve_by_hand(mc, params, seq, n_prompt=41, chunk=16)
    np.testing.assert_allclose(got, reference_logits(cfg, seq), atol=1e-4,
                               rtol=0)


def test_the_mask_path_attends_the_selection_and_not_every_key(monkeypatch):
    """The control of the test above: with the mask made of every visible
    key the same chunks leave the reference."""
    monkeypatch.setattr(
        lm, "_take", lambda u, seen, thr, quota, taken: (seen, taken))
    cfg, mc, params = tiny()
    seq = tokens(47, seed=8)
    got, _ = _serve_by_hand(mc, params, seq, n_prompt=41, chunk=16)
    assert np.max(np.abs(got - reference_logits(cfg, seq))) > 1e-2


def test_each_row_of_a_batch_walks_its_own_table_under_the_mask():
    """The mask path takes blocks of queries row by row, each with its own
    row's block table and threshold: two sequences in one program read as
    each does alone."""
    cfg, mc, params = tiny()
    seqs = [tokens(24, seed=9), tokens(24, seed=10)]
    assert lm.mask_reach(24, 24, 6) == 24
    both = lm.forward(params, jnp.asarray(seqs, jnp.int32), mc)
    for row, seq in enumerate(seqs):
        np.testing.assert_allclose(np.asarray(both[row]),
                                   reference_logits(cfg, seq), atol=1e-4,
                                   rtol=0)
