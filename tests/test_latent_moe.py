"""models/latent_moe.py against the benchmark's plain reference
(benchmark/reference/dots3.py, the one copy), at the tiny preset on the CPU
with seeded weights: the whole forward with the discrete choices (the
indexer's selected sets, kept as a mask over key tiles or sorted into a list,
and the router's experts), the mask's own arithmetic, and bfloat16.  Prefill
in chunks through the pools: ``test_latent_moe_chunks.py``; the experts' forms
and the chip's share: ``test_latent_moe_experts.py``; behind ``ServeEngine``:
``test_latent_moe_serving.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from toy_latent_moe import (TINY, fam, reference_choices, reference_logits,
                            tiny, tokens)

from horovod_tpu.models import latent_moe as lm


def test_preset_matches_the_tiny_configuration():
    assert fam.model_config(TINY, 64) == lm.latent_moe_tiny()


@pytest.mark.parametrize("form", ["list", "mask"])
def test_forward_equals_the_reference_with_the_same_choices(monkeypatch,
                                                            form):
    """The whole forward is one program of 24 tokens a row over a table of
    24, which keeps the selection as a mask; with the reach at 0 it sorts it
    into a list.  Either way the logits and the chosen sets are the
    reference's."""
    if form == "list":
        monkeypatch.setattr(lm, "MASK_REACH_TOPKS", 0)
    cfg, mc, params = tiny()
    seq = tokens(24)                    # beyond top-6 and the window of 5
    selected, routed = [], []
    select, take, route = lm._index_select, lm._take, lm.route

    def spy_select(*a, **k):
        idx, real = select(*a, **k)
        selected.append(np.where(np.asarray(real), np.asarray(idx), -1)[0])
        return idx, real

    def spy_take(*a, **k):              # one tile holds the whole table
        sel, taken = take(*a, **k)
        selected.append([np.flatnonzero(row) for row in np.asarray(sel)])
        return sel, taken

    def spy_route(*a, **k):
        experts, weights = route(*a, **k)
        routed.append(np.asarray(experts))
        return experts, weights

    monkeypatch.setattr(lm, "_index_select", spy_select)
    monkeypatch.setattr(lm, "_take", spy_take)
    monkeypatch.setattr(lm, "route", spy_route)
    with jax.disable_jit():
        got = lm.forward(params, jnp.asarray([seq], jnp.int32), mc)[0]
    np.testing.assert_allclose(np.asarray(got), reference_logits(cfg, seq),
                               atol=1e-4, rtol=0)
    want = reference_choices(cfg, seq)
    full = [a["selected"] for a in want if a["selected"] is not None]
    assert len(selected) == len(full) == 2
    for mine, theirs in zip(selected, full):        # the same sets of keys
        for t in range(len(seq)):
            assert set(mine[t]) - {-1} == set(np.asarray(theirs[t])) - {-1}
    moe = [np.asarray(a["experts"]) for a in want if a["experts"] is not None]
    assert len(routed) == len(moe) == 4
    for mine, theirs in zip(routed, moe):
        assert (np.sort(mine, -1) == np.sort(theirs, -1)).all()


def _mask_by_tiles(scores, k, tile):
    """The mask path's selection over ``scores`` [Q, m] as the program makes
    it: the k-th largest by counting, then a tile of keys at a time."""
    q, m = scores.shape
    u = lm._ordered_bits(scores)
    thr, quota = lm._kth_largest(u[None], k, m // tile, tile)
    taken = jnp.zeros((q,), jnp.int32)
    out = []
    for j in range(0, m, tile):
        sel, taken = lm._take(u[:, j:j + tile],
                              scores[:, j:j + tile] > -jnp.inf, thr[0],
                              quota[0], taken)
        out.append(np.asarray(sel))
    return np.concatenate(out, axis=-1)


def _score_cases():
    rng = np.random.default_rng(11)
    q, m = 5, 32
    inf = np.float32(-np.inf)
    random = rng.standard_normal((q, m)).astype(np.float32)
    ties = rng.integers(0, 3, (q, m)).astype(np.float32)   # ten of a value
    zeros = np.where(rng.random((q, m)) < 0.5, np.float32(0.0),
                     np.float32(-0.0))
    zeros[:, ::7] = rng.standard_normal((q, len(range(0, m, 7))))
    causal = np.where(np.arange(m)[None] <= np.arange(q)[:, None] + 3,
                      random, inf)                       # 4..8 keys visible
    exactly = np.where(np.arange(m)[None] < 8, ties, inf)
    first = np.where(np.arange(m)[None] < 1, random, inf)
    return {"random": random, "ties_at_the_threshold": ties,
            "zeros_of_both_signs": zeros, "fewer_than_k_visible": causal,
            "exactly_k_visible": exactly, "all_but_the_first_masked": first}


@pytest.mark.parametrize("tile", [32, 8])
@pytest.mark.parametrize("case", sorted(_score_cases()))
def test_the_mask_holds_the_set_that_top_k_returns(case, tile):
    """Key for key: every score above the k-th largest, of the scores equal
    to it the lowest positions until k are taken, every visible key where
    fewer than k are, never a masked one; in one tile and carried over four."""
    scores = jnp.asarray(_score_cases()[case])
    k = 8
    vals, idx = jax.lax.top_k(scores, k)
    got = _mask_by_tiles(scores, k, tile)
    for row in range(scores.shape[0]):
        want = set(np.asarray(idx[row])[np.asarray(vals[row]) > -np.inf])
        assert set(np.flatnonzero(got[row])) == want, (case, row)
    assert (got.sum(-1) == np.minimum(
        k, (np.asarray(scores) > -np.inf).sum(-1))).all()


@pytest.mark.parametrize("t, m, k, topks", [
    (512, 32768, 2048, lm.MASK_REACH_TOPKS),    # the chunk of prefill
    (512, 8192, 2048, 4),               # no further than the table
    (1, 32768, 2048, 0),                # the tick lists 2,048 rows of 32,768
    (4, 32768, 2048, 0),                # the verify round
    (16, 32768, 2048, 0),               # exactly the table: the list stays
    (17, 32768, 2048, lm.MASK_REACH_TOPKS)])
def test_the_mask_is_for_programs_whose_list_outgrows_the_table(t, m, k,
                                                                topks):
    assert 4 < lm.MASK_REACH_TOPKS <= 16
    assert lm.mask_reach(t, m, k) == topks * k


def test_bfloat16_stays_near_float32_but_for_flipped_choices():
    """The program's own precision.  bfloat16 rounds every activation to 8
    bits, which moves a logit of this size by a few hundredths; where a
    near-tie of the router or of the indexer falls the other way, one expert
    (a quarter of a layer here) or one key (a sixth of a selection) changes
    and the position's logits move by tenths.  So the typical position is held
    close and the share of far ones is bounded, not the worst one."""
    cfg, mc, params = tiny()
    seq = tokens(40, seed=2)
    want = reference_logits(cfg, seq)
    mc16 = fam.model_config(dict(cfg, torch_dtype="bfloat16"), 64)
    p16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16)
                       if x.dtype == jnp.float32 and x.ndim > 1 else x, params)
    got = np.asarray(lm.forward(p16, jnp.asarray([seq], jnp.int32), mc16)[0])
    err = np.max(np.abs(got - want), axis=-1)          # per position
    assert np.median(err) < 0.08, np.median(err)
    assert np.mean(err < 0.3) >= 0.75, np.sort(err)[::-1][:8]
    assert np.isfinite(got).all()
