"""models/latent_moe.py's held experts: each form (in place, every expert
at once or one touched expert a step; sorted into tiles) against the dense sum
of every held choice, what an idle row may hold, and the tie between the
chip's share and the uncut model (benchmark/reference/dots3.py)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from toy_latent_moe import (SEED, TINY, _forms_run, fam, ref,
                            reference_logits, tiny, tokens)

from horovod_tpu.models import latent_moe as lm


def _dense_experts(mc, lp, h2, valid):
    """The held experts' part of the layer, token by token and choice by
    choice in float64, from the layer's own choices."""
    experts, weights = (np.asarray(a) for a in lm.route(mc, lp, h2))
    gate, up, down = (np.asarray(lp[k], np.float64)
                      for k in ("e_gate", "e_up", "e_down"))
    x = np.asarray(h2, np.float64)
    y = np.zeros(x.shape)
    load = np.zeros((mc.held_count,), np.int64)
    for i in np.flatnonzero(np.asarray(valid)):
        for e, w in zip(experts[i] - mc.held_first, weights[i]):
            if 0 <= e < mc.held_count:
                g = x[i] @ gate[e]
                y[i] += w * ((g / (1 + np.exp(-g)) * (x[i] @ up[e])) @ down[e])
                load[e] += 1
    return y, load


#: rows, the valid rows, the held range's first expert, a bias that steers
#: every row's choice (None: the seeded one), and what the case is
IN_PLACE_CASES = {
    "every_row_valid": (16, "all", 0, None),
    "some_rows_idle": (16, "some", 0, None),
    "one_row_live": (16, "one", 0, None),
    "all_rows_choose_the_same_held_experts": (16, "all", 0, (0, 1, 2, 3)),
    "no_held_expert_chosen": (16, "all", 0, (8, 9, 10, 11)),
    "held_range_is_a_strict_subset": (16, "some", 4, None),
    "held_subset_chosen_by_all": (16, "all", 4, (2, 3, 4, 5)),
    "rows_at_the_threshold": (lm.IN_PLACE_ROWS, "some", 0, None),
    "rows_over_the_threshold": (lm.IN_PLACE_ROWS + 8, "some", 0, None),
    "one_row_under_the_threshold": (1, "all", 0, None),
}


@pytest.mark.parametrize("case", sorted(IN_PLACE_CASES))
def test_experts_in_place_equal_the_sorted_tiles_and_the_dense_sum(
        monkeypatch, case):
    """A program of at most ``IN_PLACE_ROWS`` rows computes its experts over
    the rows where they stand, all of them at once where most are touched
    and one touched expert a step where few are; over the threshold the
    choices are sorted into tiles as before.  Each form is the dense sum of
    every held choice, and idle rows come out exactly zero."""
    n, live, first, steer = IN_PLACE_CASES[case]
    threshold = lm.IN_PLACE_ROWS
    mc = lm.latent_moe_tiny(held_first=first)
    lp = dict(lm.init_params(mc, jax.random.key(SEED))["layers"][1])
    if steer is not None:
        lp["router_bias"] = jnp.zeros((mc.n_experts,)).at[
            jnp.asarray(steer)].set(100.0)
    h2 = jax.random.normal(jax.random.key(n), (n, mc.dim), jnp.float32)
    valid = {"all": np.ones((n,), bool), "some": np.arange(n) % 3 != 1,
             "one": np.arange(n) == n // 2}[live]
    ran = _forms_run(monkeypatch)
    y, load = lm.held_experts(mc, lp, h2, jnp.asarray(valid))
    assert ran == [("_experts_in_place" if n <= threshold
                    else "_experts_in_tiles", n)]
    want, want_load = _dense_experts(mc, lp, h2, valid)
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5, rtol=0)
    assert (np.asarray(load) == want_load).all()
    assert (np.asarray(y)[~valid] == 0).all()
    if steer is not None:
        held = [e - first for e in steer if 0 <= e - first < mc.held_count]
        assert want_load[held].tolist() == [n] * len(held)
        assert want_load.sum() == n * len(held)
    # the other forms: the tiles whatever the rows, and in place each branch
    monkeypatch.setattr(lm, "IN_PLACE_ROWS", 0)
    tiles, tiles_load = lm.held_experts(mc, lp, h2, jnp.asarray(valid))
    assert ran[-1][0] == "_experts_in_tiles"
    np.testing.assert_allclose(np.asarray(tiles), want, atol=1e-5, rtol=0)
    assert (np.asarray(tiles_load) == want_load).all()
    if n <= threshold:
        for batched in (True, False):
            monkeypatch.setattr(lm, "_most_experts_touched",
                                lambda n_touched, e, b=batched: b)
            monkeypatch.setattr(lm, "IN_PLACE_ROWS", n)
            got, _ = lm.held_experts(mc, lp, h2, jnp.asarray(valid))
            assert ran[-1][0] == "_experts_in_place"
            np.testing.assert_allclose(np.asarray(got), want, atol=1e-5,
                                       rtol=0)
            assert (np.asarray(got)[~valid] == 0).all()


def test_the_touched_experts_pick_the_form_in_place():
    """Few touched experts are walked one a step (none that no row chose is
    read), most of them are computed at once: the count decides inside the
    program."""
    mc = lm.latent_moe_tiny()
    lp = dict(lm.init_params(mc, jax.random.key(SEED))["layers"][1])
    h2 = jax.random.normal(jax.random.key(3), (16, mc.dim), jnp.float32)
    valid = jnp.ones((16,), bool)
    _, spread = lm.held_experts(mc, lp, h2, valid)
    few = dict(lp, router_bias=jnp.zeros((mc.n_experts,)).at[
        jnp.asarray([0, 1, 8, 9])].set(100.0))
    _, narrow = lm.held_experts(mc, few, h2, valid)
    touched = lambda load: int((np.asarray(load) > 0).sum())  # noqa: E731
    assert touched(narrow) == 2 and touched(spread) >= 6    # of 8 held
    assert not lm._most_experts_touched(touched(narrow), mc.held_count)
    assert lm._most_experts_touched(touched(spread), mc.held_count)


@pytest.mark.parametrize("poison", [np.inf, np.nan])
def test_an_idle_row_that_is_not_finite_spoils_no_live_row(poison):
    """An idle row may hold anything (a slot's stale state): its outcome is
    selected away, not multiplied by zero, whichever form runs."""
    mc = lm.latent_moe_tiny()
    lp = lm.init_params(mc, jax.random.key(SEED))["layers"][1]
    h2 = jax.random.normal(jax.random.key(4), (16, mc.dim), jnp.float32)
    valid = np.arange(16) % 4 != 2
    clean = jnp.where(valid[:, None], h2, 0.0)
    bad = jnp.where(valid[:, None], h2, poison)
    for batched in (True, False):
        form = jax.jit(functools.partial(lm.held_experts, mc))
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(lm, "_most_experts_touched",
                       lambda n_touched, e, b=batched: b)
            want, want_load = form(lp, clean, jnp.asarray(valid))
            got, got_load = form(lp, bad, jnp.asarray(valid))
        assert np.abs(np.asarray(want)[valid]).min() > 0
        assert (np.asarray(got) == np.asarray(want)).all()
        assert (np.asarray(got)[~valid] == 0).all()
        assert (np.asarray(got_load) == np.asarray(want_load)).all()


def test_the_eight_shares_add_up_to_the_uncut_layer_and_head():
    """What ties the chip's share to the model: the routed parts of all 8
    shares (2 experts each of 16), with the shared expert counted once, are the
    uncut reference's expert layer; the 8 slices of the vocabulary give the
    uncut head's logits side by side."""
    uncut = dict(TINY, n_routed_experts=16, n_routed_experts_published=16)
    w_all = ref.layer_weights(uncut, ref.seed_arg(SEED), 1)
    h = jax.random.normal(jax.random.key(0), (11, 32), jnp.float32)
    whole, experts, _ = ref.moe(ref._dims(uncut), h, w_all, "float32")
    total = ref._swiglu(h, w_all["s_gate"], w_all["s_up"], w_all["s_down"],
                        "float32")
    loads = []
    for share in range(8):
        cfg = dict(TINY, n_routed_experts=2, held_experts_first=2 * share)
        mc = fam.model_config(cfg, 64)
        lp = ref.layer_weights(cfg, ref.seed_arg(SEED), 1)
        np.testing.assert_array_equal(
            np.asarray(lp["e_gate"]),
            np.asarray(w_all["e_gate"][2 * share:2 * share + 2]))
        part, load = lm.held_experts(mc, lp, h, jnp.ones((11,), bool))
        total = total + part
        loads += [int(x) for x in load]
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5, rtol=0)
    assert loads == [int((np.asarray(experts) == e).sum()) for e in range(16)]
    assert sum(loads) == 11 * 4          # every choice computed somewhere

    seq = tokens(9, vocab=8, seed=3)     # ids every slice holds
    whole_vocab = reference_logits(dict(TINY, vocab_size=64), seq)
    parts = []
    for share in range(8):
        cfg, mc, params = tiny(vocab_size=8, vocab_first_row=8 * share)
        full = ref.top_weights(dict(TINY, vocab_size=64), ref.seed_arg(SEED))
        # the same inputs everywhere: the tokens' rows of the whole embedding
        params = dict(params, embed=full["embed"][:8])
        parts.append(np.asarray(lm.forward(
            params, jnp.asarray([seq], jnp.int32), mc)[0]))
    np.testing.assert_allclose(np.concatenate(parts, -1), whole_vocab,
                               atol=1e-4, rtol=0)
