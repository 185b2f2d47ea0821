"""models/latent_moe.py against the benchmark's plain reference
(benchmark/reference/dots3.py, the one copy), at the tiny preset on the CPU
with seeded weights: the whole forward and chunked prefill followed by decoding
through the three pools, the discrete choices (the indexer's selected sets,
kept as a mask over key tiles or sorted into a list, and the router's
experts), bfloat16, the tie between the chip's share and the uncut
model, the engine and the router over the model interface, and the counters."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import lib  # noqa: E402
from horovod_tpu import metrics as metrics_mod  # noqa: E402
from horovod_tpu.models import latent_moe as lm  # noqa: E402
from horovod_tpu.models import llama  # noqa: E402
from horovod_tpu.router import LocalReplica, RouterServer  # noqa: E402
from horovod_tpu.serving import Request  # noqa: E402
from horovod_tpu.serving_scheduler import ServeEngine  # noqa: E402

ref = lib.load_module("reference", "dots3")
fam = lib.load_module("families", "dots3_serve")
SEED = 5

#: The tiny preset in the configuration file's keys: all three kinds of layer,
#: 16 experts of which 8 are held, top-6 selection and a window of 5, both
#: smaller than the test lengths.
TINY = dict(
    name="tiny", reference="dots3", hidden_size=32, num_hidden_layers=5,
    layer_types=["full_attention", "full_attention", "sliding_attention",
                 "sliding_attention", "sliding_attention"],
    first_k_dense_replace=1, intermediate_size=64, num_attention_heads=4,
    q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, rope_theta=1e4, index_n_heads=2, index_head_dim=8,
    index_topk=6, swa_num_attention_heads=2, swa_q_lora_rank=16,
    swa_kv_lora_rank=16, swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4,
    swa_v_head_dim=8, swa_rope_theta=1e3, sliding_window_size=5,
    n_routed_experts=8, n_routed_experts_published=16, held_experts_first=0,
    moe_intermediate_size=16, num_experts_per_tok=4, n_shared_experts=1,
    routed_scaling_factor=1.0, vocab_size=64, vocab_first_row=0,
    rms_norm_eps=1e-5, apply_mla_qkv_lora_rescale=True,
    torch_dtype="float32")


def tiny(**changes):
    """``(configuration dict, LatentMoEConfig, parameters)``, the parameters
    the reference's own for the seed."""
    cfg = dict(TINY, **changes)
    return cfg, fam.model_config(cfg, 64), fam.make_params(cfg, SEED)


def tokens(n, vocab=64, seed=0):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def reference_logits(cfg, seq):
    n = len(seq)
    return np.asarray(ref.logits_at(cfg, SEED, [seq], [list(range(n))],
                                    "float32", pad_to=n, q_block=n,
                                    head_block=2)[0])


def reference_choices(cfg, seq):
    """Per layer what the reference's discrete parts chose over ``seq``."""
    top = ref.top_weights(cfg, ref.seed_arg(SEED))
    x = top["embed"][jnp.asarray(seq)].astype(jnp.float32)
    out = []
    for i in range(cfg["num_hidden_layers"]):
        w = ref.layer_weights(cfg, ref.seed_arg(SEED), i)
        x, aux = ref.layer(cfg, ref.layer_kind(cfg, i), x, w,
                           q_block=len(seq), head_block=2, aux=True)
        out.append(aux)
    return out


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


def _serve_by_hand(mc, params, seq, n_prompt, chunk, max_len=48):
    """Chunked prefill of ``seq[:n_prompt]`` into slot 1 of a two-slot cache,
    then the rest a token a tick: the logits at every position."""
    pc = lm.init_paged_cache(mc, 2, max_len, block_size=chunk)
    per = pc.block_table.shape[1]
    pc = pc._replace(block_table=pc.block_table.at[1].set(
        1 + jnp.arange(per, dtype=jnp.int32)))
    row = jax.jit(functools.partial(lm.decode_chunk_paged_row, cfg=mc))
    tick = jax.jit(functools.partial(lm.decode_chunk_paged, cfg=mc))
    logits = []
    for start in range(0, n_prompt, chunk):
        piece = seq[start:min(start + chunk, n_prompt)]
        toks = jnp.asarray([piece + [0] * (chunk - len(piece))], jnp.int32)
        out, pc = row(params, toks, pcache=pc, slot=1,
                      new_length=start + len(piece))
        logits.append(np.asarray(out[0, :len(piece)]))
    active = jnp.asarray([0, 1], jnp.int32)
    for tok in seq[n_prompt:]:
        out, pc = tick(params, jnp.asarray([[0], [tok]], jnp.int32),
                       pcache=pc, advance=active)
        logits.append(np.asarray(out[1]))
    return np.concatenate(logits), pc


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


#: ``MASK_REACH_TOPKS`` that sends every chunk down the mask path, none, and
#: the first two of three (a reach of 24 keys with top-6)
REACHES = {"mask": 12, "list": 0, "mask_then_list": 4}


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


def _forms_run(monkeypatch):
    """``(form, rows)`` of every expert layer :func:`lm.held_experts` lays
    out from here on, in order."""
    ran = []
    for name in ("_experts_in_place", "_experts_in_tiles"):
        def spy(*a, _name=name, _form=getattr(lm, name)):
            ran.append((_name, a[2].shape[0]))
            return _form(*a)
        monkeypatch.setattr(lm, name, spy)
    return ran


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


def _engine(mc, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("chunk", 8)
    return ServeEngine(params, mc, monitor=False, sampler=False,
                       metrics=metrics_mod.MetricsRegistry(event_log=None),
                       **kw)


@pytest.fixture(scope="module")
def served():
    cfg, mc, params = tiny()
    prompts = [tokens(19, seed=4), tokens(7, seed=5), tokens(26, seed=6)]
    want = [lm.generate(params, mc, p, 9, pad_to=48) for p in prompts]
    return mc, params, prompts, want


def test_engine_run_equals_cache_free_generate(served):
    mc, params, prompts, want = served
    eng = _engine(mc, params)
    out = eng.run([Request(prompt=p, max_new_tokens=9) for p in prompts])
    assert [r.status for r in out] == ["OK"] * 3
    assert [list(r) for r in out] == want
    assert eng.compile_cache_sizes() == \
        {"sample": 1, "tick": 1, "chunk": 1, "set_row": 1}


def _dispatched(monkeypatch):
    """Every program an engine built from here on hands to the model's
    counters: ``(rows, tokens a row, longest row's length)``."""
    seen, publish = [], lm.publish_paged_metrics

    def spy(metrics, cfg, pcache, stats_host=None, row_blocks=(),
            programs=()):
        seen.extend(programs)
        return publish(metrics, cfg, pcache, stats_host, row_blocks, programs)

    monkeypatch.setattr(lm, "publish_paged_metrics", spy)
    return seen


@pytest.mark.parametrize("path", sorted(REACHES))
def test_engine_serves_the_same_tokens_on_either_path(monkeypatch, served,
                                                      path):
    """Chunks of 16 may take the mask path (see above); the tokens are the
    cache-free program's whichever path the reach sends them down, and
    ``dsa.mask_queries`` / ``dsa.queries`` are what :func:`lm.mask_reach`
    says of the programs that were dispatched."""
    mc, params, prompts, want = served
    monkeypatch.setattr(lm, "MASK_REACH_TOPKS", REACHES[path])
    programs = _dispatched(monkeypatch)
    eng = _engine(mc, params, chunk=16)
    out = eng.run([Request(prompt=p, max_new_tokens=9) for p in prompts])
    assert [list(r) for r in out] == want
    assert eng.compile_cache_sizes() == \
        {"sample": 1, "tick": 1, "chunk": 1, "set_row": 1}
    c = eng.metrics_snapshot()["counters"]
    reach = {"mask": 48, "list": 0, "mask_then_list": 24}[path]
    chunks = [p for p in programs if p.t == 16]
    ticks = [p for p in programs if p.t == 1]
    assert len(chunks) == 2 + 1 + 2 and len(chunks) + len(ticks) \
        == len(programs)
    assert c["dsa.queries"] == 2 * (16 * len(chunks) + 2 * len(ticks))
    assert c["dsa.mask_queries"] == 2 * 16 * sum(
        1 for p in chunks if p.longest + p.t <= reach)
    assert c["dsa.mask_queries"] == {"mask": 160, "list": 0,
                                     "mask_then_list": 96}[path]
    assert c["dsa.mask_queries"] == sum(
        p.rows * p.t * 2 for p in programs
        if p.longest + p.t <= lm.mask_reach(p.t, 48, 6))


def test_ticks_alone_count_no_query_under_the_mask(monkeypatch, served):
    """Once the prompts are in, the steps dispatch ticks only: two rows of
    one token over a table of 48 list 6 rows each, the list path, whatever
    the rows hold."""
    mc, params, prompts, want = served
    programs = _dispatched(monkeypatch)
    eng = _engine(mc, params, chunk=16)
    rid = eng.submit(Request(prompt=prompts[0], max_new_tokens=9))
    while not programs or programs[-1].t > 1:       # until the first tick
        eng.step()
    counters = lambda: eng.metrics_snapshot()["counters"]  # noqa: E731
    before, n = counters(), len(programs)
    assert before["dsa.mask_queries"] == before["dsa.queries"] - 2 * 2 > 0
    while eng.pending():
        eng.step()
    assert list(eng.results[rid]) == want[0]
    assert {p.t for p in programs[n:]} == {1} and len(programs) > n
    after = counters()
    assert after["dsa.mask_queries"] == before["dsa.mask_queries"]
    assert after["dsa.queries"] - before["dsa.queries"] \
        == 2 * 2 * (len(programs) - n)


@pytest.mark.parametrize("threshold", [0, 8, 256])
def test_choices_in_place_are_those_of_the_programs_of_few_rows(
        monkeypatch, served, threshold):
    """Chunks of 16 rows and ticks of 2: with the threshold between them the
    ticks alone compute their experts in place, at 256 (the module's own)
    every program does and at 0 none; ``moe.choices_in_place`` says so from
    the dispatched programs' rows, the tokens and ``moe.choices_total`` are
    the same under each, and the device's count of the layers that took
    every expert at once stays within the layers in place."""
    mc, params, prompts, want = served
    monkeypatch.setattr(lm, "IN_PLACE_ROWS", threshold)
    programs = _dispatched(monkeypatch)
    forms = _forms_run(monkeypatch)
    eng = _engine(mc, params, chunk=16)
    out = eng.run([Request(prompt=p, max_new_tokens=9) for p in prompts])
    assert [list(r) for r in out] == want
    # 4 expert layers a program, traced once each: set_row has none
    assert sorted(set(forms)) == sorted(
        ("_experts_in_place" if rows <= threshold else "_experts_in_tiles",
         rows) for rows in (2, 16))
    c = eng.metrics_snapshot()["counters"]
    assert {(p.rows, p.t) for p in programs} == {(1, 16), (2, 1)}
    ticks = sum(1 for p in programs if p.t == 1)
    chunks = len(programs) - ticks
    assert c["moe.choices_in_place"] == 4 * 4 * (
        2 * ticks * (2 <= threshold) + 16 * chunks * (16 <= threshold))
    assert c["moe.choices_in_place"] == lm.choices_in_place(mc, programs)
    assert c["moe.layers_batched"] <= 4 * (
        ticks * (2 <= threshold) + chunks * (16 <= threshold))
    # 16 rows x top-4 over 8 held of 16 experts touch most of them
    if threshold != 8:
        assert (c["moe.layers_batched"] > 0) == (threshold == 256)
    assert c["moe.choices_total"] == 4 * 4 * sum(
        len(p) + 9 for p in prompts)


def test_prefix_cache_hit_serves_the_same_tokens(served):
    mc, params, prompts, want = served
    eng = _engine(mc, params, prefix_cache=True)
    first = eng.run([Request(prompt=prompts[2], max_new_tokens=9)])
    again = eng.run([Request(prompt=prompts[2], max_new_tokens=9)])
    assert list(first[0]) == list(again[0]) == want[2]
    # the second run mapped the first's blocks in all three pools at once
    assert eng.prefix_counters["hits"] >= 1
    assert eng.prefix_counters["tokens_skipped"] >= 16


def test_preemption_and_replay_serve_the_same_tokens(served):
    mc, params, prompts, want = served
    # 7 blocks cannot hold both long requests: the second starves, the first
    # is preempted, requeued and replayed from its prompt plus what it emitted
    eng = _engine(mc, params, n_blocks=7, preempt_after=2)
    out = eng.run([Request(prompt=prompts[0], max_new_tokens=9),
                   Request(prompt=prompts[2], max_new_tokens=9)])
    assert [list(r) for r in out] == [want[0], want[2]]
    assert eng.counters["preemptions"] >= 1


def test_speculative_round_serves_the_same_tokens(served):
    mc, params, prompts, want = served
    eng = _engine(mc, params, spec=True, draft_k=3)
    out = eng.run([Request(prompt=p, max_new_tokens=9) for p in prompts])
    assert [list(r) for r in out] == want
    assert eng.spec_counters["rounds"] > 0
    assert eng.compile_cache_sizes()["tick"] == 0       # the wide tick only


def test_router_over_a_local_replica_serves_the_same_tokens(served):
    mc, params, prompts, want = served
    router = RouterServer([LocalReplica(_engine(mc, params), "r0")])
    try:
        rids = [router.route(Request(prompt=p, max_new_tokens=9))
                for p in prompts]
        got = [router.result(rid, timeout=120) for rid in rids]
    finally:
        router.stop(drain_s=0.0)
    assert [r.status for r in got] == ["OK"] * 3
    assert [list(r) for r in got] == want


def test_cancel_frees_every_block(served):
    mc, params, prompts, _ = served
    eng = _engine(mc, params)
    rid = eng.submit(Request(prompt=prompts[2], max_new_tokens=9))
    eng.step()
    assert eng.cancel(rid)
    while eng.pending():
        eng.step()
    assert eng.results[rid].status == "CANCELLED"
    assert eng.free_block_count() == eng.pool.n_blocks - 1


def test_tensor_parallel_serving_is_refused_clearly(served):
    mc, params, _, _ = served
    with pytest.raises(NotImplementedError, match="tensor-parallel"):
        _engine(mc, params, tp_size=2)


def test_counters_equal_what_the_reference_counts(served):
    """One request, no prefix cache: the engine decodes the prompt and each
    token it emits, so the counters are the reference's choices over prompt
    plus output."""
    mc, params, prompts, want = served
    cfg = dict(TINY)
    eng = _engine(mc, params)
    out = eng.run([Request(prompt=prompts[0], max_new_tokens=9)])
    seq = prompts[0] + list(out[0])
    choices = reference_choices(cfg, seq)
    experts = np.stack([np.asarray(a["experts"]) for a in choices
                        if a["experts"] is not None])           # [4, T, k]
    snap = eng.metrics_snapshot()
    c, g = snap["counters"], snap["gauges"]
    assert c["moe.choices_total"] == experts.size
    assert c["moe.choices_held"] == int((experts < 8).sum())
    for e in range(8):
        assert g[f"moe.held_load.{e}"] == int((experts == e).sum())
    n = len(seq)
    assert c["dsa.keys_visible"] == 2 * sum(t + 1 for t in range(n))
    assert c["dsa.keys_selected"] == 2 * sum(min(t + 1, 6) for t in range(n))
    selected = [np.asarray(a["selected"]) for a in choices
                if a["selected"] is not None]
    assert c["dsa.keys_selected"] == sum(int((s >= 0).sum()) for s in selected)
    assert 0 < g["moe.experts_touched"] <= 4 * 8
    # per pool, and their sum
    pools = eng.memory_report()["kv"]["pools"]
    assert set(pools) == {"latent", "index", "window"}
    assert g["kv.block_bytes"] == sum(p["block_bytes"] for p in pools.values())
    assert g["kv.latent_block_bytes"] == pools["latent"]["block_bytes"]
    assert g["kv.window_block_bytes"] == pools["window"]["block_bytes"]
    assert "pools=" in eng.state_dump()


def test_counters_carry_past_a_word():
    """A running sum is two int32 words; the carry is exact."""
    stats = jnp.zeros((2, lm.LOAD0 + 2), jnp.int32)
    add = jnp.zeros((lm.LOAD0 + 2,), jnp.int32).at[lm.KEYS_VISIBLE].set(
        2**30 + 12345)
    for _ in range(5):
        stats = lm._add_stats(stats, add, None)
    assert lm.read_counters(np.asarray(stats))["keys_visible"] \
        == 5 * (2**30 + 12345)


def test_a_llama_engine_lowers_to_the_same_programs_as_before_the_interface():
    """The engine reaches ``models.llama`` through the model interface; for a
    ``LlamaConfig`` its tick and chunk are, letter for letter, the programs
    that named ``llama`` directly (the chunk, since it carries rows, the
    rows entry over a program of one)."""
    cfg = llama.llama_tiny()
    params = llama.init_params(cfg, jax.random.key(0))
    eng = ServeEngine(params, cfg, n_slots=2, max_len=32, chunk=8,
                      monitor=False, sampler=False,
                      metrics=metrics_mod.MetricsRegistry(event_log=None))
    assert eng.model is llama

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def _tick(params, pcache, last_logits, active):
        tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        logits, pcache = llama.decode_chunk_paged(
            params, tok[:, None], cfg, pcache, advance=active)
        return logits[:, 0], pcache     # the host reads `_sample`'s tokens

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def _chunk(params, pcache, last_logits, toks, slots, new_len, sel):
        logits, pcache = llama.decode_chunk_paged_rows(
            params, toks, cfg, pcache, slots, new_length=new_len, sel=sel)
        return pcache, last_logits.at[slots].set(logits, mode="drop")

    progs = eng.pinned_programs()
    for name, before in (("tick", _tick), ("chunk", _chunk)):
        fn, *avals = progs[name]
        assert fn.lower(*avals).as_text() == before.lower(*avals).as_text()
    assert set(eng.memory_report()["kv"]["pools"]) == {"k", "v"}
