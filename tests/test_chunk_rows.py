"""A prefill chunk program of several rows (``decode_chunk_paged_rows``): one
program of R rows leaves the logits and the whole cache as R programs of one
row do, for every model that offers the entry; a row that is not there leaves
nothing; and the one-row entry ``decode_chunk_paged_row`` is its R = 1 case."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import llama, paged, shortconv_moe, window_moe

N_SLOTS, MAX_LEN, BS, T = 5, 32, 4, 8
PER = MAX_LEN // BS
#: float32 on the CPU: a product over R x T rows and one over T rows may sum
#: in another order
TOL = dict(rtol=2e-5, atol=2e-5)

MODELS = {
    "llama": (llama, lambda: llama.llama_tiny(
        dtype=jnp.float32, param_dtype=jnp.float32, n_layers=2)),
    "shortconv_moe": (shortconv_moe, shortconv_moe.shortconv_moe_tiny),
    "window_moe": (window_moe, window_moe.window_moe_tiny),
}


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(1, 64, n).astype(np.int32)


@pytest.fixture(scope="module", params=[
    (m, walk) for m in sorted(MODELS) for walk in ("rows_share_a_bound",
                                                   "a_row_a_group")],
    ids="-".join)
def world(request):
    """A model and a cache with history, under both forms of the attention
    walk: the rows of a program to one bound (a window of no more queries
    than ``llama._ROW_GROUP``, as here by default) and each row a group of
    its own (what a real chunk's hundreds of queries a row get; here by a
    group of two rows and key tiles of one block)."""
    with pytest.MonkeyPatch.context() as mp:
        if request.param[1] == "a_row_a_group":
            mp.setattr(llama, "_ROW_GROUP", 2)
            mp.setattr(llama, "_KEY_TILE", BS)
            assert llama._row_groups(4, PER, T) == (4, 1)
        else:
            assert llama._row_groups(4, PER, T) == (1, 4)
        yield _world(request.param[0])


def _world(model):
    """The cache's history: slot 0 fresh at 0; slot 1 one whole
    chunk in (length 8); slot 2 mapped at a prefix hit's base, one block of
    slot 1's (length 4: not a multiple of the chunk), its state restored from
    that block's snapshot; slot 3 a chunk in and about to take a padded final
    window; slot 4 free (its table all trash)."""
    mod, make = MODELS[model]
    cfg = make()
    params, _ = paged.serving_tree(
        mod, mod.init_params(cfg, jax.random.key(0)), cfg, tp_size=1)
    pc = mod.init_paged_cache(cfg, N_SLOTS, MAX_LEN, block_size=BS)
    table = 1 + np.random.default_rng(3).permutation(
        N_SLOTS * PER).reshape(N_SLOTS, PER).astype(np.int32)
    table[4] = 0
    rows = jax.jit(partial(mod.decode_chunk_paged_rows, cfg=cfg))

    def set_row(pc, slot, row, length):
        if hasattr(mod, "set_row"):
            return mod.set_row(pc, slot, jnp.asarray(row), length)
        return pc._replace(block_table=pc.block_table.at[slot].set(row),
                           length=pc.length.at[slot].set(length))

    for slot in (0, 1, 3, 4):
        pc = set_row(pc, slot, table[slot], 0)
    first = _tokens(T, 1)
    for slot in (1, 3):
        _, pc = rows(params, jnp.asarray(first[None]), pcache=pc,
                     slots=jnp.asarray([slot]), new_length=jnp.asarray([T]),
                     sel=jnp.asarray([0]))
    table[2, 0] = table[1, 0]           # the shared block, then slot 2's own
    pc = set_row(pc, 2, table[2], BS)
    return mod, cfg, params, jax.tree.map(np.asarray, pc), rows


#: the rows of the program under test: (slot, tokens' seed, new length, sel)
ROWS = ((0, 11, 8, 0),          # a first window, whole
        (1, 12, 16, 0),         # a second window, whole
        (2, 13, 12, 0),         # from the hit's base, across two block ends
        (3, 14, 13, 4))         # a final window: 5 tokens and 3 of padding


def _args(rows):
    toks = np.stack([_tokens(T, seed) for _, seed, _, _ in rows])
    for i, (slot, _, new_len, sel) in enumerate(rows):
        if sel:                         # padded: zeros past the last token
            toks[i, sel + 1:] = 0
    return (jnp.asarray(toks), jnp.asarray([r[0] for r in rows], jnp.int32),
            jnp.asarray([r[2] for r in rows], jnp.int32),
            jnp.asarray([r[3] for r in rows], jnp.int32))


def _run(world, rows, pc=None):
    mod, cfg, params, pc0, fn = world
    toks, slots, new_len, sel = _args(rows)
    logits, out = fn(params, toks, pcache=jax.tree.map(
        jnp.asarray, pc0 if pc is None else pc), slots=slots,
        new_length=new_len, sel=sel)
    return np.asarray(logits), jax.tree.map(np.asarray, out)


def _assert_caches_equal(mod, got, want, exact=False):
    for name in got._fields:
        g, w = getattr(got, name), getattr(want, name)
        if isinstance(g, int):
            assert g == w
        elif name == "stats" and not exact:
            # but for the one counter that is a program's and not a row's:
            # the expert layers that took every expert at once
            g, w = mod.read_counters(g), mod.read_counters(w)
            assert dict(g, layers_batched=0) == dict(w, layers_batched=0)
        elif exact or np.issubdtype(g.dtype, np.integer):
            np.testing.assert_array_equal(g, w, err_msg=name)
        else:
            np.testing.assert_allclose(g, w, err_msg=name, **TOL)


def test_rows_in_one_program_equal_one_row_programs(world):
    """Logits and the whole cache: pools, lengths, the convolution's state or
    the ring, the snapshots at the block ends reached, the counters."""
    logits, pc = _run(world, ROWS)
    one_by_one = None
    for i, row in enumerate(ROWS):
        li, one_by_one = _run(world, (row,), one_by_one)
        np.testing.assert_allclose(logits[i], li[0], **TOL)
    _assert_caches_equal(world[0], pc, one_by_one)
    assert pc.length.tolist() == [8, 16, 12, 13, 0]
    if hasattr(pc, "snap"):             # block ends were reached, and written
        assert not np.array_equal(pc.snap, world[3].snap)


def test_one_row_equals_the_one_row_entry(world):
    """``decode_chunk_paged_row`` is the R = 1 case with every position's
    logits: the picked position's are the rows entry's, the cache the same."""
    mod, cfg, params, pc0, _ = world
    for row in ROWS:
        logits, pc = _run(world, (row,))
        toks, slots, new_len, sel = _args((row,))
        every, pc1 = jax.jit(partial(mod.decode_chunk_paged_row, cfg=cfg))(
            params, toks, pcache=jax.tree.map(jnp.asarray, pc0),
            slot=slots[0], new_length=new_len[0])
        assert every.shape == (1, T, cfg.vocab_size)
        np.testing.assert_allclose(logits[0], np.asarray(every)[0, row[3]],
                                   **TOL)
        _assert_caches_equal(mod, pc, jax.tree.map(np.asarray, pc1))
    with pytest.raises(ValueError, match="B=1"):
        mod.decode_chunk_paged_row(params, jnp.zeros((2, T), jnp.int32), cfg,
                                   jax.tree.map(jnp.asarray, pc0), 0,
                                   new_length=4)


def test_a_row_that_is_not_there_leaves_the_cache_bit_for_bit(world):
    """A row whose slot is ``n_slots`` writes no key, no state, no ring, no
    snapshot, no length and no counter: a program of such rows alone returns
    the cache it was given, and beside real rows it changes nothing of what
    they leave.  (A row as wide as a chunk that wrote through a clamped table
    could land on valid keys, where a tick's one position cannot.)"""
    pc0 = world[3]
    absent = (N_SLOTS, 15, 8, 0)
    _, pc = _run(world, (absent, absent))
    _assert_caches_equal(world[0], pc, pc0, exact=True)
    logits, pc = _run(world, ROWS[:3] + (absent,))
    want_logits, want = _run(world, ROWS[:3])
    np.testing.assert_allclose(logits[:3], want_logits, **TOL)
    _assert_caches_equal(world[0], pc, want)
