"""A tick's attention walks its rows in groups of like length, and a row whose
output nobody reads walks one tile (``llama.tile_walk``,
``llama.paged_attend_tiles``; the three models that share them).

Each at toy size on the CPU, with a key tile of one block and groups of three
rows, so that seven rows over a table of six tiles walk in three groups with
two padded places:

1. the tick and the speculative tick over ragged lengths give every row that
   is read the logits, the pool writes and the per-slot state of the body that
   walked every row to the longest row's last tile, which is kept here as the
   plain reference;
2. the host's count of the blocks visited is the trips the device takes, and
   the blocks live lie within the blocks visited within the table's;
3. a program of one row, or of no more rows than a group, has no outer loop;
4. a toy engine of each model serves the same tokens under either body, and
   its three counters stand in that order.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu import metrics as metrics_mod
from horovod_tpu.models import llama, paged
from horovod_tpu.models import shortconv_moe as sm
from horovod_tpu.models import window_moe as wm
from horovod_tpu.serving_scheduler import Request, ServeEngine

N_SLOTS, MAX_LEN, BLOCK, GROUP, DRAFT_K = 7, 48, 8, 3, 3

MODELS = {
    "llama": (llama, lambda **kw: llama.llama_tiny(
        dtype=jnp.float32, param_dtype=jnp.float32, **kw)),
    "shortconv_moe": (sm, sm.shortconv_moe_tiny),
    "window_moe": (wm, wm.window_moe_tiny),
}


def _walk_to_longest(table, qpos, bs, active=None):
    """``llama.tile_walk`` as it was before the rows walked in groups: one
    bound for the whole program, the longest row's last tile, whether or not
    a row's output is read."""
    per = table.shape[1]
    g = llama._tile_blocks(bs, per)
    n_tiles = -(-per // g)
    return llama.TileWalk(
        table=jnp.pad(table, ((0, 0), (0, n_tiles * g - per))), qpos=qpos,
        g=g, n_live=jnp.minimum(jnp.max(qpos) // (g * bs) + 1, n_tiles),
        m=per * bs, order=None, place=None)


def _attend_to_longest(q, k, v, kf, vf, layer, walk, wflat, n_blocks, bs,
                       scale=None):
    """``llama.paged_attend_tiles`` as it was: every row of the program
    through one loop over key tiles to ``walk.n_live``."""
    b, t, n_heads, dh = q.shape
    kvh = k.shape[2]
    n_rep = n_heads // kvh
    scale = 1.0 / (dh ** 0.5) if scale is None else scale
    g, w = walk.g, walk.g * bs
    off = layer * (n_blocks * bs)
    kf = kf.at[wflat + off].set(k)
    vf = vf.at[wflat + off].set(v)
    kb = kf.reshape(-1, bs, kvh, dh)
    vb = vf.reshape(-1, bs, kvh, dh)
    qg = q.reshape(b, t, kvh, n_rep, dh)
    stat = (b, kvh, n_rep, t)

    def tile(j, acc):
        mx, den, o = acc
        blk = lax.dynamic_slice_in_dim(walk.table, j * g, g, axis=1)
        blk = blk + layer * n_blocks
        kt = kb[blk].reshape(b, w, kvh, dh)
        vt = vb[blk].reshape(b, w, kvh, dh)
        s = jnp.einsum("bqkrd,bmkd->bkrqm", qg, kt,
                       preferred_element_type=jnp.float32) * scale
        kpos = j * w + jnp.arange(w)
        seen = (kpos <= walk.qpos[:, :, None]) & (kpos < walk.m)
        s = jnp.where(seen[:, None, None], s, llama.NEG_INF_LOGIT)
        mx_new = jnp.maximum(mx, jnp.max(s, axis=-1))
        p = jnp.exp(s - mx_new[..., None])
        fade = jnp.exp(mx - mx_new)
        den = fade * den + jnp.sum(p, axis=-1)
        o = fade[..., None] * o + jnp.einsum(
            "bkrqm,bmkd->bkrqd", p, vt.astype(jnp.float32))
        return mx_new, den, o

    _, den, o = lax.fori_loop(
        0, walk.n_live, tile,
        (jnp.full(stat, llama.NEG_INF_LOGIT, jnp.float32),
         jnp.zeros(stat, jnp.float32),
         jnp.zeros(stat + (dh,), jnp.float32)))
    return jnp.moveaxis(o / den[..., None], 3, 1), kf, vf


@pytest.fixture
def toy_walk(monkeypatch):
    """One block a key tile and three rows a group."""
    monkeypatch.setattr(llama, "_KEY_TILE", BLOCK)
    monkeypatch.setattr(llama, "_ROW_GROUP", GROUP)


def _to_longest(monkeypatch):
    monkeypatch.setattr(llama, "tile_walk", _walk_to_longest)
    monkeypatch.setattr(llama, "paged_attend_tiles", _attend_to_longest)


def _cache(mod, cfg, lengths, seed=1):
    """A cache of noise (pools, snapshots and the slots' own state alike, so
    a wrong gather, scatter or row shows) whose rows map shuffled blocks,
    never the trash block, at ``lengths``."""
    n_slots, per = len(lengths), MAX_LEN // BLOCK
    pc = mod.init_paged_cache(cfg, n_slots, MAX_LEN, block_size=BLOCK)
    rng = np.random.default_rng(seed)
    noise = {
        name: jnp.asarray(rng.standard_normal(a.shape), a.dtype)
        for name, a in pc._asdict().items()
        if jnp.issubdtype(a.dtype, jnp.floating)}
    table = 1 + rng.permutation(n_slots * per).reshape(n_slots, per)
    return pc._replace(block_table=jnp.asarray(table, jnp.int32),
                       length=jnp.asarray(lengths, jnp.int32), **noise)


def _tick(mod, cfg, params, pc, active):
    toks = jnp.asarray(np.random.default_rng(2).integers(
        1, cfg.vocab_size, (N_SLOTS, 1)), jnp.int32)
    return mod.decode_chunk_paged(params, toks, cfg, pc, advance=active)


def _spec_tick(mod, cfg, params, pc, active):
    rng = np.random.default_rng(3)
    last = jnp.asarray(rng.standard_normal((N_SLOTS, cfg.vocab_size)),
                       jnp.float32)
    drafts = jnp.asarray(rng.integers(1, cfg.vocab_size,
                                      (N_SLOTS, DRAFT_K)), jnp.int32)
    tok, accept, nxt, pc = mod.spec_verify_paged(
        params, cfg, pc, last, drafts.at[2, 1:].set(-1), active)
    return (tok, accept, nxt), pc


PROGRAMS = {"tick": (_tick, 1), "spec_tick": (_spec_tick, DRAFT_K + 1)}

# ``D`` stands for the deepest a row can stand: its last query at the table's
# last position.  Sorted by their last tiles, ``ragged``'s rows are the three
# of one tile (the row at 0, the free slot and the long prefilling row, which
# is not read), the two rows at 9 on either side of a group's edge, the row at
# 17 and the row at D.
SCENES = {
    "ragged-row_at_0-row_at_full_depth-straddle-idle_beside_prefilling":
        (["zero", "D", 9, 9, 17, 30, 0], [1, 1, 1, 1, 1, 0, 0]),
    "all_rows_inactive_but_one":
        ([30, 40, 20, 9, 33, "D", 12], [0, 0, 0, 1, 0, 0, 0]),
}


def _lengths(scene, t):
    return [{"zero": 0, "D": MAX_LEN - t}.get(n, n) for n in SCENES[scene][0]]


def _read_rows(out, pc_before, active, t):
    """What a program leaves that anybody reads: the active rows' results,
    their blocks up to their new frontier, their own per-slot state, and
    everything that is not per row."""
    results, pc = out
    rows = np.flatnonzero(np.asarray(active))
    kept = {"results": [np.asarray(x)[rows]
                        for x in jax.tree.leaves(results)]}
    table = np.asarray(pc.block_table)
    ends = np.asarray(pc_before.length) + t
    for name, a in pc._asdict().items():
        a = np.asarray(a)
        if name in ("k", "v"):
            kept[name] = [
                a[:, table[r]].reshape((a.shape[0], -1) + a.shape[3:])[
                    :, :ends[r]] for r in rows]
        elif name in ("conv", "ring"):          # [.., n_slots, ...] a slot
            kept[name] = np.take(a, rows, axis=a.ndim - (
                2 if name == "conv" else 4))
        else:
            kept[name] = a
    return kept


@pytest.mark.parametrize("scene", list(SCENES))
@pytest.mark.parametrize("program", list(PROGRAMS))
@pytest.mark.parametrize("model", list(MODELS))
def test_rows_that_are_read_get_the_walk_to_longests_numbers(
        model, program, scene, toy_walk, monkeypatch):
    mod, tiny = MODELS[model]
    cfg = tiny()
    run, t = PROGRAMS[program]
    params, _ = paged.serving_tree(
        mod, mod.init_params(cfg, jax.random.key(0)), cfg, tp_size=1)
    active = jnp.asarray(SCENES[scene][1], jnp.int32)
    pc = _cache(mod, cfg, _lengths(scene, t))
    assert llama._row_groups(N_SLOTS, MAX_LEN // BLOCK) == (3, 3)
    got = jax.jit(lambda p, c: run(mod, cfg, p, c, active))(params, pc)
    _to_longest(monkeypatch)
    want = jax.jit(lambda p, c: run(mod, cfg, p, c, active))(params, pc)
    got, want = (_read_rows(o, pc, active, t) for o in (got, want))
    # within a row the same tiles in the same order, then terms of zero
    jax.tree.map(np.testing.assert_array_equal, got, want)
    # and the case did write: an active row's frontier is not the noise
    row = int(np.flatnonzero(np.asarray(active))[0])
    at = int(pc.length[row])
    before = np.asarray(pc.k)[:, np.asarray(pc.block_table)[row]].reshape(
        (pc.k.shape[0], -1) + pc.k.shape[3:])[:, at]
    assert not np.array_equal(got["k"][0][:, at], before)


def _device_blocks(lengths, active, t, bs, per):
    """The blocks a program's rows read, from the walk the device computes:
    each row's group's trips times the blocks a tile spans, within the
    table.  The count is of the rows' table entries: the padded places in
    front stand in the first group and are all the row behind them, so they
    walk that row's tiles again and read no entry that is not counted."""
    lengths = jnp.asarray(lengths, jnp.int32)
    qpos = lengths[:, None] + jnp.arange(t)[None, :]
    walk = jax.jit(lambda q, a: llama.tile_walk(
        jnp.zeros((len(lengths), per), jnp.int32), q, bs, a))(
            qpos, jnp.asarray(active, jnp.int32))
    trips = np.asarray(walk.n_live)
    if walk.order is None:
        group = np.zeros(len(lengths), np.int64)
    else:
        group = np.asarray(walk.place) // walk.table.shape[1]
        # the walk's order holds every row once, behind the padded places
        order = np.asarray(walk.order)
        pad = len(order) - len(lengths)
        assert sorted(order[pad:]) == list(range(len(lengths)))
        assert pad < walk.table.shape[1] and (order[:pad] == order[pad]).all()
    return int(np.sum(np.minimum(trips[group] * walk.g, per)))


@pytest.mark.parametrize("rows,per,t", [
    (7, 6, 1), (7, 6, 4), (9, 6, 1), (20, 3, 1), (2, 6, 1), (1, 6, 8),
    (16, 5, 2)])
def test_the_hosts_count_is_the_trips_the_device_takes(rows, per, t,
                                                       toy_walk):
    rng = np.random.default_rng(rows * per + t)
    for _ in range(8):
        lengths = rng.integers(0, per * BLOCK - t + 1, rows)
        lengths[rng.integers(rows)] = per * BLOCK - t      # the full depth
        lengths[rng.integers(rows)] = 0
        active = rng.integers(0, 2, rows)
        active[rng.integers(rows)] = 1
        visited, live = llama.paged_blocks_walked(lengths, active, t, BLOCK,
                                                  per)
        assert visited == _device_blocks(lengths, active, t, BLOCK, per)
        assert 0 < live <= visited <= rows * per
        want_live = sum(min((n + t - 1) // BLOCK + 1, per)
                        for n, a in zip(lengths, active) if a)
        assert live == want_live


def test_the_walk_reads_less_than_the_walk_to_longest(toy_walk):
    """``ragged`` above, by hand: the groups' bounds are 1, 2 and 6 tiles and
    two of the first group's places are padding, where the walk to the
    longest row took all seven rows to 6; the five rows that are read span
    1 + 6 + 2 + 2 + 3 blocks."""
    lengths, active = _lengths(next(iter(SCENES)), 1), [1, 1, 1, 1, 1, 0, 0]
    assert llama.paged_blocks_walked(lengths, active, 1, BLOCK, 6) == (
        1 * 1 + 3 * 2 + 3 * 6, 14)
    assert llama.paged_blocks_walked(
        [max(lengths)], [1], 1, BLOCK, 6)[0] * 7 == 42


@pytest.mark.parametrize("rows,n_tiles,want", [
    (1, 64, (1, 1)), (4, 64, (1, 4)), (8, 64, (1, 8)), (64, 64, (8, 8)),
    (128, 4, (4, 32)), (20, 3, (3, 7)), (9, 1, (1, 9)), (81, 10, (9, 9))])
def test_groups_of_eight_rows_and_no_more_groups_than_tiles(rows, n_tiles,
                                                            want):
    assert llama._ROW_GROUP == 8
    assert llama._row_groups(rows, n_tiles) == want
    groups, r = want            # the padded places are fewer than a group
    assert 0 <= groups * r - rows < r


def _whiles(lowered) -> int:
    return lowered.as_text().count("stablehlo.while")


@pytest.mark.parametrize("model", list(MODELS))
def test_a_program_of_one_group_has_no_outer_loop(model, toy_walk,
                                                  monkeypatch):
    """The chunk's one row, and a tick of no more rows than a group, lower to
    as many loops as the walk to the longest row did (one a layer that
    attends over the table), whatever the group's size; a tick of seven rows
    in groups of three has one more a layer, the loop over its groups."""
    mod, tiny = MODELS[model]
    cfg = tiny()
    params, _ = paged.serving_tree(
        mod, mod.init_params(cfg, jax.random.key(0)), cfg, tp_size=1)
    pc, small = _cache(mod, cfg, [0] * N_SLOTS), _cache(mod, cfg, [0] * GROUP)

    def lowerings():
        chunk = jax.jit(lambda p, c: mod.decode_chunk_paged_row(
            p, jnp.ones((1, BLOCK), jnp.int32), cfg, c, jnp.int32(1),
            new_length=jnp.int32(BLOCK))).lower(params, pc)
        few = jax.jit(lambda p, c: mod.decode_chunk_paged(
            p, jnp.ones((GROUP, 1), jnp.int32), cfg, c,
            advance=jnp.ones((GROUP,), jnp.int32))).lower(params, small)
        many = jax.jit(lambda p, c: mod.decode_chunk_paged(
            p, jnp.ones((N_SLOTS, 1), jnp.int32), cfg, c,
            advance=jnp.ones((N_SLOTS,), jnp.int32))).lower(params, pc)
        return chunk, few, many

    chunk, few, many = lowerings()
    monkeypatch.setattr(llama, "_ROW_GROUP", 1)
    assert lowerings()[0].as_text() == chunk.as_text()
    _to_longest(monkeypatch)
    chunk_was, few_was, many_was = lowerings()
    assert _whiles(chunk) == _whiles(chunk_was) > 0
    assert _whiles(few) == _whiles(few_was) > 0
    assert _whiles(many) > _whiles(many_was) == _whiles(few_was)


def _served(model, monkeypatch, to_longest, tp_size=1, **sizes):
    """A toy engine of five slots over prompts of ragged lengths, one of them
    three chunks long, so that ticks hold short rows beside a long one and a
    row still prefilling: the tokens and the three counters."""
    mod, tiny = MODELS[model]
    cfg = tiny(**sizes)
    params = mod.init_params(cfg, jax.random.key(0))
    monkeypatch.setattr(llama, "_KEY_TILE", BLOCK)
    monkeypatch.setattr(llama, "_ROW_GROUP", 2)
    if to_longest:
        _to_longest(monkeypatch)
    eng = ServeEngine(params, cfg, n_slots=5, max_len=MAX_LEN, chunk=BLOCK,
                      block_size=BLOCK, tp_size=tp_size,
                      metrics=metrics_mod.MetricsRegistry(event_log=None))
    rng = np.random.default_rng(11)
    out = eng.run([Request(prompt=rng.integers(1, cfg.vocab_size, n).tolist(),
                           max_new_tokens=new)
                   for n, new in ((3, 9), (21, 6), (9, 12), (30, 5), (5, 14),
                                  (12, 4))])
    c = eng.metrics.snapshot()["counters"]
    return [list(r) for r in out], tuple(
        c[f"attn.blocks_{name}"] for name in ("live", "visited", "in_table"))


@pytest.mark.parametrize("model", list(MODELS))
def test_an_engine_serves_the_same_tokens_and_reads_fewer_blocks(
        model, monkeypatch):
    tokens, (live, visited, in_table) = _served(model, monkeypatch, False)
    assert 0 < live < visited < in_table
    # the host's count is of the grouped walk, whichever body ran: held
    # against the count of the walk to the longest row by hand instead
    tokens_was, counted = _served(model, monkeypatch, True)
    assert tokens == tokens_was and all(len(t) > 3 for t in tokens)
    assert counted == (live, visited, in_table)


def test_tensor_parallel_ticks_walk_in_groups_to_the_same_tokens(monkeypatch):
    """The order of the rows is replicated data and the heads stay split:
    two shards serve what one device serves, the walk grouped on both, and
    what the walk to the longest row served."""
    split = _served("llama", monkeypatch, False, tp_size=2, n_kv_heads=4)
    assert split == _served("llama", monkeypatch, False, n_kv_heads=4)
    assert split[0] == _served("llama", monkeypatch, True, tp_size=2,
                               n_kv_heads=4)[0]
