"""The paged KV pool is written in place and read block by block:
``_paged_attend`` carries it through the layer scan instead of scanning it in
and stacking it out, and its attention walks each row's block table up to the
longest live row with a running softmax instead of gathering the whole table.

Each at toy size on the CPU:

1. the three paged entry points give logits and pool contents equal, within
   the rounding of another order of summation, to a test-local copy of the old
   full-width, scanned-in, stacked-out body, over idle rows writing to the
   trash block, a row at length 0, a chunk that crosses a block boundary, a
   chunk that overflows its table, and a padded final prefill window, at key
   tiles of one, two and three blocks (three does not divide the table);
2. blocks past the longest live row are never read (NaN there changes
   nothing), and nothing as deep as the table exists inside the layer scan,
   whose key loop has a traced bound;
3. the jaxpr has the pool among the scan's carries and neither among its
   scanned inputs nor its stacked outputs;
4. a toy engine's compiled tick and chunk hold less scratch than one pool, and
   its ``attn.blocks_live`` / ``attn.blocks_visited`` / ``attn.blocks_in_table``
   add up to what the schedule says (``tests/test_row_groups.py`` holds the
   walk of a program of more rows than one group).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.extend.core import Literal

from horovod_tpu import metrics as metrics_mod
from horovod_tpu.models import llama
from horovod_tpu.serving_scheduler import Request, ServeEngine

N_SLOTS, MAX_LEN, BLOCK, N_BLOCKS = 4, 32, 8, 12


def _paged_attend_full_width(params, tokens, cfg, kv_k, kv_v, qpos, wflat,
                             table, active=None, sel=None):
    """The body as it was before the pool became a carry and attention
    walked the live blocks: ``kv_k`` / ``kv_v`` scanned in layer by layer,
    each written slice stacked out, and every row's whole table gathered,
    scored and put through one dense softmax (whether or not the row's output
    is read: ``active`` is not looked at)."""
    b, t = tokens.shape
    nl, n_blocks, bs, kvh, dh = kv_k.shape
    gflat = (table[:, :, None] * bs
             + jnp.arange(bs)[None, None, :]).reshape(b, -1)
    m = gflat.shape[1]
    dt = cfg.dtype
    x = params["embed"][tokens].astype(dt)
    cos, sin = llama.rope_tables(cfg, qpos)
    n_rep = cfg.n_heads // cfg.n_kv_heads
    scale = 1.0 / (cfg.head_dim ** 0.5)
    valid = jnp.arange(m)[None, None, :] <= qpos[:, :, None]
    valid = valid[:, None, None, :, :]

    def layer(x, inputs):
        lp, kc, vc = inputs
        h = llama.rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q = (h @ lp["wq"].astype(dt)).reshape(b, t, cfg.n_heads, cfg.head_dim)
        k = (h @ lp["wk"].astype(dt)).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ lp["wv"].astype(dt)).reshape(b, t, cfg.n_kv_heads, cfg.head_dim)
        q = llama.apply_rope(q, cos, sin)
        k = llama.apply_rope(k, cos, sin)
        kf = kc.reshape(n_blocks * bs, kvh, dh).at[wflat].set(k)
        vf = vc.reshape(n_blocks * bs, kvh, dh).at[wflat].set(v)
        kd = kf[gflat]
        vd = vf[gflat]
        qg = q.reshape(b, t, cfg.n_kv_heads, n_rep, cfg.head_dim)
        s = jnp.einsum("bqkrd,bmkd->bkrqm", qg.astype(jnp.float32),
                       kd.astype(jnp.float32)) * scale
        s = jnp.where(valid, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkrqm,bmkd->bqkrd", p, vd.astype(jnp.float32))
        x = x + o.astype(dt).reshape(b, t, cfg.dim) @ lp["wo"].astype(dt)
        h = llama.rmsnorm(x, lp["mlp_norm"], cfg.norm_eps)
        gate = jax.nn.silu(h @ lp["w_gate"].astype(dt))
        up = h @ lp["w_up"].astype(dt)
        x = x + (gate * up) @ lp["w_down"].astype(dt)
        return x, (kf.reshape(n_blocks, bs, kvh, dh),
                   vf.reshape(n_blocks, bs, kvh, dh))

    x, (ks, vs) = lax.scan(layer, x, (params["layers"], kv_k, kv_v))
    x = llama.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = (x @ params["lm_head"].astype(dt)).astype(jnp.float32)
    return logits, ks, vs


def _setup(dtype, serving=False):
    """A pool full of noise (so a wrong gather or scatter shows) and four
    rows: 0 mid-block at 13, 1 free (table all trash, idle), 2 admitted at
    length 0, 3 two short of the end of its table.  The public tree, which
    the full-width reference reads, or the ``serving`` tree, which the body
    under test reads."""
    cfg = llama.llama_tiny(dtype=dtype, param_dtype=dtype, n_layers=3)
    params = llama.init_params(cfg, jax.random.key(0))
    if serving:
        params = llama.serving_params(params, cfg)
    pc = llama.init_paged_cache(cfg, N_SLOTS, MAX_LEN, block_size=BLOCK,
                                n_blocks=N_BLOCKS)
    kk, kv = jax.random.split(jax.random.key(1))
    table = jnp.asarray([[3, 5, 9, 0], [0, 0, 0, 0], [7, 0, 0, 0],
                         [1, 2, 4, 6]], jnp.int32)
    pc = pc._replace(
        k=jax.random.normal(kk, pc.k.shape, jnp.float32).astype(dtype),
        v=jax.random.normal(kv, pc.v.shape, jnp.float32).astype(dtype),
        block_table=table, length=jnp.asarray([13, 0, 0, 30], jnp.int32))
    return cfg, params, pc


def _toks(seed, shape, vocab):
    return jnp.asarray(
        np.random.RandomState(seed).randint(1, vocab, size=shape), jnp.int32)


def _tick(cfg, params, pc):
    # idle row 1 scatters into trash; row 2 decodes its first position
    return llama.decode_chunk_paged(
        params, _toks(2, (N_SLOTS, 1), cfg.vocab_size), cfg, pc,
        advance=jnp.asarray([1, 0, 1, 1], jnp.int32))


def _wide(cfg, params, pc):
    # T=4: row 0 crosses 15 -> 16, row 3 runs off its table (clamped write)
    return llama.decode_chunk_paged(
        params, _toks(3, (N_SLOTS, 4), cfg.vocab_size), cfg, pc)


def _row(slot, t, new_length):
    def run(cfg, params, pc):
        return llama.decode_chunk_paged_row(
            params, _toks(4 + slot, (1, t), cfg.vocab_size), cfg, pc,
            jnp.int32(slot), new_length=jnp.int32(new_length))
    return run


def _spec(cfg, params, pc):
    last = jax.random.normal(jax.random.key(6),
                             (N_SLOTS, cfg.vocab_size), jnp.float32)
    drafts = _toks(7, (N_SLOTS, 3), cfg.vocab_size).at[2, 1:].set(-1)
    tok, accept, nxt, pc = llama.spec_verify_paged(
        params, cfg, pc, last, drafts, jnp.asarray([1, 0, 1, 1], jnp.int32))
    return (tok, accept, nxt), pc


CASES = {
    "tick-idle_rows_to_trash-row_at_0": _tick,
    "chunk_paged-crosses_block-overflows_table": _wide,
    "row-crosses_block": _row(0, 8, 21),            # 13..20 over 15 -> 16
    "row-padded_final_window": _row(0, 8, 13 + 5),  # 3 pad positions
    "row-at_length_0": _row(2, 8, 8),
    "row-free_slot_to_trash": _row(1, 8, 0),
    "row-overflows_table": _row(3, 8, 32),          # 30..37 of 32
    "spec_verify-idle_and_padded_drafts": _spec,
}


def _run_both(case, dtype, monkeypatch, tile_blocks):
    """``case`` through the body under test, its key tile set to
    ``tile_blocks`` pool blocks, and through the full-width reference."""
    cfg, params, pc = _setup(dtype)
    run = CASES[case]
    monkeypatch.setattr(llama, "_KEY_TILE", tile_blocks * BLOCK)
    got = jax.jit(lambda p, c: run(cfg, p, c))(
        llama.serving_params(params, cfg), pc)
    # (the reference reads the public tree's wq / wk / wv)
    monkeypatch.setattr(llama, "_paged_attend", _paged_attend_full_width)
    want = jax.jit(lambda p, c: run(cfg, p, c))(params, pc)
    return pc, got, want


# Read from a run of these cases at tiles of one, two and three blocks:
# float32 differs from the full-width body by at most 3.6e-6 absolute where
# the largest logit is 3.3 (another order of summation); bfloat16 not at all,
# because each layer's rounding to bfloat16 swallows that.  The bfloat16
# tolerance is one bfloat16 step at the largest logit: one activation that
# rounds the other way.
TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=0, atol=2.0 ** -6)}


def _assert_close(got, want, dtype):
    flat_got, tree_got = jax.tree.flatten(got)
    flat_want, tree_want = jax.tree.flatten(want)
    assert tree_got == tree_want
    for g, w in zip(flat_got, flat_want):
        assert g.dtype == w.dtype and g.shape == w.shape
        if jnp.issubdtype(g.dtype, jnp.integer):      # tokens, lengths
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        else:
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(w, np.float32),
                                       **TOL[dtype])


def _logits(out):
    """The logits of a case's result: ``(logits, pcache)`` or the verify
    round's ``((tok, accept, next_logits), pcache)``."""
    return out[0][2] if isinstance(out[0], tuple) else out[0]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(CASES))
def test_matches_full_width_body(case, dtype, monkeypatch):
    # one block a tile: four steps of the key loop span the table
    pc, got, want = _run_both(case, dtype, monkeypatch, 1)
    _assert_close(got, want, dtype)
    np.testing.assert_array_equal(                    # the same argmax
        np.argmax(np.asarray(_logits(got)), axis=-1),
        np.argmax(np.asarray(_logits(want)), axis=-1))
    # and the case did write: the pool differs from what went in
    assert not np.array_equal(np.asarray(got[1].k, np.float32),
                              np.asarray(pc.k, np.float32))


@pytest.mark.parametrize("tile_blocks", [2, 3], ids=["tile2", "tile3_pads"])
@pytest.mark.parametrize("case", ["tick-idle_rows_to_trash-row_at_0",
                                  "row-crosses_block",
                                  "row-overflows_table",
                                  "spec_verify-idle_and_padded_drafts"])
def test_matches_full_width_body_at_wider_tiles(case, tile_blocks,
                                                monkeypatch):
    # rows of unequal length, a row at 0 and an idle row on trash in one
    # tick; a chunk across a block boundary; a padded window that overflows
    # its table; the verify round.  Three blocks a tile do not divide the
    # table's four, so the last tile reads a padded (trash) entry.
    _, got, want = _run_both(case, jnp.float32, monkeypatch, tile_blocks)
    _assert_close(got, want, jnp.float32)


def _short_rows(pc, n_live):
    """``pc`` with no row past 13 and, after the first ``n_live`` blocks of
    each table, entries that point at block 11; and the same with NaN in
    block 11 and every other block that no live position maps to."""
    table = np.asarray(pc.block_table).copy()
    table[3] = [1, 2, 4, 6]
    table[:, n_live:] = N_BLOCKS - 1
    mapped = set(table[:, :n_live].ravel().tolist())
    dead = np.asarray([b for b in range(N_BLOCKS) if b not in mapped])
    clean = pc._replace(block_table=jnp.asarray(table),
                        length=jnp.asarray([13, 0, 0, 9], jnp.int32))
    return clean, clean._replace(k=pc.k.at[:, dead].set(jnp.nan),
                                 v=pc.v.at[:, dead].set(jnp.nan))


# the longest row is at 13: a tick ends in block 1, a four-token verify
# round and an eight-token chunk in block 2
@pytest.mark.parametrize("n_live,case", [
    (2, "tick-idle_rows_to_trash-row_at_0"),
    (3, "row-crosses_block"),
    (3, "spec_verify-idle_and_padded_drafts")])
def test_blocks_past_the_longest_row_are_never_read(n_live, case,
                                                    monkeypatch):
    cfg, public, pc = _setup(jnp.float32)
    params = llama.serving_params(public, cfg)
    clean, poisoned = _short_rows(pc, n_live)
    assert np.isnan(np.asarray(poisoned.k)).any()
    run = jax.jit(lambda p, c: CASES[case](cfg, p, c))
    monkeypatch.setattr(llama, "_KEY_TILE", BLOCK)
    want = _logits(run(params, clean))
    got = _logits(run(params, poisoned))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the full-width body multiplies the NaN by a zero probability
    monkeypatch.setattr(llama, "_paged_attend", _paged_attend_full_width)
    old = jax.jit(lambda p, c: CASES[case](cfg, p, c))(public, poisoned)
    assert not np.isfinite(np.asarray(_logits(old))).all()


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("case", ["tick-idle_rows_to_trash-row_at_0",
                                  "row-crosses_block",
                                  "spec_verify-idle_and_padded_drafts"])
def test_nothing_table_deep_in_the_layer_scan(case, monkeypatch):
    # five blocks of 8: a depth of 40 is no other size of this model
    cfg = llama.llama_tiny(n_layers=3)
    params = llama.serving_params(
        llama.init_params(cfg, jax.random.key(0)), cfg)
    depth = 5 * BLOCK
    pc = llama.init_paged_cache(cfg, N_SLOTS, depth, block_size=BLOCK,
                                n_blocks=N_BLOCKS)
    monkeypatch.setattr(llama, "_KEY_TILE", 2 * BLOCK)
    jaxpr = jax.make_jaxpr(lambda p, c: CASES[case](cfg, p, c))(params, pc)
    (scan,) = list(_scans(jaxpr.jaxpr))
    inside = list(_eqns(scan.params["jaxpr"].jaxpr))
    for eqn in inside:
        for v in eqn.outvars:
            assert depth not in getattr(v.aval, "shape", ()), eqn
    (loop,) = [e for e in inside if e.primitive.name == "while"]
    cond = loop.params["cond_jaxpr"].jaxpr
    (lt,) = [e for e in cond.eqns if e.primitive.name == "lt"]
    assert not any(isinstance(v, Literal) for v in lt.invars), lt


def _scans(jaxpr):
    return (eqn for eqn in _eqns(jaxpr) if eqn.primitive.name == "scan")


@pytest.mark.parametrize("case", ["tick-idle_rows_to_trash-row_at_0",
                                  "row-crosses_block",
                                  "spec_verify-idle_and_padded_drafts"])
def test_pool_is_a_scan_carry(case):
    cfg, params, pc = _setup(jnp.float32, serving=True)
    jaxpr = jax.make_jaxpr(lambda p, c: CASES[case](cfg, p, c))(params, pc)
    (scan,) = list(_scans(jaxpr.jaxpr))
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    pool_elems = pc.k.size

    def pools(vs):
        return [v.aval.shape for v in vs
                if hasattr(v.aval, "shape")
                and int(np.prod(v.aval.shape)) >= pool_elems // cfg.n_layers
                and v.aval.shape[-2:] == pc.k.shape[-2:]]

    carries_in = scan.invars[n_consts:n_consts + n_carry]
    xs = scan.invars[n_consts + n_carry:]
    carries_out, ys = scan.outvars[:n_carry], scan.outvars[n_carry:]
    flat = (pool_elems // (pc.k.shape[-2] * pc.k.shape[-1]),) + pc.k.shape[-2:]
    assert pools(carries_in) == [flat, flat]          # k and v, whole
    assert pools(carries_out) == [flat, flat]
    assert pools(xs) == [] and pools(ys) == []
    assert pools(scan.invars[:n_consts]) == []        # nor closed over
    assert ys == []                                   # nothing is stacked out


@pytest.fixture(scope="module")
def toy_engine():
    # 129 blocks where 9 would back both slots: the pool (528 KB) is several
    # times one layer's weights and activations (about 150 KB of scratch), so
    # a second pool, or half of one, could not hide in the scratch
    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    return ServeEngine(params, cfg, n_slots=2, max_len=32, chunk=8,
                       block_size=8, n_blocks=129, spec=True, draft_k=3,
                       metrics=metrics_mod.MetricsRegistry(event_log=None))


@pytest.mark.parametrize("prog", ["tick", "chunk", "spec_tick"])
def test_program_scratch_is_under_one_pool(toy_engine, prog):
    eng = toy_engine
    pool_bytes = eng.pcache.k.nbytes + eng.pcache.v.nbytes
    before = eng.compile_cache_sizes()
    fn, *avals = eng.pinned_programs()[prog]
    mem = fn.lower(*avals).compile().memory_analysis()
    assert eng.compile_cache_sizes() == before        # AOT mints no entry
    assert mem.alias_size_in_bytes >= pool_bytes      # donated, written in place
    assert mem.temp_size_in_bytes < pool_bytes / 2, (
        mem.temp_size_in_bytes, pool_bytes)


def test_attention_counters_follow_the_schedule(monkeypatch):
    """Row A: 6 prompt tokens (one chunk), 3 answers; row B: 19 (chunks of
    8, 8, 3), 2 answers; tables of 4 blocks of 8, one block a key tile.  A
    chunk from length ``n`` walks ``(n + 7) // 8 + 1`` blocks of its one row,
    which are the blocks it spans; a tick's two rows are one group, which
    walks to the longest row that decodes (a row that does not walks one
    tile), and the blocks live are those of the rows that decode; each of a
    table of 4."""
    monkeypatch.setattr(llama, "_KEY_TILE", 8)
    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    eng = ServeEngine(params, cfg, n_slots=2, max_len=32, chunk=8,
                      block_size=8,
                      metrics=metrics_mod.MetricsRegistry(event_log=None))
    eng.submit(Request(prompt=list(range(1, 7)), max_new_tokens=3))
    eng.submit(Request(prompt=list(range(1, 20)), max_new_tokens=2))
    counters = lambda: eng.metrics.snapshot()["counters"]  # noqa: E731
    names = [f"attn.blocks_{n}" for n in ("live", "visited", "in_table")]
    assert [counters()[n] for n in names] == [0, 0, 0]
    by_hand = [
        # A's chunk from 0, B's from 0; tick with A at 6, B (prefilling) at 8
        (1 + 1 + 1, 1 + 1 + 2 * 1, 4 + 4 + 8),
        # B's chunk from 8; tick with A at 7, B (prefilling) at 16
        (2 + 1, 2 + 2 * 1, 4 + 8),
        # B's last chunk from 16; tick with A at 8, B at 19: both decode
        (3 + 2 + 3, 3 + 2 * 3, 4 + 8),
        # A has its 3 answers and is free; tick with B at 20
        (3, 2 * 3, 8),
    ]
    seen = [0, 0, 0]
    for want in by_hand:
        eng.step()
        now = [counters()[n] for n in names]
        assert tuple(a - b for a, b in zip(now, seen)) == want
        seen = now
    assert not eng.pending()


@pytest.mark.parametrize("spec", [False, True], ids=["plain", "spec"])
def test_row_length_mirrors_the_device(spec):
    # what the counters are reckoned from: the slots' own bookkeeping says
    # what each row's device length is after every step, through prefill,
    # decode (speculative rounds advance by what they emit) and retirement
    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.key(0))
    eng = ServeEngine(params, cfg, n_slots=2, max_len=48, chunk=8,
                      block_size=8, prefix_cache=True, spec=spec, draft_k=3,
                      metrics=metrics_mod.MetricsRegistry(event_log=None))
    rng = np.random.RandomState(5)
    shared = rng.randint(1, cfg.vocab_size, size=16).tolist()
    for n, new in ((3, 6), (11, 9), (20, 4), (5, 12)):
        eng.submit(Request(
            prompt=shared + rng.randint(1, 5, size=n).tolist(),
            max_new_tokens=new))
    steps = 0
    while eng.pending():
        eng.step()
        steps += 1
        assert [eng._row_length(s) for s in eng._slots] == \
            np.asarray(eng.pcache.length).tolist()
    assert steps > 8 and all(r.status == "OK" for r in eng.results.values())
