"""The paged KV pool is written in place: ``_paged_attend`` carries it
through the layer scan instead of scanning it in and stacking it out.

Three properties, each at toy size on the CPU:

1. the three paged entry points give logits and pool contents BIT-equal to
   a test-local copy of the old scanned-in, stacked-out body, over idle rows
   writing to the trash block, a row at length 0, a chunk that crosses a
   block boundary, a chunk that overflows its table, and a padded final
   prefill window;
2. the jaxpr has the pool among the scan's carries and neither among its
   scanned inputs nor its stacked outputs;
3. a toy engine's compiled tick and chunk hold less scratch than one pool.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu import metrics as metrics_mod
from horovod_tpu.models import llama
from horovod_tpu.serving_scheduler import ServeEngine

N_SLOTS, MAX_LEN, BLOCK, N_BLOCKS = 4, 32, 8, 12


def _paged_attend_scanned(params, tokens, cfg, kv_k, kv_v, qpos, wflat, gflat):
    """The body as it was before the pool became a carry: ``kv_k`` / ``kv_v``
    scanned in layer by layer, each written slice stacked out."""
    b, t = tokens.shape
    nl, n_blocks, bs, kvh, dh = kv_k.shape
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


def _setup(dtype):
    """A pool full of noise (so a wrong gather or scatter shows) and four
    rows: 0 mid-block at 13, 1 free (table all trash, idle), 2 admitted at
    length 0, 3 two short of the end of its table."""
    cfg = llama.llama_tiny(dtype=dtype, param_dtype=dtype, n_layers=3)
    params = llama.init_params(cfg, jax.random.key(0))
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


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(CASES))
def test_bit_equal_to_scanned_body(case, dtype, monkeypatch):
    cfg, params, pc = _setup(dtype)
    run = CASES[case]
    got = jax.jit(lambda p, c: run(cfg, p, c))(params, pc)
    monkeypatch.setattr(llama, "_paged_attend", _paged_attend_scanned)
    want = jax.jit(lambda p, c: run(cfg, p, c))(params, pc)
    flat_got, tree_got = jax.tree.flatten(got)
    flat_want, tree_want = jax.tree.flatten(want)
    assert tree_got == tree_want
    for g, w in zip(flat_got, flat_want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))
    # and the case did write: the pool differs from what went in
    assert not np.array_equal(np.asarray(got[1].k, np.float32),
                              np.asarray(pc.k, np.float32))


def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


@pytest.mark.parametrize("case", ["tick-idle_rows_to_trash-row_at_0",
                                  "row-crosses_block",
                                  "spec_verify-idle_and_padded_drafts"])
def test_pool_is_a_scan_carry(case):
    cfg, params, pc = _setup(jnp.float32)
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
