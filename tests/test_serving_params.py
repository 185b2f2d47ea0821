"""The serving tree (``llama.serving_params``): ``wq`` / ``wk`` / ``wv`` side
by side in one ``wqkv``, laid out once where an engine is built.  The paged
programs give on it what the public tree's three products gave, an engine
built from
the public tree serves the tokens ``llama.generate`` computes on that tree
(through ``prefill`` and ``decode_step``, which never see the serving tree),
the caller's tree is the caller's, and ``serve.params_relaid_bytes`` reads
what was written."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics as metrics_mod
from horovod_tpu import profiler
from horovod_tpu.models import llama, shortconv_moe
from horovod_tpu.serving import OK, Request
from horovod_tpu.serving_scheduler import ServeEngine
from horovod_tpu.supervisor import clone_engine

CHUNK, MAX_LEN = 8, 48


@pytest.fixture(scope="module")
def world():
    # four KV heads, so that the heads split two and four ways
    cfg = llama.llama_tiny(dtype=jnp.float32, n_kv_heads=4)
    return cfg, llama.init_params(cfg, jax.random.key(42))


def _engine(cfg, params, n_slots=3, **kw):
    kw.setdefault("metrics", metrics_mod.MetricsRegistry(event_log=None))
    return ServeEngine(params, cfg, n_slots=n_slots, max_len=MAX_LEN,
                       chunk=CHUNK, monitor=False, sampler=False, **kw)


def _solo(params, cfg, req):
    return np.asarray(llama.generate(
        params, jnp.asarray([req.prompt], jnp.int32), cfg,
        max_new_tokens=req.max_new_tokens, max_len=MAX_LEN))[0].tolist()


def _gauge(eng):
    return eng.metrics.snapshot()["gauges"]["serve.params_relaid_bytes"]


# -- the tree ----------------------------------------------------------------


@pytest.mark.parametrize("tp", [1, 2, 4])
def test_wqkv_holds_each_shard_s_columns_side_by_side(world, tp):
    """``wqkv[:, :, j]`` is ``[q_j | k_j | v_j]``: the columns of the heads
    that shard ``j`` of ``tp`` computes, in the public tree's order."""
    cfg, params = world
    tree = llama.serving_params(params, cfg, tp_size=tp)
    pub, layers = params["layers"], tree["layers"]
    n = (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim
    assert layers["wqkv"].shape == (cfg.n_layers, cfg.dim, tp, n // tp)
    assert layers["wqkv"].dtype == pub["wq"].dtype
    assert not {"wq", "wk", "wv"} & set(layers)
    for j in range(tp):
        want = jnp.concatenate([jnp.split(pub[k], tp, axis=-1)[j]
                                for k in ("wq", "wk", "wv")], axis=-1)
        np.testing.assert_array_equal(layers["wqkv"][:, :, j], want)


def test_every_other_leaf_is_the_caller_s_and_the_caller_s_tree_is_untouched(
        world):
    cfg, params = world
    before = jax.tree.map(lambda x: x, params)         # the same leaves
    names = sorted(params["layers"])
    tree = llama.serving_params(params, cfg)
    assert sorted(params["layers"]) == names
    assert all(a is b for a, b in zip(jax.tree.leaves(params),
                                      jax.tree.leaves(before)))
    assert tree is not params and tree["layers"] is not params["layers"]
    for k in ("embed", "final_norm", "lm_head"):
        assert tree[k] is params[k]
    for k in set(names) - {"wq", "wk", "wv"}:
        assert tree["layers"][k] is params["layers"][k]
    # each call lays out a tree of its own
    again = llama.serving_params(params, cfg)
    assert again["layers"]["wqkv"] is not tree["layers"]["wqkv"]


def test_a_serving_tree_is_returned_as_it_is_and_only_for_its_own_tp(world):
    cfg, params = world
    tree = llama.serving_params(params, cfg, tp_size=2)
    assert llama.serving_params(tree, cfg, tp_size=2) is tree
    with pytest.raises(ValueError, match="tp_size=2 cannot serve tp_size=1"):
        llama.serving_params(tree, cfg, tp_size=1)


def test_the_serving_tree_s_partition_specs_name_its_leaves(world):
    cfg, params = world
    tree = llama.serving_params(params, cfg, tp_size=2)
    specs = llama.serving_partition_specs(cfg, tp_axis="tp")
    is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)  # noqa: E731
    assert (jax.tree.structure(specs, is_leaf=is_spec)
            == jax.tree.structure(tree))
    assert specs["layers"]["wqkv"] == jax.sharding.PartitionSpec(
        None, None, "tp", None)
    public = llama.param_partition_specs(cfg, tp_axis="tp")
    assert specs["layers"]["wo"] == public["layers"]["wo"]


# -- the programs ------------------------------------------------------------


def _cache(cfg, n_slots=3, lengths=(11, 0, 5)):
    """A pool whose rows hold ``lengths`` positions of noise."""
    pc = llama.init_paged_cache(cfg, n_slots, MAX_LEN, block_size=CHUNK)
    per = MAX_LEN // CHUNK
    table = 1 + np.arange(n_slots * per, dtype=np.int32).reshape(n_slots, per)
    k1, k2 = jax.random.split(jax.random.key(3))
    return pc._replace(
        k=jax.random.normal(k1, pc.k.shape, pc.k.dtype),
        v=jax.random.normal(k2, pc.v.shape, pc.v.dtype),
        block_table=jnp.asarray(table),
        length=jnp.asarray(lengths, jnp.int32))


def _tick(params, cfg, pc):
    toks = jnp.asarray([[3], [4], [5]], jnp.int32)
    return llama.decode_chunk_paged(params, toks, cfg, pc,
                                    advance=jnp.asarray([1, 0, 1]))


def _chunk_row(params, cfg, pc):
    toks = jnp.arange(1, CHUNK + 1, dtype=jnp.int32)[None]
    return llama.decode_chunk_paged_row(params, toks, cfg, pc, 1,
                                        new_length=CHUNK - 2)


def _chunk_rows(params, cfg, pc):
    toks = jnp.arange(2 * CHUNK, dtype=jnp.int32).reshape(2, CHUNK) % 50
    return llama.decode_chunk_paged_rows(
        params, toks, cfg, pc, jnp.asarray([2, 1]),
        new_length=jnp.asarray([5 + CHUNK, CHUNK]), sel=jnp.asarray([7, 3]))


def _verify(params, cfg, pc):
    last = jax.random.normal(jax.random.key(9), (3, cfg.vocab_size))
    drafts = jnp.asarray([[7, 8], [1, -1], [2, 3]], jnp.int32)
    tok, accept, nxt, pc = llama.spec_verify_paged(
        params, cfg, pc, last, drafts, jnp.asarray([1, 0, 1]))
    return (nxt, tok, accept), pc


def _three_products(h, lp, cfg):
    """The projection as it was before the serving tree: the public tree's
    three products, each reshaped into heads."""
    b, t, _ = h.shape
    q, k, v = (h @ lp[name].astype(cfg.dtype) for name in ("wq", "wk", "wv"))
    return (q.reshape(b, t, cfg.n_heads, cfg.head_dim),
            k.reshape(b, t, cfg.n_kv_heads, cfg.head_dim),
            v.reshape(b, t, cfg.n_kv_heads, cfg.head_dim))


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("program", [_tick, _chunk_row, _chunk_rows, _verify],
                         ids=["tick", "chunk_row", "chunk_rows", "verify"])
def test_a_paged_program_gives_what_the_public_tree_s_three_products_gave(
        world, program, tp, monkeypatch):
    """Every paged entry, on a tree laid out for one shard and for two: the
    logits, pools and lengths of the same entry with the public tree's three
    products in the one product's place (each output column is the same dot
    product over ``D``)."""
    cfg, params = world
    pc = _cache(cfg)
    got, got_pc = program(llama.serving_params(params, cfg, tp_size=tp),
                          cfg, pc)
    monkeypatch.setattr(llama, "_qkv_heads", _three_products)
    want, want_pc = program(params, cfg, pc)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    for a, b in zip(got_pc, want_pc):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_the_tick_on_the_serving_tree_is_decode_chunk_on_the_public_tree(
        world):
    """Against the dense cache's ``decode_chunk``, which reads ``wq`` /
    ``wk`` / ``wv`` and was not touched: a row's keys in a dense cache and
    the same keys behind a block table give the same logits."""
    cfg, params = world
    pc = _cache(cfg)
    per = MAX_LEN // CHUNK
    dense = llama.init_cache(cfg, 3, MAX_LEN)
    gather = lambda pool: pool[:, pc.block_table].reshape(  # noqa: E731
        cfg.n_layers, 3, per * CHUNK, cfg.n_kv_heads, cfg.head_dim)
    dense = dense._replace(k=gather(pc.k).astype(dense.k.dtype),
                           v=gather(pc.v).astype(dense.v.dtype),
                           length=pc.length)
    toks = jnp.asarray([[3], [4], [5]], jnp.int32)
    want, _ = llama.decode_chunk(params, toks, cfg, dense)
    got, _ = llama.decode_chunk_paged(
        llama.serving_params(params, cfg), toks, cfg, pc)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# -- the engine --------------------------------------------------------------


def _case_requests(case):
    rng = np.random.default_rng(7)
    prompt = lambda n: rng.integers(1, 200, n).tolist()     # noqa: E731
    if case == "multi_chunk":          # three windows of prefill
        return [Request(prompt=prompt(2 * CHUNK + 3), max_new_tokens=5)]
    if case == "wide_chunk":           # three rows prefill in one step
        return [Request(prompt=prompt(CHUNK - i), max_new_tokens=4)
                for i in range(3)]
    if case == "verify":               # a stream the drafter can guess
        return [Request(prompt=[5, 6, 7, 5, 6, 7, 5, 6], max_new_tokens=8),
                Request(prompt=prompt(5), max_new_tokens=6)]
    return [Request(prompt=prompt(4), max_new_tokens=6),    # tick, tp2
            Request(prompt=prompt(6), max_new_tokens=3)]


@pytest.mark.parametrize("case", ["tick", "multi_chunk", "wide_chunk",
                                  "verify", "tp2"])
def test_an_engine_built_from_the_public_tree_serves_generate_s_tokens(
        world, case):
    cfg, params = world
    eng = _engine(cfg, params,
                  **({"spec": True, "draft_k": 2} if case == "verify" else
                     {"tp_size": 2} if case == "tp2" else {}))
    assert "wqkv" in eng.params["layers"]
    assert eng.params["layers"]["wqkv"].shape[2] == eng.tp_size
    reqs = _case_requests(case)
    ids = [eng.submit(r) for r in reqs]
    if case == "wide_chunk":
        assert eng.chunk_widths == (3, 1)
        eng.step()
        row = eng.prof.log.rows()[-1]
        assert row[profiler.ROW_FIELDS.index("chunk_rows")] == 3
        assert row[profiler.ROW_FIELDS.index("chunks")] == 1
    eng.run([])
    for rid, req in zip(ids, reqs):
        res = eng.results[rid]
        assert res.status == OK
        assert list(res) == _solo(params, cfg, req)
    if case == "verify":
        assert eng.spec_counters["rounds"] > 0
    sizes = eng.compile_cache_sizes()
    assert sizes["chunk"] == 1 and sizes["set_row"] == 1


def test_the_engine_keeps_the_serving_tree_and_a_second_engine_its_own(world):
    """The engine holds ``wqkv`` and none of the three; the caller's tree
    still holds its own, untouched; a second engine over the same public tree
    lays out a tree of its own and shares the leaves that were not re-laid."""
    cfg, params = world
    leaves = jax.tree.leaves(params)
    one, two = _engine(cfg, params), _engine(cfg, params)
    for eng in (one, two):
        assert not {"wq", "wk", "wv"} & set(eng.params["layers"])
        assert eng.params["layers"]["wo"] is params["layers"]["wo"]
    assert one.params["layers"]["wqkv"] is not two.params["layers"]["wqkv"]
    assert sorted(params["layers"]) == [
        "attn_norm", "mlp_norm", "w_down", "w_gate", "w_up", "wk", "wo",
        "wq", "wv"]
    assert all(a is b for a, b in zip(jax.tree.leaves(params), leaves))
    held = sum(x.nbytes for x in jax.tree.leaves(one.params))
    assert held == sum(x.nbytes for x in leaves)        # and not 1.1 times


@pytest.mark.parametrize("tp", [1, 2])
def test_params_relaid_bytes_reads_what_was_written(world, tp):
    cfg, params = world
    eng = _engine(cfg, params, tp_size=tp)
    pub = params["layers"]
    assert _gauge(eng) == eng.params["layers"]["wqkv"].nbytes == sum(
        pub[k].nbytes for k in ("wq", "wk", "wv"))


def test_params_relaid_bytes_is_zero_for_a_model_that_lays_nothing_out():
    cfg = shortconv_moe.shortconv_moe_tiny()
    params = shortconv_moe.init_params(cfg, jax.random.key(0))
    assert not hasattr(shortconv_moe, "serving_params")
    eng = _engine(cfg, params, n_slots=2)
    assert _gauge(eng) == 0
    assert all(a is b for a, b in zip(jax.tree.leaves(eng.params),
                                      jax.tree.leaves(params)))


def test_a_clone_serves_its_original_s_tree_and_writes_nothing(world):
    """``clone_engine`` hands the engine's own tree on: it is already laid
    out, so the clone keeps it as it is, writes nothing, and leaves the
    gauge of the registry it shares as it stands."""
    cfg, params = world
    eng = _engine(cfg, params, tp_size=2)
    written = _gauge(eng)
    twin = clone_engine(eng)
    assert twin.params["layers"]["wqkv"] is eng.params["layers"]["wqkv"]
    assert twin.metrics is eng.metrics and _gauge(twin) == written > 0
    req = Request(prompt=[9, 8, 7, 6, 5], max_new_tokens=4)
    assert list(twin.run([req])[0]) == _solo(params, cfg, req)
