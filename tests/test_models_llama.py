"""Llama correctness (shapes, training step, attention and SP parity, the KV
cache, chunked prefill, TP, sampling, remat policies)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import llama


def test_llama_forward_shapes_and_loss():
    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits = llama.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    loss = llama.loss_fn(params, (tokens, tokens), cfg)
    assert np.isfinite(float(loss))
    # param count formula matches actual tree
    actual = sum(p.size for p in jax.tree.leaves(params))
    assert actual == llama.num_params(cfg)


def test_llama_trains():
    """A few SGD steps reduce loss on a fixed batch (convergence smoke —
    the MNIST-example analogue for the flagship)."""
    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
    batch = (tokens[:, :-1], tokens[:, 1:])
    tx = optax.adam(1e-2)
    st = tx.init(params)
    lf = llama.make_loss_fn(cfg)

    @jax.jit
    def step(params, st):
        loss, g = jax.value_and_grad(lf)(params, batch)
        updates, st = tx.update(g, st, params)
        return optax.apply_updates(params, updates), st, loss

    first = None
    for i in range(20):
        params, st, loss = step(params, st)
        if first is None:
            first = float(loss)
    assert float(loss) < first * 0.8


@pytest.mark.parametrize("impl", ["blockwise", "flash"])
def test_llama_attn_impls_match_dense(impl):
    cfg_d = llama.llama_tiny(dtype=jnp.float32, attn_impl="dense")
    cfg_x = llama.llama_tiny(dtype=jnp.float32, attn_impl=impl,
                             attn_block_size=8)
    params = llama.init_params(cfg_d, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, cfg_d.vocab_size)
    ref = llama.forward(params, tokens, cfg_d)
    out = llama.forward(params, tokens, cfg_x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_llama_ring_sp_matches_dense():
    """Sequence-parallel Llama (ring attention over the mesh) == dense.

    Each shard holds L/8 tokens; positions_offset differs per rank."""
    cfg_d = llama.llama_tiny(dtype=jnp.float32, attn_impl="dense")
    cfg_r = llama.llama_tiny(dtype=jnp.float32, attn_impl="ring")
    params = llama.init_params(cfg_d, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg_d.vocab_size)
    ref = llama.forward(params, tokens, cfg_d)

    lc = 64 // 8

    def shard_fwd(params, tokens):
        r = jax.lax.axis_index("hvd")
        return llama.forward(params, tokens, cfg_r,
                             positions_offset=r * lc, sp_axis="hvd")

    f = jax.jit(
        jax.shard_map(
            shard_fwd, mesh=hvd.mesh(),
            in_specs=(P(), P(None, "hvd")),
            out_specs=P(None, "hvd"),
            check_vma=False,
        )
    )
    out = f(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_llama_ulysses_flash_sp_matches_dense():
    """Sequence-parallel Llama via all-to-all + the pallas flash kernel as
    the local engine (attn_impl='ulysses_flash') == dense."""
    cfg_u = llama.llama_tiny(dtype=jnp.float32, attn_impl="ulysses_flash",
                             n_heads=8, n_kv_heads=8)
    cfg_d = llama.llama_tiny(dtype=jnp.float32, attn_impl="dense",
                             n_heads=8, n_kv_heads=8)
    params = llama.init_params(cfg_d, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                cfg_d.vocab_size)
    ref = llama.forward(params, tokens, cfg_d)
    lc = 64 // 8

    def shard_fwd(params, tokens):
        r = jax.lax.axis_index("hvd")
        return llama.forward(params, tokens, cfg_u,
                             positions_offset=r * lc, sp_axis="hvd")

    f = jax.jit(
        jax.shard_map(
            shard_fwd, mesh=hvd.mesh(),
            in_specs=(P(), P(None, "hvd")),
            out_specs=P(None, "hvd"),
            check_vma=False,
        )
    )
    out = f(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_llama_kv_cache_decode_matches_forward():
    """Cached autoregressive decode == recomputing the full forward at
    every step (greedy tokens identical, logits close)."""
    from horovod_tpu.models import llama

    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(3))
    prompt = jnp.array([[5, 17, 42], [7, 7, 9]], jnp.int32)
    n_new = 5

    out = jax.jit(
        lambda p, t: llama.generate(p, t, cfg, max_new_tokens=n_new)
    )(params, prompt)
    assert out.shape == (2, n_new)

    # oracle: re-run the whole (uncached) forward per step, argmax last pos
    toks = prompt
    for _ in range(n_new):
        logits = llama.forward(params, toks, cfg)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(toks[:, 3:]))


def test_llama_prefill_logits_match_forward():
    from horovod_tpu.models import llama

    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(4))
    tokens = jnp.array([[1, 2, 3, 4]], jnp.int32)
    cache = llama.init_cache(cfg, 1, 8)
    logits, cache = llama.prefill(params, tokens, cfg, cache)
    full = llama.forward(params, tokens, cfg)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full[:, -1]), atol=2e-5
    )
    assert int(cache.length) == 4


def test_llama_ragged_generate_matches_per_row():
    """Ragged right-padded prompts with prompt_lengths= — each row's
    continuation equals generating that row alone, unpadded (the
    continuous-batching primitive: per-row cache positions)."""
    from horovod_tpu.models import llama

    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(7))
    rows = [[5, 17, 42, 9, 3], [7, 7, 9, 0, 0]]      # lengths 5 and 3
    lengths = jnp.array([5, 3], jnp.int32)
    prompt = jnp.array(rows, jnp.int32)
    n_new = 4

    out = jax.jit(lambda p, t, ln: llama.generate(
        p, t, cfg, max_new_tokens=n_new, max_len=16, prompt_lengths=ln,
    ))(params, prompt, lengths)
    assert out.shape == (2, n_new)

    for r, ln in enumerate([5, 3]):
        solo = llama.generate(
            params, jnp.array([rows[r][:ln]], jnp.int32), cfg,
            max_new_tokens=n_new, max_len=16,
        )
        np.testing.assert_array_equal(np.asarray(out[r]),
                                      np.asarray(solo[0]))


def test_llama_decode_chunk_matches_sequential():
    """decode_chunk(T tokens) == T sequential decode_steps — logits,
    cache contents, and lengths — on lockstep and ragged caches."""
    from horovod_tpu.models import llama

    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(6))
    prompt = jnp.array([[5, 17, 42], [7, 9, 3]], jnp.int32)
    toks = jnp.array([[1, 2, 3, 4], [9, 8, 7, 6]], jnp.int32)
    for lengths in (None, jnp.array([3, 2], jnp.int32)):
        c1 = llama.init_cache(cfg, 2, 16)
        _, c1 = llama.prefill(params, prompt, cfg, c1, lengths=lengths)
        c2 = jax.tree.map(lambda x: x, c1)
        seq = []
        for j in range(4):
            lg, c1 = llama.decode_step(params, toks[:, j], cfg, c1)
            seq.append(lg)
        chunk, c2 = llama.decode_chunk(params, toks, cfg, c2)
        np.testing.assert_allclose(np.asarray(chunk),
                                   np.asarray(jnp.stack(seq, 1)),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(np.asarray(c1.length),
                                      np.asarray(c2.length))
        np.testing.assert_allclose(np.asarray(c1.k), np.asarray(c2.k),
                                   atol=2e-5)


def test_llama_prefill_chunked_matches_prefill():
    """Windowed prefill == one-shot prefill (lockstep and ragged): same
    last-valid logits, the cache decodes identically, and a lockstep
    cache keeps its scalar length (the decode fast path)."""
    from horovod_tpu.models import llama

    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(8))
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 8), 0,
                                cfg.vocab_size)
    for lengths in (None, jnp.array([7, 3], jnp.int32)):
        c1 = llama.init_cache(cfg, 2, 16)
        lg1, c1 = llama.prefill(params, tokens, cfg, c1, lengths=lengths)
        c2 = llama.init_cache(cfg, 2, 16)
        lg2, c2 = jax.jit(
            lambda p, t, c: llama.prefill_chunked(
                p, t, cfg, c, window=4, lengths=lengths)
        )(params, tokens, c2)
        np.testing.assert_allclose(np.asarray(lg2), np.asarray(lg1),
                                   rtol=2e-5, atol=2e-5)
        if lengths is None:
            assert jnp.ndim(c2.length) == 0      # fast path preserved
        np.testing.assert_array_equal(
            np.broadcast_to(np.asarray(c1.length), (2,)),
            np.broadcast_to(np.asarray(c2.length), (2,)))
        nxt = jnp.argmax(lg1, -1).astype(jnp.int32)
        d1, _ = llama.decode_step(params, nxt, cfg, c1)
        d2, _ = llama.decode_step(params, nxt, cfg, c2)
        np.testing.assert_allclose(np.asarray(d2), np.asarray(d1),
                                   rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="window"):
        llama.prefill_chunked(params, tokens, cfg,
                              llama.init_cache(cfg, 2, 16), window=3)
    with pytest.raises(ValueError, match="overflow"):
        # decode_chunk's scatter would silently drop out-of-bounds
        # writes; the capacity check fails loudly instead
        llama.prefill_chunked(params, tokens, cfg,
                              llama.init_cache(cfg, 2, 4), window=4)


def test_llama_tp_partition_specs_compile():
    """GSPMD tensor parallelism: jit with megatron specs over a (dp, tp)
    mesh compiles and matches the unsharded forward."""
    from horovod_tpu.parallel import make_mesh
    from jax.sharding import NamedSharding

    cfg = llama.llama_tiny(dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    ref = llama.forward(params, tokens, cfg)

    mesh = make_mesh(dp=2, tp=4)
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 4
    specs = llama.param_partition_specs(cfg, tp_axis="tp")
    sharded = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs,
        is_leaf=lambda x: isinstance(x, jax.Array),
    )
    with jax.sharding.set_mesh(mesh):
        out = jax.jit(lambda p, t: llama.forward(p, t, cfg))(sharded, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_llama_generate_with_tp_sharded_params():
    """KV-cache prefill logits under GSPMD with megatron column/row-sharded
    weights match the replicated run within float tolerance (TP changes
    psum reduction order), and generate runs end to end on the sharded
    weights — tensor-parallel inference needs no decode-specific code."""
    from jax.sharding import NamedSharding
    from horovod_tpu.parallel.mesh import make_mesh

    cfg = llama.llama_tiny(dtype=jnp.float32, n_heads=4, n_kv_heads=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jnp.array([[3, 1, 4, 1, 5]], jnp.int32)

    mesh = make_mesh(tp=4, dp=2)
    specs = llama.param_partition_specs(cfg, tp_axis="tp")
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
    sharded = jax.tree.map(jax.device_put, params, shardings)

    # Logits comparison with tolerance (greedy argmax on near-ties is not
    # a guaranteed-stable property across reduction orders).
    def prefill_logits(p, t):
        cache = llama.init_cache(cfg, t.shape[0], 16)
        logits, _ = llama.prefill(p, t, cfg, cache)
        return logits

    ref = jax.jit(prefill_logits)(params, prompt)
    out = jax.jit(prefill_logits)(sharded, prompt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)

    # And the full cached decode executes on sharded weights.
    toks = jax.jit(
        lambda p, t: llama.generate(p, t, cfg, max_new_tokens=4)
    )(sharded, prompt)
    assert toks.shape == (1, 4)
    t = np.asarray(toks)
    assert ((t >= 0) & (t < cfg.vocab_size)).all(), t


def test_sample_logits_filters():
    """top-k / top-p nucleus filtering: samples only ever come from the
    allowed set; greedy and degenerate settings reduce to argmax."""
    from horovod_tpu.models.llama import sample_logits

    logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0, 4.0]])
    # greedy ignores filters
    assert int(sample_logits(logits, jax.random.key(0))[0]) == 4
    # top_k=1 at any temperature == argmax
    for s in range(5):
        t = sample_logits(logits, jax.random.key(s), temperature=2.0,
                          top_k=1)
        assert int(t[0]) == 4
    # tiny top_p keeps only the argmax
    for s in range(5):
        t = sample_logits(logits, jax.random.key(s), temperature=2.0,
                          top_p=1e-6)
        assert int(t[0]) == 4
    # top_k=2: only ids {3, 4} may appear over many draws, and both do
    draws = {
        int(sample_logits(logits, jax.random.key(s), temperature=5.0,
                          top_k=2)[0])
        for s in range(64)
    }
    assert draws == {3, 4}, draws
    # top_p just over the top token's mass admits exactly the top two
    p_top = float(jax.nn.softmax(logits)[0, 4])
    draws_p = {
        int(sample_logits(logits, jax.random.key(s), temperature=1.0,
                          top_p=p_top + 1e-4)[0])
        for s in range(64)
    }
    assert draws_p == {3, 4}, draws_p


def test_generate_with_sampling_runs():
    from horovod_tpu.models import llama

    cfg = llama.llama_tiny()
    params = llama.init_params(cfg, jax.random.key(0))
    prompt = jnp.zeros((2, 4), jnp.int32)
    toks = jax.jit(
        lambda p, t: llama.generate(
            p, t, cfg, max_new_tokens=3, temperature=0.8, top_k=50,
            top_p=0.9, key=jax.random.key(7),
        )
    )(params, prompt)
    t = np.asarray(toks)
    assert t.shape == (2, 3)
    assert ((t >= 0) & (t < cfg.vocab_size)).all(), t


@pytest.mark.parametrize("policy", [None, "dots_saveable",
                                    "dots_with_no_batch_dims_saveable"])
def test_llama_remat_policy_value_and_grads_unchanged(policy):
    """Remat policies trade memory for recompute; value AND gradients must
    be bit-comparable to the no-remat forward."""
    base = llama.llama_tiny(dtype=jnp.float32, remat=False)
    rp = llama.llama_tiny(dtype=jnp.float32, remat=True, remat_policy=policy)
    params = llama.init_params(base, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                base.vocab_size)
    batch = (tokens, tokens)

    l0, g0 = jax.value_and_grad(llama.make_loss_fn(base))(params, batch)
    l1, g1 = jax.value_and_grad(llama.make_loss_fn(rp))(params, batch)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_llama_unknown_remat_policy_raises():
    cfg = llama.llama_tiny(remat=True, remat_policy="not_a_policy")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                cfg.vocab_size)
    with pytest.raises(ValueError, match="remat_policy"):
        llama.forward(params, tokens, cfg)


def test_llama_remat_policy_without_remat_raises():
    cfg = llama.llama_tiny(remat=False, remat_policy="dots_saveable")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                cfg.vocab_size)
    with pytest.raises(ValueError, match="remat=False"):
        llama.forward(params, tokens, cfg)


def test_llama_policy_factory_names_rejected():
    """jax.checkpoint_policies factories (argument-taking) are real
    attributes but NOT policies; the allowlist must reject them."""
    cfg = llama.llama_tiny(remat=True,
                           remat_policy="save_only_these_names")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                cfg.vocab_size)
    with pytest.raises(ValueError, match="remat_policy"):
        llama.forward(params, tokens, cfg)
