"""Programs of the serving path compiled at real widths for a described TPU
v5e, with no chip: what interpret-mode and CPU tests cannot see.  The
topology is described inside a fixture (never while a module is imported), in
this one file, and the tests skip where it cannot be described."""

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.models import shortconv_moe as sm


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """Such a compile is written to the persistent cache and cannot be read
    back without a chip: keep it off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _avals(tree, sharding):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


@pytest.mark.parametrize("program", ["tick", "chunk"])
def test_shortconv_programs_hold_no_second_pool(one_chip, no_compile_cache,
                                                program):
    """The cell's own size: 128 slots of 2048 over 1,041 blocks of 256.  With
    key rows of 64 lanes every attention layer's scatter copied the pool
    twice (5.8 GB of scratch); packed to 128 (``kv_pack``) a program's
    scratch is a fraction of one pool."""
    cfg = sm.ShortConvMoEConfig()
    n_slots = 128
    assert cfg.kv_pack == 2
    params = _avals(jax.eval_shape(
        lambda: sm.init_params(cfg, jax.random.key(0))), one_chip)
    cache = _avals(jax.eval_shape(lambda: sm.init_paged_cache(
        cfg, n_slots, 2048, block_size=256, n_blocks=1041)), one_chip)
    logits = jax.ShapeDtypeStruct((n_slots, cfg.vocab_size), jnp.float32,
                                  sharding=one_chip)
    i32 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    @partial(jax.jit, donate_argnums=(1, 2))
    def tick(params, pcache, last_logits, active):
        tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        out, pcache = sm.decode_chunk_paged(params, tok[:, None], cfg, pcache,
                                            advance=active)
        return tok, out[:, 0], pcache

    @partial(jax.jit, donate_argnums=(1, 2))
    def chunk(params, pcache, last_logits, toks, slot, new_len, sel):
        out, pcache = sm.decode_chunk_paged_row(params, toks, cfg, pcache,
                                                slot, new_length=new_len)
        return pcache, last_logits.at[slot].set(out[0, sel])

    if program == "tick":
        args = (params, cache, logits, jax.ShapeDtypeStruct(
            (n_slots,), jnp.int32, sharding=one_chip))
        compiled = tick.lower(*args).compile()
    else:
        args = (params, cache, logits, jax.ShapeDtypeStruct(
            (1, 256), jnp.int32, sharding=one_chip), i32, i32, i32)
        compiled = chunk.lower(*args).compile()
    mem = compiled.memory_analysis()
    pool = cache.k.size * cache.k.dtype.itemsize
    assert pool == 818_675_712
    assert mem.temp_size_in_bytes < pool // 2, mem.temp_size_in_bytes
    # weights and cache are arguments held once, the cache aliased in place
    assert mem.alias_size_in_bytes >= 2 * pool
    assert mem.argument_size_in_bytes < 11.2e9
