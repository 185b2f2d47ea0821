"""models/shortconv_moe.py against the benchmark's plain reference
(benchmark/reference/lfm2.py, the one copy), at a tiny size on the CPU with
seeded weights: the whole forward, prefill in chunks of several widths
followed by decoding through the cache, the router's bias, and bfloat16.  The
rules of its second kind of state: ``test_shortconv_moe_paged.py``; behind
``ServeEngine``: ``test_shortconv_serving.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from toy_shortconv_moe import (ATOL, SEED, TINY, _serve_by_hand, fam, ref,
                               reference_logits, tiny, tokens)

from horovod_tpu.models import latent_moe
from horovod_tpu.models import paged
from horovod_tpu.models import shortconv_moe as sm


def test_paged_model_answers_for_the_config():
    _, mc, _ = tiny()
    assert paged.paged_model(mc) is sm
    with pytest.raises(TypeError, match="a LlamaConfig, a LatentMoEConfig, a"
                                        " ShortConvMoEConfig, a"):
        paged.paged_model(object())
    for fn in (lambda: sm.param_partition_specs(mc),
               lambda: sm.paged_cache_partition_specs(),
               lambda: sm.tp_split_dims(mc)):
        with pytest.raises(NotImplementedError, match="tensor-parallel"):
            fn()


def test_forward_equals_the_reference():
    cfg, mc, params = tiny()
    seq = tokens(40, seed=1)
    got = np.asarray(sm.forward(params, jnp.asarray([seq]), mc)[0])
    np.testing.assert_allclose(got, reference_logits(cfg, seq), atol=ATOL,
                               rtol=0)


@pytest.mark.parametrize("chunk, bs, n_prompt, n", [
    (1, 4, 11, 19), (2, 4, 19, 27), (3, 4, 19, 31), (256, 256, 300, 308)])
def test_chunked_prefill_then_decode_through_the_cache_equals_the_reference(
        chunk, bs, n_prompt, n):
    """Whatever the chunks' width, and whether or not they end on a block's
    end, the carried state makes the logits the reference's full pass."""
    cfg, mc, params = tiny(max_len=1024)
    seq = tokens(n, seed=2)
    got, pc = _serve_by_hand(mc, params, seq, n_prompt, chunk, bs)
    np.testing.assert_allclose(got, reference_logits(cfg, seq), atol=ATOL,
                               rtol=0)
    assert int(pc.length[1]) == n and int(pc.length[0]) == 0
    c = sm.read_counters(np.asarray(pc.stats))
    # the idle row and the chunks' padding counted for nothing
    assert c["choices_total"] == n * mc.top_k * 5
    assert c["snapshots_written"] == n // bs
    assert c["keys_visible"] == 2 * n * (n + 1) // 2
    assert sum(c["held_load"]) == c["choices_total"]


def test_the_routers_bias_moves_choices_and_not_weights():
    cfg, mc, params = tiny()
    lp = params["layers"][2]
    h = jnp.asarray(np.random.default_rng(9).standard_normal((64, 32)),
                    jnp.float32)
    experts, weights = latent_moe.route(mc, lp, h)
    flat = dict(lp, router_bias=jnp.zeros_like(lp["router_bias"]))
    experts0, weights0 = latent_moe.route(mc, flat, h)
    assert (np.sort(experts, -1) != np.sort(experts0, -1)).any()
    same = (np.sort(experts, -1) == np.sort(experts0, -1)).all(-1)
    assert same.any()
    np.testing.assert_array_equal(
        np.sort(np.asarray(weights)[same], -1),
        np.sort(np.asarray(weights0)[same], -1))
    # the weights are the unbiased scores over their sum plus 1e-6
    s = np.asarray(jax.nn.sigmoid(h @ lp["w_router"]))
    picked = np.take_along_axis(s, np.asarray(experts), -1)
    np.testing.assert_allclose(
        weights, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    ref_e, ref_w = ref.route(ref._dims(cfg), h, lp, "float32")
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(ref_e))
    np.testing.assert_allclose(weights, ref_w, rtol=1e-5)


def test_route_norm_eps_defaults_to_nothing_for_the_latent_model():
    """``latent_moe.route`` gained the field for this model; at its default
    the other model's weights are the quotient they were."""
    mc = latent_moe.latent_moe_tiny()
    assert mc.route_norm_eps == 0.0
    lp = latent_moe.init_params(mc, jax.random.key(0))["layers"][1]
    h = jnp.asarray(np.random.default_rng(1).standard_normal((16, 32)),
                    jnp.float32)
    experts, weights = latent_moe.route(mc, lp, h)
    s = jax.nn.sigmoid(h @ lp["w_router"])
    picked = jnp.take_along_axis(s, experts, -1)
    np.testing.assert_array_equal(
        np.asarray(weights),
        np.asarray(picked / jnp.sum(picked, -1, keepdims=True)))


def test_bfloat16_stays_near_float32():
    cfg = dict(TINY, torch_dtype="bfloat16")
    mc, params = fam.model_config(cfg, 64), fam.make_params(cfg, SEED)
    seq = tokens(32, seed=11)
    got = np.asarray(sm.forward(params, jnp.asarray([seq]), mc)[0])
    want = reference_logits(cfg, seq)
    # a router's near-tie that bfloat16 flips moves a position by an expert
    # (a quarter of a layer at this size): most positions stay close
    close = np.abs(got - want).max(-1) < 0.15
    assert close.mean() > 0.8, close
