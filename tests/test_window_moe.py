"""models/window_moe.py against the benchmark's plain reference
(benchmark/reference/kexaone.py, the one copy), at a tiny size on the CPU with
seeded weights: the whole forward, prefill in chunks of several widths
followed by decoding through the cache, and the tie between the chip's share
and the uncut layer.  What a slot's ring and a block's snapshot hold:
``test_window_moe_paged.py``; behind ``ServeEngine``:
``test_window_moe_serving.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from toy_window_moe import (ATOL, N_MOE, SEED, TINY, _serve_by_hand, fam, ref,
                            reference_logits, tiny, tokens)

from horovod_tpu.models import latent_moe
from horovod_tpu.models import paged
from horovod_tpu.models import window_moe as wm


def test_paged_model_answers_for_the_config():
    _, mc, _ = tiny()
    assert paged.paged_model(mc) is wm
    assert paged.paged_model(wm.window_moe_tiny()) is wm
    with pytest.raises(TypeError, match="a WindowMoEConfig, a StateSpaceMoEConfig"):
        paged.paged_model(object())
    for fn in (lambda: wm.param_partition_specs(mc),
               lambda: wm.paged_cache_partition_specs(),
               lambda: wm.tp_split_dims(mc)):
        with pytest.raises(NotImplementedError, match="tensor-parallel"):
            fn()
    with pytest.raises(ValueError, match="pages the full layers"):
        wm.window_moe_tiny(layer_kinds=(wm.SLIDING,) * 3)
    with pytest.raises(ValueError, match="not within the router"):
        wm.window_moe_tiny(held_first=12)


def test_the_preset_and_init_params_have_the_tiny_trees_shapes():
    _, mc, params = tiny()
    assert wm.window_moe_tiny() == mc
    own = wm.init_params(mc, jax.random.key(0))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), own) == jax.tree.map(
        lambda a: (a.shape, a.dtype), params)


def test_forward_equals_the_reference():
    cfg, mc, params = tiny()
    seq = tokens(40, seed=1)
    got = np.asarray(wm.forward(params, jnp.asarray([seq]), mc)[0])
    np.testing.assert_allclose(got, reference_logits(cfg, seq), atol=ATOL,
                               rtol=0)


@pytest.mark.parametrize("chunk, bs, n_prompt, n", [
    (1, 4, 11, 19),         # every token a program of its own
    (4, 4, 19, 27),         # chunks narrower than the window of 6
    (6, 12, 19, 31),        # one query block a chunk, the window's width
    (16, 8, 37, 45),        # queries in blocks of 6, the last block padded
    (24, 24, 50, 58),       # whole blocks of 6, chunks that end on a block
    (512, 512, 520, 524)])  # blocks the full layers walk in two pieces
def test_chunked_prefill_then_decode_through_the_cache_equals_the_reference(
        chunk, bs, n_prompt, n):
    """Whatever the chunks' width, and whether or not they end on a block's
    end, the carried ring makes the logits the reference's full pass."""
    cfg, mc, params = tiny(max_len=2048)
    seq = tokens(n, seed=2)
    got, pc = _serve_by_hand(mc, params, seq, n_prompt, chunk, bs)
    np.testing.assert_allclose(got, reference_logits(cfg, seq), atol=ATOL,
                               rtol=0)
    assert int(pc.length[1]) == n and int(pc.length[0]) == 0
    assert wm.GATHER_ROWS == 256
    c = wm.read_counters(np.asarray(pc.stats))
    # the idle row and the chunks' padding counted for nothing
    assert c["choices_total"] == n * mc.top_k * N_MOE
    assert c["choices_held"] == sum(c["held_load"]) <= c["choices_total"]
    assert c["snapshots_written"] == n // bs
    w = mc.window
    assert c["keys_visible"] == sum(
        (p + 1) + 4 * min(p + 1, w) for p in range(n))
    assert c["tokens_live"] == n


def test_the_eight_shares_add_up_to_the_uncut_layer_and_head():
    """What ties the chip's share to the model: the routed parts of all 8
    shares (2 experts each of 16), with the shared expert counted once, are
    the uncut reference's expert layer; the 8 slices of the vocabulary give
    the uncut head's logits side by side."""
    uncut = dict(TINY, num_experts=16, num_experts_published=16)
    w_all = ref.layer_weights(uncut, ref.seed_arg(SEED), 1)
    h = jax.random.normal(jax.random.key(0), (11, 32), jnp.float32)
    whole, experts = ref.moe(ref._dims(uncut), h, w_all, "float32")
    total = ref._swiglu(h, w_all["s_gate"], w_all["s_up"], w_all["s_down"],
                        "float32")
    loads = []
    for share in range(8):
        cfg = dict(TINY, num_experts=2, held_experts_first=2 * share)
        mc = fam.model_config(cfg, 64)
        lp = ref.layer_weights(cfg, ref.seed_arg(SEED), 1)
        np.testing.assert_array_equal(
            np.asarray(lp["e_gate"]),
            np.asarray(w_all["e_gate"][2 * share:2 * share + 2]))
        part, load = latent_moe.held_experts(mc, lp, h, jnp.ones((11,), bool))
        total = total + part
        loads += [int(x) for x in load]
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5, rtol=0)
    assert loads == [int((np.asarray(experts) == e).sum()) for e in range(16)]
    assert sum(loads) == 11 * 2          # every choice computed somewhere

    seq = tokens(9, vocab=8, seed=3)     # ids every slice holds
    whole_vocab = reference_logits(dict(TINY, vocab_size=64), seq)
    full = ref.top_weights(dict(TINY, vocab_size=64), ref.seed_arg(SEED))
    parts = []
    for share in range(8):
        cfg, mc, params = tiny(vocab_size=8, vocab_first_row=8 * share)
        np.testing.assert_allclose(       # jitted or not: the last bit
            np.asarray(params["lm_head"]),
            np.asarray(full["lm_head"][:, 8 * share:8 * share + 8]),
            atol=1e-7, rtol=0)
        # the same inputs everywhere: the tokens' rows of the whole embedding
        params = dict(params, embed=full["embed"][:8])
        parts.append(np.asarray(wm.forward(
            params, jnp.asarray([seq], jnp.int32), mc)[0]))
    np.testing.assert_allclose(np.concatenate(parts, -1), whole_vocab,
                               atol=ATOL, rtol=0)
