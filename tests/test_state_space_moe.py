"""models/state_space_moe.py against the benchmark's plain reference
(benchmark/reference/granite.py) and against a second, independent recurrence
written here in numpy, at a tiny size on the CPU with seeded weights: the
mixer, the chunked form against the one-step form, the whole forward, the tie
between the chip's share and the uncut layer, and the second routing rule.
Prefill in chunks through the cache: ``test_state_space_moe_chunks.py``; what
a snapshot holds and ``set_row`` restores: ``test_state_space_moe_paged.py``;
behind ``ServeEngine``: ``test_state_space_serving.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from toy_state_space_moe import (ATOL, SEED, TINY, fam, ref, reference_logits,
                                 tiny, tokens)

from horovod_tpu.models import latent_moe
from horovod_tpu.models import paged
from horovod_tpu.models import state_space_moe as sm


def test_paged_model_answers_for_the_config():
    _, mc, _ = tiny()
    assert paged.paged_model(mc) is sm
    assert paged.paged_model(sm.state_space_moe_tiny()) is sm
    with pytest.raises(TypeError, match="a StateSpaceMoEConfig or a "):
        paged.paged_model(object())
    for fn in (lambda: sm.param_partition_specs(mc),
               lambda: sm.paged_cache_partition_specs(),
               lambda: sm.tp_split_dims(mc)):
        with pytest.raises(NotImplementedError, match="tensor-parallel"):
            fn()
    with pytest.raises(ValueError, match="one of each"):
        sm.state_space_moe_tiny(layer_kinds=(sm.SSM,) * 3)
    with pytest.raises(ValueError, match="not within the router"):
        sm.state_space_moe_tiny(held_first=6)
    with pytest.raises(ValueError, match="at least one entry"):
        sm.state_space_moe_tiny(snapshots=0)


def test_init_params_has_the_reference_trees_shapes():
    _, mc, params = tiny()
    own = sm.init_params(mc, jax.random.key(0))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), own) == jax.tree.map(
        lambda a: (a.shape, a.dtype), params)
    preset = sm.state_space_moe_tiny()
    assert (preset.n_of(sm.SSM), preset.n_of(sm.ATTN)) == (3, 1)


# ---------------------------------------------------------------------------
# a second reference: the mixer's recurrence in numpy, token by token
# ---------------------------------------------------------------------------

def _numpy_mixer(cfg, w, u):
    """The state-space mixer over ``u`` [T, d] as the issue writes it down,
    in float64 numpy, sharing no code with the program or the benchmark's
    reference."""
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    h, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner, taps = h * p, cfg["mamba_d_conv"]
    conv = inner + 2 * n
    zxd = np.asarray(u, np.float64) @ w["w_in"]
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:inner + conv], \
        zxd[:, inner + conv:]
    padded = np.concatenate([np.zeros((taps - 1, conv)), xbc])
    s = np.zeros((h, p, n))
    out = []
    for t in range(u.shape[0]):
        pre = w["conv_b"] + sum(w["conv_w"][:, j] * padded[t + j]
                                for j in range(taps))
        act = pre / (1.0 + np.exp(-pre))
        x = act[:inner].reshape(h, p)
        b, c = act[inner:inner + n], act[inner + n:]
        step = np.log1p(np.exp(dt[t] + w["dt_bias"]))
        s = np.exp(step * -np.exp(w["A_log"]))[:, None, None] * s \
            + (step[:, None] * x)[:, :, None] * b[None, None, :]
        y = (s @ c + w["D"][:, None] * x).reshape(inner)
        y = y * (z[t] / (1.0 + np.exp(-z[t])))
        y = y / np.sqrt(np.mean(y * y) + cfg["rms_norm_eps"]) * w["gate_norm"]
        out.append(y @ w["w_out"])
    return np.stack(out), s


def test_both_references_and_the_program_compute_the_same_mixer():
    cfg, mc, params = tiny()
    w = ref.layer_weights(cfg, ref.seed_arg(SEED), 0)
    u = jax.random.normal(jax.random.key(1), (13, 32), jnp.float32)
    want, want_state = _numpy_mixer(cfg, w, np.asarray(u))
    got_ref, (state_ref, _) = ref.mixer(ref._dims(cfg), u, w, "float32")
    np.testing.assert_allclose(np.asarray(got_ref), want, atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(state_ref), want_state, atol=1e-5,
                               rtol=0)
    zeros = (jnp.zeros((1, 8, 8, 8)), jnp.zeros((1, 3, 80)))
    got, kept, state = sm._mixer(mc, w, u[None], *zeros,
                                 jnp.ones((1, 13), bool))
    np.testing.assert_allclose(np.asarray(got[0]), want, atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(state[0]), want_state, atol=1e-5,
                               rtol=0)
    # the closed form over all 13 tokens, and over the first 6 of them
    after, carry = sm.advance_state(w, zeros[0], kept, jnp.asarray([13]))
    np.testing.assert_allclose(np.asarray(after[0]), want_state, atol=1e-5,
                               rtol=0)
    _, state6 = _numpy_mixer(cfg, w, np.asarray(u[:6]))
    after6, carry6 = sm.advance_state(w, zeros[0], kept, jnp.asarray([6]))
    np.testing.assert_allclose(np.asarray(after6[0]), state6, atol=1e-5,
                               rtol=0)
    np.testing.assert_array_equal(np.asarray(carry6[0]),
                                  np.asarray(kept.xbc[0, 6:9]))
    np.testing.assert_array_equal(np.asarray(carry[0]),
                                  np.asarray(kept.xbc[0, 13:16]))


@pytest.mark.parametrize("piece", [1, 3, 4, 13, 256])
def test_the_chunked_form_equals_the_one_step_form(piece):
    """Whatever the piece (shorter than, equal to, longer than the tokens),
    the chunked outputs and final state are those of ticking the recurrence
    a token at a time from the same state."""
    cfg, mc, _ = tiny(mamba_chunk_size=piece)
    w = ref.layer_weights(cfg, ref.seed_arg(SEED), 1)
    u = jax.random.normal(jax.random.key(2), (2, 13, 32), jnp.float32)
    state = jax.random.normal(jax.random.key(3), (2, 8, 8, 8))
    conv = jax.random.normal(jax.random.key(4), (2, 3, 80))
    valid = jnp.ones((2, 13), bool)
    got, _, after = sm._mixer(mc, w, u, state, conv, valid)
    s, c, outs = state, conv, []
    one = jnp.asarray([1, 1])
    for t in range(13):
        o, kept, none = sm._mixer(mc, w, u[:, t:t + 1], s, c, valid[:, :1])
        assert none is None     # a one-token pass leaves the states to the
        before = s              # pass over all the layers at once
        s = sm.advance_one_token([w], before[None], [kept], one)[0]
        np.testing.assert_allclose(
            np.asarray(s), np.asarray(sm.advance_state(w, before, kept,
                                                       one)[0]),
            atol=1e-6, rtol=0)
        c = sm._carry(kept, one)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.concatenate(outs, 1)),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(after), np.asarray(s), atol=1e-5,
                               rtol=0)


def test_forward_equals_the_reference():
    cfg, mc, params = tiny()
    seq = tokens(40, seed=1)
    got = np.asarray(sm.forward(params, jnp.asarray([seq]), mc)[0])
    np.testing.assert_allclose(got, reference_logits(cfg, seq), atol=ATOL,
                               rtol=0)


def test_the_two_shares_add_up_to_the_uncut_layer_and_head():
    """What ties the chip's share to the model: the routed parts of both
    shares (4 experts each of 8), with the shared expert counted once, are
    the uncut reference's expert layer; the 2 slices of the vocabulary give
    the uncut tied head's logits side by side."""
    uncut = dict(TINY, num_local_experts=8)
    w_all = ref.layer_weights(uncut, ref.seed_arg(SEED), 1)
    h = jax.random.normal(jax.random.key(0), (11, 32), jnp.float32)
    whole, experts = ref.moe(ref._dims(uncut), h, w_all, "float32")
    total = ref._swiglu(h, w_all["s_gate"], w_all["s_up"], w_all["s_down"],
                        "float32")
    loads = []
    for share in range(2):
        cfg = dict(TINY, held_experts_first=4 * share)
        mc = fam.model_config(cfg, 64)
        lp = ref.layer_weights(cfg, ref.seed_arg(SEED), 1)
        np.testing.assert_array_equal(
            np.asarray(lp["e_gate"]),
            np.asarray(w_all["e_gate"][4 * share:4 * share + 4]))
        part, load = latent_moe.held_experts(mc, lp, h, jnp.ones((11,), bool))
        total = total + part
        loads += [int(x) for x in load]
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5, rtol=0)
    assert loads == [int((np.asarray(experts) == e).sum()) for e in range(8)]
    assert sum(loads) == 11 * 3          # every choice computed somewhere

    seq = tokens(9, vocab=32, seed=3)    # ids every slice holds
    whole_vocab = reference_logits(dict(TINY, vocab_size=64), seq)
    full = ref.top_weights(dict(TINY, vocab_size=64), ref.seed_arg(SEED))
    parts = []
    for share in range(2):
        cfg, mc, params = tiny(vocab_size=32, vocab_first_row=32 * share)
        np.testing.assert_allclose(       # jitted or not: the last bit
            np.asarray(params["embed"]),
            np.asarray(full["embed"][32 * share:32 * share + 32]),
            atol=1e-7, rtol=0)
        # the same inputs everywhere: the tokens' rows of the whole
        # embedding looked up, this share's rows as the head
        x = sm.forward(dict(params, embed=full["embed"]),
                       jnp.asarray([seq], jnp.int32),
                       fam.model_config(dict(cfg, vocab_size=64), 64))[0]
        parts.append(np.asarray(x)[:, 32 * share:32 * share + 32])
    np.testing.assert_allclose(np.concatenate(parts, -1), whole_vocab,
                               atol=ATOL, rtol=0)


def test_the_second_routing_rule_is_top_k_of_the_logits_then_softmax():
    _, mc, params = tiny()
    lp = params["layers"][0]
    h = jax.random.normal(jax.random.key(0), (7, 32), jnp.float32)
    experts, weights = latent_moe.route(mc, lp, h)
    logits = np.asarray(h) @ np.asarray(lp["w_router"])
    order = np.argsort(-logits, axis=-1)[:, :3]
    np.testing.assert_array_equal(np.asarray(experts), order)
    top = np.take_along_axis(logits, order, -1)
    want = np.exp(top) / np.exp(top).sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(weights), want, atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-6)
