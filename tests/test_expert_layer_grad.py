"""The derivative of ``latent_moe.held_experts`` over its sorted tiles (the
grouped kernels in the Pallas interpreter at widths in whole lanes, and the
plain loop at toy widths) against the gradient of the plain sum over the
experts, at a load where one expert takes three tiles and one takes none."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import grouped_experts as ge
from horovod_tpu.models import latent_moe as lm

N, K, E_ALL = 300, 2, 8         # 300 tokens are over IN_PLACE_ROWS


class _Cfg:
    """What ``held_experts`` reads of a config: the softmax rule."""
    top_k, n_experts = K, E_ALL
    route_softmax_top_k = True
    routed_scale, route_norm_eps = 1.0, 0.0

    def __init__(self, dtype, held_first, held_count):
        self.dtype = dtype
        self.held_first, self.held_count = held_first, held_count


def _layer(d, f, held_count, dtype, w_dtype):
    ks = jax.random.split(jax.random.key(11), 5)

    def mat(k, fan_in, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(w_dtype)

    h2 = jax.random.normal(ks[0], (N, d), jnp.float32)
    h2 = h2.at[:, 0].set(1.0).astype(dtype)
    # the first feature is one: its router row is a bias that sends every
    # token to expert 1 (three tiles of 128) and none to expert 3
    w_router = 0.5 * jax.random.normal(ks[4], (d, E_ALL), jnp.float32)
    w_router = w_router.at[0, 1].set(50.0).at[0, 3].set(-50.0)
    return h2, {"e_gate": mat(ks[1], d, held_count, d, f),
                "e_up": mat(ks[2], d, held_count, d, f),
                "e_down": mat(ks[3], f, held_count, f, d),
                "w_router": w_router}


def _plain(cfg, lp, h2):
    """The held experts' part as a sum over the experts, every row through
    every held expert, in float32: the router's choice is ``lm.route``'s (the
    discrete part), its weights are differentiated through."""
    f32 = jnp.float32
    experts, weights = lm.route(cfg, lp, h2)
    x = h2.astype(f32)
    y = jnp.zeros(x.shape, f32)
    for j in range(cfg.held_count):
        out = (jax.nn.silu(x @ lp["e_gate"][j].astype(f32))
               * (x @ lp["e_up"][j].astype(f32))) @ lp["e_down"][j].astype(f32)
        w = jnp.sum(jnp.where(experts == cfg.held_first + j, weights, 0.0),
                    axis=1)
        y = y + w[:, None] * out
    return y


def _grads(fn, lp, h2, probe):
    def loss(h2, lp):
        return jnp.sum(fn(lp, h2).astype(jnp.float32) * probe)
    return jax.grad(loss, argnums=(0, 1))(h2, lp)


@pytest.mark.parametrize("widths,held", [
    ((128, 256), (0, 8)),       # whole lanes: the grouped kernels
    ((128, 256), (1, 4)),       # a share that holds neither end
    ((16, 24), (0, 8)),         # toy widths: the loop over the tiles
])
def test_the_gradient_is_the_plain_sums(widths, held):
    d, f = widths
    cfg = _Cfg(jnp.float32, *held)
    h2, lp = _layer(d, f, held[1], jnp.float32, jnp.float32)
    assert lm.rows_grouped(N, d, f) == ge.lane_aligned(d, f)
    valid = jnp.ones((N,), bool)
    y, load = lm.held_experts(cfg, lp, h2, valid)
    load = np.asarray(load)
    if held[0] == 0:
        assert load[1] == N and load[3] == 0       # three tiles, and none
    np.testing.assert_allclose(y, _plain(cfg, lp, h2), atol=2e-4, rtol=2e-4)
    probe = jax.random.normal(jax.random.key(5), (N, d))
    got_h, got_p = _grads(lambda lp, h2: lm.held_experts(
        cfg, lp, h2, valid)[0], lp, h2, probe)
    want_h, want_p = _grads(lambda lp, h2: _plain(cfg, lp, h2), lp, h2, probe)
    np.testing.assert_allclose(got_h, want_h, atol=3e-4, rtol=3e-4)
    for name in lp:
        scale = float(jnp.max(jnp.abs(want_p[name]))) or 1.0
        np.testing.assert_allclose(
            np.asarray(got_p[name]) / scale, np.asarray(want_p[name]) / scale,
            atol=3e-5, err_msg=name)
    if held[0] == 0:        # the expert nobody chose: exactly no gradient
        for name in ("e_gate", "e_up", "e_down"):
            assert not np.any(np.asarray(got_p[name][3]))


def test_float32_weights_under_bfloat16_products_have_float32_gradients():
    """Training's form: the kernels read the float32 weights and round them
    a block at a time, and the weights' gradients come back in float32."""
    d, f = 128, 256
    cfg = _Cfg(jnp.bfloat16, 0, 8)
    h2, lp = _layer(d, f, 8, jnp.bfloat16, jnp.float32)
    valid = jnp.ones((N,), bool)
    probe = jax.random.normal(jax.random.key(5), (N, d))
    got_h, got_p = _grads(lambda lp, h2: lm.held_experts(
        cfg, lp, h2, valid)[0], lp, h2, probe)
    want_h, want_p = _grads(lambda lp, h2: _plain(cfg, lp, h2), lp,
                            h2.astype(jnp.float32), probe)
    assert got_h.dtype == jnp.bfloat16
    for name in ("e_gate", "e_up", "e_down", "w_router"):
        assert got_p[name].dtype == jnp.float32
        a, b = np.asarray(got_p[name]), np.asarray(want_p[name])
        assert np.linalg.norm(a - b) <= 0.02 * np.linalg.norm(b), name
    a, b = np.asarray(got_h, np.float32), np.asarray(want_h)
    assert np.linalg.norm(a - b) <= 0.02 * np.linalg.norm(b)


@pytest.mark.parametrize("bias", [50.0, 0.0])
def test_a_program_of_few_rows_has_the_gradient_too(bias):
    """Within ``IN_PLACE_ROWS`` the layer computes over the rows in place,
    all experts at once or one touched expert a loop step (a loop whose bound
    is a device value: jax cannot differentiate it, the rule's backward is
    the first form's).  With the bias every token chooses expert 1 and few
    experts are touched (the loop); without it most are (all at once)."""
    d, f, n = 16, 24, 40
    cfg = _Cfg(jnp.float32, 0, 8)
    h2, lp = _layer(d, f, 8, jnp.float32, jnp.float32)
    h2 = h2[:n]
    lp["w_router"] = lp["w_router"].at[0, 1].set(bias).at[0, 3].set(-bias)
    lp["w_router"] = lp["w_router"].at[0, 4:].add(-bias)
    assert lm.rows_in_place(n)
    valid = jnp.ones((n,), bool)
    _, load = lm.held_experts(cfg, lp, h2, valid)
    touched = int(np.sum(np.asarray(load) > 0))
    assert (touched * 4 < 8 * 3) == bool(bias)
    probe = jax.random.normal(jax.random.key(5), (n, d))
    got_h, got_p = _grads(lambda lp, h2: lm.held_experts(
        cfg, lp, h2, valid)[0], lp, h2, probe)
    want_h, want_p = _grads(lambda lp, h2: _plain(cfg, lp, h2), lp, h2, probe)
    np.testing.assert_allclose(got_h, want_h, atol=3e-5, rtol=3e-4)
    for name in lp:
        np.testing.assert_allclose(got_p[name], want_p[name], atol=3e-5,
                                   rtol=3e-4, err_msg=name)


def test_invalid_rows_choose_nothing_and_get_no_gradient():
    d, f = 128, 256
    cfg = _Cfg(jnp.float32, 0, 8)
    h2, lp = _layer(d, f, 8, jnp.float32, jnp.float32)
    valid = jnp.arange(N) % 3 != 0
    probe = jax.random.normal(jax.random.key(5), (N, d))
    got_h, got_p = _grads(lambda lp, h2: lm.held_experts(
        cfg, lp, h2, valid)[0], lp, h2, probe)
    want_h, want_p = _grads(lambda lp, h2: _plain(cfg, lp, h2)
                            * valid[:, None], lp, h2, probe)
    assert not np.any(np.asarray(got_h)[~np.asarray(valid)])
    np.testing.assert_allclose(got_h, want_h, atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(got_p["e_down"], want_p["e_down"], atol=3e-4,
                               rtol=3e-4)


def test_the_served_forward_traces_what_it_traced():
    """Nothing is differentiated when a program serves: the rule's forward
    is the primal function itself, so the jaxpr holds the kernel call once
    and no residuals."""
    d, f = 128, 256
    cfg = _Cfg(jnp.bfloat16, 0, 8)
    h2, lp = _layer(d, f, 8, jnp.bfloat16, jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda lp, h2: lm.held_experts(
        cfg, lp, h2, jnp.ones((N,), bool)))(lp, h2))
    assert text.count("name=grouped_swiglu") == 1
    assert "grouped_swiglu_dx" not in text and "grouped_swiglu_dw" not in text
