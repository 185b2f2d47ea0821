"""The grouped product of the expert layer
(``horovod_tpu.models.grouped_experts``) in the Pallas interpreter, at small
widths in whole lanes (``d`` 128, ``f`` 256, tiles of 128 rows): against
``latent_moe._swiglu`` applied expert by expert, against the loop over the
tiles it replaced, and against a float32 ``jax.numpy`` reference for the
band; and the counter that says which programs took it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import grouped_experts as ge
from horovod_tpu.models import latent_moe as lm

D, F, N, K = 128, 256, 300, 2       # 300 tokens are over IN_PLACE_ROWS
TILE = 128              # twice 300 x 2 / 8 choices an expert, capped


def _all_on(e):
    return lambda rng, n_experts: np.stack(
        [np.full(N, e), (e + 1 + rng.integers(0, n_experts - 1, N))
         % n_experts], axis=1)


def _spread(rng, n_experts):
    first = rng.integers(0, n_experts, N)
    return np.stack([first, (first + 1 + rng.integers(0, n_experts - 1, N))
                     % n_experts], axis=1)


def _never_three(rng, n_experts):
    """Every expert but the fourth is chosen."""
    first = rng.choice([e for e in range(n_experts) if e != 3], N)
    second = (first + 1) % n_experts
    return np.stack([first, np.where(second == 3, (second + 1) % n_experts,
                                     second)], axis=1)


#: name -> (the router's experts, held_first, held_count, every row valid,
#: blocks of f): the held experts' loads follow from the choices
CASES = {
    "an_expert_nobody_chose": (_never_three, 0, 8, True, 1),
    "one_expert_spans_three_tiles": (_all_on(1), 0, 8, True, 1),
    "every_row_invalid_no_tile_in_use": (_spread, 0, 8, False, 1),
    "all_held_choices_on_one_expert": (_all_on(5), 5, 1, True, 1),
    "held_first_above_zero": (_spread, 2, 4, True, 1),
    "f_blocked_in_two": (_spread, 0, 8, True, 2),
}


class _Cfg:
    """What ``held_experts`` reads of a config."""
    top_k, n_experts = K, 8

    def __init__(self, dtype, held_first, held_count):
        self.dtype = dtype
        self.held_first, self.held_count = held_first, held_count


def _layer(dtype, held_count):
    ks = jax.random.split(jax.random.key(7), 4)

    def mat(k, fan_in, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    h2 = jax.random.normal(ks[0], (N, D), jnp.float32).astype(dtype)
    return h2, {"e_gate": mat(ks[1], D, held_count, D, F),
                "e_up": mat(ks[2], D, held_count, D, F),
                "e_down": mat(ks[3], F, held_count, F, D)}


def _expert_by_expert(cfg, lp, h2, experts, weights, valid, dtype):
    """Every row through every held expert (``swiglu`` in ``dtype``), the
    outcomes of the experts a row chose picked by rank and summed in float32
    as ``held_experts`` sums them."""
    up = lambda a: a.astype(dtype)      # noqa: E731
    outs = jnp.stack([lm._swiglu(up(h2), up(lp["e_gate"][e]),
                                 up(lp["e_up"][e]), up(lp["e_down"][e]),
                                 dtype) for e in range(cfg.held_count)])
    local = experts - cfg.held_first
    held = (local >= 0) & (local < cfg.held_count) & valid[:, None]
    picked = outs[jnp.clip(local, 0, cfg.held_count - 1),
                  jnp.arange(N)[:, None]]                       # [N, k, d]
    return jnp.sum(jnp.where(held[..., None], picked.astype(jnp.float32), 0)
                   * weights[..., None], axis=1)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_grouped_product_is_swiglu_expert_by_expert(monkeypatch, case,
                                                        dtype):
    """``held_experts`` over 300 tokens sorts its choices into tiles and puts
    them through the kernel.  In float32 with ``f`` in one block the rounding
    points are the loop's and the result is the loop's to the last bit; with
    ``f`` in two blocks the down product is summed in two parts, and in
    bfloat16 the interpreter's products and XLA's round a last bit apart
    (and the kernel keeps silu and its product in float32 where XLA:CPU
    rounds each step), so there the band holds: gate, up, their product and
    the outcome are each rounded once to ``dtype``, a relative ``eps / 2``
    each, the first three carried through a product of unit-scale weights,
    so an outcome stands within ``4 * eps / 2`` of the float32 reference's
    scale, and a row's sum (weights that add to one) within the same; beside
    it what a float32 sum of ``F`` terms in another order may differ by,
    ``sqrt(F)`` steps of float32's ``eps`` in each of the two products (all
    that shows when ``dtype`` is float32 itself)."""
    choose, held_first, held_count, all_valid, f_blocks = CASES[case]
    rng = np.random.default_rng(11)
    experts = jnp.asarray(choose(rng, _Cfg.n_experts), jnp.int32)
    w = rng.uniform(0.2, 0.8, (N, 1)).astype(np.float32)
    weights = jnp.asarray(np.concatenate([w, 1 - w], axis=1))
    valid = jnp.full((N,), all_valid)
    cfg = _Cfg(dtype, held_first, held_count)
    h2, lp = _layer(dtype, held_count)
    monkeypatch.setattr(lm, "route", lambda cfg, lp, h2: (experts, weights))
    isz = jnp.dtype(dtype).itemsize
    if f_blocks == 2:
        monkeypatch.setattr(ge, "VMEM_BLOCK_BYTES", TILE * D * (
            4 * isz + 4) + 6 * D * (F // 2) * isz)
    assert ge.f_block(D, F, TILE, isz) == F // f_blocks
    assert lm.rows_grouped(N, D, F) and not lm.rows_in_place(N)
    assert lm.tile_rows(N, cfg) == TILE == lm.TILE_ROWS
    ran = []
    monkeypatch.setattr(ge, "grouped_swiglu", lambda *a, _f=ge.grouped_swiglu,
                        **kw: ran.append(a[2]) or _f(*a, **kw))

    y, load = lm.held_experts(cfg, lp, h2, valid)
    assert len(ran) == 1
    local = np.asarray(experts) - held_first
    want_load = np.bincount(local[(local >= 0) & (local < held_count)
                                  & all_valid], minlength=held_count)
    assert list(np.asarray(load)) == list(want_load)
    assert int(ran[0]) == sum(-(-int(n) // TILE) for n in want_load)
    if case == "an_expert_nobody_chose":
        assert want_load[3] == 0 and (np.delete(want_load, 3) > 0).all()
    if case == "one_expert_spans_three_tiles":
        assert want_load[1] == N and -(-N // TILE) == 3
    if not all_valid:
        assert int(ran[0]) == 0 and not np.asarray(y).any()

    loop = lm._experts_in_tiles(cfg, lp, h2, *lm.held_choices(
        cfg, lp, h2, valid), lm._tiles_looped)
    y32, loop32 = (np.asarray(a, np.float32) for a in (y, loop))
    if dtype == jnp.float32 and f_blocks == 1:
        assert (y32 == loop32).all()
    exact = np.asarray(_expert_by_expert(cfg, lp, h2, experts, weights, valid,
                                         jnp.float32))
    rounded = np.asarray(_expert_by_expert(cfg, lp, h2, experts, weights,
                                           valid, dtype))
    band = (4 * float(jnp.finfo(dtype).eps) / 2 + 2 * F ** 0.5 * float(
        jnp.finfo(jnp.float32).eps)) * max(float(np.abs(exact).max()), 1e-6)
    assert np.abs(y32 - exact).max() <= band
    assert np.abs(y32 - rounded).max() <= band
    assert np.abs(y32 - loop32).max() <= band
    # not a band so wide that anything passes: an expert's weights swapped
    # for its neighbour's stand outside it
    if all_valid and held_count > 1:
        swapped = {k: jnp.roll(v, 1, axis=0) for k, v in lp.items()}
        off = np.asarray(_expert_by_expert(cfg, swapped, h2, experts, weights,
                                           valid, jnp.float32))
        assert np.abs(off - exact).max() > 20 * band


def test_every_program_s_choices_are_in_place_or_grouped(monkeypatch):
    """A block-diffusion engine at widths in whole lanes (float32, on the
    CPU): with the threshold at 8 rows its chunks of 8 tokens compute in
    place and its block ticks (3 rows of 4 positions) take the grouped
    product; ``moe.choices_in_place`` + ``moe.choices_grouped`` account for
    every dispatched program's choices, by the predicates ``held_experts``
    goes by, and every request is still the reference's sampler's."""
    from horovod_tpu import metrics as metrics_mod
    from horovod_tpu.models import block_diffusion_moe as bd
    from horovod_tpu.serving import Request
    from horovod_tpu.serving_scheduler import ServeEngine

    import toy_block_diffusion as toy

    conf = dict(toy.TINY, hidden_size=128, moe_intermediate_size=128)
    w = toy.ref.make_weights(conf, 5)
    params = {"embed": w["top"]["embed"], "layers": tuple(w["layers"]),
              "final_norm": w["top"]["final_norm"],
              "lm_head": w["top"]["lm_head"]}
    s = toy.sampler(2)
    mc = toy.model_config(conf, s)
    monkeypatch.setattr(lm, "IN_PLACE_ROWS", 8)
    programs, publish = [], bd.publish_paged_metrics

    def spy(metrics, cfg, pcache, stats_host=None, row_blocks=(),
            programs_=()):
        programs.extend(programs_)
        return publish(metrics, cfg, pcache, stats_host, row_blocks, programs_)

    monkeypatch.setattr(bd, "publish_paged_metrics", spy)
    kernels = []
    monkeypatch.setattr(ge, "grouped_swiglu", lambda *a, _f=ge.grouped_swiglu,
                        **kw: kernels.append(a[0].shape) or _f(*a, **kw))
    with jax.default_matmul_precision("highest"):
        eng = ServeEngine(params, mc, max_len=64, chunk=8, n_slots=3,
                          prefix_cache=True,
                          metrics=metrics_mod.MetricsRegistry(event_log=None))
        rng = np.random.default_rng(3)
        reqs = [Request(prompt=rng.integers(1, 60, n).tolist(),
                        max_new_tokens=k) for n, k in ((10, 7), (19, 6))]
        res = eng.run(reqs)
        for req, r in zip(reqs, res):
            want = toy.ref.sample(conf, w, s, req.prompt, req.max_new_tokens,
                                  pad_to=64)
            assert r.status == "OK" and list(r) == want["tokens"]
    c = eng.metrics_snapshot()["counters"]
    sizes = {p.rows * p.t for p in programs}
    assert sizes == {8, 12}
    per_token = mc.top_k * mc.n_layers
    assert c["moe.choices_in_place"] == per_token * sum(
        p.rows * p.t for p in programs if p.rows * p.t == 8) > 0
    assert c["moe.choices_grouped"] == per_token * sum(
        p.rows * p.t for p in programs if p.rows * p.t == 12) > 0
    assert c["moe.choices_in_place"] + c["moe.choices_grouped"] == \
        per_token * sum(p.rows * p.t for p in programs)
    assert c["moe.choices_grouped"] == lm.choices_grouped(mc, programs)
    # the block tick's layers hold the kernel: 12 x 2 choices and a tile an
    # expert at worst
    assert lm.tile_rows(12, mc) == 32
    assert (12 * 2 + 8 * 32, 128) in kernels


@pytest.mark.parametrize("form", ["tree", "in_place", "grouped",
                                  "ragged_dot"])
def test_the_sweep_s_forms_are_one_layer(form):
    """``tools/expert_layer_sweep.py`` times forms of one layer: over one
    load drawn from a router that reaches six of eight experts, each form's
    outcome is the loop's within the band of the test above (with eight
    experts and two choices a token an unreachable expert still takes the
    tokens that find no two logits above its zero: a twentieth of them)."""
    from functools import partial

    from horovod_tpu.models import shortconv_moe as sm
    from tools import expert_layer_sweep as sweep

    cfg = sm.shortconv_moe_tiny(dim=D, expert_dim=F, n_experts=8,
                                held_count=8, top_k=K, dtype=jnp.bfloat16,
                                param_dtype=jnp.bfloat16)
    lp = sweep.layer_params(cfg, jax.random.key(1), 0.75)
    assert int(jnp.sum(jnp.any(lp["w_router"] != 0, axis=0))) == 6
    h2 = jax.random.normal(jax.random.key(2), (N, D)).astype(cfg.dtype)
    valid = jnp.ones((N,), bool)
    load = lm.held_choices(cfg, lp, h2, valid)[3]
    assert int(jnp.sum(load)) == N * K
    assert int(jnp.sum(jnp.sort(load)[-6:])) >= 0.9 * N * K
    want = jax.jit(partial(sweep.FORMS["loop"], cfg))(lp, h2, valid)
    got = jax.jit(partial(sweep.FORMS[form], cfg))(lp, h2, valid)
    want, got = (np.asarray(a, np.float32) for a in (want, got))
    band = 4 * float(jnp.finfo(jnp.bfloat16).eps) / 2 * np.abs(want).max()
    assert 0 < np.abs(got - want).max() <= band or form == "tree"
    assert np.abs(got - want).max() <= band


def test_preload_imports_on_a_thread_and_the_attribute_is_the_module_s():
    """``grouped_experts`` asks for Pallas as it is imported, on a thread (a
    second of import that would else stand inside the engine's first trace):
    ``_OnFirstUse.preload`` returns at once, the module arrives, and an
    attribute asked for meanwhile or after is the module's own."""
    import sys
    import time

    from horovod_tpu.parallel.flash_attention import _OnFirstUse, pltpu

    lazy = _OnFirstUse("wave")
    sys.modules.pop("wave", None)
    lazy.preload()
    assert lazy.open is sys.modules["wave"].open
    deadline = time.monotonic() + 30
    while "jax.experimental.pallas.tpu" not in sys.modules:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    assert pltpu.VMEM is sys.modules["jax.experimental.pallas.tpu"].VMEM
