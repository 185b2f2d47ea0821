"""The ``mellum_train`` family's cell with the trained path broken, on the
CPU at ``test_mellum.py``'s toy size: whole runs through ``run.py`` in a copy,
a fault planted in the program by the child's start-up lines, each of which
has to come out not correct by the family's comparison with its reference.
The copy is cut once more, to two layers (a sliding one and a full one: every
fault here still has its layer), which is a third off each run."""

import os

import pytest

import rehearse
from benchmark.tests.test_mellum import CELL, INTERPRET, _ok, make_copy


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    dst = make_copy(str(tmp_path_factory.mktemp("bench_mellum_faults")))
    rehearse._edit(
        os.path.join(dst, "benchmark", "configs", "mellum2-12b-a2.5b.json"),
        lambda d: d.update(num_hidden_layers=2,
                           layer_types=d["layer_types"][2:]))
    return dst


BROKEN = {
    "the band left out of the sliding layers": (
        "import horovod_tpu.models.moe_decoder as M\n"
        "_f = M.flash_attention\n"
        "M.flash_attention = lambda q, k, v, **kw: _f(\n"
        "    q, k, v, **dict(kw, window=None))\n"),
    "a held expert's choices dropped": (
        "import horovod_tpu.models.latent_moe as L\n"
        "_h = L.held_choices\n"
        "def _drop(cfg, lp, h2, valid):\n"
        "    held, group, weights, load = _h(cfg, lp, h2, valid)\n"
        "    gone = group == 2\n"
        "    return (held & ~gone.reshape(held.shape),\n"
        "            L.jnp.where(gone, cfg.held_count, group), weights,\n"
        "            load.at[2].set(0))\n"
        "L.held_choices = _drop\n"),
    "the router's gradient cut": (
        "import jax\n"
        "import horovod_tpu.models.latent_moe as L\n"
        "_r = L.route\n"
        "def _cut(cfg, lp, h2):\n"
        "    e, w = _r(cfg, lp, h2)\n"
        "    return e, jax.lax.stop_gradient(w)\n"
        "L.route = _cut\n"),
    "yarn left off the full layers": (
        "import horovod_tpu.models.moe_decoder as M\n"
        "_t = M.rope_tables\n"
        "M.rope_tables = lambda cfg, kind, pos: _t(cfg, 'window', pos)\n"),
    "half of the batch left out of the loss": (
        "import horovod_tpu.models.moe_decoder as M\n"
        "_l = M.loss_and_counters\n"
        "M.loss_and_counters = lambda params, batch, cfg: _l(\n"
        "    params, tuple(x[:x.shape[0] // 2] for x in batch), cfg)\n"),
    "the state left as it was": (
        "import optax\n"
        "optax.apply_updates = lambda params, updates: params\n"),
}


@pytest.mark.parametrize("fault", sorted(BROKEN))
def test_a_broken_trained_path_is_not_correct(copy, fault):
    rc, last, out, err = rehearse.run_in_copy(
        copy, CELL, extra=INTERPRET + BROKEN[fault])
    last = _ok(rc, last, out, err)
    assert last["correct"] is False, out[-1500:]
    assert last["failed"] == 0
