"""The trainable decoder (``horovod_tpu.models.moe_decoder``) at a small
size on the CPU, seeded random weights, against the benchmark's plain
reference (``benchmark/reference/mellum.py``): the loss and every leaf's
gradient over sliding and full layers, YaRN's table, a top-4 of which 8 of 16
experts are held; the share test (the four shares' expert outputs add up to
the uncut layer, the vocabulary slices' logits concatenate to the whole
head's); the counters; and the step through ``hvd.make_train_step``."""

import copy
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.models import llama
from horovod_tpu.models import moe_decoder as md

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import lib  # noqa: E402

T = 288         # over latent_moe.IN_PLACE_ROWS: the sorted tiles


def tiny_config(**overrides) -> dict:
    """The cell's configuration file cut to a toy: widths in whole lanes (so
    that the grouped kernels run, in the interpreter), the published layer
    pattern, a band narrower than the sequence, 8 of 16 experts held."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mellum2-12b-a2.5b.json")) as f:
        cfg = json.load(f)
    cfg = copy.deepcopy(cfg)
    cfg.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, num_hidden_layers=4, sliding_window=40,
               moe_intermediate_size=128, num_experts_published=16,
               num_experts=8, held_experts_first=0, num_experts_per_tok=4,
               vocab_size=64, vocab_size_published=256, vocab_first_row=0)
    cfg["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 64
    cfg["training"].update(seq_len=T, compute_dtype="float32")
    cfg.update(overrides)
    return cfg


@pytest.fixture(scope="module")
def ref():
    return lib.load_module("reference", "mellum")


@pytest.fixture(scope="module")
def family():
    return lib.load_module("families", "mellum_train")


@pytest.fixture(scope="module")
def both(ref, family):
    """The program's and the reference's loss and gradients on one batch of
    two sequences."""
    cfg = tiny_config()
    mc = family.model_config(cfg, block_q=32, block_k=32, xent_chunk=48)
    flat = ref.make_params(cfg, ref.seed_arg(5))
    # a scale at which every layer moves the loss: 0.02 at a width of 128
    # leaves the residual stream the embedding's
    flat = {k: v if k.endswith("norm") else 5.0 * v for k, v in flat.items()}
    ids, targets = ref.make_batch(cfg, ref.seed_arg(5), 2)
    (value, aux), grads = jax.jit(jax.value_and_grad(functools.partial(
        md.loss_and_counters, cfg=mc), has_aux=True))(
            md.nest(flat), (ids, targets))
    want = [jax.jit(jax.value_and_grad(lambda p, i, t: ref.loss(
        cfg, p, i, t)))(flat, ids[i], targets[i]) for i in range(2)]
    want_value = sum(float(w[0]) for w in want) / 2
    want_grads = {k: (want[0][1][k] + want[1][1][k]) / 2 for k in flat}
    return dict(cfg=cfg, mc=mc, flat=flat, ids=ids, value=float(value),
                aux=aux, grads=family._flat(grads), want_value=want_value,
                want_grads=want_grads)


def test_the_loss_is_the_references(both):
    assert both["value"] == pytest.approx(both["want_value"], rel=2e-5)
    assert 3.0 < both["value"] < 6.0        # near ln(64), not a constant


def test_the_toy_takes_the_sorted_tiles_and_the_grouped_kernels(both):
    from horovod_tpu.models import latent_moe as lm

    assert lm.rows_grouped(T, both["mc"].dim, both["mc"].expert_dim)
    assert both["mc"].layer_kinds == ("window", "window", "window", "full")


LEAVES = sorted(md.param_shapes(md.moe_decoder_tiny(
    n_layers=4, layer_kinds=("window",) * 3 + ("full",))))


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_leafs_gradient_is_the_references(both, leaf):
    got, want = np.asarray(both["grads"][leaf]), np.asarray(
        both["want_grads"][leaf])
    assert got.shape == want.shape
    scale = float(np.max(np.abs(want)))
    assert scale > 0, "a leaf that the loss does not feel compares nothing"
    np.testing.assert_allclose(got / scale, want / scale, atol=2e-4)


def test_the_counters_count_what_the_step_did(both, ref):
    mc, aux = both["mc"], {k: np.asarray(v) for k, v in both["aux"].items()}
    assert aux["moe.choices_total"] == 2 * T * mc.top_k * mc.n_layers
    chosen = np.stack([np.asarray(ref.choices(both["cfg"], both["flat"], i))
                       for i in both["ids"]], axis=1)       # [L, B, T, k]
    held = chosen < mc.held_count
    assert aux["moe.choices_held"] == held.sum()
    np.testing.assert_array_equal(
        aux["moe.held_load"],
        [(chosen == e).sum() for e in range(mc.held_count)])
    assert aux["moe.expert_calls"] == mc.n_layers * 2
    assert aux["attn.key_blocks_causal"] == 2 * 4 * 4 * 45      # 9 blocks
    assert aux["attn.key_blocks_visited"] == 2 * 4 * (45 + 3 * (
        1 + 8 * 2 + 0) + 3 * 7)     # band of 40 over blocks of 32: 1, 2, 3..
    got = md.read_counters(both["aux"], registry=hvd.metrics.MetricsRegistry()
                           if hasattr(hvd, "metrics") else None)
    assert got["moe.held_load.0"] == aux["moe.held_load"][0]
    assert "moe.held_load" not in got


def test_the_choices_are_the_references(both, ref):
    prog = np.asarray(md.expert_choices(md.nest(both["flat"]), both["ids"],
                                        both["mc"]))
    want = np.stack([np.asarray(ref.choices(both["cfg"], both["flat"], i))
                     for i in both["ids"]], axis=1)
    assert prog.shape == want.shape == (4, 2, T, 4)
    assert (prog != want).any(-1).mean() < 0.01     # but for near-ties


# --- the share test ---------------------------------------------------------

def test_the_four_shares_experts_add_up_to_the_uncut_layer(ref, family):
    """One layer's expert part on the same input: the program's four shares
    (``held_first`` 0, 4, 8, 12 of 16) add up to the uncut reference's, and
    each share is the reference's of that share."""
    whole = tiny_config(num_experts=16, held_experts_first=0)
    flat_all = ref.make_params(whole, ref.seed_arg(9))
    pre = "layers/1/"
    u = jax.random.normal(jax.random.key(3), (T, 128), jnp.float32)
    uncut = ref.experts_part(whole, flat_all, pre, u, "float32")
    total = jnp.zeros_like(uncut)
    from horovod_tpu.models import latent_moe as lm

    for first in (0, 4, 8, 12):
        share = tiny_config(num_experts=4, held_experts_first=first)
        flat = ref.make_params(share, ref.seed_arg(9))
        for name in ("e_gate", "e_up", "e_down"):     # the shares tile it
            np.testing.assert_array_equal(
                flat[pre + name], flat_all[pre + name][first:first + 4])
        np.testing.assert_array_equal(flat[pre + "w_router"],
                                      flat_all[pre + "w_router"])
        mc = family.model_config(share)
        lp = md.nest(flat)["layers"][1]
        part, load = lm.held_experts(mc, lp, u, jnp.ones((T,), bool))
        np.testing.assert_allclose(
            part, ref.experts_part(share, flat, pre, u, "float32"),
            atol=2e-6, rtol=2e-4)
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=5e-6, rtol=2e-4)
    assert float(jnp.max(jnp.abs(uncut))) > 1e-4


def test_the_vocabulary_slices_logits_concatenate_to_the_whole_heads(
        ref, family):
    whole = tiny_config(vocab_size=256, vocab_first_row=0)
    flat_all = ref.make_params(whole, ref.seed_arg(9))
    x = jax.random.normal(jax.random.key(4), (T, 128), jnp.float32)
    parts = []
    for s in range(4):
        share = tiny_config(vocab_size=64, vocab_first_row=64 * s)
        flat = ref.make_params(share, ref.seed_arg(9))
        np.testing.assert_array_equal(
            flat["embed"], flat_all["embed"][64 * s:64 * (s + 1)])
        np.testing.assert_array_equal(
            flat["head"], flat_all["head"][:, 64 * s:64 * (s + 1)])
        parts.append(x @ flat["head"])
    np.testing.assert_allclose(jnp.concatenate(parts, axis=1),
                               x @ flat_all["head"], rtol=1e-5, atol=1e-7)


# --- rotary -----------------------------------------------------------------

def test_yarns_table_is_the_published_one(ref, family):
    """At the published numbers: pairs under 18 keep the plain frequency,
    pairs over 35 take a sixteenth, a linear blend between; cos and sin
    carry the attention factor."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mellum2-12b-a2.5b.json")) as f:
        cfg = json.load(f)
    plain = np.asarray(llama.rope_inv_freq(128, 500000.0))
    got = np.asarray(llama.yarn_inv_freq(128, 500000.0, factor=16.0,
                                         original_max=8192))
    np.testing.assert_allclose(got[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(got[35:], plain[35:] / 16, rtol=1e-6)
    assert np.all(np.diff(got / plain) <= 1e-7)
    want, factor = ref.inv_freq(cfg, "full_attention")
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert factor == 1.2772588722239782
    mc = family.model_config(cfg)
    pos = jnp.arange(8192)[None, ::511]
    cos, sin = md.rope_tables(mc, "full", pos)
    np.testing.assert_allclose(cos ** 2 + sin ** 2, factor ** 2, rtol=1e-4)
    cos, sin = md.rope_tables(mc, "window", pos)
    np.testing.assert_allclose(cos ** 2 + sin ** 2, 1.0, rtol=1e-4)
    np.testing.assert_allclose(
        cos, np.cos(np.asarray(pos)[..., None] * np.asarray(
            ref.inv_freq(cfg, "sliding_attention")[0])), atol=2e-3)


def test_llamas_table_is_what_it_was():
    cfg = llama.llama_tiny()
    pos = jnp.arange(7)[None]
    cos, sin = llama.rope_tables(cfg, pos)
    half = cfg.head_dim // 2
    ang = np.arange(7)[None, :, None] * cfg.rope_theta ** (
        -np.arange(half) / half)
    np.testing.assert_allclose(cos, np.cos(ang), atol=1e-6)
    np.testing.assert_allclose(sin, np.sin(ang), atol=1e-6)


# --- the step ---------------------------------------------------------------

def test_the_train_step_returns_the_counters_summed_over_the_ranks():
    """``make_train_step(..., has_aux=True)`` over the decoder's loss with
    ``DistributedOptimizer(adamw)``: the counters of the eight ranks'
    sequences come back summed, the loss averaged, and the weights move."""
    mc = md.moe_decoder_tiny()
    params = md.init_params(mc, jax.random.key(0), scale=0.1)
    n = hvd.size()
    ids = jax.random.randint(jax.random.key(1), (n, 33), 0, mc.vocab_size)
    batch = (jax.device_put(ids[:, :-1], hvd.rank_sharding()),
             jax.device_put(ids[:, 1:], hvd.rank_sharding()))
    tx = hvd.DistributedOptimizer(optax.adamw(1e-3))
    step = hvd.make_train_step(functools.partial(
        md.loss_and_counters, cfg=mc), tx, has_aux=True, donate=False)
    out = step(params, tx.init(params), batch)
    assert isinstance(out, hvd.TrainStepAuxResult)
    one = md.loss_and_counters(params, (ids[:1, :-1], ids[:1, 1:]), mc)[1]
    assert int(out.aux["moe.choices_total"]) == n * int(
        one["moe.choices_total"]) == n * 32 * mc.top_k * mc.n_layers
    assert int(out.aux["attn.key_blocks_visited"]) == n * int(
        one["attn.key_blocks_visited"])
    total = sum(np.asarray(md.loss_and_counters(
        params, (ids[i:i + 1, :-1], ids[i:i + 1, 1:]), mc)[1][
            "moe.held_load"]) for i in range(n))
    np.testing.assert_array_equal(out.aux["moe.held_load"], total)
    want = float(md.loss(params, (ids[:, :-1], ids[:, 1:]), mc))
    assert float(out.loss) == pytest.approx(want, rel=1e-5)
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         out.params, params)
    assert all(v > 0 for v in jax.tree.leaves(moved))
    snap = hvd.metrics.DEFAULT.snapshot()["gauges"]
    assert snap["train.params_held"] == md.param_count(mc)
    assert snap["train.state_bytes"] == 16 * md.param_count(mc)


def test_without_has_aux_the_step_is_what_it_was():
    def loss_fn(p, batch):
        return jnp.mean((batch @ p["w"]) ** 2)

    params = {"w": jnp.ones((4, 2))}
    batch = jax.device_put(jnp.ones((hvd.size(), 4)), hvd.rank_sharding())
    tx = hvd.DistributedOptimizer(optax.sgd(0.1))
    step = hvd.make_train_step(loss_fn, tx, donate=False)
    out = step(params, tx.init(params), batch)
    assert type(out) is hvd.TrainStepResult and len(out) == 3
    p, s, value = out                  # three fields, as the README unpacks
    assert float(value) == pytest.approx(16.0)


_LOWERED = (
    "import functools, hashlib, importlib, jax\n"
    "from horovod_tpu.models import moe_decoder as md\n"
    "fa = importlib.import_module('horovod_tpu.parallel.flash_attention')\n"
    "mc = md.moe_decoder_tiny()\n"
    "p = jax.eval_shape(lambda: md.init_params(mc, jax.random.key(0)))\n"
    "ids = jax.ShapeDtypeStruct((2, 32), 'int32')\n"
    "with fa.interpret_mode():\n"
    "    text = jax.jit(functools.partial(md.loss, cfg=mc)).lower(\n"
    "        p, (ids, ids)).as_text()\n"
    "print(hashlib.sha256(text.encode()).hexdigest())\n")


def test_every_process_traces_the_same_program():
    """A set's order follows the process's hash seed.  The rotary tables were
    once made in the order of ``set(layer_kinds)``: two programs, two keys in
    the compile cache, and a cell's set-up 40 s longer whenever the process
    drew the order the cache had not seen (PERF.md, PR 48)."""
    import subprocess

    runs = [subprocess.Popen(
        [sys.executable, "-c", _LOWERED], stdout=subprocess.PIPE, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
                 PYTHONHASHSEED=seed)) for seed in ("1", "2")]
    digests = [r.communicate(timeout=300)[0].strip() for r in runs]
    assert all(r.returncode == 0 for r in runs)
    assert len(digests[0]) == 64 and digests[0] == digests[1]


def test_a_config_that_names_no_layer_kind_is_refused():
    with pytest.raises(ValueError, match="layer_kinds"):
        md.moe_decoder_tiny(layer_kinds=("window", "global"))
    with pytest.raises(ValueError, match="held experts"):
        md.moe_decoder_tiny(held_first=9, held_count=8)
