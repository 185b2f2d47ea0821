"""Each plain reference against the program on the CPU at a tiny size, and
each control (the reference at the nearest lower precision) against the
reference: the comparison has to pass the one and refuse the other."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import lib

TINY_RESNET = {"stage_sizes": [1, 1, 1, 1], "width": 8, "num_classes": 10,
               "image_size": 32, "compute_dtype": "float32"}
TINY_MISTRAL = {"hidden_size": 64, "intermediate_size": 128,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 2048}


def _resnet_ctx(chips, seed=5):
    cfg = dict(lib.load_json("configs", "resnet50.json"), **TINY_RESNET)
    return types.SimpleNamespace(config=cfg, mix={"per_chip_batch": 8},
                                 seed=seed, chips=chips)


@pytest.mark.parametrize("chips", [1, 4])
def test_resnet_train_step_matches_the_reference(chips):
    """Program and reference both in float32 on the CPU, where float32
    products are exact to rounding: what is left is the order of sums, some
    1e-7 a term over a few thousand terms, amplified by batch normalisation
    over 8 rows at 1x1 spatial size.  1e-3 on the norms is ten times what a
    sound run shows here and a hundredth of what a wrong gradient would."""
    fam = lib.load_module("families", "resnet_train")
    ctx = _resnet_ctx(chips)
    job = fam.build(ctx)
    assert job.n == chips and job.items_per_step == 8 * chips
    got = job.first_steps(2)
    job.free()
    want = fam.reference_readings(ctx, 2)
    assert got["losses"][0] == pytest.approx(want["losses"][0], rel=1e-5)
    values = {c["name"]: c["value"] for c in fam.compare(got, want)}
    assert values["grad_norm_worst"] < 1e-3, values
    # the control (every tensor of the compute type rounded to fp8) is not
    # correct, and moves the first gradient far more than a sound run does
    # (the limits are the chip's, set at the cells' size: at this size it is
    # enough that one of them refuses it)
    low = fam.reference_readings(ctx, 2, precision="fp8")
    control = {c["name"]: c for c in fam.compare(low, want)}
    assert not all(c["ok"] for c in control.values()), control
    assert not control["grad_norm_median"]["ok"], control
    assert control["grad_norm_worst"]["value"] > \
        30 * values["grad_norm_worst"]


def test_resnet_faults_the_loss_and_the_delta_are_there_to_catch():
    fam = lib.load_module("families", "resnet_train")
    ctx = _resnet_ctx(1)
    want = fam.reference_readings(ctx, 2)
    # a step that returns its state unchanged: no leaf moves
    stuck = dict(want, delta_norms={k: 0.0 for k in want["delta_norms"]})
    assert not {c["name"]: c for c in fam.compare(stuck, want)}[
        "delta_norm_worst"]["ok"]
    # half of the batch left out: another loss
    half = types.SimpleNamespace(**dict(vars(ctx), mix={"per_chip_batch": 4}))
    other = fam.reference_readings(half, 2)
    assert not {c["name"]: c for c in fam.compare(other, want)}[
        "loss_rel"]["ok"]


def _mistral_cfg():
    return dict(lib.load_json("configs", "mistral-7b-v0.3.json"),
                **TINY_MISTRAL)


def test_stacked_weights_are_the_reference_layers():
    fam = lib.load_module("families", "llama_serve")
    ref = lib.load_module("reference", "mistral")
    cfg = _mistral_cfg()
    params = fam.make_params(cfg, 9)
    for i in range(2):
        w = ref.layer_weights(cfg, 9, i)
        for k, v in w.items():
            assert params["layers"][k][i].dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(params["layers"][k][i], np.float32),
                np.asarray(v, np.float32))
    assert not np.array_equal(np.asarray(params["layers"]["wq"][0]),
                              np.asarray(params["layers"]["wq"][1]))


def test_mistral_forward_matches_the_program_in_float32():
    """``llama.forward`` with float32 activations on the reference's own
    bfloat16 weights against the reference: both float32, so what is left is
    the order of sums — 1e-4 of the largest logit is a hundred times that
    and a thousandth of bfloat16's error."""
    from horovod_tpu.models import llama

    fam = lib.load_module("families", "llama_serve")
    ref = lib.load_module("reference", "mistral")
    cfg = dict(_mistral_cfg())
    params = fam.make_params(cfg, 4)
    lc = fam.model_config(cfg, 64)
    import dataclasses
    lc = dataclasses.replace(lc, dtype=jnp.float32)
    toks = np.random.default_rng(0).integers(1, 2048, size=(1, 48))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(llama.forward(params, jnp.asarray(toks), lc))[0]
    want = np.asarray(ref.logits_at(cfg, 4, [toks[0].tolist()],
                                    [list(range(48))], pad_to=16)[0])
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()


def test_served_tokens_pass_and_the_fp8_control_fails():
    """The engine (bfloat16, paged cache, chunked prefill, a prefix hit)
    serves a few requests; every served token lies within the limits of the
    reference's best, and the tokens the reference picks at fp8 do not."""
    fam = lib.load_module("families", "llama_serve")
    cfg = _mistral_cfg()
    mix = {"engine": {"n_slots": 3, "max_len": 64, "chunk": 8,
                      "prefix_cache": True},
           "check": {"sample": 4, "pad_to": 64}}
    served = fam.Served(cfg, mix, 21)
    rng = np.random.default_rng(2)
    head = rng.integers(1, 2048, 16).tolist()
    prompts = [head + rng.integers(1, 2048, n).tolist() for n in (5, 11, 20)]
    prompts.append(rng.integers(1, 2048, 27).tolist())
    rids = [served.route(p, 12) for p in prompts[:1]]
    served.collect(rids[0], 60.0)        # its blocks are cached by now
    rids += [served.route(p, 12) for p in prompts[1:]]
    results = [served.collect(r, 60.0)[0] for r in rids]
    assert served.engine.prefix_counters["hits"] > 0
    finished = [(p, list(r)) for p, r in zip(prompts, results)]
    assert all(r.status == "OK" and len(r) == 12 for r in results)
    served.close()
    g = fam.gaps(cfg, mix, 21, finished, precision="fp8")
    sound = {c["name"]: c for c in fam.compare(g["served"])}
    control = {c["name"]: c for c in fam.compare(g["control"])}
    # at this size the chip's limits do not apply: what has to hold is the
    # order — bfloat16 serving stays close, fp8 is several times further
    assert sound["gap_max"]["value"] < 0.05, sound
    assert control["gap_mean"]["value"] > 3 * max(
        sound["gap_mean"]["value"], 1e-4), (sound, control)
    # a token altered where it is produced is far from the reference's best
    broken = [(p, [(t + 1) % 2048 for t in toks]) for p, toks in finished]
    b = fam.compare(fam.gaps(cfg, mix, 21, broken)["served"])
    assert not any(c["ok"] for c in b), b
