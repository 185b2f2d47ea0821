"""models/shortconv_moe.py against the benchmark's plain reference
(benchmark/reference/lfm2.py, the one copy), at a tiny size on the CPU with
seeded weights, and the rules of its second kind of state: the whole forward,
prefill in chunks of several widths followed by decoding through the cache,
what a slot's convolution state and a block's snapshot hold after each kind of
program, what ``set_row`` restores, the verify round's pick, the router's
bias, the packed key heads and the counters."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import lib  # noqa: E402
from horovod_tpu.models import latent_moe  # noqa: E402
from horovod_tpu.models import paged  # noqa: E402
from horovod_tpu.models import shortconv_moe as sm  # noqa: E402

ref = lib.load_module("reference", "lfm2")
fam = lib.load_module("families", "lfm2_serve")
SEED = 5

#: A tiny configuration in the configuration file's keys: the published order
#: of the first seven layers (two dense convolution layers, then attention,
#: conv, conv, conv, attention), 8 experts top-2.
TINY = dict(
    name="tiny", reference="lfm2", conv_L_cache=3, hidden_size=32,
    intermediate_size=64, num_hidden_layers=7,
    layer_types=["conv", "conv", "full_attention", "conv", "conv", "conv",
                 "full_attention", "conv"],
    moe_intermediate_size=16, norm_eps=1e-5, num_attention_heads=4,
    num_key_value_heads=2, num_dense_layers=2, num_experts=8,
    num_experts_per_tok=2, rope_theta=1e4, routed_scaling_factor=1.0,
    route_norm_eps=1e-6, tie_word_embeddings=True, vocab_size=64,
    torch_dtype="float32")
#: float32 on the CPU: the program and the reference differ by the order of
#: their sums (measured: 9e-6 on logits of unit spread)
ATOL = 2e-4


def tiny(max_len=64, **changes):
    """``(configuration dict, ShortConvMoEConfig, parameters)``, the
    parameters the reference's own for the seed."""
    cfg = dict(TINY, **changes)
    return cfg, fam.model_config(cfg, max_len), fam.make_params(cfg, SEED)


def tokens(n, vocab=64, seed=0):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def reference_logits(cfg, seq):
    n = len(seq)
    return np.asarray(ref.logits_at(cfg, SEED, [seq], [list(range(n))],
                                    "float32", pad_to=n)[0])


def _cache(mc, n_slots, max_len, bs, seed=0):
    """A cache whose rows map shuffled blocks (never the trash block)."""
    pc = sm.init_paged_cache(mc, n_slots, max_len, block_size=bs)
    per = max_len // bs
    table = 1 + np.random.default_rng(seed).permutation(
        n_slots * per).reshape(n_slots, per)
    return pc._replace(block_table=jnp.asarray(table, jnp.int32))


def _serve_by_hand(mc, params, seq, n_prompt, chunk, bs, slot=1):
    """Prefill ``seq[:n_prompt]`` into slot ``slot`` of a two-slot cache in
    chunks of ``chunk`` (the last padded), then decode the rest a tick at a
    time with the other slot idle.  Returns the logits at every position and
    the cache."""
    max_len = -(-(len(seq) + chunk) // bs) * bs
    pc = _cache(mc, 2, max_len, bs)
    row = jax.jit(lambda p, t, c, n: sm.decode_chunk_paged_row(
        p, t, mc, c, slot, new_length=n))
    tick = jax.jit(lambda p, t, c, a: sm.decode_chunk_paged(
        p, t, mc, c, advance=a))
    got = []
    for lo in range(0, n_prompt, chunk):
        hi = min(lo + chunk, n_prompt)
        toks = seq[lo:hi] + [0] * (chunk - (hi - lo))
        logits, pc = row(params, jnp.asarray([toks], jnp.int32), pc, hi)
        got.append(np.asarray(logits[0, :hi - lo]))
    active = jnp.asarray([s == slot for s in range(2)], jnp.int32)
    for tok in seq[n_prompt:]:
        toks = jnp.asarray([[tok] if s == slot else [7] for s in range(2)],
                           jnp.int32)
        logits, pc = tick(params, toks, pc, active)
        got.append(np.asarray(logits[slot]))
    return np.concatenate(got), pc


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


def _z_of(cfg, seq):
    """What the reference's convolution layers take in: per conv layer
    ``z = B * X`` at every position, [n_conv, T, d]."""
    m = ref._dims(cfg)
    top = ref.top_weights(cfg, ref.seed_arg(SEED))
    x = top["embed"][jnp.asarray(seq)].astype(jnp.float32)
    zs = []
    for i in range(cfg["num_hidden_layers"]):
        kind = ref.layer_kind(cfg, i)
        w = ref.layer_weights(cfg, ref.seed_arg(SEED), i)
        if kind[0] == "conv":
            u = ref._rms(x, w["op_norm"], m["eps"])
            b, _, xx = jnp.split(jnp.dot(u, w["w_in"], precision=ref.HI), 3,
                                 axis=-1)
            zs.append(np.asarray(b * xx))
        x = ref.layer(cfg, kind, x, w)
    return np.stack(zs)


def _state_at(z, p):
    """The state a sequence carries after ``p`` tokens, as the cache's row."""
    pad = np.concatenate([np.zeros((z.shape[0], 2, z.shape[2])), z], axis=1)
    return pad[:, p:p + 2].reshape(z.shape[0], -1)


def test_slot_state_and_block_snapshots_hold_the_reference_s_inputs():
    """After prefill and ticks the slot holds the last two inputs of every
    convolution at its length, and every full block the two at its end."""
    cfg, mc, params = tiny()
    seq = tokens(27, seed=3)
    _, pc = _serve_by_hand(mc, params, seq, n_prompt=13, chunk=8, bs=4)
    z = _z_of(cfg, seq)
    np.testing.assert_allclose(np.asarray(pc.conv[:, 1]), _state_at(z, 27),
                               atol=ATOL, rtol=0)
    table = np.asarray(pc.block_table[1])
    for b in range(27 // 4):
        np.testing.assert_allclose(
            np.asarray(pc.snap[:, table[b]]), _state_at(z, 4 * (b + 1)),
            atol=ATOL, rtol=0, err_msg=f"block {b}")
    # the idle slot's state stayed zero, the trash block holds no snapshot
    assert not np.asarray(pc.conv[:, 0]).any()
    assert not np.asarray(pc.snap[:, 0]).any()


def test_set_row_restores_the_state_of_the_block_that_ends_at_the_length():
    cfg, mc, params = tiny()
    seq = tokens(21, seed=4)
    _, pc = _serve_by_hand(mc, params, seq, n_prompt=21, chunk=8, bs=4)
    row = pc.block_table[1]
    set_row = jax.jit(sm.set_row)
    # another slot mapped onto the first three blocks, as a prefix hit maps it
    hit = set_row(pc, 0, row, 12)
    np.testing.assert_array_equal(np.asarray(hit.conv[:, 0]),
                                  np.asarray(pc.snap[:, row[2]]))
    assert int(hit.length[0]) == 12
    np.testing.assert_array_equal(np.asarray(hit.block_table[0]),
                                  np.asarray(row))
    assert sm.read_counters(np.asarray(hit.stats))["state_restores"] == 1
    # ... and continues to the same logits as the row that wrote them
    z = _z_of(cfg, seq)
    np.testing.assert_allclose(np.asarray(hit.conv[:, 0]), _state_at(z, 12),
                               atol=ATOL, rtol=0)
    # mapped at 0 (a fresh admission, a retirement) the state is zeros
    fresh = set_row(hit, 0, jnp.zeros_like(row), 0)
    assert not np.asarray(fresh.conv[:, 0]).any()
    assert sm.read_counters(np.asarray(fresh.stats))["state_restores"] == 1


def test_a_row_continued_from_a_snapshot_equals_the_reference():
    """Two slots share a prefix's blocks; the second starts at the prefix's
    end with the state ``set_row`` restored and prefills only its own part."""
    cfg, mc, params = tiny()
    shared, own = tokens(8, seed=5), tokens(9, seed=6)
    _, pc = _serve_by_hand(mc, params, shared + tokens(3, seed=7), 11, 8, 4)
    row = np.asarray(pc.block_table[0]).copy()
    row[:2] = np.asarray(pc.block_table[1])[:2]         # the shared blocks
    pc = sm.set_row(pc, 0, jnp.asarray(row), 8)
    toks = own + [0] * 3           # 12 wide: the table holds 20
    logits, pc = sm.decode_chunk_paged_row(
        params, jnp.asarray([toks], jnp.int32), mc, pc, 0, new_length=17)
    want = reference_logits(cfg, shared + own)[8:]
    np.testing.assert_allclose(np.asarray(logits[0, :9]), want, atol=ATOL,
                               rtol=0)
    # with the snapshot zeroed the same program gives other logits
    zeroed = sm.set_row(pc._replace(snap=jnp.zeros_like(pc.snap)), 0,
                        jnp.asarray(row), 8)
    wrong, _ = sm.decode_chunk_paged_row(
        params, jnp.asarray([toks], jnp.int32), mc, zeroed, 0, new_length=17)
    assert np.abs(np.asarray(wrong[0, :9]) - want).max() > 100 * ATOL


@pytest.mark.parametrize("n_accept", [0, 1, 3])
def test_a_verify_round_leaves_the_state_after_the_accepted_tokens(n_accept):
    """Drafts of which ``n_accept`` are the model's own choices: the round
    leaves the lengths, the slot states and the snapshots as ``1 + n_accept``
    plain ticks do (up to the order of a wider product's sums), for the
    active row, and the idle row alone."""
    _, mc, params = tiny()
    seq = tokens(14, seed=8)
    got, pc = _serve_by_hand(mc, params, seq, n_prompt=14, chunk=8, bs=4)
    last = jnp.stack([jnp.zeros((64,)), jnp.asarray(got[-1])])
    active = jnp.asarray([0, 1], jnp.int32)
    tick = jax.jit(lambda c, lg: _greedy_tick(params, mc, c, lg, active))
    # the model's own next five tokens, by plain ticks
    own, c, lg = [], pc, last
    for _ in range(5):
        tok, lg, c = tick(c, lg)
        own.append(int(tok[1]))
    drafts = own[1:1 + n_accept] + [(own[1 + n_accept] + 1) % 64] * (
        3 - n_accept)
    tok, accept, next_logits, got = sm.spec_verify_paged(
        params, mc, pc, last, jnp.asarray([[-1] * 3, drafts], jnp.int32),
        active)
    assert int(tok[1]) == own[0] and int(accept[1]) == n_accept
    want, lg = pc, last
    for _ in range(1 + n_accept):
        _, lg, want = tick(want, lg)
    np.testing.assert_array_equal(np.asarray(got.length),
                                  np.asarray(want.length))
    assert int(got.length[1]) == 14 + 1 + n_accept
    for name in ("conv", "snap"):
        np.testing.assert_allclose(np.asarray(getattr(got, name)),
                                   np.asarray(getattr(want, name)),
                                   atol=1e-5, rtol=0, err_msg=name)
    # a state one token further on (what the lengths alone would leave if the
    # round kept its last position's) is another state
    assert np.abs(np.asarray(got.conv[:, 1]) - np.asarray(c.conv[:, 1])
                  ).max() > 1e-3
    np.testing.assert_allclose(np.asarray(next_logits[1]), np.asarray(lg[1]),
                               atol=1e-5, rtol=0)


def _greedy_tick(params, mc, pc, last_logits, active):
    tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    logits, pc = sm.decode_chunk_paged(params, tok[:, None], mc, pc,
                                       advance=active)
    return tok, logits[:, 0], pc


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


@pytest.mark.parametrize("n_kv_heads, head_dim, pack", [
    (8, 64, 2), (2, 8, 2), (8, 128, 1), (3, 32, 3), (8, 16, 8)])
def test_key_heads_are_packed_into_rows_of_128_lanes(n_kv_heads, head_dim,
                                                     pack):
    mc = sm.shortconv_moe_tiny(n_heads=2 * n_kv_heads, n_kv_heads=n_kv_heads,
                               head_dim=head_dim)
    assert mc.kv_pack == pack
    pc = sm.init_paged_cache(mc, 2, 16, block_size=8)
    assert pc.k.shape == (2, 5, 8, n_kv_heads // pack, pack * head_dim)
    assert sm.paged_pool_bytes(pc)["k"] == 2 * 8 * n_kv_heads * head_dim * 4


def test_packed_heads_attend_as_unpacked_ones(monkeypatch):
    """The same weights with one key head a pool row give the same logits."""
    _, mc, params = tiny()
    seq = jnp.asarray([tokens(24, seed=10)])
    packed = sm.forward(params, seq, mc)
    monkeypatch.setattr(sm.ShortConvMoEConfig, "kv_pack", property(
        lambda self: 1))
    assert mc.kv_pack == 1
    np.testing.assert_allclose(np.asarray(sm.forward(params, seq, mc)),
                               np.asarray(packed), atol=1e-5, rtol=0)


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


def test_counters_carry_past_a_word():
    stats = jnp.zeros((2, latent_moe.LOAD0 + 8), jnp.int32)
    add = jnp.zeros((latent_moe.LOAD0 + 8,), jnp.int32).at[
        sm.KEYS_VISIBLE].set(2**23 + 5)
    for _ in range(5):
        stats = latent_moe._add_stats(stats, add, None)
    assert sm.read_counters(np.asarray(stats))["keys_visible"] == 5 * (
        2**23 + 5)
