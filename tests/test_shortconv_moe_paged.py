"""models/shortconv_moe.py's second kind of state and its cache's layout:
what a slot's convolution state and a block's snapshot hold after each kind of
program, what ``set_row`` restores, a row continued from a snapshot against the
reference (benchmark/reference/lfm2.py), the verify round's pick, the packed
key heads and the counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from toy_shortconv_moe import (ATOL, SEED, _serve_by_hand, ref,
                               reference_logits, tiny, tokens)

from horovod_tpu.models import latent_moe
from horovod_tpu.models import shortconv_moe as sm


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


def test_counters_carry_past_a_word():
    stats = jnp.zeros((2, latent_moe.LOAD0 + 8), jnp.int32)
    add = jnp.zeros((latent_moe.LOAD0 + 8,), jnp.int32).at[
        sm.KEYS_VISIBLE].set(2**23 + 5)
    for _ in range(5):
        stats = latent_moe._add_stats(stats, add, None)
    assert sm.read_counters(np.asarray(stats))["keys_visible"] == 5 * (
        2**23 + 5)
