"""models/state_space_moe.py against the benchmark's plain reference
(benchmark/reference/granite.py) and against a second, independent recurrence
written here in numpy, at a tiny size on the CPU with seeded weights: the
whole forward, prefill in chunks of several widths followed by decoding
through the cache, the chunked form against the one-step form, what pads and
idle rows leave alone, what a snapshot holds and ``set_row`` restores, the
verify round at every acceptance, the tie between the chip's share and the
uncut layer, the second routing rule, and the snapshot budget's own
bookkeeping.  Behind ``ServeEngine``: ``tests/test_state_space_serving.py``."""

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
from horovod_tpu import metrics as metrics_mod  # noqa: E402
from horovod_tpu.models import latent_moe  # noqa: E402
from horovod_tpu.models import paged  # noqa: E402
from horovod_tpu.models import state_space_moe as sm  # noqa: E402

ref = lib.load_module("reference", "granite")
fam = lib.load_module("families", "granite_serve")
SEED = 5

#: A tiny configuration in the configuration file's keys: state-space layers
#: on both sides of the attention layer, pieces of 4 tokens, 8 experts of
#: which 4 are held, top-3, a shared expert.
TINY = dict(
    name="tiny", reference="granite", hidden_size=32, intermediate_size=16,
    shared_intermediate_size=24, num_hidden_layers=4,
    layer_types=["mamba", "mamba", "attention", "mamba", "mamba"],
    mamba_n_heads=8, mamba_d_head=8, mamba_d_state=8, mamba_d_conv=4,
    mamba_expand=2, mamba_n_groups=1, mamba_chunk_size=4,
    mamba_proj_bias=False, num_attention_heads=4, num_key_value_heads=2,
    attention_multiplier=0.125, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=16, num_local_experts=4,
    num_local_experts_published=8, held_experts_first=0,
    num_experts_per_tok=3, rms_norm_eps=1e-5, tie_word_embeddings=True,
    vocab_size=64, torch_dtype="float32")
#: float32 on the CPU: the program and the reference differ by the order of
#: their sums (measured: 2e-7 on logits of spread 0.3)
ATOL = 2e-5
N_LAYERS, N_SSM = 4, 3


def tiny(max_len=64, snapshots=3, **changes):
    """``(configuration dict, StateSpaceMoEConfig, parameters)``, the
    parameters the reference's own for the seed."""
    cfg = dict(TINY, **changes)
    return (cfg, fam.model_config(cfg, max_len, snapshots),
            fam.make_params(cfg, SEED))


def tokens(n, vocab=64, seed=0):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def reference_logits(cfg, seq):
    n = len(seq)
    return np.asarray(ref.logits_at(cfg, SEED, [seq], [list(range(n))],
                                    "float32", pad_to=n)[0])


def _cache(mc, n_slots, max_len, bs, seed=0):
    """A cache whose rows map shuffled blocks (never the trash block) and
    whose states hold rubbish, as a slot's does when another row leaves
    it."""
    pc = sm.init_paged_cache(mc, n_slots, max_len, block_size=bs)
    per = max_len // bs
    table = 1 + np.random.default_rng(seed).permutation(
        n_slots * per).reshape(n_slots, per)
    return pc._replace(block_table=jnp.asarray(table, jnp.int32),
                       ssm=jnp.full_like(pc.ssm, 3.0),
                       conv=jnp.full_like(pc.conv, 3.0))


def _serve_by_hand(mc, params, seq, n_prompt, chunk, bs, slot=1, snaps=None):
    """Prefill ``seq[:n_prompt]`` into slot ``slot`` of a two-slot cache in
    chunks of ``chunk`` (the last padded), then decode the rest a tick at a
    time with the other slot idle.  ``snaps``: the entry of each of the row's
    blocks (none by default).  Returns the logits at every position and the
    cache."""
    max_len = -(-(len(seq) + chunk) // bs) * bs
    pc = _cache(mc, 2, max_len, bs)
    none = np.full((max_len // bs,), mc.snapshots, np.int32)
    if snaps is not None:
        none[:len(snaps)] = snaps
    pc = sm.set_row(pc, slot, pc.block_table[slot], 0, jnp.asarray(none))
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
    assert paged.paged_model(sm.state_space_moe_tiny()) is sm
    with pytest.raises(TypeError, match="or a StateSpaceMoEConfig"):
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


@pytest.mark.parametrize("chunk, bs, n_prompt, n", [
    (1, 4, 11, 19),         # every token a program of its own
    (3, 4, 19, 27),         # chunks narrower than a piece of 4
    (4, 8, 19, 31),         # one piece a chunk
    (16, 8, 37, 45),        # pieces of 4, two block ends a chunk
    (24, 24, 50, 58),       # chunks that end on a block
    (512, 512, 520, 524)])  # a block the attention layer walks in two pieces
def test_chunked_prefill_then_decode_through_the_cache_equals_the_reference(
        chunk, bs, n_prompt, n):
    """Whatever the chunks' width, and whether or not they end on a block's
    end, the carried state makes the logits the reference's full pass."""
    cfg, mc, params = tiny(max_len=2048)
    seq = tokens(n, seed=2)
    got, pc = _serve_by_hand(mc, params, seq, n_prompt, chunk, bs)
    np.testing.assert_allclose(got, reference_logits(cfg, seq), atol=ATOL,
                               rtol=0)
    assert int(pc.length[1]) == n and int(pc.length[0]) == 0
    c = sm.read_counters(np.asarray(pc.stats))
    # the idle row and the chunks' padding counted for nothing
    assert c["choices_total"] == n * mc.top_k * N_LAYERS
    assert c["choices_held"] == sum(c["held_load"]) <= c["choices_total"]
    assert c["snapshots_written"] == 0          # no block was given an entry
    assert c["keys_visible"] == sum(p + 1 for p in range(n))
    # the idle slot's rubbish is as it was
    np.testing.assert_array_equal(np.asarray(pc.ssm[:, 0]), 3.0)
    np.testing.assert_array_equal(np.asarray(pc.conv[:, 0]), 3.0)


def test_the_state_is_the_same_whatever_the_programs_were():
    """A slot's state after 29 tokens is the same whether they came a token,
    four or sixteen at a time: pads and the idle row's ticks changed
    nothing."""
    _, mc, params = tiny(max_len=128)
    seq = tokens(29, seed=3)
    states = []
    for chunk in (1, 4, 16):
        _, pc = _serve_by_hand(mc, params, seq, 29, chunk, 8)
        states.append((np.asarray(pc.ssm[:, 1]), np.asarray(pc.conv[:, 1])))
    for s, c in states[1:]:
        np.testing.assert_allclose(s, states[0][0], atol=1e-5, rtol=0)
        np.testing.assert_allclose(c, states[0][1], atol=1e-5, rtol=0)


@pytest.mark.parametrize("chunk", [1, 5, 16])
def test_a_snapshot_is_the_state_at_its_blocks_last_position(chunk):
    """Prefill past the ends of three blocks of which two were given an
    entry: each entry is the state a row has that stopped at that end, the
    third entry is as it was, and a block end with no entry wrote nothing."""
    _, mc, params = tiny(max_len=128)
    bs, seq = 8, tokens(27, seed=4)
    _, pc = _serve_by_hand(mc, params, seq, 27, chunk, bs, snaps=[2, 3, 0])
    for end, entry in ((8, 2), (24, 0)):
        _, at_end = _serve_by_hand(mc, params, seq[:end], end, 3, bs)
        np.testing.assert_allclose(np.asarray(pc.snap_ssm[:, entry]),
                                   np.asarray(at_end.ssm[:, 1]), atol=1e-5,
                                   rtol=0)
        np.testing.assert_allclose(np.asarray(pc.snap_conv[:, entry]),
                                   np.asarray(at_end.conv[:, 1]), atol=1e-5,
                                   rtol=0)
    np.testing.assert_array_equal(np.asarray(pc.snap_ssm[:, 1]), 0.0)
    assert sm.read_counters(np.asarray(pc.stats))["snapshots_written"] == 2


def test_set_row_restores_the_state_from_the_entry_it_is_told():
    cfg, mc, params = tiny(max_len=128)
    bs, seq = 8, tokens(21, seed=5)
    _, pc = _serve_by_hand(mc, params, seq, 21, 4, bs, snaps=[1, 2])
    row = pc.block_table[1]
    set_row = jax.jit(sm.set_row)
    none = mc.snapshots
    per = pc.block_table.shape[1]
    for length, snaps, entry in ((16, [none, 2], 2), (8, [1, none], 1)):
        full = jnp.asarray(snaps + [none] * (per - 2), jnp.int32)
        got = set_row(pc, 0, row, length, full)
        np.testing.assert_array_equal(np.asarray(got.ssm[:, 0]),
                                      np.asarray(pc.snap_ssm[:, entry]))
        np.testing.assert_array_equal(np.asarray(got.conv[:, 0]),
                                      np.asarray(pc.snap_conv[:, entry]))
        assert int(got.length[0]) == length
        np.testing.assert_array_equal(np.asarray(got.snap_dest[0]),
                                      np.asarray(full))
    fresh = set_row(pc, 0, row, 0, jnp.full((per,), none, jnp.int32))
    np.testing.assert_array_equal(np.asarray(fresh.ssm[:, 0]), 0.0)
    np.testing.assert_array_equal(np.asarray(fresh.conv[:, 0]), 0.0)
    c0, c1 = (sm.read_counters(np.asarray(p.stats)) for p in (pc, got))
    assert c1["state_restores"] == c0["state_restores"] + 1
    assert sm.read_counters(np.asarray(fresh.stats))["state_restores"] == \
        c0["state_restores"]
    # continuing from the restored state is continuing the sequence
    cont = set_row(pc, 0, row, 16, jnp.asarray([none, 2] + [none] * (per - 2),
                                               jnp.int32))
    logits, _ = sm.decode_chunk_paged_row(
        params, jnp.asarray([seq[16:21] + [0] * 3], jnp.int32), mc, cont, 0,
        new_length=21)
    np.testing.assert_allclose(np.asarray(logits[0, :5]),
                               reference_logits(cfg, seq)[16:], atol=ATOL,
                               rtol=0)


@pytest.mark.parametrize("accepted", [0, 1, 2, 3])
def test_the_verify_round_leaves_the_state_as_after_the_accepted_tokens(
        accepted):
    """Drafts of which the first ``accepted`` are right: the round advances
    by one more, and the cache is the one that many ticks leave."""
    cfg, mc, params = tiny(max_len=128)
    bs, seq = 8, tokens(13, seed=6)
    _, pc = _serve_by_hand(mc, params, seq, 13, 4, bs, slot=0)
    rubbish = (np.asarray(pc.ssm[:, 1]), np.asarray(pc.conv[:, 1]))
    want = sm.generate(params, mc, seq, 4, pad_to=24)
    full = reference_logits(cfg, seq + want)
    last = jnp.asarray(np.stack([full[12], full[12]]))
    draft = [want[i + 1] if i < accepted else 63 - want[i + 1]
             for i in range(3)]
    drafts = jnp.asarray([draft, [-1, -1, -1]], jnp.int32)
    tok, accept, nxt, got = jax.jit(
        lambda c: sm.spec_verify_paged(params, mc, c, last, drafts,
                                       jnp.asarray([1, 0])))(pc)
    assert int(tok[0]) == want[0] and int(accept[0]) == accepted
    assert int(got.length[0]) == 13 + 1 + accepted
    assert int(got.length[1]) == 0
    np.testing.assert_allclose(np.asarray(nxt[0]), full[13 + accepted],
                               atol=ATOL, rtol=0)
    ticked = pc
    for t in want[:1 + accepted]:
        _, ticked = sm.decode_chunk_paged(
            params, jnp.asarray([[t], [7]], jnp.int32), mc, ticked,
            advance=jnp.asarray([1, 0]))
    np.testing.assert_allclose(np.asarray(got.ssm), np.asarray(ticked.ssm),
                               atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(got.conv), np.asarray(ticked.conv),
                               atol=1e-5, rtol=0)
    np.testing.assert_array_equal(np.asarray(got.ssm[:, 1]), rubbish[0])
    np.testing.assert_array_equal(np.asarray(got.conv[:, 1]), rubbish[1])


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


def test_an_idle_row_that_is_not_finite_spoils_no_live_row():
    """A slot another row left holds whatever that row computed; a tick is
    over every slot, and the idle one's numbers reach no live row."""
    cfg, mc, params = tiny(max_len=128)
    seq = tokens(14, seed=8)
    _, pc = _serve_by_hand(mc, params, seq[:13], 13, 4, 8)
    bad = dict(params, embed=params["embed"].at[7].set(jnp.inf))
    logits, _ = sm.decode_chunk_paged(
        bad, jnp.asarray([[7], [seq[13]]], jnp.int32), mc, pc,
        advance=jnp.asarray([0, 1]))
    # (the head is the embedding: the logit of the row that is not finite
    # is not, in every row, and is left out)
    keep = np.arange(64) != 7
    np.testing.assert_allclose(np.asarray(logits[1, 0])[keep],
                               reference_logits(cfg, seq)[13][keep],
                               atol=ATOL, rtol=0)


# ---------------------------------------------------------------------------
# the snapshot budget, by itself
# ---------------------------------------------------------------------------

def test_the_budget_grants_commits_evicts_and_drops():
    reg = metrics_mod.MetricsRegistry(event_log=None)
    b = paged.SnapshotBudget(2, evicted=reg.counter("ssm.snapshots_evicted"),
                             live=reg.gauge("ssm.snapshots_live"))
    assert b.none == 2 and b.entry(7) is None and not b.wanted(7)
    e7 = b.grant(7)
    assert e7 == 0 and b.wanted(7) and b.entry(7) is None     # pending
    e9 = b.grant(9)
    assert b.grant(11) is None          # every entry pending: refused
    b.commit(e7)
    b.commit(e9)
    assert (b.entry(7), b.entry(9)) == (e7, e9)
    assert reg.gauge("ssm.snapshots_live").value == 2
    b.touch(7)                  # restored from: 9, never restored, goes
    e11 = b.grant(11)           # first, though it was committed after 7
    assert e11 == e9 and b.entry(9) is None and b.entry(7) == e7
    assert reg.counter("ssm.snapshots_evicted").value == 1
    b.drop(11)                          # freed while its write is pending
    b.commit(e11)                       # ... the entry goes free, unheld
    assert b.entry(11) is None and b.held_count() == 1
    # ... and among the restored, the least recently restored
    b.commit(b.grant(12))
    b.touch(12)
    b.touch(7)
    assert b.grant(13) is not None and b.entry(12) is None
    assert b.entry(7) == e7
    # asked for without evidence, an entry that was restored from stays
    assert b.grant(14, on_evidence=False) is None and b.entry(7) == e7
    b.drop(13)
    b.commit(b.entry(13) or next(iter(b._pending)))
    b.drop(7)
    assert b.held_count() == 0 and b.pending_count() == 0
    e = b.grant(5)
    b.cancel(e)
    assert not b.wanted(5)
    b.check_consistency()
    # an entry follows the block that stays, unless that one holds its own
    b.commit(b.grant(20))
    b.move(20, 21)
    assert b.entry(20) is None and b.entry(21) is not None
    b.commit(b.grant(22))
    b.move(22, 21)
    assert b.entry(22) is not None
    b.check_consistency()
    with pytest.raises(ValueError):
        paged.SnapshotBudget(0)
