"""models/window_moe.py against the benchmark's plain reference
(benchmark/reference/kexaone.py, the one copy), at a tiny size on the CPU with
seeded weights, and behind ``ServeEngine``: the whole forward, prefill in
chunks of several widths followed by decoding through the cache, what a slot's
ring and a block's snapshot hold after each kind of program, what ``set_row``
restores, a prefix hit, preemption with replay, the verify round, a cloned
engine, the tie between the chip's share and the uncut layer, and the
counters."""

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
from horovod_tpu import supervisor  # noqa: E402
from horovod_tpu.models import latent_moe  # noqa: E402
from horovod_tpu.models import paged  # noqa: E402
from horovod_tpu.models import window_moe as wm  # noqa: E402
from horovod_tpu.router import LocalReplica, RouterServer  # noqa: E402
from horovod_tpu.serving import Request  # noqa: E402
from horovod_tpu.serving_scheduler import ServeEngine  # noqa: E402

ref = lib.load_module("reference", "kexaone")
fam = lib.load_module("families", "kexaone_serve")
SEED = 5
N_NEW = 9

#: A tiny configuration in the configuration file's keys: the published order
#: of the first five layers (sliding, sliding, sliding, full, sliding; the
#: first dense), a window of 6 positions, 16 experts of which 8 are held,
#: top-2, a shared expert.
TINY = dict(
    name="tiny", reference="kexaone", hidden_size=32, intermediate_size=64,
    num_hidden_layers=5,
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 2,
    first_k_dense_replace=1, head_dim=8, num_attention_heads=4,
    num_key_value_heads=2,
    rope_parameters={"rope_theta": 1e4, "rope_type": "default"},
    sliding_window=6, num_experts=8, num_experts_published=16,
    held_experts_first=0, moe_intermediate_size=16, num_experts_per_tok=2,
    num_shared_experts=1, routed_scaling_factor=2.5, rms_norm_eps=1e-5,
    tie_word_embeddings=False, vocab_size=64, torch_dtype="float32")
#: float32 on the CPU: the program and the reference differ by the order of
#: their sums (measured: 3e-6 on logits of spread 0.6)
ATOL = 2e-4
N_MOE = 4                   # expert layers of the tiny model


def tiny(max_len=64, **changes):
    """``(configuration dict, WindowMoEConfig, parameters)``, the parameters
    the reference's own for the seed."""
    cfg = dict(TINY, **changes)
    return cfg, fam.model_config(cfg, max_len), fam.make_params(cfg, SEED)


def tokens(n, vocab=64, seed=0):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def reference_logits(cfg, seq):
    n = len(seq)
    return np.asarray(ref.logits_at(cfg, SEED, [seq], [list(range(n))],
                                    "float32", pad_to=n)[0])


def _cache(mc, n_slots, max_len, bs, seed=0):
    """A cache whose rows map shuffled blocks (never the trash block) and
    whose rings hold rubbish, as a slot's does when another row leaves it."""
    pc = wm.init_paged_cache(mc, n_slots, max_len, block_size=bs)
    per = max_len // bs
    table = 1 + np.random.default_rng(seed).permutation(
        n_slots * per).reshape(n_slots, per)
    return pc._replace(block_table=jnp.asarray(table, jnp.int32),
                       ring=jnp.full_like(pc.ring, 3.0))


def _serve_by_hand(mc, params, seq, n_prompt, chunk, bs, slot=1):
    """Prefill ``seq[:n_prompt]`` into slot ``slot`` of a two-slot cache in
    chunks of ``chunk`` (the last padded), then decode the rest a tick at a
    time with the other slot idle.  Returns the logits at every position and
    the cache."""
    max_len = -(-(len(seq) + chunk) // bs) * bs
    pc = _cache(mc, 2, max_len, bs)
    row = jax.jit(lambda p, t, c, n: wm.decode_chunk_paged_row(
        p, t, mc, c, slot, new_length=n))
    tick = jax.jit(lambda p, t, c, a: wm.decode_chunk_paged(
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
    assert paged.paged_model(mc) is wm
    assert paged.paged_model(wm.window_moe_tiny()) is wm
    with pytest.raises(TypeError, match="a WindowMoEConfig or a StateSpaceMoEConfig"):
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


def test_the_ring_holds_the_last_window_whatever_the_programs_were():
    """A slot's ring after 29 tokens is the same whether they came a token,
    four or sixteen at a time, and the rubbish it held before is gone from
    every index a position was written to."""
    _, mc, params = tiny(max_len=128)
    seq = tokens(29, seed=3)
    rings = []
    for chunk in (1, 4, 16):
        _, pc = _serve_by_hand(mc, params, seq, 29, chunk, 8)
        rings.append(np.asarray(pc.ring[:, :, 1]))
        np.testing.assert_array_equal(np.asarray(pc.ring[:, :, 0]), 3.0)
    np.testing.assert_allclose(rings[0], rings[1], atol=1e-5, rtol=0)
    np.testing.assert_allclose(rings[0], rings[2], atol=1e-5, rtol=0)
    assert not (rings[0] == 3.0).any()


@pytest.mark.parametrize("chunk", [1, 5, 16])
def test_a_blocks_snapshot_is_the_ring_at_its_last_position(chunk):
    """Prefill to a block's end one way, and further another: the snapshot of
    each block that filled is the ring a row has that stopped at its end."""
    _, mc, params = tiny(max_len=128)
    bs, seq = 8, tokens(27, seed=4)
    _, pc = _serve_by_hand(mc, params, seq, 27, chunk, bs)
    table = np.asarray(pc.block_table[1])
    for end in (8, 16, 24):
        _, at_end = _serve_by_hand(mc, params, seq[:end], end, 3, bs)
        np.testing.assert_allclose(
            np.asarray(pc.snap[:, :, table[end // bs - 1]]),
            np.asarray(at_end.ring[:, :, 1]), atol=1e-5, rtol=0)
    # the block that has not filled holds none
    np.testing.assert_array_equal(np.asarray(pc.snap[:, :, table[3]]), 0.0)


def test_set_row_restores_the_ring_from_the_block_that_ends_at_the_length():
    _, mc, params = tiny(max_len=128)
    bs, seq = 8, tokens(21, seed=5)
    _, pc = _serve_by_hand(mc, params, seq, 21, 4, bs)
    row = pc.block_table[1]
    set_row = jax.jit(wm.set_row)
    for length, block in ((16, row[1]), (8, row[0])):
        got = set_row(pc, 0, row, length)
        np.testing.assert_array_equal(np.asarray(got.ring[:, :, 0]),
                                      np.asarray(pc.snap[:, :, block]))
        assert int(got.length[0]) == length
        np.testing.assert_array_equal(np.asarray(got.block_table[0]),
                                      np.asarray(row))
    fresh = set_row(pc, 0, row, 0)
    np.testing.assert_array_equal(np.asarray(fresh.ring[:, :, 0]), 0.0)
    c0, c1 = (wm.read_counters(np.asarray(p.stats)) for p in (pc, got))
    assert c1["state_restores"] == c0["state_restores"] + 1
    assert wm.read_counters(np.asarray(fresh.stats))["state_restores"] == \
        c0["state_restores"]
    # continuing from the restored ring is continuing the sequence
    cfg = dict(TINY)
    cont = set_row(pc, 0, row, 16)
    logits, _ = wm.decode_chunk_paged_row(
        params, jnp.asarray([seq[16:21] + [0] * 3], jnp.int32), mc, cont, 0,
        new_length=21)
    np.testing.assert_allclose(np.asarray(logits[0, :5]),
                               reference_logits(cfg, seq)[16:], atol=ATOL,
                               rtol=0)


def test_the_verify_round_leaves_the_ring_as_after_the_accepted_tokens():
    """Drafts of which the first two are right: the round advances by three,
    and the cache is the one that three ticks leave, rings and the snapshot
    of the block that filled included."""
    cfg, mc, params = tiny(max_len=128)
    bs, seq = 8, tokens(13, seed=6)
    _, pc = _serve_by_hand(mc, params, seq, 13, 4, bs, slot=0)
    pc = pc._replace(length=pc.length.at[1].set(0))
    want = wm.generate(params, mc, seq, 4, pad_to=24)
    full = reference_logits(cfg, seq + want)
    last = jnp.asarray(np.stack([full[12], full[12]]))
    drafts = jnp.asarray([[want[1], want[2], 63 - want[3]], [-1, -1, -1]],
                         jnp.int32)
    tok, accept, nxt, got = jax.jit(
        lambda c: wm.spec_verify_paged(params, mc, c, last, drafts,
                                       jnp.asarray([1, 0])))(pc)
    assert int(tok[0]) == want[0] and int(accept[0]) == 2
    assert int(got.length[0]) == 16 and int(got.length[1]) == 0
    np.testing.assert_allclose(np.asarray(nxt[0]), full[15], atol=ATOL,
                               rtol=0)
    ticked = pc
    for t in want[:3]:
        _, ticked = wm.decode_chunk_paged(
            params, jnp.asarray([[t], [7]], jnp.int32), mc, ticked,
            advance=jnp.asarray([1, 0]))
    for a, b in ((got.ring, ticked.ring), (got.snap, ticked.snap)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=0)
    assert wm.read_counters(np.asarray(got.stats))["snapshots_written"] == 2


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


def test_an_idle_row_that_is_not_finite_spoils_no_live_row():
    """A slot another row left holds whatever that row computed; a tick is
    over every slot, and the idle one's numbers reach no live row."""
    cfg, mc, params = tiny(max_len=128)
    seq = tokens(14, seed=8)
    _, pc = _serve_by_hand(mc, params, seq[:13], 13, 4, 8)
    bad = dict(params, embed=params["embed"].at[7].set(jnp.inf))
    logits, _ = wm.decode_chunk_paged(
        bad, jnp.asarray([[7], [seq[13]]], jnp.int32), mc, pc,
        advance=jnp.asarray([0, 1]))
    np.testing.assert_allclose(np.asarray(logits[1, 0]),
                               reference_logits(cfg, seq)[13], atol=ATOL,
                               rtol=0)


# ---------------------------------------------------------------------------
# behind ServeEngine
# ---------------------------------------------------------------------------

def _engine(mc, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("chunk", 8)
    return ServeEngine(params, mc, monitor=False, sampler=False,
                       metrics=metrics_mod.MetricsRegistry(event_log=None),
                       **kw)


def _requests(prompts):
    return [Request(prompt=p, max_new_tokens=N_NEW) for p in prompts]


def _counters(eng):
    return eng.metrics.snapshot()["counters"]


@pytest.fixture(scope="module")
def served():
    """The tiny model, four prompts of which three share their first two
    blocks (a system prompt), and each prompt's solo tokens with no cache."""
    _, mc, params = tiny()
    system = tokens(16, seed=3)
    prompts = [system + tokens(5, seed=4), tokens(7, seed=5),
               system + tokens(11, seed=6), system + tokens(3, seed=7)]
    want = [wm.generate(params, mc, p, N_NEW, pad_to=48) for p in prompts]
    return mc, params, prompts, want


def test_engine_prefill_and_decode_agree_with_the_reference_on_logits(served):
    """One request through ``ServeEngine`` a step at a time: the logits the
    engine holds for the row after its prefill and after each tick are the
    reference's full pass over the prompt and the tokens served."""
    mc, params, prompts, want = served
    eng = _engine(mc, params, n_slots=1)
    rid = eng.submit(Request(prompt=prompts[2], max_new_tokens=N_NEW))
    seen = {}
    while eng.pending():
        eng.step()
        s = eng._slots[0]
        if s.request_id == rid and s.out is not None and s.budget > 0 \
                and int(eng.pcache.length[0]) >= len(prompts[2]):
            seen[int(eng.pcache.length[0])] = np.asarray(eng.last_logits[0])
    out = list(eng.results[rid])
    assert out == want[2]
    full = reference_logits(dict(TINY), prompts[2] + out)
    assert len(seen) >= N_NEW - 1
    for length, logits in seen.items():
        np.testing.assert_allclose(logits, full[length - 1], atol=ATOL,
                                   rtol=0)


def test_engine_run_equals_cache_free_generate(served):
    mc, params, prompts, want = served
    eng = _engine(mc, params)
    assert eng.model is wm
    out = eng.run(_requests(prompts))
    assert [r.status for r in out] == ["OK"] * 4
    assert [list(r) for r in out] == want
    assert eng.compile_cache_sizes() == \
        {"sample": 1, "tick": 1, "chunk": 1, "set_row": 1}
    snap = eng.metrics.snapshot()
    # float32: 1 full layer and 4 sliding ones of 2 key heads of 8
    assert snap["gauges"]["kv.bytes_per_token"] == 2 * 1 * 2 * 8 * 4
    assert snap["gauges"]["state.bytes_per_slot"] == 2 * 4 * 6 * 2 * 8 * 4
    assert snap["gauges"]["kv.snapshot_block_bytes"] == \
        snap["gauges"]["state.bytes_per_slot"]
    assert snap["counters"]["window.state_restores"] == 0
    assert eng.memory_report()["kv"]["pools"].keys() == {"k", "v", "snap"}


def test_a_prefix_hit_serves_the_cold_tokens_and_restores_the_ring(served):
    """Admitted on a hit, a request prefills only its own part; its tokens
    are its solo cache-off run's bit for bit, which they are not when the
    snapshots it restores from are zeroed."""
    mc, params, prompts, want = served
    eng = _engine(mc, params, prefix_cache=True)
    first = eng.run(_requests(prompts[:1]))
    assert list(first[0]) == want[0]
    assert _counters(eng)["window.state_restores"] == 0
    hit = eng.run(_requests(prompts[2:]))
    assert [list(r) for r in hit] == want[2:]
    assert eng.prefix_counters["hits"] == 2
    assert eng.prefix_counters["tokens_skipped"] == 32
    assert _counters(eng)["window.state_restores"] == 2
    assert eng.compile_cache_sizes() == \
        {"sample": 1, "tick": 1, "chunk": 1, "set_row": 1}

    broken = _engine(mc, params, prefix_cache=True)
    assert list(broken.run(_requests(prompts[:1]))[0]) == want[0]
    broken.pcache = broken.pcache._replace(
        snap=jnp.zeros_like(broken.pcache.snap))
    wrong = broken.run(_requests(prompts[2:]))
    assert broken.prefix_counters["hits"] == 2
    assert [list(r) for r in wrong] != want[2:]


def test_preemption_and_replay_serve_the_same_tokens(served):
    mc, params, prompts, want = served
    # 7 blocks cannot hold both long requests: the second starves, the first
    # is preempted, its blocks (and their snapshots) released to the cache,
    # and replayed through a hit on them from its prompt plus what it emitted
    eng = _engine(mc, params, n_blocks=7, preempt_after=2, prefix_cache=True)
    out = eng.run(_requests([prompts[0], prompts[2]]))
    assert [list(r) for r in out] == [want[0], want[2]]
    assert eng.counters["preemptions"] >= 1
    assert _counters(eng)["window.state_restores"] >= 1
    # and with no cache to replay through: prefill from position 0
    eng = _engine(mc, params, n_blocks=7, preempt_after=2)
    out = eng.run(_requests([prompts[0], prompts[2]]))
    assert [list(r) for r in out] == [want[0], want[2]]
    assert eng.counters["preemptions"] >= 1
    assert _counters(eng)["window.state_restores"] == 0


@pytest.mark.parametrize("prefix_cache", [False, True])
def test_speculation_on_and_off_serve_the_same_tokens(served, prefix_cache):
    mc, params, prompts, want = served
    # prompts that repeat themselves, so that drafts are proposed (and some
    # accepted, some not): the round has a ring to pick
    loops = [p + p[-6:] * 2 for p in prompts[:3]]
    solo = [wm.generate(params, mc, p, N_NEW, pad_to=48) for p in loops]
    outs = {}
    for spec in (False, True):
        eng = _engine(mc, params, spec=spec, draft_k=3,
                      prefix_cache=prefix_cache)
        outs[spec] = [list(r) for r in eng.run(_requests(loops))]
        if spec:
            assert eng.spec_counters["rounds"] > 0
            assert eng.spec_counters["proposed"] > 0
            assert eng.compile_cache_sizes() == {
                "sample": 0, "tick": 0, "chunk": 1, "set_row": 1,
                "spec_tick": 1}
    assert outs[True] == outs[False] == solo


def test_a_cloned_engine_serves_the_same_tokens(served):
    """``supervisor.clone_engine`` after the engine has ticked: same registry,
    fresh state; the clone's device counters start at zero under counters
    that do not."""
    mc, params, prompts, want = served
    eng = _engine(mc, params, prefix_cache=True)
    assert [list(r) for r in eng.run(_requests(prompts))] == want
    before = dict(_counters(eng))
    clone = supervisor.clone_engine(eng)
    assert clone.metrics is eng.metrics
    assert [list(r) for r in clone.run(_requests(prompts))] == want
    after = _counters(clone)
    for name in ("moe.choices_total", "moe.choices_held",
                 "window.state_restores", "window.snapshots_written",
                 "attn.keys_visible"):
        assert after[name] == 2 * before[name], name
    assert clone.compile_cache_sizes() == {"sample": 1, "tick": 1, "chunk": 1,
                                           "set_row": 1}


def test_router_over_a_local_replica_serves_the_same_tokens(served):
    mc, params, prompts, want = served
    router = RouterServer([LocalReplica(
        _engine(mc, params, prefix_cache=True), "r0")])
    try:
        rids = [router.route(r) for r in _requests(prompts)]
        got = [router.result(rid, timeout=120) for rid in rids]
    finally:
        router.stop(drain_s=0.0)
    assert [r.status for r in got] == ["OK"] * 4
    assert [list(r) for r in got] == want


def test_cancel_mid_prefill_frees_every_block_and_the_slot_serves_on(served):
    mc, params, prompts, want = served
    eng = _engine(mc, params, prefix_cache=True)
    rid = eng.submit(Request(prompt=prompts[2], max_new_tokens=N_NEW))
    eng.step()
    assert eng.cancel(rid)
    while eng.pending():
        eng.step()
    assert eng.results[rid].status == "CANCELLED"
    # the block its one dispatched chunk filled stays indexed, the rest free
    assert eng.cached_block_count() == 1
    assert eng.free_block_count() == eng.pool.n_blocks - 2
    # the slot's stale ring is not the next row's: mapped at 0 it is zeros
    assert [list(r) for r in eng.run(_requests(prompts))] == want


def test_tensor_parallel_serving_is_refused_clearly(served):
    mc, params, _, _ = served
    with pytest.raises(NotImplementedError, match="tensor-parallel"):
        _engine(mc, params, tp_size=2)


def test_counters_equal_what_the_run_did(served):
    """One request, no cache: every prompt and served token (a tick feeds the
    token it emits) is a counted token of every expert layer; the blocks that
    filled hold snapshots."""
    mc, params, prompts, _ = served
    eng = _engine(mc, params)
    eng.run(_requests(prompts[2:3]))
    n = len(prompts[2]) + N_NEW
    c = _counters(eng)
    assert c["moe.choices_total"] == n * mc.top_k * N_MOE
    assert 0 < c["moe.choices_held"] < c["moe.choices_total"]
    assert c["window.snapshots_written"] == n // 8
    assert c["attn.keys_visible"] == sum(
        (p + 1) + 4 * min(p + 1, mc.window) for p in range(n))
    gauges = eng.metrics.snapshot()["gauges"]
    load = [gauges[f"moe.held_load.{e}"] for e in range(8)]
    assert sum(load) == c["moe.choices_held"]
    assert gauges["moe.load_max"] == max(load)
    assert 0 < gauges["moe.experts_touched"] <= N_MOE * 8
    # a table of 48 positions is one key tile: no walk can read less
    assert c["attn.blocks_visited"] == c["attn.blocks_in_table"] \
        > c["attn.blocks_live"] > 0
    assert c["moe.choices_in_place"] > 0


def test_a_rows_window_bytes_are_a_ring_and_a_snapshot_a_block(served):
    """What a live row holds for its sliding layers is its ring, fixed, and
    one snapshot a block its table maps: read off ``kv.window_bytes_live``
    with one row decoding at two lengths, and no key or value of a sliding
    layer is kept anywhere else (the pools are the full layer's)."""
    mc, params, _, _ = served
    ring = 2 * 4 * mc.window * 2 * 8 * 4        # k and v, 4 sliding layers
    read = {}
    for n_prompt in (5, 29):
        eng = _engine(mc, params)
        eng.submit(Request(prompt=tokens(n_prompt, seed=9),
                           max_new_tokens=N_NEW))
        while eng.pending():
            eng.step()
            g = eng.metrics.snapshot()["gauges"]
            if g["serve.decoding"] and g["kv.tokens_live"] > n_prompt:
                read[n_prompt] = (g["kv.window_bytes_live"],
                                  g["kv.full_bytes_live"],
                                  g["kv.tokens_live"])
    blocks = {n: -(-(n + N_NEW) // 8) for n in (5, 29)}      # reserved whole
    assert blocks == {5: 2, 29: 5}
    for n, (window_bytes, full_bytes, live) in read.items():
        assert window_bytes == ring + blocks[n] * ring
        assert full_bytes == blocks[n] * 8 * (2 * 1 * 2 * 8 * 4)
        assert n < live <= n + N_NEW
    eng_pool = wm.paged_pool_bytes(eng.pcache)
    assert eng.pcache.k.shape[0] == mc.n_of(wm.FULL) == 1
    assert eng_pool["snap"] == ring
    # six times the tokens, the same ring: 3 more snapshots is all it costs
    assert read[29][0] - read[5][0] == 3 * ring
