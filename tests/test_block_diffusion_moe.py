"""``horovod_tpu.models.block_diffusion_moe`` on the CPU at a toy size, in
float32, against the plain reference ``benchmark/reference/sdar.py`` (seeded
random weights): the forward pass under the block-causal mask; prefill in
chunks and then block ticks through the pages, the logits of every denoise
step and the unmask order, at blocks of 4 and 8, 1, 2 and ``B`` denoise steps
and both remasking rules; the unmask rule on made-up logits; and the walk's
mask parameter, which leaves a causal model's programs the HLO they had."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import block_diffusion_moe as bd
from horovod_tpu.models import llama, paged

from toy_block_diffusion import (DYNAMIC, SOME, STATIC, model_config, ref,
                                 sampler, toy)

PAD = 64


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def test_paged_model_answers_for_the_config():
    mc = bd.block_diffusion_moe_tiny()
    assert paged.paged_model(mc) is bd
    assert bd.block_length(mc) == 4
    for entry in ("init_paged_cache", "decode_chunk_paged_row",
                  "decode_chunk_paged_rows", "paged_pool_bytes",
                  "paged_counters", "publish_paged_metrics",
                  "decode_block_paged", "unmask"):
        assert callable(getattr(bd, entry)), entry
    assert not hasattr(bd, "spec_verify_paged")
    with pytest.raises(NotImplementedError, match="diffusion over blocks"):
        bd.tp_split_dims(mc)


@pytest.mark.parametrize("bad", [
    dict(denoising_steps=0), dict(denoising_steps=5),
    dict(remasking="sequential"), dict(mask_token_id=64),
    dict(n_kv_heads=3)])
def test_the_config_refuses_what_the_sampler_cannot_run(bad):
    with pytest.raises(ValueError):
        bd.block_diffusion_moe_tiny(**bad)


@pytest.mark.parametrize("block", [4, 8])
def test_forward_under_the_block_causal_mask_equals_the_reference(block):
    cfg, w, params = toy(block)
    mc = model_config(cfg, sampler(1))
    ids = np.random.default_rng(block).integers(1, 60, (2, 24))
    got = bd.forward(params, jnp.asarray(ids), mc)
    for row, want in zip(got, (ref.forward(cfg, w, r) for r in ids)):
        np.testing.assert_allclose(row, want, atol=2e-5)
    # a token of block 1 moves every logit of blocks 1.. and none of block 0
    moved = np.abs(np.asarray(
        bd.forward(params, jnp.asarray(ids).at[0, block + 1].set(7), mc)
        - got))[0].max(-1)
    assert not moved[:block].any() and moved[block:].all()


def _paged_sample(params, mc, prompt, n_out, chunk=8):
    """The program's functions driven as the engine drives them: the prompt's
    whole blocks through chunks, then block ticks and the unmask program
    until ``n_out`` tokens are out.  Returns the blocks, the step that
    unmasked each position and the logits every denoise step saw."""
    b, mask = mc.block_length, mc.mask_token_id
    cache = bd.init_paged_cache(mc, 2, PAD, block_size=chunk)
    cache = cache._replace(block_table=cache.block_table.at[1].set(
        1 + jnp.arange(PAD // chunk, dtype=jnp.int32)))
    head = len(prompt) // b * b
    chunk_fn = jax.jit(lambda c, t, n: bd.decode_chunk_paged_rows(
        params, t, mc, c, jnp.asarray([1]), new_length=n,
        sel=jnp.asarray([0]))[1])
    for at in range(0, head, chunk):
        toks = list(prompt[at:min(at + chunk, head)])
        toks += [0] * (chunk - len(toks))
        cache = chunk_fn(cache, jnp.asarray([toks], jnp.int32),
                         jnp.asarray([min(at + chunk, head)]))
    tick = jax.jit(lambda c, t, commit: bd.decode_block_paged(
        params, t, mc, c, active=jnp.asarray([0, 1]), commit=commit))
    unmask = jax.jit(lambda lg, t, s: bd.unmask(mc, lg, t, s))
    tail = list(prompt[head:])
    blocks, steps, seen = [], [], []
    while len(blocks) * b < len(tail) + n_out:
        cur = (tail if not blocks else []) + [mask] * b
        cur = cur[:b]
        when = [-1 if t != mask else None for t in cur]
        logits_of, s = [], 0
        while mask in cur:
            toks = jnp.asarray([[0] * b, cur], jnp.int32)
            logits, cache = tick(cache, toks, jnp.asarray([0, 0]))
            logits_of.append(np.asarray(logits[1]))
            new, left, _ = unmask(logits, toks, jnp.asarray([0, s]))
            new = [int(t) for t in new[1]]
            assert int(left[1]) == new.count(mask)
            for i in range(b):
                if cur[i] == mask and new[i] != mask:
                    when[i] = s
            cur, s = new, s + 1
        # the commit: the clean block once more, its length advanced
        before = int(cache.length[1])
        _, cache = tick(cache, jnp.asarray([[0] * b, cur], jnp.int32),
                        jnp.asarray([0, 1]))
        assert int(cache.length[1]) == before + b and int(cache.length[0]) == 0
        blocks.append(cur)
        steps.append(when)
        seen.append(logits_of)
    return blocks, steps, seen


@pytest.mark.parametrize("block,steps,remasking", [
    (4, 1, STATIC), (4, 2, DYNAMIC), (4, 4, STATIC), (4, 4, DYNAMIC),
    (8, 1, DYNAMIC), (8, 2, STATIC), (8, 8, DYNAMIC), (8, 2, DYNAMIC)])
def test_chunks_then_block_ticks_equal_the_references_sampler(
        block, steps, remasking):
    """Logits of every denoise step and the unmask order; the prompt leaves
    a tail in its first block and ``n_out`` cuts the last."""
    cfg, w, params = toy(block)
    s = sampler(steps, remasking, SOME)
    mc = model_config(cfg, s)
    rng = np.random.default_rng([block, steps])
    by_threshold = by_schedule = 0
    for length, n_out in ((block * 2 + 3, block * 2 + 1), (block + 1, 5)):
        prompt = rng.integers(1, 60, length).tolist()
        want = ref.sample(cfg, w, s, prompt, n_out, pad_to=PAD)
        blocks, when, seen = _paged_sample(params, mc, prompt, n_out)
        assert blocks == want["blocks"] and when == want["steps"]
        assert len(prompt) % block and n_out % block
        for got_b, want_b in zip(seen, want["logits"]):
            assert len(got_b) == len(want_b)
            for got_s, want_s in zip(got_b, want_b):
                np.testing.assert_allclose(got_s, want_s, atol=3e-5)
        for blk_logits, blk_when in zip(want["logits"], when):
            for st, lg in enumerate(blk_logits):
                over = np.exp(ref.confidences(cfg, lg)[1]) > SOME
                took = np.asarray([x == st for x in blk_when])
                if remasking == DYNAMIC and (over & took).sum() == took.sum() \
                        and took.sum() >= ref.schedule(block, steps, st) \
                        and over[[x is None or x >= st
                                  for x in blk_when]].sum() == took.sum():
                    by_threshold += 1
                else:
                    by_schedule += 1
    if remasking == DYNAMIC and steps > 1:
        # the threshold decided some steps and the schedule others
        assert by_threshold and by_schedule, (by_threshold, by_schedule)


def _logits(conf, picks, vocab=16, mask_id=15):
    """Block logits whose best id at position i is ``picks[i]`` with softmax
    probability ``conf[i]``; the mask id's logit is the largest of all."""
    out = np.zeros((len(conf), vocab), np.float32)
    for i, (c, t) in enumerate(zip(conf, picks)):
        rest = (1.0 - c) / (vocab - 2)
        out[i] = np.log(rest)
        out[i, t] = np.log(c)
        out[i, mask_id] = 5.0
    return out


def test_the_unmask_rule_on_made_up_logits():
    m = 15
    mc = bd.block_diffusion_moe_tiny(vocab_size=16, mask_token_id=m,
                                     denoising_steps=2, remasking=DYNAMIC,
                                     confidence_threshold=0.5)
    conf = [[0.9, 0.6, 0.7, 0.3],       # two clear the threshold: both go
            [0.4, 0.2, 0.45, 0.3],      # none does: the schedule's two best
            [0.9, 0.2, 0.3, 0.45],      # one does, fewer than n_s: schedule
            [0.4, 0.41, 0.42, 0.3]]     # a given position is not ranked
    toks = np.asarray([[m, m, 3, m], [m, m, m, m], [m, 4, m, m],
                       [5, m, m, m]], np.int32)
    picks = [[1, 2, 9, 3]] * 4
    logits = jnp.asarray(np.stack([_logits(c, p) for c, p in
                                   zip(conf, picks)]))
    new, left, thr = bd.unmask(mc, logits, jnp.asarray(toks),
                               jnp.zeros((4,), jnp.int32))
    want = [[1, 2, 3, m], [1, m, 9, m], [1, 4, m, 3], [5, 2, 9, m]]
    assert new.tolist() == want
    assert left.tolist() == [1, 2, 1, 1] and thr.tolist() == [2, 0, 0, 0]
    # the static rule never asks the threshold; B=4 over S=3: 2, 1, 1
    mc3 = bd.block_diffusion_moe_tiny(vocab_size=16, mask_token_id=m,
                                      denoising_steps=3, remasking=STATIC)
    for step, want_left in ((0, 2), (1, 3), (2, 3)):
        new, left, thr = bd.unmask(mc3, logits[1:2], jnp.asarray(toks[1:2]),
                                   jnp.asarray([step]))
        assert int(left[0]) == want_left and int(thr[0]) == 0
    # the reference's own rule reads the same
    cfg = {"block_length": 4, "mask_token_id": m, "mlp_only_layers": [],
           "decoder_sparse_step": 1, "hidden_size": 1,
           "num_hidden_layers": 1, "num_attention_heads": 1,
           "num_key_value_heads": 1, "head_dim": 1, "rope_theta": 1,
           "num_experts": 1, "moe_intermediate_size": 1,
           "num_experts_per_tok": 1, "vocab_size": 16, "rms_norm_eps": 1}
    s = {"denoising_steps": 2, "remasking": DYNAMIC,
         "confidence_threshold": 0.5}
    for row in range(4):
        got, _, by_thr = ref.unmask_rule(cfg, s, logits[row],
                                         toks[row].tolist(), 0)
        assert got == want[row] and by_thr == (row == 0)


def _old_tile_walk(table, qpos, bs, active=None):
    """``llama.tile_walk`` as it stood before it took ``span``."""
    b, per = table.shape
    g = llama._tile_blocks(bs, per)
    n_tiles = -(-per // g)
    table = jnp.pad(table, ((0, 0), (0, n_tiles * g - per)))
    last = jnp.minimum(qpos[:, -1] // (g * bs) + 1, n_tiles)
    if active is not None:
        last = jnp.where(jnp.asarray(active) > 0, last, 1)
    groups, r = llama._row_groups(b, n_tiles, qpos.shape[1])
    if groups == 1:
        return llama.TileWalk(table=table[None], qpos=qpos[None], g=g,
                              n_live=jnp.max(last)[None], m=per * bs,
                              order=None, place=None)
    by_last = jnp.argsort(last)
    pad = groups * r - b
    order = jnp.concatenate([jnp.broadcast_to(by_last[:1], (pad,)), by_last])
    place = jnp.zeros((b,), jnp.int32).at[by_last].set(
        pad + jnp.arange(b, dtype=jnp.int32))
    return llama.TileWalk(
        table=table[order].reshape(groups, r, -1),
        qpos=qpos[order].reshape(groups, r, -1), g=g,
        n_live=last[order].reshape(groups, r)[:, -1], m=per * bs,
        order=order, place=place)


def test_a_causal_models_programs_lower_to_the_hlo_they_had(monkeypatch):
    """The mask parameter is a branch in Python: a program that does not give
    it is traced as before this parameter was in the tree."""
    mc = llama.llama_tiny(n_kv_heads=2)
    params = llama.serving_params(llama.init_params(mc, jax.random.key(0)),
                                  mc)
    cache = llama.init_paged_cache(mc, 12, 64, block_size=8)

    def texts():
        tick = jax.jit(lambda c, t, a: llama.decode_chunk_paged(
            params, t, mc, c, advance=a)).lower(
                cache, jnp.zeros((12, 1), jnp.int32),
                jnp.ones((12,), jnp.int32)).as_text()
        chunk = jax.jit(lambda c, t: llama.decode_chunk_paged_rows(
            params, t, mc, c, jnp.asarray([1, 12]),
            new_length=jnp.asarray([8, 0]), sel=jnp.asarray([7, 0]))).lower(
                cache, jnp.zeros((2, 8), jnp.int32)).as_text()
        return tick, chunk

    now = texts()
    monkeypatch.setattr(llama, "tile_walk", _old_tile_walk)
    assert texts() == now
    # and the parameter does change what is traced when it is given
    walk = lambda span: jax.jit(lambda q: llama.tile_walk(   # noqa: E731
        cache.block_table, q, 8, span=span).qpos).lower(
            jnp.zeros((12, 4), jnp.int32)).as_text()
    monkeypatch.undo()
    assert walk(1) != walk(4)
    qpos = jnp.asarray([[8, 9, 10, 11]])
    assert llama.tile_walk(cache.block_table[:1], qpos, 8,
                           span=4).qpos.tolist() == [[[11, 11, 11, 11]]]
    assert llama.tile_walk(cache.block_table[:1], qpos, 8,
                           span=1).qpos.tolist() == [[[8, 9, 10, 11]]]
