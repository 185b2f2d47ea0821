"""models/state_space_moe.py against the benchmark's plain reference
(benchmark/reference/granite.py): prefill in chunks of several widths followed
by decoding through the cache, by hand, and the state they leave whatever the
programs were."""

import numpy as np
import pytest

from toy_state_space_moe import (ATOL, N_LAYERS, _serve_by_hand,
                                 reference_logits, tiny, tokens)

from horovod_tpu.models import state_space_moe as sm


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
