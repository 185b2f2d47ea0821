"""The tiny ``models/shortconv_moe.py`` that ``tests/test_shortconv_*.py``
share: a configuration in the configuration file's keys with the reference's
own seeded weights (benchmark/reference/lfm2.py, the one copy), the reference's
logits over a sequence, a cache of shuffled blocks, and a prefill by hand
through it.  No test lives here and pytest does not collect the file."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import lib  # noqa: E402
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
