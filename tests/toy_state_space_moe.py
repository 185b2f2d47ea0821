"""The tiny ``models/state_space_moe.py`` that ``tests/test_state_space_*.py``
share: a configuration in the configuration file's keys with the reference's
own seeded weights (benchmark/reference/granite.py), the reference's logits
over a sequence, a cache of shuffled blocks and rubbish states, and a prefill
by hand through it.  No test lives here and pytest does not collect the
file."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import lib  # noqa: E402
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
