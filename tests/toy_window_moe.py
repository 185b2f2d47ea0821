"""The tiny ``models/window_moe.py`` that ``tests/test_window_moe*.py`` share:
a configuration in the configuration file's keys with the reference's own
seeded weights (benchmark/reference/kexaone.py, the one copy), the reference's
logits over a sequence, a cache of shuffled blocks and rubbish rings, a prefill
by hand through it, and an engine.  No test lives here and pytest does not
collect the file."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import lib  # noqa: E402
from horovod_tpu import metrics as metrics_mod  # noqa: E402
from horovod_tpu.models import window_moe as wm  # noqa: E402
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
