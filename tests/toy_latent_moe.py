"""The tiny ``models/latent_moe.py`` that ``tests/test_latent_moe*.py`` share:
the preset in the configuration file's keys with the reference's own seeded
weights (benchmark/reference/dots3.py, the one copy), what the reference
computes and chooses over a sequence, a prefill by hand through the three
pools, an engine, and the spies that say which path and which form a program
took.  No test lives here and pytest does not collect the file."""

import functools
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
from horovod_tpu.models import latent_moe as lm  # noqa: E402
from horovod_tpu.serving_scheduler import ServeEngine  # noqa: E402

ref = lib.load_module("reference", "dots3")
fam = lib.load_module("families", "dots3_serve")
SEED = 5

#: The tiny preset in the configuration file's keys: all three kinds of layer,
#: 16 experts of which 8 are held, top-6 selection and a window of 5, both
#: smaller than the test lengths.
TINY = dict(
    name="tiny", reference="dots3", hidden_size=32, num_hidden_layers=5,
    layer_types=["full_attention", "full_attention", "sliding_attention",
                 "sliding_attention", "sliding_attention"],
    first_k_dense_replace=1, intermediate_size=64, num_attention_heads=4,
    q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, rope_theta=1e4, index_n_heads=2, index_head_dim=8,
    index_topk=6, swa_num_attention_heads=2, swa_q_lora_rank=16,
    swa_kv_lora_rank=16, swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4,
    swa_v_head_dim=8, swa_rope_theta=1e3, sliding_window_size=5,
    n_routed_experts=8, n_routed_experts_published=16, held_experts_first=0,
    moe_intermediate_size=16, num_experts_per_tok=4, n_shared_experts=1,
    routed_scaling_factor=1.0, vocab_size=64, vocab_first_row=0,
    rms_norm_eps=1e-5, apply_mla_qkv_lora_rescale=True,
    torch_dtype="float32")


def tiny(**changes):
    """``(configuration dict, LatentMoEConfig, parameters)``, the parameters
    the reference's own for the seed."""
    cfg = dict(TINY, **changes)
    return cfg, fam.model_config(cfg, 64), fam.make_params(cfg, SEED)


def tokens(n, vocab=64, seed=0):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def reference_logits(cfg, seq):
    n = len(seq)
    return np.asarray(ref.logits_at(cfg, SEED, [seq], [list(range(n))],
                                    "float32", pad_to=n, q_block=n,
                                    head_block=2)[0])


def reference_choices(cfg, seq):
    """Per layer what the reference's discrete parts chose over ``seq``."""
    top = ref.top_weights(cfg, ref.seed_arg(SEED))
    x = top["embed"][jnp.asarray(seq)].astype(jnp.float32)
    out = []
    for i in range(cfg["num_hidden_layers"]):
        w = ref.layer_weights(cfg, ref.seed_arg(SEED), i)
        x, aux = ref.layer(cfg, ref.layer_kind(cfg, i), x, w,
                           q_block=len(seq), head_block=2, aux=True)
        out.append(aux)
    return out


def _serve_by_hand(mc, params, seq, n_prompt, chunk, max_len=48):
    """Chunked prefill of ``seq[:n_prompt]`` into slot 1 of a two-slot cache,
    then the rest a token a tick: the logits at every position."""
    pc = lm.init_paged_cache(mc, 2, max_len, block_size=chunk)
    per = pc.block_table.shape[1]
    pc = pc._replace(block_table=pc.block_table.at[1].set(
        1 + jnp.arange(per, dtype=jnp.int32)))
    row = jax.jit(functools.partial(lm.decode_chunk_paged_row, cfg=mc))
    tick = jax.jit(functools.partial(lm.decode_chunk_paged, cfg=mc))
    logits = []
    for start in range(0, n_prompt, chunk):
        piece = seq[start:min(start + chunk, n_prompt)]
        toks = jnp.asarray([piece + [0] * (chunk - len(piece))], jnp.int32)
        out, pc = row(params, toks, pcache=pc, slot=1,
                      new_length=start + len(piece))
        logits.append(np.asarray(out[0, :len(piece)]))
    active = jnp.asarray([0, 1], jnp.int32)
    for tok in seq[n_prompt:]:
        out, pc = tick(params, jnp.asarray([[0], [tok]], jnp.int32),
                       pcache=pc, advance=active)
        logits.append(np.asarray(out[1]))
    return np.concatenate(logits), pc


#: ``MASK_REACH_TOPKS`` that sends every chunk down the mask path, none, and
#: the first two of three (a reach of 24 keys with top-6)
REACHES = {"mask": 12, "list": 0, "mask_then_list": 4}


def _forms_run(monkeypatch):
    """``(form, rows)`` of every expert layer :func:`lm.held_experts` lays
    out from here on, in order."""
    ran = []
    for name in ("_experts_in_place", "_experts_in_tiles"):
        def spy(*a, _name=name, _form=getattr(lm, name)):
            ran.append((_name, a[2].shape[0]))
            return _form(*a)
        monkeypatch.setattr(lm, name, spy)
    return ran


def _engine(mc, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("chunk", 8)
    return ServeEngine(params, mc, monitor=False, sampler=False,
                       metrics=metrics_mod.MetricsRegistry(event_log=None),
                       **kw)


def _dispatched(monkeypatch):
    """Every program an engine built from here on hands to the model's
    counters: ``(rows, tokens a row, longest row's length)``."""
    seen, publish = [], lm.publish_paged_metrics

    def spy(metrics, cfg, pcache, stats_host=None, row_blocks=(),
            programs=()):
        seen.extend(programs)
        return publish(metrics, cfg, pcache, stats_host, row_blocks, programs)

    monkeypatch.setattr(lm, "publish_paged_metrics", spy)
    return seen
